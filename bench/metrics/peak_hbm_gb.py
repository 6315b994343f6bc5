"""Peak device memory of the fullest chip after the window
(``peak_bytes_in_use`` from the device's allocator), in GB."""


def read(run):
    if run.peak_bytes is None:
        return None
    return run.peak_bytes / 1e9
