"""Host time per completed field of the viz cell's copy of the device
results to the host: the host pipeline's own ``d2h_s`` (its
``device_get``s, without the wait for the device program) over the
fields it completed in the window."""


def read(run):
    rep = run.pipeline
    if not rep or not rep.get("completed") or "d2h_s" not in rep:
        return None
    return rep["d2h_s"] / rep["completed"] * 1e3
