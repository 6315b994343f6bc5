"""Host time per completed field of the viz cell's image endpoint: the
host pipeline's own ``host_timings_s["visualize"]`` over the fields it
completed in the window."""


def read(run):
    rep = run.pipeline
    if not rep or not rep.get("completed"):
        return None
    secs = rep["host_timings_s"].get("visualize")
    return None if secs is None else secs / rep["completed"] * 1e3
