"""Host tail per field: the chain's host pipeline's own timings
(``device_get`` wait plus every host endpoint) over the fields it
completed in the window."""


def read(run):
    rep = run.pipeline
    if not rep or not rep.get("completed"):
        return None
    busy = rep["wait_s"] + sum(rep["host_timings_s"].values())
    return busy / rep["completed"] * 1e3
