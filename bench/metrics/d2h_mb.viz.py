"""Megabytes (10^6 bytes) the viz cell's host pipeline lands on the
host per completed field: its own ``d2h_bytes`` over the fields it
completed in the window."""


def read(run):
    rep = run.pipeline
    if not rep or not rep.get("completed") or "d2h_bytes" not in rep:
        return None
    return rep["d2h_bytes"] / rep["completed"] / 1e6
