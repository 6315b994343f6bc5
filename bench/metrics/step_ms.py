"""The window over the solver steps completed in it (host clock; the
window closes on a host read after the last whole step)."""


def read(run):
    if run.unit != "step":
        return None
    return run.window_s / run.completed * 1e3
