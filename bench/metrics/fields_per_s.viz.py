"""Fields of the viz cell whose whole chain (host tail included) finished
inside the window, over the window's length (host clock). The window
runs from the first handover until the last field handed over in
``--seconds`` has finished."""


def read(run):
    if run.unit != "field":
        return None
    return run.completed / run.window_s
