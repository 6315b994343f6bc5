"""Device time per field of the viz cell: the union of the busiest
device's operation intervals in the traced window, over the fields
handed over in it."""
from bench.metrics._device import busy_per_unit_s


def read(run):
    t = busy_per_unit_s(run, "field")
    return None if t is None else t * 1e3
