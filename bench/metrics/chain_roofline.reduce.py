"""Share of the roofline per field of the reduce cell: the least time
the nominal bytes and flops of the driver's ``work`` need at the
chip's peaks (``bench/peaks.json``; HBM bandwidth bounds it at these
sizes), over the measured device time per field, on the busiest
chip."""
from bench.metrics._device import roofline_pct


def read(run):
    return roofline_pct(run, "field")
