"""Share of the roofline per step: the least time the nominal bytes
and flops of the driver's ``work`` need at the chip's peaks
(``bench/peaks.json``; HBM bandwidth bounds it at these sizes), over
the measured device time per step, on the busiest chip."""
from bench.metrics._device import roofline_pct


def read(run):
    return roofline_pct(run, "step")
