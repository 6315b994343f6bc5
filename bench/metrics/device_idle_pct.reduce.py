"""Share of the reduce cell's traced window in which no operation ran
on the device, averaged over the chips (1 − busy / window)."""
from bench.metrics._device import idle_pct


def read(run):
    return idle_pct(run, "field")
