"""Shared arithmetic of the trace-read metrics (not a metric itself)."""
from bench import work


def busy_per_unit_s(run, unit):
    """Busiest device's busy seconds per field or step handed over in
    the traced window, or None."""
    if run.unit != unit or run.trace is None or not run.attempted:
        return None
    return max(run.trace["busy_s"].values()) / run.attempted


def idle_pct(run, unit):
    if run.unit != unit or run.trace is None:
        return None
    busy = run.trace["busy_s"]
    return 100.0 * (1.0 - sum(busy.values()) / len(busy)
                    / run.trace["window_s"])


def roofline_pct(run, unit):
    """Least time for the nominal work (the driver's ``work``) over the
    measured busy time per unit, on the busiest chip."""
    t = busy_per_unit_s(run, unit)
    if t is None or run.peak is None or t <= 0:
        return None
    least, _bound = work.roofline_s(run.work, run.peak, run.chips)
    return 100.0 * least / t
