"""95th percentile, over every field handed over in the window, of the
time from its handover to the chain until its energies reached the host
(host clock). Only a closed loop with fields in flight times fields."""
import numpy as np


def read(run):
    if run.unit != "field" or not run.latencies_s:
        return None
    return float(np.percentile(run.latencies_s, 95)) * 1e3
