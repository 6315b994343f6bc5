"""The two measures every correctness number is built from."""
from __future__ import annotations

import numpy as np


def rel_l2(got, want) -> float:
    """‖got − want‖₂ / ‖want‖₂ in float64 (complex128 for spectra)."""
    complex_ = np.iscomplexobj(got) or np.iscomplexobj(want)
    dtype = np.complex128 if complex_ else np.float64
    got = np.asarray(got, dtype)
    want = np.asarray(want, dtype)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def rel_scalar(got: float, want: float) -> float:
    return abs(float(got) - float(want)) / abs(float(want))
