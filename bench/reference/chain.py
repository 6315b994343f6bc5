"""The paper's analysis chain in float64: r2c FFT → low-pass box mask
→ c2r FFT, plus the kept and total spectral energies the bandpass
reports (unweighted sums of |X|² over the stored half-spectrum)."""
from __future__ import annotations

import os

import numpy as np
import scipy.fft as sfft

WORKERS = os.cpu_count() or 1


def freq_index(n: int) -> np.ndarray:
    """|k| per position in unshifted FFT order: 0, 1, …, n/2, …, 2, 1."""
    k = np.arange(n)
    return np.minimum(k, n - k)


def half_mask(shape, keep_frac: float) -> np.ndarray:
    """The low-pass box on the half-spectrum: along every axis keep
    |k| < max(1, round(n·keep_frac)); the last axis holds bins 0..n/2."""
    shape = tuple(int(n) for n in shape)
    out = np.ones(shape[:-1] + (shape[-1] // 2 + 1,), bool)
    for ax, n in enumerate(shape):
        cutoff = max(1, int(round(n * keep_frac)))
        k = freq_index(n)
        if ax == len(shape) - 1:
            k = k[: n // 2 + 1]
        view = [1] * len(shape)
        view[ax] = k.size
        out &= (k < cutoff).reshape(view)
    return out


def rfftn(x):
    return sfft.rfftn(np.asarray(x, np.float64), workers=WORKERS)


def irfftn(spec, shape):
    return sfft.irfftn(spec, s=tuple(shape), workers=WORKERS)


def chain(x, keep_frac: float, *, forward=rfftn, inverse=irfftn):
    """``(filtered field, kept energy, total energy)`` of real field
    ``x``. ``forward``/``inverse`` default to float64 ``scipy.fft``."""
    shape = tuple(np.shape(x))
    spec = forward(x)
    mask = half_mask(shape, keep_frac)
    power = spec.real ** 2 + spec.imag ** 2
    total = float(np.sum(power, dtype=np.float64))
    kept = float(np.sum(power * mask, dtype=np.float64))
    del power
    y = inverse(spec * mask, shape)
    return y, kept, total
