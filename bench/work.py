"""Nominal work of the benchmark's device programs, from their shapes.

Each transform reads its input and writes its output once, and the
bandpass reads and writes the half-spectrum once; an r2c or c2r
transform of N points counts 2.5·N·log2(N) flops. The counts are lower
bounds: the elementwise algebra around the transforms is left out, so
a roofline share from them can only read low, never above 100%.
"""
from __future__ import annotations

import json
import math
from pathlib import Path

F32 = 4
C64 = 8

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip of ``device_kind``; a kind that
    is not in ``peaks.json`` is an error, never a default."""
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def _points(shape) -> int:
    return math.prod(int(n) for n in shape)


def half_shape(shape):
    """The r2c half-spectrum's shape: the last axis keeps N/2 + 1 bins."""
    return tuple(shape[:-1]) + (int(shape[-1]) // 2 + 1,)


def real_transform(shape) -> dict:
    """One r2c (or c2r) transform: the real field one way, the
    complex half-spectrum the other."""
    n = _points(shape)
    return {"bytes": n * F32 + _points(half_shape(shape)) * C64,
            "flops": 2.5 * n * math.log2(n)}


def bandpass(shape) -> dict:
    """Mask the half-spectrum: read it and write it once."""
    return {"bytes": 2 * _points(half_shape(shape)) * C64, "flops": 0.0}


def chain_field(shape) -> dict:
    """One field through r2c → bandpass → c2r."""
    parts = [real_transform(shape), bandpass(shape), real_transform(shape)]
    return {k: sum(p[k] for p in parts) for k in ("bytes", "flops")}


# IF-RK4 evaluates the nonlinear term four times; each evaluation runs
# one batched c2r of four fields (u, v, ∂xω, ∂yω) and one r2c
NS2D_TRANSFORMS_PER_STEP = 4 * (4 + 1)


def ns2d_step(shape) -> dict:
    """One IF-RK4 step of the 2-D vorticity solver: its transforms."""
    one = real_transform(shape)
    return {k: NS2D_TRANSFORMS_PER_STEP * one[k] for k in ("bytes", "flops")}


def roofline_s(work: dict, peak: dict, chips: int = 1):
    """Least seconds one chip needs for its share of ``work``, and which
    bound sets it (``"hbm"`` or ``"flops"``)."""
    t_bytes = work["bytes"] / chips / peak["hbm_bytes_per_s"]
    t_flops = work["flops"] / chips / peak["flops_per_s"]
    return (t_bytes, "hbm") if t_bytes >= t_flops else (t_flops, "flops")
