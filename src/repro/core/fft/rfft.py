"""Real-input (r2c/c2r) distributed transforms — FFTW's real plans.

The paper's data model is "real or complex-valued structured meshes"
(§2.2) and its demonstration field is real; a complex transform wastes
2× everywhere. These transforms keep only the non-negative half of the
spectrum along the *last* grid dim (Hermitian symmetry):

  * local rfft along the unsharded dim (half-spectrum, ~N/2+1 bins)
  * all_to_all on the half-width planes (≈2× less wire than c2c —
    collective bytes dominate distributed FFT cost at scale, so this
    is the single biggest lever)
  * full complex FFT along the remaining dim(s)

The real paths are ordinary *schedules* (see ``schedule.py``): the r2c
direction is ``LocalRFFT`` (real field → padded half-spectrum pair)
followed by the same exchange/FFT stages as the complex decomposition;
c2r mirrors it and ends in ``LocalIRFFT``. Because they run through
the one generic executor they inherit everything the complex schedules
have — batching, reduced-precision wire, and chunked overlap
pipelining (``plan_rfft(..., overlap_chunks=C)``).

Every complex decomposition in ``schedule.CAPS`` that transforms the
last grid dim locally has an r2c sibling here, mirroring
``schedule.py``'s builders:

  * ``rfft2_slab``/``irfft2_slab``       — 2-D slab, one mesh axis
  * ``rfft3_slab3d``/``irfft3_slab3d``   — 3-D slab, one mesh axis,
    one exchange; the half axis never travels, so it is UNPADDED
  * ``rfft3_pencil``/``irfft3_pencil``   — 3-D pencil, two mesh axes,
    two all_to_all rotations on half-width planes
  * ``rfft3_pencil_tf``/``irfft3_pencil_tf`` — transpose-free pencil:
    same cyclic-input / digit-permuted-x contract as the complex
    ``pencil_tf`` (see ``docs/layouts.md``), half-width planes in both
    exchanges
  * ``rfft2_pencil2d``/``irfft2_pencil2d`` — 2-axis decomposition of
    2-D grids; the gather of the (real!) last axis moves half the
    bytes of its complex sibling's, and the spectral scatters move
    half-width columns

The half-spectrum is zero-padded up to a multiple of the shard count
of every mesh axis that exchanges along it (``spectral_half_extent``
gives the per-decomposition extent) and sliced back on inversion.
``halfspec_freq_of_position`` / ``halfspec_position_of_freq`` are the
layout maps for the (possibly padded) half axis, shaped like the
four-step digit maps in ``distributed.py`` so consumers can treat
every permuted/truncated axis the same way.
"""
from __future__ import annotations

from typing import Tuple

import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.core.fft.dft import Pair
from repro.core.fft.schedule import (AllToAll, LocalFFT, LocalIRFFT,
                                     LocalRFFT, Reorder, Schedule, Twiddle,
                                     WireSpec, _wire_tuple,
                                     execute_schedule)


def half_bins(n1: int) -> int:
    return n1 // 2 + 1


def padded_half(n1: int, p: int) -> int:
    h = half_bins(n1)
    return h + (-h) % p


def spectral_half_extent(decomp: str, n_last: int, mesh: Mesh,
                         axis_names: Tuple[str, ...]) -> int:
    """Global extent of the half-spectrum axis a real plan's forward
    output carries for ``decomp`` — ``half_bins(n_last)`` padded to a
    multiple of the shard count of every mesh axis whose tiled
    all_to_all splits along it. ``slab3d`` never exchanges the half
    axis, so it is the one decomposition with NO padding."""
    if decomp == "slab":
        return padded_half(n_last, mesh.shape[axis_names[0]])
    if decomp == "slab3d":
        return half_bins(n_last)
    if decomp in ("pencil", "pencil_tf"):
        return padded_half(n_last, mesh.shape[axis_names[1]])
    if decomp == "pencil2d":
        return padded_half(n_last, mesh.shape[axis_names[0]]
                           * mesh.shape[axis_names[1]])
    raise ValueError(f"no r2c/c2r schedules for decomp {decomp!r}")


# ---------------------------------------------------------------------------
# Half-spectrum layout maps (pure numpy, like the four-step maps in
# ``distributed.py``)
# ---------------------------------------------------------------------------

def halfspec_freq_of_position(n: int, hp: int = None):
    """freq[g] = the DFT bin stored at position ``g`` of the padded
    half-spectrum axis of a length-``n`` real transform; ``-1`` marks
    the zero-padding positions (``g >= n//2+1``) that exist only to
    tile the all_to_all. The half-axis sibling of
    ``fourstep_freq_of_position``."""
    h = half_bins(n)
    hp = h if hp is None else hp
    out = np.full(hp, -1, dtype=int)
    out[:h] = np.arange(h)
    return out


def halfspec_position_of_freq(n: int, hp: int = None):
    """pos[k] = the half-spectrum position holding bin ``k``, defined
    for EVERY full-spectrum bin ``k`` in ``[0, n)``: bins above the
    Nyquist fold onto their Hermitian partner (``pos[k] = pos[n-k]``,
    whose stored value is the conjugate). The exact inverse of
    ``halfspec_freq_of_position`` on the unfolded bins — scatters a
    natural full-spectrum mask into the half layout."""
    del hp  # positions are independent of padding; kept for symmetry
    k = np.arange(n)
    return np.minimum(k, n - k)


# ---------------------------------------------------------------------------
# Schedule builders (registered with schedule.build_schedule via
# plan.py's ``real=True`` dispatch)
# ---------------------------------------------------------------------------

def rfft_slab_schedule(n1: int, mesh: Mesh, axis_name: str = "data", *,
                       inverse: bool = False, backend: str = "auto",
                       wire_dtype: WireSpec = None) -> Schedule:
    """2-D slab r2c/c2r as a schedule. ``n1`` is the full (real) extent
    of the last grid dim; forward maps real P(ax, None) → half-spectrum
    pair (..., N0, Hp) P(None, ax) with Hp = N1/2+1 padded to a
    multiple of the shard count."""
    pn = mesh.shape[axis_name]
    (w,) = _wire_tuple(wire_dtype, 1)
    hp = padded_half(n1, pn)
    if inverse:
        stages = (LocalFFT(-2, True, backend),
                  AllToAll(axis_name, -2, -1, pn, w),
                  LocalIRFFT(n1, half_bins(n1), backend))
        return Schedule("rfft_slab_inv", 2, stages,
                        (None, axis_name), (axis_name, None),
                        in_arity=2, out_arity=1)
    stages = (LocalRFFT(hp, backend),
              AllToAll(axis_name, -1, -2, pn, w),
              LocalFFT(-2, False, backend))
    return Schedule("rfft_slab", 2, stages,
                    (axis_name, None), (None, axis_name),
                    in_arity=1, out_arity=2)


def rfft_pencil_schedule(n2: int, mesh: Mesh,
                         axes: Tuple[str, str] = ("data", "model"), *,
                         inverse: bool = False, backend: str = "auto",
                         wire_dtype: WireSpec = None) -> Schedule:
    """3-D pencil r2c/c2r as a schedule: same two-rotation dataflow as
    the complex pencil but every all_to_all moves half-width planes."""
    a0, a1 = axes
    p0, p1 = mesh.shape[a0], mesh.shape[a1]
    wa, wb = _wire_tuple(wire_dtype, 2)
    hp = padded_half(n2, p1)
    if inverse:
        stages = (LocalFFT(-3, True, backend),
                  AllToAll(a0, -3, -2, p0, wa),
                  LocalFFT(-2, True, backend),
                  AllToAll(a1, -2, -1, p1, wb),
                  LocalIRFFT(n2, half_bins(n2), backend))
        return Schedule("rfft_pencil_inv", 3, stages,
                        (None, a0, a1), (a0, a1, None),
                        in_arity=2, out_arity=1)
    stages = (LocalRFFT(hp, backend),
              AllToAll(a1, -1, -2, p1, wa),
              LocalFFT(-2, False, backend),
              AllToAll(a0, -2, -3, p0, wb),
              LocalFFT(-3, False, backend))
    return Schedule("rfft_pencil", 3, stages,
                    (a0, a1, None), (None, a0, a1),
                    in_arity=1, out_arity=2)


def rfft_slab3d_schedule(n2: int, mesh: Mesh, axis_name: str = "data", *,
                         inverse: bool = False, backend: str = "auto",
                         wire_dtype: WireSpec = None) -> Schedule:
    """3-D slab r2c/c2r on ONE mesh axis: local rfft + y pass, one
    exchange on half-width planes, x pass. The single all_to_all splits
    the y axis, never the half axis, so the half-spectrum is UNPADDED
    (global extent exactly ``half_bins(n2)``).
    forward real P(ax, None, None) → half pair P(None, ax, None)."""
    pn = mesh.shape[axis_name]
    (w,) = _wire_tuple(wire_dtype, 1)
    h = half_bins(n2)
    if inverse:
        stages = (LocalFFT(-3, True, backend),
                  AllToAll(axis_name, -3, -2, pn, w),
                  LocalFFT(-2, True, backend),
                  LocalIRFFT(n2, h, backend))
        return Schedule("rfft_slab3d_inv", 3, stages,
                        (None, axis_name, None), (axis_name, None, None),
                        in_arity=2, out_arity=1)
    stages = (LocalRFFT(h, backend),
              LocalFFT(-2, False, backend),
              AllToAll(axis_name, -2, -3, pn, w),
              LocalFFT(-3, False, backend))
    return Schedule("rfft_slab3d", 3, stages,
                    (axis_name, None, None), (None, axis_name, None),
                    in_arity=1, out_arity=2)


def rfft_pencil_tf_schedule(n2: int, mesh: Mesh,
                            axes: Tuple[str, str] = ("data", "model"), *,
                            inverse: bool = False, backend: str = "auto",
                            wire_dtype: WireSpec = None) -> Schedule:
    """Transpose-free pencil r2c/c2r: the complex ``pencil_tf_3d``
    dataflow with a LocalRFFT/LocalIRFFT endcap, so both exchanges move
    half-width planes and the x-sharding still never moves.

    Same layout contract as the complex schedule (``docs/layouts.md``):
    forward input axis 0 must be CYCLIC over the first mesh axis
    (requires P0 | (n0/P0)); output position g' along axis 0 holds bin
    ``fourstep_freq_of_position(n0, P0)[g']`` and the last axis is the
    padded half-spectrum (``padded_half(n2, P1)`` — the z↔y rotation
    splits it)."""
    a0, a1 = axes
    p0, p1 = mesh.shape[a0], mesh.shape[a1]
    wa, wb = _wire_tuple(wire_dtype, 2)
    hp = padded_half(n2, p1)
    if inverse:
        stages = (Reorder("unfold_T", -3, p0),        # x: (M0)→(P0, M0/P0)
                  LocalFFT(-4, True, backend),        # length-P0 pass
                  AllToAll(a0, -4, -3, p0, wa),       # → (1, M0, ...)
                  Reorder("merge", -4),
                  Twiddle(-3, a0, p0, +1.0),
                  LocalFFT(-3, True, backend),        # x local
                  LocalFFT(-2, True, backend),        # y
                  AllToAll(a1, -2, -1, p1, wb),       # y ↔ z rotation
                  LocalIRFFT(n2, half_bins(n2), backend))
        return Schedule("rfft_pencil_tf_inv", 3, stages,
                        (a0, None, a1), (a0, a1, None),
                        in_arity=2, out_arity=1)
    stages = (LocalRFFT(hp, backend),                 # z (half-spectrum)
              AllToAll(a1, -1, -2, p1, wa),           # z ↔ y rotation
              LocalFFT(-2, False, backend),           # y
              LocalFFT(-3, False, backend),           # x local (cyclic)
              Twiddle(-3, a0, p0, -1.0),
              Reorder("expand", -4),
              AllToAll(a0, -3, -4, p0, wb),           # four-step exchange
              LocalFFT(-4, False, backend),           # length-P0 pass
              Reorder("fold_T", -4))                  # column-major flatten
    return Schedule("rfft_pencil_tf", 3, stages,
                    (a0, a1, None), (a0, None, a1),
                    in_arity=1, out_arity=2)


def rfft_pencil2d_schedule(n1: int, mesh: Mesh,
                           axes: Tuple[str, str] = ("data", "model"), *,
                           inverse: bool = False, backend: str = "auto",
                           wire_dtype: WireSpec = None) -> Schedule:
    """2-axis pencil2d r2c/c2r (see ``schedule.pencil_2d`` for the
    complex dataflow): the first gather moves the REAL field (half the
    bytes of the complex gather), the rfft endcap runs on the locally
    complete last axis, and the two spectral scatters move half-width
    columns. Half-spectrum padded to a multiple of P0·P1 (both scatters
    split along it). forward real P(a0, a1) → half pair
    P(None, (a1, a0))."""
    a0, a1 = axes
    p0, p1 = mesh.shape[a0], mesh.shape[a1]
    w0, w1, w2 = _wire_tuple(wire_dtype, 3)
    hp = padded_half(n1, p0 * p1)
    if inverse:
        stages = (LocalFFT(-2, True, backend),
                  AllToAll(a0, -2, -1, p0, w0),       # undo k0 scatter
                  AllToAll(a1, -2, -1, p1, w1),       # regroup half axis
                  LocalIRFFT(n1, half_bins(n1), backend),
                  AllToAll(a1, -1, -2, p1, w2))       # re-scatter real x
        return Schedule("rfft_pencil2d_inv", 2, stages,
                        (None, (a1, a0)), (a0, a1),
                        in_arity=2, out_arity=1)
    stages = (AllToAll(a1, -2, -1, p1, w0),           # gather REAL axis 1
              LocalRFFT(hp, backend),
              AllToAll(a1, -1, -2, p1, w1),           # scatter half axis
              AllToAll(a0, -1, -2, p0, w2),           # gather axis 0
              LocalFFT(-2, False, backend))
    return Schedule("rfft_pencil2d", 2, stages,
                    (a0, a1), (None, (a1, a0)),
                    in_arity=1, out_arity=2)


# r2c/c2r builder registry — ``schedule.build_schedule(real=True)``
# dispatches through this; keys must match ``CAPS`` entries with
# ``real=True``. Values: (builder, number of mesh axes it takes).
RFFT_BUILDERS = {
    "slab": (rfft_slab_schedule, 1),
    "slab3d": (rfft_slab3d_schedule, 1),
    "pencil": (rfft_pencil_schedule, 2),
    "pencil_tf": (rfft_pencil_tf_schedule, 2),
    "pencil2d": (rfft_pencil2d_schedule, 2),
}


# ---------------------------------------------------------------------------
# Functional API (thin executor wrappers, signatures stable)
# ---------------------------------------------------------------------------

def rfft2_slab(x, mesh: Mesh, axis_name: str = "data", *,
               backend: str = "auto", wire_dtype=None) -> Pair:
    """Real (..., N0, N1) P(..., ax, None) → half-spectrum
    Y[..., k0, k1≤N1/2] (re, im) of shape (..., N0, Hp) with
    P(..., None, ax); Hp = N1/2+1 padded to a multiple of the shard
    count. Leading dims are batch."""
    sched = rfft_slab_schedule(x.shape[-1], mesh, axis_name,
                               backend=backend, wire_dtype=wire_dtype)
    return execute_schedule(sched, mesh, x)


def irfft2_slab(re, im, n1: int, mesh: Mesh, axis_name: str = "data", *,
                backend: str = "auto", wire_dtype=None):
    """Inverse of ``rfft2_slab``: half-spectrum P(..., None, ax) → real
    (..., N0, N1) P(..., ax, None)."""
    sched = rfft_slab_schedule(n1, mesh, axis_name, inverse=True,
                               backend=backend, wire_dtype=wire_dtype)
    return execute_schedule(sched, mesh, re, im)


def rfft3_pencil(x, mesh: Mesh, axes: Tuple[str, str] = ("data", "model"),
                 *, backend: str = "auto", wire_dtype=None) -> Pair:
    """Real (..., n0, n1, n2) P(..., a0, a1, None) (z-pencils) →
    half-spectrum Y[..., k0, k1, k2≤N2/2] of global shape
    (..., N0, N1, Hp) with P(..., None, a0, a1) (x-pencils);
    Hp = N2/2+1 padded to a multiple of the a1 shard count."""
    sched = rfft_pencil_schedule(x.shape[-1], mesh, tuple(axes),
                                 backend=backend, wire_dtype=wire_dtype)
    return execute_schedule(sched, mesh, x)


def irfft3_pencil(re, im, n2: int, mesh: Mesh,
                  axes: Tuple[str, str] = ("data", "model"), *,
                  backend: str = "auto", wire_dtype=None):
    """Inverse of ``rfft3_pencil``: P(..., None, a0, a1) → real
    (..., N0, N1, N2) P(..., a0, a1, None)."""
    sched = rfft_pencil_schedule(n2, mesh, tuple(axes), inverse=True,
                                 backend=backend, wire_dtype=wire_dtype)
    return execute_schedule(sched, mesh, re, im)


def rfft3_slab3d(x, mesh: Mesh, axis_name: str = "data", *,
                 backend: str = "auto", wire_dtype=None) -> Pair:
    """Real (..., N0, N1, N2) P(..., ax, None, None) → half-spectrum
    (re, im) of shape (..., N0, N1, N2/2+1) with P(..., None, ax, None).
    One exchange; the half axis is unpadded (it never travels)."""
    sched = rfft_slab3d_schedule(x.shape[-1], mesh, axis_name,
                                 backend=backend, wire_dtype=wire_dtype)
    return execute_schedule(sched, mesh, x)


def irfft3_slab3d(re, im, n2: int, mesh: Mesh, axis_name: str = "data", *,
                  backend: str = "auto", wire_dtype=None):
    """Inverse of ``rfft3_slab3d``: half pair P(..., None, ax, None) →
    real (..., N0, N1, N2) P(..., ax, None, None)."""
    sched = rfft_slab3d_schedule(n2, mesh, axis_name, inverse=True,
                                 backend=backend, wire_dtype=wire_dtype)
    return execute_schedule(sched, mesh, re, im)


def rfft3_pencil_tf(x, mesh: Mesh,
                    axes: Tuple[str, str] = ("data", "model"), *,
                    backend: str = "auto", wire_dtype=None) -> Pair:
    """Transpose-free pencil r2c: real (..., n0, n1, n2)
    P(..., a0, a1, None) with **axis 0 cyclic over a0** → half-spectrum
    (..., N0, N1, Hp) P(..., a0, None, a1); axis 0 in four-step digit
    order (``fourstep_freq_of_position``), Hp = padded_half(n2, P1)."""
    sched = rfft_pencil_tf_schedule(x.shape[-1], mesh, tuple(axes),
                                    backend=backend, wire_dtype=wire_dtype)
    return execute_schedule(sched, mesh, x)


def irfft3_pencil_tf(re, im, n2: int, mesh: Mesh,
                     axes: Tuple[str, str] = ("data", "model"), *,
                     backend: str = "auto", wire_dtype=None):
    """Inverse of ``rfft3_pencil_tf`` (back to the cyclic spatial
    layout along axis 0)."""
    sched = rfft_pencil_tf_schedule(n2, mesh, tuple(axes), inverse=True,
                                    backend=backend, wire_dtype=wire_dtype)
    return execute_schedule(sched, mesh, re, im)


def rfft2_pencil2d(x, mesh: Mesh,
                   axes: Tuple[str, str] = ("data", "model"), *,
                   backend: str = "auto", wire_dtype=None) -> Pair:
    """2-axis r2c of a real (..., N0, N1) grid tiled P(..., a0, a1) →
    half-spectrum (..., N0, Hp) P(..., None, (a1, a0));
    Hp = padded_half(N1, P0·P1). Requires P0·P1 | N0 and P1 | N1."""
    sched = rfft_pencil2d_schedule(x.shape[-1], mesh, tuple(axes),
                                   backend=backend, wire_dtype=wire_dtype)
    return execute_schedule(sched, mesh, x)


def irfft2_pencil2d(re, im, n1: int, mesh: Mesh,
                    axes: Tuple[str, str] = ("data", "model"), *,
                    backend: str = "auto", wire_dtype=None):
    """Inverse of ``rfft2_pencil2d``: half pair P(..., None, (a1, a0))
    → real (..., N0, N1) P(..., a0, a1)."""
    sched = rfft_pencil2d_schedule(n1, mesh, tuple(axes), inverse=True,
                                   backend=backend, wire_dtype=wire_dtype)
    return execute_schedule(sched, mesh, re, im)


# ---------------------------------------------------------------------------
# Spectral-domain helpers
# ---------------------------------------------------------------------------

def half_mask(full_mask) -> jnp.ndarray:
    """Slice a full-spectrum mask to the half-spectrum (last dim)."""
    return full_mask[..., : half_bins(full_mask.shape[-1])]


def rfft_chain_2d(x, full_mask, mesh: Mesh, axis_name: str = "data"):
    """The paper's fwd → bandpass → inv chain on the half-spectrum."""
    from repro.core.fft.filters import halfspec_mask
    Pn = mesh.shape[axis_name]
    n1 = x.shape[-1]
    hp = padded_half(n1, Pn)
    hm = halfspec_mask(full_mask, hp).astype(jnp.float32)
    re, im = rfft2_slab(x, mesh, axis_name)
    re, im = re * hm, im * hm
    return irfft2_slab(re, im, n1, mesh, axis_name)
