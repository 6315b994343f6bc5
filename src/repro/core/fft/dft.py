"""Local (single-shard) FFT backends on split re/im planes.

TPU Pallas has no complex dtype, and the MXU wants matmuls — so the
building blocks here carry (re, im) float pairs and expose two
TPU-native formulations:

* ``fourstep_fft`` — Bailey's four-step: a size-N FFT as N₁×N₁ and
  N₂×N₂ DFT-matrix matmuls around a twiddle multiply (N = N₁·N₂).
  This is the MXU-friendly form the Pallas kernel implements.
* ``stockham_fft`` — iterative radix-2 Stockham autosort (no bit
  reversal), the VMEM-resident alternative for small/odd batch shapes.

``local_fft`` dispatches between them (or jnp.fft for reference/CPU).
All functions operate along the LAST axis; callers move axes.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp


Pair = Tuple[jax.Array, jax.Array]


def to_pair(x) -> Pair:
    x = jnp.asarray(x)
    if jnp.iscomplexobj(x):
        return jnp.real(x).astype(jnp.float32), jnp.imag(x).astype(jnp.float32)
    return x.astype(jnp.float32), jnp.zeros_like(x, jnp.float32)


def to_complex(p: Pair):
    return p[0] + 1j * p[1]


# ---------------------------------------------------------------------------
# DFT matrices / twiddles
# ---------------------------------------------------------------------------

def dft_matrix(n: int, sign: float) -> Pair:
    # reduce j·k mod n in integers first: the float32 product j·k/n
    # reaches ~n radians and would lose ~n·2⁻²⁴ of angle per entry
    k = jnp.arange(n, dtype=jnp.int32)
    jk = (jnp.outer(k, k) % n).astype(jnp.float32)
    ang = sign * 2.0 * math.pi * jk / n
    return jnp.cos(ang), jnp.sin(ang)


def twiddle(n1: int, n2: int, sign: float) -> Pair:
    """exp(sign·2πi·j·k/(n1·n2)) for j<n1, k<n2."""
    j = jnp.arange(n1, dtype=jnp.float32)[:, None]
    k = jnp.arange(n2, dtype=jnp.float32)[None, :]
    ang = sign * 2.0 * math.pi * j * k / (n1 * n2)
    return jnp.cos(ang), jnp.sin(ang)


def cmul(ar, ai, br, bi) -> Pair:
    return ar * br - ai * bi, ar * bi + ai * br


def cmatmul(ar, ai, br, bi) -> Pair:
    """(...,m,k) complex @ (k,n) complex via four real matmuls, at
    ``Precision.HIGHEST``: a TPU's default precision is one bf16 pass,
    which misses float32 FFT accuracy."""
    dot = partial(jnp.matmul, precision=jax.lax.Precision.HIGHEST)
    return dot(ar, br) - dot(ai, bi), dot(ar, bi) + dot(ai, br)


# ---------------------------------------------------------------------------
# Four-step (Bailey) FFT — the MXU formulation
# ---------------------------------------------------------------------------

def split_factor(n: int) -> Tuple[int, int]:
    """n = n1·n2 with n1 ≤ n2, both as close to √n as possible."""
    n1 = 1 << (int(math.log2(n)) // 2) if n & (n - 1) == 0 else 1
    if n1 == 1:  # non power of two: greedy factor near sqrt
        f = int(math.sqrt(n))
        while n % f:
            f -= 1
        n1 = f
    return n1, n // n1


def fourstep_fft(re, im, *, inverse: bool = False) -> Pair:
    """FFT along the last axis via the four-step algorithm.

    view x as (n2, n1) [row-major  x[k] = X[k // n1, k % n1]]:
      1. FFT over the n2 axis (DFT matmul)
      2. twiddle multiply
      3. FFT over the n1 axis (DFT matmul)
      4. transpose (n2, n1) -> (n1, n2) and flatten
    """
    n = re.shape[-1]
    n1, n2 = split_factor(n)
    sign = 1.0 if inverse else -1.0
    batch = re.shape[:-1]

    xr = re.reshape(*batch, n2, n1)
    xi = im.reshape(*batch, n2, n1)

    # step 1: FFT over the n2 axis: move it last via swap
    xr = jnp.swapaxes(xr, -1, -2)                   # (..., n1, n2)
    xi = jnp.swapaxes(xi, -1, -2)
    w2r, w2i = dft_matrix(n2, sign)
    xr, xi = cmatmul(xr, xi, w2r, w2i)              # (..., n1, n2)

    # step 2: twiddle exp(sign·2πi·j·k / n), j over n1, k over n2
    tr, ti = twiddle(n1, n2, sign)
    xr, xi = cmul(xr, xi, tr, ti)

    # step 3: FFT over the n1 axis
    xr = jnp.swapaxes(xr, -1, -2)                   # (..., n2, n1)
    xi = jnp.swapaxes(xi, -1, -2)
    w1r, w1i = dft_matrix(n1, sign)
    xr, xi = cmatmul(xr, xi, w1r, w1i)

    # step 4: output index is k1·n2 + k2 -> transpose then flatten
    xr = jnp.swapaxes(xr, -1, -2)                   # (..., n1, n2)
    xi = jnp.swapaxes(xi, -1, -2)
    out_r = xr.reshape(*batch, n)
    out_i = xi.reshape(*batch, n)
    if inverse:
        out_r = out_r / n
        out_i = out_i / n
    return out_r, out_i


# ---------------------------------------------------------------------------
# Stockham radix-2 (autosort, ping-pong buffers)
# ---------------------------------------------------------------------------

def stockham_fft(re, im, *, inverse: bool = False) -> Pair:
    """Radix-2 Stockham FFT along the last axis (N a power of two)."""
    n = re.shape[-1]
    assert n & (n - 1) == 0, f"stockham needs power-of-two, got {n}"
    stages = int(math.log2(n))
    sign = 1.0 if inverse else -1.0

    xr, xi = re.astype(jnp.float32), im.astype(jnp.float32)
    half = n // 2
    for s in range(stages):
        l = 1 << s              # combined block size so far
        m = n >> (s + 1)        # butterflies per block pair
        # view (..., 2, m, l): columns already sorted by Stockham
        ar = xr.reshape(*xr.shape[:-1], 2, m, l)
        ai = xi.reshape(*xi.shape[:-1], 2, m, l)
        x0r, x1r = ar[..., 0, :, :], ar[..., 1, :, :]
        x0i, x1i = ai[..., 0, :, :], ai[..., 1, :, :]
        ang = sign * 2.0 * math.pi * (jnp.arange(l, dtype=jnp.float32)
                                      * (n // (2 * l))) / n
        wr, wi = jnp.cos(ang), jnp.sin(ang)          # (l,)
        t1r, t1i = cmul(x1r, x1i, wr, wi)
        yr = jnp.concatenate([x0r + t1r, x0r - t1r], axis=-1)  # (...,m,2l)
        yi = jnp.concatenate([x0i + t1i, x0i - t1i], axis=-1)
        xr = yr.reshape(*re.shape[:-1], n)
        xi = yi.reshape(*re.shape[:-1], n)
    if inverse:
        xr, xi = xr / n, xi / n
    return xr, xi


# ---------------------------------------------------------------------------
# Dispatch
# ---------------------------------------------------------------------------

def local_fft(re, im, *, inverse: bool = False, backend: str = "auto"
              ) -> Pair:
    """FFT along the last axis.
    backend: auto | fourstep | stockham | jnp | pallas."""
    n = re.shape[-1]
    if backend == "auto":
        backend = "fourstep" if n >= 64 else "stockham" \
            if n & (n - 1) == 0 else "fourstep"
    if backend == "pallas":
        from repro.kernels import ops as kops
        shape = re.shape
        r2 = re.reshape(-1, n)
        i2 = im.reshape(-1, n)
        rr, ii = kops.fft(r2, i2, inverse=inverse)
        return rr.reshape(shape), ii.reshape(shape)
    if backend == "jnp":
        fn = jnp.fft.ifft if inverse else jnp.fft.fft
        out = fn(to_complex((re, im)), axis=-1)
        return (jnp.real(out).astype(jnp.float32),
                jnp.imag(out).astype(jnp.float32))
    if backend == "stockham":
        return stockham_fft(re, im, inverse=inverse)
    if backend == "fourstep":
        return fourstep_fft(re, im, inverse=inverse)
    raise ValueError(backend)


def fft_along(re, im, axis: int, **kw) -> Pair:
    re = jnp.moveaxis(re, axis, -1)
    im = jnp.moveaxis(im, axis, -1)
    rr, ii = local_fft(re, im, **kw)
    return jnp.moveaxis(rr, -1, axis), jnp.moveaxis(ii, -1, axis)
