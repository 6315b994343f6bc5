"""Dense DFTs in bf16×3 arithmetic: the precision control.

A TPU matmul at ``Precision.HIGH`` splits each float32 operand into a
high and a low bfloat16 part and keeps three of the four products
(hi·hi + hi·lo + lo·hi) in float32 accumulators. These transforms do
that explicitly, so the control reads the same on the chip and on a
CPU. Every transform is one dense matmul per axis, with the
twiddles computed in float64 and rounded to float32.
"""
from __future__ import annotations

from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.chain import half_mask

BF16, F32 = jnp.bfloat16, jnp.float32


def _split(a):
    """``a`` ≈ hi + lo in bfloat16, hi rounded to nearest even. hi is
    rounded on the bits, not by a round trip through bfloat16, which
    XLA may fold away (it did on the TPU, leaving lo = 0)."""
    bits = jax.lax.bitcast_convert_type(a, jnp.uint32)
    odd = (bits >> 16) & jnp.uint32(1)
    bits = (bits + jnp.uint32(0x7FFF) + odd) & jnp.uint32(0xFFFF0000)
    hi = jax.lax.bitcast_convert_type(bits, F32)
    return hi.astype(BF16), (a - hi).astype(BF16)


def _dot3(a, b):
    """``a @ b`` over float32 operands in bf16×3."""
    dot = lambda x, y: jnp.matmul(x, y, preferred_element_type=F32)
    ah, al = _split(a)
    bh, bl = _split(b)
    return dot(ah, bh) + (dot(ah, bl) + dot(al, bh))


@lru_cache(maxsize=None)
def _twiddles(n: int, m: int, sign: float, scale: float):
    """cos and sign·sin of 2π·j·k/n for j < n, k < m, times ``scale``."""
    jk = np.outer(np.arange(n), np.arange(m)) % n
    ang = 2.0 * np.pi * jk / n
    return (np.asarray(np.cos(ang) * scale, np.float32),
            np.asarray(sign * np.sin(ang) * scale, np.float32))


def _along(x, axis, f):
    """Apply ``f`` to the 2-D (rows, n) view of ``x`` with ``axis`` last."""
    x = jnp.moveaxis(x, axis, -1)
    lead = x.shape[:-1]
    y = f(x.reshape(-1, x.shape[-1]))
    return jnp.moveaxis(y.reshape(lead + y.shape[-1:]), -1, axis)


def c2c(re, im, axis, inverse):
    """Complex DFT of (re, im) along ``axis`` (inverse scaled by 1/n)."""
    n = re.shape[axis]
    c, s = map(jnp.asarray, _twiddles(n, n, 1.0 if inverse else -1.0,
                                      1.0 / n if inverse else 1.0))
    # (re + i·im)(c + i·s) = (re·c − im·s) + i(re·s + im·c)
    r = _along(re, axis, lambda a: _dot3(a, c)) \
        - _along(im, axis, lambda a: _dot3(a, s))
    i = _along(re, axis, lambda a: _dot3(a, s)) \
        + _along(im, axis, lambda a: _dot3(a, c))
    return r, i


def _r2c_last(x):
    n = x.shape[-1]
    c, s = map(jnp.asarray, _twiddles(n, n // 2 + 1, -1.0, 1.0))
    f = lambda a: _dot3(a.reshape(-1, n), c).reshape(a.shape[:-1] + (-1,))
    g = lambda a: _dot3(a.reshape(-1, n), s).reshape(a.shape[:-1] + (-1,))
    return f(x), g(x)


def _c2r_last(re, im, n):
    h = n // 2 + 1
    w = np.full(h, 2.0)
    w[0] = 1.0
    if n % 2 == 0:
        w[-1] = 1.0
    c, s = _twiddles(n, h, 1.0, 1.0 / n)
    # y_j = Σ_k w_k (Re X_k cos − Im X_k sin)(2π jk/n) / n
    ct = jnp.asarray(np.ascontiguousarray((c * w).T))
    st = jnp.asarray(np.ascontiguousarray((s * w).T))
    rows = lambda a: a.reshape(-1, h)
    y = _dot3(rows(re), ct) - _dot3(rows(im), st)
    return y.reshape(re.shape[:-1] + (n,))


def rfftn(x, naxes=None):
    """r2c over the last ``naxes`` axes (all by default) → complex64."""
    x = jnp.asarray(x, F32)
    naxes = x.ndim if naxes is None else naxes
    re, im = _r2c_last(x)
    for ax in range(x.ndim - 2, x.ndim - naxes - 1, -1):
        re, im = c2c(re, im, ax, inverse=False)
    return jax.lax.complex(re, im)


def irfftn(spec, shape):
    """c2r over the last ``len(shape)`` axes → float32 of ``shape``."""
    spec = jnp.asarray(spec)
    re, im = jnp.real(spec).astype(F32), jnp.imag(spec).astype(F32)
    nd = spec.ndim
    for ax in range(nd - len(shape), nd - 1):
        re, im = c2c(re, im, ax, inverse=True)
    return _c2r_last(re, im, int(shape[-1]))


rfftn_jit = jax.jit(rfftn, static_argnums=1)
irfftn_jit = jax.jit(irfftn, static_argnums=1)


def chain(x, keep_frac, device, rows=64):
    """The chain (r2c, bandpass, c2r) on a 3-D field in bf16×3, its
    energies summed in bfloat16, on one ``device``, in blocks of
    ``rows`` planes or columns so that a field of any size fits: r2c
    along the last axis and the DFT along axis 1 per block of axis-0
    planes, then per block of axis-1 columns the DFT along axis 0, the
    mask, the energies and the inverse, then the rest of the inverse per
    plane block. Returns (field, kept energy, total energy) on the
    host."""
    n0, n1, n2 = shape = x.shape
    mask = half_mask(shape, keep_frac)
    put = lambda a: jax.device_put(a, device)
    planes = jax.jit(lambda b: rfftn(b, 2))

    @jax.jit
    def columns(spec, m):
        re, im = c2c(jnp.real(spec), jnp.imag(spec), 0, False)
        power = (re * re + im * im).astype(BF16)
        kept = jnp.sum(power * m.astype(BF16), dtype=BF16)
        total = jnp.sum(power, dtype=BF16)
        re, im = c2c(re * m, im * m, 0, True)
        return jax.lax.complex(re, im), kept, total

    inverse = jax.jit(lambda s: irfftn(s, (n1, n2)))
    spec = np.empty((n0, n1, n2 // 2 + 1), np.complex64)
    for a in range(0, n0, rows):
        spec[a:a + rows] = np.asarray(planes(put(x[a:a + rows])))
    kept = total = 0.0
    for j in range(0, n1, rows):
        s, k, t = columns(put(spec[:, j:j + rows]),
                          put(mask[:, j:j + rows].astype(np.float32)))
        spec[:, j:j + rows] = np.asarray(s)
        kept, total = kept + float(k), total + float(t)
    y = np.empty(shape, np.float32)
    for a in range(0, n0, rows):
        y[a:a + rows] = np.asarray(inverse(put(spec[a:a + rows])))
    return y, kept, total
