"""Pallas TPU kernel: batched radix-2 Stockham FFT.

VMEM-resident alternative to the four-step kernel for power-of-two sizes
where the DFT-matmul formulation wastes MXU cycles (small N) or the
factorization is degenerate. The autosort structure needs no bit-reversal
pass — each stage is a regular strided butterfly expressible as reshapes
+ elementwise ops on the VMEM block, with the log₂N stage loop unrolled
at trace time (N is static).

Grid: one program per batch block; VMEM per block ≈ 2·block_b·N·4 bytes.
Twiddles arrive as an input (Mosaic cannot build them from a float iota
in the kernel), but Mosaic also refuses the stage reshapes, which split
the lane axis; so this kernel runs only in interpret mode, and
``ops.fft`` refuses it on TPU (``tests/test_tpu_compile.py`` checks
that the refusal is still needed).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl


def _twiddles(n: int, sign: float):
    """Every stage's twiddles in one (2, n) array: stage s (l = 2^s)
    reads row 0/1 (cos/sin) at columns [l, 2l)."""
    col = np.arange(n)
    l = 1 << np.floor(np.log2(np.maximum(col, 1))).astype(np.int64)
    ang = sign * 2.0 * np.pi * (col - l) * (n // (2 * l)) / n
    return jnp.asarray(np.stack([np.cos(ang), np.sin(ang)]), jnp.float32)


def _kernel(xr_ref, xi_ref, tw_ref, or_ref, oi_ref, *, n: int,
            inverse: bool):
    xr = xr_ref[...]
    xi = xi_ref[...]
    tw = tw_ref[...]
    bb = xr.shape[0]
    stages = int(math.log2(n))

    for s in range(stages):
        l = 1 << s
        m = n >> (s + 1)
        ar = xr.reshape(bb, 2, m, l)
        ai = xi.reshape(bb, 2, m, l)
        x0r, x1r = ar[:, 0], ar[:, 1]
        x0i, x1i = ai[:, 0], ai[:, 1]
        wr, wi = tw[0, l:2 * l], tw[1, l:2 * l]
        t1r = x1r * wr - x1i * wi
        t1i = x1r * wi + x1i * wr
        xr = jnp.concatenate([x0r + t1r, x0r - t1r], axis=-1) \
                .reshape(bb, n)
        xi = jnp.concatenate([x0i + t1i, x0i - t1i], axis=-1) \
                .reshape(bb, n)
    if inverse:
        xr = xr / n
        xi = xi / n
    or_ref[...] = xr
    oi_ref[...] = xi


@functools.partial(jax.jit, static_argnames=("inverse", "block_b",
                                             "interpret"))
def fft_stockham(re, im, *, inverse: bool = False, block_b: int = 128,
                 interpret: bool = False):
    """Batched radix-2 FFT along the last axis. re/im: (B, N) float32,
    N a power of two."""
    B, N = re.shape
    assert N & (N - 1) == 0, N
    bb = min(block_b, B)
    assert B % bb == 0, (B, bb)
    out_shape = (jax.ShapeDtypeStruct((B, N), jnp.float32),
                 jax.ShapeDtypeStruct((B, N), jnp.float32))
    return pl.pallas_call(
        functools.partial(_kernel, n=N, inverse=inverse),
        grid=(B // bb,),
        in_specs=[pl.BlockSpec((bb, N), lambda i: (i, 0)),
                  pl.BlockSpec((bb, N), lambda i: (i, 0)),
                  pl.BlockSpec((2, N), lambda i: (0, 0))],
        out_specs=[pl.BlockSpec((bb, N), lambda i: (i, 0)),
                   pl.BlockSpec((bb, N), lambda i: (i, 0))],
        out_shape=out_shape,
        interpret=interpret,
    )(re, im, _twiddles(N, 1.0 if inverse else -1.0))
