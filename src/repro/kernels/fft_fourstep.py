"""Pallas TPU kernel: batched four-step (Bailey) FFT.

The MXU-native FFT: a size-N transform (N = n1·n2) becomes two DFT-matrix
matmuls (n2×n2 and n1×n1) around an elementwise twiddle — exactly the
shape of work the systolic array wants, with the whole working set
resident in VMEM per batch block. Complex values travel as split re/im
planes (TPU Pallas has no complex dtype); each complex matmul is four
real MXU matmuls at ``Precision.HIGHEST`` (the default single bf16 pass
misses float32 FFT accuracy).

Mosaic lowers no in-kernel reshape that splits the lane axis and no
3-D transpose, so the wrapper does both layout moves in XLA: it views
each row as an (n2, n1) matrix ``X[j2, j1] = x[j2·n1 + j1]`` before the
call, and the kernel computes, per batch row, the two 2-D matmuls

    Y = W2 @ X            (DFT over j2 → k2)
    Z = (Y ∘ T) @ W1      (twiddle T[k2, j1], DFT over j1 → k1)

leaving ``Z[k2, k1]``; the wrapper's transpose puts bin k1·n2 + k2 in
natural order. Grid: one program per block of ``block_b`` batch rows;
``ops.fft_block_b`` sizes the block from the VMEM budget.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.fft.dft import dft_matrix, split_factor, twiddle

_HI = jax.lax.Precision.HIGHEST


def _cmatmul(ar, ai, br, bi):
    dot = functools.partial(jnp.dot, precision=_HI,
                            preferred_element_type=jnp.float32)
    return dot(ar, br) - dot(ai, bi), dot(ar, bi) + dot(ai, br)


def _kernel(xr_ref, xi_ref, w2r_ref, w2i_ref, twr_ref, twi_ref,
            w1r_ref, w1i_ref, or_ref, oi_ref, *, scale: float):
    w2r, w2i = w2r_ref[...], w2i_ref[...]          # (n2, n2)
    twr, twi = twr_ref[...], twi_ref[...]          # (n2, n1)
    w1r, w1i = w1r_ref[...], w1i_ref[...]          # (n1, n1)

    def row(r, carry):
        yr, yi = _cmatmul(w2r, w2i, xr_ref[r], xi_ref[r])
        yr, yi = yr * twr - yi * twi, yr * twi + yi * twr
        zr, zi = _cmatmul(yr, yi, w1r, w1i)
        if scale != 1.0:
            zr, zi = zr * scale, zi * scale
        or_ref[r] = zr
        oi_ref[r] = zi
        return carry

    jax.lax.fori_loop(0, xr_ref.shape[0], row, 0)


@functools.partial(jax.jit, static_argnames=("inverse", "block_b",
                                             "interpret"))
def fft_fourstep(re, im, *, inverse: bool = False, block_b: int = 128,
                 interpret: bool = False):
    """Batched FFT along the last axis. re/im: (B, N) float32."""
    B, N = re.shape
    n1, n2 = split_factor(N)
    sign = 1.0 if inverse else -1.0
    bb = min(block_b, B)
    assert B % bb == 0, (B, bb)

    w2 = dft_matrix(n2, sign)
    w1 = dft_matrix(n1, sign)
    tw = twiddle(n2, n1, sign)

    const_spec = lambda shape: pl.BlockSpec(shape, lambda i: (0, 0))
    row_spec = pl.BlockSpec((bb, n2, n1), lambda i: (i, 0, 0))
    out_shape = (jax.ShapeDtypeStruct((B, n2, n1), jnp.float32),) * 2

    zr, zi = pl.pallas_call(
        functools.partial(_kernel, scale=1.0 / N if inverse else 1.0),
        grid=(B // bb,),
        in_specs=[
            row_spec, row_spec,
            const_spec((n2, n2)), const_spec((n2, n2)),
            const_spec((n2, n1)), const_spec((n2, n1)),
            const_spec((n1, n1)), const_spec((n1, n1)),
        ],
        out_specs=[row_spec, row_spec],
        out_shape=out_shape,
        interpret=interpret,
    )(re.reshape(B, n2, n1), im.reshape(B, n2, n1),
      w2[0], w2[1], tw[0], tw[1], w1[0], w1[1])
    return (zr.swapaxes(1, 2).reshape(B, N),
            zi.swapaxes(1, 2).reshape(B, N))
