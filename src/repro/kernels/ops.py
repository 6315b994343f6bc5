"""Jit'd dispatch wrappers for the Pallas kernels.

Off TPU the kernels run in ``interpret=True`` mode — the kernel body
executes as traced jnp on the host, which validates the Pallas program
logic for the tests; on TPU the same calls compile to Mosaic or raise.
The FFT core's ``local_fft(backend="pallas")`` routes here, so the
distributed slab/pencil transforms can run their per-shard FFTs
through the kernels.

Block sizes come from a VMEM budget: every input and output block,
double-buffered, must fit the 16 MiB of scoped VMEM a TPU v5e kernel
gets by default. ``VMEM_BLOCK_BUDGET`` leaves the rest of it for the
kernel body's temporaries and the small constant inputs.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.fft.dft import split_factor
from repro.kernels.bandpass import bandpass_filter
from repro.kernels.fft_fourstep import fft_fourstep
from repro.kernels.fft_stockham import fft_stockham

VMEM_BLOCK_BUDGET = 12 << 20

# What Mosaic answers when the Stockham kernel is compiled for a TPU
# v5e (jax 0.9.0): its per-stage (bb, 2, m, l) views split the lane axis.
STOCKHAM_REFUSED = (
    "the Stockham kernel does not compile for TPU: Mosaic failed to "
    "compile TPU kernel: infer-vector-layout: unsupported shape cast "
    "(tpu.reshape vector<128x128xf32> -> vector<128x2x64x1xf32>, the "
    "first stage's (bb, 2, m, l) view); use kernel='fourstep'")


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _tile_bytes(rows: int, cols: int) -> int:
    """float32 bytes of a (rows, cols) VMEM tile padded to (8, 128)."""
    return -(-rows // 8) * 8 * (-(-cols // 128) * 128) * 4


def fft_block_b(B: int, N: int) -> int:
    """Largest divisor of ``B`` whose four (bb, n2, n1) blocks (re/im in
    and out), double-buffered, fit the VMEM block budget."""
    n1, n2 = split_factor(N)
    cap = max(1, VMEM_BLOCK_BUDGET // (8 * _tile_bytes(n2, n1)))
    return max(d for d in range(1, min(B, cap) + 1) if B % d == 0)


def bandpass_block_rows(R: int, C: int) -> int:
    """Rows per bandpass block: the whole (R, C) plane when its five
    blocks (re, im, mask in; re, im out), double-buffered, fit the VMEM
    block budget, else the largest multiple of 8 that fits and divides
    ``R`` rounded up to a multiple of 8 (``bandpass`` pads to that)."""
    cap = VMEM_BLOCK_BUDGET // (10 * _tile_bytes(8, C)) * 8
    if R <= cap:
        return R
    rows = R + -R % 8
    return max(br for br in range(8, cap + 1, 8) if rows % br == 0)


def fft(re, im, *, inverse: bool = False, kernel: str = "auto"):
    """Batched FFT along the last axis, (B, N) split planes. ``auto`` is
    the four-step kernel, the one that compiles for TPU; Stockham runs
    only in interpret mode, since Mosaic refuses its stage reshapes."""
    B, N = re.shape
    interpret = _interpret()
    if kernel == "stockham":
        if not interpret:
            raise NotImplementedError(STOCKHAM_REFUSED)
        bb = 128
        while B % bb:
            bb //= 2
        return fft_stockham(re, im, inverse=inverse, block_b=bb,
                            interpret=True)
    return fft_fourstep(re, im, inverse=inverse,
                        block_b=fft_block_b(B, N), interpret=interpret)


def bandpass(re, im, mask):
    """Fused mask multiply + kept/total energy over an (R, C) spectrum.
    Rows are zero-padded to a whole number of blocks (a no-op when the
    plane fits whole or ``R`` is a multiple of 8)."""
    R, C = re.shape
    br = bandpass_block_rows(R, C)
    pad = ((0, -R % br), (0, 0))
    re, im = jnp.pad(re, pad), jnp.pad(im, pad)
    mask = jnp.pad(jnp.asarray(mask), pad)
    r, i, kept, tot = bandpass_filter(re, im, mask, block_rows=br,
                                      interpret=_interpret())
    return r[:R], i[:R], kept, tot
