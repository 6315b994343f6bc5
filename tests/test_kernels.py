"""Per-Pallas-kernel validation: shape/dtype sweeps vs the ref.py oracles
(interpret mode on CPU; the same programs compile via Mosaic on TPU)."""
import numpy as np
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref
from repro.kernels.fft_fourstep import fft_fourstep
from repro.kernels.fft_stockham import fft_stockham

RNG = np.random.default_rng(7)


def _pair(b, n):
    return (jnp.asarray(RNG.standard_normal((b, n)).astype(np.float32)),
            jnp.asarray(RNG.standard_normal((b, n)).astype(np.float32)))


@pytest.mark.parametrize("b", [1, 4, 64])
@pytest.mark.parametrize("n", [128, 256, 1024, 4096])
@pytest.mark.parametrize("kernel", ["fourstep", "stockham"])
def test_fft_kernels_shape_sweep(b, n, kernel):
    re, im = _pair(b, n)
    gr, gi = ops.fft(re, im, kernel=kernel)
    rr, ri = ref.fft_ref(re, im)
    scale = float(jnp.max(jnp.abs(rr))) + 1e-6
    assert float(jnp.max(jnp.abs(gr - rr))) / scale < 5e-5
    assert float(jnp.max(jnp.abs(gi - ri))) / scale < 5e-5


@pytest.mark.parametrize("kernel", ["fourstep", "stockham"])
def test_fft_kernel_inverse(kernel):
    re, im = _pair(8, 512)
    fr, fi = ops.fft(re, im, kernel=kernel)
    br, bi = ops.fft(fr, fi, inverse=True, kernel=kernel)
    np.testing.assert_allclose(np.asarray(br), np.asarray(re), atol=1e-4)
    np.testing.assert_allclose(np.asarray(bi), np.asarray(im), atol=1e-4)


def test_fft_fourstep_nonpow2():
    re, im = _pair(2, 360)
    gr, gi = fft_fourstep(re, im, block_b=2, interpret=True)
    rr, ri = ref.fft_ref(re, im)
    np.testing.assert_allclose(np.asarray(gr), np.asarray(rr), rtol=1e-3,
                               atol=2e-3)


def test_fft_block_sizes():
    re, im = _pair(64, 256)
    for bb in (8, 16, 64):
        gr, gi = fft_stockham(re, im, block_b=bb, interpret=True)
        rr, ri = ref.fft_ref(re, im)
        np.testing.assert_allclose(np.asarray(gr), np.asarray(rr),
                                   rtol=1e-4, atol=1e-3)


@given(b=st.sampled_from([1, 2, 8]), n=st.sampled_from([64, 256, 1024]),
       seed=st.integers(0, 2**31 - 1))
@settings(max_examples=12, deadline=None)
def test_fft_kernel_property_roundtrip(b, n, seed):
    rng = np.random.default_rng(seed)
    re = jnp.asarray(rng.standard_normal((b, n)).astype(np.float32))
    im = jnp.asarray(rng.standard_normal((b, n)).astype(np.float32))
    fr, fi = ops.fft(re, im)
    br, bi = ops.fft(fr, fi, inverse=True)
    assert float(jnp.max(jnp.abs(br - re))) < 1e-3
    assert float(jnp.max(jnp.abs(bi - im))) < 1e-3


@pytest.mark.parametrize("shape", [(64, 64), (256, 200), (128, 1000),
                                   (512, 4097), (100, 4097)])
def test_bandpass_kernel(shape):
    R, C = shape
    re = jnp.asarray(RNG.standard_normal((R, C)).astype(np.float32))
    im = jnp.asarray(RNG.standard_normal((R, C)).astype(np.float32))
    mask = jnp.asarray((RNG.random((R, C)) > 0.3).astype(np.float32))
    outr, outi, kept, tot = ops.bandpass(re, im, mask)
    rr, ri, rk, rt = ref.bandpass_ref(re, im, mask)
    np.testing.assert_allclose(np.asarray(outr), np.asarray(rr))
    np.testing.assert_allclose(np.asarray(outi), np.asarray(ri))
    np.testing.assert_allclose(float(kept), float(rk), rtol=1e-5)
    np.testing.assert_allclose(float(tot), float(rt), rtol=1e-5)


@pytest.mark.parametrize("kind,shape", [
    ("bandpass", (8192, 4097)), ("bandpass", (200, 200)),
    ("bandpass", (104, 4097)), ("fft", (1024, 4096)), ("fft", (1024, 256)),
    ("fft", (7, 360))])
def test_kernel_blocks_fit_vmem(kind, shape):
    """Every in/out block, double-buffered, fits the VMEM block budget,
    and a split row axis is cut in multiples of 8 (Mosaic's tiling)."""
    R, C = shape
    if kind == "bandpass":
        br = ops.bandpass_block_rows(R, C)
        assert R % br == 0 and (br == R or br % 8 == 0)
        used = 10 * ops._tile_bytes(br, C)
    else:
        from repro.core.fft.dft import split_factor
        n1, n2 = split_factor(C)
        bb = ops.fft_block_b(R, C)
        assert R % bb == 0
        used = 8 * bb * ops._tile_bytes(n2, n1)
    assert used <= ops.VMEM_BLOCK_BUDGET


def test_pallas_backend_in_fft_core():
    """local_fft(backend='pallas') routes through the kernels."""
    from repro.core.fft.dft import local_fft
    re, im = _pair(4, 256)
    gr, gi = local_fft(re, im, backend="pallas")
    rr, ri = ref.fft_ref(re, im)
    np.testing.assert_allclose(np.asarray(gr), np.asarray(rr), rtol=1e-4,
                               atol=1e-3)
