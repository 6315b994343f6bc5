"""The float64 references against plain ``np.fft`` at small sizes."""
import numpy as np
import pytest

from bench.reference import chain as ref
from bench.reference import ns2d


def test_half_mask_is_the_low_pass_box():
    shape = (8, 6, 10)
    mask = ref.half_mask(shape, 0.25)
    assert mask.shape == (8, 6, 6)
    for idx in np.ndindex(mask.shape):
        k = [min(i, n - i) for i, n in zip(idx, shape)]
        keep = all(kk < max(1, round(n * 0.25)) for kk, n in zip(k, shape))
        assert mask[idx] == keep


@pytest.mark.parametrize("shape", [(16, 16, 16), (8, 12, 10), (32, 32)])
def test_chain_matches_numpy(shape):
    x = np.random.default_rng(3).standard_normal(shape).astype(np.float32)
    y, kept, total = ref.chain(x, 0.25)
    spec = np.fft.rfftn(x.astype(np.float64))
    mask = ref.half_mask(shape, 0.25)
    want = np.fft.irfftn(spec * mask, s=shape, axes=range(len(shape)))
    np.testing.assert_allclose(y, want, rtol=0, atol=1e-12)
    p = np.abs(spec) ** 2
    assert kept == pytest.approx(float(np.sum(p * mask)), rel=1e-12)
    assert total == pytest.approx(float(np.sum(p)), rel=1e-12)


def test_ns2d_transforms_match_numpy():
    model = ns2d.NS2D((16, 24), nu=1e-3, dt=1e-2)
    x = np.random.default_rng(0).standard_normal((2, 16, 24))
    np.testing.assert_allclose(model.rfft2(x), np.fft.rfft2(x), atol=1e-12)
    s = np.fft.rfft2(x)
    np.testing.assert_allclose(model.irfft2(s, (16, 24)),
                               np.fft.irfft2(s, s=(16, 24)), atol=1e-12)


def test_ns2d_taylor_green_decays_in_closed_form():
    """ω = 2 sin x sin y: the nonlinear term vanishes, ω(t) = ω₀e^{−2νt}."""
    n, nu, dt = 32, 0.05, 0.01
    x = 2 * np.pi * np.arange(n) / n
    w0 = 2 * np.outer(np.sin(x), np.sin(x))
    model = ns2d.NS2D((n, n), nu=nu, dt=dt)
    s = model.initial(w0)
    assert np.abs(model.nonlinear(s)).max() < 1e-12
    for _ in range(5):
        s = model.step(s)
    got = np.fft.irfft2(s, s=(n, n))
    np.testing.assert_allclose(got, w0 * np.exp(-2 * nu * 5 * dt),
                               atol=1e-12)


def test_ns2d_nonlinear_term_against_finite_sum():
    """The advection −(u ωx + v ωy) of a two-mode field, written out."""
    n = 32
    x = 2 * np.pi * np.arange(n) / n
    X, Y = np.meshgrid(x, x, indexing="ij")
    w = np.cos(X) + np.sin(2 * Y)          # ψ = cos x + sin(2y)/4
    u = np.cos(2 * Y) / 2                  # ∂ψ/∂y
    v = np.sin(X)                          # −∂ψ/∂x
    want = -(u * -np.sin(X) + v * 2 * np.cos(2 * Y))
    model = ns2d.NS2D((n, n), nu=0.0, dt=0.0)
    got = np.fft.irfft2(model.nonlinear(np.fft.rfft2(w)), s=(n, n))
    np.testing.assert_allclose(got, want, atol=1e-12)


def test_initial_vorticity_is_seeded_and_band_limited():
    a = ns2d.initial_vorticity((32, 32), 2**31 + 5)
    b = ns2d.initial_vorticity((32, 32), 2**31 + 5)
    np.testing.assert_array_equal(a, b)
    assert np.abs(a).max() == pytest.approx(1.0)
    spec = np.abs(np.fft.rfft2(a))
    k0 = np.minimum(np.arange(32), 32 - np.arange(32))
    outside = (k0[:, None] > 4) | (np.arange(17)[None, :] > 4)
    assert spec[outside].max() < 1e-10
    assert abs(spec[0, 0]) < 1e-10
