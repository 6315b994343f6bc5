"""Pseudo-spectral solver suite (``core/solver``) — physics invariants
as the oracle for the whole distributed transform stack.

The analytic fixtures (Taylor–Green's closed-form viscous decay, the
Beltrami/ABC eigenfield's ``e^{-2νt}`` energy law, inviscid
energy/enstrophy conservation) validate every layer at once: if a
schedule mis-permutes a wavenumber, drops a Hermitian weight, or
mis-normalizes an inverse, the decay curve leaves the closed form
immediately. Cross-schedule equivalence then pins all decompositions
(slab / pencil2d / pencil / pencil_tf, r2c AND c2c) to the same
trajectory, and layout-aware dealiasing is property-tested against the
published index maps.

Device-mesh checks run in subprocesses with 8 forced host devices (the
repo's isolation rule, as ``tests/test_schedule.py``); mask properties
run in-process on numpy.
"""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

SRC = str(Path(__file__).resolve().parents[1] / "src")


def _run(script, *argv, timeout=900):
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, "-c", script, *argv], env=env,
                         capture_output=True, text=True, timeout=timeout)
    assert res.returncode == 0, res.stdout[-4000:] + res.stderr[-4000:]
    return json.loads(res.stdout.strip().splitlines()[-1])


# ---------------------------------------------------------------------------
# In-process: layout-aware dealiasing properties
# ---------------------------------------------------------------------------

def _twothirds(shape):
    from repro.core.fft.filters import twothirds_mask
    return np.asarray(twothirds_mask(shape), bool)


@given(shape=st.lists(st.sampled_from([4, 6, 8, 9, 12, 16]),
                      min_size=1, max_size=3))
@settings(max_examples=25, deadline=None)
def test_twothirds_mask_hermitian_symmetric(shape):
    """The 2/3-rule mask keeps k and −k together (index-negation
    invariance) — the condition for a masked spectrum of a real field
    to stay Hermitian, i.e. for dealiasing to commute with r2c."""
    m = _twothirds(shape)
    neg = m[np.ix_(*[(-np.arange(n)) % n for n in shape])]
    np.testing.assert_array_equal(m, neg)
    # box rule: the per-axis keep count is the number of |k|*3 < n bins
    kept = [int(np.sum(np.minimum(np.arange(n), n - np.arange(n)) * 3 < n))
            for n in shape]
    assert int(m.sum()) == int(np.prod(kept))


@given(n=st.sampled_from([8, 12, 16, 24]), p=st.sampled_from([1, 2, 4]),
       n0=st.sampled_from([4, 6, 8, 12]))
@settings(max_examples=25, deadline=None)
def test_mask_r2c_matches_halfspec_map(n, p, n0):
    """The half-spectrum mask is the full mask read through
    ``halfspec_freq_of_position`` — pad columns exactly zero."""
    from repro.core.fft.filters import mask_r2c, twothirds_mask
    from repro.core.fft.rfft import halfspec_freq_of_position, padded_half

    hp = padded_half(n, p)
    m = np.asarray(mask_r2c((n0, n), hp, build=twothirds_mask), bool)
    full = _twothirds((n0, n))
    fmap = halfspec_freq_of_position(n, hp)
    assert m.shape == (n0, hp)
    for g, f in enumerate(fmap):
        if f < 0:
            assert not m[:, g].any(), f"pad column {g} not zero"
        else:
            np.testing.assert_array_equal(m[:, g], full[:, f])


@given(combo=st.sampled_from([(8, 2), (16, 2), (16, 4), (12, 2),
                              (24, 2), (18, 3), (32, 4)]),
       n1=st.sampled_from([4, 6, 8]), n2=st.sampled_from([6, 8, 12]))
@settings(max_examples=25, deadline=None)
def test_mask_pencil_tf_is_permuted_full_mask(combo, n1, n2):
    """The transpose-free pencil mask is the natural mask with axis 0
    re-indexed by ``fourstep_freq_of_position`` — and the r2c variant
    composes that with the half-axis map (different axes, so the two
    permutations commute)."""
    from repro.core.fft.distributed import fourstep_freq_of_position
    from repro.core.fft.filters import (mask_pencil_tf_3d,
                                        mask_pencil_tf_3d_r2c,
                                        twothirds_mask)
    from repro.core.fft.rfft import halfspec_freq_of_position, padded_half

    n0, p0 = combo
    shape = (n0, n1, n2)
    full = _twothirds(shape)
    perm = fourstep_freq_of_position(n0, p0)
    m = np.asarray(mask_pencil_tf_3d(shape, p0, build=twothirds_mask), bool)
    np.testing.assert_array_equal(m, full[perm])

    hp = padded_half(n2, p0)
    mr = np.asarray(mask_pencil_tf_3d_r2c(shape, p0, hp,
                                          build=twothirds_mask), bool)
    fmap = halfspec_freq_of_position(n2, hp)
    want = np.zeros((n0, n1, hp), bool)
    keep = fmap >= 0
    want[:, :, keep] = full[perm][:, :, fmap[keep]]
    np.testing.assert_array_equal(mr, want)


def test_solver_basis_dealias_matches_layout():
    """`SpectralBasis` (no devices needed for the mask itself) must pick
    the layout-matched builder per decomp — spot-check pencil_tf r2c on
    a 1-process mesh where the permutation is identity-free to compute
    directly."""
    from repro.core.fft.distributed import fourstep_freq_of_position
    from repro.core.fft.rfft import halfspec_freq_of_position

    # pure index-map consistency (no mesh): the two maps are inverses
    # of the layouts the basis builds wavenumbers for
    n, p = 16, 2
    perm = fourstep_freq_of_position(n, p)
    assert sorted(perm) == list(range(n))
    fmap = halfspec_freq_of_position(n, n // 2 + 1)
    assert list(fmap) == list(range(n // 2 + 1))


# ---------------------------------------------------------------------------
# Subprocess (8 host devices): analytic oracles
# ---------------------------------------------------------------------------

_ORACLE = textwrap.dedent("""
    import os, json
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np
    from repro.compat import make_mesh
    from repro.core.solver import Boussinesq3DSolver, NS2DSolver

    mesh = make_mesh((4, 2), ("data", "model"))
    out = {}

    # Taylor-Green: omega = 2 sin x sin y has identically zero Jacobian,
    # so E(t) = E0 * exp(-4 nu t) exactly -- both steppers must track it
    nu, dt, steps = 0.1, 0.01, 25
    for stepper in ("if_rk4", "rk4"):
        s = NS2DSolver((32, 32), mesh, nu=nu, dt=dt, decomp="slab",
                       axis_names=("data",), stepper=stepper)
        s.init_taylor_green()
        e0 = s.energy()
        s.step(steps)
        want = e0 * float(np.exp(-4.0 * nu * steps * dt))
        out["tg_" + stepper] = abs(s.energy() - want) / want

    # inviscid: RK4 on a random smooth field conserves energy AND
    # enstrophy to time-integration accuracy (the 2-D invariant pair)
    s = NS2DSolver((32, 32), mesh, nu=0.0, dt=2e-3, decomp="slab",
                   axis_names=("data",), stepper="rk4")
    s.init_random(seed=3)
    e0, z0 = s.energy(), s.enstrophy()
    s.step(20)
    out["inviscid_e"] = abs(s.energy() - e0) / e0
    out["inviscid_z"] = abs(s.enstrophy() - z0) / z0

    # the shell-summed spectrum is an exact partition of the energy
    _, ek = s.spectrum(12)
    out["spec_sum"] = abs(float(np.sum(np.asarray(ek))) - s.energy()) \\
        / s.energy()

    # Beltrami/ABC: curl eigenfield, u x omega = 0, E = E0 exp(-2 nu t)
    nu3, dt3, steps3 = 0.05, 0.01, 15
    b = Boussinesq3DSolver((16, 16, 16), mesh, nu=nu3, dt=dt3,
                           decomp="slab3d", axis_names=("data",))
    b.init_beltrami()
    e0 = b.energy()
    b.step(steps3)
    want = e0 * float(np.exp(-2.0 * nu3 * steps3 * dt3))
    out["beltrami"] = abs(b.energy() - want) / want

    # buoyancy coupling: gravity converts scalar variance into kinetic
    # energy from an exact rest state
    g = Boussinesq3DSolver((16, 16, 16), mesh, gravity=1.0, dt=0.01,
                           decomp="slab3d", axis_names=("data",))
    g.init_random(seed=1, amplitude=0.0, b_amplitude=1.0)
    assert g.energy() == 0.0
    g.step(5)
    out["buoyancy_ke"] = g.energy()
    print(json.dumps(out))
""")


def test_analytic_oracles():
    got = _run(_ORACLE)
    assert got["tg_if_rk4"] < 1e-5, got
    assert got["tg_rk4"] < 1e-5, got
    assert got["inviscid_e"] < 1e-5, got
    assert got["inviscid_z"] < 1e-5, got
    assert got["spec_sum"] < 1e-5, got
    assert got["beltrami"] < 1e-5, got
    assert got["buoyancy_ke"] > 0.0, got


# ---------------------------------------------------------------------------
# Subprocess: device Parseval sums against float64 numpy
# ---------------------------------------------------------------------------

_PARSEVAL = textwrap.dedent("""
    import os, json
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np
    from repro.compat import make_mesh
    from repro.core.solver import Boussinesq3DSolver, NS2DSolver

    mesh = make_mesh((4, 2), ("data", "model"))
    rng = np.random.default_rng(11)
    out = {}

    def randomize(s, pair):
        # amplitudes spread over 6 decades, mode by mode
        b = s.basis
        shape = b.spectral_shape
        amp = 10.0 ** rng.uniform(-3.0, 3.0, shape)
        return tuple(b.place_spectral(amp * rng.standard_normal(shape))
                     for _ in pair)

    def host_sum(s, pair, extra=None):
        b = s.basis
        re, im = (np.asarray(b.gather_spectral(x), np.float64)
                  for x in pair)
        p = (re * re + im * im) * np.asarray(b.weights, np.float64)
        if extra is not None:
            p = p * np.asarray(extra, np.float64)
        return float(np.sum(p)) * 0.5 / (b.norm * b.norm)

    def relerr(got, want):
        return abs(got - want) / abs(want)

    for tag, kw in (
            ("r2c_slab", dict(decomp="slab", axis_names=("data",))),
            ("c2c_slab", dict(decomp="slab", axis_names=("data",),
                              real=False)),
            ("r2c_pencil2d", dict(decomp="pencil2d"))):
        s = NS2DSolver((32, 64), mesh, **kw)
        s.state = randomize(s, (0, 1))
        out["ns2d_energy_" + tag] = relerr(
            s.energy(), host_sum(s, s.state, s.basis.inv_k2))
        out["ns2d_enstrophy_" + tag] = relerr(
            s.enstrophy(), host_sum(s, s.state))

    for tag, kw in (
            ("r2c_slab3d", dict(decomp="slab3d", axis_names=("data",))),
            ("r2c_pencil_tf", dict(decomp="pencil_tf"))):
        s = Boussinesq3DSolver((16, 16, 16), mesh, **kw)
        s.state = {k: randomize(s, (0, 1))
                   for k in ("u0", "u1", "u2", "b")}
        out["bq3d_energy_" + tag] = relerr(
            s.energy(), sum(host_sum(s, s.state[k])
                            for k in ("u0", "u1", "u2")))
        out["bq3d_scalar_variance_" + tag] = relerr(
            s.scalar_variance(), host_sum(s, s.state["b"]))
    print(json.dumps(out))
""")

PARSEVAL_CASES = [
    f"ns2d_{q}_{tag}" for tag in ("r2c_slab", "c2c_slab", "r2c_pencil2d")
    for q in ("energy", "enstrophy")] + [
    f"bq3d_{q}_{tag}" for tag in ("r2c_slab3d", "r2c_pencil_tf")
    for q in ("energy", "scalar_variance")]


@pytest.fixture(scope="module")
def parseval_errors():
    return _run(_PARSEVAL)


@pytest.mark.parametrize("case", PARSEVAL_CASES)
def test_device_parseval_sum_matches_float64(parseval_errors, case):
    """The diagnostics sum each row on the device in float32 and finish
    in float64 on the host: within 1e-6 of a float64 numpy sum of the
    gathered state, for amplitudes over 6 decades (so ``inv_k2``'s
    weighting spans its range) on every layout."""
    assert parseval_errors[case] <= 1e-6, parseval_errors


# ---------------------------------------------------------------------------
# Subprocess: cross-schedule equivalence (the basis contract)
# ---------------------------------------------------------------------------

_SCHEDULES = textwrap.dedent("""
    import os, json
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np
    from repro.compat import make_mesh
    from repro.core.solver import Boussinesq3DSolver, NS2DSolver

    mesh = make_mesh((4, 2), ("data", "model"))
    out = {}

    def relerr(a, b):
        return float(np.max(np.abs(a - b)) / np.max(np.abs(a)))

    # 2-D: same physics through 1-axis slab, 2-axis pencil2d, r2c + c2c
    kw = dict(nu=5e-3, dt=5e-3)
    ref = NS2DSolver((64, 64), mesh, decomp="slab", axis_names=("data",),
                     **kw)
    ref.init_random(seed=3)
    ref.step(5)
    w_ref = ref.vorticity()
    for tag, extra in (
            ("pencil2d_r2c", dict(decomp="pencil2d")),
            ("slab_c2c", dict(decomp="slab", axis_names=("data",),
                              real=False)),
            ("pencil2d_c2c", dict(decomp="pencil2d", real=False))):
        s = NS2DSolver((64, 64), mesh, **kw, **extra)
        s.init_random(seed=3)
        s.step(5)
        out["ns2d_" + tag] = relerr(w_ref, s.vorticity())

    # 3-D: slab3d / pencil / pencil_tf (digit-permuted axis 0), r2c+c2c
    kw3 = dict(nu=0.02, kappa=0.02, gravity=1.0, dt=5e-3)
    ref3 = Boussinesq3DSolver((16, 16, 16), mesh, decomp="slab3d",
                              axis_names=("data",), **kw3)
    ref3.init_random(seed=5)
    ref3.step(3)
    u_ref, b_ref = ref3.field("u0"), ref3.field("b")
    for tag, extra in (
            ("pencil_r2c", dict(decomp="pencil")),
            ("pencil_tf_r2c", dict(decomp="pencil_tf")),
            ("slab3d_c2c", dict(decomp="slab3d", axis_names=("data",),
                                real=False)),
            ("pencil_tf_c2c", dict(decomp="pencil_tf", real=False))):
        s = Boussinesq3DSolver((16, 16, 16), mesh, **kw3, **extra)
        s.init_random(seed=5)
        s.step(3)
        out["bq3d_u_" + tag] = relerr(u_ref, s.field("u0"))
        out["bq3d_b_" + tag] = relerr(b_ref, s.field("b"))
    print(json.dumps(out))
""")


def test_cross_schedule_equivalence():
    """Every decomposition — including the digit-permuted pencil_tf
    layout and the half-spectrum r2c paths — must integrate the SAME
    trajectory: the basis' layout-aware wavenumbers/masks make the
    schedule invisible to the physics."""
    got = _run(_SCHEDULES)
    for name, err in got.items():
        assert err < 1e-4, f"{name} diverged from reference: {got}"


# ---------------------------------------------------------------------------
# Subprocess: restart round-trip (bit-identical continuation)
# ---------------------------------------------------------------------------

_RESTART = textwrap.dedent("""
    import os, json, sys, tempfile
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax
    import numpy as np
    from repro.compat import make_mesh
    from repro.core.solver import Boussinesq3DSolver, NS2DSolver

    mesh = make_mesh((4, 2), ("data", "model"))
    out = {}

    def gathered(s):
        return jax.tree_util.tree_map(s.basis.gather_spectral, s.state)

    def bit_identical(a, b):
        la = jax.tree_util.tree_leaves(a)
        lb = jax.tree_util.tree_leaves(b)
        return all(np.array_equal(x, y) for x, y in zip(la, lb))

    # NS2D: 8 uninterrupted steps vs 4 + save + restore-into-fresh + 4
    kw = dict(nu=5e-3, dt=5e-3, decomp="slab", axis_names=("data",))
    a = NS2DSolver((32, 32), mesh, **kw)
    a.init_random(seed=7)
    a.step(8)
    b = NS2DSolver((32, 32), mesh, **kw)
    b.init_random(seed=7)
    b.step(4)
    with tempfile.TemporaryDirectory() as td:
        b.save(td)
        c = NS2DSolver((32, 32), mesh, **kw)
        c.init_taylor_green()          # deliberately different state
        out["restored_step"] = c.restore(td)
        c.step(4)
    out["ns2d_identical"] = bit_identical(gathered(a), gathered(c))
    out["ns2d_t"] = abs(c.t - a.t) < 1e-12
    out["ns2d_steps"] = c.step_count == a.step_count == 8

    # Boussinesq: the 4-field dict tree through the same ckpt path
    kw3 = dict(nu=0.02, kappa=0.02, gravity=1.0, dt=5e-3,
               decomp="slab3d", axis_names=("data",))
    a3 = Boussinesq3DSolver((16, 16, 16), mesh, **kw3)
    a3.init_random(seed=9)
    a3.step(4)
    b3 = Boussinesq3DSolver((16, 16, 16), mesh, **kw3)
    b3.init_random(seed=9)
    b3.step(2)
    with tempfile.TemporaryDirectory() as td:
        b3.save(td)
        c3 = Boussinesq3DSolver((16, 16, 16), mesh, **kw3)
        c3.init_random(seed=0)
        c3.restore(td)
        c3.step(2)
    out["bq3d_identical"] = bit_identical(gathered(a3), gathered(c3))
    print(json.dumps(out))
""")


def test_restart_roundtrip_bit_identical():
    """A save → fresh-solver restore → continue run must reproduce the
    uninterrupted trajectory BIT-identically (same plans, same state
    bytes — the continuation indistinguishable from never stopping)."""
    got = _run(_RESTART)
    assert got["restored_step"] == 4, got
    assert got["ns2d_identical"], got
    assert got["ns2d_t"] and got["ns2d_steps"], got
    assert got["bq3d_identical"], got


# ---------------------------------------------------------------------------
# Subprocess pair: warm-wisdom solver bring-up plans with zero sweeps
# ---------------------------------------------------------------------------

_WISDOM = textwrap.dedent("""
    import os, json, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    from repro.compat import make_mesh
    from repro.core.fft.plan import plan_cache_stats, set_wisdom
    from repro.core.solver import NS2DSolver

    set_wisdom(sys.argv[1], "readwrite")
    mesh = make_mesh((4, 2), ("data", "model"))
    s = NS2DSolver((32, 32), mesh, decomp="slab", axis_names=("data",),
                   backend="measure")
    s.init_taylor_green()
    s.step(1)                     # touches fwd, bwd AND batched plans
    st = plan_cache_stats()
    print(json.dumps({"timed": st["sweep_candidates_timed"],
                      "wisdom_hits": st["wisdom_hits"]}))
""")


def test_solver_warm_wisdom_zero_sweeps(tmp_path):
    """A measured solver bring-up against a warm wisdom file must plan
    its whole plan set (both directions + the batched RHS plans) with
    ZERO timed sweep candidates — the restart-economics contract of
    docs/wisdom.md applied to the full solver."""
    wfile = str(tmp_path / "wisdom.json")
    cold = _run(_WISDOM, wfile)
    assert cold["timed"] > 0, cold
    warm = _run(_WISDOM, wfile)
    assert warm["wisdom_hits"] > 0, warm
    assert warm["timed"] == 0, warm
