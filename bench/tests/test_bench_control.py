"""The correctness check fails where it must: the precision control,
and the timed path broken underneath a whole run (tiny grids, CPU).

On a CPU the program's float32 energy sums read above the chip's
limit, so a fault is judged by the number it breaks, against the same
run without it.
"""
import jax.numpy as jnp
import pytest

from bench import control, run

SEED = 2**31 + 23
CHAIN, SOLVER = "chain3d-512.reduce", "ns2d-8192.step"
CUBE, SQUARE = (16, 16, 16), (32, 32)


def _over(checks):
    return {k for k, c in checks.items() if c["value"] > c["limit"]}


@pytest.mark.parametrize("cell,shape", [(CHAIN, CUBE), (SOLVER, SQUARE)])
def test_precision_control_is_not_correct(cell, shape):
    assert _over(control.control_checks(cell, SEED, shape=shape))


def _run(cell, shape):
    return run.run_cell(cell, SEED, 0.2, False, require_tpu=False,
                        shape=shape)


def test_conjugated_spectrum_fails_the_field(monkeypatch):
    """An answer altered where it is produced: the forward plan's
    spectrum conjugated, which the energies cannot see."""
    from repro.core.insitu.endpoints.fft_endpoint import FFTEndpoint
    sound = _run(CHAIN, CUBE)["checks"]
    execute = FFTEndpoint.execute

    def conjugated(self, data):
        out = execute(self, data)
        if out.domain == "spectral":
            re, im = out.arrays[self.array]
            out.arrays[self.array] = (re, -im)
        return out
    monkeypatch.setattr(FFTEndpoint, "execute", conjugated)
    broken = _run(CHAIN, CUBE)
    assert not broken["correct"]
    assert "field_err" not in _over(sound) and "field_err" in _over(
        broken["checks"])
    assert broken["checks"]["energy_err"]["value"] < 1e-4


def test_altered_energy_fails_the_energies(monkeypatch):
    """An answer altered where it is produced: the bandpass' kept
    energy off by one part in ten thousand."""
    from repro.core.insitu.endpoints.bandpass import BandpassEndpoint
    sound = _run(CHAIN, CUBE)["checks"]["energy_err"]["value"]
    execute = BandpassEndpoint.execute

    def altered(self, data):
        out = execute(self, data)
        out.arrays["insitu_kept_energy"] = \
            out.arrays["insitu_kept_energy"] * jnp.float32(1 + 1e-4)
        return out
    monkeypatch.setattr(BandpassEndpoint, "execute", altered)
    broken = _run(CHAIN, CUBE)
    assert not broken["correct"] and broken["failed"] == broken["attempted"]
    assert broken["checks"]["energy_err"]["value"] > max(
        10 * sound, broken["checks"]["energy_err"]["limit"])


@pytest.mark.parametrize("fault", ["unchanged", "no_nonlinear_term"])
def test_broken_step_fails_the_state(monkeypatch, fault):
    """A step that returns its state unchanged, and one that drops the
    nonlinear term (which Taylor–Green's decay cannot see)."""
    from repro.core.solver.base import SpectralSolverBase
    from repro.core.solver.ns2d import NS2DSolver
    assert "state_err" not in _over(_run(SOLVER, SQUARE)["checks"])
    if fault == "unchanged":
        def step(self, n=1):
            self.step_count += n
            self.t = self.step_count * self.dt
        monkeypatch.setattr(SpectralSolverBase, "step", step)
    else:
        monkeypatch.setattr(NS2DSolver, "_nonlinear", lambda self, s: (
            jnp.zeros_like(s[0]), jnp.zeros_like(s[1])))
    broken = _run(SOLVER, SQUARE)
    assert not broken["correct"] and "state_err" in _over(broken["checks"])
