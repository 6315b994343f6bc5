"""The program's own names in a profiler trace (docs/architecture.md,
"Tracing"): device scopes in the lowered programs' op locations, and
host spans with their ``step`` argument in a trace taken on the CPU."""
import glob
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.compat import make_mesh
from repro.core.insitu.bridge import BridgeData, GridMeta
from repro.core.insitu.config import build_chain
from repro.core.solver.ns2d import NS2DSolver

SHAPE = (8, 8, 8)
CHAIN = [{"endpoint": "fft", "array": "field", "direction": "forward",
          "real": True},
         {"endpoint": "bandpass", "array": "field", "keep_frac": 0.25},
         {"endpoint": "fft", "array": "field", "direction": "backward",
          "real": True}]
SCHEDULE_STAGES = re.compile(r"\d+\.(LocalRFFT|LocalIRFFT|LocalFFT|AllToAll"
                             r"|Twiddle|Reorder)")


@pytest.fixture(scope="module")
def mesh():
    return make_mesh((1,), ("data",), devices=jax.devices()[:1])


def _locations(lowered) -> str:
    return "\n".join(re.findall(r'loc\("([^"]*)"',
                                lowered.as_text(debug_info=True)))


def _solver(mesh, shape=(16, 16)):
    s = NS2DSolver(shape, mesh, nu=1e-3, dt=5e-3)
    s.init_random(seed=1)
    return s


@pytest.mark.parametrize("mode", ["pipelined", "intransit"])
def test_chain_program_carries_endpoint_plan_and_stage_scopes(mesh, mode):
    grid = GridMeta(dims=SHAPE)
    chain = build_chain({"mode": mode, "chain": CHAIN}, mesh=mesh, grid=grid)
    data = BridgeData(arrays={"field": jnp.ones(SHAPE, jnp.float32)},
                      grid=grid, step=0)
    if mode == "pipelined":
        text = _locations(chain._device_fn(False).lower(data))
        for scope in ("insitu.0.fft", "insitu.1.bandpass", "insitu.2.fft"):
            assert scope in text
    else:
        text = _locations(chain._staged_fn(0, chain.endpoints[0])
                          .lower(data))
        assert "insitu.0.fft" in text
    # a plan's scopes sit inside its own jitted transform
    fwd = chain.endpoints[0].plan
    plan_text = _locations(jax.jit(fwd.execute).lower(
        jnp.ones(SHAPE, jnp.float32)))
    assert "plan.r2c" in plan_text
    assert SCHEDULE_STAGES.search(plan_text)


def test_solver_step_carries_step_nonlinear_and_plan_scopes(mesh):
    s = _solver(mesh)
    text = _locations(s._step_fn.lower(s.state))
    for scope in ("solver.step", "solver.nonlinear"):
        assert scope in text
    assert "plan.r2c" in text and "plan.c2r" in text


@pytest.mark.parametrize("real,direction,scope", [
    (True, "forward", "plan.r2c"), (True, "backward", "plan.c2r"),
    (False, "forward", "plan.fwd"), (False, "backward", "plan.bwd")])
def test_plan_scope_names_its_kind(mesh, real, direction, scope):
    from repro.core.fft.plan import plan_dft
    assert plan_dft((8, 8), direction, mesh, real=real).scope() == scope


def _host_spans(trace_dir):
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name, dict(e.stats)) for e in line.events
                             if e.name.startswith(("insitu.", "solver.")))
    return spans


def test_host_spans_carry_their_step(mesh, tmp_path):
    grid = GridMeta(dims=SHAPE)
    chain = build_chain({"mode": "pipelined", "chain": CHAIN + [
        {"endpoint": "visualize", "array": "field",
         "out_dir": str(tmp_path / "viz")}]}, mesh=mesh, grid=grid)
    field = jnp.ones(SHAPE, jnp.float32)
    # rows long enough that one partial a row is under 1% of a leaf
    solver = _solver(mesh, shape=(16, 256))
    solver.step(1)
    solver.energy()          # compile and warm outside the trace
    trace_dir = str(tmp_path / "trace")
    with jax.profiler.trace(trace_dir):
        for step in (5, 6):
            chain.execute(BridgeData(arrays={"field": field}, grid=grid,
                                     step=step))
        chain.drain()
        solver.step(2)
        solver.energy()
    chain.finalize()
    spans = _host_spans(trace_dir)
    names = {n for n, _ in spans}
    assert {"insitu.dispatch", "insitu.submit", "insitu.wait_device",
            "insitu.d2h", "insitu.host.visualize", "solver.wait_device",
            "solver.parseval_sum"} <= names
    assert "solver.gather" not in names
    monitor = {n for n in names if n.startswith("solver.")}
    for name in names - monitor:
        assert sorted(a["step"] for n, a in spans if n == name) == [5, 6]
    for name in monitor:
        assert {a["step"] for n, a in spans if n == name} == {3}
    # the device results as they landed on the host: the field, its
    # zero imaginary part and the energies
    d2h = [a["bytes"] for n, a in spans if n == "insitu.d2h"]
    assert all(b >= 2 * field.nbytes for b in d2h)
    # the energy's row partials, not the state, came to the host
    (copied,) = [a["bytes"] for n, a in spans if n == "solver.parseval_sum"]
    assert 0 < copied < 0.01 * solver.state[0].nbytes


def test_pipeline_reports_the_copy_inside_its_wait(mesh, tmp_path):
    grid = GridMeta(dims=SHAPE)
    chain = build_chain({"mode": "pipelined", "chain": CHAIN + [
        {"endpoint": "visualize", "array": "field",
         "out_dir": str(tmp_path)}]}, mesh=mesh, grid=grid)
    field = jnp.ones(SHAPE, jnp.float32)
    for step in range(3):
        out = chain.execute(BridgeData(arrays={"field": field}, grid=grid,
                                       step=step))
    chain.drain()
    rep = chain.marshaling_report()["pipeline"]
    assert rep["completed"] == 3
    assert 0 < rep["d2h_s"] <= rep["wait_s"]
    per_field = sum(x.nbytes for x in jax.tree.leaves(out.arrays))
    assert rep["d2h_bytes"] == 3 * per_field
    chain.reset_stats()
    rep = chain.marshaling_report()["pipeline"]
    assert rep["d2h_s"] == 0.0 and rep["d2h_bytes"] == 0
    chain.finalize()
