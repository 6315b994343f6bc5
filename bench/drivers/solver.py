"""Solver traffic: the 2-D vorticity solver stepped back to back.

Set-up builds the solver through the launch driver's ``build_solver``
(seeded ``init_random``) and drives that same object through its first
steps, keeping the state before and after each on the host. The window
steps it on, reading energy and enstrophy on the host every
``monitor_every`` steps as the driver does, and closes after the first
monitor read past the window's length, so only whole steps count.
After the window the kept states are compared with the float64
reference from the same seed.

``work`` gives a step's nominal work and ``control_numbers`` the
cell's numbers for the precision control, as every driver does.
"""
from __future__ import annotations

import argparse
import gc
import math
import time

import numpy as np

from bench import work as nominal
from bench.compare import rel_l2
from bench.reference import lowprec
from bench.reference import ns2d as ref
from repro.compat import make_mesh
from repro.launch.solver import build_solver


def work(shape) -> dict:
    """Nominal bytes and flops of one IF-RK4 step."""
    return nominal.ns2d_step(shape)


def control_numbers(shape, seed, config, traffic, devices) -> dict:
    """The cell's numbers for the IF-RK4 reference with its transforms
    in bf16×3 (``reference/lowprec.py``), from the seed's initial
    field, over the steps the cell checks."""
    import jax.numpy as jnp
    low = ref.NS2D(shape, nu=config["nu"], dt=config["dt"], xp=jnp,
                   rfft2=lambda x: lowprec.rfftn_jit(x, 2),
                   irfft2=lambda s, sh: lowprec.irfftn_jit(s, tuple(sh)),
                   real=np.float32)
    exact = ref.NS2D(shape, nu=config["nu"], dt=config["dt"])
    w0 = ref.initial_vorticity(shape, seed)
    s_low = low.initial(w0.astype(np.float32))
    s_ref = exact.initial(w0)
    err = 0.0
    for _ in range(traffic["checked_steps"]):
        s_low, s_ref = low.step(s_low), exact.step(s_ref)
        err = max(err, rel_l2(np.asarray(s_low), s_ref))
    return {"state_err": err}


class Cell:
    unit = "step"

    def __init__(self, config, traffic, cell, seed, devices, shape=None):
        self.shape = tuple(shape or config["grid"])
        self.config, self.traffic, self.seed = config, traffic, int(seed)
        self.mesh = make_mesh(tuple(config["mesh"]), tuple(config["axes"]),
                              devices=devices)

    def _host_state(self):
        re, im = (np.asarray(x) for x in self.solver.state)
        h = self.shape[-1] // 2 + 1
        return re[:, :h] + 1j * im[:, :h].astype(np.float64)

    def setup(self, span):
        t0 = time.perf_counter()
        c = self.config
        args = argparse.Namespace(
            solver="ns2d", grid=list(self.shape), nu=c["nu"], dt=c["dt"],
            decomp=c.get("decomp"), c2c=False, backend="auto",
            stepper=c["stepper"], init="random", seed=self.seed)
        self.solver = build_solver(args, self.mesh)
        t1 = time.perf_counter()
        self.states = [self._host_state()]
        for _ in range(self.traffic["checked_steps"]):
            with span("step"):
                self.solver.step(1)
            self.states.append(self._host_state())
        t2 = time.perf_counter()
        self._monitor(span)
        self.phases = {"build_solver": t1 - t0, "first_steps": t2 - t1,
                       "monitor": time.perf_counter() - t2}

    def _monitor(self, span):
        with span("monitor"):
            return self.solver.energy(), self.solver.enstrophy()

    def window(self, seconds, span):
        every = self.traffic["monitor_every"]
        steps, bad = 0, 0
        with span("window"):
            t0 = time.perf_counter()
            while time.perf_counter() - t0 < seconds:
                with span("step"):
                    self.solver.step(every)
                # the reads copy the state to the host: the steps are done
                bad += not all(map(math.isfinite, self._monitor(span)))
                steps += every
            window_s = time.perf_counter() - t0
        self.bad = bad
        return {"attempted": steps, "completed": steps, "window_s": window_s,
                "latencies_s": [], "pipeline": None}

    def check(self, limits):
        del self.solver
        gc.collect()
        c = self.config
        model = ref.NS2D(self.shape, nu=c["nu"], dt=c["dt"])
        s = model.initial(ref.initial_vorticity(self.shape, self.seed))
        err = 0.0
        for got in self.states[1:]:
            s = model.step(s)
            err = max(err, rel_l2(got, s))
        return {"state_err": err}, self.bad
