"""Chain traffic: seeded fields handed to the in-situ chain back to back.

Set-up builds the chain from the traffic's endpoint list through
``build_chain``, makes a ring of fields on the device from the seed, and
runs a few fields through the window's own loop. The window then hands
the ring's fields over one after another: where the cell sets
``in_flight``, the loop waits for the oldest field's energies before it
hands over more (a closed loop); otherwise the chain's own host
pipeline pushes back. After the window every field's kept and total
energies, and in full the last field handed over (and, with a host
tail, the last field it received), are compared with the float64
reference. Those are fields the loop holds anyway, so the check adds
nothing to the device's peak memory.

``work`` gives a field's nominal work and ``control_numbers`` the
cell's numbers for the precision control, as every driver does.
"""
from __future__ import annotations

import collections
import gc
import shutil
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from bench import work as nominal
from bench.compare import rel_l2, rel_scalar
from bench.reference import chain as ref
from bench.reference import lowprec
from repro.compat import make_mesh
from repro.core.insitu.bridge import BridgeData, GridMeta
from repro.core.insitu.config import build_chain

ENERGIES = ("insitu_kept_energy", "insitu_total_energy")
HOST_OUT = ("visualize", "writer")


def make_ring(shape, seed: int, n: int, sharding):
    """``n`` standard normal float32 fields made on the device from the
    seed (of any size: each field's key takes 32 bits of it)."""
    words = np.random.SeedSequence(int(seed)).generate_state(n)
    gen = jax.jit(lambda k: jax.random.normal(k, shape, jnp.float32),
                  out_shardings=sharding)
    return [gen(jax.random.key(int(w))) for w in words]


def work(shape) -> dict:
    """Nominal bytes and flops of one field through the chain."""
    return nominal.chain_field(shape)


def _keep_frac(traffic) -> float:
    return next(e["keep_frac"] for e in traffic["chain"]
                if e["endpoint"] == "bandpass")


def control_numbers(shape, seed, config, traffic, devices) -> dict:
    """The cell's numbers for the chain in bf16×3 with bfloat16 energy
    sums (``reference/lowprec.py``), on the ring's first field: one
    field per seed shows the control fail."""
    mesh = make_mesh(tuple(config["mesh"]), tuple(config["axes"]),
                     devices=devices)
    ring = make_ring(shape, seed, traffic["ring"],
                     NamedSharding(mesh, P(config["axes"][0])))
    x = np.asarray(ring[0])
    del ring
    keep = _keep_frac(traffic)
    y, kept, total = lowprec.chain(x, keep, devices[0])
    want, k_ref, t_ref = ref.chain(x, keep)
    return {"field_err": rel_l2(y, want),
            "energy_err": max(rel_scalar(kept, k_ref),
                              rel_scalar(total, t_ref))}


class Cell:
    unit = "field"

    def __init__(self, config, traffic, cell, seed, devices, shape=None):
        self.shape = tuple(shape or config["grid"])
        self.traffic, self.cell, self.seed = traffic, cell, int(seed)
        self.mesh = make_mesh(tuple(config["mesh"]), tuple(config["axes"]),
                              devices=devices)
        self.keep_frac = _keep_frac(traffic)
        self.in_flight = cell.get("in_flight")
        self.out_dir = None
        self.next_step = 0
        self.energies, self.late = {}, {}

    # -- set-up -------------------------------------------------------------
    def setup(self, span):
        t0 = time.perf_counter()
        chain_spec = []
        for ep in self.traffic["chain"]:
            ep = dict(ep)
            if ep["endpoint"] in HOST_OUT:
                if self.out_dir is None:
                    self.out_dir = tempfile.mkdtemp(prefix="bench-chain-")
                ep["out_dir"] = self.out_dir
            chain_spec.append(ep)
        cfg = {k: v for k, v in self.traffic.items()
               if k in ("mode", "pipeline_depth", "pipeline_workers")}
        self.grid = GridMeta(dims=self.shape)
        self.chain = build_chain(dict(cfg, chain=chain_spec),
                                 mesh=self.mesh, grid=self.grid)
        t1 = time.perf_counter()
        self.ring = make_ring(self.shape, self.seed, self.traffic["ring"],
                              self.chain.endpoints[0].plan.input_sharding())
        jax.block_until_ready(self.ring)
        t2 = time.perf_counter()
        self._loop(self.traffic["warmup_fields"], None, span)
        self.chain.reset_stats()
        self.phases = {"build": t1 - t0, "inputs": t2 - t1,
                       "warmup": time.perf_counter() - t2}

    # -- the loop the window runs ------------------------------------------
    def _hand_over(self):
        i = self.next_step
        self.next_step += 1
        field = self.ring[i % len(self.ring)]
        out = self.chain.execute(BridgeData(arrays={"field": field},
                                            grid=self.grid, step=i))
        return i, out

    def _loop(self, fields, seconds, span, on_out=None):
        """Hand over ``fields`` fields, or fields until ``seconds`` have
        passed, and wait for all of them; return (handed over, completed,
        seconds from the first handover to the last completion, per-field
        latencies in seconds)."""
        pending = collections.deque()
        latencies = []
        handed = 0
        t0 = time.perf_counter()

        def settle():
            i, t_in, out = pending.popleft()
            with span("wait"):
                got = jax.device_get([out.arrays[k] for k in ENERGIES])
            latencies.append(time.perf_counter() - t_in)
            self.energies[i] = tuple(float(v) for v in got)

        while True:
            now = time.perf_counter()
            done = handed >= fields if seconds is None else now - t0 >= seconds
            if done:
                break
            if self.in_flight and len(pending) >= self.in_flight:
                settle()
                continue
            with span("handover"):
                t_in = time.perf_counter()
                i, out = self._hand_over()
            handed += 1
            if on_out is not None:
                on_out(i, out)
            if self.in_flight:
                pending.append((i, t_in, out))
            else:
                self.late[i] = [out.arrays[k] for k in ENERGIES]
            # hold a field's outputs only while it is pending: at 1024^3
            # a second field's outputs do not fit beside the next one's
            del out
        with span("drain"):
            while pending:
                settle()
            self.chain.drain()
        # the window closes when the last field handed over has finished,
        # so it ends on a completion and every field in it is whole
        window_s = time.perf_counter() - t0
        report = self.chain.marshaling_report().get("pipeline")
        completed = report["completed"] if report else len(latencies)
        return handed, completed, window_s, latencies

    # -- the window -----------------------------------------------------------
    def window(self, seconds, span):
        self.energies, self.late = {}, {}
        self.first = self.next_step

        def keep(i, out):
            # the newest field: the loop holds it anyway while it is pending
            self.last = (i, out.arrays["field"])

        with span("window"):
            handed, done, window_s, lat = self._loop(None, seconds, span, keep)
        for i, vals in jax.device_get(self.late).items():
            self.energies[i] = tuple(float(v) for v in vals)
        self.late = {}
        report = self.chain.marshaling_report().get("pipeline")
        self.host_last = None
        if report is not None:
            self.host_last = self.chain.drain()
        return {"attempted": handed, "completed": done, "window_s": window_s,
                "latencies_s": lat, "pipeline": report}

    # -- correctness ----------------------------------------------------------
    def check(self, limits):
        """Free the program's state, then compare with the reference."""
        n_ring = len(self.ring)
        inputs = [np.asarray(x) for x in self.ring]
        got = [(self.last[0], np.asarray(self.last[1]))]
        if self.host_last is not None:
            got.append((int(self.host_last.step),
                        np.asarray(self.host_last.arrays["field"])))
        files = (self.chain.finalize() if self.out_dir else {})
        images = sum(len([f for f in v.get("files", []) if f.endswith(".pgm")])
                     for v in files.values())
        del self.chain, self.ring, self.last, self.host_last
        gc.collect()
        if self.out_dir:
            shutil.rmtree(self.out_dir, ignore_errors=True)

        field_err, energy_err, failed = 0.0, 0.0, 0
        for r in range(n_ring):
            want_y, kept, total = ref.chain(inputs[r], self.keep_frac)
            for i, y in got:
                if i % n_ring == r:
                    field_err = max(field_err, rel_l2(y, want_y))
            del want_y
            for i, (k, t) in self.energies.items():
                if i % n_ring == r:
                    e = max(rel_scalar(k, kept), rel_scalar(t, total))
                    energy_err = max(energy_err, e)
                    failed += e > limits["energy_err"]
        handed = self.next_step - self.first
        failed += handed - len([i for i in self.energies if i >= self.first])
        if self.out_dir:
            failed += max(0, self.next_step - images)
        return {"field_err": field_err, "energy_err": energy_err}, failed
