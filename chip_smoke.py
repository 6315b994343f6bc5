#!/usr/bin/env python3
"""Bring-up check: the in-situ FFT chain and the NS2D solver on TPU.

    python chip_smoke.py              # one chip: phases ns2d, chain2d, chain3d
    python chip_smoke.py --chips 4    # four chips: the sharded 3-D r2c chain
                                      # on a (4,) slab3d and a (2, 2) pencil mesh
    python chip_smoke.py --rehearse   # CPU at shrunken sizes (every line says so)

Every phase goes through the entry points a user calls and prints one
JSON line: its size, compile and steady seconds (each ending in
``block_until_ready`` or a host copy of the result), the device's
``peak_bytes_in_use`` so far in the process, and its relative L2 error
against a float64 reference computed on the host:

* ``ns2d``    ``repro.launch.solver.main`` (Taylor-Green, 4096², 8 steps)
              against the closed-form decay E = e^{-4νt}/4, Z = e^{-4νt}/2,
              at the 1e-5 bound ``tests/test_solver.py`` holds.
* ``chain2d`` ``build_chain`` in pipelined mode, r2c FFT → bandpass (the
              fused Pallas kernel, since the 2-D spectrum is unsharded) →
              c2r FFT → writer, on 8192² fields, against
              ``np.fft.irfft2(mask · np.fft.rfft2(x))`` read back from the
              writer's files, plus the kernel's kept/total energies.
* ``chain3d`` the same chain on 512³ fields, the largest power-of-two cube
              whose field, half-spectrum and four-step temporaries fit one
              v5e chip's 16 GB: compiled for a described v5e, the program
              takes 0.54 GB of arguments, 1.07 GB of outputs and 1.61 GB of
              temporaries, and 1024³ needs eight times that.

Phases run smallest first, so each one's peak is its own. The last line
is ``{"ok": true, "device": {...}}`` only when every phase met its bound
on the TPU; with no TPU the script exits 2 and prints no result.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import json
import os
import shutil
import sys
import time
import traceback
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

SIZES = {"ns2d": 4096, "chain2d": 8192, "chain3d": 512}
REHEARSAL_SIZES = {"ns2d": 64, "chain2d": 128, "chain3d": 32}
REHEARSAL_NOTE = "CPU rehearsal at shrunken sizes; not a chip result"
FFT_BOUND = 1e-4
SOLVER_BOUND = 1e-5
# a wide pass band checks most of the spectrum; the paper's 0.75% would
# leave a few hundred modes to compare
KEEP_FRAC = 0.25
NU, DT, STEPS, MONITOR = 0.1, 0.01, 8, 4


def _peak_bytes(devices):
    stats = [d.memory_stats() for d in devices]
    if any(s is None for s in stats):
        return None
    return max(int(s["peak_bytes_in_use"]) for s in stats)


def _rel(got, want) -> float:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def phase_ns2d(n):
    """The solver driver in-process, checked against Taylor-Green's
    closed-form decay. compile_s is plan bring-up plus the first monitor
    interval's excess over the second (same step count, warm)."""
    from repro.launch import solver as driver

    argv = ["--solver", "ns2d", "--grid", str(n), str(n),
            "--steps", str(STEPS), "--monitor-every", str(MONITOR),
            "--nu", str(NU), "--dt", str(DT), "--init", "taylor-green"]
    with contextlib.redirect_stdout(sys.stderr):
        summary = driver.main(argv)
    reps = summary["reports"]
    decay = np.exp(-4.0 * NU * np.array([r["t"] for r in reps]))
    got = [[r["energy"], r["enstrophy"]] for r in reps]
    want = np.stack([0.25 * decay, 0.5 * decay], axis=1)
    first, steady = reps[0]["interval_s"], reps[1]["interval_s"]
    return {"size": f"{n}x{n} float32, {STEPS} IF-RK4 steps",
            "decomp": summary["decomp"],
            "rel_err": _rel(got, want), "bound": SOLVER_BOUND,
            "reference": "analytic Taylor-Green decay (float64)",
            "compile_s": summary["bringup_s"] + first - steady,
            "run_s_per_step": steady / MONITOR}


class Case:
    """Seeded real fields and their float64 numpy chain references."""

    def __init__(self, shape, seed, nfields):
        from repro.core.fft.filters import lowpass_mask

        self.shape = tuple(shape)
        rng = np.random.default_rng(seed)
        self.fields = [rng.standard_normal(self.shape, dtype=np.float32)
                       for _ in range(nfields)]
        half = np.asarray(lowpass_mask(self.shape, KEEP_FRAC),
                          np.float64)[..., : self.shape[-1] // 2 + 1]
        self.refs, self.energies = [], []
        for x in self.fields:
            spec = np.fft.rfftn(x.astype(np.float64))
            power = spec.real ** 2 + spec.imag ** 2
            self.energies.append((float(np.sum(power * half)),
                                  float(np.sum(power))))
            self.refs.append(np.fft.irfftn(
                spec * half, s=self.shape,
                axes=tuple(range(len(self.shape)))).astype(np.float32))
            del spec, power


def run_chain(case, mesh, out_dir):
    """The paper's chain through ``build_chain`` (pipelined, host
    writer), every field compared with its reference as written."""
    import jax
    from repro.core.insitu.bridge import BridgeData, GridMeta
    from repro.core.insitu.config import build_chain

    shutil.rmtree(out_dir, ignore_errors=True)
    grid = GridMeta(dims=case.shape)
    chain = build_chain({"mode": "pipelined", "chain": [
        {"endpoint": "fft", "array": "field", "direction": "forward",
         "real": True},
        {"endpoint": "bandpass", "array": "field", "keep_frac": KEEP_FRAC},
        {"endpoint": "fft", "array": "field", "direction": "backward",
         "real": True},
        {"endpoint": "writer", "array": "field", "out_dir": str(out_dir)},
    ]}, mesh=mesh, grid=grid)
    fwd = chain.endpoints[0].plan
    want_devs = set(mesh.devices.flat)
    fields = [jax.device_put(x, fwd.input_sharding()) for x in case.fields]
    jax.block_until_ready(fields)

    shards = fields[0].addressable_shards
    nbytes = fields[0].nbytes
    if ({s.device for s in shards} != want_devs
            or len(shards) != len(want_devs)
            or any(s.data.nbytes * len(shards) != nbytes for s in shards)):
        raise AssertionError(
            f"field shards {[(str(s.device), s.data.nbytes) for s in shards]}"
            f" are not an even split over {sorted(map(str, want_devs))}")

    def launch(i):
        return chain.execute(BridgeData(arrays={"field": fields[i]},
                                        grid=grid, step=i))

    t0 = time.perf_counter()
    outs = [launch(0)]
    jax.block_until_ready(outs[0].arrays)
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    outs += [launch(i) for i in range(1, len(fields))]
    jax.block_until_ready([o.arrays for o in outs])
    chain.drain()
    run_s = (time.perf_counter() - t0) / max(len(fields) - 1, 1)
    files = chain.finalize()["writer"]["files"]

    for o in outs:
        where = o.arrays["field"].sharding.device_set
        if where != want_devs:
            raise AssertionError(f"chain output on {sorted(map(str, where))}"
                                 f", not {sorted(map(str, want_devs))}")
    errs = [_rel(np.load(f), ref) for f, ref in zip(files, case.refs)]
    energy_errs = [max(abs(float(o.arrays["insitu_kept_energy"]) - k) / k,
                       abs(float(o.arrays["insitu_total_energy"]) - t) / t)
                   for o, (k, t) in zip(outs, case.energies)]
    shutil.rmtree(out_dir, ignore_errors=True)
    if len(files) != len(fields):
        raise AssertionError(f"writer wrote {len(files)} of {len(fields)}")
    return {"size": "x".join(map(str, case.shape)) + " float32",
            "fields": len(fields), "decomp": fwd.decomp,
            "mesh": dict(mesh.shape), "rel_err": max(errs),
            "rel_err_per_field": errs,
            "energy_rel_err": max(energy_errs), "bound": FFT_BOUND,
            "reference": "np.fft float64 r2c -> mask -> c2r",
            "compile_s": compile_s, "run_s_per_field": run_s}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the sharded 3-D chain, on four chips")
    ap.add_argument("--rehearse", action="store_true",
                    help="run on the CPU at shrunken sizes; never "
                         "prints ok")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if args.rehearse:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.chips}")

    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    import jax
    from repro.compat import make_mesh

    devices = jax.devices()
    platform = devices[0].platform
    if not args.rehearse and platform != "tpu":
        print(f"chip_smoke: JAX finds no TPU (platform {platform!r}); "
              f"use --rehearse for a CPU rehearsal", file=sys.stderr)
        return 2
    if len(devices) != args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX finds "
              f"{len(devices)} {platform} device(s)", file=sys.stderr)
        return 2
    sizes = REHEARSAL_SIZES if args.rehearse else SIZES
    out_root = ROOT / "results" / "chip_smoke"

    if args.chips == 1:
        mesh = make_mesh((1,), ("data",))
        phases = [
            ("ns2d", lambda: phase_ns2d(sizes["ns2d"])),
            ("chain2d", lambda: run_chain(
                Case((sizes["chain2d"],) * 2, args.seed, 3), mesh,
                out_root / "chain2d")),
            ("chain3d", lambda: run_chain(
                Case((sizes["chain3d"],) * 3, args.seed, 3), mesh,
                out_root / "chain3d")),
        ]
    else:
        case3d = {}

        def shared_case():
            if not case3d:
                case3d["c"] = Case((sizes["chain3d"],) * 3, args.seed, 2)
            return case3d["c"]
        phases = [
            ("chain3d_slab3d", lambda: run_chain(
                shared_case(), make_mesh((4,), ("data",)),
                out_root / "chain3d_slab3d")),
            ("chain3d_pencil", lambda: run_chain(
                shared_case(), make_mesh((2, 2), ("data", "model")),
                out_root / "chain3d_pencil")),
        ]

    failed = []
    for name, run in phases:
        line = {"phase": name}
        if args.rehearse:
            line["rehearsal"] = REHEARSAL_NOTE
        try:
            line.update(run())
            line["peak_bytes_in_use"] = _peak_bytes(devices)
            within = (line["rel_err"] <= line["bound"]
                      and line.get("energy_rel_err", 0.0) <= line["bound"])
            line["passed"] = bool(within)
        except Exception as err:          # report, then run the next phase
            traceback.print_exc()
            line.update(passed=False, error=f"{type(err).__name__}: {err}")
        if not line["passed"]:
            failed.append(name)
        print(json.dumps(line), flush=True)
        gc.collect()

    device = {"platform": platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    if args.rehearse:
        print(json.dumps({"rehearsal": REHEARSAL_NOTE, "failed": failed,
                          "compile_cache": cache, "device": device}))
        return 1 if failed else 0
    if failed:
        print(json.dumps({"ok": False, "failed": failed, "device": device}))
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
