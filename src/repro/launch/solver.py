"""Solver driver: ``python -m repro.launch.solver --solver ns2d --grid 64 64 …``

Runs the pseudo-spectral solvers (``core/solver``) as the in-situ
chain's producer: a time-stepping loop whose every stage flows through
the cached distributed FFT plans, with energy/enstrophy monitoring, the
shell-summed spectrum shipped through a pipelined ``WriterEndpoint``
chain, checkpoint/restart via ``ckpt/``, and ``--wisdom`` warm-start
(a restarted solver plans with ZERO timed sweeps — the bench asserts
it). Single-process by default; on a cluster (``--coordinator`` etc.
or the ``REPRO_*`` env contract) the same entry point runs the solve
over a DCN-spanning mesh, exactly like ``launch/train.py``.
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

import jax
import numpy as np

from repro.compat import make_mesh
from repro.core.fft import plan as plan_mod
from repro.core.insitu.bridge import BridgeData, GridMeta
from repro.core.insitu.chain import InSituChain
from repro.core.insitu.endpoints.writer import WriterEndpoint
from repro.core.solver import Boussinesq3DSolver, NS2DSolver
from repro.launch.mesh import make_multihost_mesh
from repro.runtime.cluster import (add_cluster_args, config_from_args,
                                   init_cluster)


def _discard(_data):
    """--transit-async on_result for producer-only processes: their
    send() result is a None-leaved placeholder — drop it instead of
    letting the async hop retain it until drain."""


def build_solver(args, mesh):
    grid = tuple(args.grid)
    common = dict(nu=args.nu, dt=args.dt, decomp=args.decomp,
                  real=not args.c2c, backend=args.backend,
                  stepper=args.stepper)
    if args.solver == "ns2d":
        assert len(grid) == 2, "--solver ns2d wants --grid N0 N1"
        s = NS2DSolver(grid, mesh, **common)
        if args.init == "taylor-green":
            s.init_taylor_green()
        else:
            s.init_random(seed=args.seed)
    else:
        assert len(grid) == 3, "--solver bq3d wants --grid N0 N1 N2"
        s = Boussinesq3DSolver(grid, mesh, kappa=args.kappa,
                               gravity=args.gravity, **common)
        if args.init == "beltrami":
            s.init_beltrami()
        else:
            s.init_random(seed=args.seed)
    return s


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--solver", default="ns2d", choices=("ns2d", "bq3d"))
    ap.add_argument("--grid", type=int, nargs="+", default=[64, 64])
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--dt", type=float, default=5e-3)
    ap.add_argument("--nu", type=float, default=1e-3)
    ap.add_argument("--kappa", type=float, default=1e-3)
    ap.add_argument("--gravity", type=float, default=1.0)
    ap.add_argument("--decomp", default=None,
                    help="slab/pencil/pencil_tf/pencil2d/slab3d/measure "
                         "(default: inferred from grid rank and mesh)")
    ap.add_argument("--backend", default="auto")
    ap.add_argument("--c2c", action="store_true",
                    help="run through full c2c plans instead of r2c/c2r")
    ap.add_argument("--stepper", default="if_rk4",
                    choices=("rk4", "if_rk4"))
    ap.add_argument("--init", default="auto",
                    help="taylor-green | beltrami | random | auto "
                         "(taylor-green for ns2d, beltrami for bq3d)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--mesh-shape", type=int, nargs="+", default=None,
                    help="single-process mesh shape, e.g. --mesh-shape "
                         "4 2, over the first devices; exits when they "
                         "do not fit (default: all devices on one "
                         "axis)")
    ap.add_argument("--monitor-every", type=int, default=5)
    ap.add_argument("--spectrum-bins", type=int, default=16)
    ap.add_argument("--spectra-dir", default=None,
                    help="persist per-report E(k) through a pipelined "
                         "WriterEndpoint chain (.npy per report)")
    ap.add_argument("--transit-consumers", type=int, default=0,
                    metavar="N",
                    help="M→N in-transit split: solve on all but the "
                         "last N devices and ship each E(k) report to "
                         "a disjoint N-device consumer mesh through "
                         "core/insitu/transit.TransitBridge (0 = "
                         "persist in place)")
    ap.add_argument("--transit-async", action="store_true",
                    help="overlap the transit hop with the next solve "
                         "interval: send_async() snapshots the E(k) "
                         "report and a bounded background worker runs "
                         "the exchange plus the consumer-side chain; "
                         "a failed hop surfaces on the next send or "
                         "drain (requires --transit-consumers; "
                         "docs/multihost.md)")
    ap.add_argument("--elastic", action="store_true",
                    help="put the transit consumer mesh under an "
                         "ElasticController: consumer ranks heartbeat "
                         "every report, missed leases trigger a "
                         "restart-free rescale (docs/elastic.md; "
                         "requires --transit-consumers)")
    ap.add_argument("--elastic-lease", type=float, default=30.0,
                    metavar="SECONDS",
                    help="heartbeat lease; a consumer rank missing 3 "
                         "leases is declared dead")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0,
                    help="checkpoint every N steps (0 = off)")
    ap.add_argument("--restore", action="store_true",
                    help="resume from the latest checkpoint in "
                         "--ckpt-dir before stepping")
    ap.add_argument("--wisdom", default=None, metavar="FILE",
                    help="persistent autotune wisdom file (read at "
                         "bring-up, new winners persisted; "
                         "docs/wisdom.md)")
    ap.add_argument("--wisdom-mode", default="readwrite",
                    choices=("off", "read", "readwrite"))
    add_cluster_args(ap)
    args = ap.parse_args(argv)
    if args.init == "auto":
        args.init = "taylor-green" if args.solver == "ns2d" else "beltrami"
    if args.wisdom:
        plan_mod.set_wisdom(args.wisdom, args.wisdom_mode)
    init_cluster(config_from_args(args))

    transit_bridge = None
    elastic = None
    if args.transit_consumers:
        # M→N in-transit: solve on a producer mesh excluding the last
        # N devices; E(k) reports hop to the consumer mesh
        if args.elastic:
            from repro.launch.mesh import make_elastic_setup
            mesh, elastic = make_elastic_setup(
                args.transit_consumers, noun="solver",
                lease=args.elastic_lease)
            transit_bridge = elastic
        else:
            from repro.launch.mesh import make_transit_setup
            mesh, transit_bridge = make_transit_setup(
                args.transit_consumers, noun="solver")
    elif args.elastic:
        raise SystemExit("--elastic requires --transit-consumers N "
                         "(there is no consumer mesh to rescale)")
    elif args.transit_async:
        raise SystemExit("--transit-async requires --transit-consumers "
                         "N (there is no transit hop to overlap)")
    elif jax.process_count() > 1:
        mesh = make_multihost_mesh()
    else:
        devices = jax.devices()
        shape = (tuple(args.mesh_shape) if args.mesh_shape
                 else (len(devices),))
        need = int(np.prod(shape))
        if len(shape) > 2 or need > len(devices):
            raise SystemExit(
                f"--mesh-shape {' '.join(map(str, shape))} needs "
                f"{need} devices on at most 2 axes; this process has "
                f"{len(devices)} {devices[0].platform} device(s)")
        mesh = make_mesh(shape, ("data", "model")[: len(shape)],
                         devices=devices[:need])

    t0 = time.perf_counter()
    solver = build_solver(args, mesh)
    bringup_s = time.perf_counter() - t0
    stats0 = solver.basis.plan_stats()

    if args.ckpt_dir and jax.process_count() > 1:
        # replicated gathers, same bytes per process — but the atomic
        # tmp-dir rename races across processes sharing one directory
        args.ckpt_dir = str(Path(args.ckpt_dir)
                            / f"proc{jax.process_index()}")
    if args.restore:
        assert args.ckpt_dir, "--restore needs --ckpt-dir"
        step = solver.restore(args.ckpt_dir)
        print(f"restored step {step} (t={solver.t:.4f})")

    chain = None
    if args.spectra_dir:
        chain = InSituChain(
            [WriterEndpoint(array="spectrum", out_dir=args.spectra_dir,
                            prefix=f"{args.solver}_spectrum")],
            mesh=mesh, mode="pipelined").initialize(
                grid=GridMeta(dims=tuple(args.grid)))

    reports = []
    t1 = time.perf_counter()
    done = 0
    while done < args.steps:
        n = min(args.monitor_every, args.steps - done)
        t_interval = time.perf_counter()
        solver.step(n)
        done += n
        rep = {"step": solver.step_count, "t": round(solver.t, 6),
               "energy": solver.energy()}
        if args.solver == "ns2d":
            rep["enstrophy"] = solver.enstrophy()
        else:
            rep["scalar_variance"] = solver.scalar_variance()
        # the diagnostics above wait for the steps in flight, so this
        # interval ends after the device finished them
        rep["interval_s"] = time.perf_counter() - t_interval
        reports.append(rep)
        if jax.process_index() == 0:
            print(json.dumps(rep))
        if chain is not None:
            _, ek = solver.spectrum(args.spectrum_bins)
            payload = BridgeData(arrays={"spectrum": np.asarray(ek)},
                                 step=solver.step_count,
                                 domain="spectral")
            deliver = True
            if transit_bridge is not None:
                # collective hop onto the consumer mesh — every process
                # calls send(); only consumer participants get arrays
                if args.transit_async:
                    # bounded background worker runs the exchange and
                    # (on consumers) the writer chain, overlapping the
                    # next solve interval; failures surface contained
                    # at the next send/drain
                    transit_bridge.send_async(
                        payload,
                        on_result=(chain.execute
                                   if transit_bridge.is_consumer()
                                   else _discard))
                    deliver = False
                else:
                    payload = transit_bridge.send(payload)
                    deliver = transit_bridge.is_consumer()
            if deliver:
                chain.execute(payload)
        if elastic is not None:
            # lease renewal + failure poll once per monitor interval —
            # tick() is collective and every process is here each loop
            if args.transit_async:
                # tick() runs host collectives; drain pending async
                # sends first so the worker's collective never
                # interleaves with them (transit.py contract)
                transit_bridge.drain_async()
            elastic.heartbeat_all()
            elastic.tick()
        if (args.ckpt_every and args.ckpt_dir
                and solver.step_count % args.ckpt_every == 0):
            solver.save(args.ckpt_dir)
    wall = time.perf_counter() - t1

    if transit_bridge is not None and args.transit_async:
        # consumer-side chain work runs on the async worker — finish
        # every pending hop (surfacing contained failures) before the
        # chain finalizes and the bridge reports
        transit_bridge.drain_async()
    files = []
    if chain is not None:
        fin = chain.finalize()
        files = fin.get("writer", {}).get("files", [])
    stats1 = solver.basis.plan_stats()
    summary = {
        "solver": args.solver, "grid": list(args.grid),
        "decomp": solver.basis.decomp, "real": solver.basis.real,
        "steps": args.steps, "wall_s": round(wall, 4),
        "steps_per_s": round(args.steps / max(wall, 1e-9), 3),
        "bringup_s": round(bringup_s, 4),
        "final": reports[-1] if reports else None,
        "reports": reports,
        "spectra_files": len(files),
        "plan_stats": {"wisdom_hits": stats1["wisdom_hits"],
                       "sweep_candidates_timed":
                           stats1["sweep_candidates_timed"],
                       "bringup_misses": stats0["misses"]},
    }
    if transit_bridge is not None:
        summary["elastic" if elastic is not None else "transit"] = \
            transit_bridge.report()
    if jax.process_index() == 0:
        print(json.dumps(summary, default=str))
    return summary


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
