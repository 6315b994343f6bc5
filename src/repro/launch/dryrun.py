import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this proves, without hardware:
  * the sharding config is coherent (no mismatch / unsupported collective),
  * the program fits (memory_analysis per chip),
  * and extracts roofline terms (cost_analysis + HLO collective parse,
    with the L∈{0,1,full} scan-trip extrapolation — see roofline.py).

Usage:
  python -m repro.launch.dryrun --arch qwen3-4b --shape train_4k
  python -m repro.launch.dryrun --all               # every cell, 1-pod + 2-pod
  python -m repro.launch.dryrun --all --mesh pod1   # single-pod only

Results land in results/dryrun/<arch>__<shape>__<mesh>.json.
"""
import argparse
import dataclasses
import json
import time
import traceback
from pathlib import Path

import jax

from repro.configs import registry
from repro.configs.base import SHAPES
from repro.launch import specs as specs_mod
from repro.launch import roofline as rl
from repro.launch.mesh import make_production_mesh
from repro.models import blocks as blk

RESULTS = Path(__file__).resolve().parents[3] / "results" / "dryrun"


def _with_groups(cfg, groups: int):
    """Copy of cfg with the scan trip count forced to `groups`."""
    period = len(cfg.layer_pattern)
    reps = {"num_layers": period * groups}
    if cfg.family == "encdec":
        reps.update(encoder_layers=groups, decoder_layers=groups)
    return dataclasses.replace(cfg, **reps)


def lower_cell(cfg, shape, mesh, *, multi_pod: bool, **overrides):
    built, policy = specs_mod.build_cell(cfg, shape, mesh,
                                         multi_pod=multi_pod, **overrides)
    jitted = jax.jit(built["fn"], in_shardings=built["in_shardings"],
                     out_shardings=built.get("out_shardings"),
                     donate_argnums=built["donate_argnums"])
    with jax.set_mesh(mesh):
        lowered = jitted.lower(*built["args"])
        compiled = lowered.compile()
    return built, compiled


def run_cell(arch: str, shape_name: str, mesh_name: str,
             *, full_roofline: bool = True, **overrides) -> dict:
    cfg = registry.get_config(arch)
    if "moe_mode" in overrides:     # §Perf: EP↔TP expert-sharding probe
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe,
                                         mode=overrides.pop("moe_mode")))
    if "capacity_factor" in overrides:
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(
                cfg.moe, capacity_factor=overrides.pop("capacity_factor")))
    shape = SHAPES[shape_name]
    multi_pod = mesh_name == "pod2"
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = 512 if multi_pod else 256

    t0 = time.time()
    result = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
              "chips": chips, "status": "ok"}
    try:
        built, compiled = lower_cell(cfg, shape, mesh, multi_pod=multi_pod,
                                     **overrides)
        result["meta"] = built["meta"]
        result["memory"] = rl.memory_report(compiled)
        cL = rl.raw_costs(compiled)
        result["raw_cost_full"] = {k: v for k, v in cL.items()}

        if full_roofline:
            trips = (cfg.encoder_layers if cfg.family == "encdec"
                     else blk.n_groups(cfg))
            # Roofline compiles force microbatches=1: a second (microbatch)
            # scan would break the single-loop L-extrapolation, and the
            # micro=1 step is the bandwidth-optimal variant of the same
            # algorithm. The full artifact above keeps the real microbatch
            # count for the memory report.
            ro = dict(overrides)
            if shape.kind == "train":
                ro["microbatches"] = 1
            costs = {}
            for g in (0, 1):
                _, cg = lower_cell(_with_groups(cfg, g), shape, mesh,
                                   multi_pod=multi_pod, **ro)
                costs[g] = rl.raw_costs(cg)
            cell = rl.extrapolate(costs[0], costs[1], trips)
            result["roofline"] = cell.to_dict()
            result["roofline"]["trips"] = trips
            mf = rl.model_flops(cfg, shape, per_chip=True, chips=chips)
            result["roofline"]["model_flops_per_chip"] = mf
            result["roofline"]["useful_ratio"] = (
                mf / cell.flops if cell.flops else 0.0)
    except Exception as e:  # noqa: BLE001 — record and continue the sweep
        result["status"] = "error"
        result["error"] = f"{type(e).__name__}: {e}"
        result["traceback"] = traceback.format_exc()[-4000:]
    result["compile_seconds"] = round(time.time() - t0, 1)
    return result


def save(result: dict):
    RESULTS.mkdir(parents=True, exist_ok=True)
    name = f"{result['arch']}__{result['shape']}__{result['mesh']}.json"
    (RESULTS / name).write_text(json.dumps(result, indent=2, default=str))
    mem = result.get("memory", {}).get("total_hbm_per_chip", 0) / 2**30
    dom = result.get("roofline", {}).get("dominant", "-")
    print(f"[{result['status']:5s}] {result['arch']:16s} "
          f"{result['shape']:12s} {result['mesh']}  "
          f"hbm/chip={mem:6.2f}GiB dom={dom:10s} "
          f"t={result['compile_seconds']}s", flush=True)
    if result["status"] == "error":
        print("   ", result["error"].splitlines()[0][:160], flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="both", choices=["pod1", "pod2",
                                                       "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--no-roofline", action="store_true")
    args = ap.parse_args()

    meshes = ["pod1", "pod2"] if args.mesh == "both" else [args.mesh]
    if args.all:
        todo = [(a, s.name) for a in registry.list_archs()
                for s in registry.cells(a)]
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        todo = [(args.arch, args.shape)]

    for arch, shape in todo:
        for mesh_name in meshes:
            out = (RESULTS /
                   f"{arch}__{shape}__{mesh_name}.json")
            if args.skip_existing and out.exists():
                prev = json.loads(out.read_text())
                if prev.get("status") == "ok":
                    continue
            # roofline terms are a single-pod report; pod2 is the
            # sharding-coherence proof for the pod axis
            full = (mesh_name == "pod1") and not args.no_roofline
            save(run_cell(arch, shape, mesh_name, full_roofline=full))


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
