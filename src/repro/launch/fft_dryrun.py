import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Dry-run + roofline for the paper's own workload: the distributed FFT
on the production mesh.

Cells (all single-pod 16×16 unless suffixed `@pod2`):

  slab2d-16384           — paper-faithful slab (1-D) decomposition: only
                           the 16-way data axis participates (the
                           scalability ceiling the paper names in §5)
  pencil2d-16384         — 2-axis decomposition of the same 2-D grid:
                           all 256 chips tile it (three small exchanges
                           instead of one 16-way exchange)
  pencil3d-1024          — pencil (2-D) decomposition over all 256 chips
  pencil3d-1024-bf16     — + bf16 wire transport (beyond-paper)
  pencil3d-1024-dcnwire  — per-STAGE wire: bf16 on the second (a0)
                           rotation only — the hop that crosses DCN on
                           multi-host meshes, i.e. the tuple the
                           topology-aware measure sweep generates
  slab2d-16384-overlap4  — + chunked compute/comm pipelining
  r2c3d-slab3d-1024      — real-input 3-D slab: half-spectrum planes,
                           one exchange, unpadded half axis
  fig2-chain-8192        — forward → bandpass → inverse fused chain (the
                           full paper workflow at scale)
  fig2-r2c-8192          — the same chain on the r2c half-spectrum

No depth scan ⇒ cost_analysis needs no trip extrapolation; collective
bytes come from the same HLO parser. FLOP reference: 5·N·log2 N per 1-D
transform (the classic FFT count).
"""
import argparse
import json
import math
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P


from repro.core.fft import distributed as D
from repro.core.fft.filters import lowpass_mask
from repro.launch import roofline as rl
from repro.launch.mesh import make_production_mesh

RESULTS = Path(__file__).resolve().parents[3] / "results" / "dryrun_fft"


def build(kind: str, mesh):
    """Returns (fn, arg ShapeDtypeStructs, in_shardings, model_flops)."""
    sds = jax.ShapeDtypeStruct
    if kind.startswith("slab2d"):
        n = int(kind.split("-")[1])
        shape = (n, n)
        spec = P("data", None)
        chunks = 4 if "overlap" in kind else 0
        if chunks:
            fn = lambda r, i: D.slab_fft_2d_overlap(r, i, mesh, "data",
                                                    chunks=chunks)
        else:
            fn = lambda r, i: D.slab_fft_2d(r, i, mesh, "data")
        flops = 2 * 5 * n * n * math.log2(n)     # two 1-D passes
    elif kind.startswith("pencil3d"):
        n = int(kind.split("-")[1])
        shape = (n, n, n)
        spec = P("data", "model", None)
        # per-stage wire ("dcnwire"): cast only the SECOND rotation
        # (the a0 exchange — the hop that crosses DCN on this repo's
        # multi-host meshes) — the tuple the topology-aware measure
        # sweep generates for that profile
        wire = (jnp.bfloat16 if kind.endswith("bf16")
                else (None, "bfloat16") if kind.endswith("dcnwire")
                else None)
        fn = lambda r, i: D.pencil_fft_3d(r, i, mesh,
                                          wire_dtype=wire)
        flops = 3 * 5 * n * n * n * math.log2(n)
    elif kind.startswith("pencil2d"):
        n = int(kind.split("-")[1])
        shape = (n, n)
        spec = P("data", "model")
        fn = lambda r, i: D.pencil2d_fft_2d(r, i, mesh)
        flops = 2 * 5 * n * n * math.log2(n)
    elif kind.startswith("r2c3d-slab3d"):
        from repro.core.fft import rfft as rfft_mod
        n = int(kind.split("-")[-1])
        shape = (n, n, n)
        fn = lambda x: rfft_mod.rfft3_slab3d(x, mesh, "data")
        flops = 3 * 5 * n * n * n * math.log2(n) / 2   # half-spectrum
        args = (sds(shape, jnp.float32),)
        sh = NamedSharding(mesh, P("data", None, None))
        return fn, args, (sh,), flops
    elif kind.startswith("fig2-r2c"):
        # real-input half-spectrum chain (FFTW r2c analogue, §Perf C5)
        from repro.core.fft import rfft as rfft_mod
        n = int(kind.split("-")[-1])
        shape = (n, n)
        mask = lowpass_mask(shape, 0.05)
        fn = lambda x: rfft_mod.rfft_chain_2d(x, mask, mesh, "data")
        flops = 2 * 5 * n * n * math.log2(n)     # ~half of the c2c chain
        args = (sds(shape, jnp.float32),)
        sh = NamedSharding(mesh, P("data", None))
        return fn, args, (sh,), flops
    elif kind.startswith("fig2-chain"):
        n = int(kind.split("-")[-1])
        shape = (n, n)
        spec = P("data", None)
        mask = lowpass_mask(shape, 0.05).astype(jnp.float32)

        def fn(r, i):
            fr, fi = D.slab_fft_2d(r, i, mesh, "data")
            fr, fi = fr * mask, fi * mask
            return D.slab_fft_2d(fr, fi, mesh, "data", inverse=True)
        flops = 4 * 5 * n * n * math.log2(n)
    else:
        raise ValueError(kind)
    args = (sds(shape, jnp.float32), sds(shape, jnp.float32))
    sh = NamedSharding(mesh, spec)
    return fn, args, (sh, sh), flops


def run_cell(kind: str, mesh_name: str = "pod1") -> dict:
    multi_pod = mesh_name == "pod2"
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = 512 if multi_pod else 256
    t0 = time.time()
    result = {"arch": f"fft:{kind}", "shape": "-", "mesh": mesh_name,
              "chips": chips, "status": "ok"}
    try:
        fn, args, in_sh, mf = build(kind, mesh)
        with jax.set_mesh(mesh):
            lowered = jax.jit(fn, in_shardings=in_sh).lower(*args)
            compiled = lowered.compile()
        result["memory"] = rl.memory_report(compiled)
        costs = rl.raw_costs(compiled)
        # shard_map collectives are explicit ops in the *pre-optimization*
        # HLO; the CPU backend rewrites them to local shuffles during
        # optimization, so parse the lowered module for wire bytes.
        coll = rl.collective_wire_bytes(lowered.as_text(dialect="hlo"))
        cell = rl.CellCost(flops=costs["flops"], bytes_hbm=costs["bytes"],
                           coll_bytes=coll.get("total", 0.0),
                           coll_by_kind=coll)
        result["roofline"] = cell.to_dict()
        result["roofline"]["model_flops_per_chip"] = mf / chips
        result["roofline"]["useful_ratio"] = (
            mf / chips / cell.flops if cell.flops else 0.0)
        result["roofline"]["trips"] = 1
    except Exception as e:  # noqa: BLE001
        result["status"] = "error"
        result["error"] = f"{type(e).__name__}: {e}"
        result["traceback"] = traceback.format_exc()[-3000:]
    result["compile_seconds"] = round(time.time() - t0, 1)
    return result


CELLS = ["slab2d-16384", "slab2d-16384-overlap4", "pencil2d-16384",
         "pencil3d-1024", "pencil3d-1024-bf16", "pencil3d-1024-dcnwire",
         "r2c3d-slab3d-1024", "fig2-chain-8192", "fig2-r2c-8192"]


def main():
    ap = argparse.ArgumentParser(
        description="Dry-run + roofline for the distributed FFT on the "
                    "production mesh (see module docstring for what "
                    "each cell exercises).")
    ap.add_argument("--cell", default=None,
                    help="run ONE cell instead of the full grid; known: "
                         + ", ".join(CELLS))
    ap.add_argument("--mesh", default="pod1", choices=["pod1", "pod2"],
                    help="pod1 = 16x16 single pod (256 chips), "
                         "pod2 = 2x16x16 (512 chips)")
    args = ap.parse_args()
    RESULTS.mkdir(parents=True, exist_ok=True)
    cells = [args.cell] if args.cell else CELLS
    for kind in cells:
        r = run_cell(kind, args.mesh)
        name = f"fft_{kind}__{args.mesh}.json"
        (RESULTS / name).write_text(json.dumps(r, indent=2, default=str))
        rf = r.get("roofline", {})
        print(f"[{r['status']:5s}] fft:{kind:24s} {args.mesh} "
              f"t_comp={rf.get('t_compute_s', 0)*1e3:8.3f}ms "
              f"t_mem={rf.get('t_memory_s', 0)*1e3:8.3f}ms "
              f"t_coll={rf.get('t_collective_s', 0)*1e3:8.3f}ms "
              f"dom={rf.get('dominant', '-')}", flush=True)
        if r["status"] == "error":
            print("   ", r["error"][:200])


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
