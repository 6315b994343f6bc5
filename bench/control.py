#!/usr/bin/env python3
"""The precision control: the plain reference put in the program's
place, computed one precision step below the configuration's, on the
cell's own inputs and sizes, and judged by the cell's own numbers and
limits. The configuration states float32 with every DFT matmul at
``HIGHEST``: the control runs the DFT matmuls in bf16×3
(``Precision.HIGH``) and the chain's energy reductions, plain float32
arithmetic, in bfloat16. Each traffic's driver (``bench/drivers/``)
computes the cell's numbers for it (``control_numbers``). Every seed
has to come out not correct.

    python bench/control.py --workload <cell> --seeds <n> [<n> ...]

Prints one JSON line per seed. The benchmark's runs never run it.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def control_checks(name, seed, *, shape=None, devices=None):
    """The cell's numbers, beside its limits, for the control on
    ``seed``."""
    import jax
    from bench.run import cell_spec, driver_module
    spec = cell_spec(name)
    config, traffic, limits = (spec["config"], spec["traffic"],
                               spec["cell"]["limits"])
    shape = tuple(shape or config["grid"])
    devices = devices or jax.devices()[: spec["entry"]["chips"]]
    numbers = driver_module(traffic).control_numbers(
        shape, seed, config, traffic, devices)
    return {k: {"value": v, "limit": limits[k]} for k, v in numbers.items()}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    from bench.run import enable_compile_cache
    enable_compile_cache()
    for seed in args.seeds:
        checks = control_checks(args.workload, seed)
        correct = all(c["value"] <= c["limit"] for c in checks.values())
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "correct": correct, "checks": checks}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
