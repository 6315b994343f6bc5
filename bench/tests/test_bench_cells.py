"""Each cell's code path at a tiny grid on the CPU, and the result line.

The harness's look for a chip is skipped (``require_tpu=False``) and
the grid is replaced by a tiny one; everything else is a whole run.
"""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench import run

ROOT = Path(__file__).resolve().parents[2]
SEED = 2**31 + 11
TINY = {"chain3d-512.viz": (16, 16, 16),
        "chain3d-512.reduce": (16, 16, 16),
        "ns2d-8192.step": (32, 32)}
HEAD = ["correct", "attempted", "failed", "metrics", "device"]


def _names(kind, cell):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    return {m["name"] for m in bench[kind]
            if cell in m.get("workloads", [cell])}


def _check_line(result, cell, trace):
    keys = list(result)
    assert keys[:5] == HEAD and keys[-1] == "checks"
    assert set(keys) <= set(HEAD) | {"breakdown", "checks"}
    json.loads(json.dumps(result))
    assert result["attempted"] > 0
    assert result["device"]["count"] == 1
    kind = "per_layer" if trace else "end_to_end"
    assert set(result["metrics"]) <= _names(kind, cell)
    for m in result["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    for c in result["checks"].values():
        assert set(c) == {"value", "limit"}


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", sorted(TINY))
def test_cell_path_at_a_tiny_grid(cell, trace):
    result = run.run_cell(cell, SEED, 0.3, bool(trace), require_tpu=False,
                          shape=TINY[cell])
    _check_line(result, cell, trace)
    if not trace:
        assert "setup_s" in result["metrics"]
        assert len(result["metrics"]) >= 2


def _cpu_env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.pathsep.join([str(ROOT), str(ROOT / "src")])
    return env


def test_no_chip_exits_2_and_prints_no_result():
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "chain3d-512.reduce",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        env=_cpu_env(), cwd=ROOT, capture_output=True, text=True, timeout=300)
    assert out.returncode == 2
    assert out.stdout == ""


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = _cpu_env()
    env["PYTHONPATH"] = ""
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "chain3d-512.reduce",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        env=env, cwd=tmp_path, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert out.stdout == ""


@pytest.mark.parametrize("traffic", sorted(
    p.stem for p in (ROOT / "bench" / "traffic").glob("*.json")))
def test_traffic_driver_has_cell_work_and_control(traffic):
    """A traffic mix names its driver; the harness and the control find
    everything of that kind of work on the driver, by that name."""
    spec = json.loads((ROOT / "bench" / "traffic" / f"{traffic}.json")
                      .read_text())
    driver = run.driver_module(spec)
    assert callable(driver.Cell) and callable(driver.control_numbers)
    assert driver.work((8, 8))["bytes"] > 0


def test_every_metric_has_its_reader():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()
