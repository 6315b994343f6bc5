#!/usr/bin/env python3
"""Run one benchmark cell once on the chip.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``'s ``workloads``; its files
are found by name: ``bench/configs/<config>.json`` (sizes and mesh),
``bench/traffic/<traffic>.json`` (the mix, and the driver
``bench/drivers/<driver>.py`` that runs it and counts its work), ``bench/workloads/<cell>.json`` (its
fields in flight and its correctness limits) and one reader
``bench/metrics/<metric>.py`` per metric. A run sets up (build, make the
inputs from the seed on the device, warm every shape it uses), measures
for ``--seconds``, reads the device's peak memory, frees the program's
state and compares what the window produced with the plain reference.

With ``--trace 0`` the result carries the cell's end-to-end metrics;
with ``--trace 1`` the window runs under the profiler and the result
carries its per-layer metrics, the device's busy time and a breakdown.
The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown``),
and last ``checks``, each compared number beside its limit; the same
numbers close standard error. Without a TPU, or with fewer chips than
the cell asks for, it exits 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

class NoChip(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


def _load_json(path: Path) -> dict:
    return json.loads(path.read_text())


def _module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _applies(entry: dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def cell_spec(name: str) -> dict:
    """Everything the files say about one cell."""
    bench = _load_json(ROOT / "BENCHMARK.json")
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    return {
        "entry": entry,
        "config": _load_json(BENCH / "configs" / f"{entry['config']}.json"),
        "traffic": _load_json(BENCH / "traffic" / f"{entry['traffic']}.json"),
        "cell": _load_json(BENCH / "workloads" / f"{name}.json"),
        "end_to_end": [m for m in bench["end_to_end"] if _applies(m, name)],
        "per_layer": [m for m in bench["per_layer"] if _applies(m, name)],
    }


def enable_compile_cache() -> str:
    """The checkout's persistent compilation cache
    (``repro.launch.compile_cache``), holding every program however
    quick its compile or small its entry."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache as enable
    path = enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def driver_module(traffic: dict):
    """The driver that runs a traffic mix: ``bench/drivers/<driver>.py``,
    with its ``Cell``, ``work(shape)`` and ``control_numbers``."""
    return _module(BENCH / "drivers" / f"{traffic['driver']}.py")


def _devices(chips: int, require_tpu: bool):
    import jax
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise NoChip(f"JAX finds no TPU (platform {devices[0].platform!r})")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips; JAX finds "
                     f"{len(devices)} {devices[0].platform} device(s)")
    return devices[:chips]


def _peak_bytes(devices):
    stats = [d.memory_stats() for d in devices]
    if any(s is None or "peak_bytes_in_use" not in s for s in stats):
        return None
    return max(int(s["peak_bytes_in_use"]) for s in stats)


class _CompileCounter:
    """Counts programs lowered (so compiled or fetched from the
    persistent cache) while it is armed."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        from jax import monitoring
        self.armed, self.count = False, 0
        monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, key, _secs, **_kw):
        if self.armed and key == self.EVENT:
            self.count += 1


def _span(tracing: bool):
    import jax
    if tracing:
        return jax.profiler.TraceAnnotation
    return lambda _name: contextlib.nullcontext()


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             require_tpu: bool = True, shape=None) -> dict:
    """One run of cell ``name``; returns the result line's object.
    ``shape`` replaces the configuration's grid (the tests' tiny
    rehearsals); ``require_tpu=False`` lets a CPU stand in."""
    spec = cell_spec(name)
    config, traffic, cell = spec["config"], spec["traffic"], spec["cell"]
    devices = _devices(spec["entry"]["chips"], require_tpu)
    import jax
    from bench import trace as trace_mod
    from bench import work

    driver = driver_module(traffic)
    counter = _CompileCounter()
    span = _span(trace)
    kind = devices[0].device_kind
    peak_table = work.peaks(kind) if require_tpu else None
    runner = driver.Cell(config, traffic, cell, seed, devices, shape)
    runner.setup(span)
    setup_s = time.perf_counter() - T_START

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        counter.armed = True
        if trace:
            jax.profiler.start_trace(trace_dir)
        try:
            window = runner.window(seconds, span)
        finally:
            if trace:
                jax.profiler.stop_trace()
            counter.armed = False
        reduced = (trace_mod.reduce(trace_mod.load(
            trace_mod.find_xplane(trace_dir))) if trace else None)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    peak_bytes = _peak_bytes(devices)
    t_check = time.perf_counter()
    numbers, failed = runner.check(cell["limits"])
    print(json.dumps({"cell": name, "compiles_in_window": counter.count,
                      "setup_s": setup_s, "setup_phases_s": runner.phases,
                      "check_s": time.perf_counter() - t_check,
                      **{k: window[k] for k in
                         ("attempted", "completed", "window_s")}}),
          flush=True)
    checks = {k: {"value": v, "limit": cell["limits"][k]}
              for k, v in numbers.items()}
    correct = failed == 0 and all(c["value"] <= c["limit"]
                                  for c in checks.values())

    shape_now = tuple(shape or config["grid"])
    # what the metric readers see of the run
    run = types.SimpleNamespace(
        cell=name, config=config, traffic=traffic, unit=runner.unit,
        chips=len(devices), setup_s=setup_s, peak_bytes=peak_bytes,
        work=driver.work(shape_now), peak=peak_table,
        trace=reduced, **window)
    wanted = spec["per_layer"] if trace else spec["end_to_end"]
    metrics = {}
    for m in wanted:
        value = _module(BENCH / "metrics" / f"{m['name']}.py").read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": peak_bytes}
    result = {"correct": bool(correct), "attempted": window["attempted"],
              "failed": int(failed), "metrics": metrics, "device": device}
    if reduced is not None:
        busy = reduced["busy_s"]
        device["busy_s"] = sum(busy.values()) / len(busy)
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["top_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace))
    except NoChip as err:
        print(f"bench: {err}", file=sys.stderr)
        return 2
    for k, c in result["checks"].items():
        print(f"check {k} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
