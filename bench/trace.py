"""Profiler trace → the benchmark's layer numbers.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes: the
device operations of every accelerator plane (its "XLA Ops" line) and
the benchmark's own host spans (``TraceAnnotation`` names in
``SPANS``). ``reduce`` works on those plain lists, so it can be checked
on a synthetic event list as well as on a recorded trace:

* busy time is the union of a device's operation intervals inside the
  traced window (the host span ``window``), idle is the rest;
* ``all-to-all`` time is the summed duration of the device's
  ``all-to-all`` operations in the window, synchronous or asynchronous
  (the "Async XLA Ops" line), the latter not counted as busy;
* each idle gap is named by the benchmark span that overlaps it most
  (``none`` where the host was in none of them).
"""
from __future__ import annotations

import dataclasses
import glob
import os
from typing import Dict, List, Tuple

# the benchmark's host spans; ``window`` brackets the traced window
SPANS = ("window", "handover", "wait", "drain", "step", "monitor")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
EXCHANGE_PREFIX = "all-to-all"

Interval = Tuple[str, int, int]          # (name, start_ns, end_ns)


@dataclasses.dataclass
class Trace:
    ops: Dict[str, List[Interval]]       # device plane → its operations
    spans: List[Interval]                # benchmark host spans
    async_ops: Dict[str, List[Interval]] = dataclasses.field(
        default_factory=dict)            # device plane → async operations


def op_name(hlo: str) -> str:
    """``%fusion.17 = (f32[…]) fusion(…)`` → ``fusion.17``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def find_xplane(trace_dir) -> str:
    paths = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"{len(paths)} .xplane.pb files under "
                                f"{trace_dir}, expected one")
    return paths[0]


def load(path) -> Trace:
    """Device operations and benchmark spans of one ``.xplane.pb``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(path))
    ops: Dict[str, List[Interval]] = {}
    async_ops: Dict[str, List[Interval]] = {}
    spans: List[Interval] = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                into = {OPS_LINE: ops, ASYNC_LINE: async_ops}.get(line.name)
                if into is not None:
                    into.setdefault(plane.name, []).extend(
                        (op_name(e.name), int(e.start_ns),
                         int(e.start_ns + e.duration_ns))
                        for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name, int(e.start_ns),
                              int(e.start_ns + e.duration_ns))
                             for e in line.events if e.name in SPANS)
    return Trace(ops=ops, spans=spans, async_ops=async_ops)


def merge(intervals, lo: int, hi: int) -> List[Tuple[int, int]]:
    """The union of ``(start, end)`` intervals clipped to [lo, hi], as
    sorted disjoint intervals."""
    out: List[List[int]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _overlap(a0, a1, b0, b1) -> int:
    return max(0, min(a1, b1) - max(a0, b0))


def reduce(trace: Trace, top: int = 10) -> dict:
    """Busy and idle time, exchange time, the costliest operations and
    the idle gaps by host span, over the ``window`` span.

    Per device: ``busy_s`` and ``exchange_s``. Over the busiest device:
    ``top_ops`` ([name, seconds], most time first) and ``idle_gaps``
    ([span, seconds] summed over its gaps, most first). Returns None
    when the trace holds no window or no device operation."""
    windows = [(s, e) for n, s, e in trace.spans if n == "window"]
    if not windows or not any(trace.ops.values()):
        return None
    lo, hi = min(s for s, _ in windows), max(e for _, e in windows)
    busy, exchange, merged = {}, {}, {}
    for dev, ops in trace.ops.items():
        merged[dev] = merge([(s, e) for _, s, e in ops], lo, hi)
        busy[dev] = sum(e - s for s, e in merged[dev]) * 1e-9
        both = ops + trace.async_ops.get(dev, [])
        exchange[dev] = sum(_overlap(s, e, lo, hi) for n, s, e in both
                            if n.startswith(EXCHANGE_PREFIX)) * 1e-9
    busiest = max(busy, key=busy.get)
    per_op: Dict[str, float] = {}
    for name, s, e in trace.ops[busiest]:
        t = _overlap(s, e, lo, hi) * 1e-9
        if t > 0:
            per_op[name] = per_op.get(name, 0.0) + t
    gaps: Dict[str, float] = {}
    edges = [lo] + [x for iv in merged[busiest] for x in iv] + [hi]
    host = [sp for sp in trace.spans if sp[0] != "window"]
    for g0, g1 in zip(edges[::2], edges[1::2]):
        if g1 <= g0:
            continue
        best, name = 0, "none"
        for n, s, e in host:
            ov = _overlap(g0, g1, s, e)
            if ov > best:
                best, name = ov, n
        gaps[name] = gaps.get(name, 0.0) + (g1 - g0) * 1e-9
    rank = lambda d: [[k, v] for k, v in
                      sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    return {"window_s": (hi - lo) * 1e-9, "busy_s": busy,
            "exchange_s": exchange, "busiest": busiest,
            "top_ops": rank(per_op), "idle_gaps": rank(gaps)}
