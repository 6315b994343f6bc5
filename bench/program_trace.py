"""The program's own names in a profiler trace.

The program names its work (``docs/architecture.md``, "Tracing"):
device scopes (``jax.named_scope``: ``plan.r2c``, ``1.AllToAll``,
``insitu.1.bandpass``, ``solver.step``, ...) reach each operation's
``tf_op`` path, and host spans (``TraceAnnotation``: ``insitu.d2h``,
``solver.gather``, ...) carry arguments such as ``step`` and
``bytes``. ``bench/trace.py`` reads neither; this module reads both
from the same ``.xplane.pb``, beside it:

* ``load`` joins each device operation of ``bench.trace.load`` to its
  ``tf_op`` and ``hlo_category`` (the file's event metadata, read by
  ``bench/xplane.py``) and keeps the program's host spans;
* ``reduce`` gives, over the traced ``window`` and on the busiest
  device, as ``bench.trace.reduce`` does:

  - ``scopes``: busy time per scope component (an operation counts in
    every scope it is nested in), its part under a plan
    (``in_plan_s``) and its split by ``hlo_category``. An operation
    XLA added without a name-stack path (a layout copy, a pad, a
    parameter's relayout) counts where the next operation on the
    device does, usually its consumer; operations under none of the
    program's scopes count under ``(unscoped)``;
  - ``program_spans``: each program span's time in the window, its
    count and the sums of its numeric arguments (``step`` is an
    identifier, not summed);
  - ``program_gaps``: the device's idle time named instant by instant
    by the open program span that started last, where a producer that
    only waits on the pipeline (``insitu.submit``) yields to any other
    open span, so a blocked producer does not hide what its worker did.

From the root of a checkout,

    python -m bench.program_trace <trace dir or .xplane.pb>

prints ``bench.trace.reduce``'s object with these three keys added.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
from typing import Dict, List, Optional, Tuple

from bench import trace as bench_trace
from bench import xplane

# the program's own scopes and host spans start with these
PREFIXES = ("insitu.", "solver.", "plan.")
WAITS = ("insitu.submit",)       # program spans that only wait
UNSCOPED = "(unscoped)"

OpInfo = Tuple[str, str]                 # (tf_op, hlo_category)
Span = Tuple[str, int, int, dict]        # (name, start_ns, end_ns, args)


@dataclasses.dataclass
class Names:
    op_info: Dict[str, List[OpInfo]]     # device plane → one per op of
    spans: List[Span]                    # bench.trace.load; host spans


def _op_info(events) -> Dict[str, OpInfo]:
    """Operation name → ``(tf_op, hlo_category)`` from a device plane's
    event metadata. The name is the operation's whole HLO text, shapes
    and operands included, so two programs share one only for the same
    instruction."""
    return {ev.name: (str(ev.stats.get("tf_op", "")),
                      str(ev.stats.get("hlo_category", "")))
            for ev in events.values()
            if "tf_op" in ev.stats or "hlo_category" in ev.stats}


def load(path) -> Names:
    """Each device operation's ``(tf_op, hlo_category)``, in the order
    of ``bench.trace.load(path).ops``, and the program's host spans."""
    from jax.profiler import ProfileData
    meta = xplane.read(path)
    op_info: Dict[str, List[OpInfo]] = {}
    spans: List[Span] = []
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/device:"):
            info = _op_info(meta.get(plane.name, {}))
            for line in plane.lines:
                if line.name == bench_trace.OPS_LINE:
                    op_info.setdefault(plane.name, []).extend(
                        info.get(e.name, ("", "")) for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend(
                    (e.name, int(e.start_ns),
                     int(e.start_ns + e.duration_ns), dict(e.stats))
                    for e in line.events if e.name.startswith(PREFIXES))
    return Names(op_info=op_info, spans=spans)


def scope_components(tf_op: str) -> List[str]:
    """The scopes an operation runs in, outermost first:
    ``jit(run)/insitu.0.fft/jit(fn)/plan.r2c/0.LocalRFFT/dot_general:``
    → ``[jit(run), insitu.0.fft, jit(fn), plan.r2c, 0.LocalRFFT]``.
    A fused operation may carry several names (``a/mul;b/add``): its
    first names it."""
    path = tf_op.split(";", 1)[0].split(":", 1)[0]
    return [c for c in path.split("/")[:-1] if c]


def _scopes(ops, info: List[OpInfo], lo: int, hi: int) -> dict:
    order = sorted(range(len(ops)), key=lambda k: ops[k][1])
    comps_of: Dict[int, List[str]] = {}
    after: List[str] = []
    for k in reversed(order):
        own = scope_components(info[k][0] if k < len(info) else "")
        comps_of[k] = own or after
        after = comps_of[k]
    groups: Dict[str, dict] = {}
    for k, (_, s, e) in enumerate(ops):
        if e <= lo or s >= hi:
            continue
        category = (info[k][1] if k < len(info) else "") or "unknown"
        comps = list(comps_of[k])
        in_plan = any(c.startswith("plan.") for c in comps)
        if not any(c.startswith(PREFIXES) for c in comps):
            comps.append(UNSCOPED)
        for c in set(comps):
            g = groups.setdefault(c, {"all": [], "plan": [], "cat": {}})
            g["all"].append((s, e))
            g["cat"].setdefault(category, []).append((s, e))
            if in_plan:
                g["plan"].append((s, e))

    def busy(iv):
        return sum(e - s for s, e in bench_trace.merge(iv, lo, hi)) * 1e-9

    return {c: {"busy_s": busy(g["all"]), "in_plan_s": busy(g["plan"]),
                "hlo_category": {k: busy(v) for k, v in g["cat"].items()}}
            for c, g in groups.items()}


def _program_spans(spans: List[Span], lo: int, hi: int) -> dict:
    out: Dict[str, dict] = {}
    for name, s, e, args in spans:
        if e < lo or s > hi:
            continue
        d = out.setdefault(name, {"s": 0.0, "n": 0, "args": {}})
        d["s"] += max(0, min(e, hi) - max(s, lo)) * 1e-9
        d["n"] += 1
        for k, v in args.items():
            if k != "step" and isinstance(v, (int, float)):
                d["args"][k] = d["args"].get(k, 0) + v
    return out


def span_segments(spans: List[Span]) -> List[Tuple[int, int, str]]:
    """Sorted disjoint ``(start, end, name)``: at each instant the open
    program span that started last, a ``WAITS`` span yielding to any
    other."""
    n = len(spans)
    bounds = sorted({t for _, s, e, _ in spans for t in (s, e)})
    by_start = sorted(range(n), key=lambda k: spans[k][1])
    by_end = sorted(range(n), key=lambda k: spans[k][2])
    rank = lambda k: (spans[k][0] not in WAITS, spans[k][1])
    open_, si, ei, segs = set(), 0, 0, []
    for a, b in zip(bounds, bounds[1:]):
        while si < n and spans[by_start[si]][1] <= a:
            open_.add(by_start[si])
            si += 1
        while ei < n and spans[by_end[ei]][2] <= a:
            open_.discard(by_end[ei])
            ei += 1
        if open_:
            segs.append((a, b, spans[max(open_, key=rank)][0]))
    return segs


def _program_gaps(gaps, spans: List[Span]) -> Dict[str, float]:
    segs = span_segments(spans)
    named: Dict[str, float] = {}
    j = 0
    for g0, g1 in gaps:
        while j < len(segs) and segs[j][1] <= g0:
            j += 1
        left, k = g1 - g0, j
        while k < len(segs) and segs[k][0] < g1:
            ov = max(0, min(g1, segs[k][1]) - max(g0, segs[k][0]))
            if ov > 0:
                named[segs[k][2]] = named.get(segs[k][2], 0.0) + ov * 1e-9
                left -= ov
            k += 1
        if left > 0:
            named["none"] = named.get("none", 0.0) + left * 1e-9
    return named


def reduce(trace: bench_trace.Trace, names: Names) -> Optional[dict]:
    """``scopes``, ``program_spans`` and ``program_gaps`` (the module
    docstring) over the trace's ``window``; None where
    ``bench.trace.reduce`` gives None."""
    windows = [(s, e) for n, s, e in trace.spans if n == "window"]
    if not windows or not any(trace.ops.values()):
        return None
    lo, hi = min(s for s, _ in windows), max(e for _, e in windows)
    merged = {dev: bench_trace.merge([(s, e) for _, s, e in ops], lo, hi)
              for dev, ops in trace.ops.items()}
    busiest = max(merged, key=lambda d: sum(e - s for s, e in merged[d]))
    edges = [lo] + [x for iv in merged[busiest] for x in iv] + [hi]
    idle = [(g0, g1) for g0, g1 in zip(edges[::2], edges[1::2]) if g1 > g0]
    gaps = _program_gaps(idle, names.spans)
    return {"scopes": _scopes(trace.ops[busiest],
                              names.op_info.get(busiest, []), lo, hi),
            "program_spans": _program_spans(names.spans, lo, hi),
            "program_gaps": [[k, v] for k, v in
                             sorted(gaps.items(), key=lambda kv: -kv[1])]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("trace", help="a trace directory or an .xplane.pb")
    args = ap.parse_args(argv)
    path = (args.trace if args.trace.endswith(".xplane.pb")
            else bench_trace.find_xplane(args.trace))
    trace = bench_trace.load(path)
    out = bench_trace.reduce(trace)
    if out is not None:
        out.update(reduce(trace, load(path)))
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
