"""The program's names in a trace (``bench/program_trace.py``,
``bench/xplane.py``), on synthetic events and on recorded chip traces."""
import importlib.util
import json
from pathlib import Path

import pytest

from bench import program_trace as pt
from bench import trace as tr
from bench import xplane

MS = 1_000_000
DATA = Path(__file__).parent / "data"
RECORDED = DATA / "trace128.xplane.pb"
PINNED = DATA / "trace128.reduce.json"
SCOPED = DATA / "trace128_scoped.xplane.pb"


def _same(got, want):
    """Equal, floats to rounding (JSON keeps 17 digits)."""
    if isinstance(want, float):
        return got == pytest.approx(want, rel=1e-12, abs=1e-15)
    if isinstance(want, dict):
        return set(got) == set(want) and all(_same(got[k], want[k])
                                             for k in want)
    if isinstance(want, list):
        return len(got) == len(want) and all(map(_same, got, want))
    return got == want


def test_recorded_reduce_is_pinned():
    """``bench.trace.reduce`` on the recorded trace, key by key, as it
    read before the program named its work."""
    want = json.loads(PINNED.read_text())
    got = tr.reduce(tr.load(RECORDED))
    assert set(got) == set(want)
    for key in want:
        assert _same(got[key], want[key]), key


def _trace(spans=()):
    ops = {"/device:TPU:0": [("fusion.1", 10 * MS, 20 * MS),
                             ("fusion.2", 15 * MS, 25 * MS),  # overlaps
                             ("fusion.3", 30 * MS, 40 * MS),
                             ("copy.4", 40 * MS, 45 * MS),
                             ("fusion.5", 50 * MS, 60 * MS),
                             ("fusion.7", 70 * MS, 75 * MS),
                             ("fusion.6", 95 * MS, 130 * MS)],  # clipped
           "/device:TPU:1": [("fusion.1", 10 * MS, 12 * MS)]}
    fft = "jit(run)/insitu.0.fft/jit(fn)/plan.r2c/"
    info = {"/device:TPU:0": [
        (fft + "0.LocalRFFT/dot_general:", "convolution fusion"),
        (fft + "1.LocalFFT/transpose:", "data formatting"),
        ("jit(run)/insitu.1.bandpass/mul:", "loop fusion"),
        # XLA's own copy: no path, it counts where the next op does
        ("", "data formatting"),
        # a fusion that carries two names takes its first
        ("jit(run)/insitu.2.fft/jit(fn)/plan.c2r/0.LocalFFT/dot_general;"
         "jit(run)/insitu.1.bandpass/mul", "convolution fusion"),
        ("jit(run)/convert_element_type:", "loop fusion"),
        ("jit(run)/insitu.2.fft/jit(fn)/plan.c2r/3.LocalIRFFT/slice:",
         "")]}
    return (tr.Trace(ops=ops, spans=[("window", 0, 100 * MS)]),
            pt.Names(op_info=info, spans=list(spans)))


def test_scopes_nest_and_count_overlapping_ops_once():
    s = pt.reduce(*_trace())["scopes"]
    ms = lambda v: pytest.approx(v * 1e-3)
    # fusion.1 and fusion.2 overlap: 10–25 ms once, in each enclosing scope
    for scope in ("plan.r2c", "insitu.0.fft"):
        assert s[scope]["busy_s"] == ms(15)
        assert s[scope]["in_plan_s"] == ms(15)
        assert s[scope]["hlo_category"] == {
            "convolution fusion": ms(10), "data formatting": ms(10)}
    assert s["0.LocalRFFT"]["busy_s"] == ms(10)
    assert s["1.LocalFFT"]["busy_s"] == ms(10)
    # 40–45 (the copy before it), 50–60 and 95–100 (clipped to the window)
    assert s["plan.c2r"]["busy_s"] == ms(20)
    assert s["plan.c2r"]["hlo_category"] == {
        "data formatting": ms(5), "convolution fusion": ms(10),
        "unknown": ms(5)}
    assert s["insitu.1.bandpass"] == {
        "busy_s": ms(10), "in_plan_s": 0.0,
        "hlo_category": {"loop fusion": ms(10)}}
    assert s["jit(run)"]["busy_s"] == ms(50)
    assert s["jit(run)"]["in_plan_s"] == ms(35)
    assert "jit(fn)" in s and "dot_general" not in s


def test_ops_under_no_program_scope_count_as_unscoped():
    s = pt.reduce(*_trace())["scopes"]
    assert s[pt.UNSCOPED] == {"busy_s": pytest.approx(0.005),
                              "in_plan_s": 0.0,
                              "hlo_category": {
                                  "loop fusion": pytest.approx(0.005)}}
    # a program that names nothing: all its time is unscoped
    t, _ = _trace()
    r = pt.reduce(t, pt.Names(op_info={}, spans=[]))
    assert r["scopes"] == {pt.UNSCOPED: {
        "busy_s": pytest.approx(0.05), "in_plan_s": 0.0,
        "hlo_category": {"unknown": pytest.approx(0.05)}}}
    assert r["program_spans"] == {}
    assert r["program_gaps"] == [["none", pytest.approx(0.05)]]


def test_nothing_to_read_gives_none():
    names = pt.Names(op_info={}, spans=[])
    assert pt.reduce(tr.Trace(ops={}, spans=[("window", 0, 1)]),
                     names) is None
    assert pt.reduce(tr.Trace(ops={"d": [("x", 0, 1)]}, spans=[]),
                     names) is None


def test_scope_components_drop_the_primitive_and_the_op_type():
    assert pt.scope_components(
        "jit(run)/insitu.0.fft/jit(fn)/plan.r2c/2.AllToAll/all_to_all:") \
        == ["jit(run)", "insitu.0.fft", "jit(fn)", "plan.r2c", "2.AllToAll"]
    assert pt.scope_components("d[0]['field']:") == []
    assert pt.scope_components("") == []


PROGRAM_SPANS = [
    ("insitu.d2h", 0, 8 * MS, {"step": 1, "bytes": 100}),
    # the worker's image started before the producer blocked
    ("insitu.host.visualize", 8 * MS, 30 * MS, {"step": 1}),
    ("insitu.submit", 12 * MS, 48 * MS, {"step": 3}),
    ("solver.gather", 62 * MS, 65 * MS, {"step": 7, "bytes": 50}),
    ("insitu.d2h", 120 * MS, 130 * MS, {"step": 2, "bytes": 100}),
]


def test_program_gaps_name_idle_time_by_the_working_span():
    r = pt.reduce(*_trace(PROGRAM_SPANS))
    gaps = dict((k, v) for k, v in r["program_gaps"])
    # idle: 0–10, 25–30, 45–50, 60–70, 75–95 ms
    assert gaps == {"insitu.d2h": pytest.approx(0.008),
                    # 8–10 and 25–30: the image, though the producer
                    # waits from 12 ms
                    "insitu.host.visualize": pytest.approx(0.007),
                    "insitu.submit": pytest.approx(0.003),   # 45–48
                    "solver.gather": pytest.approx(0.003),
                    # 48–50, 60–62, 65–70, 75–95
                    "none": pytest.approx(0.029)}
    t, _ = _trace()
    want = tr.reduce(t)
    idle = want["window_s"] - want["busy_s"][want["busiest"]]
    assert sum(gaps.values()) == pytest.approx(idle)
    assert sum(v for _, v in want["idle_gaps"]) == pytest.approx(idle)


def test_program_spans_sum_time_count_and_arguments_in_the_window():
    spans = pt.reduce(*_trace(PROGRAM_SPANS))["program_spans"]
    assert spans["insitu.d2h"] == {"s": pytest.approx(0.008), "n": 1,
                                   "args": {"bytes": 100}}
    assert spans["insitu.submit"]["args"] == {}      # step: an identifier
    assert spans["solver.gather"]["args"] == {"bytes": 50}


def test_span_segments_of_overlapping_and_nested_spans():
    segs = pt.span_segments([("a", 0, 10, {}), ("b", 2, 4, {}),
                             ("insitu.submit", 3, 12, {}),
                             ("c", 11, 11, {})])
    assert segs == [(0, 2, "a"), (2, 3, "b"), (3, 4, "b"), (4, 10, "a"),
                    (10, 11, "insitu.submit"), (11, 12, "insitu.submit")]


def test_recorded_ops_join_their_tf_op_and_category():
    t, names = tr.load(RECORDED), pt.load(RECORDED)
    ops, info = t.ops["/device:TPU:0"], names.op_info["/device:TPU:0"]
    assert len(info) == len(ops)
    # every op has its category; XLA's own copies and pads carry no tf_op
    assert all(c for _, c in info)
    assert sum(1 for tf_op, _ in info if tf_op.startswith("jit(run)/")) \
        > 0.8 * len(ops)
    assert {"convolution fusion", "data formatting"} <= {c for _, c in info}


def test_recorded_scoped_chip_trace():
    """A 128^3 reduce-chain window recorded on one TPU v5e chip with the
    program's scopes."""
    t = tr.load(SCOPED)
    r = pt.reduce(t, pt.load(SCOPED))
    s = r["scopes"]
    busy = tr.reduce(t)["busy_s"]["/device:TPU:0"]
    for scope in ("plan.r2c", "plan.c2r", "insitu.1.bandpass"):
        assert s[scope]["busy_s"] > 0, scope
    assert s.get(pt.UNSCOPED, {"busy_s": 0.0})["busy_s"] < 0.05 * busy
    assert "insitu.dispatch" in r["program_spans"]


def test_command_line_prints_both_reductions(capsys):
    assert pt.main([str(RECORDED)]) == 0
    out = json.loads(capsys.readouterr().out)
    assert {"busy_s", "idle_gaps", "scopes", "program_gaps"} <= set(out)


def _xplane_pb2():
    pytest.importorskip("google.protobuf")
    spec = importlib.util.find_spec("tensorflow")
    if spec is None or spec.origin is None:
        pytest.skip("tensorflow is not installed")
    path = (Path(spec.origin).parent / "tsl" / "profiler" / "protobuf"
            / "xplane_pb2.py")
    if not path.is_file():
        pytest.skip("this tensorflow has no xplane_pb2")
    mod_spec = importlib.util.spec_from_file_location("xplane_pb2", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def test_wire_reader_matches_the_protobuf_module():
    pb2 = _xplane_pb2()
    space = pb2.XSpace()
    space.ParseFromString(RECORDED.read_bytes())
    mine = xplane.read(RECORDED)
    assert set(mine) == {p.name for p in space.planes}
    n = 0
    for plane in space.planes:
        names = {k: v.name for k, v in plane.stat_metadata.items()}
        assert set(mine[plane.name]) == set(plane.event_metadata)
        for key, meta in plane.event_metadata.items():
            got = mine[plane.name][key]
            assert (got.name, got.display_name) == (meta.name,
                                                    meta.display_name)
            want = {}
            for st in meta.stats:
                kind = st.WhichOneof("value")
                value = getattr(st, kind) if kind else None
                if kind == "ref_value":
                    value = names.get(value, "")
                want[names.get(st.metadata_id, str(st.metadata_id))] = value
            assert got.stats == want
            n += 1
    assert n > 200


def _viz_run(pipeline):
    import types
    return types.SimpleNamespace(pipeline=pipeline)


@pytest.mark.parametrize("name,want", [("d2h_ms.viz", 400.0),
                                       ("d2h_mb.viz", 1073.741824)])
def test_host_tail_copy_metrics_read_the_pipeline_counters(name, want):
    from bench import run
    reader = run._module(run.BENCH / "metrics" / f"{name}.py")
    rep = {"completed": 2, "wait_s": 0.9, "d2h_s": 0.8,
           "d2h_bytes": 2_147_483_648, "host_timings_s": {}}
    assert reader.read(_viz_run(rep)) == pytest.approx(want)
    # the parent's pipeline counts no copies; the reduce cell has none
    old = {k: v for k, v in rep.items() if not k.startswith("d2h")}
    assert reader.read(_viz_run(old)) is None
    assert reader.read(_viz_run(None)) is None


def test_image_metric_reads_the_pipeline_visualize_timing():
    from bench import run
    reader = run._module(run.BENCH / "metrics" / "image_ms.viz.py")
    rep = {"completed": 4, "wait_s": 1.6,
           "host_timings_s": {"visualize": 0.5, "writer": 0.1}}
    assert reader.read(_viz_run(rep)) == pytest.approx(125.0)
    # the reduce cell has no image endpoint
    assert reader.read(_viz_run({**rep, "host_timings_s": {}})) is None
    assert reader.read(_viz_run({**rep, "completed": 0})) is None
    assert reader.read(_viz_run(None)) is None
