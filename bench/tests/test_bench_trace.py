"""The trace reduction, on synthetic events and on a recorded trace."""
from pathlib import Path

import pytest

from bench import trace as tr

MS = 1_000_000
RECORDED = Path(__file__).parent / "data" / "trace128.xplane.pb"


def _trace():
    ops = {"/device:TPU:0": [("fusion.1", 10 * MS, 30 * MS),
                             ("fusion.2", 20 * MS, 40 * MS),   # overlaps
                             ("all-to-all.3", 60 * MS, 70 * MS),
                             ("copy.4", 95 * MS, 130 * MS)],   # clipped
           "/device:TPU:1": [("fusion.1", 10 * MS, 20 * MS)]}
    spans = [("window", 0, 100 * MS), ("handover", 0, 5 * MS),
             ("wait", 40 * MS, 60 * MS), ("drain", 70 * MS, 100 * MS)]
    async_ops = {"/device:TPU:1": [("all-to-all-start.5", 50 * MS, 60 * MS)]}
    return tr.Trace(ops=ops, spans=spans, async_ops=async_ops)


def test_op_name_is_the_hlo_name():
    assert tr.op_name("%fusion.17 = (f32[8]{0}) fusion(f32[8]{0} %p)") == \
        "fusion.17"


def test_merge_unions_and_clips():
    assert tr.merge([(5, 9), (0, 3), (2, 4), (8, 12)], 1, 10) == \
        [(1, 4), (5, 10)]


def test_busy_idle_exchange_and_gaps():
    r = tr.reduce(_trace())
    assert r["window_s"] == pytest.approx(0.1)
    assert r["busiest"] == "/device:TPU:0"
    # 10–40 ms, 60–70 ms and 95–100 ms inside the window
    assert r["busy_s"]["/device:TPU:0"] == pytest.approx(0.045)
    assert r["busy_s"]["/device:TPU:1"] == pytest.approx(0.010)
    assert r["exchange_s"]["/device:TPU:0"] == pytest.approx(0.010)
    # an asynchronous exchange counts as exchange, not as busy
    assert r["exchange_s"]["/device:TPU:1"] == pytest.approx(0.010)
    assert dict((k, v) for k, v in r["top_ops"]) == {
        "fusion.1": pytest.approx(0.020), "fusion.2": pytest.approx(0.020),
        "all-to-all.3": pytest.approx(0.010), "copy.4": pytest.approx(0.005)}
    gaps = dict((k, v) for k, v in r["idle_gaps"])
    # 0–10 handover (5 of 10 ms), 40–60 wait, 70–95 drain
    assert gaps == {"handover": pytest.approx(0.010),
                    "wait": pytest.approx(0.020),
                    "drain": pytest.approx(0.025)}


def test_nothing_to_read_gives_none():
    assert tr.reduce(tr.Trace(ops={}, spans=[("window", 0, 1)])) is None
    assert tr.reduce(tr.Trace(ops={"d": [("x", 0, 1)]}, spans=[])) is None


def test_recorded_chip_trace():
    """A 128^3 reduce-chain window recorded on one TPU v5e chip."""
    t = tr.load(RECORDED)
    assert list(t.ops) == ["/device:TPU:0"]
    assert {"window", "handover", "wait", "drain"} <= {s[0] for s in t.spans}
    r = tr.reduce(t)
    busy = r["busy_s"]["/device:TPU:0"]
    assert 0 < busy < r["window_s"]
    assert r["exchange_s"]["/device:TPU:0"] == 0.0
    assert sum(v for _, v in r["idle_gaps"]) == \
        pytest.approx(r["window_s"] - busy, rel=1e-6)
    assert len(r["top_ops"]) == 10
