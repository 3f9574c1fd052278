"""The reduction from a profiler trace to the per-layer metrics, on
hand-made events and on a small trace recorded on a TPU v5e
(``bench/testdata``, made by ``bench/capture_testdata.py``)."""
import json
import os

import pytest

from bench import run
from bench.trace_reduce import TraceSummary, merge, read_xspace

TESTDATA = os.path.join(run.BENCH, "testdata")
TRACE = os.path.join(TESTDATA, "fixpoint_small.xplane.pb.gz")
PEAK = run.load_json(run.BENCH, "peaks.json")["devices"]["TPU v5 lite"]


def metric(name):
    return run.load_module(os.path.join(run.BENCH, "metrics",
                                        name + ".py"))


def test_merge_unions_overlaps():
    assert merge([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]


def hand_made():
    ops = {0: [("a", 10, 20), ("b", 15, 30), ("c", 50, 60),
               ("d", 0, 5), ("e", 95, 120)],
           1: [("a", 10, 40), ("c", 70, 100)]}
    spans = [("bench.window", 10, 100), ("bench.fixpoint", 10, 45),
             ("bench.query", 30, 52), ("bench.fixpoint", 52, 100)]
    return TraceSummary(ops, spans)


def test_busy_idle_and_ops_on_hand_made_events():
    t = hand_made()
    assert t.window_s == pytest.approx(90e-9)
    # chip 0: [10,30] + [50,60] + [95,100]; chip 1: [10,40] + [70,100]
    assert t.busy_s_of(0) == pytest.approx(35e-9)
    assert t.busy_s_of(1) == pytest.approx(60e-9)
    assert t.busy_s == pytest.approx(47.5e-9)
    gaps = t.idle_gaps()
    assert [round(s * 1e9) for _, s in gaps] == [20, 35]
    assert [n for n, _ in gaps] == ["bench.query", "bench.fixpoint"]
    assert t.busy_s_within("bench.fixpoint") == pytest.approx(
        (20 + 8 + 5 + 30 + 30) * 1e-9 / 2)
    ops = t.op_seconds(0)
    assert ops["b"] == pytest.approx(15e-9) and "d" not in ops
    b = t.breakdown()
    assert b["device_ops"][0][0] == "a" and len(b["idle_gaps"]) == 2


def test_a_trace_needs_one_window():
    with pytest.raises(ValueError):
        TraceSummary({0: []}, [])


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(TESTDATA, "fixpoint_small.json")) as f:
        meta = json.load(f)
    return read_xspace(TRACE, 1), meta


def test_recorded_trace_busy_gaps_and_ops(recorded):
    t, meta = recorded
    assert meta["kind"] == "TPU v5 lite" and t.devices == [0]
    (w0, w1), = [(a, b) for n, a, b in meta["spans"] if n == "bench.window"]
    assert t.window_s == pytest.approx(w1 - w0, rel=0.05)
    assert 0 < t.busy_s <= t.window_s
    gaps = t.idle_gaps()
    assert sum(s for _, s in gaps) + t.busy_s == pytest.approx(t.window_s)
    ops = t.op_seconds()
    assert sum(ops.values()) >= t.busy_s * (1 - 1e-9)
    assert not any(label.split()[1] in ("while", "conditional", "call")
                   for label in ops)
    b = t.breakdown()
    assert 0 < len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert b["device_ops"][0][1] >= b["device_ops"][-1][1]
    assert {n for n, _ in gaps} <= {"bench.fixpoint", "bench.window"}
    assert t.busy_s_within("bench.fixpoint") <= t.busy_s


def recorded_ctx(recorded):
    t, meta = recorded
    workload, config = run.load_cell("dbpedia-pagerank.delta")
    return dict(trace=t, stats=meta["stats"], spans=meta["spans"],
                peak=PEAK, workload=workload, config=config)


def test_fixpoint_metrics_on_the_recorded_trace(recorded):
    ctx = recorded_ctx(recorded)
    strata = sum(c["strata"] for c in ctx["stats"]["calls"])
    ms = metric("stratum_ms.batch").reduce(ctx)
    assert ms == pytest.approx(
        recorded[0].busy_s_within("bench.fixpoint") * 1e3 / strata)
    roof = metric("loop_roofline.batch").reduce(ctx)
    assert 0 < roof <= 100
    idle = metric("device_idle.batch").reduce(ctx)
    assert 0 <= idle < 100
    assert metric("all_to_all_ms.s4").reduce(ctx) is None


def test_roofline_bytes_never_exceed_a_dense_stratum(recorded):
    roof = metric("loop_roofline.batch")
    stats = recorded[1]["stats"]
    n = stats["vertices"]
    for call in stats["calls"]:
        e = stats["edges"][call["graph"]]
        dense = roof.dense_bytes(n, e)
        for d, u in zip(call["delta_counts"], call["used_dense"]):
            assert 0 <= roof.stratum_bytes(n, e, d, u) <= dense
        assert roof.fixpoint_bytes(n, e, call) <= dense * call["strata"]
    for d in (0, 1, 10**3, 10**6, 10**9):
        assert roof.stratum_bytes(1000, 15000, d, False) <= roof.dense_bytes(
            1000, 15000)
    assert roof.stratum_bytes(1000, 15000, 10, False) == 120
    assert roof.dense_bytes(1000, 15000) == 4 * 15000 + 8 * 1000
