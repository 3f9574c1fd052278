"""Device time per ``rex.*`` scope (``bench/scopes.py``) and the four
per-layer metrics that read it: the join of the compiled program's scopes
to a trace's operations, on hand-made text and events, on the loop the
CPU compiles, and on a small trace recorded on a TPU v5e with the
program's scopes (``bench/testdata/fixpoint_small_scoped.*``, made by
``bench/capture_scoped.py``)."""
import json
import os

import pytest

from bench import run, scopes
from bench.trace_reduce import TraceSummary, read_xspace
from tiny import tiny_cell

TESTDATA = os.path.join(run.BENCH, "testdata")
SCOPED = os.path.join(TESTDATA, "fixpoint_small_scoped")
LAYER_METRICS = ["emit_ms.batch", "route_ms.batch", "apply_ms.batch",
                 "select_ms.batch"]


def metric(name):
    return run.load_module(os.path.join(run.BENCH, "metrics",
                                        name + ".py"))


HLO = """HloModule jit__fixpoint, is_scheduled=true

%body.1 (p: s32[4]) -> s32[4] {
  %p = s32[4]{0} parameter(0)
  %fusion.7 = s32[4]{0} fusion(%p), kind=kLoop, calls=%f.1, metadata={op_name="jit(_fixpoint)/while/body/cond/branch_0_fun/rex.rung0/rex.emit/while/body/gather"}
  %reduce-window.3 = s32[4]{0} reduce-window(%fusion.7), window={size=4}
  ROOT %sort.2 = s32[4]{0} sort(%reduce-window.3), metadata={op_name="jit(_fixpoint)/while/body/cond/branch_0_fun/rex.rung0/rex.emit/jit(searchsorted)/jit(_fixpoint)/while/body/cond/branch_4_fun/rex.dense/rex.emit/sort"}
}

ENTRY %main.9 (a: s32[4]) -> s32[4] {
  %a = s32[4]{0} parameter(0)
  %copy.1 = s32[4]{0} copy(%a)
  %add.5 = s32[4]{0} add(%copy.1, %copy.1), metadata={op_name="jit(_fixpoint)/while/body/rex.select/add"}
  %scatter.2 = s32[4]{0} scatter(%add.5), metadata={op_name="jit(_fixpoint)/while/body/cond/branch_4_fun/rex.dense/rex.route/scatter-add"}
  ROOT %compare.4 = pred[] compare(%add.5), metadata={op_name="jit(_fixpoint)/while/cond/rex.loop/lt"}
}
"""


def test_scope_map_joins_each_instruction_to_its_scopes():
    m = scopes.scope_map(HLO)
    assert m["fusion.7 fusion/kLoop"] == ["rex.rung0", "rex.emit"]
    # No metadata: the scopes every annotated instruction of the
    # computation shares.
    assert m["reduce-window.3 reduce-window"] == ["rex.rung0", "rex.emit"]
    assert m["sort.2 sort"][-2:] == ["rex.dense", "rex.emit"]
    assert m["add.5 add"] == ["rex.select"]
    assert m["compare.4 compare"] == ["rex.loop"]
    # ENTRY's annotated instructions share no scope.
    assert "copy.1 copy" not in m
    assert scopes.scope_map(HLO.replace("rex.", "x.")) == {}


def test_layer_and_body_of_a_path():
    assert scopes.layer_of(["rex.rung0", "rex.emit"]) == "emit"
    assert scopes.layer_of(["rex.loop"]) == "select"
    assert scopes.layer_of(["rex.rung0"]) is None
    assert scopes.body_of(["rex.rung3", "rex.route", "rex.rung0",
                           "rex.route"]) == "rex.rung0+rex.rung3"
    assert scopes.body_of(["rex.select"]) == "rex.select"


def hand_made_ctx(scope_map):
    ops = {0: [("fusion.7 fusion/kLoop s32[4]", 10, 30),
               ("sort.2 sort s32[4]", 30, 40),
               ("scatter.2 scatter s32[4]", 40, 45),
               ("add.5 add s32[4]", 45, 47),
               ("compare.4 compare pred[]", 47, 48),
               ("copy.1 copy s32[4]", 48, 50),
               ("fusion.7 fusion/kLoop s32[4]", 60, 70)]}
    spans = [("bench.window", 0, 100), ("bench.fixpoint", 5, 55),
             ("bench.fixpoint", 58, 65)]
    workload, config = run.load_cell("dbpedia-pagerank.delta")
    stats = {"calls": [{"strata": 2}, {"strata": 3}]}
    return dict(trace=TraceSummary(ops, spans), stats=stats, spans=spans,
                workload=workload, config=config, scope_map=scope_map)


def test_layer_metrics_on_hand_made_events():
    ctx = hand_made_ctx(scopes.scope_map(HLO))
    ns = 1e-9 * 1e3 / 5       # one ns of busy time, in ms a stratum
    values = {n: metric(n).reduce(ctx) for n in LAYER_METRICS}
    assert values["emit_ms.batch"] == pytest.approx((20 + 10 + 5) * ns)
    assert values["route_ms.batch"] == pytest.approx(5 * ns)
    assert values["apply_ms.batch"] == pytest.approx(0)
    assert values["select_ms.batch"] == pytest.approx(3 * ns)
    split = ctx["scope_split"]
    assert split["unscoped"] == pytest.approx(2e-9)
    assert split["no_layer"] == pytest.approx(0)
    assert split["all"] == pytest.approx(45e-9)
    assert split["rex.dense+rex.rung0"] == pytest.approx(10e-9)
    stratum = metric("stratum_ms.batch").reduce(ctx)
    assert sum(values.values()) + 2 * ns == pytest.approx(stratum)


def test_layer_metrics_are_missing_without_scopes():
    """A program without the scopes (the parent of the change that added
    them) gives no value, not 0."""
    ctx = hand_made_ctx({})
    assert all(metric(n).reduce(ctx) is None for n in LAYER_METRICS)
    ctx = hand_made_ctx(scopes.scope_map(HLO))
    ctx["workload"] = dict(ctx["workload"], driver="view")
    assert all(metric(n).reduce(ctx) is None for n in LAYER_METRICS)


def test_a_failed_map_leaves_the_metrics_missing(capsys):
    ctx = hand_made_ctx(None)
    del ctx["scope_map"]
    ctx["config"] = dict(ctx["config"], algorithm={})
    assert metric("emit_ms.batch").reduce(ctx) is None
    assert "no scope map" in capsys.readouterr().err


def test_idle_gaps_are_named_by_program_spans():
    ctx = hand_made_ctx({})
    program = [("rex.pagerank.run", 50, 62), ("rex.executor.run", 51, 60),
               ("rex.executor.dispatch", 55, 59)]
    gaps = scopes.idle_gaps(ctx["trace"], program)
    # busy [10,50] and [60,70] in [0,100]: gaps [0,10], [50,60], [70,100]
    assert [round(s * 1e9) for _, s in gaps] == [10, 10, 30]
    assert [n for n, _ in gaps] == ["bench.fixpoint",
                                    "rex.executor.dispatch", "bench.window"]
    assert [n for n, _ in ctx["trace"].idle_gaps()] == [
        "bench.fixpoint", "bench.fixpoint", "bench.window"]


@pytest.mark.parametrize("cell", ["dbpedia-pagerank.delta",
                                  "dbpedia-pagerank.nodelta"])
def test_the_compiled_loop_carries_every_layer(cell):
    workload, config = tiny_cell(cell)
    m = scopes.scope_map(scopes.fixpoint_hlo(config, workload))
    layers = {scopes.layer_of(p) for p in m.values()}
    assert layers >= set(scopes.LAYERS)
    bodies = {s for p in m.values() for s in p
              if s.startswith("rex.rung") or s == "rex.dense"}
    tiers = config["algorithm"]["ladder_tiers"]
    assert bodies == ({"rex.dense"} if workload["mode"] == "nodelta" else
                      {f"rex.rung{k}" for k in range(tiers)} | {"rex.dense"})


def test_the_map_is_compiled_past_a_cache_with_stale_scopes(tmp_path):
    """The persistent cache keys a program without its metadata: the same
    code under new scope names loads the old names from it."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache

    def program(name):
        def f(x):
            with jax.named_scope(name):
                return jnp.sin(x) * 2
        return jax.jit(f).lower(jax.ShapeDtypeStruct((8,), jnp.float32))

    saved = {k: getattr(jax.config, k) for k in (
        "jax_enable_compilation_cache", "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs")}
    try:
        jax.config.update("jax_enable_compilation_cache", True)
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        compilation_cache.reset_cache()
        assert "rex.old" in program("rex.old").compile().as_text()
        assert "rex.old" in program("rex.new").compile().as_text()
        fresh = scopes.uncached(lambda: program("rex.new").compile().as_text())
        assert "rex.new" in fresh and "rex.old" not in fresh
        assert jax.config.jax_enable_compilation_cache
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def recorded():
    with open(SCOPED + ".json") as f:
        meta = json.load(f)
    with open(SCOPED + ".scopes.json") as f:
        scope_map = json.load(f)
    return read_xspace(SCOPED + ".xplane.pb.gz", 1), meta, scope_map


def test_recorded_layers_sum_to_the_stratum(recorded):
    t, meta, scope_map = recorded
    assert meta["kind"] == "TPU v5 lite"
    workload, config = run.load_cell("dbpedia-pagerank.delta")
    ctx = dict(trace=t, stats=meta["stats"], spans=meta["spans"],
               workload=workload, config=config, scope_map=scope_map)
    values = [metric(n).reduce(ctx) for n in LAYER_METRICS]
    assert all(v is not None and v >= 0 for v in values)
    stratum = metric("stratum_ms.batch").reduce(ctx)
    split = ctx["scope_split"]
    assert split["unscoped"] / split["all"] < 0.05
    outside = (split["unscoped"] + split["no_layer"]) / split["all"]
    assert stratum * (1 - outside) - 1e-9 <= sum(values) <= stratum * (
        1 + 1e-9)
    assert sum(values) >= 0.95 * stratum


def test_recorded_program_spans_name_the_idle_gaps(recorded):
    t = recorded[0]
    program = scopes.program_spans(SCOPED + ".xplane.pb.gz")
    names = {n for n, _, _ in program}
    assert {"rex.pagerank.run", "rex.executor.run", "rex.executor.prepare",
            "rex.executor.dispatch"} <= names
    gaps = scopes.idle_gaps(t, program)
    assert sum(s for _, s in gaps) == pytest.approx(
        sum(s for _, s in t.idle_gaps()))
    assert any(n.startswith("rex.") for n, _ in gaps)
