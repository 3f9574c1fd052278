"""Declarative rule frontend: parse round-trips, optimizer rewrites,
lowering bit-identity against the handwritten algorithms, and a rules-only
program (reachability) running end-to-end with zero engine changes."""
import os
import subprocess
import sys
import tempfile

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

import jax.numpy as jnp

from repro import frontend as F
from repro.algorithms import connected_components as cc
from repro.algorithms import pagerank, sssp
from repro.core import plan as P
from repro.core.engine import ShardedExecutor
from repro.core.optimizer import CostModel, optimize
from repro.core.partition import PartitionSnapshot
from repro.data.graphs import make_powerlaw_graph, shard_csr
from repro.frontend import expr as E
from repro.frontend.lower import CompiledProgram, _extract_spec
from repro.obs.calibrate import RouteCostTable
from repro.runtime import FaultEvent, FaultSchedule

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
N, S = 1024, 4


@pytest.fixture(scope="module")
def graph():
    indptr, indices = make_powerlaw_graph(N, avg_degree=8.0, seed=0)
    snap = PartitionSnapshot(n_keys=N, num_shards=S)
    return indptr, indices, snap, shard_csr(indptr, indices, S)


# ---------------------------------------------------------------------------
# Parse / build / render round-trips.
# ---------------------------------------------------------------------------

def _random_expr(rng, rels, var="u", depth=0):
    roll = rng.integers(0, 3 if depth < 3 else 2)
    if roll == 0:
        return E.Const(float(np.round(rng.uniform(-4, 4), 3)))
    if roll == 1:
        return E.Ref(str(rng.choice(rels)), var)
    op = str(rng.choice(["+", "-", "*", "/"]))
    return E.BinOp(op, _random_expr(rng, rels, var, depth + 1),
                   _random_expr(rng, rels, var, depth + 1))


class TestParseRoundTrip:
    @pytest.mark.parametrize("text,builder", [
        (F.PAGERANK_TEXT, F.pagerank_program),
        (F.SSSP_TEXT, F.sssp_program),
        (F.CC_TEXT, F.cc_program),
        (F.REACHABILITY_TEXT, F.reachability_program),
    ])
    def test_canonical_programs(self, text, builder):
        parsed = F.parse_program(text)
        assert parsed == builder()
        assert F.parse_program(parsed.to_text()) == parsed

    @settings(max_examples=30)
    @given(seed=st.integers(0, 10**6),
           agg=st.sampled_from(["add", "min", "max"]),
           threshold=st.floats(min_value=1e-6, max_value=10.0))
    def test_random_programs(self, seed, agg, threshold):
        """Property: build → to_text → parse is the identity."""
        rng = np.random.default_rng(seed)
        b = F.ProgramBuilder(f"p{seed}").threshold(threshold)
        b.input("edge", "u", "v")
        if rng.integers(0, 2):
            b.init("head", _random_expr(rng, ["id"], var="v"), var="v")
        for _ in range(rng.integers(0, 3)):
            b.fact("head", int(rng.integers(0, 100)),
                   float(np.round(rng.uniform(-9, 9), 3)))
        b.rule("head", agg, _random_expr(rng, ["head", "deg"]),
               var="v", src="u")
        prog = b.build()
        assert F.parse_program(prog.to_text()) == prog

    @settings(max_examples=20)
    @given(seed=st.integers(0, 10**6))
    def test_expr_text_round_trip(self, seed):
        """Property: the expression TREE (not just its value) round-trips
        through to_text — parenthesization must respect associativity."""
        rng = np.random.default_rng(seed)
        e = _random_expr(rng, ["x", "deg"])
        b = (F.ProgramBuilder("t").input("edge", "u", "v")
             .rule("x", "add", e, var="v", src="u").build())
        assert F.parse_program(b.to_text()).rules[0].term == b.rules[0].term

    def test_comments_and_whitespace(self):
        text = ("# header comment\nprogram   demo.\n"
                "input edge(u, v).  # trailing\n"
                "x(v) min= x(u) :- edge(u, v).\n")
        prog = F.parse_program(text)
        assert prog.name == "demo" and prog.rules[0].agg == "min"

    def test_parse_errors(self):
        with pytest.raises(F.ParseError):
            F.parse_program("program p. @!?")
        with pytest.raises(F.ParseError):
            F.parse_program("x(v) foo= x(u) :- edge(u, v).")
        with pytest.raises(F.ParseError):
            F.parse_program("input edge(u, v). x(w) min= x(u) "
                            ":- edge(u, v).")  # head var != edge dst
        with pytest.raises(F.FrontendError):
            F.parse_program("threshold 0.0.\ninput edge(u, v).")

    def test_builder_validation(self):
        with pytest.raises(F.FrontendError):
            F.ProgramBuilder("p").rule("x", "add", E.ref("x")).build()
        with pytest.raises(F.FrontendError):
            (F.ProgramBuilder("p").input("edge", "u", "v")
             .rule("x", "avg", E.ref("x")).build())
        with pytest.raises(F.FrontendError):  # cross-variable reference
            (F.ProgramBuilder("p").input("edge", "u", "v")
             .rule("x", "add", E.ref("x", "v")).build())


# ---------------------------------------------------------------------------
# Planner + optimizer: real IR-to-IR rewrites.
# ---------------------------------------------------------------------------

class TestPlanAndOptimize:
    def test_plan_shape(self):
        plan = F.plan_program(F.pagerank_program())
        assert plan.op == "fixpoint" and plan.combiner == "add"
        ops = [n.op for n in P.walk(plan)]
        for op in ("scan", "select", "udf", "join", "project", "rehash",
                   "groupby"):
            assert op in ops
        names = [n.name for n in P.walk(plan) if n.op == "udf"]
        assert "view:rank" in names and "term" in names

    def test_optimizer_pushes_preagg_below_rehash(self):
        raw = F.plan_program(F.pagerank_program())
        opt = optimize(raw)
        seq = [n.op for n in P.walk(opt)]
        assert seq.index("rehash") < seq.index("preagg")  # preagg under it
        # Sender-side combining shrinks the network lane by ~the preagg
        # reduction; the plan stays scan(disk)-dominated overall.
        assert P.total_resource(opt)[2] < 0.2 * P.total_resource(raw)[2]
        assert P.plan_runtime(opt) <= P.plan_runtime(raw)

    def test_optimizer_idempotent(self):
        plan = F.plan_program(F.pagerank_program())
        once = optimize(plan)
        twice = optimize(once)
        assert once == twice

    def test_pinned_udfs_survive_in_order(self):
        opt = optimize(F.plan_program(F.pagerank_program()))
        names = [n.name for n in P.walk(opt) if n.op == "udf"]
        assert names.index("term") < names.index("view:rank")  # term above

    def test_fixpoint_idempotent_takes_retraction_path(self):
        """Satellite: min/max fixpoints cost-estimate along the §6
        delta-retraction path — geometric Δ decay, fewer iterations and a
        cheaper plan than the same shape under a monotone add."""
        base = P.scan("r", 1e5)
        rec = P.rehash(P.scan("delta", 1e5))
        fp_add = P.fixpoint(base, rec, max_iters=64, combiner="add")
        fp_min = P.fixpoint(base, rec, max_iters=64, combiner="min")
        fp_max = P.fixpoint(base, rec, max_iters=64, combiner="max")
        assert fp_min.estimated_iterations < fp_add.estimated_iterations
        assert fp_max.estimated_iterations == fp_min.estimated_iterations
        assert P.plan_runtime(fp_min) < P.plan_runtime(fp_add)
        assert fp_add.estimated_iterations == 64  # monotone: full budget

    def test_cost_model_from_route_table(self):
        """Satellite: the optimizer consults measured route costs when a
        calibration table is provided, static constants otherwise."""
        table = RouteCostTable(backend="cpu", combiner="add",
                               entries={1024: (1.024e-4, 2e-4),
                                        4096: (8e-4, 4.096e-4)})
        assert table.per_tuple_cost(1024) == pytest.approx(1e-7)
        assert table.per_tuple_cost(4096) == pytest.approx(1e-7)
        cm = CostModel.from_route_table(table)
        assert cm.rehash_net_per_tuple == pytest.approx(
            table.median_per_tuple())
        assert cm.source == "measured:cpu"
        assert CostModel().source == "static"
        plan = F.plan_program(F.pagerank_program(), cost_model=cm)
        rh = next(n for n in P.walk(plan) if n.op == "rehash")
        assert rh.resource[2] == pytest.approx(
            rh.out_cardinality * cm.rehash_net_per_tuple)

    def test_optimized_plan_runs_identically(self, graph):
        """Rewrites change cost, never semantics: lowering the raw planner
        output and the optimized plan gives bit-identical runs."""
        _, _, snap, g = graph
        prog = F.pagerank_program()
        opt_cp = F.compile_program(prog)
        logical = F.plan_program(prog)
        raw_cp = CompiledProgram(program=prog, logical=logical,
                                 optimized=logical,
                                 spec=_extract_spec(prog, logical))
        a, _ = opt_cp.run(g, snap, max_iters=40)
        b, _ = raw_cp.run(g, snap, max_iters=40)
        assert bool(jnp.all(a == b))


# ---------------------------------------------------------------------------
# Lowering validation.
# ---------------------------------------------------------------------------

class TestLoweringValidation:
    def test_nonlinear_add_term_rejected(self):
        prog = (F.ProgramBuilder("bad").input("edge", "u", "v")
                .rule("x", "add", E.ref("x") * E.ref("x")).build())
        with pytest.raises(F.FrontendError, match="homogeneous-linear"):
            F.compile_program(prog)

    def test_affine_add_term_rejected(self):
        # T(a) = 0.15 + 0.85 a is affine: T(a) − T(b) ≠ T(a − b).
        prog = (F.ProgramBuilder("bad").input("edge", "u", "v")
                .rule("x", "add", 0.15 + 0.85 * E.ref("x")).build())
        with pytest.raises(F.FrontendError, match="homogeneous-linear"):
            F.compile_program(prog)

    def test_view_over_idempotent_head_rejected(self):
        prog = (F.ProgramBuilder("bad").input("edge", "u", "v")
                .view("y", 2.0 * E.ref("x"))
                .rule("x", "min", E.ref("y")).build())
        with pytest.raises(NotImplementedError):
            F.compile_program(prog)

    def test_multi_rule_rejected(self):
        prog = (F.ProgramBuilder("bad").input("edge", "u", "v")
                .rule("x", "min", E.ref("x"))
                .rule("y", "min", E.ref("y")).build())
        with pytest.raises(NotImplementedError, match="one recursive rule"):
            F.compile_program(prog)

    def test_unknown_relation_in_term_rejected(self):
        prog = (F.ProgramBuilder("bad").input("edge", "u", "v")
                .rule("x", "min", E.ref("mystery")).build())
        with pytest.raises(F.FrontendError, match="mystery"):
            F.compile_program(prog)


# ---------------------------------------------------------------------------
# Compiled vs handwritten: bit-identity (simulated backend).
# ---------------------------------------------------------------------------

def _ulp_close(a, b, ulps=1):
    a, b = np.asarray(a), np.asarray(b)
    tol = ulps * np.spacing(np.maximum(np.abs(a), np.abs(b)))
    both_nonfinite = ~np.isfinite(a) & ~np.isfinite(b) & (np.sign(a)
                                                          == np.sign(b))
    return bool(np.all(both_nonfinite | (np.abs(a - b) <= tol)))


class TestBitIdentity:
    @settings(max_examples=4, deadline=None)
    @given(seed=st.integers(0, 1000), deg=st.sampled_from([4.0, 12.0]))
    def test_pagerank(self, seed, deg):
        indptr, indices = make_powerlaw_graph(512, avg_degree=deg, seed=seed)
        snap = PartitionSnapshot(n_keys=512, num_shards=S)
        g = shard_csr(indptr, indices, S)
        cp = F.compile_program(F.pagerank_program())
        got, rg = cp.run(g, snap, max_iters=60)
        want, rw = pagerank.run(g, snap, max_iters=60)
        # ≤1 ulp budget for the float-add combiner; currently exact.
        assert _ulp_close(got, want, ulps=1)
        assert bool(jnp.all(got == want))
        assert np.array_equal(np.asarray(rg.stats.delta_counts),
                              np.asarray(rw.stats.delta_counts))

    @settings(max_examples=4, deadline=None)
    @given(seed=st.integers(0, 1000), source=st.integers(0, 511))
    def test_sssp(self, seed, source):
        indptr, indices = make_powerlaw_graph(512, avg_degree=8.0, seed=seed)
        snap = PartitionSnapshot(n_keys=512, num_shards=S)
        g = shard_csr(indptr, indices, S)
        cp = F.compile_program(F.sssp_program(source=source))
        got, _ = cp.run(g, snap, max_iters=80)
        want, _ = sssp.run(g, snap, source=source, max_iters=80)
        assert bool(jnp.all(got == want))

    @settings(max_examples=4, deadline=None)
    @given(seed=st.integers(0, 1000))
    def test_connected_components(self, seed):
        indptr, indices = make_powerlaw_graph(512, avg_degree=6.0, seed=seed)
        snap = PartitionSnapshot(n_keys=512, num_shards=S)
        g = shard_csr(indptr, indices, S)
        cp = F.compile_program(F.cc_program())
        got, _ = cp.run(g, snap, max_iters=80)
        want, _ = cc.run(g, snap, max_iters=80)
        assert bool(jnp.all(got == want))

    def test_dense_mode_and_ladder(self, graph):
        """Compiled algorithms inherit the executor machinery unchanged:
        no-delta mode and the capacity ladder stay bit-identical."""
        _, _, snap, g = graph
        cp = F.compile_program(F.pagerank_program())
        a, _ = cp.run(g, snap, mode="nodelta", max_iters=40)
        b, _ = pagerank.run(g, snap, mode="nodelta", max_iters=40)
        assert bool(jnp.all(a == b))
        c, rc = cp.run(g, snap, max_iters=40, ladder_tiers=4,
                       src_capacity=snap.block_size)
        d, rd = pagerank.run(g, snap, max_iters=40, ladder_tiers=4,
                             src_capacity=snap.block_size)
        assert bool(jnp.all(c == d))
        assert np.array_equal(np.asarray(rc.stats.delta_counts),
                              np.asarray(rd.stats.delta_counts))


# ---------------------------------------------------------------------------
# Rules-only reachability: whole pipeline, zero engine changes.
# ---------------------------------------------------------------------------

class TestReachability:
    @settings(max_examples=5, deadline=None)
    @given(seed=st.integers(0, 1000), source=st.integers(0, 511))
    def test_matches_bfs_oracle(self, seed, source):
        n = 512
        indptr, indices = make_powerlaw_graph(n, avg_degree=6.0, seed=seed)
        snap = PartitionSnapshot(n_keys=n, num_shards=S)
        g = shard_csr(indptr, indices, S)
        cp = F.compile_program(F.reachability_program(source=source))
        vals, res = cp.run(g, snap, max_iters=80)
        dist = np.asarray(sssp.reference_sssp(np.asarray(indptr),
                                              np.asarray(indices), n,
                                              source=source))
        assert np.array_equal(np.asarray(vals)[:n] == 1.0, dist < np.inf)
        assert int(res.stats.iterations) < 80  # converged, not exhausted

    def test_from_text(self, graph):
        _, _, snap, g = graph
        cp = F.compile_program(F.parse_program(F.REACHABILITY_TEXT))
        vals, _ = cp.run(g, snap, max_iters=80)
        assert float(np.asarray(vals)[0]) == 1.0


# ---------------------------------------------------------------------------
# Resilient driver + shard_map backend.
# ---------------------------------------------------------------------------

class TestEngineIntegration:
    def test_resilient_run_with_fault_schedule(self, graph):
        """A compiled program survives injected failures and lands on the
        same state as the undisturbed run."""
        _, _, snap, g = graph
        cp = F.compile_program(F.sssp_program())
        ex = ShardedExecutor(snapshot=snap, seg_capacity=8192,
                             edge_capacity=8192,
                             src_capacity=snap.block_size)
        algo = cp.make_algorithm(snap, src_capacity=snap.block_size,
                                 edge_capacity=8192)
        state0 = cp.initial_state(snap)
        live0 = ex.live_count(algo, state0, g)
        ref = ex.run(algo, state0, live0, g, 80)
        schedule = FaultSchedule(events=(
            FaultEvent(kind="fail", at=2, shard=1),
            FaultEvent(kind="fail", at=4, shard=3),
        ))
        with tempfile.TemporaryDirectory() as td:
            rr = ex.run_resilient(algo, state0, live0, g, 80,
                                  ckpt_root=td, fault_plan=schedule)
        assert rr.metrics["converged"]
        assert rr.metrics["recoveries"] == 2
        assert bool(jnp.all(jnp.stack(
            [jnp.all(x == y) for x, y in zip(ref.state,
                                             rr.result.state)])))
        assert bool(jnp.all(cp.values(rr.result.state)
                            == cp.values(ref.state)))

    @pytest.mark.slow
    def test_bit_identical_shard_map(self):
        """Compiled PR/SSSP/CC match the handwritten algorithms on the
        real-SPMD shard_map backend too."""
        code = """
import jax, jax.numpy as jnp, numpy as np
from repro.data.graphs import make_powerlaw_graph, shard_csr
from repro.core.partition import PartitionSnapshot
from repro.core.engine import ShardedExecutor
from repro.algorithms import pagerank, sssp, connected_components as cc
from repro import frontend as F
n, S = 512, 8
indptr, indices = make_powerlaw_graph(n, avg_degree=8.0, seed=0)
snap = PartitionSnapshot(n_keys=n, num_shards=S)
g = shard_csr(indptr, indices, S)
mesh = jax.make_mesh((S,), ('shards',))
def make_ex():
    return ShardedExecutor(snapshot=snap, seg_capacity=8192,
                           edge_capacity=8192, src_capacity=snap.block_size,
                           backend='shard_map', axis_name='shards',
                           mesh=mesh)
cases = [(F.pagerank_program(), pagerank, {}, 60),
         (F.sssp_program(), sssp, dict(source=0), 80),
         (F.cc_program(), cc, {}, 80)]
caps = dict(src_capacity=snap.block_size, edge_capacity=8192)
for prog, mod, kw, iters in cases:
    cp = F.compile_program(prog)
    a, _ = cp.run(g, snap, max_iters=iters, executor=make_ex(), **caps)
    b, _ = mod.run(g, snap, max_iters=iters, executor=make_ex(), **kw,
                   **caps)
    assert bool(jnp.all(a == b)), prog.name
print('FRONTEND_SHARD_MAP_OK')
"""
        env = dict(os.environ)
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        env["PYTHONPATH"] = SRC
        out = subprocess.run([sys.executable, "-c", code], env=env,
                             capture_output=True, text=True, timeout=900)
        assert out.returncode == 0, out.stderr[-3000:]
        assert "FRONTEND_SHARD_MAP_OK" in out.stdout
