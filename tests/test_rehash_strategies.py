"""Sort vs scatter combine-route strategies: equivalence + dispatch.

The contract of the physical rehash strategies is that they change HOW a
stratum's deltas are grouped, never WHAT the stratum computes: the
scatter-based ``combine_route_scatter`` must reproduce the sort-based
``combine_route`` slot for slot — keys, annotations, counts, overflow —
across combiners, overflowing segment capacities, all-padding buffers,
out-of-range owners, and both partition schemes.  Payloads are
bit-identical for min/max/replace (order-free or single-writer merges);
float "add" is compared to addition order (≤1 ulp reassociation).
"""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import jax.numpy as jnp

from repro.algorithms import pagerank, sssp
from repro.core.delta import (ANN_ADJUST, PAD_KEY, DeltaBuffer,
                              combine_route, combine_route_scatter)
from repro.core.engine import ShardedExecutor
from repro.core.fixpoint import (ROUTE_SCATTER, ROUTE_SCATTER_KERNEL,
                                 ROUTE_SORT, ROUTE_SORT_KERNEL)
from repro.core.partition import PartitionSnapshot
from repro.data.graphs import make_powerlaw_graph, shard_csr


def _random_buffer(rng, n, keyspace, payload_width=2):
    count = int(rng.integers(0, n + 1))          # 0 = all-padding buffer
    keys = np.full(n, -1, np.int32)
    keys[:count] = rng.integers(0, keyspace, count)
    pay = rng.normal(size=(n, payload_width)).astype(np.float32)
    pay[count:] = 0
    return DeltaBuffer(
        keys=jnp.asarray(keys), payload=jnp.asarray(pay),
        ann=jnp.full(n, ANN_ADJUST, jnp.int8),
        count=jnp.asarray(count),
        overflowed=jnp.asarray(bool(rng.integers(0, 2))))


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 9999),
       shards=st.sampled_from([1, 2, 4, 5, 8]),
       combiner=st.sampled_from(["add", "min", "max", "replace"]))
def test_scatter_equals_sort_strategy(seed, shards, combiner):
    """Property: the scatter-slab combine-route is element-wise identical
    to the fused-sort combine_route — keys/ann/count/overflow exact for
    every combiner, payload bits exact for min/max/replace — over small
    caps (overflow), all-padding buffers, out-of-range owners, and both
    block and hash partition schemes."""
    rng = np.random.default_rng(seed)
    n, keyspace = 48, 24
    cap = int(rng.integers(1, n + 2))            # small caps overflow
    db = _random_buffer(rng, n, keyspace)
    snap = PartitionSnapshot(n_keys=keyspace, num_shards=shards,
                             scheme=("block", "hash")[seed % 2])
    owners = snap.owner_of(db.keys)
    # Out-of-range owners drop the whole key — corrupt per key VALUE so
    # the assignment stays a function of the key (the scatter contract).
    owners = jnp.where((db.keys % 5 == 0) & (db.keys >= 0),
                       shards + 3, owners)
    ref = combine_route(db, owners, shards, cap, combiner)
    got = combine_route_scatter(db, owners, shards, cap, combiner,
                                snapshot=snap)
    np.testing.assert_array_equal(np.asarray(ref.keys),
                                  np.asarray(got.keys))
    np.testing.assert_array_equal(np.asarray(ref.ann), np.asarray(got.ann))
    assert int(ref.count) == int(got.count)
    assert bool(ref.overflowed) == bool(got.overflowed)
    if combiner == "add":
        np.testing.assert_allclose(np.asarray(ref.payload),
                                   np.asarray(got.payload),
                                   rtol=1e-6, atol=1e-6)
    else:
        np.testing.assert_array_equal(np.asarray(ref.payload),
                                      np.asarray(got.payload))


def test_scatter_all_padding():
    db = DeltaBuffer.empty(16, 1)
    snap = PartitionSnapshot(n_keys=32, num_shards=4)
    out = combine_route_scatter(db, jnp.full((16,), -1, jnp.int32), 4, 8,
                                "add", snapshot=snap)
    assert int(out.count) == 0 and not bool(out.overflowed)
    assert bool(jnp.all(out.keys == PAD_KEY))


def test_to_dense_replace_combiner():
    """DeltaBuffer.to_dense("replace"): last live slot of a key wins
    (parity with combine_route's replace semantics)."""
    keys = jnp.array([2, 1, 2, PAD_KEY], jnp.int32)
    pay = jnp.array([[5.0], [7.0], [9.0], [99.0]])
    db = DeltaBuffer(keys=keys, payload=pay, ann=jnp.zeros(4, jnp.int8),
                     count=jnp.asarray(3), overflowed=jnp.asarray(False))
    out = db.to_dense(4, "replace")
    assert out.tolist() == [0.0, 7.0, 9.0, 0.0]


class TestAutoDispatch:
    def _exec(self, snap, **kw):
        return ShardedExecutor(snapshot=snap, seg_capacity=16384,
                               edge_capacity=16384, src_capacity=1024, **kw)

    def test_cost_model_crossover(self):
        """Auto picks scatter when the slab is small next to C·log₂C and
        keeps the sort for tiny rungs on huge key spaces."""
        small = PartitionSnapshot(n_keys=4096, num_shards=8)
        ex = self._exec(small, route_strategy="auto")
        assert ex.pick_route_strategy(65536, "add") == "scatter"
        huge = PartitionSnapshot(n_keys=1 << 22, num_shards=8)
        ex2 = self._exec(huge, route_strategy="auto")
        assert ex2.pick_route_strategy(256, "add") == "sort"

    def test_non_composable_combiner_forces_sort(self):
        snap = PartitionSnapshot(n_keys=4096, num_shards=8)
        ex = self._exec(snap, route_strategy="auto")
        assert ex.pick_route_strategy(65536, None) == "sort"
        ex2 = self._exec(snap, route_strategy="scatter")
        assert ex2.pick_route_strategy(65536, None) == "sort"

    def test_invalid_strategy_rejected(self):
        snap = PartitionSnapshot(n_keys=64, num_shards=4)
        with pytest.raises(ValueError):
            self._exec(snap, route_strategy="quantum").pick_route_strategy(
                256, "add")


@pytest.fixture(scope="module")
def graph():
    n, S = 1024, 4
    indptr, indices = make_powerlaw_graph(n, avg_degree=8.0, seed=0)
    snap = PartitionSnapshot(n_keys=n, num_shards=S)
    return snap, shard_csr(indptr, indices, S)


def test_strategy_invariant_end_to_end(graph):
    """Full PageRank fixpoint under sort / scatter / auto: identical delta
    counts, rehash bytes, tier dispatch, and (on XLA CPU, where scatter
    updates apply in slot order) bit-identical float state."""
    snap, g = graph
    caps = dict(edge_capacity=16384, src_capacity=snap.block_size)
    runs = {}
    for strat in ("sort", "scatter", "auto"):
        runs[strat] = pagerank.run(g, snap, mode="delta", ladder_tiers=4,
                                   route_strategy=strat, **caps)
    pr0, r0 = runs["sort"]
    for strat in ("scatter", "auto"):
        pr, r = runs[strat]
        for field in ("delta_counts", "rehash_bytes", "used_dense",
                      "tiers"):
            np.testing.assert_array_equal(
                np.asarray(getattr(r.stats, field)),
                np.asarray(getattr(r0.stats, field)),
                err_msg=f"{strat}:{field}")
        np.testing.assert_allclose(np.asarray(pr), np.asarray(pr0),
                                   rtol=1e-6, atol=1e-7, err_msg=strat)

    iters = int(r0.stats.iterations)
    assert np.all(np.asarray(r0.stats.routes)[:iters] == ROUTE_SORT)
    routes_scatter = np.asarray(runs["scatter"][1].stats.routes)[:iters]
    assert np.all(routes_scatter == ROUTE_SCATTER)


def test_sssp_scatter_bit_identical(graph):
    """min-combiner merges are order-free: the scatter strategy must be
    bit-identical to the sort strategy, not merely close."""
    snap, g = graph
    caps = dict(edge_capacity=16384, src_capacity=snap.block_size)
    d0, r0 = sssp.run(g, snap, mode="delta", source=0,
                      route_strategy="sort", **caps)
    d1, r1 = sssp.run(g, snap, mode="delta", source=0,
                      route_strategy="scatter", **caps)
    assert bool(jnp.all(d0 == d1))
    np.testing.assert_array_equal(np.asarray(r0.stats.delta_counts),
                                  np.asarray(r1.stats.delta_counts))


def test_pallas_route_in_loop_matches_jnp(graph):
    """use_pallas_route dispatches the delta_route kernel inside the
    stratum body (interpret mode on CPU) and is bit-exact against the jnp
    engine under SSSP's min combiner.  The scatter kernel implements
    "add" only, so the scatter strategy routes SSSP through jnp, and the
    recorded route codes say so."""
    snap, g = graph
    caps = dict(edge_capacity=2048, src_capacity=snap.block_size)

    def ex(**kw):
        return ShardedExecutor(snapshot=snap, seg_capacity=2048,
                               edge_capacity=2048,
                               src_capacity=snap.block_size, **kw)

    d0, r0 = sssp.run(g, snap, mode="delta", source=0, executor=ex(),
                      **caps)
    for strat in ("sort", "scatter"):
        d1, r1 = sssp.run(g, snap, mode="delta", source=0,
                          executor=ex(route_strategy=strat,
                                      use_pallas_route=True), **caps)
        assert bool(jnp.all(d0 == d1)), strat
        np.testing.assert_array_equal(
            np.asarray(r0.stats.delta_counts),
            np.asarray(r1.stats.delta_counts), err_msg=strat)
        np.testing.assert_array_equal(
            np.asarray(r0.stats.rehash_bytes),
            np.asarray(r1.stats.rehash_bytes), err_msg=strat)
        routes = np.asarray(r1.stats.routes)[:int(r1.stats.iterations)]
        expect = ROUTE_SORT_KERNEL if strat == "sort" else ROUTE_SCATTER
        sparse = routes >= 0
        assert sparse.any() and np.all(routes[sparse] == expect), strat


@pytest.mark.parametrize("strat,kernel_code", [
    ("sort", ROUTE_SORT_KERNEL), ("scatter", ROUTE_SCATTER_KERNEL)])
def test_pallas_route_pagerank_kernel_path(graph, strat, kernel_code):
    """PageRank's "add" combiner is within both kernels' bounds: every
    sparse stratum records the kernel's route code, delta counts equal
    the jnp engine's, and values agree to float addition order."""
    snap, g = graph
    caps = dict(edge_capacity=2048, src_capacity=snap.block_size)

    def ex(**kw):
        return ShardedExecutor(snapshot=snap, seg_capacity=2048,
                               edge_capacity=2048,
                               src_capacity=snap.block_size,
                               route_strategy=strat, **kw)

    p0, r0 = pagerank.run(g, snap, mode="delta", threshold=1e-3,
                          executor=ex(), **caps)
    p1, r1 = pagerank.run(g, snap, mode="delta", threshold=1e-3,
                          executor=ex(use_pallas_route=True), **caps)
    routes = np.asarray(r1.stats.routes)[:int(r1.stats.iterations)]
    sparse = routes >= 0
    assert sparse.any() and np.all(routes[sparse] == kernel_code)
    np.testing.assert_array_equal(np.asarray(r0.stats.delta_counts),
                                  np.asarray(r1.stats.delta_counts))
    np.testing.assert_allclose(np.asarray(p1), np.asarray(p0), rtol=1e-6,
                               atol=1e-7)
