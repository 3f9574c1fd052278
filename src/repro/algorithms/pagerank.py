"""Delta-based PageRank (paper §1 Ex.1, §3.5, Listing 1, Figs 2/6/8).

Fixpoint: ``pr(v) = 0.15 + 0.85 * Σ_{u→v} sent(u) / outdeg(u)``.

Delta formulation (the paper's PRAgg handler): every vertex tracks the value
it last *propagated* (``sent``) and its accumulated incoming mass (``acc``).
A vertex is in the Δᵢ set when its current value ``pr = 0.15 + 0.85·acc``
differs from ``sent`` by more than the threshold; it then emits
``(pr − sent)/outdeg`` along each out-edge (the paper's
``deltaPr/nbrBucket.size()``) and records ``sent ← pr``.  Receivers fold the
adjustment deltas (δ(E), arithmetic-sum semantics) into ``acc``.

The no-delta mode re-derives every vertex's full contribution each stratum
(Hadoop/HaLoop behaviour): contributions are *replaced*, not adjusted.

Both modes converge to the same fixpoint (property-tested); the delta mode
does O(|Δᵢ| edges) work and moves O(|Δᵢ|) bytes per stratum.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.algorithms import emission
from repro.core.delta import DeltaBuffer
from repro.core.engine import DeltaAlgorithm, ShardedExecutor
from repro.core.fixpoint import FixpointResult
from repro.core.partition import PartitionSnapshot, shard_dense_state
from repro.data.graphs import CSRGraph
from repro.obs.trace import span

DAMPING = 0.85
BASE = 0.15


class PRState(NamedTuple):
    acc: jax.Array    # f32[block] — accumulated incoming mass Σ sent(u)/deg(u)
    sent: jax.Array   # f32[block] — value last propagated to neighbors


def current_pr(state: PRState) -> jax.Array:
    return BASE + DAMPING * state.acc


# Memoized: equal arguments give the same algorithm object, so a repeated
# run() with equal settings reuses the compiled fixpoint loop.
@functools.lru_cache(maxsize=64)
def make_algorithm(snapshot: PartitionSnapshot, threshold: float = 1e-3,
                   src_capacity: int = 1024, edge_capacity: int = 16384
                   ) -> DeltaAlgorithm:
    block = snapshot.block_size

    def active_fn(state: PRState, graph: CSRGraph):
        diff = jnp.abs(current_pr(state) - state.sent)
        active = diff > threshold
        est_edges = jnp.sum(jnp.where(active, graph.out_degree, 0))
        return active, est_edges

    def make_sparse_emit(src_cap: int, edge_cap: int):
        def sparse_emit(state: PRState, graph: CSRGraph, active, stratum,
                        shard_id):
            pr = current_pr(state)
            deg = jnp.maximum(graph.out_degree, 1).astype(pr.dtype)
            payload = jnp.where(active, (pr - state.sent) / deg, 0.0)
            out = emission.emit_over_edges(graph, active, payload,
                                           src_cap, edge_cap)
            # sent <- pr for the sources whose diff we just shipped.
            new_sent = jnp.where(active, pr, state.sent)
            return PRState(acc=state.acc, sent=new_sent), out
        return sparse_emit

    sparse_emit = make_sparse_emit(src_capacity, edge_capacity)

    def dense_emit(state: PRState, graph: CSRGraph, stratum, shard_id):
        pr = current_pr(state)
        deg = jnp.maximum(graph.out_degree, 1).astype(pr.dtype)
        dst, payload = emission.dense_push(graph, pr / deg)
        n_padded = snapshot.padded_keys
        contrib = jnp.zeros((n_padded + 1,), payload.dtype).at[
            jnp.where(dst >= 0, dst, n_padded)].add(
            payload, mode="drop")[:n_padded]
        # Dense strata REPLACE acc, so sent must reflect the full pr pushed.
        return PRState(acc=state.acc, sent=pr), contrib[:, None]

    def apply_sparse(state: PRState, incoming: DeltaBuffer, graph: CSRGraph,
                     stratum, shard_id):
        inc = emission.scatter_local(incoming, shard_id, block, "add")
        acc = state.acc + inc
        new_state = PRState(acc=acc, sent=state.sent)
        diff = jnp.abs(current_pr(new_state) - new_state.sent)
        return new_state, jnp.sum((diff > threshold).astype(jnp.int32))

    def apply_dense(state: PRState, incoming: jax.Array, graph: CSRGraph,
                    stratum, shard_id):
        acc = incoming[:, 0]                  # full replacement semantics
        new_state = PRState(acc=acc, sent=state.sent)
        diff = jnp.abs(current_pr(new_state) - new_state.sent)
        return new_state, jnp.sum((diff > threshold).astype(jnp.int32))

    return DeltaAlgorithm(
        active_fn=active_fn, sparse_emit=sparse_emit, dense_emit=dense_emit,
        apply_sparse=apply_sparse, apply_dense=apply_dense,
        combiner="add", payload_width=1, bytes_per_delta=8,
        emit_factory=make_sparse_emit)


def initial_state(snapshot: PartitionSnapshot) -> PRState:
    """Δ₀ = every vertex (sent=0, so pr₀ = 0.15 must propagate)."""
    z = jnp.zeros((snapshot.num_shards, snapshot.block_size), jnp.float32)
    return PRState(acc=z, sent=z)


@span("rex.pagerank.run")
def run(graph_sharded: CSRGraph, snapshot: PartitionSnapshot,
        mode: str = "delta", threshold: float = 1e-3, max_iters: int = 60,
        executor: Optional[ShardedExecutor] = None,
        src_capacity: int = 1024, edge_capacity: int = 16384,
        ladder_tiers: int = 1, route_strategy: str = "sort"
        ) -> tuple[jax.Array, FixpointResult]:
    """Run PageRank; returns (pr values [padded_keys], FixpointResult)."""
    algo = make_algorithm(snapshot, threshold, src_capacity, edge_capacity)
    if executor is None:
        executor = ShardedExecutor(
            snapshot=snapshot, seg_capacity=edge_capacity,
            edge_capacity=edge_capacity, src_capacity=src_capacity,
            ladder_tiers=ladder_tiers, route_strategy=route_strategy)
    state0 = initial_state(snapshot)
    live0 = snapshot.padded_keys
    res = executor.run(algo, state0, live0, graph_sharded, max_iters,
                       mode=mode)
    state = res.state
    pr = current_pr(PRState(*state)).reshape(-1)
    return pr, res


def reference_pagerank(indptr, indices, n: int, iters: int = 100
                       ) -> jnp.ndarray:
    """Dense NumPy-style oracle: pr = 0.15 + 0.85 Σ pr(u)/deg(u)."""
    import numpy as np
    deg = np.maximum(np.diff(indptr), 1)
    pr = np.full(n, BASE, np.float64)
    src_of_edge = np.repeat(np.arange(n), np.diff(indptr))
    for _ in range(iters):
        contrib = np.zeros(n, np.float64)
        np.add.at(contrib, indices, pr[src_of_edge] / deg[src_of_edge])
        pr = BASE + DAMPING * contrib
    return jnp.asarray(pr.astype(np.float32))
