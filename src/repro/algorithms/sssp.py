"""Delta-based single-source shortest path (paper Listing 2, Figs 7/9).

Fixpoint: ``dist(v) = min(dist(v), min_{u→v} dist(u) + 1)`` (unweighted, as
in the paper's DBPedia/Twitter experiments; a weighted variant only changes
the payload).

Delta formulation (the paper's SPAgg handler): a vertex is in the Δᵢ set —
the *frontier* — when its distance improved since it last propagated.  It
emits ``dist+1`` to each out-neighbor; receivers fold with a min-combiner.
This is exactly the paper's "frontier set" observation: Δᵢ is the BFS
frontier, expanding one hop per stratum.

No-delta re-relaxes EVERY settled vertex each stratum (the Hadoop/HaLoop
behaviour even with relation-level Δ updates the paper grants them).
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
from repro.core.partition import PartitionSnapshot
from repro.data.graphs import CSRGraph
from repro.obs.trace import span

INF = jnp.float32(jnp.inf)


class SPState(NamedTuple):
    dist: jax.Array  # f32[block] — current best distance
    sent: jax.Array  # f32[block] — distance last propagated (inf = never)


# Memoized: equal arguments give the same algorithm object, so a repeated
# run() with equal settings reuses the compiled fixpoint loop.
@functools.lru_cache(maxsize=64)
def make_algorithm(snapshot: PartitionSnapshot, src_capacity: int = 1024,
                   edge_capacity: int = 16384) -> DeltaAlgorithm:
    block = snapshot.block_size

    def active_fn(state: SPState, graph: CSRGraph):
        active = state.dist < state.sent          # improved since last send
        est_edges = jnp.sum(jnp.where(active, graph.out_degree, 0))
        return active, est_edges

    def make_sparse_emit(src_cap: int, edge_cap: int):
        def sparse_emit(state: SPState, graph: CSRGraph, active, stratum,
                        shard_id):
            payload = jnp.where(active, state.dist + 1.0, INF)
            out = emission.emit_over_edges(graph, active, payload,
                                           src_cap, edge_cap)
            new_sent = jnp.where(active, state.dist, state.sent)
            return SPState(dist=state.dist, sent=new_sent), out
        return sparse_emit

    sparse_emit = make_sparse_emit(src_capacity, edge_capacity)

    def dense_emit(state: SPState, graph: CSRGraph, stratum, shard_id):
        reachable = state.dist < INF
        payload = jnp.where(reachable, state.dist + 1.0, INF)
        dst, pay = emission.dense_push(graph, payload)
        # dense_push zeroes invalid payload slots; min-combine needs +inf.
        pay = jnp.where(dst >= 0, pay, INF)
        n_padded = snapshot.padded_keys
        contrib = jnp.full((n_padded + 1,), INF, pay.dtype).at[
            jnp.where(dst >= 0, dst, n_padded)].min(
            pay, mode="drop")[:n_padded]
        return SPState(dist=state.dist, sent=state.dist), contrib[:, None]

    def apply_sparse(state: SPState, incoming: DeltaBuffer, graph: CSRGraph,
                     stratum, shard_id):
        inc = emission.scatter_local(incoming, shard_id, block, "min")
        dist = jnp.minimum(state.dist, inc)
        new_state = SPState(dist=dist, sent=state.sent)
        return new_state, jnp.sum((dist < state.sent).astype(jnp.int32))

    def apply_dense(state: SPState, incoming: jax.Array, graph: CSRGraph,
                    stratum, shard_id):
        dist = jnp.minimum(state.dist, incoming[:, 0])
        new_state = SPState(dist=dist, sent=state.sent)
        return new_state, jnp.sum((dist < state.sent).astype(jnp.int32))

    return DeltaAlgorithm(
        active_fn=active_fn, sparse_emit=sparse_emit, dense_emit=dense_emit,
        apply_sparse=apply_sparse, apply_dense=apply_dense,
        combiner="min", payload_width=1, bytes_per_delta=8,
        emit_factory=make_sparse_emit)


def initial_state(snapshot: PartitionSnapshot, source: int = 0) -> SPState:
    S, block = snapshot.num_shards, snapshot.block_size
    dist = jnp.full((S, block), INF, jnp.float32)
    owner = source // block
    dist = dist.at[owner, source % block].set(0.0)
    sent = jnp.full((S, block), INF, jnp.float32)
    return SPState(dist=dist, sent=sent)


@span("rex.sssp.run")
def run(graph_sharded: CSRGraph, snapshot: PartitionSnapshot,
        source: int = 0, mode: str = "delta", max_iters: int = 80,
        executor: Optional[ShardedExecutor] = None,
        src_capacity: int = 1024, edge_capacity: int = 16384,
        ladder_tiers: int = 1, route_strategy: str = "sort"
        ) -> tuple[jax.Array, FixpointResult]:
    algo = make_algorithm(snapshot, src_capacity, edge_capacity)
    if executor is None:
        executor = ShardedExecutor(
            snapshot=snapshot, seg_capacity=edge_capacity,
            edge_capacity=edge_capacity, src_capacity=src_capacity,
            ladder_tiers=ladder_tiers, route_strategy=route_strategy)
    state0 = initial_state(snapshot, source)
    res = executor.run(algo, state0, 1, graph_sharded, max_iters, mode=mode)
    dist = SPState(*res.state).dist.reshape(-1)
    return dist, res


def reference_sssp(indptr, indices, n: int, source: int = 0) -> jnp.ndarray:
    """BFS oracle (unweighted shortest path)."""
    import collections

    import numpy as np
    dist = np.full(n, np.inf, np.float32)
    dist[source] = 0.0
    q = collections.deque([source])
    while q:
        u = q.popleft()
        for v in indices[indptr[u]:indptr[u + 1]]:
            if v >= 0 and dist[v] == np.inf:
                dist[v] = dist[u] + 1
                q.append(v)
    return jnp.asarray(dist)
