"""Connected components by min-label propagation (delta form).

Not one of the paper's three benchmark algorithms, but the canonical extra
member of its Δᵢ-set family (same shape as Fig 3's shortest-path row): the
mutable set is each vertex's component label, the Δᵢ set is the vertices
whose label decreased since last propagation.  Reuses the SSSP machinery
with label payloads instead of distances: fixpoint
``label(v) = min(label(v), min_{u→v} label(u))``.
"""
from __future__ import annotations

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


class CCState(NamedTuple):
    label: jax.Array  # f32[block] — current component label (vertex ids)
    sent: jax.Array   # f32[block] — label last propagated


def make_algorithm(snapshot: PartitionSnapshot, src_capacity: int = 1024,
                   edge_capacity: int = 16384) -> DeltaAlgorithm:
    block = snapshot.block_size

    def active_fn(state: CCState, graph: CSRGraph):
        active = state.label < state.sent
        est_edges = jnp.sum(jnp.where(active, graph.out_degree, 0))
        return active, est_edges

    def make_sparse_emit(src_cap: int, edge_cap: int):
        def sparse_emit(state, graph, active, stratum, shard_id):
            payload = jnp.where(active, state.label, jnp.inf)
            out = emission.emit_over_edges(graph, active, payload,
                                           src_cap, edge_cap)
            new_sent = jnp.where(active, state.label, state.sent)
            return CCState(label=state.label, sent=new_sent), out
        return sparse_emit

    sparse_emit = make_sparse_emit(src_capacity, edge_capacity)

    def dense_emit(state, graph, stratum, shard_id):
        dst, pay = emission.dense_push(graph, state.label)
        pay = jnp.where(dst >= 0, pay, jnp.inf)
        n_padded = snapshot.padded_keys
        contrib = jnp.full((n_padded + 1,), jnp.inf, pay.dtype).at[
            jnp.where(dst >= 0, dst, n_padded)].min(
            pay, mode="drop")[:n_padded]
        return CCState(label=state.label, sent=state.label), contrib[:, None]

    def apply_sparse(state, incoming: DeltaBuffer, graph, stratum, shard_id):
        inc = emission.scatter_local(incoming, shard_id, block, "min")
        label = jnp.minimum(state.label, inc)
        new_state = CCState(label=label, sent=state.sent)
        return new_state, jnp.sum((label < state.sent).astype(jnp.int32))

    def apply_dense(state, incoming, graph, stratum, shard_id):
        label = jnp.minimum(state.label, incoming[:, 0])
        new_state = CCState(label=label, sent=state.sent)
        return new_state, jnp.sum((label < state.sent).astype(jnp.int32))

    return DeltaAlgorithm(
        active_fn=active_fn, sparse_emit=sparse_emit, dense_emit=dense_emit,
        apply_sparse=apply_sparse, apply_dense=apply_dense,
        combiner="min", payload_width=1, bytes_per_delta=8,
        emit_factory=make_sparse_emit)


def initial_state(snapshot: PartitionSnapshot) -> CCState:
    S, block = snapshot.num_shards, snapshot.block_size
    ids = jnp.arange(S * block, dtype=jnp.float32).reshape(S, block)
    return CCState(label=ids, sent=jnp.full((S, block), jnp.inf, jnp.float32))


@span("rex.connected_components.run")
def run(graph_sharded: CSRGraph, snapshot: PartitionSnapshot,
        mode: str = "delta", max_iters: int = 80,
        executor: Optional[ShardedExecutor] = None,
        src_capacity: int = 1024, edge_capacity: int = 16384,
        ladder_tiers: int = 1) -> tuple[jax.Array, FixpointResult]:
    algo = make_algorithm(snapshot, src_capacity, edge_capacity)
    if executor is None:
        executor = ShardedExecutor(
            snapshot=snapshot, seg_capacity=edge_capacity,
            edge_capacity=edge_capacity, src_capacity=src_capacity,
            ladder_tiers=ladder_tiers)
    state0 = initial_state(snapshot)
    res = executor.run(algo, state0, snapshot.padded_keys, graph_sharded,
                       max_iters, mode=mode)
    label = CCState(*res.state).label.reshape(-1)
    return label, res


def reference_components(indptr, indices, n: int) -> jnp.ndarray:
    """Union-find oracle over the undirected view... the propagation model is
    DIRECTED min-label (labels flow along edge direction only), so the oracle
    iterates the same fixpoint densely."""
    import numpy as np
    label = np.arange(n, dtype=np.float64)
    src_of_edge = np.repeat(np.arange(n), np.diff(indptr))
    for _ in range(n):  # worst-case diameter
        contrib = np.full(n, np.inf)
        np.minimum.at(contrib, indices, label[src_of_edge])
        new = np.minimum(label, contrib)
        if (new == label).all():
            break
        label = new
    return jnp.asarray(label.astype(np.float32))
