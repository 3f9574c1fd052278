"""Delta-based adsorption / label propagation (paper Fig 3, row 2).

Each vertex carries an L-dimensional label distribution.  Seeded vertices
inject their own label; every vertex's vector is the damped average of its
in-neighbors' vectors plus its injection:

    vec(v) = inj·seed(v) + (1 − inj) · Σ_{u→v} sent(u) / outdeg(u)

The Δᵢ set is "adsorption vector positions with change ≥ 1% since iteration
i−1" — we track per-vertex L∞ change of the whole vector (a vertex re-emits
when any position moved past the threshold), matching the per-position
criterion at vector granularity.  Payloads are W=L columns; everything else
is the PageRank pattern with vector deltas.
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

INJECTION = 0.25


class AdsorptionState(NamedTuple):
    acc: jax.Array    # f32[block, L] — accumulated incoming mass
    sent: jax.Array   # f32[block, L] — vector last propagated
    seed: jax.Array   # f32[block, L] — injected label (immutable per run)


def current_vec(state: AdsorptionState) -> jax.Array:
    return INJECTION * state.seed + (1.0 - INJECTION) * state.acc


def make_algorithm(snapshot: PartitionSnapshot, n_labels: int,
                   threshold: float = 1e-2, src_capacity: int = 1024,
                   edge_capacity: int = 16384) -> DeltaAlgorithm:
    block = snapshot.block_size

    def active_fn(state: AdsorptionState, graph: CSRGraph):
        diff = jnp.max(jnp.abs(current_vec(state) - state.sent), axis=-1)
        active = diff > threshold
        est_edges = jnp.sum(jnp.where(active, graph.out_degree, 0))
        return active, est_edges

    def make_sparse_emit(src_cap: int, edge_cap: int):
        def sparse_emit(state, graph, active, stratum, shard_id):
            vec = current_vec(state)
            deg = jnp.maximum(graph.out_degree, 1).astype(vec.dtype)[:, None]
            payload = jnp.where(active[:, None], (vec - state.sent) / deg,
                                0.0)
            out = emission.emit_over_edges_vec(graph, active, payload,
                                               src_cap, edge_cap)
            new_sent = jnp.where(active[:, None], vec, state.sent)
            return AdsorptionState(state.acc, new_sent, state.seed), out
        return sparse_emit

    sparse_emit = make_sparse_emit(src_capacity, edge_capacity)

    def dense_emit(state, graph, stratum, shard_id):
        vec = current_vec(state)
        deg = jnp.maximum(graph.out_degree, 1).astype(vec.dtype)
        n_padded = snapshot.padded_keys
        L = vec.shape[-1]
        # Full push: every source contributes vec/deg along every edge.
        nnz = graph.nnz_capacity
        slots = jnp.arange(nnz, dtype=jnp.int32)
        src = jnp.clip(jnp.searchsorted(graph.indptr.astype(jnp.int32),
                                        slots, side="right") - 1,
                       0, block - 1)
        dst = graph.indices
        valid = dst >= 0
        per_edge = jnp.where(valid[:, None], vec[src] / deg[src, None], 0.0)
        contrib = jnp.zeros((n_padded + 1, L), vec.dtype).at[
            jnp.where(valid, dst, n_padded)].add(
            per_edge, mode="drop")[:n_padded]
        return AdsorptionState(state.acc, vec, state.seed), contrib

    def apply_sparse(state, incoming: DeltaBuffer, graph, stratum, shard_id):
        inc = emission.scatter_local_vec(incoming, shard_id, block)
        acc = state.acc + inc
        new_state = AdsorptionState(acc, state.sent, state.seed)
        diff = jnp.max(jnp.abs(current_vec(new_state) - new_state.sent), -1)
        return new_state, jnp.sum((diff > threshold).astype(jnp.int32))

    def apply_dense(state, incoming, graph, stratum, shard_id):
        new_state = AdsorptionState(incoming, state.sent, state.seed)
        diff = jnp.max(jnp.abs(current_vec(new_state) - new_state.sent), -1)
        return new_state, jnp.sum((diff > threshold).astype(jnp.int32))

    return DeltaAlgorithm(
        active_fn=active_fn, sparse_emit=sparse_emit, dense_emit=dense_emit,
        apply_sparse=apply_sparse, apply_dense=apply_dense,
        combiner="add", payload_width=n_labels,
        bytes_per_delta=4 + 4 * n_labels, emit_factory=make_sparse_emit)


def initial_state(snapshot: PartitionSnapshot, seeds: jax.Array
                  ) -> AdsorptionState:
    """seeds: f32[padded_keys, L] one-hot (or zero) injection vectors."""
    S, block = snapshot.num_shards, snapshot.block_size
    L = seeds.shape[-1]
    seed = seeds.reshape(S, block, L)
    z = jnp.zeros((S, block, L), jnp.float32)
    return AdsorptionState(acc=z, sent=z, seed=seed)


@span("rex.adsorption.run")
def run(graph_sharded: CSRGraph, snapshot: PartitionSnapshot,
        seeds: jax.Array, mode: str = "delta", threshold: float = 1e-2,
        max_iters: int = 50, executor: Optional[ShardedExecutor] = None,
        src_capacity: int = 1024, edge_capacity: int = 16384,
        ladder_tiers: int = 1) -> tuple[jax.Array, FixpointResult]:
    n_labels = seeds.shape[-1]
    algo = make_algorithm(snapshot, n_labels, threshold, src_capacity,
                          edge_capacity)
    if executor is None:
        executor = ShardedExecutor(
            snapshot=snapshot, seg_capacity=edge_capacity,
            edge_capacity=edge_capacity, src_capacity=src_capacity,
            ladder_tiers=ladder_tiers)
    state0 = initial_state(snapshot, seeds)
    res = executor.run(algo, state0, snapshot.padded_keys, graph_sharded,
                       max_iters, mode=mode)
    state = AdsorptionState(*res.state)
    vec = current_vec(state).reshape(-1, n_labels)
    return vec, res
