"""Delta-based k-means clustering (paper Ex.2, Listing 3, Fig 5).

The mutable set is the point→centroid assignment; the Δᵢ set is the points
that *switched* centroids this stratum (paper Fig 3).  The paper's KMAgg
handler emits, per switched point, an adjustment delta ``(cid, +x, +y, +1)``
for the new centroid and ``(oldCid, −x, −y, −1)`` for the old one — the
centroid's (sum, count) state is *incrementally* maintained rather than
recomputed.  KMSampleAgg seeds centroids by sampling point coordinates.

Wire model: switched-point deltas are pre-aggregated per centroid (the §5.2
combiner) before the cross-shard reduction; the no-delta mode ships every
point's assignment record every stratum (the MapReduce shuffle the paper
compares against — Hadoop re-shuffles all N points per iteration, which is
why Fig 5 shows a ~100× gap).

Centroids are replicated on every shard (k is small); the cross-shard
combine of (sum_x, sum_y, count) adjustments is a ``psum`` in SPMD — here
expressed as a sum over the stacked shard axis (identical arithmetic).
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core.fixpoint import (FixpointResult, StratumOutcome, run_strata)
from repro.obs.trace import span

BYTES_PER_DELTA = 16          # cid:int32 + x:f32 + y:f32 + count:f32
BYTES_PER_POINT_RECORD = 16   # what a MapReduce shuffle ships per point


class KMState(NamedTuple):
    assign: jax.Array   # int32[S, block]  — current centroid per point
    sums: jax.Array     # f32[k, 2]        — Σ coords per centroid (replicated)
    counts: jax.Array   # f32[k]           — points per centroid (replicated)


def assign_points(points: jax.Array, centroids: jax.Array) -> jax.Array:
    """Nearest centroid per point: ‖p‖² − 2p·cᵀ + ‖c‖² argmin (MXU form).

    points f32[..., 2]; centroids f32[k, 2] -> int32[...].
    kernels/kmeans_assign provides the blocked Pallas version of this
    contract; this is the reference used by the engine on CPU.
    """
    d2 = (jnp.sum(points ** 2, -1, keepdims=True)
          - 2.0 * points @ centroids.T
          + jnp.sum(centroids ** 2, -1))
    return jnp.argmin(d2, axis=-1).astype(jnp.int32)


def centroids_of(state: KMState) -> jax.Array:
    return state.sums / jnp.maximum(state.counts, 1.0)[:, None]


def _segment_sums(points, assign, valid, k):
    """Per-centroid (Σx, Σy, n) over the masked points: f32[k, 3]."""
    w = valid.astype(points.dtype)
    data = jnp.concatenate([points * w[:, None], w[:, None]], axis=-1)
    idx = jnp.where(valid, assign, k)
    return jnp.zeros((k + 1, 3), points.dtype).at[idx].add(
        data, mode="drop")[:k]


def initial_state(points_sharded: jax.Array, init_centroids: jax.Array,
                  valid: Optional[jax.Array] = None) -> KMState:
    """Base-case stratum: assign every (valid) point once, build sums."""
    S, block, _ = points_sharded.shape
    k = init_centroids.shape[0]
    if valid is None:
        valid = jnp.ones((S, block), jnp.bool_)
    assign0 = jax.vmap(assign_points, in_axes=(0, None))(
        points_sharded, init_centroids)
    seg0 = jnp.sum(jax.vmap(_segment_sums, in_axes=(0, 0, 0, None))(
        points_sharded, assign0, valid, k), axis=0)          # psum in SPMD
    return KMState(assign=assign0, sums=seg0[:, :2], counts=seg0[:, 2])


def make_stratum(points_sharded: jax.Array, k: int, mode: str = "delta",
                 valid: Optional[jax.Array] = None):
    """One Lloyd stratum over a (possibly masked) point set.

    ``valid`` masks out dead point slots — the incremental view subsystem
    keeps a fixed-capacity point array and toggles slots on insert/remove,
    so shapes stay static across refreshes.  Invalid slots never switch and
    never contribute to centroid sums.
    """
    if mode not in ("delta", "nodelta"):
        raise ValueError(mode)
    S, block, _ = points_sharded.shape
    if valid is None:
        valid = jnp.ones((S, block), jnp.bool_)
    n_points = jnp.sum(valid.astype(jnp.int32))

    def stratum(state: KMState, stratum_idx):
        cents = centroids_of(state)
        new_assign = jax.vmap(assign_points, in_axes=(0, None))(
            points_sharded, cents)
        new_assign = jnp.where(valid, new_assign, state.assign)
        switched = (new_assign != state.assign) & valid
        n_switched = jnp.sum(switched.astype(jnp.int32))     # psum in SPMD

        if mode == "delta":
            # KMAgg: +(x,y,1) to the new centroid, −(x,y,1) from the old —
            # pre-aggregated per centroid locally before the reduction.
            plus = jax.vmap(_segment_sums, in_axes=(0, 0, 0, None))(
                points_sharded, new_assign, switched, k)
            minus = jax.vmap(_segment_sums, in_axes=(0, 0, 0, None))(
                points_sharded, state.assign, switched, k)
            adj = jnp.sum(plus - minus, axis=0)              # psum in SPMD
            sums = state.sums + adj[:, :2]
            counts = state.counts + adj[:, 2]
            bytes_moved = (2 * n_switched * BYTES_PER_DELTA).astype(
                jnp.float32)
            used_dense = jnp.asarray(False)
        else:
            seg = jnp.sum(jax.vmap(_segment_sums, in_axes=(0, 0, 0, None))(
                points_sharded, new_assign, valid, k), axis=0)
            sums, counts = seg[:, :2], seg[:, 2]
            bytes_moved = (n_points * BYTES_PER_POINT_RECORD).astype(
                jnp.float32)
            used_dense = jnp.asarray(True)

        new_state = KMState(assign=new_assign, sums=sums, counts=counts)
        return new_state, StratumOutcome(
            live_count=n_switched, used_dense=used_dense,
            rehash_bytes=bytes_moved, emitted=n_switched)

    return stratum


@span("rex.kmeans.run")
def run(points_sharded: jax.Array, init_centroids: jax.Array,
        mode: str = "delta", max_iters: int = 60,
        valid: Optional[jax.Array] = None) -> tuple[
            jax.Array, FixpointResult]:
    """points_sharded f32[S, block, 2]; init_centroids f32[k, 2].

    Returns (final centroids, FixpointResult with per-stratum stats).
    """
    k = init_centroids.shape[0]
    state0 = initial_state(points_sharded, init_centroids, valid)
    stratum = make_stratum(points_sharded, k, mode, valid)
    res = run_strata(stratum, state0, jnp.asarray(1, jnp.int32), max_iters)
    return centroids_of(res.state), res


def resume(points_sharded: jax.Array, state: KMState, max_iters: int = 60,
           mode: str = "delta", valid: Optional[jax.Array] = None
           ) -> tuple[jax.Array, FixpointResult]:
    """Resume Lloyd iteration from a warm (repaired) KMState.

    The incremental k-means rule nudges (sums, counts, assign) for the
    inserted/removed points, then calls this to re-converge; the first
    stratum re-checks every valid point's assignment against the nudged
    centroids, so the live count self-corrects to zero when the nudge was
    already a fixpoint."""
    k = state.sums.shape[0]
    stratum = make_stratum(points_sharded, k, mode, valid)
    res = run_strata(stratum, state, jnp.asarray(1, jnp.int32), max_iters)
    return centroids_of(res.state), res


def reference_kmeans(points: jnp.ndarray, init_centroids: jnp.ndarray,
                     max_iters: int = 60) -> jnp.ndarray:
    """Lloyd-iteration oracle over the flat point set."""
    import numpy as np
    pts = np.asarray(points, np.float32).reshape(-1, 2)
    cents = np.asarray(init_centroids, np.float32).copy()
    assign = None
    for _ in range(max_iters):
        d2 = ((pts[:, None, :] - cents[None, :, :]) ** 2).sum(-1)
        new_assign = d2.argmin(1)
        if assign is not None and (new_assign == assign).all():
            break
        assign = new_assign
        for c in range(cents.shape[0]):
            sel = pts[assign == c]
            if len(sel):
                cents[c] = sel.mean(0)
    return jnp.asarray(cents)
