"""Stratified fixpoint execution (paper §3.1, §3.4, §4.2).

REX executes recursive queries in *strata*: the base case seeds the mutable
set; each stratum applies incoming deltas to operator state and emits the
next Δ set; punctuation ends a stratum; the engine terminates *implicitly*
(no new deltas — a fixpoint) or *explicitly* (a user condition over
consecutive strata, which REX converts to implicit by filtering deltas).

TPU mapping: a stratum is one iteration of ``jax.lax.while_loop``.  The
"punctuation + stratum vote at the requestor" becomes a global reduction of
the live-delta count (a ``psum`` when sharded) carried into the loop
condition.  Each stratum chooses between the **sparse** (delta) body —
O(|Δᵢ|) work — and the **dense** body (full re-derivation) *before* doing
any work, from the exactly-predicted emission size (Σ out-degree of active
keys).  This is the delta analogue of direction-optimizing BFS push/pull
switching and replaces post-hoc overflow recovery: the decision is made on
exact counts so no delta is ever dropped.

Per-stratum statistics (Δᵢ counts, dense fallbacks, bytes rehashed) are
carried in preallocated arrays so they can be reported like the paper's
Figure 2 / Figure 11.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp


# Rehash implementation a sparse stratum ran (``StratumStats.routes``).
# The low bit is the strategy; codes >= ROUTE_SORT_KERNEL ran a Pallas
# kernel (``ShardedExecutor.route_code``).
ROUTE_SORT = 0            # sort-based combine-route (jnp)
ROUTE_SCATTER = 1         # scatter-based combine-route (jnp)
ROUTE_SORT_KERNEL = 2     # pre-aggregate + kernels/delta_route
ROUTE_SCATTER_KERNEL = 3  # kernels/scatter_route


class StratumStats(NamedTuple):
    delta_counts: jax.Array   # int32[max_iters]   — |Δᵢ| emitted per stratum
    used_dense: jax.Array     # bool[max_iters]    — stratum ran densely
    rehash_bytes: jax.Array   # float32[max_iters] — bytes moved by the rehash
    iterations: jax.Array     # int32[]            — strata actually executed
    tiers: jax.Array          # int32[max_iters]   — ladder rung per stratum
    #                           (0 = smallest sparse tier, -1 = dense / n.a.)
    routes: jax.Array = None  # int32[max_iters]   — rehash implementation
    #                           per stratum (ROUTE_* codes, -1 = dense /
    #                           n.a.)


class StratumOutcome(NamedTuple):
    """What one stratum reports back to the driver (globally reduced)."""

    live_count: jax.Array    # int32[]  — |Δ| still live after this stratum
    used_dense: jax.Array    # bool[]   — ran the dense body
    rehash_bytes: jax.Array  # float32[] — bytes the rehash moved
    emitted: jax.Array       # int32[]  — deltas emitted this stratum
    tier: jax.Array = -1     # int32[]  — capacity-ladder rung (-1 = dense)
    route: jax.Array = -1    # int32[]  — ROUTE_* code (-1 = dense / n.a.)


class FixpointResult(NamedTuple):
    state: object
    stats: StratumStats


def run_strata(stratum_fn: Callable, state0, live0, max_iters: int,
               tracer=None) -> FixpointResult:
    """Run ``stratum_fn`` until no live deltas remain or ``max_iters``.

    stratum_fn(state, stratum) -> (state', StratumOutcome)
        Owns the whole stratum: density decision, emission, rehash
        (collectives), application.  Outcome fields must be globally
        reduced (identical on every shard) — they feed the loop condition.
    live0
        Globally-reduced initial live count (size of Δ₀).
    tracer
        Optional ``repro.obs.Tracer``: fires a fixpoint-complete probe
        after the loop (per-stratum probes live inside ``stratum_fn``,
        inserted by the engine).  None leaves the computation untouched.
    """
    stats0 = StratumStats(
        delta_counts=jnp.zeros((max_iters,), jnp.int32),
        used_dense=jnp.zeros((max_iters,), jnp.bool_),
        rehash_bytes=jnp.zeros((max_iters,), jnp.float32),
        iterations=jnp.zeros((), jnp.int32),
        tiers=jnp.full((max_iters,), -1, jnp.int32),
        routes=jnp.full((max_iters,), -1, jnp.int32),
    )

    def cond_fn(carry):
        _, stratum, live, _ = carry
        return (stratum < max_iters) & (live > 0)

    def body_fn(carry):
        state, stratum, _, stats = carry
        new_state, outcome = stratum_fn(state, stratum)
        stats = StratumStats(
            delta_counts=stats.delta_counts.at[stratum].set(outcome.emitted),
            used_dense=stats.used_dense.at[stratum].set(outcome.used_dense),
            rehash_bytes=stats.rehash_bytes.at[stratum].set(
                outcome.rehash_bytes),
            iterations=stratum + 1,
            tiers=stats.tiers.at[stratum].set(outcome.tier),
            routes=stats.routes.at[stratum].set(outcome.route),
        )
        return (new_state, stratum + 1, outcome.live_count, stats)

    carry = (state0, jnp.zeros((), jnp.int32), jnp.asarray(live0, jnp.int32),
             stats0)
    state, _, _, stats = jax.lax.while_loop(cond_fn, body_fn, carry)
    if tracer is not None:
        tracer.fixpoint_probe(stats.iterations, max_iters)
    return FixpointResult(state=state, stats=stats)


def empty_stats(max_iters: int) -> StratumStats:
    """Stats of a run that executed zero strata (warm resume no-op)."""
    return StratumStats(
        delta_counts=jnp.zeros((max_iters,), jnp.int32),
        used_dense=jnp.zeros((max_iters,), jnp.bool_),
        rehash_bytes=jnp.zeros((max_iters,), jnp.float32),
        iterations=jnp.zeros((), jnp.int32),
        tiers=jnp.full((max_iters,), -1, jnp.int32),
        routes=jnp.full((max_iters,), -1, jnp.int32),
    )


def stats_from_outcomes(outcomes: list, max_iters: int) -> StratumStats:
    """Assemble :class:`StratumStats` from host-collected per-stratum
    outcomes — the stratum-sliced drivers' (runtime/recovery.py) equivalent
    of the recording done inside :func:`run_strata`'s while_loop.

    ``outcomes`` may be longer than ``max_iters`` when strata were redone
    after a failure (restart recovery); the stats then record the LAST
    ``max_iters`` outcomes and ``iterations`` is clipped to ``max_iters``
    so every consumer invariant (``stats.x[:iterations]`` in bounds) holds
    — the driver's work-unit metrics account the redone strata exactly.
    """
    import numpy as np
    n = min(len(outcomes), max_iters)
    tail = outcomes[-max_iters:]

    def col(getter, dtype, fill):
        arr = np.full((max_iters,), fill, dtype)
        for i, o in enumerate(tail):
            arr[i] = getter(o)
        return jnp.asarray(arr)

    return StratumStats(
        delta_counts=col(lambda o: int(o.emitted), np.int32, 0),
        used_dense=col(lambda o: bool(o.used_dense), np.bool_, False),
        rehash_bytes=col(lambda o: float(o.rehash_bytes), np.float32, 0.0),
        iterations=jnp.asarray(n, jnp.int32),
        tiers=col(lambda o: int(o.tier), np.int32, -1),
        routes=col(lambda o: int(o.route), np.int32, -1),
    )


def merge_stats(a: StratumStats, b: StratumStats) -> StratumStats:
    """Concatenate the per-stratum stats of two consecutive runs (host-side;
    used by incremental views to account a cold start plus its warm resumes
    as one logical computation)."""
    import numpy as np
    ia, ib = int(a.iterations), int(b.iterations)

    def cat(xa, xb):
        return jnp.asarray(np.concatenate(
            [np.asarray(xa)[:ia], np.asarray(xb)[:ib]]))

    return StratumStats(
        delta_counts=cat(a.delta_counts, b.delta_counts),
        used_dense=cat(a.used_dense, b.used_dense),
        rehash_bytes=cat(a.rehash_bytes, b.rehash_bytes),
        iterations=jnp.asarray(ia + ib, jnp.int32),
        tiers=cat(a.tiers, b.tiers),
        routes=cat(a.routes, b.routes),
    )


# ---------------------------------------------------------------------------
# Explicit termination (paper §3.4): a user condition over consecutive
# strata, converted to the implicit form by zeroing the live count.
# ---------------------------------------------------------------------------

def with_explicit_condition(stratum_fn: Callable, cond: Callable) -> Callable:
    """Wrap a stratum so that ``cond(new_state, old_state, stratum) -> bool``
    (True = keep iterating) gates the live count — the paper's conversion of
    explicit termination into the implicit fixpoint form."""

    def wrapped(state, stratum):
        new_state, outcome = stratum_fn(state, stratum)
        keep = cond(new_state, state, stratum)
        return new_state, outcome._replace(
            live_count=jnp.where(keep, outcome.live_count, 0))

    return wrapped
