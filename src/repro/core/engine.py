"""Distributed delta execution: the rehash operator + sharded fixpoint.

The paper's runtime (§4.1–4.2) pushes batched delta messages point-to-point
(TCP) between workers according to the partition snapshot.  The TPU-native
equivalent of that shuffle is a single ``all_to_all`` over equal-size
segments: each shard groups its outgoing deltas by destination
(``route_by_owner``), the collective swaps segments, the receiver recounts
live slots.  The dense (no-delta / fallback) path instead exchanges each
shard's full contribution vector with a summed all_to_all — the two
communication patterns are the delta/dense duality at the wire level, and
their byte counts are what benchmarks/bench_bandwidth.py reports (Fig. 11).

Two execution backends share all algorithm code:

  * ``simulated`` — shards are a leading array axis on one device; the
    all_to_all is an axis transpose.  Deterministically identical to the
    distributed run; used by unit tests and single-host benches.
  * ``shard_map`` — real SPMD over a mesh axis: ``jax.lax.all_to_all`` for
    rehash, ``psum`` for stratum votes.

Algorithms are written against :class:`DeltaAlgorithm` — five shard-local
functions; the engine owns routing, density switching, and the fixpoint
loop.  Outgoing deltas use GLOBAL keys; the engine routes by the partition
snapshot (paper §4.1).
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import delta as deltamod
from repro.core.delta import PAD_KEY, DeltaBuffer
from repro.core.fixpoint import (ROUTE_SCATTER, ROUTE_SCATTER_KERNEL,
                                 ROUTE_SORT, ROUTE_SORT_KERNEL,
                                 FixpointResult, StratumOutcome, run_strata,
                                 with_explicit_condition)
from repro.core.partition import PartitionSnapshot


@dataclasses.dataclass(frozen=True)
class DeltaAlgorithm:
    """A REX recursive query lowered to shard-local callables.

    active_fn(state, imm) -> (active_mask[bool; block], est_edges[int32;])
        The Δᵢ set (keys whose refinement must propagate) plus the EXACT
        emission size if run sparsely (Σ out-degree of active keys).
    sparse_emit(state, imm, active, stratum, shard_id)
        -> (state_partial, DeltaBuffer)        — O(|Δ|) emission.
    dense_emit(state, imm, stratum, shard_id)
        -> (state_partial, contrib[f32; n_padded_global, payload_width])
        — full re-derivation: this shard's contribution to EVERY key.
    apply_sparse(state_partial, incoming: DeltaBuffer, imm, stratum, shard_id)
        -> (state', next_active_count[int32;])
    apply_dense(state_partial, incoming[f32; block, payload_width], imm,
        stratum, shard_id) -> (state', next_active_count)

    combiner — how concurrent contributions to one key merge ("add"|"min").
    payload_width, bytes_per_delta — wire accounting for Fig. 11.
    emit_factory(src_capacity, edge_capacity) -> sparse_emit-like callable
        Optional: rebuild the sparse emission at a different capacity tier.
        Providing it lets the executor compile the stratum body at several
        capacity rungs (the density ladder) and dispatch each stratum to the
        smallest rung that fits its exactly-predicted emission size.
    """

    active_fn: Callable
    sparse_emit: Callable
    dense_emit: Callable
    apply_sparse: Callable
    apply_dense: Callable
    combiner: str = "add"
    payload_width: int = 1
    bytes_per_delta: int = 8  # int32 key + f32 payload
    emit_factory: Optional[Callable] = None

    def dense_identity(self) -> float:
        return {"add": 0.0, "min": float("inf"), "max": float("-inf")}[
            self.combiner]


def _dense_combine(stacked: jax.Array, combiner: str, axis: int) -> jax.Array:
    if combiner == "add":
        return jnp.sum(stacked, axis=axis)
    if combiner == "min":
        return jnp.min(stacked, axis=axis)
    if combiner == "max":
        return jnp.max(stacked, axis=axis)
    raise ValueError(combiner)


def _shard_map(body, mesh, in_specs, out_specs):
    """``jax.shard_map`` with the engine's settings (replication is not
    checked: stratum outcomes are made uniform by explicit collectives)."""
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def _over_shards(fn, *args, in_axes=None):
    """Apply shard-local ``fn`` to each shard of the leading [S] axis of
    its arguments, one shard after another (``lax.map``); ``in_axes``
    marks with None the arguments every shard shares.  Shard-local code
    then compiles as the 1-D program it is written as, the same one each
    device runs under shard_map: batched (vmapped) gathers and scatters
    over large arrays take the TPU compiler many times longer."""
    if in_axes is None:
        in_axes = (0,) * len(args)
    mapped = tuple(a for a, ax in zip(args, in_axes) if ax == 0)

    def one(xs):
        it = iter(xs)
        return fn(*[next(it) if ax == 0 else a
                    for a, ax in zip(args, in_axes)])

    return jax.lax.map(one, mapped)


# Per-device views under shard_map keep a length-1 leading shard axis;
# bodies squeeze it on entry and expand on exit.
_squeeze = partial(jax.tree.map, lambda x: x[0] if x.ndim else x)
_expand = partial(jax.tree.map,
                  lambda x: x[None] if hasattr(x, "ndim") else x)


class CapacityTier(NamedTuple):
    """One rung of the density ladder: the three sparse-stratum budgets."""

    src: int    # active-source compaction slots
    edge: int   # edge-emission slots
    seg: int    # per-destination rehash segment slots


@dataclasses.dataclass(frozen=True)
class ShardedExecutor:
    """Runs a DeltaAlgorithm over a partitioned key space.

    snapshot      — partition snapshot routed against (paper §4.1).
    seg_capacity  — per-destination segment slots in the sparse rehash.
    edge_capacity — stratum edge-slot budget for sparse emission; strata
                    whose predicted |Δ| edges exceed it run densely.
    src_capacity  — active-source compaction budget (sparse emission).

    Density ladder: with ``ladder_tiers > 1`` (and an algorithm providing
    ``emit_factory``) the sparse stratum body is compiled at ``ladder_tiers``
    capacity rungs — powers of ``ladder_factor`` below the configured
    capacities — and each stratum dispatches to the SMALLEST rung whose
    budgets cover the exactly-predicted emission size from ``active_fn``.
    The paper's |Δᵢ|-shrinks-as-we-converge observation (§3.3, Fig. 2) then
    translates into per-stratum cost that tracks |Δᵢ| instead of the static
    worst-case capacity: tail strata sort/scatter arrays 4–64× smaller.
    The dense body stays the top rung of the same ladder (the sparse/dense
    duality becomes a multi-rung density ladder).

    Rehash strategy: each capacity rung's local rehash runs one of two
    physical implementations (Pregelix-style per-operator-instance strategy
    choice) — ``"sort"`` (the fused single-lexicographic-sort
    ``combine_route``) or ``"scatter"`` (the sort-free
    ``combine_route_scatter``: dense per-destination slab + prefix-sum
    compaction, O(C + slab) instead of O(C log C)).  ``"auto"`` applies a
    static cost model per rung at trace time: sort cost ~ C·log₂C, scatter
    cost ~ weight·(C + slab cells), so big rungs (C ≳ slab) go scatter and
    tiny tail rungs on huge key spaces keep the sort.  Strategies are
    bit-identical in keys/ann/count/overflow; float "add" payloads may
    reassociate by ≤1 ulp (identical in practice on XLA CPU).  Algorithms
    whose combiner is not composable always route with the sort path.

    ``use_pallas_route`` dispatches the per-shard local rehash to the
    Pallas kernels (``kernels/delta_route`` for sort-strategy routing,
    ``kernels/scatter_route`` for the scatter strategy) — interpret mode
    on CPU, compiled on TPU — instead of the jnp implementations, on
    every rung within the kernel's bounds.  A rung beyond them, or a
    combiner the kernel lacks, routes through jnp; each stratum's
    ``route`` code records which implementation ran (``route_code``).

    Observability: an attached ``tracer`` (``repro.obs.Tracer``) records a
    per-stratum probe from inside the compiled loop —
    ``jax.debug.callback`` survives ``lax.while_loop`` and ``shard_map``,
    so arrival-time deltas measure per-stratum (per-shard under
    shard_map) wall clock along with tier/route/emitted/rehash-bytes.
    ``tracer=None`` (the default) emits no callbacks at all: the traced
    computation is exactly the uninstrumented one, bit-identical.

    ``route_strategy="measured"`` swaps the "auto" static cost model for
    a measured per-rung dispatch table (``route_table``, built by
    ``repro.obs.calibrate`` from real sort/scatter timings on the current
    backend) — the per-backend calibration the static weight
    approximated.
    """

    snapshot: PartitionSnapshot
    seg_capacity: int
    edge_capacity: int
    src_capacity: int
    backend: str = "simulated"
    axis_name: str = "shards"
    mesh: Optional[object] = None
    ladder_tiers: int = 1          # 1 = ladder off (single sparse rung)
    ladder_factor: int = 4         # capacity ratio between adjacent rungs
    ladder_src_floor: int = 64     # smallest useful src budget
    ladder_edge_floor: int = 256   # smallest useful edge/seg budget
    route_strategy: str = "sort"   # "sort" | "scatter" | "auto" | "measured"
    route_scatter_weight: float = 0.4  # auto model: relative cost of one
    #                                scatter/slab element vs one sort
    #                                compare·log₂C unit.  Calibrated from
    #                                benchmarks/bench_rehash.py on XLA CPU
    #                                (crossover between C=1024 and C=4096
    #                                at 65536 slab cells).
    use_pallas_route: bool = False  # kernels instead of jnp local rehash
    tracer: Optional[object] = dataclasses.field(
        default=None, compare=False)   # repro.obs.Tracer (None = untraced)
    route_table: Optional[object] = dataclasses.field(
        default=None, compare=False)   # obs.calibrate.RouteCostTable for
    #                                    route_strategy="measured"

    # ------------------------------------------------------------------
    # Density ladder.
    # ------------------------------------------------------------------
    def capacity_tiers(self, algo: DeltaAlgorithm) -> list[CapacityTier]:
        """Ascending capacity rungs for ``algo`` (top = configured budgets).

        Collapses to a single rung when the ladder is off or the algorithm
        cannot re-emit at other capacities (no ``emit_factory``).
        """
        top = CapacityTier(self.src_capacity, self.edge_capacity,
                           self.seg_capacity)
        if self.ladder_tiers <= 1 or algo.emit_factory is None:
            return [top]
        tiers: list[CapacityTier] = []
        for i in range(self.ladder_tiers - 1, 0, -1):
            d = self.ladder_factor ** i
            t = CapacityTier(
                src=min(max(self.src_capacity // d, self.ladder_src_floor),
                        top.src),
                edge=min(max(self.edge_capacity // d, self.ladder_edge_floor),
                         top.edge),
                seg=min(max(self.seg_capacity // d, self.ladder_edge_floor),
                        top.seg))
            if t != top and (not tiers or t != tiers[-1]):
                tiers.append(t)
        tiers.append(top)
        return tiers

    def _emit_fn(self, algo: DeltaAlgorithm, tier: CapacityTier) -> Callable:
        if (algo.emit_factory is None
                or (tier.src, tier.edge) == (self.src_capacity,
                                             self.edge_capacity)):
            return algo.sparse_emit
        return algo.emit_factory(tier.src, tier.edge)

    # ------------------------------------------------------------------
    # Rehash strategy selection (per capacity rung, at trace time).
    # ------------------------------------------------------------------
    def pick_route_strategy(self, edge_capacity: int,
                            combiner: Optional[str]) -> str:
        """Physical combine-route implementation for a rung whose routed
        buffer holds ``edge_capacity`` slots.

        The scatter strategy merges deltas by construction (one slab cell
        per key), so a non-composable combiner forces the sort path.  In
        "auto" mode a static cost model compares sort work (C·log₂C) with
        scatter work (C scatter ops + one pass over the slab —
        ``padded_keys`` cells for the block scheme, ×num_shards for the
        hash scheme's per-owner rank counts).  ``route_scatter_weight``
        calibrates the per-element cost ratio (benchmarks/bench_rehash.py
        measures it; XLA CPU sorts are far costlier per element than
        scatters, hence the weight < 1).

        In "measured" mode the static model is bypassed entirely: the
        attached ``route_table`` (measured sort/scatter seconds per rung
        capacity on this backend, ``repro.obs.calibrate``) decides."""
        if self.route_strategy not in ("sort", "scatter", "auto",
                                       "measured"):
            raise ValueError(self.route_strategy)
        if combiner is None:
            return "sort"
        if self.route_strategy == "measured":
            if self.route_table is None:
                raise ValueError(
                    "route_strategy='measured' needs a route_table — "
                    "build one with repro.obs.calibrate."
                    "calibrate_executor_table(executor, algo) (eagerly, "
                    "before tracing) or RouteCostTable.from_bench_records")
            return self.route_table.pick(edge_capacity)
        if self.route_strategy != "auto":
            return self.route_strategy
        slab = self.snapshot.padded_keys
        if self.snapshot.scheme != "block":
            slab *= self.snapshot.num_shards
        c = max(edge_capacity, 2)
        sort_cost = c * math.log2(c)
        scatter_cost = self.route_scatter_weight * (c + slab)
        return "scatter" if scatter_cost < sort_cost else "sort"

    # ------------------------------------------------------------------
    # Sparse rehash (fused combine + route).
    # ------------------------------------------------------------------
    def route_code(self, tier: CapacityTier, combiner: Optional[str]) -> int:
        """Rehash implementation of a rung, fixed at trace time.

        The strategy comes from :meth:`pick_route_strategy`.  With
        ``use_pallas_route`` the rung runs that strategy's Pallas kernel
        when its segment capacity, the snapshot and the combiner are
        within the kernel's bounds, and the jnp implementation otherwise;
        the returned code (``ROUTE_*`` in ``core/fixpoint.py``) is what
        the stratum records."""
        strategy = self.pick_route_strategy(tier.edge, combiner)
        if strategy == "scatter":
            if self.use_pallas_route:
                from repro.kernels.scatter_route import scatter_route_fits
                if scatter_route_fits(self.snapshot, tier.seg, combiner):
                    return ROUTE_SCATTER_KERNEL
            return ROUTE_SCATTER
        if self.use_pallas_route:
            from repro.kernels.delta_route import route_fits
            if route_fits(tier.seg, self.snapshot.padded_keys):
                return ROUTE_SORT_KERNEL
        return ROUTE_SORT

    def _route_one(self, db: DeltaBuffer, seg_capacity: int,
                   combiner: Optional[str], route: int = ROUTE_SORT
                   ) -> DeltaBuffer:
        """Local half of the rehash: one shard's outgoing Δ -> per-owner
        segments.  With a composable ``combiner`` this is the FUSED
        combine-route — ``route`` picks the implementation (one
        lexicographic sort on (owner, key), the sort-free scatter-slab,
        or the Pallas kernel of either); without a combiner it is plain
        stable routing."""
        S = self.snapshot.num_shards
        if route == ROUTE_SCATTER_KERNEL:
            from repro.kernels import interpret_mode
            from repro.kernels.scatter_route import scatter_route_deltas
            return scatter_route_deltas(
                db, self.snapshot.owner_of(db.keys), S, seg_capacity,
                combiner, snapshot=self.snapshot, interpret=interpret_mode())
        if route == ROUTE_SORT_KERNEL:
            # Kernel path: §5.2 pre-aggregation (jnp) + the Pallas
            # routing kernel — property-tested equal to the fused
            # single-sort combine_route.
            from repro.core.handlers import pre_aggregate
            from repro.kernels import interpret_mode
            from repro.kernels.delta_route import route_deltas
            if combiner is not None:
                db = pre_aggregate(db, combiner)
            return route_deltas(db, self.snapshot.owner_of(db.keys), S,
                                seg_capacity,
                                max_key=self.snapshot.padded_keys,
                                interpret=interpret_mode())
        owners = self.snapshot.owner_of(db.keys)
        if route == ROUTE_SCATTER:
            return deltamod.combine_route_scatter(
                db, owners, S, seg_capacity, combiner,
                snapshot=self.snapshot)
        if combiner is not None:
            return deltamod.combine_route(db, owners, S, seg_capacity,
                                          combiner)
        return deltamod.route_by_owner(db, owners, S, seg_capacity)

    def rehash_sparse_simulated(self, stacked: DeltaBuffer,
                                seg_capacity: Optional[int] = None,
                                combiner: Optional[str] = None,
                                route: int = ROUTE_SORT
                                ) -> tuple[DeltaBuffer, jax.Array]:
        """stacked: [S] leading axis of per-shard outgoing Δ -> (incoming Δ,
        globally-summed routed delta count)."""
        S = self.snapshot.num_shards
        cap = self.seg_capacity if seg_capacity is None else seg_capacity
        routed = _over_shards(
            lambda db: self._route_one(db, cap, combiner, route), stacked)
        keys = routed.keys.reshape(S, S, cap)             # [src, dst, cap]
        payload = routed.payload.reshape(S, S, cap, -1)
        ann = routed.ann.reshape(S, S, cap)
        keys = jnp.swapaxes(keys, 0, 1)                   # [dst, src, cap]
        payload = jnp.swapaxes(payload, 0, 1)
        ann = jnp.swapaxes(ann, 0, 1)
        overflow = jnp.broadcast_to(jnp.any(routed.overflowed), (S,))

        def assemble(k, p, a, o):
            total = S * cap
            db = DeltaBuffer(keys=k.reshape(total),
                             payload=p.reshape(total, p.shape[-1]),
                             ann=a.reshape(total),
                             count=jnp.zeros((), jnp.int32), overflowed=o)
            return deltamod.recount(db)

        return jax.vmap(assemble)(keys, payload, ann, overflow), jnp.sum(
            routed.count)

    def rehash_sparse_shard_map(self, db: DeltaBuffer,
                                seg_capacity: Optional[int] = None,
                                combiner: Optional[str] = None,
                                route: int = ROUTE_SORT
                                ) -> tuple[DeltaBuffer, jax.Array]:
        S = self.snapshot.num_shards
        cap = self.seg_capacity if seg_capacity is None else seg_capacity
        routed = self._route_one(db, cap, combiner, route)
        keys = jax.lax.all_to_all(routed.keys.reshape(S, cap),
                                  self.axis_name, 0, 0, tiled=False)
        payload = jax.lax.all_to_all(
            routed.payload.reshape(S, cap, routed.payload_width),
            self.axis_name, 0, 0, tiled=False)
        ann = jax.lax.all_to_all(routed.ann.reshape(S, cap),
                                 self.axis_name, 0, 0, tiled=False)
        overflow = jax.lax.psum(routed.overflowed.astype(jnp.int32),
                                self.axis_name) > 0
        total = S * cap
        out = DeltaBuffer(keys=keys.reshape(total),
                          payload=payload.reshape(total, routed.payload_width),
                          ann=ann.reshape(total),
                          count=jnp.zeros((), jnp.int32), overflowed=overflow)
        return deltamod.recount(out), jax.lax.psum(routed.count,
                                                   self.axis_name)

    # ------------------------------------------------------------------
    # Dense rehash: contribution vectors -> summed local blocks.
    # ------------------------------------------------------------------
    def rehash_dense_simulated(self, contrib: jax.Array, combiner: str
                               ) -> jax.Array:
        """contrib: [S_src, n_padded, W] -> incoming [S_dst, block, W]."""
        S, block = self.snapshot.num_shards, self.snapshot.block_size
        w = contrib.shape[-1]
        seg = contrib.reshape(S, S, block, w)             # [src, dst, b, w]
        return _dense_combine(jnp.swapaxes(seg, 0, 1), combiner, axis=1)

    def rehash_dense_shard_map(self, contrib: jax.Array, combiner: str
                               ) -> jax.Array:
        """contrib: [n_padded, W] (one shard's view) -> [block, W]."""
        S, block = self.snapshot.num_shards, self.snapshot.block_size
        w = contrib.shape[-1]
        seg = jax.lax.all_to_all(contrib.reshape(S, block, w),
                                 self.axis_name, 0, 0, tiled=False)
        return _dense_combine(seg, combiner, axis=0)

    # ------------------------------------------------------------------
    # Stratum assembly.
    # ------------------------------------------------------------------
    def run(self, algo: DeltaAlgorithm, state0, live0, immutable,
            max_iters: int, mode: str = "delta",
            explicit_cond: Optional[Callable] = None) -> FixpointResult:
        """state0 / immutable carry a leading [S] shard axis in BOTH
        backends (shard_map splits that axis across devices).

        The fixpoint is one jitted computation taking the state, the live
        count and the immutable set as arguments: a second call with the
        same shapes and the same executor, algorithm, mode, ``max_iters``
        and ``explicit_cond`` reuses the compiled loop."""
        if mode not in ("delta", "nodelta"):
            raise ValueError(mode)
        self._check_backend()
        if self.tracer is not None:
            # Anchor shard timelines at dispatch so the first stratum's
            # measured duration excludes host setup (eager calls; under
            # an enclosing jit this runs once at trace time, which only
            # shifts the first measured stratum).
            self.tracer.mark_shards(self.snapshot.num_shards)
        return _fixpoint(
            state0, jnp.asarray(live0, jnp.int32), immutable,
            executor=self,
            observers=(_ByIdentity(self.tracer),
                       _ByIdentity(self.route_table)),
            algo=algo, mode=mode, max_iters=max_iters,
            explicit_cond=explicit_cond)

    def precompile(self, algo: DeltaAlgorithm, state0, immutable,
                   max_iters: int, mode: str = "delta",
                   explicit_cond: Optional[Callable] = None):
        """Compile the fixpoint loop :meth:`run` would run for these
        arguments (arrays or ``jax.ShapeDtypeStruct``s), without running
        it; a later ``run`` with equal shapes and settings reuses the
        executable.  Independent loops can be compiled from several
        threads at once.  Returns the ``jax.stages.Compiled``."""
        if mode not in ("delta", "nodelta"):
            raise ValueError(mode)
        self._check_backend()
        return _fixpoint.lower(
            state0, jax.ShapeDtypeStruct((), jnp.int32), immutable,
            executor=self,
            observers=(_ByIdentity(self.tracer),
                       _ByIdentity(self.route_table)),
            algo=algo, mode=mode, max_iters=max_iters,
            explicit_cond=explicit_cond).compile()

    def _fixpoint_loop(self, algo: DeltaAlgorithm, state0, live0,
                       immutable, max_iters: int, mode: str,
                       explicit_cond: Optional[Callable]) -> FixpointResult:
        """Traced body of :meth:`run`."""
        if self.backend == "simulated":
            stratum_fn = self._stratum_simulated(algo, immutable, mode)
        else:
            stratum_fn = self._stratum_shard_map(algo, mode)
        if explicit_cond is not None:
            stratum_fn = with_explicit_condition(stratum_fn, explicit_cond)
        if self.backend == "shard_map":
            return self._run_shard_map_loop(stratum_fn, state0, live0,
                                            immutable, max_iters)
        return run_strata(stratum_fn, state0, live0, max_iters,
                          tracer=self.tracer)

    def _check_backend(self) -> None:
        """Refuse an unknown backend, and a shard_map run whose shard
        count differs from the mesh axis: each device holds exactly one
        shard of the leading [S] axis."""
        if self.backend == "simulated":
            return
        if self.backend != "shard_map":
            raise ValueError(self.backend)
        if self.mesh is None:
            raise ValueError("backend='shard_map' needs a mesh")
        size = self.mesh.shape[self.axis_name]
        if size != self.snapshot.num_shards:
            raise ValueError(
                f"snapshot has {self.snapshot.num_shards} shards but mesh "
                f"axis {self.axis_name!r} has {size} devices; shard_map "
                "places exactly one shard on each device")

    # ------------------------------------------------------------------
    # Resume-from-state (incremental view maintenance).
    # ------------------------------------------------------------------
    def live_count(self, algo: DeltaAlgorithm, state, immutable) -> jax.Array:
        """Globally-reduced |Δ₀| of ``state``: how many keys would refine if
        the fixpoint were (re)entered right now.  This is the seed live
        count for :meth:`resume`.  Under shard_map each device counts its
        own shard and a ``psum`` reduces, so state and graph keep the one
        sharding they have."""
        self._check_backend()
        return _live_count(state, immutable, executor=self, algo=algo)

    def _live_count_body(self, algo: DeltaAlgorithm, state, immutable):
        if self.backend == "simulated":
            active, _ = _over_shards(algo.active_fn, state, immutable)
            return jnp.sum(active.astype(jnp.int32))

        def count(st, imm):
            active, _ = algo.active_fn(_squeeze(st), _squeeze(imm))
            return jax.lax.psum(jnp.sum(active.astype(jnp.int32)),
                                self.axis_name)

        spec = P(self.axis_name)
        return _shard_map(count, self.mesh, in_specs=(spec, spec),
                          out_specs=P())(state, immutable)

    def resume(self, algo: DeltaAlgorithm, warm_state, immutable,
               max_iters: int, mode: str = "delta",
               explicit_cond: Optional[Callable] = None) -> FixpointResult:
        """Re-enter the fixpoint from a previously-converged (then repaired)
        state instead of the base case.

        This is the engine half of incremental view maintenance
        (repro.incremental): a base-data mutation is translated into seed
        deltas by editing ``warm_state`` so that the affected keys fail the
        algorithm's convergence test; the fixpoint then propagates only the
        repair.  Δ₀ is derived from ``active_fn`` — no caller-supplied live
        count, so an unchanged state returns immediately with zero strata.

        With the density ladder enabled the per-stratum dispatch doubles as
        warm-start tier selection: a small repair's first stratum (and every
        tail stratum after it) lands on a tiny capacity rung, so incremental
        views pay O(|repair|)-scaled sort/scatter cost instead of the full
        configured capacity.
        """
        live0 = self.live_count(algo, warm_state, immutable)
        return self.run(algo, warm_state, live0, immutable, max_iters,
                        mode=mode, explicit_cond=explicit_cond)

    def make_stratum_fn(self, algo: DeltaAlgorithm, immutable,
                        mode: str = "delta",
                        explicit_cond: Optional[Callable] = None):
        """One-stratum function (state, idx) -> (state', outcome) for the
        stratum-sliced drivers (runtime/recovery.py) — identical semantics
        to the fused while_loop, on BOTH backends: one jitted dispatch of
        the simulated stratum body, or of the shard_map stratum (same
        specs as the fused loop), so a stratum-sliced run is bit-identical
        to ``run`` stratum for stratum.  The immutable set stays a runtime
        argument, as in ``run``: closing the jit over it would bake the
        graph into the computation as constants."""
        self._check_backend()
        if self.backend == "simulated":
            observers = (_ByIdentity(self.tracer),
                         _ByIdentity(self.route_table))
            return lambda state, idx: _stratum_step(
                state, immutable, idx, executor=self, observers=observers,
                algo=algo, mode=mode, explicit_cond=explicit_cond)
        stratum = self._stratum_shard_map(algo, mode)
        if explicit_cond is not None:
            stratum = with_explicit_condition(stratum, explicit_cond)
        spec = P(self.axis_name)

        def one(state, imm, idx):
            (new_state, _), outcome = stratum(
                (_squeeze(state), _squeeze(imm)), idx)
            return _expand(new_state), outcome

        fn = jax.jit(_shard_map(one, self.mesh, in_specs=(spec, spec, P()),
                                out_specs=(spec, P())))
        return lambda state, idx: fn(state, immutable, idx)

    # ------------------------------------------------------------------
    # Fault-tolerant elastic execution (runtime/recovery.py driver).
    # ------------------------------------------------------------------
    def run_resilient(self, algo: DeltaAlgorithm, state0, live0, immutable,
                      max_iters: int, mode: str = "delta",
                      explicit_cond: Optional[Callable] = None, *,
                      ckpt_root: str, fault_plan=None, policy=None,
                      latency_model=None, remake=None, metrics=None,
                      retry=None, budget=None, tracer=None):
        """``run`` with fault tolerance and elasticity: stratum-sliced
        execution that maintains a per-stratum replica chain of
        changed-entry deltas (paper §4.1), rebuilds a failed shard from
        replicas and resumes warm, migrates state + in-flight route
        buffers to a fresh partition snapshot on rescale, and
        speculatively re-issues straggling shards against their replica.

        A failure-free resilient run is bit-identical to :meth:`run`.
        Returns a ``runtime.recovery.ResilientResult`` whose ``result``
        matches ``run``'s FixpointResult; ``metrics`` carries the Fig 12
        work/byte accounting and all recovery events.  See
        :class:`repro.runtime.recovery.ResilientDriver` for the knobs.

        ``ckpt_root`` must be a dedicated directory: the replica chain
        owns it and DELETES any existing contents at query start.
        """
        from repro.runtime.recovery import ResilientDriver
        driver = ResilientDriver(
            self, algo, state0, live0, immutable, max_iters, mode=mode,
            explicit_cond=explicit_cond, ckpt_root=ckpt_root,
            fault_plan=fault_plan, policy=policy,
            latency_model=latency_model, remake=remake, metrics=metrics,
            retry=retry, budget=budget, tracer=tracer)
        return driver.run()

    def resume_resilient(self, algo: DeltaAlgorithm, warm_state, immutable,
                         max_iters: int, mode: str = "delta",
                         explicit_cond: Optional[Callable] = None,
                         **resilient_kw):
        """:meth:`resume` (warm re-entry, Δ₀ from ``active_fn``) through
        the fault-tolerant driver — incremental views use this so standing
        queries survive executor failures mid-repair."""
        live0 = self.live_count(algo, warm_state, immutable)
        return self.run_resilient(algo, warm_state, live0, immutable,
                                  max_iters, mode=mode,
                                  explicit_cond=explicit_cond,
                                  **resilient_kw)

    # ---- simulated backend ------------------------------------------------
    def _stratum_simulated(self, algo: DeltaAlgorithm, immutable, mode):
        S = self.snapshot.num_shards
        shard_ids = jnp.arange(S, dtype=jnp.int32)
        tiers = self.capacity_tiers(algo)
        # Sender-side combiner (§5.2) is fused into the route: merging
        # deltas sharing a key BEFORE the rehash shrinks collective bytes
        # exactly as the paper's pre-aggregation pushdown prescribes, and
        # the fused single-sort pass halves the per-stratum sort work.
        combiner = (algo.combiner
                    if algo.combiner in ("add", "min", "max") else None)

        def make_sparse_body(tier: CapacityTier, tier_idx: int):
            emit_fn = self._emit_fn(algo, tier)
            # Physical rehash implementation is a per-rung trace-time
            # constant, made from the rung's static capacities.
            route_code = self.route_code(tier, combiner)

            def sparse_body(state, stratum, active):
                partial_state, outgoing = _over_shards(
                    emit_fn, state, immutable, active, stratum, shard_ids,
                    in_axes=(0, 0, 0, None, 0))
                incoming, emitted = self.rehash_sparse_simulated(
                    outgoing, seg_capacity=tier.seg, combiner=combiner,
                    route=route_code)
                new_state, next_active = _over_shards(
                    algo.apply_sparse, partial_state, incoming, immutable,
                    stratum, shard_ids, in_axes=(0, 0, 0, None, 0))
                bytes_moved = emitted.astype(
                    jnp.float32) * algo.bytes_per_delta
                return new_state, StratumOutcome(
                    live_count=jnp.sum(next_active),
                    used_dense=jnp.asarray(False),
                    rehash_bytes=bytes_moved, emitted=emitted,
                    tier=jnp.asarray(tier_idx, jnp.int32),
                    route=jnp.asarray(route_code, jnp.int32))

            return sparse_body

        def dense_body(state, stratum, active):
            partial_state, contrib = _over_shards(
                algo.dense_emit, state, immutable, stratum, shard_ids,
                in_axes=(0, 0, None, 0))
            incoming = self.rehash_dense_simulated(contrib, algo.combiner)
            new_state, next_active = _over_shards(
                algo.apply_dense, partial_state, incoming, immutable,
                stratum, shard_ids, in_axes=(0, 0, 0, None, 0))
            n_padded = contrib.shape[1]
            bytes_moved = jnp.asarray(
                S * n_padded * algo.payload_width * 4, jnp.float32)
            return new_state, StratumOutcome(
                live_count=jnp.sum(next_active),
                used_dense=jnp.asarray(True),
                rehash_bytes=bytes_moved,
                emitted=jnp.sum(active.astype(jnp.int32)),
                tier=jnp.asarray(-1, jnp.int32),
                route=jnp.asarray(-1, jnp.int32))

        bodies = [make_sparse_body(t, i) for i, t in enumerate(tiers)]
        bodies.append(dense_body)

        def stratum(state, stratum_idx):
            active, est_edges = _over_shards(algo.active_fn, state,
                                             immutable)
            per_shard_src = jnp.sum(active.astype(jnp.int32), axis=1)
            if mode == "nodelta":
                new_state, outcome = dense_body(state, stratum_idx, active)
            else:
                # Smallest rung whose budgets cover the exact predicted
                # sizes; tiers ascend, so "fits" is monotone and the rung
                # index is len(tiers) − (#rungs that fit).  No rung fits
                # -> dense body.  The seg budget is guarded too: one
                # shard's emission can land entirely in one destination
                # segment, so a rung with seg < edge must also cover the
                # edge count or deltas would be silently dropped by the
                # route.
                max_src = jnp.max(per_shard_src)
                max_edges = jnp.max(est_edges)
                fits = jnp.stack([(max_src <= t.src)
                                  & (max_edges <= min(t.edge, t.seg))
                                  for t in tiers])
                branch = len(tiers) - jnp.sum(fits.astype(jnp.int32))
                new_state, outcome = jax.lax.switch(
                    branch, bodies, state, stratum_idx, active)
            if self.tracer is not None:
                # One probe per stratum (all shards share the device);
                # ordered keeps arrival deltas = stratum wall clock even
                # inside the while_loop.
                self.tracer.stratum_probe(stratum_idx, outcome,
                                          ordered=True)
            return new_state, outcome

        return stratum

    # ---- shard_map backend --------------------------------------------
    def _stratum_shard_map(self, algo: DeltaAlgorithm, mode):
        axis = self.axis_name
        S = self.snapshot.num_shards
        tiers = self.capacity_tiers(algo)
        combiner = (algo.combiner
                    if algo.combiner in ("add", "min", "max") else None)

        def stratum(carry, stratum_idx):
            state, imm = carry
            shard_id = jax.lax.axis_index(axis)
            active, est_edges = algo.active_fn(state, imm)
            n_src = jnp.sum(active.astype(jnp.int32))

            def make_sparse_body(tier: CapacityTier, tier_idx: int):
                emit_fn = self._emit_fn(algo, tier)
                # Trace-time constant, identical on every shard (pure
                # function of static rung capacities).
                route_code = self.route_code(tier, combiner)

                def sparse_body(st):
                    partial_state, outgoing = emit_fn(
                        st, imm, active, stratum_idx, shard_id)
                    incoming, emitted = self.rehash_sparse_shard_map(
                        outgoing, seg_capacity=tier.seg, combiner=combiner,
                        route=route_code)
                    new_state, next_active = algo.apply_sparse(
                        partial_state, incoming, imm, stratum_idx, shard_id)
                    return (new_state, imm), StratumOutcome(
                        live_count=jax.lax.psum(next_active, axis),
                        used_dense=jnp.asarray(False),
                        rehash_bytes=emitted.astype(jnp.float32)
                        * algo.bytes_per_delta,
                        emitted=emitted,
                        tier=jnp.asarray(tier_idx, jnp.int32),
                        route=jnp.asarray(route_code, jnp.int32))

                return sparse_body

            def dense_body(st):
                partial_state, contrib = algo.dense_emit(
                    st, imm, stratum_idx, shard_id)
                incoming = self.rehash_dense_shard_map(contrib, algo.combiner)
                new_state, next_active = algo.apply_dense(
                    partial_state, incoming, imm, stratum_idx, shard_id)
                n_padded = contrib.shape[0]
                return (new_state, imm), StratumOutcome(
                    live_count=jax.lax.psum(next_active, axis),
                    used_dense=jnp.asarray(True),
                    rehash_bytes=jnp.asarray(
                        S * n_padded * algo.payload_width * 4, jnp.float32),
                    emitted=jax.lax.psum(n_src, axis),
                    tier=jnp.asarray(-1, jnp.int32),
                    route=jnp.asarray(-1, jnp.int32))

            if mode == "nodelta":
                carry_out, outcome = dense_body(state)
            else:
                # Globally-reduced predicted sizes -> every shard picks
                # the same rung (the dispatch feeds a collective-bearing
                # branch).  The seg budget is guarded like the simulated
                # backend.
                max_src = jax.lax.pmax(n_src, axis)
                max_edges = jax.lax.pmax(est_edges, axis)
                fits = jnp.stack([(max_src <= t.src)
                                  & (max_edges <= min(t.edge, t.seg))
                                  for t in tiers])
                branch = len(tiers) - jnp.sum(fits.astype(jnp.int32))
                bodies = [make_sparse_body(t, i)
                          for i, t in enumerate(tiers)]
                bodies.append(dense_body)
                carry_out, outcome = jax.lax.switch(branch, bodies, state)
            if self.tracer is not None:
                # Per-shard probe: each device calls back with its own
                # shard id, so arrival times are per-shard stratum
                # latencies.  Unordered — ordered effects cannot cross
                # the shard_map collectives.
                self.tracer.stratum_probe(stratum_idx, outcome,
                                          shard_id=shard_id, ordered=False)
            return carry_out, outcome

        return stratum

    def _run_shard_map_loop(self, stratum_fn, state0, live0, immutable,
                            max_iters):
        def body(state, imm, live):
            state, imm = _squeeze(state), _squeeze(imm)
            res = run_strata(stratum_fn, (state, imm), live, max_iters)
            final_state, _ = res.state
            return FixpointResult(state=_expand(final_state),
                                  stats=res.stats)

        spec = P(self.axis_name)
        fn = _shard_map(body, self.mesh, in_specs=(spec, spec, P()),
                        out_specs=FixpointResult(state=spec, stats=P()))
        res = fn(state0, immutable, live0)
        if self.tracer is not None:
            # Fixpoint marker outside the shard_map (replicated stats —
            # one probe, not one per shard).
            self.tracer.fixpoint_probe(res.stats.iterations, max_iters)
        return res


class _ByIdentity:
    """Static jit-key wrapper equal only to a wrapper of the same object.

    The executor's tracer and route table take no part in its equality,
    yet they change the traced loop: keying the compiled loop on their
    identity keeps executors that differ only there apart."""

    __slots__ = ("obj",)

    def __init__(self, obj):
        self.obj = obj

    def __hash__(self) -> int:
        return id(self.obj)

    def __eq__(self, other) -> bool:
        return isinstance(other, _ByIdentity) and other.obj is self.obj


@partial(jax.jit, static_argnames=("executor", "observers", "algo", "mode",
                                   "max_iters", "explicit_cond"))
def _fixpoint(state0, live0, immutable, *, executor, observers, algo, mode,
              max_iters, explicit_cond) -> FixpointResult:
    del observers  # part of the compile key only
    return executor._fixpoint_loop(algo, state0, live0, immutable,
                                   max_iters, mode, explicit_cond)


@partial(jax.jit, static_argnames=("executor", "observers", "algo", "mode",
                                   "explicit_cond"))
def _stratum_step(state, immutable, idx, *, executor, observers, algo, mode,
                  explicit_cond):
    del observers  # part of the compile key only
    fn = executor._stratum_simulated(algo, immutable, mode)
    if explicit_cond is not None:
        fn = with_explicit_condition(fn, explicit_cond)
    return fn(state, idx)


@partial(jax.jit, static_argnames=("executor", "algo"))
def _live_count(state, immutable, *, executor, algo):
    return executor._live_count_body(algo, state, immutable)
