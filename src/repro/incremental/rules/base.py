"""Rule protocol, registry, and seed-delta helpers.

A mutation batch becomes a set of **seed deltas** over the converged
state — host-built :class:`~repro.core.delta.DeltaBuffer`s carrying the
paper's annotations: ``−()`` invalidates derived values the batch may have
broken, ``→(t')`` replaces a value with a known-better bound, and ``δ(E)``
adjusts accumulated aggregates.  Applying the seeds edits the warm state so
that exactly the repaired keys fail the algorithm's convergence test; the
engine's ``resume`` then propagates the repair, doing O(|repair|) work
instead of a cold O(|base data| × strata) rerun.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import numpy as np

import jax.numpy as jnp

from repro.core.delta import DeltaBuffer

_REGISTRY: dict[str, Callable[[], "IncrementalRule"]] = {}


def register(name: str):
    """Class decorator: make a rule constructible by algorithm name."""

    def deco(cls):
        cls.algorithm = name
        _REGISTRY[name] = cls
        return cls

    return deco


def get_rule(name: str) -> "IncrementalRule":
    if name not in _REGISTRY:
        raise KeyError(
            f"no incremental rule registered for {name!r}; known: "
            f"{sorted(_REGISTRY)}")
    return _REGISTRY[name]()


def registered() -> list[str]:
    return sorted(_REGISTRY)


@dataclasses.dataclass
class RepairPlan:
    """Outcome of translating one batch into seed deltas.

    ``state`` is the repaired (still host/device mixed) warm state;
    ``touched_keys`` drives the ViewManager's repair-vs-recompute policy;
    ``seeds`` records the DeltaBuffers that were folded in, for
    introspection and tests.
    """

    state: object
    touched_keys: int
    seeds: dict[str, DeltaBuffer] = dataclasses.field(default_factory=dict)


def make_seed(keys: np.ndarray, payload: np.ndarray, ann: int
              ) -> DeltaBuffer:
    """Host-built seed Δ buffer sized exactly to the batch (host code has
    no static-shape constraint — only the device fixpoint does)."""
    keys = np.asarray(keys, np.int32)
    payload = np.asarray(payload, np.float32)
    if payload.ndim == 1:
        payload = payload[:, None]
    n = len(keys)
    return DeltaBuffer(
        keys=jnp.asarray(keys),
        payload=jnp.asarray(payload),
        ann=jnp.full((n,), ann, jnp.int8),
        count=jnp.asarray(n, jnp.int32),
        overflowed=jnp.asarray(False))


class IncrementalRule:
    """Abstract per-algorithm repair rule.

    Lifecycle: ``bind(view)`` once at view creation (build the
    DeltaAlgorithm, executor, and jitted cold/resume callables against the
    store's pinned shapes); ``cold(view)`` for a from-scratch fixpoint;
    ``repair(view, effect, state)`` to translate one sealed batch;
    ``resume(view, state)`` to re-converge; ``extract(view, state)`` to
    produce the queryable result.  ``rebind`` is called when pinned
    capacities grew (one re-trace).
    """

    algorithm: str = "?"

    def bind(self, view) -> None:
        raise NotImplementedError

    def rebind(self, view) -> None:
        self.bind(view)

    def cold(self, view):
        """-> (state, FixpointResult)"""
        raise NotImplementedError

    def repair(self, view, effect, state) -> RepairPlan:
        raise NotImplementedError

    def resume(self, view, state):
        """-> (state, FixpointResult)"""
        raise NotImplementedError

    def extract(self, view, state) -> np.ndarray:
        raise NotImplementedError

    def state_template(self, view):
        """A zero-cost state pytree with the view's shapes — journal
        recovery uses it as the ``like`` argument when reloading."""
        raise NotImplementedError


class GraphRuleBase(IncrementalRule):
    """Shared machinery for rules over the sharded graph engine: builds the
    partition snapshot, executor, and jitted cold/resume callables; exposes
    flat <-> sharded state helpers for the host-side seed translation."""

    def bind(self, view) -> None:
        import jax

        from repro.core.engine import ShardedExecutor
        from repro.core.partition import PartitionSnapshot

        n, S = view.store.n, view.store.num_shards
        self.snapshot = PartitionSnapshot(n_keys=n, num_shards=S)
        self.edge_capacity = int(view.params.get(
            "edge_capacity", max(4 * n, 4096)))
        self.src_capacity = int(view.params.get(
            "src_capacity", self.snapshot.block_size))
        # Warm resumes run with a much tighter Δ budget: repairs are small
        # by construction, sparse-stratum cost is O(capacity) (static
        # shapes), and a flooding repair just falls back to the dense body
        # — correctness never depends on the budget.
        self.resume_edge_capacity = int(view.params.get(
            "resume_edge_capacity", max(self.edge_capacity // 8, 1024)))
        self.resume_src_capacity = int(view.params.get(
            "resume_src_capacity", max(self.src_capacity // 8, 64)))
        self.max_iters = int(view.params.get("max_iters", 80))
        self.mode = view.params.get("mode", "delta")
        # Density ladder (core/engine.py): per-stratum dispatch to the
        # smallest capacity rung that fits the predicted emission.  On the
        # resume executor this doubles as warm-start tier selection — a
        # small repair's strata run at tiny capacities for free.
        self.ladder_tiers = int(view.params.get("ladder_tiers", 4))
        # Rehash strategy (sort | scatter | auto): warm repairs are the
        # tail-stratum regime the scatter path targets, so default to the
        # per-rung cost model instead of pinning the sort.
        self.route_strategy = view.params.get("route_strategy", "auto")
        # Fault-tolerant warm resumes: with a "resilient_root" param the
        # repair fixpoint runs through ShardedExecutor.run_resilient — a
        # per-stratum replica chain under that directory absorbs executor
        # shard failures mid-repair (inject one for tests by setting
        # ``view.fault_plan``), so standing queries survive engine
        # failures without losing the in-flight repair.
        self.resilient_root = view.params.get("resilient_root")
        # Execution backend: views ran pinned to the simulated backend
        # before; backend/mesh/axis_name now flow through to both
        # executors so warm resumes run real-SPMD under shard_map too.
        backend_kw = dict(
            backend=view.params.get("backend", "simulated"),
            mesh=view.params.get("mesh"),
            axis_name=view.params.get("axis_name", "shards"),
            route_strategy=self.route_strategy,
            use_pallas_route=bool(view.params.get("use_pallas_route",
                                                  False)))
        self.executor = ShardedExecutor(
            snapshot=self.snapshot, seg_capacity=self.edge_capacity,
            edge_capacity=self.edge_capacity, src_capacity=self.src_capacity,
            ladder_tiers=self.ladder_tiers, **backend_kw)
        self.resume_executor = ShardedExecutor(
            snapshot=self.snapshot, seg_capacity=self.resume_edge_capacity,
            edge_capacity=self.resume_edge_capacity,
            src_capacity=self.resume_src_capacity,
            ladder_tiers=self.ladder_tiers, **backend_kw)
        self.algo = self.make_algo(view, self.src_capacity,
                                   self.edge_capacity)
        self.resume_algo = self.make_algo(view, self.resume_src_capacity,
                                          self.resume_edge_capacity)

    def make_algo(self, view, src_capacity: int, edge_capacity: int):
        raise NotImplementedError

    def cold_impl(self, graph):
        """-> FixpointResult (shapes pinned by the store, so every refresh
        reuses the executor's compiled loop)."""
        raise NotImplementedError

    def cold(self, view):
        res = self.cold_impl(view.immutable)
        return res.state, res

    def resume(self, view, state):
        fault_plan = getattr(view, "fault_plan", None)
        retry = getattr(view, "retry_policy", None)
        budget = getattr(view, "retry_budget", None)
        if self.resilient_root is None and fault_plan is None \
                and retry is None and budget is None:
            res = self.resume_executor.resume(
                self.resume_algo, state, view.immutable, self.max_iters,
                mode=self.mode)
            return res.state, res
        import shutil
        import tempfile
        # No configured root: a throwaway unique dir per repair — the
        # chain only needs to outlive this one resume (a fixed path
        # could collide across processes, and ReplicaChain wipes its
        # root on construction), so it is removed afterwards.
        root = self.resilient_root or tempfile.mkdtemp(
            prefix="rex_view_chain_")
        try:
            rr = self.resume_executor.resume_resilient(
                self.resume_algo, state, view.immutable, self.max_iters,
                mode=self.mode, ckpt_root=root, fault_plan=fault_plan,
                retry=retry, budget=budget)
        finally:
            if self.resilient_root is None:
                shutil.rmtree(root, ignore_errors=True)
            # Consumed even when the resume fails — a degraded view's
            # catch-up refresh must not re-inject the same faults.
            view.fault_plan = None
        view.last_recovery = rr.metrics
        return rr.result.state, rr.result

    # ---- flat <-> sharded helpers ---------------------------------------
    def flat64(self, field) -> np.ndarray:
        """[S, block] device array -> f64[padded_keys] host array."""
        return np.asarray(field, np.float64).reshape(-1)

    def shard_f32(self, flat: np.ndarray):
        S, B = self.snapshot.num_shards, self.snapshot.block_size
        return jnp.asarray(flat.astype(np.float32).reshape(S, B))
