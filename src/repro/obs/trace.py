"""Host spans on the profiler's clock, and an in-jit probe path.

Two recording surfaces share one event buffer:

  * **Host spans** — ``with tracer.span("view.refresh", view=name):`` for
    driver-side code (the resilient driver's stratum slices, view repairs,
    replica writes).  Durations are real ``perf_counter`` intervals, and
    each span is also a ``jax.profiler.TraceAnnotation`` of the same name,
    so a profiler trace shows it on the host plane beside the device's
    operations.  Code with no tracer uses the module-level :func:`span`,
    which always annotates and records only into a tracer it is given.
  * **In-jit probes** — ``tracer.stratum_probe(...)`` is called at *trace
    time* inside the engine's stratum bodies and inserts a
    ``jax.debug.callback`` whose operands are the stratum's outcome
    scalars.  The callback survives ``lax.while_loop``, ``lax.switch`` and
    ``shard_map`` and fires on the host when the device reaches it, so
    each probe's duration is the gap between two host arrivals: host
    time, not device time, and the callbacks themselves change the
    compiled loop (an ordered callback syncs the host every stratum).
    Probes are emitted only when a tracer is threaded in —
    ``tracer=None`` leaves the compiled computation untouched.

Device time per layer comes from elsewhere: the engine wraps each layer of
a stratum in a ``jax.named_scope`` (``rex.select``, ``rex.rung<k>``,
``rex.dense``, ``rex.emit``, ``rex.route``, ``rex.apply``, ``rex.loop``),
which the compiled program carries as HLO metadata, so a profiler trace's
operations can be attributed to them without any callback.

Timestamps are ``perf_counter`` seconds relative to the tracer's epoch;
``obs/export.py`` converts to the Chrome-trace µs timeline.  Probe
ordering: the simulated backend uses ordered callbacks (strict program
order); shard_map uses unordered ones (ordered effects cannot cross a
collective), so events carry their stratum index and the exporter orders
by it, not by arrival.

:class:`MeasuredLatencies` is the per-shard timing source the resilient
driver feeds to ``SpeculationPolicy`` when no synthetic
``latency_model`` is supplied, and ``obs/calibrate.py`` turns recorded
per-rung route timings into the ``route_strategy="measured"`` table.
"""
from __future__ import annotations

import contextlib
import threading
import time
from typing import Dict, List, Optional

import jax

from repro.obs.metrics import MetricsRegistry

# StratumOutcome.tier / .route use -1 for "dense / not applicable".
_DENSE = -1


class Tracer:
    """Append-only event recorder (host spans + in-jit probe arrivals).

    Events are dicts with ``name``, ``ph`` ("X" span / "i" instant),
    ``ts`` (start, seconds since epoch), ``dur`` (spans), ``tid`` (host
    thread or ``shard<k>``), and free-form ``args``.  Thread-safe: jit
    callbacks may arrive from runtime threads.
    """

    def __init__(self, name: str = "rex",
                 metrics: Optional[MetricsRegistry] = None,
                 clock=time.perf_counter):
        self.name = name
        self.metrics = metrics
        self._clock = clock
        self.epoch = clock()
        self.events: List[dict] = []
        self._lock = threading.Lock()
        # Last probe arrival per tid — the previous stratum boundary, used
        # to turn arrival times into per-stratum durations.
        self._last_ts: Dict[str, float] = {}
        # (stratum, shard) -> (start, dur) of the most recent probe, the
        # index MeasuredLatencies / the resilient driver query.
        self._stratum_times: Dict[tuple, tuple] = {}

    # ------------------------------------------------------------------
    # Host-side recording.
    # ------------------------------------------------------------------
    def _now(self) -> float:
        return self._clock() - self.epoch

    def _append(self, ev: dict) -> None:
        with self._lock:
            self.events.append(ev)

    @contextlib.contextmanager
    def span(self, name: str, tid: str = "host", **attrs):
        """Record a complete (ph "X") event around a host-side block, and
        annotate the block for the profiler under the same name.
        Yields the args dict — mutate it to attach results measured
        inside the span."""
        t0 = self._now()
        args = dict(attrs)
        try:
            with jax.profiler.TraceAnnotation(name):
                yield args
        finally:
            self._append({"name": name, "ph": "X", "ts": t0,
                          "dur": self._now() - t0, "tid": tid,
                          "args": args})

    def instant(self, name: str, tid: str = "host", **attrs) -> None:
        """Record a point event (recovery, rescale, speculation verdict)."""
        self._append({"name": name, "ph": "i", "ts": self._now(),
                      "tid": tid, "args": dict(attrs)})

    def mark_shards(self, num_shards: int) -> None:
        """Anchor every shard timeline (and the aggregate "shards" row)
        at now — the stratum-dispatch boundary, so the next probe's
        duration measures device work only, not host time in between."""
        now = self._now()
        with self._lock:
            self._last_ts["shards"] = now
            for s in range(num_shards):
                self._last_ts[f"shard{s}"] = now

    # ------------------------------------------------------------------
    # In-jit probes (trace-time insertion, host-side arrival).
    # ------------------------------------------------------------------
    def _on_stratum(self, stratum, emitted, tier, route, rehash_bytes,
                    used_dense, live, shard) -> None:
        now = self._now()
        stratum = int(stratum)
        shard = int(shard)
        tid = "shards" if shard < 0 else f"shard{shard}"
        with self._lock:
            start = self._last_ts.get(tid, self.epoch - self.epoch)
            self._last_ts[tid] = now
        dur = max(now - start, 0.0)
        self._stratum_times[(stratum, shard)] = (start, dur)
        self._append({"name": f"stratum{stratum}", "ph": "X", "ts": start,
                      "dur": dur, "tid": tid,
                      "args": {"stratum": stratum, "emitted": int(emitted),
                               "tier": int(tier), "route": int(route),
                               "rehash_bytes": float(rehash_bytes),
                               "used_dense": bool(used_dense),
                               "live_after": int(live)}})
        if self.metrics is not None:
            m = self.metrics
            m.counter("engine.strata").inc()
            m.counter("engine.deltas_emitted").inc(int(emitted))
            m.counter("engine.rehash_bytes").inc(float(rehash_bytes))
            if bool(used_dense):
                m.counter("engine.dense_fallbacks").inc()
            m.histogram("engine.stratum_seconds").observe(dur)
            m.gauge("engine.live_deltas").set(int(live))

    def stratum_probe(self, stratum_idx, outcome, shard_id=None,
                      ordered: bool = True) -> None:
        """Insert the per-stratum callback into the traced computation.

        Called from the engine's stratum bodies with traced scalars;
        ``shard_id`` is ``lax.axis_index`` under shard_map (per-shard
        arrival times) and None on the simulated backend (one probe per
        stratum, tid "shards").  ``ordered=False`` is required wherever
        ordered effects are unsupported (shard_map bodies).
        """
        import jax.numpy as jnp
        shard = jnp.asarray(-1) if shard_id is None else shard_id
        jax.debug.callback(self._on_stratum, stratum_idx, outcome.emitted,
                           outcome.tier, outcome.route,
                           outcome.rehash_bytes, outcome.used_dense,
                           outcome.live_count, shard, ordered=ordered)

    def _on_fixpoint(self, iterations, max_iters) -> None:
        self.instant("fixpoint_done", iterations=int(iterations),
                     max_iters=int(max_iters))
        if self.metrics is not None:
            self.metrics.counter("engine.fixpoints").inc()
            self.metrics.gauge("engine.last_fixpoint_strata").set(
                int(iterations))

    def fixpoint_probe(self, iterations, max_iters: int) -> None:
        """Fixpoint-complete marker (fires once per ``run``)."""
        jax.debug.callback(self._on_fixpoint, iterations, max_iters,
                           ordered=False)

    # ------------------------------------------------------------------
    # Measured-timing queries.
    # ------------------------------------------------------------------
    def stratum_seconds(self, stratum: int, shard: int = -1
                        ) -> Optional[float]:
        """Measured wall time of a recorded stratum probe (None if that
        (stratum, shard) never fired)."""
        hit = self._stratum_times.get((int(stratum), int(shard)))
        return None if hit is None else hit[1]

    def per_shard_latencies(self, stratum: int, num_shards: int,
                            default: Optional[float] = None
                            ) -> Optional[List[float]]:
        """Per-shard measured latencies for one stratum — the feed for
        ``SpeculationPolicy``.  Under shard_map every shard probes
        individually; on the simulated backend only the aggregate probe
        exists, so ``default`` (typically the driver's host-side stratum
        wall) fills all shards.  Returns None when nothing was measured
        and no default is given."""
        out = []
        for s in range(num_shards):
            t = self.stratum_seconds(stratum, s)
            if t is None:
                t = self.stratum_seconds(stratum, -1)
            if t is None:
                t = default
            if t is None:
                return None
            out.append(float(t))
        return out

    def clear(self) -> None:
        with self._lock:
            self.events.clear()
            self._last_ts.clear()
            self._stratum_times.clear()


@contextlib.contextmanager
def span(name: str, tracer: Optional[Tracer] = None, **attrs):
    """A host span for code that may have no tracer: always a
    ``jax.profiler.TraceAnnotation`` called ``name``, and a recorded
    :meth:`Tracer.span` only when ``tracer`` is given.  Yields the span's
    args dict either way.  Inserts nothing into a jitted program."""
    if tracer is not None:
        with tracer.span(name, **attrs) as args:
            yield args
    else:
        with jax.profiler.TraceAnnotation(name):
            yield dict(attrs)


class MeasuredLatencies:
    """Recorded per-shard stratum timings, callable like the synthetic
    ``latency_model(stratum) -> [seconds per shard]`` the resilient driver
    consumed before — measurement replacing extrapolation (ROADMAP item 5).

    The driver appends one list per executed stratum (tracer per-shard
    probes when available, host stratum wall otherwise)."""

    def __init__(self):
        self.latencies: List[List[float]] = []

    def observe(self, per_shard: List[float]) -> None:
        self.latencies.append([float(x) for x in per_shard])

    def __len__(self) -> int:
        return len(self.latencies)

    def __call__(self, stratum: int) -> List[float]:
        if not self.latencies:
            raise ValueError("no measured latencies recorded yet")
        # Strata are appended in execution order; a restart re-executes
        # early strata, so index from the END (most recent measurement).
        idx = min(int(stratum), len(self.latencies) - 1)
        return list(self.latencies[idx])
