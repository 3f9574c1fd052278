"""Fixpoint observability: tracing, metrics, exporters, calibration.

``obs`` is the measurement layer the rest of the engine reports into —
and reads back from.  ``obs.trace.span`` marks the program's entry points
(``rex.<algo>.run``, ``rex.executor.run``/``resume`` and their
``prepare``/``dispatch`` children, ``rex.precompile``, ``rex.shard_csr``)
as ``jax.profiler`` annotations, so a profiler trace names the host time
around the device's work; the device time of each layer of a stratum is
read from the engine's ``rex.*`` named scopes in the same trace.

A :class:`~repro.obs.trace.Tracer` threaded into ``ShardedExecutor``
records a probe per stratum from inside ``lax.while_loop``/``shard_map``
(via ``jax.debug.callback``): its durations are gaps between host
arrivals, not device time, and the callbacks change the compiled loop.
A :class:`~repro.obs.metrics.MetricsRegistry` accumulates counters,
gauges and histograms; ``obs.export`` renders Perfetto-loadable timelines
and flat metric dumps; and ``obs.calibrate`` turns recorded route timings
into the measured dispatch table behind ``route_strategy="measured"``.

With no tracer/registry attached (the default) no callback is compiled
in; the named scopes are HLO metadata only, so outputs are bit-identical
either way.
"""
from repro.obs.calibrate import (RouteCostTable, calibrate_executor_table,
                                 calibrate_route_table)
from repro.obs.export import (metrics_to_json, to_chrome_trace,
                              write_chrome_trace, write_metrics)
from repro.obs.metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                               default_registry, reset_default_registry)
from repro.obs.trace import MeasuredLatencies, Tracer, span

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "default_registry", "reset_default_registry",
    "Tracer", "MeasuredLatencies", "span",
    "to_chrome_trace", "write_chrome_trace", "metrics_to_json",
    "write_metrics",
    "RouteCostTable", "calibrate_route_table", "calibrate_executor_table",
]
