"""REX core: delta-based recursive data-centric computation (paper §3–§5).

Public surface:
  DeltaBuffer, annotations          repro.core.delta
  Aggregator / delta handlers       repro.core.handlers
  Relational operators              repro.core.operators
  Stratified fixpoint driver        repro.core.fixpoint
  Partition snapshots               repro.core.partition
  Sharded execution (rehash)        repro.core.engine
  Plan IR + cost-based optimizer    repro.core.plan / repro.core.optimizer
"""
from repro.core.delta import (ANN_ADJUST, ANN_DELETE, ANN_INSERT, ANN_REPLACE,
                              PAD_KEY, DeltaBuffer, combine_route,
                              combine_route_scatter)
from repro.core.engine import CapacityTier, DeltaAlgorithm, ShardedExecutor
from repro.core.fixpoint import (ROUTE_SCATTER, ROUTE_SCATTER_KERNEL,
                                 ROUTE_SORT, ROUTE_SORT_KERNEL,
                                 FixpointResult, StratumOutcome,
                                 StratumStats, run_strata,
                                 with_explicit_condition)
from repro.core.handlers import BUILTIN_UDAS, Aggregator
from repro.core.partition import PartitionSnapshot

__all__ = [
    "ANN_ADJUST", "ANN_DELETE", "ANN_INSERT", "ANN_REPLACE", "PAD_KEY",
    "DeltaBuffer", "combine_route", "combine_route_scatter", "CapacityTier",
    "DeltaAlgorithm", "ShardedExecutor", "FixpointResult",
    "ROUTE_SORT", "ROUTE_SCATTER", "ROUTE_SORT_KERNEL",
    "ROUTE_SCATTER_KERNEL",
    "StratumOutcome", "StratumStats", "run_strata",
    "with_explicit_condition", "BUILTIN_UDAS", "Aggregator",
    "PartitionSnapshot",
]
