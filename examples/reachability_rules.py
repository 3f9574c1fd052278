"""Reachability written purely as rules — no handwritten DeltaAlgorithm.

The program below is the whole algorithm: parse it, compile it through the
plan IR + optimizer + lowering, and run it on the sharded engine.  Nothing
in ``algorithms/`` or ``core/`` knows reachability exists.

  PYTHONPATH=src python examples/reachability_rules.py [--quick]
"""
import argparse

import numpy as np

from repro import frontend as F
from repro.algorithms import sssp
from repro.compile_cache import enable_compile_cache
from repro.core.partition import PartitionSnapshot
from repro.core.plan import plan_runtime
from repro.data.graphs import DATASETS, load_dataset, make_powerlaw_graph

RULES = """
program reachability.
input edge(u, v).
reach(0) := 1.0.                      # the source vertex is reachable
reach(v) max= reach(u) :- edge(u, v). # reachability propagates over edges
"""


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="small dataset / fewer shards (CI smoke mode)")
    args = ap.parse_args()
    enable_compile_cache()
    dataset = "dbpedia-small" if args.quick else "dbpedia"
    shards = 4 if args.quick else 8

    program = F.parse_program(RULES)
    compiled = F.compile_program(program)
    print(f"program {program.name!r}: combiner={compiled.combiner}, "
          f"optimized plan runtime estimate "
          f"{plan_runtime(compiled.optimized):.3g}s")

    n, graph = load_dataset(dataset, num_shards=shards)
    snap = PartitionSnapshot(n_keys=n, num_shards=shards)
    values, res = compiled.run(graph, snap, max_iters=80)

    reached = int(np.sum(np.asarray(values)[:n] == 1.0))
    print(f"{dataset}: {reached}/{n} vertices reachable from 0 "
          f"in {int(res.stats.iterations)} strata")

    # Cross-check against the BFS oracle (same generator parameters).
    nn, avg_deg, alpha = DATASETS[dataset]
    indptr, indices = make_powerlaw_graph(nn, avg_degree=avg_deg,
                                          alpha=alpha, seed=0)
    dist = np.asarray(sssp.reference_sssp(np.asarray(indptr),
                                          np.asarray(indices), n))
    assert np.array_equal(np.asarray(values)[:n] == 1.0, dist < np.inf), \
        "rules-only reachability disagrees with the BFS oracle"
    print("matches BFS oracle: OK")


if __name__ == "__main__":
    main()
