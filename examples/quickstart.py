"""Quickstart: delta-based PageRank on a power-law graph (paper Ex. 1).

  PYTHONPATH=src python examples/quickstart.py

Runs the same query in REX ``delta`` mode (propagate only Δᵢ) and
``nodelta`` mode (re-derive everything — the MapReduce-style baseline) and
prints per-iteration Δᵢ sizes, bytes moved, and the identical fixpoint.
"""
import numpy as np

import jax.numpy as jnp

from repro.algorithms import pagerank
from repro.compile_cache import enable_compile_cache
from repro.core.partition import PartitionSnapshot
from repro.data.graphs import load_dataset

SHARDS = 8

enable_compile_cache()

n, graph = load_dataset("dbpedia-small", num_shards=SHARDS)
snap = PartitionSnapshot(n_keys=n, num_shards=SHARDS)
print(f"graph: {n} vertices, {SHARDS} shards "
      f"(block partition, replication={snap.replication})")

results = {}
for mode in ("delta", "nodelta"):
    pr, res = pagerank.run(graph, snap, mode=mode, threshold=1e-5,
                           max_iters=80, edge_capacity=65536,
                           src_capacity=snap.block_size)
    iters = int(res.stats.iterations)
    moved = float(np.sum(res.stats.rehash_bytes))
    results[mode] = pr
    print(f"\n{mode}: converged in {iters} strata, "
          f"rehash moved {moved / 1e6:.2f} MB")
    if mode == "delta":
        counts = np.asarray(res.stats.delta_counts)[:iters]
        print("  |Δᵢ| per stratum:", counts[:10].tolist(), "...",
              counts[-3:].tolist())

diff = float(jnp.max(jnp.abs(results["delta"] - results["nodelta"])))
print(f"\nfixpoint agreement (delta vs dense): max |Δpr| = {diff:.2e}")
top = jnp.argsort(-results["delta"][:n])[:5]
print("top-5 pages by PageRank:", top.tolist())
