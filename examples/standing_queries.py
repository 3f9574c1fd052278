"""Standing queries: materialized views absorbing live base-data deltas.

The serving-side story of the REX reproduction: a ViewManager keeps three
standing queries (PageRank, SSSP, k-means) converged while the base data
mutates underneath them.  Each tick applies a batch of edge/point
mutations and refreshes; the views repair their warm state through the
per-algorithm rules and resume the sharded fixpoint, falling back to a
cold recompute only when the estimated repair volume crosses the
threshold.  A durable mutation journal (runtime/checkpoint.py delta
checkpoints) makes the whole session recoverable — the final section
restarts from disk and proves the restored views are identical.

  PYTHONPATH=src python examples/standing_queries.py
"""
import tempfile

import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.data.graphs import make_powerlaw_graph
from repro.incremental import (EdgeDelete, EdgeInsert, PointInsert,
                               PointRemove, ViewManager)

rng = np.random.default_rng(0)
enable_compile_cache()
N = 2_048
TICKS = 5

indptr, indices = make_powerlaw_graph(N, avg_degree=8, seed=0)
points = np.concatenate([
    rng.normal((0, 0), 0.4, (200, 2)),
    rng.normal((5, 5), 0.4, (200, 2)),
    rng.normal((0, 5), 0.4, (200, 2))]).astype(np.float32)

journal_root = tempfile.mkdtemp(prefix="rex_views_")
mgr = ViewManager(journal_root=journal_root, fallback_threshold=0.5)
mgr.create_graph_view("ranks", "pagerank", indptr, indices, N,
                      num_shards=4, threshold=1e-4, max_iters=100)
mgr.create_graph_view("dists", "sssp", indptr, indices, N,
                      num_shards=4, source=0, max_iters=100)
mgr.create_kmeans_view("clusters", points, k=3, num_shards=4, seed=1)

for name, view in mgr.views.items():
    r = view.history[-1]
    print(f"cold-start {name:>8}: {r.strata:3d} strata, "
          f"{r.rehash_bytes / 1e3:8.1f} KB rehashed, {r.wall_s:6.3f}s")

for tick in range(TICKS):
    # Edge churn: a handful of inserts + deletes per graph view.
    store = mgr["ranks"].store
    src, dst = store.edges()
    batch = [EdgeInsert(int(rng.integers(N)), int(rng.integers(N)))
             for _ in range(6)]
    for i in rng.choice(len(src), 6, replace=False):
        batch.append(EdgeDelete(int(src[i]), int(dst[i])))
    mgr.mutate("ranks", *batch)
    mgr.mutate("dists", *batch)

    # Point churn: sensors appear and disappear.
    valid = np.flatnonzero(mgr["clusters"].store.to_arrays()["valid"])
    mgr.mutate("clusters",
               PointInsert(float(rng.normal(5, 0.4)),
                           float(rng.normal(5, 0.4))),
               PointRemove(int(rng.choice(valid))))

    print(f"-- tick {tick}:")
    for name, r in mgr.refresh().items():
        print(f"   {name:>8} v{r.version}: {r.mode:6s} "
              f"touched={r.touched_keys:4d} strata={r.strata:3d} "
              f"rehash={r.rehash_bytes / 1e3:7.1f} KB "
              f"wall={r.wall_s * 1e3:6.1f} ms")

top = np.argsort(mgr.query("ranks"))[-3:][::-1]
print(f"top pages by rank: {list(top)}")
reach = np.isfinite(mgr.query("dists")).sum()
print(f"vertices reachable from 0: {reach}/{N}")
print(f"cluster centroids:\n{np.round(mgr.query('clusters'), 3)}")

# ---- crash, restart, resume from the journal ------------------------------
restored = ViewManager.restore(journal_root)
for name in mgr.views:
    same = np.array_equal(restored.query(name), mgr.query(name),
                          equal_nan=True)
    print(f"restored {name:>8} v{restored[name].version}: "
          f"identical={same}")
