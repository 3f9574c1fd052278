"""The benchmark's own graph generator: a copy of the program's
power-law generator (``repro.data.graphs.make_powerlaw_graph`` and
``zipf_outdegrees``), kept here so that the graphs the benchmark measures
on cannot move when the program's generator changes.
``tests/bench/test_bench_refs.py`` checks the copy against the original.

Out-degrees are Zipf(alpha), capped at 50x the mean and scaled to the
requested mean out-degree; a third of the edges point into the first
1% of vertex ids and the rest are uniform, so in-degrees are
heavy-tailed too.  The result is a global CSR: ``indptr`` int64[n+1],
``indices`` int32[nnz].
"""
from __future__ import annotations

import numpy as np


def zipf_outdegrees(n: int, avg_degree: float, alpha: float,
                    rng: np.random.Generator) -> np.ndarray:
    raw = rng.zipf(alpha, size=n).astype(np.float64)
    raw = np.minimum(raw, max(int(avg_degree * 50), 8))
    scale = avg_degree * n / raw.sum()
    deg = np.maximum(np.round(raw * scale), 0).astype(np.int64)
    return np.minimum(deg, n - 1).astype(np.int32)


def powerlaw_graph(n: int, avg_degree: float, alpha: float, seed: int,
                   head_one_in: int = 3, head_vertices_per: int = 100
                   ) -> tuple[np.ndarray, np.ndarray]:
    """One edge in ``head_one_in`` points into the head: the first
    ``n // head_vertices_per`` vertex ids."""
    rng = np.random.default_rng(seed)
    deg = zipf_outdegrees(n, avg_degree, alpha, rng)
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(deg, out=indptr[1:])
    nnz = int(indptr[-1])
    n_head = max(n // head_vertices_per, 1)
    n_from_head = nnz // head_one_in
    dst = np.empty(nnz, np.int32)
    dst[:n_from_head] = rng.integers(0, n_head, n_from_head)
    dst[n_from_head:] = rng.integers(0, n, nnz - n_from_head)
    rng.shuffle(dst)
    return indptr, dst


def shard_loads(indptr: np.ndarray, num_shards: int) -> np.ndarray:
    """Edges per shard under the block partition of source ids."""
    n = len(indptr) - 1
    block = -(-n // num_shards)
    bounds = np.minimum(np.arange(num_shards + 1) * block, n)
    return np.diff(indptr[bounds])

