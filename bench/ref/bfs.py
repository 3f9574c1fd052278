"""Plain unweighted shortest paths by a vectorised frontier BFS (a copy
of ``ref_bfs`` in ``chip_smoke.py``)."""
from __future__ import annotations

import numpy as np


def bfs(indptr, indices, n, source=0):
    dist = np.full(n, np.inf, np.float32)
    dist[source] = 0.0
    frontier = np.array([source], np.int64)
    d = 0
    while frontier.size:
        starts, ends = indptr[frontier], indptr[frontier + 1]
        lens = ends - starts
        total = int(lens.sum())
        offs = np.arange(total) - np.repeat(np.cumsum(lens) - lens, lens)
        nbrs = indices[np.repeat(starts, lens) + offs]
        nbrs = np.unique(nbrs[np.isinf(dist[nbrs])])
        d += 1
        dist[nbrs] = d
        frontier = nbrs
    return dist
