"""Synthetic graphs shaped like the paper's datasets (§6 "Data").

The paper uses the DBPedia article-link graph (48M edges / 3.3M vertices,
avg degree ~14.5) and a Twitter follower graph (1.4B edges / 41M vertices,
avg degree ~34, heavy-tailed).  We generate power-law (Zipf out-degree)
directed graphs with matching shape statistics at configurable scale, stored
as padded CSR partitioned by source vertex — the paper's "edge relation
partitioned by vertexId" (immutable set).

CSR layout per shard (block partition over sources):
  indptr:  int32[block+1]       — local CSR row pointers
  indices: int32[nnz_capacity]  — destination GLOBAL vertex ids (PAD = -1)
  out_degree: int32[block]      — true out-degree per local source

nnz is padded per shard to the max across shards so that shards stack into a
single array (static shapes; the padding models the skew the paper's
consistent hashing tries to avoid).
"""
from __future__ import annotations

import dataclasses

import jax
import numpy as np
import jax.numpy as jnp

from repro.obs.trace import span


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class CSRGraph:
    """Single-shard (or global) padded CSR directed graph."""

    indptr: jax.Array      # int32[n_src + 1]
    indices: jax.Array     # int32[nnz_cap], PAD = -1
    out_degree: jax.Array  # int32[n_src]   (global out-degree of each source)

    @property
    def n_src(self) -> int:
        return self.out_degree.shape[0]

    @property
    def nnz_capacity(self) -> int:
        return self.indices.shape[0]


def zipf_outdegrees(n_vertices: int, avg_degree: float, alpha: float,
                    rng: np.random.Generator, max_degree: int | None = None
                    ) -> np.ndarray:
    """Zipf-ish out-degree sequence normalized to the requested average."""
    raw = rng.zipf(alpha, size=n_vertices).astype(np.float64)
    if max_degree is None:
        max_degree = max(int(avg_degree * 50), 8)
    raw = np.minimum(raw, max_degree)
    scale = avg_degree * n_vertices / raw.sum()
    deg = np.maximum(np.round(raw * scale), 0).astype(np.int64)
    deg = np.minimum(deg, n_vertices - 1)
    return deg.astype(np.int32)


def make_powerlaw_graph(n_vertices: int, avg_degree: float = 14.5,
                        alpha: float = 2.1, seed: int = 0) -> tuple[
                            np.ndarray, np.ndarray]:
    """Global CSR (indptr, indices) with Zipf out-degrees.

    avg_degree defaults to DBPedia's ~14.5; use ~34 and alpha≈1.9 for the
    Twitter-shaped configuration.
    """
    rng = np.random.default_rng(seed)
    deg = zipf_outdegrees(n_vertices, avg_degree, alpha, rng)
    indptr = np.zeros(n_vertices + 1, np.int64)
    np.cumsum(deg, out=indptr[1:])
    nnz = int(indptr[-1])
    # Destinations ~ preferential-attachment-ish: mix uniform with a head
    # bias so in-degree is also heavy-tailed (as in web/social graphs).
    n_head = max(n_vertices // 100, 1)
    n_from_head = nnz // 3
    dst = np.empty(nnz, np.int32)
    dst[:n_from_head] = rng.integers(0, n_head, n_from_head)
    dst[n_from_head:] = rng.integers(0, n_vertices, nnz - n_from_head)
    rng.shuffle(dst)
    return indptr.astype(np.int64), dst


@span("rex.shard_csr")
def shard_csr(indptr: np.ndarray, indices: np.ndarray, num_shards: int,
              nnz_capacity: int | None = None) -> CSRGraph:
    """Partition a global CSR by source block into stacked per-shard CSR.

    Returns a CSRGraph whose arrays carry a leading [num_shards] axis
    (matching the simulated engine backend; shard_map splits the same axis).

    ``nnz_capacity`` pins the per-shard edge-slot capacity so that graphs
    rebuilt after base-data mutations keep static shapes (the incremental
    view subsystem relies on this to avoid re-tracing the fixpoint between
    refreshes).  Raises if any shard's edges exceed the pinned capacity.
    """
    n = indptr.shape[0] - 1
    block = -(-n // num_shards)
    padded = block * num_shards
    deg = np.diff(indptr)
    deg_padded = np.zeros(padded, np.int64)
    deg_padded[:n] = deg
    per_shard_nnz = deg_padded.reshape(num_shards, block).sum(axis=1)
    nnz_cap = int(per_shard_nnz.max()) if len(per_shard_nnz) else 0
    nnz_cap = max(nnz_cap, 1)
    if nnz_capacity is not None:
        if nnz_cap > nnz_capacity:
            raise ValueError(
                f"shard nnz {nnz_cap} exceeds pinned capacity {nnz_capacity}")
        nnz_cap = nnz_capacity

    sh_indptr = np.zeros((num_shards, block + 1), np.int32)
    sh_indices = np.full((num_shards, nnz_cap), -1, np.int32)
    sh_deg = np.zeros((num_shards, block), np.int32)
    for s in range(num_shards):
        lo, hi = s * block, min((s + 1) * block, n)
        local_deg = deg_padded[s * block:(s + 1) * block]
        sh_indptr[s, 1:] = np.cumsum(local_deg)
        sh_deg[s] = local_deg
        if hi > lo:
            seg = indices[indptr[lo]:indptr[hi]]
            sh_indices[s, :len(seg)] = seg
    return CSRGraph(indptr=jnp.asarray(sh_indptr),
                    indices=jnp.asarray(sh_indices),
                    out_degree=jnp.asarray(sh_deg))


def csr_to_edges(indptr: np.ndarray, indices: np.ndarray
                 ) -> tuple[np.ndarray, np.ndarray]:
    """Global CSR -> (src, dst) edge list (drops PAD=-1 slots)."""
    n = indptr.shape[0] - 1
    src = np.repeat(np.arange(n, dtype=np.int32),
                    np.diff(indptr).astype(np.int64))
    dst = np.asarray(indices[:len(src)], np.int32)
    keep = dst >= 0
    return src[keep], dst[keep]


def edges_to_csr(src: np.ndarray, dst: np.ndarray, n: int
                 ) -> tuple[np.ndarray, np.ndarray]:
    """(src, dst) edge list -> global CSR (indptr int64, indices int32).

    Stable with respect to the input edge order within each source row, so
    rebuilding after a mutation batch is deterministic.
    """
    src = np.asarray(src, np.int64)
    order = np.argsort(src, kind="stable")
    deg = np.bincount(src, minlength=n).astype(np.int64)
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(deg, out=indptr[1:])
    return indptr, np.asarray(dst, np.int32)[order]


def global_csr(indptr: np.ndarray, indices: np.ndarray) -> CSRGraph:
    """Single-shard CSRGraph view of a global CSR."""
    deg = np.diff(indptr).astype(np.int32)
    return CSRGraph(indptr=jnp.asarray(indptr.astype(np.int32)),
                    indices=jnp.asarray(indices),
                    out_degree=jnp.asarray(deg))


# Named dataset shapes (scaled-down analogues of the paper's datasets).
DATASETS = {
    # name: (n_vertices, avg_degree, alpha)
    "dbpedia-small": (4_096, 14.5, 2.1),     # unit tests
    "dbpedia": (65_536, 14.5, 2.1),          # benches (paper: 3.3M x 14.5)
    "twitter-small": (8_192, 34.0, 1.9),
    "twitter": (131_072, 34.0, 1.9),         # benches (paper: 41M x 34)
}


def load_dataset(name: str, num_shards: int = 1, seed: int = 0):
    """Sharded CSR with a leading [num_shards] axis (1 included — the
    engine always expects the shard axis)."""
    n, avg, alpha = DATASETS[name]
    indptr, indices = make_powerlaw_graph(n, avg, alpha, seed)
    return n, shard_csr(indptr, indices, num_shards)
