"""Pieces the drivers share: the cell's graph, built by the benchmark's
own generator and handed to the program, and the host spans the
benchmark records around its calls into the program."""
from __future__ import annotations

import contextlib
import time

import numpy as np

from bench.ref.graphs import powerlaw_graph, shard_loads


def make_graph(config: dict, seed) -> tuple[np.ndarray, np.ndarray]:
    """The power-law graph of the cell's shape drawn from ``seed``, as a
    global CSR.  Refuses a graph that overflows the pinned edge slots:
    every seed must reuse the compiled programs."""
    g = config["graph"]
    indptr, indices = powerlaw_graph(g["vertices"], g["avg_degree"],
                                     g["alpha"], seed, g["head_one_in"],
                                     g["head_vertices_per"])
    most = int(shard_loads(indptr, config["shards"]).max())
    if most > config["edge_slots_per_shard"]:
        raise ValueError(
            f"seed {seed}: a shard holds {most} edges, more than the "
            f"{config['edge_slots_per_shard']} slots the config pins")
    return indptr, indices


class Spans:
    """Host spans (name, start, end) on ``time.perf_counter``, each also a
    ``jax.profiler.TraceAnnotation`` so a traced run sees them on the
    device trace's clock."""

    def __init__(self):
        self.spans: list[tuple[str, float, float]] = []

    @contextlib.contextmanager
    def __call__(self, name: str):
        import jax
        with jax.profiler.TraceAnnotation(name):
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.spans.append((name, t0, time.perf_counter()))
