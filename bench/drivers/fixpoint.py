"""Back-to-back cold fixpoints: whole ``algorithms.pagerank.run`` calls,
each from submit to ``block_until_ready`` of the converged ranks.

A run holds ``graphs`` graphs of the cell's shape, graph ``i`` drawn
from the seed ``[seed, i]`` (``drivers/common.py``); all share the
pinned shapes, so one compiled loop serves them all.  The window runs rounds of one fixpoint
on each graph, and starts another round only while the rounds so far
say it will end inside ``--seconds``; so it always holds whole rounds,
at least one, and every run does the same mix of work.

Every call's ranks are kept on the device and compared, after the
window, with the float64 power iteration of ``bench/ref/pagerank.py``
on that call's graph.
"""
from __future__ import annotations

import time

import numpy as np

from bench.drivers.common import make_graph
from bench.ref.pagerank import pagerank_f64


class Driver:

    def __init__(self, config: dict, workload: dict, seed: int, devices):
        self.config, self.workload = config, workload
        self.seed, self.devices = seed, devices
        self.results = []

    def setup(self) -> None:
        import jax

        from repro.algorithms import pagerank
        from repro.core.engine import ShardedExecutor
        from repro.core.partition import PartitionSnapshot
        from repro.data.graphs import shard_csr

        cfg, algo = self.config, self.config["algorithm"]
        n, S = cfg["graph"]["vertices"], cfg["shards"]
        cap = cfg["edge_slots_per_shard"]
        snap = PartitionSnapshot(n_keys=n, num_shards=S)
        place, backend = self.devices[0], {}
        if cfg["backend"] == "shard_map":
            from jax.sharding import NamedSharding, PartitionSpec
            from repro.launch.mesh import flat_mesh
            mesh = flat_mesh(devices=self.devices[:S])
            backend = dict(backend="shard_map", mesh=mesh, axis_name="shards")
            place = NamedSharding(mesh, PartitionSpec("shards"))
        self.csr = [make_graph(cfg, [self.seed, i])
                    for i in range(self.workload["graphs"])]
        self.graphs = [jax.device_put(shard_csr(ip, ix, S, nnz_capacity=cap),
                                      place) for ip, ix in self.csr]
        # The top rung holds a shard's whole emission, so every stratum
        # that is not dense can run sparse.
        executor = ShardedExecutor(
            snapshot=snap, seg_capacity=cap, edge_capacity=cap,
            src_capacity=snap.block_size, ladder_tiers=algo["ladder_tiers"],
            route_strategy=algo["route_strategy"], **backend)

        def call(graph):
            return pagerank.run(
                graph, snap, mode=self.workload["mode"],
                threshold=algo["threshold"], max_iters=algo["max_iters"],
                executor=executor, src_capacity=snap.block_size,
                edge_capacity=cap)

        self.call = call
        # Warm up on a graph of the same shapes with no edges: it loads
        # (or compiles) every program a call runs, and converges in one
        # stratum instead of a whole fixpoint.
        empty = shard_csr(np.zeros(n + 1, np.int64), np.zeros(0, np.int32), S,
                          nnz_capacity=cap)
        jax.block_until_ready(call(jax.device_put(empty, place)))

    def window(self, seconds: float, spans) -> dict:
        import jax

        t0 = time.perf_counter()
        rounds = 0
        while True:
            for i, graph in enumerate(self.graphs):
                with spans("bench.fixpoint"):
                    pr, res = self.call(graph)
                    jax.block_until_ready((pr, res.stats))
                self.results.append((i, pr, res.stats))
            rounds += 1
            elapsed = time.perf_counter() - t0
            if elapsed * (rounds + 1) / rounds > seconds:
                break
        return {"fixpoint_s": elapsed / len(self.results)}

    def attempted(self) -> int:
        return len(self.results)

    def release(self) -> None:
        """Move every windowed answer and count to the host and drop the
        program's device state, before the reference runs."""
        n = self.config["graph"]["vertices"]
        self.answers, calls = [], []
        for i, pr, stats in self.results:
            it = int(stats.iterations)
            self.answers.append((i, np.asarray(pr)[:n]))
            calls.append(dict(
                graph=i, strata=it,
                delta_counts=np.asarray(stats.delta_counts)[:it].tolist(),
                used_dense=np.asarray(stats.used_dense)[:it].tolist()))
        self.stats = dict(vertices=n, shards=self.config["shards"],
                          edges=[int(ip[-1]) for ip, _ in self.csr],
                          calls=calls)
        self.results = self.graphs = self.call = None

    def compare(self) -> dict:
        """Each compared number, one value per windowed fixpoint."""
        n = self.config["graph"]["vertices"]
        refs = [pagerank_f64(ip, ix, n) for ip, ix in self.csr]
        return {"pr_max_abs_err": [float(np.max(np.abs(a - refs[i])))
                                   for i, a in self.answers]}
