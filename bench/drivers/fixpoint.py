"""Back-to-back cold fixpoints: whole ``algorithms.pagerank.run`` calls,
each from submit to ``block_until_ready`` of the converged ranks.

A run holds ``graphs`` graphs of the cell's shape, graph ``i`` drawn
from the seed ``[seed, i]`` (``drivers/common.py``); all share the
pinned shapes, so one compiled loop serves them all.  The window runs rounds of one fixpoint
on each graph, and starts another round only while the rounds so far
say it will end inside ``--seconds``; so it always holds whole rounds,
at least one, and every run does the same mix of work.

As each call ends, its ranks and counts are copied to the host and its
device arrays are dropped, so what the window leaves on the chip does
not grow with the number of calls it completed.  After the window each
call's ranks are compared with the float64 power iteration of
``bench/ref/pagerank.py`` on that call's graph.
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
        t0 = time.perf_counter()
        rounds = 0
        while True:
            for i, graph in enumerate(self.graphs):
                self.results.append(self._fixpoint(i, graph, spans))
            rounds += 1
            elapsed = time.perf_counter() - t0
            if elapsed * (rounds + 1) / rounds > seconds:
                break
        # The calls' own spans: the copies to the host between calls are
        # the benchmark's, not the program's.
        return {"fixpoint_s": sum(c["seconds"] for c in self.results)
                / len(self.results)}

    def _fixpoint(self, i: int, graph, spans) -> dict:
        """One call on graph ``i``, from submit to ``block_until_ready``,
        its answer and counts copied to the host once it has ended."""
        import jax

        with spans("bench.fixpoint"):
            pr, res = self.call(graph)
            jax.block_until_ready((pr, res.stats))
        _, start, end = spans.spans[-1]     # the span this call closed
        s = res.stats
        pr, it, counts, dense = jax.device_get(
            (pr, s.iterations, s.delta_counts, s.used_dense))
        it = int(it)
        return dict(graph=i, seconds=end - start,
                    ranks=pr[:self.config["graph"]["vertices"]], strata=it,
                    delta_counts=counts[:it], used_dense=dense[:it])

    def attempted(self) -> int:
        return len(self.results)

    def release(self) -> None:
        """Gather the windowed answers and counts, already on the host,
        and drop the program's device state, before the reference runs."""
        self.answers = [(c["graph"], c["ranks"]) for c in self.results]
        calls = [dict(graph=c["graph"], strata=c["strata"],
                      delta_counts=c["delta_counts"].tolist(),
                      used_dense=c["used_dense"].tolist())
                 for c in self.results]
        self.stats = dict(vertices=self.config["graph"]["vertices"],
                          shards=self.config["shards"],
                          edges=[int(ip[-1]) for ip, _ in self.csr],
                          calls=calls)
        self.results = self.graphs = self.call = None

    def compare(self) -> dict:
        """Each compared number, one value per windowed fixpoint."""
        n = self.config["graph"]["vertices"]
        refs = [pagerank_f64(ip, ix, n) for ip, ix in self.csr]
        return {"pr_max_abs_err": [float(np.max(np.abs(a - refs[i])))
                                   for i, a in self.answers]}
