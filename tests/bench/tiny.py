"""Cells of the benchmark cut to a size the CPU runs in a second."""
import copy
import os
import time

import jax

from bench import run

SIZES = {
    "dbpedia-pagerank": dict(vertices=2048, edge_slots_per_shard=8192),
    "twitter-pagerank-s4": dict(vertices=2048, edge_slots_per_shard=32768),
}
PEAK = {"hbm_bytes_per_s": 819e9}


def tiny_cell(cell: str, **workload_over):
    workload, config = run.load_cell(cell)
    workload = dict(copy.deepcopy(workload), **workload_over)
    config = copy.deepcopy(config)
    size = SIZES[config["name"]]
    config["graph"]["vertices"] = size["vertices"]
    config["edge_slots_per_shard"] = size["edge_slots_per_shard"]
    return workload, config


def execute_tiny(cell: str, seed: int = 2**31 + 11, seconds: float = 0.3,
                 devices=None, **workload_over) -> dict:
    workload, config = tiny_cell(cell, **workload_over)
    bench = run.load_json(run.ROOT, "BENCHMARK.json")
    e2e, per_layer = run.declared_metrics(bench, cell)
    devices = devices or jax.devices()[:config["chips"]]
    return run.execute(workload, config, seed, seconds, False, devices,
                       PEAK, e2e, per_layer, t_start=time.perf_counter())


CELLS = sorted(f[:-5] for f in os.listdir(os.path.join(run.BENCH,
                                                        "workloads")))
