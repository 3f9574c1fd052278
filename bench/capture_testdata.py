"""Record the small chip trace that ``tests/bench/test_bench_trace.py``
reads: a few PageRank fixpoints of a tiny graph through the fixpoint
driver, under the profiler, with the benchmark's spans.

    python3 bench/capture_testdata.py --out <dir>

Writes ``<dir>/fixpoint_small.xplane.pb.gz`` and
``<dir>/fixpoint_small.json`` (the driver's counts); copy both to
``bench/testdata/``.  Needs a TPU.
"""
from __future__ import annotations

import argparse
import copy
import glob
import gzip
import json
import os
import shutil
import sys
import tempfile

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
SMALL = dict(vertices=512, shards=2, edge_slots_per_shard=8192,
             threshold=1e-2, graphs=2)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax

    from bench import run
    from bench.drivers.common import Spans
    devices, peak = run.chips(1)
    workload, config = run.load_cell("dbpedia-pagerank.delta")
    config = copy.deepcopy(config)
    config["graph"]["vertices"] = SMALL["vertices"]
    config["shards"] = SMALL["shards"]
    config["edge_slots_per_shard"] = SMALL["edge_slots_per_shard"]
    config["algorithm"]["threshold"] = SMALL["threshold"]
    workload = dict(workload, graphs=SMALL["graphs"])
    driver = run.load_module(os.path.join(BENCH, "drivers", "fixpoint.py")
                             ).Driver(config, workload, 7, devices)
    driver.setup()
    spans = Spans()
    tmp = tempfile.mkdtemp(prefix="bench_capture_")
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tmp, profiler_options=opts)
        with spans("bench.window"):
            driver.window(0.0, spans)
        jax.profiler.stop_trace()
        driver.release()
        os.makedirs(args.out, exist_ok=True)
        (path,) = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                            recursive=True)
        with open(path, "rb") as f, gzip.open(os.path.join(
                args.out, "fixpoint_small.xplane.pb.gz"), "wb") as g:
            shutil.copyfileobj(f, g)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    with open(os.path.join(args.out, "fixpoint_small.json"), "w") as f:
        json.dump(dict(stats=driver.stats, spans=spans.spans,
                       small=SMALL, kind=devices[0].device_kind), f)
    print(json.dumps(dict(strata=[c["strata"] for c in
                                  driver.stats["calls"]])))
    return 0


if __name__ == "__main__":
    sys.exit(main())
