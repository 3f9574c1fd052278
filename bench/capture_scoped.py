"""Record the small chip trace with the program's scopes that
``tests/bench/test_bench_scopes.py`` reads: the capture of
``bench/capture_testdata.py`` (a few PageRank fixpoints of a tiny graph
under the profiler), taken of a program whose stratum carries the
``rex.*`` scopes, and beside it the map from each operation to its
scopes (``bench/scopes.py``) from the same loop compiled again.

    python3 bench/capture_scoped.py --out <dir>

Writes ``<dir>/fixpoint_small_scoped.xplane.pb.gz``, ``.json`` (the
driver's counts) and ``.scopes.json`` (the map); copy the three to
``bench/testdata/``.  Prints the per-layer split of the recording and
its idle gaps named by the program's spans.  Needs a TPU.
"""
from __future__ import annotations

import argparse
import copy
import json
import os
import shutil
import sys
import tempfile

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
NAME = "fixpoint_small_scoped"


def small_cell() -> tuple[dict, dict]:
    """The cell ``capture_testdata.py`` records, cut to its small size."""
    from bench import run
    from bench.capture_testdata import SMALL
    workload, config = run.load_cell("dbpedia-pagerank.delta")
    config = copy.deepcopy(config)
    config["graph"]["vertices"] = SMALL["vertices"]
    config["shards"] = SMALL["shards"]
    config["edge_slots_per_shard"] = SMALL["edge_slots_per_shard"]
    config["algorithm"]["threshold"] = SMALL["threshold"]
    return dict(workload, graphs=SMALL["graphs"]), config


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import capture_testdata, scopes
    from bench.trace_reduce import read_xspace

    tmp = tempfile.mkdtemp(prefix="bench_scoped_")
    try:
        if capture_testdata.main(["--out", tmp]):
            return 1
        os.makedirs(args.out, exist_ok=True)
        for suffix in (".xplane.pb.gz", ".json"):
            shutil.move(os.path.join(tmp, "fixpoint_small" + suffix),
                        os.path.join(args.out, NAME + suffix))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    workload, config = small_cell()
    scope_map = scopes.scope_map(scopes.fixpoint_hlo(config, workload))
    with open(os.path.join(args.out, NAME + ".scopes.json"), "w") as f:
        json.dump(scope_map, f, sort_keys=True)

    path = os.path.join(args.out, NAME + ".xplane.pb.gz")
    with open(os.path.join(args.out, NAME + ".json")) as f:
        meta = json.load(f)
    trace = read_xspace(path, 1)
    ctx = dict(trace=trace, stats=meta["stats"], workload=workload,
               config=config, scope_map=scope_map)
    gaps = scopes.idle_gaps(trace, scopes.program_spans(path))
    print(json.dumps(dict(
        ops=len(scope_map), split=scopes.split(ctx),
        layer_ms={k: scopes.layer_ms(ctx, k) for k in scopes.LAYERS},
        gaps=sorted(gaps, key=lambda g: -g[1])[:10])))
    return 0


if __name__ == "__main__":
    sys.exit(main())
