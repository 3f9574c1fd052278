"""Readings that the limits of ``correct`` are set from, at a cell's own
size, on the chip.

    python3 bench/control.py --workload <cell> --seeds 1 2 3 ... \
        [--control-seeds 4 5 6] [--seconds 8]

Needs the chips the cell asks for.

For each seed in ``--seeds`` the cell's driver sets up, runs a short
window and compares its answers with the reference: the program's
reading (the lower one).  For each seed in ``--control-seeds`` the
control takes the program's place and is compared the same way (the
upper reading): the same power iteration carried in bfloat16 on the
device, the precision below the configuration's float32.

One JSON line per reading, then a summary line with the largest program
reading and the smallest control reading of each compared number.  The
benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def program_reading(run, workload, config, seed, seconds, devices):
    driver = run.load_module(os.path.join(
        BENCH, "drivers", workload["driver"] + ".py")).Driver(
        config, workload, seed, devices)
    from bench.drivers.common import Spans
    t0 = time.perf_counter()
    driver.setup()
    setup_s = time.perf_counter() - t0
    driver.window(seconds, Spans())
    driver.release()
    compared = driver.compare()
    return {k: max(v) for k, v in compared.items()}, dict(
        setup_s=setup_s, compared=len(next(iter(compared.values()))),
        strata=[c["strata"] for c in driver.stats["calls"]])


def pagerank_control(config, seed):
    import numpy as np

    from bench.drivers.common import make_graph
    from bench.ref.pagerank import pagerank_bf16, pagerank_f64
    n = config["graph"]["vertices"]
    indptr, indices = make_graph(config, [seed, 0])
    ref = pagerank_f64(indptr, indices, n)
    low = pagerank_bf16(indptr, indices, n)
    return {"pr_max_abs_err": float(np.max(np.abs(low - ref)))}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--seconds", type=float, default=0.0)
    args = ap.parse_args(argv)

    sys.path.insert(0, ROOT)
    sys.path.insert(1, os.path.join(ROOT, "src"))
    from bench import run
    workload, config = run.load_cell(args.workload)
    try:
        devices, _ = run.chips(config["chips"])
    except run.NoChip as e:
        print(f"control: {e}", file=sys.stderr)
        return 2
    run.enable_compile_cache()
    lower, upper = {}, {}
    for seed in args.seeds:
        got, info = program_reading(run, workload, config, seed,
                                    args.seconds, devices)
        print(json.dumps(dict(side="program", seed=seed, **got, **info)),
              flush=True)
        for k, v in got.items():
            lower[k] = max(lower.get(k, v), v)
    for seed in args.control_seeds:
        t0 = time.perf_counter()
        got = pagerank_control(config, seed)
        print(json.dumps(dict(side="control", seed=seed, **got,
                              seconds=time.perf_counter() - t0)), flush=True)
        for k, v in got.items():
            upper[k] = min(upper.get(k, v), v)
    print(json.dumps(dict(workload=args.workload, lower=lower, upper=upper,
                          limits=workload["limits"])), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
