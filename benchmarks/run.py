"""Benchmark harness: one module per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run [--only fig5,...] [--quick]
      [--artifact-dir bench_artifacts]

Emits ``name,value,unit[,k=v...]`` CSV lines per data point AND one
machine-readable ``BENCH_<suite>.json`` artifact per suite (records +
config + wall clock) under ``--artifact-dir`` so the perf trajectory is
tracked across PRs.  ``--quick`` runs each suite's reduced configuration
(small datasets, fewer reps) — the CI smoke mode.
"""
import argparse
import inspect
import json
import os
import sys
import time
import traceback

from benchmarks import (bench_agg, bench_bandwidth, bench_chaos,
                        bench_compression, bench_distributed,
                        bench_frontend, bench_incremental, bench_kmeans,
                        bench_pagerank, bench_recovery, bench_rehash,
                        bench_scalability, bench_sssp, common)
from repro.compile_cache import enable_compile_cache

SUITES = [
    ("fig4_agg", bench_agg),
    ("fig5_kmeans", bench_kmeans),
    ("fig6_pagerank", bench_pagerank),      # also fig2, fig8
    ("fig7_sssp", bench_sssp),              # also fig9
    ("fig10_scalability", bench_scalability),
    ("fig11_bandwidth", bench_bandwidth),
    ("recovery", bench_recovery),               # fig12, resilient engine
    ("chaos", bench_chaos),                 # beyond-paper: chaos schedules
    ("distributed", bench_distributed),     # beyond-paper: real launch path
    ("compression", bench_compression),     # beyond-paper
    ("incremental", bench_incremental),     # beyond-paper: view maintenance
    ("rehash", bench_rehash),               # beyond-paper: route strategies
    ("frontend", bench_frontend),           # rules-vs-handwritten overhead
]


def write_artifact(artifact_dir: str, suite: str, records: list,
                   wall_s: float, quick: bool, failed: bool) -> str:
    os.makedirs(artifact_dir, exist_ok=True)
    path = os.path.join(artifact_dir, f"BENCH_{suite}.json")
    payload = {
        "suite": suite,
        "quick": quick,
        "failed": failed,
        "wall_s": round(wall_s, 3),
        "config": common.environment_metadata(),
        "metrics": common.metrics_snapshot(),
        "records": records,
    }
    with open(path, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
        f.write("\n")
    return path


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="")
    ap.add_argument("--quick", action="store_true",
                    help="reduced configs (CI smoke mode)")
    ap.add_argument("--artifact-dir", default="bench_artifacts",
                    help="where BENCH_<suite>.json artifacts are written")
    args = ap.parse_args()
    enable_compile_cache()
    sel = [s for s in args.only.split(",") if s]
    failed = []
    for name, mod in SUITES:
        if sel and not any(k in name for k in sel):
            continue
        print(f"# === {name} ===", flush=True)
        common.reset_records()
        from repro.obs import reset_default_registry
        reset_default_registry()    # per-suite metrics in the artifact
        kwargs = {}
        if args.quick and "quick" in inspect.signature(mod.main).parameters:
            kwargs["quick"] = True
        t0 = time.time()
        suite_failed = False
        try:
            mod.main(**kwargs)
        except Exception:  # noqa: BLE001 — keep the harness going
            traceback.print_exc()
            failed.append(name)
            suite_failed = True
        wall = time.time() - t0
        path = write_artifact(args.artifact_dir, name,
                              common.drain_records(), wall, args.quick,
                              suite_failed)
        print(f"# {name} done in {wall:.1f}s -> {path}", flush=True)
    if failed:
        print(f"# FAILED: {failed}", file=sys.stderr)
        sys.exit(1)
    print("# all suites complete", flush=True)


if __name__ == "__main__":
    main()
