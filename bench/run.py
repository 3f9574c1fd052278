"""One run of one benchmark cell on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is ``bench/workloads/<cell>.json``; it names its configuration
(``bench/configs/<config>.json``) and the driver that runs its traffic
(``bench/drivers/<driver>.py``).  A run sets up (graph from ``--seed``,
program compiled or loaded from the compilation cache, one warm call),
measures for ``--seconds``, reads the device's peak memory, frees the
program's state and compares the window's answers with the plain
reference under the cell's limits.  With ``--trace 1`` the window runs
under the profiler and the per-layer metrics (``bench/metrics/<name>.py``)
are reduced from the trace and the program's counts instead.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and last ``checks``, each compared number with its limit;
the same comparisons are the last lines of standard error.  Without a
TPU, with fewer chips than the cell asks for, or on a device kind that
``bench/peaks.json`` lacks, the run exits 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


class NoChip(RuntimeError):
    pass


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(path: str):
    name = "bench_" + os.path.relpath(path, BENCH).replace("/", "_")[:-3]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(name: str) -> tuple[dict, dict]:
    workload = load_json(BENCH, "workloads", name + ".json")
    return workload, load_json(BENCH, "configs", workload["config"] + ".json")


def declared_metrics(benchmark: dict, cell: str) -> tuple[list, list]:
    """The end-to-end and per-layer metrics ``BENCHMARK.json`` gives this
    cell."""
    def mine(m):
        return cell in m.get("workloads", [cell])
    return ([m for m in benchmark["end_to_end"] if mine(m)],
            [m for m in benchmark["per_layer"] if mine(m)])


def chips(count: int):
    """The first ``count`` TPU chips and their peaks; NoChip otherwise."""
    import jax
    devices = jax.devices()
    kind = devices[0].device_kind
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX finds no TPU (platform {devices[0].platform!r})")
    if len(devices) < count:
        raise NoChip(f"the cell needs {count} chips, JAX finds "
                     f"{len(devices)}")
    peaks = load_json(BENCH, "peaks.json")["devices"]
    if kind not in peaks:
        raise NoChip(f"device kind {kind!r} is not in bench/peaks.json")
    return devices[:count], peaks[kind]


def enable_compile_cache() -> None:
    """JAX's persistent compilation cache at ``<checkout>/.jax_cache``
    (or where ``JAX_COMPILATION_CACHE_DIR`` says), so that only a
    checkout's first run compiles the fixpoint loops."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        path = os.path.join(ROOT, ".jax_cache")
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)


def peak_bytes(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


class ProgramsBuilt:
    """Counts the programs JAX builds, compiled or read from the
    persistent cache, while it is on: inside the window it should read
    0."""

    EVENT = "/jax/compilation_cache/compile_requests_use_cache"

    def __init__(self):
        import jax
        self.count, self.on = 0, False
        jax.monitoring.register_event_listener(self._event)

    def _event(self, event, **kwargs):
        if self.on and event == self.EVENT:
            self.count += 1


def execute(workload: dict, config: dict, seed: int, seconds: float,
            trace: bool, devices, peak: dict, e2e: list, per_layer: list,
            t_start: float = T_START) -> dict:
    """Set up, measure, compare: the result line of one run, as a dict."""
    import jax

    from bench.drivers.common import Spans

    driver = load_module(os.path.join(
        BENCH, "drivers", workload["driver"] + ".py")).Driver(
        config, workload, seed, devices)
    driver.setup()
    setup_s = time.perf_counter() - t_start
    spans, built = Spans(), ProgramsBuilt()
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    try:
        if trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        built.on = True
        try:
            with spans("bench.window"):
                measured = driver.window(seconds, spans)
        finally:
            built.on = False
            if trace:
                jax.profiler.stop_trace()
        memory = peak_bytes(devices)
        attempted = driver.attempted()
        driver.release()
        summary = None
        if trace:
            from bench.trace_reduce import reduce_trace
            summary = reduce_trace(trace_dir, len(devices))
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    compared = driver.compare()

    limits = workload["limits"]
    checks, failed = {}, 0
    for name, values in compared.items():
        checks[name] = {"value": max(values), "limit": limits[name]}
    n_calls = max(len(v) for v in compared.values())
    for i in range(n_calls):
        failed += any(i < len(v) and v[i] > limits[k]
                      for k, v in compared.items())

    values = dict(measured, setup_s=setup_s, peak_hbm_gb=memory / 1e9)
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": memory}
    result = {"correct": failed == 0 and attempted > 0,
              "attempted": attempted, "failed": failed}
    if trace:
        ctx = dict(trace=summary, stats=driver.stats, spans=spans.spans,
                   peak=peak, workload=workload, config=config)
        metrics = {}
        for m in per_layer:
            reducer = load_module(os.path.join(BENCH, "metrics",
                                               m["name"] + ".py"))
            v = reducer.reduce(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)
        result.update(metrics=metrics, device=device,
                      breakdown=summary.breakdown())
    else:
        result.update(metrics={m["name"]: {"value": values[m["name"]],
                                           "unit": m["unit"]}
                               for m in e2e if m["name"] in values},
                      device=device)
    result["checks"] = checks
    print(f"bench: {built.count} programs built inside the window",
          file=sys.stderr)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, ROOT)
    workload, config = load_cell(args.workload)
    e2e, per_layer = declared_metrics(load_json(ROOT, "BENCHMARK.json"),
                                      args.workload)
    try:
        devices, peak = chips(config["chips"])
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"bench: no program under {src}", file=sys.stderr)
        return 2
    sys.path.insert(1, src)
    enable_compile_cache()
    result = execute(workload, config, args.seed, args.seconds,
                     bool(args.trace), devices, peak, e2e, per_layer)
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
