"""Shared benchmark helpers: timing, CSV emission, artifact records.

Every ``emit()`` call both prints the legacy CSV line and appends a
structured record to an in-process collector; ``benchmarks/run.py`` drains
the collector after each suite into a machine-readable
``BENCH_<suite>.json`` artifact so the perf trajectory is tracked across
PRs (and uploaded by CI).
"""
import time

import jax

_RECORDS: list[dict] = []


def tier_histogram(stats) -> str:
    """Per-rung stratum counts, e.g. '[12;4;0;43]' (index 0 = smallest
    ladder rung; dense strata excluded)."""
    import numpy as np
    iters = int(stats.iterations)
    tiers = np.asarray(stats.tiers)[:iters]
    if iters == 0 or tiers.max(initial=-1) < 0:
        return "[]"
    counts = np.bincount(tiers[tiers >= 0], minlength=int(tiers.max()) + 1)
    return "[" + ";".join(str(int(c)) for c in counts) + "]"


def route_histogram(stats) -> str:
    """Per-strategy stratum counts '[sort;scatter]' (dense strata and
    runs predating the routes field excluded)."""
    import numpy as np
    if getattr(stats, "routes", None) is None:
        return "[]"
    iters = int(stats.iterations)
    routes = np.asarray(stats.routes)[:iters]
    if iters == 0 or routes.max(initial=-1) < 0:
        return "[]"
    # The low bit of a route code is the strategy (kernel codes fold in).
    counts = np.bincount(routes[routes >= 0] % 2, minlength=2)
    return "[" + ";".join(str(int(c)) for c in counts[:2]) + "]"


def timeit(fn, *args, warmup: int = 2, reps: int = 5):
    """Median wall time of fn(*args) with block_until_ready."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2]


def timeit_split(fn, *args, reps: int = 5):
    """Split timing: ``(compile_s, steady_s)``.

    The FIRST call is timed separately — it includes tracing + XLA
    compilation, the number a "why is my benchmark slow" report usually
    conflates with steady-state throughput.  ``steady_s`` is the median
    of ``reps`` subsequent calls (all cache hits).  Use this instead of
    :func:`timeit` wherever the compile cost is itself a datapoint.
    """
    t0 = time.perf_counter()
    jax.block_until_ready(fn(*args))
    compile_s = time.perf_counter() - t0
    times = []
    for _ in range(max(reps, 1)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    times.sort()
    return compile_s, times[len(times) // 2]


def environment_metadata() -> dict:
    """Backend/device/version stamp embedded in every BENCH_*.json —
    cross-machine artifact diffs are meaningless without it."""
    import platform
    devs = jax.devices()
    return {
        "jax_version": jax.__version__,
        "backend": jax.default_backend(),
        "device_count": jax.device_count(),
        "device_kind": devs[0].device_kind if devs else "none",
        "platform": platform.platform(),
        "python": platform.python_version(),
    }


def metrics_snapshot() -> dict:
    """Snapshot of the default metrics registry (empty dict when nothing
    was recorded) — drained into the artifact next to the records."""
    from repro.obs import default_registry
    return default_registry().snapshot()


def emit(name: str, value, unit: str = "s", **extra):
    kv = ",".join(f"{k}={v}" for k, v in extra.items())
    print(f"{name},{value:.6g},{unit}" + ("," + kv if kv else ""),
          flush=True)
    _RECORDS.append({"name": name, "value": float(value), "unit": unit,
                     **extra})


def reset_records() -> None:
    """Start a fresh record set (one per benchmark suite)."""
    _RECORDS.clear()


def drain_records() -> list[dict]:
    """Return and clear the records emitted since the last reset."""
    out = list(_RECORDS)
    _RECORDS.clear()
    return out
