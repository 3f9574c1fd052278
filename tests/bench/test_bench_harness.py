"""The benchmark's files hang together, a run refuses anything but a
TPU it knows, and each driver runs a tiny cell end to end on the CPU
and passes its comparison."""
import itertools
import json
import os
import re
import subprocess
import sys
import types

import jax
import numpy as np
import pytest

from bench import run
from bench.drivers.common import Spans
from tiny import CELLS, execute_tiny, tiny_cell

BENCHMARK = run.load_json(run.ROOT, "BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
ONE_CHIP_CELLS = [c for c in CELLS if run.load_cell(c)[1]["chips"] == 1]


def test_benchmark_json_keys():
    assert set(BENCHMARK) == {"command", "paths", "run_seconds", "configs",
                              "workloads", "end_to_end", "per_layer"}
    assert BENCHMARK["command"] == ["python3", "bench/run.py"]
    for path in BENCHMARK["paths"]:
        assert os.path.isdir(os.path.join(run.ROOT, path))
    assert {m["name"] for m in BENCHMARK["end_to_end"]} >= {"setup_s"}
    for m in BENCHMARK["end_to_end"] + BENCHMARK["per_layer"]:
        assert NAME.match(m["name"]) and m["better"] in ("lower", "higher")


@pytest.mark.parametrize("cell", [w["name"] for w in BENCHMARK["workloads"]])
def test_every_cell_has_its_files(cell):
    entry = next(w for w in BENCHMARK["workloads"] if w["name"] == cell)
    workload, config = run.load_cell(cell)
    assert workload["config"] == entry["config"] == config["name"]
    assert config["chips"] == entry["chips"]
    assert os.path.isfile(os.path.join(run.BENCH, "drivers",
                                       workload["driver"] + ".py"))
    declared = next(c for c in BENCHMARK["configs"]
                    if c["name"] == config["name"])
    assert declared["file"] == f"bench/configs/{config['name']}.json"
    assert sorted(declared["reduced"]) == sorted(config["reduced"])
    assert declared["source"] == config["source"]


@pytest.mark.parametrize("metric", [m["name"] for m in
                                    BENCHMARK["per_layer"]])
def test_every_layer_metric_moves_a_metric_its_cells_report(metric):
    m = next(x for x in BENCHMARK["per_layer"] if x["name"] == metric)
    assert os.path.isfile(os.path.join(run.BENCH, "metrics",
                                       metric + ".py"))
    moves = next(x for x in BENCHMARK["end_to_end"]
                 if x["name"] == m["moves"])
    cells = {w["name"] for w in BENCHMARK["workloads"]}
    for cell in m.get("workloads", cells):
        assert cell in cells
        assert cell in moves.get("workloads", cells)


def test_every_cell_reports_setup_and_another_end_to_end_metric():
    for w in BENCHMARK["workloads"]:
        e2e, per_layer = run.declared_metrics(BENCHMARK, w["name"])
        names = {m["name"] for m in e2e}
        assert "setup_s" in names and len(names) >= 2 and per_layer


def test_a_cpu_run_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(run.BENCH, "run.py"), "--workload",
         CELLS[0], "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=run.ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "{" not in proc.stdout


def _fake_devices(kind, count, platform="tpu"):
    return [types.SimpleNamespace(platform=platform, device_kind=kind)
            for _ in range(count)]


def test_unknown_device_kind_and_too_few_chips_are_refused(monkeypatch):
    import jax
    monkeypatch.setattr(jax, "devices",
                        lambda: _fake_devices("TPU v99 imaginary", 4))
    with pytest.raises(run.NoChip, match="peaks"):
        run.chips(1)
    monkeypatch.setattr(jax, "devices",
                        lambda: _fake_devices("TPU v5 lite", 1))
    with pytest.raises(run.NoChip, match="4 chips"):
        run.chips(4)
    devices, peak = run.chips(1)
    assert len(devices) == 1 and peak["hbm_bytes_per_s"] == 819e9


@pytest.mark.parametrize("cell", ONE_CHIP_CELLS)
def test_driver_runs_a_tiny_cell_and_is_correct(cell):
    result = execute_tiny(cell)
    assert result["correct"], result
    assert result["attempted"] >= 1 and result["failed"] == 0
    e2e, _ = run.declared_metrics(BENCHMARK, cell)
    assert set(result["metrics"]) == {m["name"] for m in e2e}
    assert all(v["value"] > 0 for k, v in result["metrics"].items()
               if k != "peak_hbm_gb")
    assert list(result)[-1] == "checks"


@pytest.mark.parametrize("cell", ONE_CHIP_CELLS)
def test_window_keeps_host_copies_and_times_only_the_calls(cell,
                                                           monkeypatch):
    """After a window of three rounds the driver keeps no device array,
    so what the window leaves on the chip does not grow with its calls
    (the CPU reports no peak memory); ``fixpoint_s`` is the calls'
    ``bench.fixpoint`` spans over the calls."""
    workload, config = tiny_cell(cell)
    module = run.load_module(os.path.join(run.BENCH, "drivers",
                                          workload["driver"] + ".py"))
    driver = module.Driver(config, workload, 2**31 + 29, jax.devices()[:1])
    driver.setup()
    # The round rule reads a clock that ticks once a round: three rounds.
    monkeypatch.setattr(module, "time", types.SimpleNamespace(
        perf_counter=itertools.count().__next__))
    spans = Spans()
    measured = driver.window(3.5, spans)
    calls = driver.attempted()
    assert calls == 3 * workload["graphs"]
    leaves = jax.tree_util.tree_leaves(driver.results)
    assert all(isinstance(x, (int, float, np.ndarray)) for x in leaves)
    assert not any(isinstance(x, jax.Array) for x in leaves)
    timed = [e - s for name, s, e in spans.spans if name == "bench.fixpoint"]
    assert len(timed) == calls
    assert measured["fixpoint_s"] == pytest.approx(sum(timed) / calls,
                                                   rel=1e-12)
    driver.release()
    kept = jax.tree_util.tree_leaves((driver.answers, driver.stats))
    assert not any(isinstance(x, jax.Array) for x in kept)


@pytest.mark.parametrize("cell", ONE_CHIP_CELLS)
def test_memory_probe_reads_the_device_after_each_call(cell):
    """``bench/memory_probe.py`` reads the device after set-up, after each
    windowed call, after the window and after ``release()``.  The CPU
    has no counts, so a stand-in device numbers its readings."""
    probe = run.load_module(os.path.join(run.BENCH, "memory_probe.py"))
    workload, config = tiny_cell(cell)
    driver = run.load_module(os.path.join(
        run.BENCH, "drivers", workload["driver"] + ".py")).Driver(
        config, workload, 2**31 + 31, jax.devices()[:1])
    reads = itertools.count()
    device = types.SimpleNamespace(memory_stats=lambda: dict(
        bytes_in_use=next(reads), peak_bytes_in_use=0))
    out = probe.probe(driver, device, 0.0)
    calls = out["calls"]
    assert calls == workload["graphs"]
    assert [m[0] for m in out["after_each_call"]] == list(range(2, 2 + calls))
    assert out["window"][0] == 2 + calls and out["release"][0] == 3 + calls
    assert out["pr_max_abs_err"] <= workload["limits"]["pr_max_abs_err"]


def test_a_cpu_memory_probe_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(run.BENCH, "memory_probe.py"),
         "--workload", CELLS[0], "--seed", "3", "--seconds", "1"],
        cwd=run.ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 2
    assert "{" not in proc.stdout


def test_shard_map_cell_runs_on_four_virtual_devices():
    """The four-chip cell's path (shard_map on a 4-device mesh), on four
    virtual CPU devices in a process of its own."""
    cells = [c for c in CELLS if run.load_cell(c)[1]["chips"] == 4]
    code = (
        "import json, jax, sys\n"
        f"sys.path[:0] = [{run.ROOT!r}, {os.path.dirname(__file__)!r}]\n"
        "from tiny import execute_tiny\n"
        f"for cell in {cells!r}:\n"
        "    r = execute_tiny(cell, devices=jax.devices()[:4])\n"
        "    print(json.dumps(r))\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(run.ROOT, "src"))
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [json.loads(x) for x in proc.stdout.splitlines()
             if x.startswith("{")]
    assert len(lines) == len(cells)
    for r in lines:
        assert r["correct"] and r["device"]["count"] == 4, r
