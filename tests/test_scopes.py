"""Where the device and host time of a fixpoint is attributed: the
engine's ``rex.*`` named scopes reach the compiled program's metadata on
both backends, and the program's host spans reach the profiler's trace
(``repro.obs.trace.span``)."""
import glob
import json
import os

import numpy as np
import pytest

import jax

from repro.algorithms import pagerank
from repro.core.engine import ShardedExecutor
from repro.core.partition import PartitionSnapshot
from repro.data.graphs import make_powerlaw_graph, shard_csr
from repro.obs.trace import Tracer, span
from subproc import run_sub

N, S, CAP, TIERS = 256, 4, 16384, 4
LEAVES = ("rex.emit", "rex.route", "rex.apply")


def compiled_text(mode: str, backend: str) -> str:
    """HLO text of the compiled PageRank loop at a tiny size: four rungs
    under ``delta``, the dense body under ``nodelta``."""
    snap = PartitionSnapshot(n_keys=N, num_shards=S)
    ip, ix = make_powerlaw_graph(N, 8.0, 2.1, seed=3)
    graph = shard_csr(ip, ix, S, nnz_capacity=CAP)
    kw = {}
    if backend == "shard_map":
        from repro.launch.mesh import flat_mesh
        kw = dict(backend="shard_map", axis_name="shards",
                  mesh=flat_mesh(devices=jax.devices()[:S]))
    ex = ShardedExecutor(snapshot=snap, seg_capacity=CAP, edge_capacity=CAP,
                         src_capacity=snap.block_size, ladder_tiers=TIERS,
                         route_strategy="auto", **kw)
    algo = pagerank.make_algorithm(snap, 1e-3, snap.block_size, CAP)
    return ex.precompile(algo, pagerank.initial_state(snap), graph, 40,
                         mode=mode).as_text()


def rex_paths(text: str) -> list:
    """The ``rex.*`` components of every ``op_name`` in an HLO text."""
    out = set()
    for part in text.split('op_name="')[1:]:
        path = tuple(c for c in part.split('"', 1)[0].split("/")
                     if c.startswith("rex."))
        if path:
            out.add(path)
    return sorted(out)


def check_scopes(paths, mode: str) -> None:
    flat = {s for p in paths for s in p}
    assert {"rex.select", "rex.loop"} <= flat
    bodies = (["rex.dense"] if mode == "nodelta"
              else [f"rex.rung{k}" for k in range(TIERS)] + ["rex.dense"])
    for body in bodies:
        for leaf in LEAVES:
            assert any(body in p and leaf in p[p.index(body):]
                       for p in paths), (body, leaf)
    if mode == "nodelta":
        assert not any(s.startswith("rex.rung") for s in flat)


@pytest.mark.parametrize("mode", ["delta", "nodelta"])
def test_scopes_reach_the_compiled_loop_simulated(mode):
    text = compiled_text(mode, "simulated")
    check_scopes(rex_paths(text), mode)
    # Scopes are metadata: no callback is compiled in without a tracer.
    assert "callback" not in text


@pytest.mark.parametrize("mode", ["delta", "nodelta"])
def test_scopes_reach_the_compiled_loop_shard_map(mode):
    code = (
        "import json, sys\n"
        f"sys.path.insert(0, {os.path.dirname(__file__)!r})\n"
        "from test_scopes import compiled_text, rex_paths\n"
        f"print(json.dumps(rex_paths(compiled_text({mode!r}, "
        "'shard_map'))))\n")
    out = run_sub(code, devices=S, env={"JAX_PLATFORMS": "cpu"})
    paths = [tuple(p) for p in json.loads(out.strip().splitlines()[-1])]
    check_scopes(paths, mode)


def test_span_records_only_into_a_given_tracer():
    tr = Tracer("t")
    with span("rex.test", tr, tid="host", k=1) as args:
        args["n"] = 2
    (ev,) = tr.events
    assert ev["name"] == "rex.test" and ev["args"] == {"k": 1, "n": 2}
    with span("rex.test", k=1) as args:
        assert args == {"k": 1}
    assert len(tr.events) == 1


def host_event_names(trace_dir: str) -> set:
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    data = ProfileData.from_file(path)
    return {e.name for plane in data.planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events}


def test_program_spans_appear_on_the_profilers_host_plane(tmp_path):
    snap = PartitionSnapshot(n_keys=N, num_shards=S)
    ip, ix = make_powerlaw_graph(N, 8.0, 2.1, seed=3)
    tr = Tracer("t")
    jax.profiler.start_trace(str(tmp_path))
    try:
        graph = shard_csr(ip, ix, S)
        pr, res = pagerank.run(graph, snap, max_iters=20)
        jax.block_until_ready(pr)
        with tr.span("view.refresh"):
            pass
    finally:
        jax.profiler.stop_trace()
    names = host_event_names(str(tmp_path))
    assert {"rex.shard_csr", "rex.pagerank.run", "rex.executor.run",
            "rex.executor.prepare", "rex.executor.dispatch",
            "view.refresh"} <= names
    assert int(res.stats.iterations) > 0
    assert np.isfinite(np.asarray(pr)).all()
