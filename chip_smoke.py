"""Smoke run of the delta fixpoint engine on a TPU on a DBpedia-shaped graph.

    python chip_smoke.py [--seed 0]          # one chip
    python chip_smoke.py --four-chips        # a host with four chips

One chip: a power-law graph generated from ``--seed`` with the shape of
the paper's DBpedia graph (mean out-degree 14.5, Zipf alpha 2.1) at an
eighth of its published 3.3M vertices: 412,500 vertices, about 6M edges
(``--vertices 3300000`` gives the published scale, about 48M edges).
8 shards on the simulated backend, driven through the library's entry
points:

* PageRank, ``delta`` mode (density ladder of 4 rungs, ``auto`` rehash)
  and ``nodelta`` mode, against a float64 power iteration;
* SSSP from vertex 0, ``delta`` mode, against a NumPy frontier BFS;
* a standing SSSP view: two batches of about 0.1% edge churn, each
  refreshed warm and required to equal a cold recompute and the BFS;
* PageRank with the Pallas route kernels (``use_pallas_route``) for the
  sort and the scatter strategy, at the largest size within the kernels'
  bounds, against the same run on the jnp route.

``--four-chips`` runs only the shard_map path, one process driving a
4-device mesh with S=4 on the same graph: PageRank, SSSP and one warm
view resume, each compared with the same run on the simulated backend.

Each phase prints one JSON line (first-call seconds including compile,
steady seconds of a second call, strata, the device's peak bytes in use,
and its checks).  The last line is ``{"ok": true, "device": {...}}``.
The script exits non-zero, without that line, when JAX finds no TPU,
when the repo's ``src/`` is not beside it, or when any check fails.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

DBPEDIA = dict(vertices=3_300_000, avg_degree=14.5, alpha=2.1)
# Default size: an eighth of the published scale.  On a v5e a PageRank
# stratum takes 0.59 s (delta) / 0.35 s (dense) at 200,000 vertices and
# grows with the edge count, so one PageRank call at 3.3M vertices
# (~110 strata) runs about 18 minutes; at 412,500 the whole script,
# each phase called twice, ends within about 10.
SMOKE_VERTICES = DBPEDIA["vertices"] // 8
PR_THRESHOLD = 1e-5
PR_BOUND = 5e-3          # max |delta| against the float64 reference
MAX_ITERS = 300
CHURN = 1e-3             # edge churn per view mutation batch
# Route kernels: the largest graph whose shard block fits the scatter
# kernel's slab (MAX_BLOCK) — DATASETS["dbpedia"] over 16 shards.
KERNEL_VERTICES, KERNEL_SHARDS = 65_536, 16
# Segment capacities at the kernels' bounds for a 4096-key block:
# cap·block <= 2^22 for scatter_route, cap <= 8192 for delta_route.
KERNEL_SEG = dict(sort=8192, scatter=1024)
# Compiles in flight at once (each full-scale loop takes ~4 GB of host
# memory while it compiles).
COMPILE_WORKERS, COMPILE_WORKERS_FOUR_CHIPS = 4, 6


class CheckFailed(RuntimeError):
    pass


def _import_repo():
    """Import ``repro`` from the ``src/`` beside this script, and nowhere
    else: a copy of the script without the repo must fail."""
    src = os.path.join(HERE, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise SystemExit(f"chip_smoke: no repro package under {src}")
    sys.path.insert(0, src)
    import repro
    # ``repro`` is a namespace package: it has a path, not a ``__file__``.
    where = [os.path.abspath(p) for p in repro.__path__]
    if where[:1] != [os.path.join(src, "repro")]:
        raise SystemExit(f"chip_smoke: repro imported from {where}, "
                         f"not from {src}")


# ---------------------------------------------------------------------------
# NumPy references.
# ---------------------------------------------------------------------------

def ref_pagerank(indptr, indices, n, tol=1e-7, max_iters=500):
    """Float64 power iteration: pr = 0.15 + 0.85 * sum pr(u)/deg(u)."""
    deg = np.diff(indptr)
    src = np.repeat(np.arange(n), deg)
    inv = 1.0 / np.maximum(deg, 1)
    pr = np.full(n, 0.15)
    for _ in range(max_iters):
        new = 0.15 + 0.85 * np.bincount(indices, weights=(pr * inv)[src],
                                        minlength=n)
        done = np.max(np.abs(new - pr)) < tol
        pr = new
        if done:
            break
    return pr


def ref_bfs(indptr, indices, n, source=0):
    """Unweighted shortest paths by a vectorised frontier BFS."""
    dist = np.full(n, np.inf, np.float32)
    dist[source] = 0.0
    frontier = np.array([source], np.int64)
    d = 0
    while frontier.size:
        starts, ends = indptr[frontier], indptr[frontier + 1]
        lens = ends - starts
        total = int(lens.sum())
        offs = np.arange(total) - np.repeat(np.cumsum(lens) - lens, lens)
        nbrs = indices[np.repeat(starts, lens) + offs]
        nbrs = np.unique(nbrs[np.isinf(dist[nbrs])])
        d += 1
        dist[nbrs] = d
        frontier = nbrs
    return dist


# ---------------------------------------------------------------------------
# Harness.
# ---------------------------------------------------------------------------

def _peak_bytes(dev):
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _timed(fn):
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


def _report(name, dev, first_s, steady_s, strata, checks, **extra):
    ok = all(bool(v) for k, v in checks.items() if k.endswith("_ok"))
    line = dict(phase=name, first_call_s=first_s, steady_s=steady_s,
                strata=strata, peak_bytes=_peak_bytes(dev), **extra,
                checks=checks, ok=ok)
    print(json.dumps(line, default=_jsonable), flush=True)
    if not ok:
        raise CheckFailed(f"phase {name} failed its checks: {checks}")


def _jsonable(x):
    if isinstance(x, (np.integer,)):
        return int(x)
    if isinstance(x, (np.floating,)):
        return float(x)
    if isinstance(x, (np.bool_,)):
        return bool(x)
    if isinstance(x, np.ndarray):
        return x.tolist()
    raise TypeError(type(x))


def _graph(vertices, num_shards, seed):
    from repro.data.graphs import make_powerlaw_graph, shard_csr
    indptr, indices = make_powerlaw_graph(
        vertices, DBPEDIA["avg_degree"], DBPEDIA["alpha"], seed)
    return indptr, indices, shard_csr(indptr, indices, num_shards)


def _strata(res):
    return int(res.stats.iterations)


def _ulps(a, b):
    """Per-element distance in float32 units in the last place."""
    a = np.asarray(a, np.float32).view(np.int32).astype(np.int64)
    b = np.asarray(b, np.float32).view(np.int32).astype(np.int64)
    return np.abs(a - b)


def _executor(snap, edge, **kw):
    from repro.core.engine import ShardedExecutor
    # The top rung holds the largest shard's whole emission: every
    # stratum that is not dense can run sparse.
    return ShardedExecutor(snapshot=snap, seg_capacity=edge,
                           edge_capacity=edge,
                           src_capacity=snap.block_size, ladder_tiers=4,
                           route_strategy="auto", **kw)


# ---------------------------------------------------------------------------
# Compilation: independent loops compile in parallel threads (an XLA
# compile releases the GIL) while the main thread runs earlier phases.
# ---------------------------------------------------------------------------

class Background:
    """Named jobs on a thread pool; ``get`` waits for one and returns
    (result, seconds it took)."""

    def __init__(self, workers):
        from concurrent.futures import ThreadPoolExecutor
        self._pool = ThreadPoolExecutor(workers)
        self._jobs = {}

    def submit(self, name, fn, *args, **kw):
        def timed():
            t0 = time.perf_counter()
            out = fn(*args, **kw)
            return out, time.perf_counter() - t0
        self._jobs[name] = self._pool.submit(timed)

    def get(self, name):
        return self._jobs[name].result()

    def close(self):
        self._pool.shutdown(wait=True, cancel_futures=True)


def _precompile_fixpoints(bg, ex, snap, graph, graph_v, edge, tag=""):
    """Queue the PageRank (delta, nodelta) and SSSP loops of ``ex``."""
    from repro.algorithms import pagerank, sssp
    b = snap.block_size
    pr_algo = pagerank.make_algorithm(snap, PR_THRESHOLD, b, edge)
    sp_algo = sssp.make_algorithm(snap, b, edge)
    for mode in ("delta", "nodelta") if not tag else ("delta",):
        bg.submit(f"pagerank_{mode}{tag}", ex.precompile, pr_algo,
                  pagerank.initial_state(snap), graph, MAX_ITERS, mode)
    bg.submit(f"sssp_delta{tag}", ex.precompile, sp_algo,
              sssp.initial_state(snap, 0), graph_v, MAX_ITERS)


def _run_pagerank(dev, bg, name, graph, snap, ex, edge, mode, pr_ref,
                  extra_checks=None):
    from repro.algorithms import pagerank
    _, compile_s = bg.get(name)
    n = snap.n_keys

    def call():
        return pagerank.run(graph, snap, mode=mode, threshold=PR_THRESHOLD,
                            max_iters=MAX_ITERS, executor=ex,
                            src_capacity=snap.block_size,
                            edge_capacity=edge)
    (pr, res), first = _timed(call)
    (pr2, _), steady = _timed(call)
    pr = np.asarray(pr)[:n]
    err = float(np.max(np.abs(pr - pr_ref)))
    it = _strata(res)
    checks = dict(max_abs_err=err, err_ok=err < PR_BOUND,
                  converged_ok=it < MAX_ITERS,
                  repeat_ok=np.array_equal(pr, np.asarray(pr2)[:n]))
    checks.update((extra_checks or (lambda v, r: {}))(pr, res))
    _report(name, dev, first, steady, it, checks, compile_s=compile_s,
            sparse_strata=int(np.sum(~np.asarray(res.stats.used_dense)[:it])))
    return pr, np.asarray(res.stats.delta_counts)


def _run_sssp(dev, bg, name, graph_v, snap, ex, edge, dist_ref,
              extra_checks=None):
    from repro.algorithms import sssp
    _, compile_s = bg.get(name)
    n = snap.n_keys

    def call():
        return sssp.run(graph_v, snap, source=0, mode="delta",
                        max_iters=MAX_ITERS, executor=ex,
                        src_capacity=snap.block_size, edge_capacity=edge)
    (dist, res), first = _timed(call)
    (dist2, _), steady = _timed(call)
    dist = np.asarray(dist)[:n]
    checks = dict(reached=int(np.sum(np.isfinite(dist_ref))),
                  equal_ok=np.array_equal(dist, dist_ref),
                  repeat_ok=np.array_equal(dist, np.asarray(dist2)[:n]))
    checks.update((extra_checks or (lambda v, r: {}))(dist, res))
    _report(name, dev, first, steady, _strata(res), checks,
            compile_s=compile_s)
    return dist, np.asarray(res.stats.delta_counts)


# ---------------------------------------------------------------------------
# One chip.
# ---------------------------------------------------------------------------

def one_chip(args, dev):
    from repro.core.partition import PartitionSnapshot
    from repro.incremental.stores import GraphStore

    n, S = args.vertices, 8
    t0 = time.perf_counter()
    indptr, indices, graph = _graph(n, S, args.seed)
    edge = int(graph.indices.shape[1])
    snap = PartitionSnapshot(n_keys=n, num_shards=S)
    # SSSP runs on the view's store layout, so the view's cold run is the
    # SSSP phase's compiled loop.
    store = GraphStore(indptr, indices, n, S)
    graph_v = store.build_sharded()
    print(json.dumps(dict(
        phase="graph", vertices=n, edges=int(indptr[-1]), shards=S,
        edge_slots_per_shard=edge, block=snap.block_size,
        setup_s=time.perf_counter() - t0, peak_bytes=_peak_bytes(dev))),
        flush=True)

    bg = Background(COMPILE_WORKERS)
    try:
        ex = _executor(snap, edge)
        _precompile_fixpoints(bg, ex, snap, graph, graph_v, edge)
        kernels = kernel_programs(args, dev, bg)
        bg.submit("pagerank_ref", ref_pagerank, indptr, indices, n)
        bg.submit("sssp_ref", ref_bfs, indptr, indices, n)
        bg.get("sssp_delta")
        view = make_view(args, dev, bg, store, snap, edge, {}, "sssp_view")
        pr_ref, _ = bg.get("pagerank_ref")
        for mode in ("delta", "nodelta"):
            _run_pagerank(dev, bg, f"pagerank_{mode}", graph, snap, ex, edge,
                          mode, pr_ref)
        del graph
        dist_ref, _ = bg.get("sssp_ref")
        _run_sssp(dev, bg, "sssp_delta", graph_v, snap, ex, edge, dist_ref)
        del graph_v
        churn_view(args, dev, bg, *view)
        kernel_phases(dev, bg, kernels)
    finally:
        bg.close()


def make_view(args, dev, bg, store, snap, edge, backend_kw, name):
    """Create a standing SSSP view (its cold run is the SSSP loop) and
    queue the compile of its warm-resume loop."""
    import jax
    from repro.incremental import ViewManager

    t0 = time.perf_counter()
    view = ViewManager(fallback_threshold=1.0).create_view(
        name, "sssp", store, source=0, edge_capacity=edge,
        src_capacity=snap.block_size, ladder_tiers=4, route_strategy="auto",
        max_iters=MAX_ITERS, **backend_kw)
    jax.block_until_ready(view.state)
    rule = view.rule
    # Repaired states reach resume as fresh (uncommitted) host-built
    # arrays: compile for plain shapes, as such a call is keyed.
    def shapes(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                            tree)
    bg.submit(name + "_resume", rule.resume_executor.precompile,
              rule.resume_algo, shapes(view.state), shapes(view.immutable),
              rule.max_iters, rule.mode)
    return view, time.perf_counter() - t0


def churn_view(args, dev, bg, view, create_s, batches=2):
    """Mutation batches of about 0.1% edge churn, each refreshed warm; the
    view must equal a cold recompute of itself and the NumPy BFS.  The
    second batch's refresh is the steady time."""
    from repro.data.graphs import edges_to_csr
    from repro.incremental import EdgeDelete, EdgeInsert

    n, store, rule = view.store.n, view.store, view.rule
    _, compile_s = bg.get(view.name + "_resume")
    rng = np.random.default_rng(args.seed + 1)
    times, checks, strata = [], {}, []
    for batch in range(batches):
        src, dst = store.edges()
        k = max(int(CHURN * len(src)) // 2, 1)
        gone = rng.choice(len(src), size=k, replace=False)
        muts = [EdgeDelete(int(src[i]), int(dst[i])) for i in gone]
        muts += [EdgeInsert(int(u), int(v))
                 for u, v in rng.integers(0, n, size=(k, 2))]
        view.apply(*muts)
        t0 = time.perf_counter()
        rep = view.refresh(force="repair")
        warm = np.array(view.query())
        times.append(time.perf_counter() - t0)
        strata.append(rep.strata)
        state, _ = rule.cold(view)
        cold = rule.extract(view, state)
        src, dst = store.edges()
        ip, ix = edges_to_csr(src, dst, n)
        checks[f"batch{batch}_mutations"] = len(muts)
        checks[f"batch{batch}_repair_ok"] = rep.mode == "repair"
        checks[f"batch{batch}_warm_eq_cold_ok"] = np.array_equal(warm, cold)
        checks[f"batch{batch}_eq_bfs_ok"] = np.array_equal(
            warm, ref_bfs(ip, ix, n))
    _report(view.name, dev, times[0], times[1] if batches > 1 else None,
            strata, checks, cold_create_s=create_s, compile_s=compile_s)
    return warm


def kernel_programs(args, dev, bg):
    """Queue the compiles of PageRank with each Pallas route kernel and
    of the same run on the jnp route."""
    import jax
    from repro.algorithms import pagerank
    from repro.core.engine import ShardedExecutor
    from repro.core.partition import PartitionSnapshot

    n, S = KERNEL_VERTICES, KERNEL_SHARDS
    _, _, graph = _graph(n, S, args.seed)
    snap = PartitionSnapshot(n_keys=n, num_shards=S)
    for strategy, seg in KERNEL_SEG.items():
        for use_kernel in (True, False):
            ex = ShardedExecutor(snapshot=snap, seg_capacity=seg,
                                 edge_capacity=seg,
                                 src_capacity=snap.block_size,
                                 route_strategy=strategy,
                                 use_pallas_route=use_kernel)

            def f(g, ex=ex, seg=seg):
                pr, res = pagerank.run(g, snap, mode="delta",
                                       threshold=PR_THRESHOLD,
                                       max_iters=MAX_ITERS, executor=ex,
                                       src_capacity=snap.block_size,
                                       edge_capacity=seg)
                return pr, res.stats
            bg.submit(("kernel", strategy, use_kernel),
                      lambda f=f: jax.jit(f).lower(graph).compile())
    return graph, snap


def kernel_phases(dev, bg, kernels):
    """PageRank with the Pallas route kernels, against the jnp route."""
    import jax
    from repro.core.fixpoint import ROUTE_SCATTER_KERNEL, ROUTE_SORT_KERNEL

    graph, snap = kernels
    codes = dict(sort=ROUTE_SORT_KERNEL, scatter=ROUTE_SCATTER_KERNEL)
    for strategy, seg in KERNEL_SEG.items():
        compiled, compile_s = bg.get(("kernel", strategy, True))
        (pr_k, st_k), first = _timed(lambda: compiled(graph))
        _, steady = _timed(lambda: compiled(graph))
        jnp_run, _ = bg.get(("kernel", strategy, False))
        pr_j, st_j = jax.block_until_ready(jnp_run(graph))
        it = int(st_k.iterations)
        routes = np.asarray(st_k.routes)[:it]
        sparse = routes >= 0
        ulps = _ulps(pr_k, pr_j)
        _report(f"pagerank_route_kernel_{strategy}", dev, first, steady, it,
                dict(vertices=snap.n_keys, shards=snap.num_shards,
                     block=snap.block_size, seg_capacity=seg,
                     kernel_strata=int(np.sum(sparse)),
                     kernel_ran_ok=bool(sparse.any())
                     and bool(np.all(routes[sparse] == codes[strategy])),
                     compiled_kernel_ok="tpu_custom_call" in
                     compiled.as_text(),
                     delta_counts_equal_ok=np.array_equal(
                         np.asarray(st_k.delta_counts),
                         np.asarray(st_j.delta_counts)),
                     max_ulps=int(ulps.max()),
                     ulp_ok=int(ulps.max()) <= 1),
                compile_s=compile_s)


# ---------------------------------------------------------------------------
# Four chips: the shard_map path and its simulated twin.
# ---------------------------------------------------------------------------

def four_chips(args, dev):
    import jax
    from repro.core.partition import PartitionSnapshot
    from repro.incremental.stores import GraphStore
    from repro.launch.mesh import flat_mesh

    devices = jax.devices()
    if len(devices) < 4:
        raise SystemExit(f"chip_smoke: --four-chips needs 4 devices, JAX "
                         f"finds {len(devices)}")
    S = 4
    mesh = flat_mesh(devices=devices[:S])
    n = args.vertices
    indptr, indices, graph = _graph(n, S, args.seed)
    edge = int(graph.indices.shape[1])
    snap = PartitionSnapshot(n_keys=n, num_shards=S)
    backends = dict(
        simulated={},
        shard_map=dict(backend="shard_map", mesh=mesh, axis_name="shards"))
    stores = {b: GraphStore(indptr, indices, n, S) for b in backends}
    graph_v = stores["simulated"].build_sharded()
    bg = Background(COMPILE_WORKERS_FOUR_CHIPS)
    try:
        executors = {b: _executor(snap, edge, **kw)
                     for b, kw in backends.items()}
        for b, ex in executors.items():
            _precompile_fixpoints(bg, ex, snap, graph, graph_v, edge,
                                  tag=f"_{b}_s{S}")
        bg.submit("pagerank_ref", ref_pagerank, indptr, indices, n)
        bg.submit("sssp_ref", ref_bfs, indptr, indices, n)
        made = {}
        for b, kw in backends.items():
            bg.get(f"sssp_delta_{b}_s{S}")
            made[b] = make_view(args, dev, bg, stores[b], snap, edge, kw,
                                f"sssp_view_{b}_s{S}")
        pr_ref, _ = bg.get("pagerank_ref")
        dist_ref, _ = bg.get("sssp_ref")
        sim = {}
        for b, ex in executors.items():
            def vs_sim(vals, res, algo, b=b):
                if b == "simulated":
                    return {}
                sim_vals, sim_counts = sim[algo]
                out = dict(delta_counts_match_simulated=np.array_equal(
                    sim_counts, np.asarray(res.stats.delta_counts)))
                if algo == "pagerank":
                    out["max_abs_diff_vs_simulated"] = float(
                        np.max(np.abs(vals - sim_vals)))
                else:
                    out["equal_simulated_ok"] = np.array_equal(vals,
                                                               sim_vals)
                return out
            pr = _run_pagerank(
                dev, bg, f"pagerank_delta_{b}_s{S}", graph, snap, ex, edge,
                "delta", pr_ref,
                lambda v, r: vs_sim(v, r, "pagerank"))
            dist = _run_sssp(
                dev, bg, f"sssp_delta_{b}_s{S}", graph_v, snap, ex, edge,
                dist_ref, lambda v, r: vs_sim(v, r, "sssp"))
            if b == "simulated":
                sim = dict(pagerank=pr, sssp=dist)
        # One warm resume per backend: the four-chip call is charged four
        # times over, and the one-chip run already times a second batch.
        views = {b: churn_view(args, dev, bg, *made[b], batches=1)
                 for b in made}
    finally:
        bg.close()
    same = np.array_equal(views["simulated"], views["shard_map"])
    print(json.dumps(dict(phase="sssp_view_backends_agree", ok=same)),
          flush=True)
    if not same:
        raise CheckFailed("shard_map view differs from the simulated view")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--vertices", type=int, default=SMOKE_VERTICES,
                    help="graph size (default: an eighth of the paper's "
                    "3.3M-vertex DBpedia scale)")
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the shard_map path on a 4-device mesh")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX finds no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 2
    _import_repo()
    from repro.compile_cache import enable_compile_cache
    print(json.dumps(dict(phase="start", compile_cache=enable_compile_cache(),
                          device_kind=dev.device_kind,
                          devices=len(devices))), flush=True)
    try:
        (four_chips if args.four_chips else one_chip)(args, dev)
    except CheckFailed as e:
        print(f"chip_smoke: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
