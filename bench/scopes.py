"""Device time per layer of the fixpoint loop, from the program's own
``rex.*`` scopes: what ``emit_ms.batch``, ``route_ms.batch``,
``apply_ms.batch`` and ``select_ms.batch`` read.

The engine wraps each layer of a stratum in a ``jax.named_scope``:
``rex.select`` (active sources and the rung choice), ``rex.rung<k>`` and
``rex.dense`` (the bodies), inside each ``rex.emit``, ``rex.route`` and
``rex.apply``, and ``rex.loop`` (the loop's condition and counts).  The
compiled program keeps each operation's scope path in its HLO metadata
(``metadata={op_name="…/rex.rung0/rex.emit/…"}``), but a chip's ``XLA
Ops`` events carry the instruction's text without it.  So the scopes are
joined to the trace's operations by instruction name and opcode, from the
HLO text of the cell's compiled fixpoint loop.

The reducers run after the window, so the text is got then: the cell's
loop is compiled again from its shapes through
``ShardedExecutor.precompile``, with the compilation cache off (it keys a
program without its metadata, so it may hold the same program with an
older build's scopes), and nothing of it is timed.  Compiling is
deterministic, so the instructions are named as in the program the window
ran.  The map is kept in ``ctx["scope_map"]`` for the other reducers.  A
program built before the scopes existed gives a map without any, and
every metric here is then None, not 0.
"""
from __future__ import annotations

import copy
import re
import sys
from collections import defaultdict

from bench.trace_reduce import clip, merge, parse_op

FIXPOINT = "bench.fixpoint"
OP_NAME = re.compile(r'op_name="([^"]*)"')
# The scopes each per-layer metric reads.
LAYERS = {"emit": ("rex.emit",), "route": ("rex.route",),
          "apply": ("rex.apply",), "select": ("rex.select", "rex.loop")}


def op_key(label: str) -> str:
    """Instruction name and opcode of an operation's label
    (``trace_reduce.parse_op``), which the trace and the HLO text share."""
    return " ".join(label.split(" ", 2)[:2])


def scope_map(hlo_text: str) -> dict:
    """``op_key`` -> the ``rex.*`` scopes on the path of each instruction
    of an HLO module's text that lies under any.  An instruction the
    compiler made without metadata (a cumulative sum rewritten as
    ``reduce-window``s, say) takes the scopes that every annotated
    instruction of its computation shares."""
    out = {}
    for body in computations(hlo_text):
        paths, bare = [], []
        for line in body:
            m = OP_NAME.search(line)
            key = op_key(parse_op.__wrapped__(
                line[:m.start()] if m else line)[0])
            scopes = ([c for c in m.group(1).split("/")
                       if c.startswith("rex.")] if m else [])
            if scopes:
                out[key] = scopes
                paths.append(scopes)
            elif not m:
                bare.append(key)
        shared = common_scopes(paths) if bare else []
        out.update((key, shared) for key in bare if shared)
    return out


def computations(hlo_text: str):
    """The instruction lines of each computation of an HLO module's text,
    without ``ROOT``."""
    body = None
    for line in hlo_text.splitlines():
        if line.endswith("{") and not line.startswith(" "):
            body = []
        elif line == "}" and body is not None:
            yield body
            body = None
        elif body is not None and line.strip():
            line = line.strip().removeprefix("ROOT ")
            if line.startswith("%"):
                body.append(line)


def common_scopes(paths: list) -> list:
    """The scopes on every one of ``paths``, in the order of the first."""
    if not paths:
        return []
    return [s for i, s in enumerate(paths[0])
            if s not in paths[0][:i] and all(s in p for p in paths[1:])]


def fixpoint_hlo(config: dict, workload: dict) -> str:
    """HLO text of the cell's compiled fixpoint loop, built from shapes
    alone the way ``drivers/fixpoint.py`` builds the loop it runs."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

    from repro.algorithms import pagerank
    from repro.core.engine import ShardedExecutor
    from repro.core.partition import PartitionSnapshot
    from repro.data.graphs import shard_csr

    algo = config["algorithm"]
    n, S = config["graph"]["vertices"], config["shards"]
    cap = config["edge_slots_per_shard"]
    devices = jax.devices()[:config["chips"]]
    snap = PartitionSnapshot(n_keys=n, num_shards=S)
    place, backend = SingleDeviceSharding(devices[0]), {}
    if config["backend"] == "shard_map":
        from repro.launch.mesh import flat_mesh
        mesh = flat_mesh(devices=devices[:S])
        backend = dict(backend="shard_map", mesh=mesh, axis_name="shards")
        place = NamedSharding(mesh, PartitionSpec("shards"))
    executor = ShardedExecutor(
        snapshot=snap, seg_capacity=cap, edge_capacity=cap,
        src_capacity=snap.block_size, ladder_tiers=algo["ladder_tiers"],
        route_strategy=algo["route_strategy"], **backend)
    graph = shard_csr(np.zeros(n + 1, np.int64), np.zeros(0, np.int32), S,
                      nnz_capacity=cap)
    graph = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=place),
        graph)
    return uncached(lambda: executor.precompile(
        pagerank.make_algorithm(snap, algo["threshold"], snap.block_size,
                                cap),
        pagerank.initial_state(snap), graph, algo["max_iters"],
        mode=workload["mode"]).as_text())


def uncached(compile_fn):
    """``compile_fn()`` with JAX's caches out of the way, so that it
    compiles.  The persistent cache keys a program without its metadata
    (``jax_compilation_cache_include_metadata_in_key`` is off), so what
    it returns may carry the scopes of an older build of the same
    program, or none; and a program already loaded in this process came
    from there."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    enabled = jax.config.jax_enable_compilation_cache
    jax.clear_caches()
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return compile_fn()
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
        compilation_cache.reset_cache()


def ctx_scope_map(ctx) -> dict:
    """The op -> scopes map of a traced fixpoint cell, built once."""
    if "scope_map" not in ctx:
        try:
            ctx["scope_map"] = scope_map(fixpoint_hlo(ctx["config"],
                                                      ctx["workload"]))
        except Exception as e:  # noqa: BLE001 — the metrics go missing
            print(f"bench.scopes: no scope map: {e!r}", file=sys.stderr)
            ctx["scope_map"] = {}
    return ctx["scope_map"]


def busy_by(trace, groups: dict) -> dict:
    """Busy seconds inside the ``bench.fixpoint`` spans, averaged over the
    chips, of each group of operations: ``groups`` maps a group's name to
    a predicate on an operation's label."""
    spans = merge((s, e) for n, s, e in trace.spans if n == FIXPOINT)
    total = defaultdict(float)
    for dev in trace.devices:
        members = defaultdict(list)
        for label, s, e in trace.ops[dev]:
            for name, pred in groups.items():
                if pred(label):
                    members[name].append((s, e))
        for name, ivs in members.items():
            ivs = merge(ivs)
            total[name] += sum(e - s for lo, hi in spans
                               for s, e in clip(ivs, lo, hi))
    return {name: total[name] * 1e-9 / len(trace.devices)
            for name in groups}


def layer_of(path: list):
    """The layer of ``LAYERS`` an operation counts in: that of the first
    of its scopes that one of them reads (a helper the compiler shares
    between call sites carries the scopes of each)."""
    for scope in path:
        for layer, names in LAYERS.items():
            if scope in names:
                return layer
    return None


def body_of(path: list) -> str:
    """The stratum body an operation ran in: ``rex.rung<k>`` or
    ``rex.dense``, or several joined by ``+`` where the compiler shares
    the operation between their bodies; ``rex.select`` or ``rex.loop``
    outside them."""
    bodies = sorted({s for s in path
                     if s.startswith("rex.rung") or s == "rex.dense"})
    return "+".join(bodies) or path[0]


def split(ctx) -> dict:
    """Busy seconds in ``bench.fixpoint`` per layer of ``LAYERS``, per
    stratum body, outside every ``rex.*`` scope (``unscoped``), under one
    but in no layer (``no_layer``) and in all (``all``); empty where the
    program has no scopes.  Reports the unscoped share and its largest
    operations on standard error once."""
    if "scope_split" in ctx:
        return ctx["scope_split"]
    scopes = ctx_scope_map(ctx)
    out = {}
    if scopes:
        trace = ctx["trace"]
        layers = {k: layer_of(p) for k, p in scopes.items()}
        bodies = {k: body_of(p) for k, p in scopes.items()}

        def where(table, value):
            return lambda label: table.get(op_key(label)) == value

        groups = {layer: where(layers, layer) for layer in LAYERS}
        groups.update({b: where(bodies, b)
                       for b in sorted(set(bodies.values()))})
        groups["unscoped"] = lambda label: op_key(label) not in scopes
        groups["no_layer"] = lambda label: (
            op_key(label) in scopes and layers[op_key(label)] is None)
        groups["all"] = lambda label: True
        out = busy_by(trace, groups)
        share = out["unscoped"] / out["all"] if out["all"] else 0.0
        loose = {label: sec for label, sec in trace.op_seconds().items()
                 if op_key(label) not in scopes}
        top = sorted(loose.items(), key=lambda kv: -kv[1])[:5]
        print(f"bench.scopes: unscoped {100 * share:.3f}% of the busy time "
              f"in {FIXPOINT}; largest unscoped ops {top}; split (s) "
              f"{ {k: round(v, 6) for k, v in out.items()} }",
              file=sys.stderr)
    ctx["scope_split"] = out
    return out


def layer_ms(ctx, layer: str):
    """Device milliseconds per stratum of one layer of ``LAYERS``, over
    the strata of the traced fixpoints; None without scopes."""
    if ctx["workload"]["driver"] != "fixpoint":
        return None
    seconds = split(ctx)
    strata = sum(c["strata"] for c in ctx["stats"]["calls"])
    if not seconds or not strata:
        return None
    return seconds[layer] * 1e3 / strata


def program_spans(path: str) -> list:
    """(name, start, end) of the program's ``rex.*`` host spans in a
    recorded ``.xplane.pb`` (or ``.gz``), on the trace's clock."""
    import gzip

    from jax.profiler import ProfileData
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        data = ProfileData.from_serialized_xspace(f.read())
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for plane in data.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith("rex.")]


def idle_gaps(trace, spans: list, dev=None) -> list:
    """``TraceSummary.idle_gaps`` with the program's ``rex.*`` spans
    among the names: each gap is named by the innermost ``bench.*`` or
    ``rex.*`` span over its middle."""
    named = copy.copy(trace)
    named.spans = trace.spans + list(spans)
    return named.idle_gaps(dev)
