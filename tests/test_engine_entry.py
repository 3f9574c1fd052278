"""The engine's entry points: compiled-loop reuse, backend checks, the
kernel ops' bounds and where the compilation cache is placed."""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.algorithms import pagerank
from repro.core.delta import DeltaBuffer
from repro.core.engine import ShardedExecutor, _fixpoint
from repro.core.partition import PartitionSnapshot
from repro.data.graphs import make_powerlaw_graph, shard_csr
from repro.kernels import interpret_mode


@pytest.fixture(scope="module")
def graph():
    n, S = 512, 4
    indptr, indices = make_powerlaw_graph(n, avg_degree=6.0, seed=2)
    return PartitionSnapshot(n_keys=n, num_shards=S), shard_csr(
        indptr, indices, S)


def test_second_run_reuses_compiled_loop(graph):
    """A second run with equal shapes and settings compiles nothing."""
    snap, g = graph
    kw = dict(mode="delta", threshold=1e-4, max_iters=40,
              src_capacity=snap.block_size, edge_capacity=4096)
    pr0, _ = pagerank.run(g, snap, **kw)
    size = _fixpoint._cache_size()
    pr1, _ = pagerank.run(g, snap, **kw)
    assert _fixpoint._cache_size() == size
    np.testing.assert_array_equal(np.asarray(pr0), np.asarray(pr1))
    pagerank.run(g, snap, **dict(kw, max_iters=41))
    assert _fixpoint._cache_size() == size + 1


def test_shard_map_refuses_mismatched_mesh(graph):
    """Each device holds one shard: a snapshot with more shards than the
    mesh axis has devices is refused, not silently truncated."""
    snap, g = graph
    mesh = jax.make_mesh((1,), ("shards",))
    ex = ShardedExecutor(snapshot=snap, seg_capacity=256, edge_capacity=256,
                         src_capacity=snap.block_size, backend="shard_map",
                         mesh=mesh)
    algo = pagerank.make_algorithm(snap, 1e-4, snap.block_size, 256)
    state = pagerank.initial_state(snap)
    with pytest.raises(ValueError, match="one shard on each device"):
        ex.run(algo, state, snap.padded_keys, g, 4)
    with pytest.raises(ValueError, match="one shard on each device"):
        ex.live_count(algo, state, g)


def test_route_deltas_refuses_capacity_beyond_kernel():
    from repro.kernels.delta_route import route_deltas
    from repro.kernels.delta_route.delta_route import MAX_CAP
    db = DeltaBuffer.empty(256)
    owners = jnp.zeros(256, jnp.int32)
    with pytest.raises(ValueError, match="jnp path"):
        route_deltas(db, owners, 2, MAX_CAP + 1, max_key=512,
                     interpret=interpret_mode())
    with pytest.raises(ValueError, match="jnp path"):
        route_deltas(db, owners, 2, 64, max_key=1 << 25,
                     interpret=interpret_mode())


@pytest.mark.parametrize("placed", [None, "elsewhere"])
def test_compile_cache_placement(monkeypatch, tmp_path, placed):
    """JAX_COMPILATION_CACHE_DIR, when set, is left to JAX; otherwise the
    cache goes to the checkout's fixed .jax_cache."""
    from repro import compile_cache
    prev = jax.config.jax_compilation_cache_dir
    if placed is None:
        monkeypatch.delenv(compile_cache.CACHE_ENV, raising=False)
    else:
        monkeypatch.setenv(compile_cache.CACHE_ENV, str(tmp_path / placed))
    try:
        path = compile_cache.enable_compile_cache()
        if placed is None:
            root = os.path.dirname(os.path.dirname(
                os.path.dirname(os.path.abspath(compile_cache.__file__))))
            assert path == os.path.join(root, ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == path
        else:
            assert path == str(tmp_path / placed)
            assert jax.config.jax_compilation_cache_dir == prev
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def _load_smoke(path):
    import importlib.util
    spec = importlib.util.spec_from_file_location("chip_smoke_copy", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("alone", [False, True])
def test_chip_smoke_imports_repo_beside_it(tmp_path, alone):
    """chip_smoke.py imports the namespace package ``repro`` from the
    ``src/`` beside it, and a copy of the script without the repo stops."""
    import shutil
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script = os.path.join(root, "chip_smoke.py")
    if alone:
        script = shutil.copy(script, tmp_path / "chip_smoke.py")
    smoke = _load_smoke(script)
    if alone:
        with pytest.raises(SystemExit, match="no repro package"):
            smoke._import_repo()
    else:
        smoke._import_repo()
