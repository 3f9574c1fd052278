"""Compile rehearsals for a TPU v5e, made without the chip.

The TPU compiler ships with jaxlib and compiles for a described
``v5e:2x2`` topology: it refuses what the chip would refuse (a kernel
block the Mosaic lowering cannot lay out, a primitive Pallas TPU does not
lower, more VMEM than the scoped limit), which interpret mode never
shows.  These tests compile the route kernels with ``interpret=False`` at
the largest sizes the engine hands them, and the engine's kernel path
inside a PageRank fixpoint, and check that the compiled HLO holds the
kernel (``tpu_custom_call``).  Nothing runs: a compile that passes is not
a chip run.

The topology is described inside a fixture, never at import, so every
pytest-xdist worker collects the same tests and only the worker given
this file loads the TPU library.
"""
import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels.delta_route import delta_route
from repro.kernels.scatter_route import scatter_route

C, S = 65536, 8          # deltas per shard buffer, shards


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip: keep the cache off."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def topo(no_persistent_cache):
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no topology
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(sharding, shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_args(sharding):
    return (_spec(sharding, (C,)), _spec(sharding, (C, 1), jnp.float32),
            _spec(sharding, (C,)), _spec(sharding, (C,)))


@pytest.mark.parametrize("cap", [1024, 8192])
def test_delta_route_compiles_for_v5e(one_chip, cap):
    def route(keys, payload, ann, owners):
        return delta_route(keys, payload, ann, owners, S, cap,
                           interpret=False)

    compiled = jax.jit(route).lower(*_kernel_args(one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("cap", [256, 1024])
def test_scatter_route_compiles_for_v5e(one_chip, cap):
    block = 4096          # MAX_BLOCK; cap·block up to MAX_MATCH_CELLS

    def route(keys, payload, local, owners):
        return scatter_route(keys, payload, local, owners, S, block, cap,
                             interpret=False)

    compiled = jax.jit(route).lower(*_kernel_args(one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("strategy", ["sort", "scatter"])
def test_engine_kernel_path_compiles_for_v5e(one_chip, monkeypatch,
                                             strategy):
    """A PageRank fixpoint with use_pallas_route, compiled for the chip:
    the engine hands each kernel only shapes it compiles.  The kernels'
    interpret mode follows the default backend (the CPU here), so the
    test steers it to compiled."""
    import repro.kernels
    from repro.algorithms import pagerank
    from repro.core.engine import ShardedExecutor
    from repro.core.partition import PartitionSnapshot
    from repro.data.graphs import CSRGraph

    monkeypatch.setattr(repro.kernels, "interpret_mode", lambda: False)
    n, shards, seg, nnz = 4096, 16, 256, 4096
    snap = PartitionSnapshot(n_keys=n, num_shards=shards)
    b = snap.block_size
    graph = CSRGraph(indptr=_spec(one_chip, (shards, b + 1)),
                     indices=_spec(one_chip, (shards, nnz)),
                     out_degree=_spec(one_chip, (shards, b)))
    ex = ShardedExecutor(snapshot=snap, seg_capacity=seg, edge_capacity=seg,
                         src_capacity=b, route_strategy=strategy,
                         use_pallas_route=True)

    def run(g):
        _, res = pagerank.run(g, snap, threshold=1e-5, max_iters=8,
                              executor=ex, src_capacity=b,
                              edge_capacity=seg)
        return res.stats

    compiled = jax.jit(run).lower(graph).compile()
    assert "tpu_custom_call" in compiled.as_text()
