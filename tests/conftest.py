"""Shared fixtures.  NOTE: no XLA_FLAGS here — smoke tests and benches
must see 1 device; only launch/dryrun.py forces 512 virtual devices.
"""
import gc

import numpy as np
import pytest

import jax

# Every module's fixpoints stay alive in jax's global jit caches even
# after the module's fixtures are torn down; by the tail of the suite the
# accumulated executables segfault XLA inside backend_compile on small
# CI boxes.  Dropping the caches after the HEAVY modules keeps the live
# set bounded by one module's worth of whole-engine compilations, while
# fast unit modules (pure-python logic, subprocess-only, or a handful of
# tiny jits) skip the drop so they neither pay the clear nor force the
# next module to recompile shared helpers.
_CACHE_HEAVY_MODULES = frozenset({
    "test_algorithms", "test_chaos", "test_incremental", "test_kernels",
    "test_ladder", "test_models", "test_obs", "test_optimized_paths",
    "test_rehash_strategies", "test_resilient", "test_sharding_roofline",
})


@pytest.fixture(autouse=True, scope="module")
def _clear_jax_caches_after_module(request):
    yield
    if request.module.__name__ in _CACHE_HEAVY_MODULES:
        jax.clear_caches()
        gc.collect()


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


@pytest.fixture(scope="session")
def key():
    return jax.random.PRNGKey(0)
