"""Per-kernel shape/dtype sweeps: Pallas (interpret mode off the chip) vs
ref oracle."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import jax.numpy as jnp

from repro.data.graphs import make_powerlaw_graph
from repro.kernels import interpret_mode
from repro.kernels.delta_route import (delta_route, delta_route_ref,
                                       route_deltas)
from repro.kernels.scatter_route import (scatter_route, scatter_route_ref,
                                         scatter_route_deltas)
from repro.kernels.delta_scatter import (apply_delta, delta_scatter,
                                         delta_scatter_ref)
from repro.kernels.edge_propagate import (build_tiled_csc, edge_propagate,
                                          edge_propagate_ref, propagate)
from repro.kernels.flash_attention import attention_ref, flash_attention
from repro.kernels.kmeans_assign import assign, kmeans_assign_ref
from repro.core.delta import ANN_ADJUST, DeltaBuffer


class TestDeltaScatter:
    @pytest.mark.parametrize("n,w,c", [(512, 1, 256), (1024, 4, 512),
                                       (2048, 8, 256), (512, 1, 1024)])
    @pytest.mark.parametrize("combiner", ["add", "min", "max"])
    def test_sweep(self, n, w, c, combiner):
        if combiner in ("min", "max") and w != 1:
            pytest.skip("min/max kernels are W=1")
        rng = np.random.default_rng(n + c)
        state = jnp.asarray(rng.normal(size=(n, w)).astype(np.float32))
        idx = jnp.asarray(rng.integers(-1, n, size=c).astype(np.int32))
        pay = jnp.asarray(rng.normal(size=(c, w)).astype(np.float32))
        out_k = delta_scatter(state, idx, pay, combiner, tile_n=256,
                              chunk=256)
        out_r = delta_scatter_ref(state, idx, pay, combiner)
        np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                                   rtol=1e-5, atol=1e-5)

    def test_collisions_accumulate(self):
        state = jnp.zeros((512, 1))
        idx = jnp.zeros(256, jnp.int32)          # all hit key 0
        pay = jnp.ones((256, 1))
        out = delta_scatter(state, idx, pay, "add")
        assert float(out[0, 0]) == 256.0

    @settings(max_examples=20, deadline=None)
    @given(seed=st.integers(0, 999))
    def test_property_apply_delta_buffer(self, seed):
        rng = np.random.default_rng(seed)
        n = 512
        cnt = rng.integers(0, 64)
        keys = np.full(64, -1, np.int32)
        keys[:cnt] = rng.integers(0, n, cnt)
        pay = rng.normal(size=(64, 1)).astype(np.float32)
        db = DeltaBuffer(keys=jnp.asarray(keys), payload=jnp.asarray(pay),
                         ann=jnp.full(64, ANN_ADJUST, jnp.int8),
                         count=jnp.asarray(cnt),
                         overflowed=jnp.asarray(False))
        state = jnp.asarray(rng.normal(size=n).astype(np.float32))
        out_k = apply_delta(state, db, "add", use_kernel=True)
        out_r = apply_delta(state, db, "add", use_kernel=False)
        np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                                   rtol=1e-5, atol=1e-5)


class TestDeltaRoute:
    @pytest.mark.parametrize("c,w,shards,cap", [
        (256, 1, 4, 64), (512, 2, 8, 32), (256, 4, 1, 256), (1024, 1, 7, 8)])
    def test_sweep(self, c, w, shards, cap):
        rng = np.random.default_rng(c + shards)
        keys = rng.integers(-1, 1000, size=c).astype(np.int32)
        pay = rng.normal(size=(c, w)).astype(np.float32)
        ann = rng.integers(0, 4, size=c).astype(np.int32)
        owners = np.where(keys >= 0, keys % shards, shards).astype(np.int32)
        args = (jnp.asarray(keys), jnp.asarray(pay), jnp.asarray(ann),
                jnp.asarray(owners), shards, cap)
        out_k = delta_route(*args, interpret=interpret_mode())
        out_r = delta_route_ref(*args)
        for a, b in zip(out_k, out_r):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_matches_route_by_owner(self):
        """ops-level dispatch == the engine's jnp routing, slot for slot."""
        from repro.core.delta import DeltaBuffer, route_by_owner
        rng = np.random.default_rng(0)
        n, shards, cap = 300, 6, 40
        count = 250
        keys = np.full(n, -1, np.int32)
        keys[:count] = rng.integers(0, 500, count)
        pay = rng.normal(size=(n, 2)).astype(np.float32)
        db = DeltaBuffer(keys=jnp.asarray(keys), payload=jnp.asarray(pay),
                         ann=jnp.asarray(rng.integers(0, 4, n), jnp.int8),
                         count=jnp.asarray(count),
                         overflowed=jnp.asarray(False))
        owners = jnp.where(db.keys >= 0, db.keys % shards, shards)
        ref = route_by_owner(db, owners, shards, cap)
        for use_kernel in (False, True):
            got = route_deltas(db, owners, shards, cap, max_key=500,
                               interpret=interpret_mode(),
                               use_kernel=use_kernel)
            np.testing.assert_array_equal(np.asarray(ref.keys),
                                          np.asarray(got.keys))
            np.testing.assert_array_equal(np.asarray(ref.payload),
                                          np.asarray(got.payload))
            np.testing.assert_array_equal(np.asarray(ref.ann),
                                          np.asarray(got.ann))
            assert int(ref.count) == int(got.count)
            assert bool(ref.overflowed) == bool(got.overflowed)

    def test_overflowing_segment_sets_flag(self):
        from repro.core.delta import DeltaBuffer
        keys = jnp.arange(8, dtype=jnp.int32)          # all owner 0
        db = DeltaBuffer(keys=keys, payload=jnp.ones((8, 1)),
                         ann=jnp.zeros(8, jnp.int8), count=jnp.asarray(8),
                         overflowed=jnp.asarray(False))
        out = route_deltas(db, jnp.zeros(8, jnp.int32), 2, 4, max_key=8,
                           interpret=interpret_mode())
        assert bool(out.overflowed) and int(out.count) == 4


class TestScatterRoute:
    @pytest.mark.parametrize("c,w,shards,block,cap", [
        (256, 1, 4, 64, 32), (512, 2, 8, 32, 32), (256, 4, 1, 256, 128),
        (512, 1, 7, 40, 8)])
    def test_sweep_kernel_vs_ref(self, c, w, shards, block, cap):
        rng = np.random.default_rng(c + shards)
        n_keys = shards * block
        keys = rng.integers(-1, n_keys, size=c).astype(np.int32)
        pay = rng.normal(size=(c, w)).astype(np.float32)
        owners = np.where(keys >= 0, keys // block, shards).astype(np.int32)
        local = np.where(keys >= 0, keys % block, -1).astype(np.int32)
        args = (jnp.asarray(keys), jnp.asarray(pay), jnp.asarray(local),
                jnp.asarray(owners), shards, block, cap)
        out_k = scatter_route(*args, interpret=interpret_mode())
        out_r = scatter_route_ref(*args, combiner="add")
        np.testing.assert_array_equal(np.asarray(out_k[0]),
                                      np.asarray(out_r[0]))
        np.testing.assert_allclose(np.asarray(out_k[1]),
                                   np.asarray(out_r[1]),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_array_equal(np.asarray(out_k[2]),
                                      np.asarray(out_r[2]))

    @pytest.mark.parametrize("combiner", ["add", "min", "max", "replace"])
    def test_ops_matches_combine_route(self, combiner):
        """ops-level dispatch == the engine's fused sort path, slot for
        slot (payloads to float addition order for add)."""
        from repro.core.delta import (ANN_ADJUST, DeltaBuffer,
                                      combine_route)
        from repro.core.partition import PartitionSnapshot
        rng = np.random.default_rng(7)
        n, shards, cap, keyspace = 300, 6, 40, 500
        count = 250
        keys = np.full(n, -1, np.int32)
        keys[:count] = rng.integers(0, keyspace, count)
        pay = rng.normal(size=(n, 2)).astype(np.float32)
        db = DeltaBuffer(keys=jnp.asarray(keys), payload=jnp.asarray(pay),
                         ann=jnp.full(n, ANN_ADJUST, jnp.int8),
                         count=jnp.asarray(count),
                         overflowed=jnp.asarray(False))
        snap = PartitionSnapshot(n_keys=keyspace, num_shards=shards)
        owners = snap.owner_of(db.keys)
        ref = combine_route(db, owners, shards, cap, combiner)
        if combiner != "add":
            # The kernel implements "add" only, and never hands another
            # combiner to the oracle behind the caller's back.
            with pytest.raises(ValueError, match="jnp path"):
                scatter_route_deltas(db, owners, shards, cap, combiner,
                                     snapshot=snap,
                                     interpret=interpret_mode())
        for use_kernel in (False, True) if combiner == "add" else (False,):
            got = scatter_route_deltas(db, owners, shards, cap, combiner,
                                       snapshot=snap,
                                       interpret=interpret_mode(),
                                       use_kernel=use_kernel)
            np.testing.assert_array_equal(np.asarray(ref.keys),
                                          np.asarray(got.keys))
            np.testing.assert_array_equal(np.asarray(ref.ann),
                                          np.asarray(got.ann))
            if combiner == "add":
                np.testing.assert_allclose(np.asarray(ref.payload),
                                           np.asarray(got.payload),
                                           rtol=1e-5, atol=1e-6)
            else:
                np.testing.assert_array_equal(np.asarray(ref.payload),
                                              np.asarray(got.payload))
            assert int(ref.count) == int(got.count)
            assert bool(ref.overflowed) == bool(got.overflowed)


class TestEdgePropagate:
    @pytest.mark.parametrize("n,deg", [(600, 6.0), (1500, 12.0)])
    @pytest.mark.parametrize("combiner", ["add", "min"])
    def test_sweep(self, n, deg, combiner):
        indptr, indices = make_powerlaw_graph(n, avg_degree=deg, seed=n)
        csc = build_tiled_csc(indptr, indices, n, tile_n=512, chunk=256)
        rng = np.random.default_rng(1)
        payload = jnp.asarray(rng.normal(size=n).astype(np.float32))
        out_k = propagate(payload, csc, n, combiner, use_kernel=True)
        out_r = propagate(payload, csc, n, combiner, use_kernel=False)
        mask = np.isfinite(np.asarray(out_r))
        np.testing.assert_allclose(np.asarray(out_k)[mask],
                                   np.asarray(out_r)[mask],
                                   rtol=1e-4, atol=1e-4)

    def test_matches_pagerank_dense_push(self):
        """The kernel's contract == the engine's dense push semantics."""
        n = 512
        indptr, indices = make_powerlaw_graph(n, avg_degree=8.0, seed=3)
        deg = np.maximum(np.diff(indptr), 1)
        pr = np.random.default_rng(0).random(n).astype(np.float32)
        csc = build_tiled_csc(indptr, indices, n)
        out = np.asarray(propagate(jnp.asarray(pr / deg), csc, n, "add"))
        expect = np.zeros(n, np.float32)
        src = np.repeat(np.arange(n), np.diff(indptr))
        np.add.at(expect, indices, (pr / deg)[src])
        np.testing.assert_allclose(out, expect, rtol=1e-4, atol=1e-4)


class TestKMeansAssign:
    @pytest.mark.parametrize("n,k,d", [(1000, 8, 2), (777, 32, 5),
                                       (4096, 128, 2), (256, 3, 16)])
    def test_sweep(self, n, k, d):
        rng = np.random.default_rng(n * k)
        pts = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
        cents = jnp.asarray(rng.normal(size=(k, d)).astype(np.float32))
        a_k, d_k = assign(pts, cents, tile_p=256)
        a_r, d_r = kmeans_assign_ref(pts, cents)
        assert bool(jnp.all(a_k == a_r))
        np.testing.assert_allclose(np.asarray(d_k), np.asarray(d_r),
                                   rtol=1e-4, atol=1e-4)


class TestFlashAttention:
    @pytest.mark.parametrize("b,h,hkv,t,s,d", [
        (2, 4, 2, 256, 256, 64), (1, 8, 8, 128, 128, 32),
        (2, 4, 1, 256, 384, 64), (1, 2, 2, 384, 128, 128)])
    @pytest.mark.parametrize("causal", [True, False])
    def test_sweep(self, b, h, hkv, t, s, d, causal):
        if causal and t != s:
            pytest.skip("causal kernels assume aligned diag (t == s)")
        rng = np.random.default_rng(t + s)
        q = jnp.asarray(rng.normal(size=(b, h, t, d)).astype(np.float32))
        k = jnp.asarray(rng.normal(size=(b, hkv, s, d)).astype(np.float32))
        v = jnp.asarray(rng.normal(size=(b, hkv, s, d)).astype(np.float32))
        out_k = flash_attention(q, k, v, causal=causal)
        out_r = attention_ref(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                                   rtol=2e-4, atol=2e-4)

    def test_blocked_xla_variant_matches(self):
        from repro.models.attention import blocked_attention
        rng = np.random.default_rng(0)
        q = jnp.asarray(rng.normal(size=(2, 4, 256, 32)).astype(np.float32))
        k = jnp.asarray(rng.normal(size=(2, 2, 256, 32)).astype(np.float32))
        v = jnp.asarray(rng.normal(size=(2, 2, 256, 32)).astype(np.float32))
        out_b = blocked_attention(q, k, v, causal=True, block_k=64)
        out_r = attention_ref(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out_b), np.asarray(out_r),
                                   rtol=2e-4, atol=2e-4)
