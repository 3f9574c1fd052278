"""Pallas TPU kernel: sort-free combine-route via a per-owner slab.

This is the scatter-based physical implementation of the fused
combine + rehash-local operator (the jnp reference is
``core/delta.py:combine_route_scatter``).  Where the sort-based path pays
an O(C log C) lexicographic sort per stratum, this kernel exploits that —
under a block partition snapshot — the destination slot of a key is a pure
function of the key itself: deltas are **accumulated into a dense slab**
addressed by the key's local index inside its owner block, then the slab
is **compacted by a prefix count over cell occupancy** into the owner's
segment, in ascending-key order (identical slot layout to the sort path).

Everything is lane-dense.  A delta chunk is a row of 128 lanes; the packed
per-delta values (payload columns, then a row of ones that counts
occupancy) are the rows of one ``[R, C]`` f32 operand, and the slab is an
``[R, B]`` VMEM scratch with the owner's cells along lanes.

Per grid step (output segment s × delta chunk c):

    onehot[B, CHUNK] = (cell_iota == local) & (owner == s)   (VPU compare)
    slab[R, B]      += rows · onehotᵀ                        (MXU)

and at the final chunk the compaction, FIN lanes of cells at a time:

    live[1, FIN]     = occupancy > 0
    rank[1, FIN]     = live · incl + (live cells before)     (MXU prefix)
    match[FIN, FIN]  = (slot_iota == rank) & live            (VPU compare)
    out[R, cap]     += (payload | filled | key+1) · matchᵀ   (MXU)

Keys are decoded from the cell index itself (s·B + cell), so no key rides
the per-delta contraction; only the final decode does, bounding exactness
at padded_keys < 2^24 (enforced by the ops wrapper).  The kernel
implements the "add" combiner (the engine's PageRank/adsorption hot
path); the engine routes other combiners through jnp.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_CHUNK = 128             # deltas per grid step (one lane row)
FIN = 512                       # cells / slots per finalize contraction
MAX_BLOCK = 4096                # slab cells per owner kept in VMEM scratch
MAX_MATCH_CELLS = 1 << 22       # cap·block bound on the finalize work
MAX_EXACT_KEY = (1 << 24) - 2   # keys+1 must stay exact in f32

_HIGHEST = jax.lax.Precision.HIGHEST


def _contract_lanes(a: jax.Array, b: jax.Array) -> jax.Array:
    """a[m, k] · b[n, k]ᵀ -> f32[m, n] at full f32 precision."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               precision=_HIGHEST,
                               preferred_element_type=jnp.float32)


def _kernel_scatter_route(rows_ref, local_ref, own_ref, out_ref, slab_ref,
                          *, block, block_p, cap_p, width, chunk):
    s = pl.program_id(0)
    c = pl.program_id(1)
    nc = pl.num_programs(1)
    n_rows = slab_ref.shape[0]

    @pl.when(c == 0)
    def _init():
        slab_ref[...] = jnp.zeros_like(slab_ref)

    # Slab accumulate: one-hot cell match, contracted on the MXU.  Every
    # delta of this owner hits exactly one cell; duplicate keys accumulate
    # there.  Deltas of other owners match no cell.
    local = jnp.where(own_ref[...] == s, local_ref[...], -1)   # [1, CHUNK]
    cell_iota = jax.lax.broadcasted_iota(jnp.int32, (block_p, chunk), 0)
    onehot = (cell_iota == local).astype(jnp.float32)          # [B, CHUNK]
    slab_ref[...] += _contract_lanes(rows_ref[...], onehot)

    @pl.when(c == nc - 1)
    def _finalize():
        fin_b = min(FIN, block_p)
        fin_c = min(FIN, cap_p)
        out_ref[...] = jnp.zeros_like(out_ref)
        r_i = jax.lax.broadcasted_iota(jnp.int32, (fin_b, fin_b), 0)
        c_i = jax.lax.broadcasted_iota(jnp.int32, (fin_b, fin_b), 1)
        incl = (r_i <= c_i).astype(jnp.float32)
        sub = jax.lax.broadcasted_iota(jnp.int32, (n_rows, fin_b), 0)
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, fin_b), 1)

        def cells(kb, before):
            start = pl.multiple_of(kb * fin_b, fin_b)
            blk = slab_ref[:, pl.ds(start, fin_b)]               # [R, FIN]
            occ = jnp.sum(jnp.where(sub == width, blk, 0.0), axis=0,
                          keepdims=True)                         # [1, FIN]
            live = (occ > 0.0).astype(jnp.float32)
            # Prefix count without a cumsum: rank = #live cells before
            # this one, cell order being key order under the block scheme.
            rank = jnp.dot(live, incl, precision=_HIGHEST,
                           preferred_element_type=jnp.float32) + before - 1
            slot = jnp.where(live > 0.0, rank.astype(jnp.int32), -1)
            key1 = (s * block + start + lane + 1).astype(jnp.float32)
            lhs = jnp.where(sub == width, live,
                            jnp.where(sub == width + 1, key1, blk))

            def slots(cb, carry):
                first = pl.multiple_of(cb * fin_c, fin_c)
                slot_iota = first + jax.lax.broadcasted_iota(
                    jnp.int32, (fin_c, fin_b), 0)
                match = (slot_iota == slot).astype(jnp.float32)
                out_ref[:, pl.ds(first, fin_c)] += _contract_lanes(lhs, match)
                return carry

            jax.lax.fori_loop(0, cap_p // fin_c, slots, 0)
            return before + jnp.sum(live, axis=1, keepdims=True)

        jax.lax.fori_loop(0, block_p // fin_b, cells,
                          jnp.zeros((1, 1), jnp.float32))


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


@functools.partial(jax.jit, static_argnames=("num_shards", "block_size",
                                             "per_shard_capacity", "chunk",
                                             "interpret"))
def scatter_route(keys: jax.Array, payload: jax.Array, local: jax.Array,
                  owners: jax.Array, num_shards: int, block_size: int,
                  per_shard_capacity: int, chunk: int = DEFAULT_CHUNK, *,
                  interpret: bool
                  ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """keys int32[C] (-1 = padding); payload f32[C, W]; local int32[C]
    (key's index inside its owner block, out-of-range = dropped); owners
    int32[C] (out-of-range = dropped).  C % chunk == 0.  Block partition +
    "add" combiner only (callers dispatch through ops.py).  Returns
    (keys', payload', ann') of length num_shards * per_shard_capacity with
    segment s holding owner-s deltas merged per key, ascending-key order.
    """
    c_total, w = payload.shape
    if c_total % chunk:
        raise ValueError(f"C={c_total} not a multiple of chunk={chunk}")
    if block_size > MAX_BLOCK:
        raise ValueError(f"block_size={block_size} exceeds the VMEM slab "
                         f"bound {MAX_BLOCK}; use the jnp path")
    if per_shard_capacity * block_size > MAX_MATCH_CELLS:
        raise ValueError(
            f"cap·block = {per_shard_capacity * block_size} exceeds the "
            f"finalize bound {MAX_MATCH_CELLS}; use the jnp path")
    S, B, cap = num_shards, block_size, per_shard_capacity
    block_p = _round_up(B, 128)
    if block_p > FIN:
        block_p = _round_up(B, FIN)
    cap_p = _round_up(cap, 128)
    if cap_p > FIN:
        cap_p = _round_up(cap, FIN)
    n_rows = _round_up(w + 2, 8)
    live = ((keys != -1) & (owners >= 0) & (owners < S)
            & (local >= 0) & (local < B))
    local = jnp.where(live, local, -1).astype(jnp.int32)[None, :]
    owners = jnp.where(live, owners, -1).astype(jnp.int32)[None, :]
    # Rows: payload columns, then a row of ones that counts occupancy
    # (row w); the finalize reuses rows w and w+1 for filled and key+1.
    rows = jnp.concatenate([
        jnp.where(live[None, :], payload.astype(jnp.float32).T, 0.0),
        jnp.ones((1, c_total), jnp.float32),
        jnp.zeros((n_rows - w - 1, c_total), jnp.float32)])
    kernel = functools.partial(_kernel_scatter_route, block=B,
                               block_p=block_p, cap_p=cap_p, width=w,
                               chunk=chunk)
    out = pl.pallas_call(
        kernel,
        grid=(S, c_total // chunk),
        in_specs=[
            pl.BlockSpec((n_rows, chunk), lambda s, c: (0, c)),
            pl.BlockSpec((1, chunk), lambda s, c: (0, c)),
            pl.BlockSpec((1, chunk), lambda s, c: (0, c)),
        ],
        out_specs=pl.BlockSpec((n_rows, cap_p), lambda s, c: (0, s)),
        out_shape=jax.ShapeDtypeStruct((n_rows, S * cap_p), jnp.float32),
        scratch_shapes=[pltpu.VMEM((n_rows, block_p), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(rows, local, owners)
    out = out.reshape(n_rows, S, cap_p)[:, :, :cap].reshape(n_rows, S * cap)
    # Merged slots carry the ADJUST annotation (code 3), like the jnp
    # combine paths; empty slots carry 0.
    filled = out[w] > 0.0
    return (out[w + 1].astype(jnp.int32) - 1,
            out[:w].T.astype(payload.dtype),
            jnp.where(filled, 3, 0).astype(jnp.int32))
