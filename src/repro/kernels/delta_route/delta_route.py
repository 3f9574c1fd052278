"""Pallas TPU kernel: bucket a delta buffer into per-owner rehash segments.

This is the routing half of the paper's ``rehash`` operator (the local
shuffle before the all_to_all): place delta i at segment slot
``owner[i] * cap + rank[i]`` where ``rank`` is the delta's stable position
among earlier deltas with the same owner.  The jnp reference
(``core/delta.py:route_by_owner``) computes ranks with an argsort; sorting
is control-flow-heavy on TPU, so the kernel derives them from a prefix
count on the MXU instead.  Everything is lane-dense: a delta chunk is a
row of 128 lanes, and the packed per-delta values (key+1, annotation,
payload columns) are the rows of one ``[R, C]`` f32 operand.

Per grid step (segment s × slot block b × delta chunk c):

    mine[1, CHUNK]     = (owner == s)                         (VPU compare)
    rank[1, CHUNK]     = mine · before + base                 (MXU: before[j, i]
                         = j < i counts earlier same-owner deltas in chunk)
    match[CB, CHUNK]   = (slot_iota == rank) & mine & rank < cap
    out[R, CB]        += rows · matchᵀ                        (MXU)

with ``base`` the owner's running count carried across chunks in VMEM.
Every slot receives at most one delta, so the contraction places values
exactly; keys ride it as key+1 in f32 (empty slots decode to the -1 PAD
key), exact while keys < 2^24, which the ops wrapper enforces.  Only
segment s's deltas are ranked at each step, so the shard count is not
bounded by a lane axis.

Grid: (segments ×parallel, slot blocks ×parallel, delta chunks
×arbitrary).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_CHUNK = 128        # deltas per grid step (one lane row)
SLOT_BLOCK = 512           # segment slots per grid step
MAX_CAP = 8192             # largest per-segment capacity the kernel takes
MAX_EXACT_KEY = (1 << 24) - 2   # keys+1 must stay exact in f32

_HIGHEST = jax.lax.Precision.HIGHEST


def _contract_lanes(a: jax.Array, b: jax.Array) -> jax.Array:
    """a[m, k] · b[n, k]ᵀ -> f32[m, n] at full f32 precision."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               precision=_HIGHEST,
                               preferred_element_type=jnp.float32)


def _kernel_route(rows_ref, own_ref, out_ref, base_ref, *, cap, slot_block,
                  chunk):
    s = pl.program_id(0)
    b = pl.program_id(1)
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        base_ref[...] = jnp.zeros_like(base_ref)
        out_ref[...] = jnp.zeros_like(out_ref)

    mine = (own_ref[...] == s).astype(jnp.float32)          # [1, CHUNK]
    rows_i = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    cols_i = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    before = (rows_i < cols_i).astype(jnp.float32)
    prior = jnp.dot(mine, before, precision=_HIGHEST,
                    preferred_element_type=jnp.float32)     # [1, CHUNK]
    rank = prior + base_ref[...]
    ok = (mine > 0.0) & (rank < cap)
    slot = jnp.where(ok, rank.astype(jnp.int32), -1)
    slot_iota = b * slot_block + jax.lax.broadcasted_iota(
        jnp.int32, (slot_block, chunk), 0)
    match = (slot_iota == slot).astype(jnp.float32)         # [CB, CHUNK]
    out_ref[...] += _contract_lanes(rows_ref[...], match)
    # Ranks count every delta of the owner (overflowed slots keep
    # consuming ranks, matching route_by_owner), so update pre rank-clip.
    base_ref[...] += jnp.sum(mine, axis=1, keepdims=True)


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


@functools.partial(jax.jit, static_argnames=("num_shards",
                                             "per_shard_capacity", "chunk",
                                             "interpret"))
def delta_route(keys: jax.Array, payload: jax.Array, ann: jax.Array,
                owners: jax.Array, num_shards: int, per_shard_capacity: int,
                chunk: int = DEFAULT_CHUNK, *, interpret: bool
                ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """keys int32[C] (-1 = padding); payload f32[C, W]; ann int32[C];
    owners int32[C] (out-of-range = dropped).  C % chunk == 0.  Returns
    (keys', payload', ann') of length num_shards * per_shard_capacity with
    segment s holding owner-s deltas in stable input order."""
    c_total, w = payload.shape
    if c_total % chunk:
        raise ValueError(f"C={c_total} not a multiple of chunk={chunk}")
    if per_shard_capacity > MAX_CAP:
        raise ValueError(f"per_shard_capacity={per_shard_capacity} exceeds "
                         f"the kernel's bound {MAX_CAP}; use the jnp path")
    S, cap = num_shards, per_shard_capacity
    slot_block = min(_round_up(cap, 128), SLOT_BLOCK)
    cap_p = _round_up(cap, slot_block)
    n_blocks = cap_p // slot_block
    n_rows = _round_up(w + 2, 8)
    live = (keys != -1) & (owners >= 0) & (owners < S)
    own = jnp.where(live, owners, -1).astype(jnp.int32)[None, :]
    rows = jnp.concatenate([
        (keys + 1).astype(jnp.float32)[None, :],
        ann.astype(jnp.float32)[None, :],
        payload.astype(jnp.float32).T,
        jnp.zeros((n_rows - w - 2, c_total), jnp.float32)])
    kernel = functools.partial(_kernel_route, cap=cap,
                               slot_block=slot_block, chunk=chunk)
    out = pl.pallas_call(
        kernel,
        grid=(S, n_blocks, c_total // chunk),
        in_specs=[
            pl.BlockSpec((n_rows, chunk), lambda s, b, c: (0, c)),
            pl.BlockSpec((1, chunk), lambda s, b, c: (0, c)),
        ],
        out_specs=pl.BlockSpec((n_rows, slot_block),
                               lambda s, b, c: (0, s * n_blocks + b)),
        out_shape=jax.ShapeDtypeStruct((n_rows, S * cap_p), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, chunk), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(rows, own)
    out = out.reshape(n_rows, S, cap_p)[:, :, :cap].reshape(n_rows, S * cap)
    return (out[0].astype(jnp.int32) - 1,
            out[2:2 + w].T.astype(payload.dtype),
            out[1].astype(jnp.int32))
