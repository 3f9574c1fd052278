"""Public op: delta-buffer routing through the Pallas kernel.

``route_deltas(db, owners, num_shards, per_shard_capacity, max_key=...,
interpret=...)`` pads the buffer to kernel-friendly shapes and calls the
kernel.  It never substitutes the jnp oracle on its own: a call outside
the kernel's bounds (``route_fits``) raises, and callers that want the
oracle ask for it with ``use_kernel=False``.  The result matches
``core/delta.py:route_by_owner`` slot-for-slot.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.delta import PAD_KEY, DeltaBuffer
from repro.kernels.delta_route.delta_route import (DEFAULT_CHUNK, MAX_CAP,
                                                   MAX_EXACT_KEY,
                                                   delta_route)
from repro.kernels.delta_route.ref import delta_route_ref
from repro.kernels.pad import pad_to as _pad_to


def route_fits(per_shard_capacity: int, max_key: int) -> bool:
    """Whether the kernel takes segments of ``per_shard_capacity`` slots
    for keys up to ``max_key``."""
    return per_shard_capacity <= MAX_CAP and max_key <= MAX_EXACT_KEY


def route_deltas(db: DeltaBuffer, owners: jax.Array, num_shards: int,
                 per_shard_capacity: int, *, max_key: int, interpret: bool,
                 use_kernel: bool = True) -> DeltaBuffer:
    """Bucket ``db`` into per-owner segments (route_by_owner contract).

    ``max_key``: largest key value the caller can produce — the kernel
    rides keys through an f32 contraction, exact only below 2^24.
    """
    mask = db.keys != PAD_KEY
    owners = jnp.where(mask, owners, num_shards)
    ann32 = db.ann.astype(jnp.int32)
    if use_kernel:
        if not route_fits(per_shard_capacity, max_key):
            raise ValueError(
                f"delta_route takes per_shard_capacity <= {MAX_CAP} and "
                f"keys <= {MAX_EXACT_KEY}; got {per_shard_capacity} and "
                f"{max_key} — route this buffer with the jnp path")
        keys_p = _pad_to(db.keys, DEFAULT_CHUNK, -1)
        pay_p = _pad_to(db.payload, DEFAULT_CHUNK, 0.0)
        ann_p = _pad_to(ann32, DEFAULT_CHUNK, 0)
        own_p = _pad_to(owners, DEFAULT_CHUNK, num_shards)
        out_keys, out_pay, out_ann = delta_route(
            keys_p, pay_p, ann_p, own_p, num_shards, per_shard_capacity,
            interpret=interpret)
    else:
        out_keys, out_pay, out_ann = delta_route_ref(
            db.keys, db.payload, ann32, owners, num_shards,
            per_shard_capacity)
    live = mask & (owners >= 0) & (owners < num_shards)
    per_owner = jnp.zeros((num_shards + 1,), jnp.int32).at[
        jnp.clip(owners, 0, num_shards)].add(
        live.astype(jnp.int32), mode="drop")[:num_shards]
    return DeltaBuffer(
        keys=out_keys, payload=out_pay, ann=out_ann.astype(jnp.int8),
        count=jnp.sum(jnp.minimum(per_owner, per_shard_capacity)),
        overflowed=db.overflowed | jnp.any(per_owner > per_shard_capacity))
