"""Public op: sort-free combine-route through the Pallas kernel.

``scatter_route_deltas(db, owners, num_shards, per_shard_capacity,
combiner, snapshot=..., interpret=...)`` pads the buffer to kernel-friendly
shapes and calls the kernel.  It never substitutes the jnp oracle on its
own: a call outside the kernel's bounds (``scatter_route_fits``: the
"add" combiner, the block partition scheme, block_size within the VMEM
slab bound, cap·block within the finalize bound, padded_keys < 2^24)
raises, and callers that want the oracle ask for it with
``use_kernel=False``.  The result matches
``core/delta.py:combine_route_scatter`` slot-for-slot (payloads to float
addition order for "add").
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.delta import PAD_KEY, DeltaBuffer
from repro.kernels.pad import pad_to as _pad_to
from repro.kernels.scatter_route.ref import scatter_route_ref
from repro.kernels.scatter_route.scatter_route import (DEFAULT_CHUNK,
                                                       MAX_BLOCK,
                                                       MAX_EXACT_KEY,
                                                       MAX_MATCH_CELLS,
                                                       scatter_route)


def scatter_route_fits(snapshot, per_shard_capacity: int,
                       combiner: str) -> bool:
    """Whether the kernel takes this snapshot, segment capacity and
    combiner."""
    B = snapshot.block_size
    return (combiner == "add" and snapshot.scheme == "block"
            and B <= MAX_BLOCK
            and per_shard_capacity * B <= MAX_MATCH_CELLS
            and snapshot.padded_keys <= MAX_EXACT_KEY)


def scatter_route_deltas(db: DeltaBuffer, owners: jax.Array,
                         num_shards: int, per_shard_capacity: int,
                         combiner: str = "add", *, snapshot,
                         interpret: bool, use_kernel: bool = True
                         ) -> DeltaBuffer:
    """Combine + route ``db`` into per-owner segments, sort-free.

    Same contract as ``core.delta.combine_route_scatter`` (and therefore
    ``combine_route``): merged per key, segments in ascending-key order,
    overflowing owners keep their smallest keys.  ``owners`` must be a
    function of the key via ``snapshot`` (out-of-range owners drop the
    whole key).
    """
    if use_kernel and not scatter_route_fits(snapshot, per_shard_capacity,
                                             combiner):
        raise ValueError(
            f"scatter_route takes the 'add' combiner on a block snapshot "
            f"with block_size <= {MAX_BLOCK}, cap·block <= "
            f"{MAX_MATCH_CELLS} and padded_keys <= {MAX_EXACT_KEY}; got "
            f"{combiner!r}, {snapshot.scheme!r}, block_size="
            f"{snapshot.block_size}, cap={per_shard_capacity} — route this "
            "buffer with the jnp path")
    if snapshot.scheme != "block":
        # (owner, local) slab addressing is only injective under the
        # block scheme; the hash scheme goes through the global-key slab
        # of the core implementation.
        from repro.core.delta import combine_route_scatter
        return combine_route_scatter(db, owners, num_shards,
                                     per_shard_capacity, combiner,
                                     snapshot=snapshot)
    S = num_shards
    B = snapshot.block_size
    mask = db.keys != PAD_KEY
    owners = jnp.where(mask, owners, S)
    local = snapshot.local_index(db.keys)
    if use_kernel:
        keys_p = _pad_to(db.keys, DEFAULT_CHUNK, -1)
        pay_p = _pad_to(db.payload, DEFAULT_CHUNK, 0.0)
        loc_p = _pad_to(local, DEFAULT_CHUNK, -1)
        own_p = _pad_to(owners, DEFAULT_CHUNK, S)
        out_keys, out_pay, out_ann = scatter_route(
            keys_p, pay_p, loc_p, own_p, S, B, per_shard_capacity,
            interpret=interpret)
    else:
        out_keys, out_pay, out_ann = scatter_route_ref(
            db.keys, db.payload, local, owners, S, B, per_shard_capacity,
            combiner)
    # Count / overflow from MERGED key occupancy (jnp; cheap): an owner
    # overflows when it has more distinct live keys than capacity.
    valid = (mask & (owners >= 0) & (owners < S)
             & (db.keys >= 0) & (db.keys < snapshot.padded_keys))
    n_cells = S * B
    addr = jnp.where(valid, owners * B + local, n_cells)
    occ = jnp.zeros((n_cells + 1,), jnp.int32).at[addr].max(
        valid.astype(jnp.int32), mode="drop")[:n_cells]
    per_owner = jnp.sum(occ.reshape(S, B), axis=1)
    return DeltaBuffer(
        keys=out_keys, payload=out_pay, ann=out_ann.astype(jnp.int8),
        count=jnp.sum(jnp.minimum(per_owner, per_shard_capacity)),
        overflowed=db.overflowed | jnp.any(
            per_owner > per_shard_capacity))
