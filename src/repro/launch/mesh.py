"""Production meshes (multi-pod dry-run spec).

``make_production_mesh`` is a FUNCTION (importing this module never touches
jax device state): single-pod = (16, 16) over ("data", "model") = 256 chips;
multi-pod = (2, 16, 16) over ("pod", "data", "model") = 512 chips.  The
"pod" axis is pure data parallelism across ICI-disjoint pods (gradient
all-reduce crosses DCN); "data" is in-pod DP/FSDP; "model" is TP/EP.

REX analytics shards its key space over the FLATTENED device list (a
partition snapshot has no TP notion) — ``flat_mesh`` provides that view.

Every mesh here asks for Auto axis types explicitly.
"""
from __future__ import annotations

from typing import Optional, Sequence

import numpy as np

import jax


def _auto(num_axes: int) -> tuple:
    return (jax.sharding.AxisType.Auto,) * num_axes


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def make_mesh(shape, axes):
    """Arbitrary mesh (tests, small-scale drivers)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=_auto(len(axes)))


def flat_mesh(num_devices: int | None = None, axis: str = "shards", *,
              devices: Optional[Sequence] = None):
    """1-D mesh over an explicit device list, or over all (or the first
    N) devices — the REX partition-snapshot view for the analytics
    engine.

    Under a multi-process (``jax.distributed``) launch, ``jax.devices()``
    is the GLOBAL device list while a worker only owns
    ``jax.local_devices()`` — the legacy ``num_devices``-prefix form
    would silently build a mesh over the first N global devices (all of
    process 0's, typically).  Pass ``devices=`` explicitly in that
    regime; :func:`local_shards` / :func:`shard_process_indices` then
    answer which shards of the flat mesh each process owns.
    """
    if devices is not None:
        devices = list(devices)
        if num_devices is not None and num_devices != len(devices):
            raise ValueError(
                f"flat_mesh: num_devices={num_devices} contradicts the "
                f"explicit device list of length {len(devices)} — pass "
                "one or the other")
        if not devices:
            raise ValueError("flat_mesh: empty device list")
        arr = np.empty(len(devices), dtype=object)
        arr[:] = devices
        return jax.sharding.Mesh(arr, (axis,), axis_types=_auto(1))
    n = num_devices or len(jax.devices())
    return jax.make_mesh((n,), (axis,), axis_types=_auto(1))


# ---------------------------------------------------------------------------
# Process-aware ownership of a flat mesh (multi-process launches).
# ---------------------------------------------------------------------------

def mesh_devices(mesh) -> list:
    """The mesh's devices flattened in mesh order (shard i of a flat
    mesh lives on ``mesh_devices(mesh)[i]``)."""
    return list(np.asarray(mesh.devices, dtype=object).flat)


def shard_process_indices(mesh) -> list[int]:
    """Owning process index per flat-mesh position — the global shard →
    process map a coordinator uses to translate one process's death
    into the shards whose leases just died with it."""
    return [int(d.process_index) for d in mesh_devices(mesh)]


def local_shards(mesh, process_index: int | None = None) -> list[int]:
    """Flat-mesh positions owned by ``process_index`` (default: the
    calling process) — the worker-side view of shard ownership."""
    if process_index is None:
        process_index = jax.process_index()
    return [i for i, p in enumerate(shard_process_indices(mesh))
            if p == process_index]


def dp_axes(mesh) -> tuple:
    """The data-parallel axes of a production mesh (batch sharding)."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def model_axis_size(mesh) -> int:
    return mesh.shape["model"] if "model" in mesh.axis_names else 1


def dp_size(mesh) -> int:
    out = 1
    for a in dp_axes(mesh):
        out *= mesh.shape[a]
    return out
