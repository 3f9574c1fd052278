"""Pallas TPU kernels for the paper's compute hot-spots.

Each subpackage ships the kernel (pl.pallas_call + explicit BlockSpec VMEM
tiling), a jit'd public wrapper (ops.py), and a pure-jnp oracle (ref.py)
validated in interpret mode over shape/dtype sweeps:

  delta_scatter    — AGGSTATE: delta buffer → dense keyed state (one-hot
                     MXU contraction instead of scatter atomics)
  delta_route      — rehash bucketing: delta buffer → per-owner segments
                     (per-owner histogram + prefix-sum one-hot contraction
                     instead of argsort)
  scatter_route    — sort-free combine-route: delta buffer → per-owner
                     segments merged per key (dense slab accumulate +
                     prefix-sum compaction on the MXU; the scatter
                     strategy of ShardedExecutor.route_strategy)
  edge_propagate   — the REX hot loop: fused join→rehash-local→group-by
                     over destination-tiled CSC (the immutable set)
  kmeans_assign    — blocked point×centroid distances + argmin (MXU)
  flash_attention  — blocked online-softmax attention, GQA-aware (the LM
                     serving/training hot spot)

The route kernels take ``interpret`` without a default; callers pass
:func:`interpret_mode`, the one place that decides it.
"""
import jax


def interpret_mode() -> bool:
    """Pallas interpret mode unless the default backend is a TPU: a
    kernel runs compiled only on the chip it was written for."""
    return jax.default_backend() != "tpu"

