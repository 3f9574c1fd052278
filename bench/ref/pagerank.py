"""Plain PageRank references: ``pr = 0.15 + 0.85 * sum pr(u) / deg(u)``.

``pagerank_f64`` is the float64 power iteration the benchmark compares
every fixpoint with (a copy of ``ref_pagerank`` in ``chip_smoke.py``).
``pagerank_bf16`` is the same iteration carried in bfloat16 on the
device: the control that a comparison must fail, since bfloat16 is the
precision below the float32 the program states.
"""
from __future__ import annotations

import numpy as np

BASE, DAMPING = 0.15, 0.85


def pagerank_f64(indptr, indices, n, tol=1e-7, max_iters=500):
    deg = np.diff(indptr)
    src = np.repeat(np.arange(n), deg)
    inv = 1.0 / np.maximum(deg, 1)
    pr = np.full(n, BASE)
    for _ in range(max_iters):
        new = BASE + DAMPING * np.bincount(indices, weights=(pr * inv)[src],
                                           minlength=n)
        done = np.max(np.abs(new - pr)) < tol
        pr = new
        if done:
            break
    return pr


def pagerank_bf16(indptr, indices, n, iters=200):
    """The power iteration with every value and sum held in bfloat16."""
    import jax
    import jax.numpy as jnp

    deg = np.diff(indptr)
    src = jnp.asarray(np.repeat(np.arange(n, dtype=np.int32), deg))
    dst = jnp.asarray(indices, jnp.int32)
    inv = jnp.asarray(1.0 / np.maximum(deg, 1), jnp.bfloat16)
    base = jnp.bfloat16(BASE)
    damping = jnp.bfloat16(DAMPING)

    @jax.jit
    def run(pr):
        def step(_, pr):
            contrib = jax.ops.segment_sum((pr * inv)[src], dst,
                                          num_segments=n)
            return base + damping * contrib
        return jax.lax.fori_loop(0, iters, step, pr)

    pr = run(jnp.full((n,), base, jnp.bfloat16))
    return np.asarray(pr.astype(jnp.float32), np.float64)
