"""Analytics pipeline: relational operators + cost-based optimizer (§3, §5).

A Listing-1-style workload: join an edge relation against per-page
metadata, aggregate with UDAs, and show the optimizer's pre-aggregation
pushdown + UDF rank ordering decisions on the plan.

  PYTHONPATH=src python examples/analytics_pipeline.py
"""
import numpy as np

import jax.numpy as jnp

from repro.compile_cache import enable_compile_cache
from repro.core.operators import (Table, apply_function, fk_join, group_by,
                                  select)
from repro.core.optimizer import (best_udf_join_interleaving,
                                  estimate_recursive_cost, optimize)
from repro.core.plan import (PlanNode, groupby, join, plan_runtime, rehash,
                             scan, udf)

rng = np.random.default_rng(0)
enable_compile_cache()
N_EDGES, N_PAGES = 100_000, 4_096

# ---- physical execution ---------------------------------------------------
edges = Table.from_columns(
    src=jnp.asarray(rng.integers(0, N_PAGES, N_EDGES).astype(np.int32)),
    dst=jnp.asarray(rng.integers(0, N_PAGES, N_EDGES).astype(np.int32)))
pages = Table.from_columns(
    page=jnp.asarray(np.arange(N_PAGES, dtype=np.int32)),
    quality=jnp.asarray(rng.random(N_PAGES).astype(np.float32)))

t = fk_join(edges, pages, "src", "page", n_keys=N_PAGES)
t = apply_function(t, lambda q: {"w": q * q}, ("quality",))     # UDF
t = select(t, lambda t: t.columns["w"] > 0.25)                  # predicate
out = group_by(t, "dst", {"mass": ("sum", "w"),
                          "fans": ("count", "w")}, n_keys=N_PAGES)
best = int(jnp.argmax(out.columns["mass"]))
print(f"pipeline: {int(t.count())} joined rows pass the filter; "
      f"page {best} has max incoming mass "
      f"{float(out.columns['mass'][best]):.2f}")

# ---- what the optimizer decides (§5) ---------------------------------------
base = scan("edges", N_EDGES)
cheap = PlanNode(op="udf", name="cheap_filter", cost_per_tuple=1e-9,
                 selectivity=0.3)
pricey = PlanNode(op="udf", name="expensive_udf", cost_per_tuple=1e-6,
                  selectivity=0.9)
plan, cost = best_udf_join_interleaving(
    base, [pricey, cheap],
    lambda n: join(n, scan("pages", N_PAGES), key_fk=True), 1)
print(f"§5.1 interleaving: best plan cost {cost:.4f}s "
      "(cheap+selective UDF pushed below the join, expensive one above)")

g = groupby(rehash(udf(scan("edges", N_EDGES), "w", 1e-9)), "sum",
            n_groups=N_PAGES, composable=True)
print(f"§5.2 pre-agg pushdown: {plan_runtime(g):.4f}s -> "
      f"{plan_runtime(optimize(g)):.4f}s")

total, final_card, iters = estimate_recursive_cost(
    base_cost=0.1, base_card=N_PAGES,
    step_cost_fn=lambda c: c * 2e-7, step_card_fn=lambda c: 0.6 * c)
print(f"§5.3 recursive estimate: {iters} strata simulated, "
      f"total {total:.4f}s, final Δ cardinality {final_card:.0f}")
