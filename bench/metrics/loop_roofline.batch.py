"""Share of the HBM roofline that the traced fixpoints reach: the least
bytes their strata had to move, over the chips' HBM bandwidth, over the
device busy time inside the ``bench.fixpoint`` spans.

The bytes are a lower bound that holds whatever implements a stratum,
taken from the real (unpadded) vertex and edge counts and the program's
per-stratum counts:

* a dense stratum reads every edge's destination id (4 B) and every
  vertex's value, and writes every vertex's new value (4 B each);
* a sparse stratum reads, for each delta it routes (``delta_counts``),
  the edge that made it (4 B) and reads and writes the value it folds
  into (4 B each); never more than a dense stratum, which would do the
  same work.
"""

EDGE_BYTES = 4
VALUE_BYTES = 4


def dense_bytes(vertices: int, edges: int) -> int:
    return EDGE_BYTES * edges + 2 * VALUE_BYTES * vertices


def stratum_bytes(vertices: int, edges: int, delta_count: int,
                  dense: bool) -> int:
    full = dense_bytes(vertices, edges)
    if dense:
        return full
    return min((EDGE_BYTES + 2 * VALUE_BYTES) * delta_count, full)


def fixpoint_bytes(vertices: int, edges: int, call: dict) -> int:
    return sum(stratum_bytes(vertices, edges, d, u)
               for d, u in zip(call["delta_counts"], call["used_dense"]))


def reduce(ctx):
    if ctx["workload"]["driver"] != "fixpoint":
        return None
    stats, trace = ctx["stats"], ctx["trace"]
    total = sum(fixpoint_bytes(stats["vertices"], stats["edges"][c["graph"]],
                               c) for c in stats["calls"])
    busy = trace.busy_s_within("bench.fixpoint")
    if not total or not busy:
        return None
    chips = len(trace.devices)
    return 100.0 * total / (ctx["peak"]["hbm_bytes_per_s"] * chips * busy)
