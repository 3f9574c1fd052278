"""Device milliseconds per stratum in the fixpoint loop's own work: busy
time inside the ``bench.fixpoint`` spans of the operations under the
engine's ``rex.select`` (active sources, rung choice) and ``rex.loop``
(condition, counts) scopes (``bench/scopes.py``), over the strata of the
traced fixpoints."""
from bench.scopes import layer_ms


def reduce(ctx):
    return layer_ms(ctx, "select")
