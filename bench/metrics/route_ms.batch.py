"""Device milliseconds per stratum in the rehash: busy time inside the
``bench.fixpoint`` spans of the operations under the engine's
``rex.route`` scope (``bench/scopes.py``), over the strata of the traced
fixpoints."""
from bench.scopes import layer_ms


def reduce(ctx):
    return layer_ms(ctx, "route")
