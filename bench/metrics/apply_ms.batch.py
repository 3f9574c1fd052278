"""Device milliseconds per stratum in apply: busy time inside the
``bench.fixpoint`` spans of the operations under the engine's
``rex.apply`` scope (``bench/scopes.py``), over the strata of the traced
fixpoints."""
from bench.scopes import layer_ms


def reduce(ctx):
    return layer_ms(ctx, "apply")
