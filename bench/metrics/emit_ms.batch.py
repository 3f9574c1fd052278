"""Device milliseconds per stratum in emission: busy time inside the
``bench.fixpoint`` spans of the operations under the engine's ``rex.emit``
scope (``bench/scopes.py``), over the strata of the traced fixpoints."""
from bench.scopes import layer_ms


def reduce(ctx):
    return layer_ms(ctx, "emit")
