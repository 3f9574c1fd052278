"""Device busy milliseconds per stratum of the traced window's
fixpoints: busy time inside the ``bench.fixpoint`` spans (averaged over
the chips) over the strata those fixpoints ran (``StratumStats``)."""


def reduce(ctx):
    if ctx["workload"]["driver"] != "fixpoint":
        return None
    strata = sum(c["strata"] for c in ctx["stats"]["calls"])
    busy = ctx["trace"].busy_s_within("bench.fixpoint")
    if not strata or not busy:
        return None
    return busy * 1e3 / strata
