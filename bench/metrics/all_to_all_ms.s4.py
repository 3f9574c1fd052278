"""Device milliseconds of all-to-all operations per stratum on the first
chip of a ``shard_map`` fixpoint cell (the rehash between chips)."""


def reduce(ctx):
    if (ctx["workload"]["driver"] != "fixpoint"
            or ctx["config"]["backend"] != "shard_map"):
        return None
    t = ctx["trace"]
    seconds = sum(s for name, s in t.op_seconds(t.devices[0]).items()
                  if "all-to-all" in name)
    strata = sum(c["strata"] for c in ctx["stats"]["calls"])
    if not seconds or not strata:
        return None
    return seconds * 1e3 / strata
