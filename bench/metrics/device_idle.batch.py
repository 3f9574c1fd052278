"""Idle share of the chips in the traced window of a fixpoint cell:
1 - busy union / window, in percent, averaged over the chips."""


def reduce(ctx):
    if ctx["workload"]["driver"] != "fixpoint":
        return None
    t = ctx["trace"]
    return 100.0 * (1.0 - t.busy_s / t.window_s)
