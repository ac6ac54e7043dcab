"""Device idle share of the traced window, in percent: 1 - (union of
the device's operation intervals) / window, averaged over the chips
(trace_reduce.idle_share)."""


def read(ctx):
    tr = ctx["trace"]
    if tr is None:
        return None
    from benchmarks.chip import trace_reduce
    share = trace_reduce.idle_share(tr)
    return None if share is None else 100.0 * share
