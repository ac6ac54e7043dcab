"""Device time of the SIMT machine per simulated cycle, in microseconds:
the device time of the machine's run loop (`machine._run_jit`, one
while_loop iteration per cycle) over the launches inside the traced
window, over the cycles those launches simulated."""


def read(ctx):
    tr = ctx["trace"]
    if tr is None:
        return None
    from benchmarks.chip import trace_reduce
    secs, runs = trace_reduce.program_time(tr, "_run_jit")
    cycles = sum(L["cycles"] for L in ctx["launches"] if L["traced"])
    n = sum(L["runs"] for L in ctx["launches"] if L["traced"])
    if not runs or not cycles:
        return None
    if runs != n:
        raise ValueError(f"{runs} machine runs in the trace, {n} launched "
                         "inside it")
    return secs / cycles * 1e6
