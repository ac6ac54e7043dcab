"""Device time of one run of the engine's decode program
(`Engine._decode_step`), in ms, from the profiler trace: the program's
module events in the traced window, summed, over their count."""


def read(ctx):
    tr = ctx["trace"]
    if tr is None:
        return None
    from benchmarks.chip import trace_reduce
    secs, runs = trace_reduce.program_time(tr, "_decode_step")
    return secs / runs * 1e3 if runs else None
