"""Share of its roofline that the engine's decode program reaches, in
percent: for the decode steps in the traced window, the least time
their work needs on this chip, over their measured device time.

The work of one step is that of a plain decode of the lanes that
decoded in it (not every lane the program computes): every weight and
the LM head read once, the embedding rows, the live keys and values of
those lanes at the logical head_dim, and their matrix products and
attention, all from the configuration's sizes (work/<family>.py).  The
least time is the larger of operations over peak FLOP/s and bytes over
peak bandwidth (peaks.json)."""


def read(ctx):
    tr = ctx["trace"]
    if tr is None:
        return None
    from benchmarks.chip import trace_reduce
    secs, runs = trace_reduce.program_time(tr, "_decode_step")
    steps = [s["spans"] for s in ctx["step_log"] if s["traced"]
             and s["spans"]]
    if not runs or not steps:
        return None
    if runs != len(steps):
        raise ValueError(f"{runs} decode runs in the trace, {len(steps)} "
                         "decode steps stamped inside it")
    work = ctx["work"]
    need = sum(work.least_seconds(*work.decode_step(ctx["cfg"], sp),
                                  ctx["peaks"]) for sp in steps)
    return 100.0 * need / secs
