"""Model FLOP/s utilization of the traced part of the window, in
percent: the operations a plain forward needs for every token the
engine processed in the engine steps inside the trace (each decoded
token, and the prompt of each request whose first token came there,
every token attending over the positions before it; work/<family>.py,
from the configuration's sizes), over the traced window's seconds times
the chip's peak bf16 FLOP/s (peaks.json).

A prompt counts whole in the step of its first token, from position 0,
so a cell whose prompts hit the prefix cache reads nothing here."""


def _delta(ctx, key):
    return (ctx["counters_after"].get(key, {}).get("value", 0)
            - ctx["counters_before"].get(key, {}).get("value", 0))


def read(ctx):
    tr = ctx["trace"]
    if tr is None or _delta(ctx, "serving.prefix_cache.hit_tokens"):
        return None
    from benchmarks.chip import trace_reduce
    work, cfg = ctx["work"], ctx["cfg"]
    flops = 0
    for s in ctx["step_log"]:
        if s["traced"]:
            flops += sum(work.token_flops(cfg, n) for n in s["spans"])
            flops += sum(work.token_flops(cfg, p + 1)
                         for P in s["prompts"] for p in range(P))
    if not flops:
        return None
    peak = ctx["peaks"]["bf16_flops_per_s"] * ctx["device"]["count"]
    return 100.0 * flops / (trace_reduce.window_s(tr) * peak)
