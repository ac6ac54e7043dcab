"""Share of prompt tokens served from the prefix cache in the window, in
percent: the engine's counters `serving.prefix_cache.hit_tokens` over
`serving.prompt_tokens`, taken before and after the window."""


def _delta(ctx, key):
    a = ctx["counters_after"].get(key, {}).get("value", 0)
    b = ctx["counters_before"].get(key, {}).get("value", 0)
    return a - b


def read(ctx):
    prompt = _delta(ctx, "serving.prompt_tokens")
    if prompt <= 0:
        return None
    return 100.0 * _delta(ctx, "serving.prefix_cache.hit_tokens") / prompt
