"""Mean (token, expert) pairs that one held expert takes per MoE layer
per decode step, in tokens: the engine's counter
`serving.moe.held_pairs` (the pairs that decode steps routed to the
experts held here, over the lanes that decoded, summed over MoE layers)
over `serving.decode_ticks`, the MoE layers and the held experts, taken
before the window and after it (the closed loop's drain included).

A chip whose lanes all decode reads top_k x lanes / published experts
on average: 6 x 64 / 64 = 6 for DeepSeek-V2-Lite on 64 slots.  None
where the program has no such counter."""


def _delta(ctx, key):
    return (ctx["counters_after"].get(key, {}).get("value", 0)
            - ctx["counters_before"].get(key, {}).get("value", 0))


def read(ctx):
    pairs = _delta(ctx, "serving.moe.held_pairs")
    ticks = _delta(ctx, "serving.decode_ticks")
    cfg = ctx["cfg"]
    if pairs <= 0 or ticks <= 0 or "n_routed_experts" not in cfg:
        return None
    moe_layers = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    return pairs / (ticks * moe_layers * cfg["n_routed_experts"])
