"""The DeepSeek-V2 family of the serving benchmark at a width a CPU test
runs, and one call that runs a cell of it through the serving harness
(the device check is the caller's to skip: this never asks for a chip).

The small cell's limit: the bf16 program read 0.036-0.057 on seeds 1, 2
and 2**33 + 3, and the controls 0.53-3.36 (fp8 1.03-1.31, plain rotary
1.03-3.36, routed experts left out 0.53-0.84), so it is held to 0.2."""
from __future__ import annotations

import os
import time

from benchmarks.chip import bench, small

CELL = bench.load_json(os.path.join(bench.HERE, "configs",
                                    "deepseek-v2-lite-ep8.json"))
LIMITS = {"max_logit_gap": 0.2, "min_checked_tokens": 40}


def config(**over):
    """The cell's configuration at a width a test runs: 3 layers (one
    dense), 4 of 16 experts held (experts 4-7), qk 16 + 8 against v 16."""
    cfg = dict(CELL)
    cfg.update(num_hidden_layers=3, hidden_size=64, num_attention_heads=4,
               intermediate_size=128, moe_intermediate_size=32,
               kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
               v_head_dim=16, vocab_size=256, max_len=128,
               n_routed_experts=4, held_experts=[4, 8],
               published={"n_routed_experts": 16, "max_len": 163840})
    cfg["engine"] = dict(cfg["engine"], n_slots=4, prefill_chunk=16,
                         prefix_cache_entries=8)
    cfg.update(over)
    return cfg


def run(mix, seed=1, seconds=1.0):
    """One run of the small cell through `serving.run_cell` on the CPU."""
    import jax
    from benchmarks.chip import serving
    d = jax.devices()[0]
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": len(jax.devices())}
    meter = bench.CompileMeter()
    try:
        return serving.run_cell({"name": "small"}, config(), mix, seed=seed,
                                seconds=seconds, trace=False,
                                t_process=time.perf_counter(), meter=meter,
                                device=device, spec_metrics=small.E2E,
                                limits=LIMITS)
    finally:
        meter.close()
