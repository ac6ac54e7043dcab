"""Small stand-ins for the benchmark's tests on the CPU: a serving
configuration at a width a test can run, mixes of both loop shapes sized
to it, and one call that runs a cell of them through the serving
harness (the device check is the caller's to skip: this never asks for
a chip)."""
from __future__ import annotations

import os
import time
from typing import Any, Dict

from benchmarks.chip import bench

# the float32 reference's widest gap below its best logit, for the small
# bf16 program at these sizes: 0.009-0.019 on seeds 1-3 (the fp8
# control: 0.37-0.56), so the small cells are held to 0.1
SMALL_LIMITS = {"max_logit_gap": 0.1, "min_checked_tokens": 40}


def config(**over) -> Dict[str, Any]:
    cfg = bench.load_json(os.path.join(bench.HERE, "configs",
                                       "phi3-mini-3.8b.json"))
    cfg.update(num_hidden_layers=2, hidden_size=64, num_attention_heads=4,
               num_key_value_heads=2, head_dim=16, intermediate_size=128,
               vocab_size=256, max_len=128, sliding_window=None)
    cfg["engine"] = dict(cfg["engine"], n_slots=4, prefill_chunk=16,
                         prefix_cache_entries=8)
    cfg.update(over)
    return cfg


CLOSED = {"loop": "closed", "clients": 8,
          "prompt_len": {"dist": "uniform_int", "lo": 8, "hi": 40},
          "max_new": {"dist": "uniform_int", "lo": 8, "hi": 40}}

SESSIONS = {"loop": "sessions", "rate_per_s": 3.0,
            "arrivals": {"dist": "exponential"},
            "turns": {"dist": "uniform_int", "lo": 2, "hi": 3},
            "system_prompt_len": 32,
            "user_len": {"dist": "lognormal", "median": 8, "sigma": 0.6,
                         "min": 2},
            "answer_len": {"dist": "lognormal", "median": 6, "sigma": 0.6,
                           "min": 2, "cap": 24},
            "think_s": {"dist": "exponential", "mean": 0.2}}

E2E = [{"name": n, "unit": u} for n, u in (
    ("ttft_p95_ms", "ms"), ("itl_p95_ms", "ms"), ("tokens_per_s", "tokens/s"),
    ("setup_s", "s"))]


def run(mix: Dict[str, Any], *, seed: int = 1, seconds: float = 2.0
        ) -> Dict[str, Any]:
    """One run of a small cell through `serving.run_cell` on whatever
    device JAX has (the CPU in tests)."""
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
                                device=device, spec_metrics=E2E,
                                limits=SMALL_LIMITS)
    finally:
        meter.close()


def readings(mix: Dict[str, Any], seed: int, seconds: float = 2.0):
    """(program, control) widest gaps of one small window, on the same
    sample of finished requests (what calibrate.py reads on the chip)."""
    from benchmarks.chip import serving
    r = serving.readings(config(), mix, seed, seconds)
    return r["program"]["max_gap"], r["control"]["max_gap"]


# the Rodinia mix at sizes a CPU test can run
RODINIA_SMALL = {"vecadd": {"n": 64}, "saxpy": {"n": 64, "repeats": 2},
                 "sgemm": {"m": 4, "k": 4, "n": 4},
                 "bfs": {"n_nodes": 64, "avg_deg": 3}, "gaussian": {"n": 6},
                 "nn": {"n": 64}, "kmeans": {"n": 32, "k": 4}}


def run_simt(seconds: float = 3.0, seed: int = 1) -> Dict[str, Any]:
    """One run of the SIMT cell's harness, with the Fig. 9 kernels cut to
    RODINIA_SMALL, on whatever device JAX has."""
    import jax
    cfg = bench.load_json(os.path.join(bench.HERE, "configs",
                                       "vortex-8w8t.json"))
    mix = bench.load_json(os.path.join(bench.HERE, "traffic",
                                       "rodinia_fig9.json"))
    for k in mix["kernels"]:
        k["kwargs"] = RODINIA_SMALL[k["name"]]
    d = jax.devices()[0]
    device = {"platform": d.platform, "kind": d.device_kind, "count": 1}
    meter = bench.CompileMeter()
    try:
        return bench.load_module("simt.py").run_cell(
            {"name": "vortex-rodinia-8w8t"}, cfg, mix, seed=seed,
            seconds=seconds, trace=False, t_process=time.perf_counter(),
            meter=meter, device=device,
            spec_metrics=[{"name": "sim_instrs_per_s", "unit": "instr/s"},
                          {"name": "setup_s", "unit": "s"}])
    finally:
        meter.close()
