"""A whole run of the DeepSeek-V2 family's small cell on the CPU (the
harness's look for a chip skipped), clean and with the timed path broken
underneath: `correct` must come out false for a token altered in every
lane or in the last lane only, and for a decode step that keeps its
latent cache; and the work functions and the held-expert reader counted
by hand."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import pytest  # noqa: E402

from benchmarks.chip import bench, serving, small  # noqa: E402
from benchmarks.chip.small_deepseek import CELL, run  # noqa: E402

work = bench.load_module("work/deepseek_v2.py")


def test_clean_run_is_correct():
    res = run(small.CLOSED)
    assert res["correct"], res["compared"]
    assert res["failed"] == 0
    assert res["compared"]["checked_slots"]["value"] == 4
    assert {"itl_p95_ms", "tokens_per_s", "setup_s"} <= set(res["metrics"])


def _alter_tokens(eng):
    step, V = eng._decode_fn, eng.cfg.vocab_size

    def altered(*a):
        out = step(*a)
        return ((out[0] + 1) % V,) + tuple(out[1:])
    eng._decode_fn = altered


def _alter_last_lane(eng):
    step, V = eng._decode_fn, eng.cfg.vocab_size

    def altered(*a):
        out = step(*a)
        return (out[0].at[-1].set((out[0][-1] + 1) % V),) + tuple(out[1:])
    eng._decode_fn = altered


def _keep_state(eng):
    import jax
    import jax.numpy as jnp
    step = eng._decode_fn

    def unchanged(params, caches, *rest):
        kept = jax.tree.map(jnp.copy, caches)
        out = step(params, caches, *rest)
        return (out[0], kept) + tuple(out[2:])
    eng._decode_fn = unchanged


FAULTS = {"token_altered": _alter_tokens,
          "one_lane_altered": _alter_last_lane,
          "state_unchanged": _keep_state}


@pytest.mark.parametrize("fault", list(FAULTS))
def test_broken_step_is_not_correct(fault, monkeypatch):
    build = serving.build_engine

    def broken(*args):
        eng = build(*args)
        FAULTS[fault](eng)
        return eng
    monkeypatch.setattr(serving, "build_engine", broken)
    res = run(small.CLOSED)
    assert not res["correct"], res["compared"]
    assert res["compared"]["checked_slots"]["value"] == 4
    gap = res["compared"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]


# 1 dense + 2 MoE layers, d 8, 2 heads, latent 4, qk 2 + 2, v 2, dense
# MLP 16, experts of 4, 2 shared, 2 of 8 experts held, top-2, vocab 10
SMALL = {"num_hidden_layers": 3, "first_k_dense_replace": 1,
         "hidden_size": 8, "num_attention_heads": 2, "kv_lora_rank": 4,
         "qk_nope_head_dim": 2, "qk_rope_head_dim": 2, "v_head_dim": 2,
         "intermediate_size": 16, "moe_intermediate_size": 4,
         "n_shared_experts": 2, "n_routed_experts": 2,
         "published": {"n_routed_experts": 8}, "num_experts_per_tok": 2,
         "vocab_size": 10, "torch_dtype": "bfloat16"}


def test_work_by_hand():
    # attention: q 8*2*4 + latent and key 8*6 + up-projection 4*2*4
    # + output 2*2*8 = 64 + 48 + 32 + 32 = 176
    assert work._attn_params(SMALL) == 176
    # 2 bytes * (3 layers * (176 + 2 norms of 8 + latent norm 4) + dense
    # MLP 3*8*16 + 2 MoE layers * (router 8*8 + 4 experts of 3*8*4)
    # + final norm 8 + head 80)
    assert work.weight_bytes(SMALL) == 2 * (3 * 196 + 384 + 2 * (64 + 384)
                                            + 8 + 80) == 3912
    # 2 bytes * 3 layers * (4 + 2)
    assert work.cache_bytes_per_position(SMALL) == 36
    # 2 * (3 * 176 + 384 + 2 * (64 + 2 * 96 + 2 * 2/8 * 96) + 80) = 3200,
    # attention 2 * 3 layers * 2 heads * (2 * 4 + 2) = 120 per position
    assert work.token_flops(SMALL, 5) == 3200 + 120 * 5
    flops, nbytes = work.decode_step(SMALL, [5, 3])
    assert flops == 3200 * 2 + 120 * 8
    assert nbytes == 3912 + 2 * 8 * 2 + 36 * 6 + 36 * 2


def test_cell_decode_needs_its_weights():
    """At the cell's sizes a decode step is bound by bytes: 5.8 GB of
    weights (3.6 GB of them held experts) and 64 lanes of latent cache at
    500 positions (1.0 GB) at 819 GB/s is 8.3 ms."""
    assert work.weight_bytes(CELL) == pytest.approx(5.8025e9, rel=1e-3)
    assert work.cache_bytes_per_position(CELL) == 31104
    t = work.least_seconds(*work.decode_step(CELL, [500] * 64),
                           bench.peaks_for("TPU v5 lite"))
    assert 8.2e-3 < t < 8.4e-3


def test_held_expert_reader():
    read = bench.metric_reader("held_expert_tokens.decode")

    def ctx(before, after):
        return {"cfg": CELL, "counters_before": before,
                "counters_after": after}
    c = {"serving.moe.held_pairs": {"value": 0},
         "serving.decode_ticks": {"value": 10}}
    # 100 ticks of 64 lanes at 6 of 64 experts: 6 pairs an expert a layer
    d = {"serving.moe.held_pairs": {"value": 100 * 64 * 6 * 8 // 64 * 26},
         "serving.decode_ticks": {"value": 110}}
    assert read(ctx(c, d)) == pytest.approx(6.0)
    # a program without the counter reads nothing
    ticks = {"serving.decode_ticks": {"value": 110}}
    assert read(ctx({}, ticks)) is None
