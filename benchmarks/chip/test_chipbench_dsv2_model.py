"""The DeepSeek-V2 family's adapter and plain reference at a small width
on the CPU: the adapter's ModelConfig at the published widths and the
reference's names for the program's arrays; the reference against the
program's forward, and against the engine's chunked prefill and decode
through the latent cache; YaRN's scale and frequencies in the
reference."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from benchmarks.chip import bench, serving  # noqa: E402
from benchmarks.chip.small_deepseek import CELL, config  # noqa: E402

ref = bench.load_module("reference/deepseek_v2.py")
adapter = bench.load_module("program/deepseek_v2.py")


def test_model_config_is_pinned():
    from repro.configs.base import MLAConfig, ModelConfig, MoEConfig, YaRN
    got = adapter.model_config(CELL)
    assert got == ModelConfig(
        name="deepseek-v2-lite-ep8", family="moe", num_layers=27,
        d_model=2048, num_heads=16, num_kv_heads=16, d_ff=1408,
        vocab_size=102400, rope_theta=10000.0, norm_eps=1e-06,
        tie_embeddings=False, dtype="bfloat16",
        moe=MoEConfig(num_experts=64, top_k=6, num_shared=2, d_ff=1408,
                      first_k_dense=1, dense_d_ff=10944,
                      norm_topk_prob=False, held=(0, 8)),
        mla=MLAConfig(kv_lora_rank=512, qk_nope_head_dim=128,
                      qk_rope_head_dim=64, v_head_dim=128),
        rope_scaling=YaRN(factor=40.0, original_max_position_embeddings=4096,
                          beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                          mscale_all_dim=0.707))
    assert serving.program(CELL).model_config(CELL) == got


def test_reference_weights_are_the_same_arrays():
    import jax
    pcfg = adapter.model_config(config())
    params = jax.eval_shape(lambda: __import__(
        "repro.models.api", fromlist=["api"]).build_params(
            jax.random.PRNGKey(0), pcfg))
    w = adapter.reference_weights(params)
    s0, s1 = params["stage0"], params["stage1"]
    assert w["dense"]["wkv_b"] is s0["attn"]["wkv_b"]
    assert w["dense"]["w_down"] is s0["mlp"]["w_down"]
    assert w["moe"]["attn_norm"] is s1["norm1"]
    assert w["moe"]["router"] is s1["moe"]["router"]
    assert w["moe"]["shared_up"] is s1["moe"]["shared"]["w_up"]
    assert w["moe"]["w_gate"].shape == (2, 4, 64, 32)
    assert set(w["moe"]) == set(ref.MOE_KEYS)
    assert set(w["dense"]) == set(ref.DENSE_KEYS)


def _f32():
    import jax.numpy as jnp
    cfg = config(torch_dtype="float32")
    pcfg = serving.program(cfg).model_config(cfg)
    params = serving.make_weights(pcfg, 2**33 + 3)
    # weights from the draw are tiny at this width: scale the embedding
    # up so the logits spread over several units
    params["embed"] = params["embed"] * 50
    assert params["stage1"]["attn"]["wq"].dtype == jnp.float32
    return cfg, pcfg, params


def test_reference_matches_program_forward():
    """Float32 on the CPU, so the two differ only by the order of float32
    sums: 1e-4 of the logits' scale covers that, and a wrong rotary
    pairing, temperature, norm or routing moves logits by far more."""
    import jax.numpy as jnp
    from repro.models import api
    cfg, pcfg, params = _f32()
    toks = np.random.default_rng(0).integers(2, cfg["vocab_size"], 40)
    got, _, _ = api.forward(params, {"tokens": jnp.asarray(toks)[None]},
                            pcfg, mode="train", remat="none")
    got = np.asarray(got[0, :, :cfg["vocab_size"]])
    want = np.asarray(ref.logits(adapter.reference_weights(params), cfg,
                                 toks))
    scale = np.abs(want).max()
    assert scale > 1.0
    np.testing.assert_allclose(got, want, atol=1e-4 * scale, rtol=0)


def test_engine_prefill_then_decode_agrees_with_reference():
    """Chunks and decode steps through the latent cache (absorbed), with
    the lane mask, give the reference's full-forward logits (float32,
    same tolerance as above); the engine's greedy tokens are the
    reference's argmax at every position."""
    import jax.numpy as jnp
    from repro.models import api
    from repro.serving.engine import Engine
    from repro.serving.sampler import SamplerConfig
    cfg, pcfg, params = _f32()
    w = adapter.reference_weights(params)
    rng = np.random.default_rng(1)
    seqs = rng.integers(2, cfg["vocab_size"], (2, 40))
    caches = api.init_caches(pcfg, 2, cfg["max_len"])
    caches["len"] = jnp.zeros(2, jnp.int32)
    lanes = jnp.ones(2, bool)
    outs = []
    for t0, n, mode in ((0, 16, "chunk"), (16, 16, "chunk")) + tuple(
            (t, 1, "decode") for t in range(32, 40)):
        lg, _, caches, stats = api.forward(
            params, {"tokens": jnp.asarray(seqs[:, t0:t0 + n])}, pcfg,
            mode=mode, caches=dict(caches, lanes=lanes), remat="none",
            with_stats=True)
        assert stats["moe_held_pairs"].shape == (2,)
        outs.append(np.asarray(lg[..., :cfg["vocab_size"]]))
    got = np.concatenate(outs, 1)
    for b in range(2):
        want = np.asarray(ref.logits(w, cfg, seqs[b]))
        np.testing.assert_allclose(got[b], want,
                                   atol=1e-4 * np.abs(want).max(), rtol=0)
    eng = Engine(pcfg, params, n_slots=4, max_len=cfg["max_len"],
                 sampler=SamplerConfig(temperature=0.0, seed=0), eos_id=-1,
                 prefill_chunk=16, prefix_cache_entries=8)
    rids = [eng.submit(rng.integers(2, 256, n).tolist(), max_new=12)
            for n in (5, 17, 33, 40, 12)]
    eng.run()
    for rid in rids:
        r = eng.requests[rid]
        assert float(ref.served_gaps(w, cfg, r.prompt, r.out,
                                     cfg["max_len"]).max()) == 0.0


def test_yarn_differs_from_plain_rotary():
    m = config()
    assert ref._softmax_scale(m, False) == pytest.approx(
        (16 + 8) ** -0.5 * (0.1 * 0.707 * np.log(40) + 1) ** 2)
    assert ref._softmax_scale(m, True) == pytest.approx((16 + 8) ** -0.5)
    assert not np.allclose(ref._inv_freq(m, False), ref._inv_freq(m, True))
