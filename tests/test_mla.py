"""Multi-head latent attention and YaRN (DeepSeek-V2) at small sizes on
the CPU, in float32: the absorbed decode and chunk paths against the
decompressed forward, the lane mask on the latent cache, YaRN and the
rotary's pairing against the published formulas, and the cache leaves
without a head axis through api and the engine."""
import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced_config
from repro.models import api, common, mla

KEY = jax.random.PRNGKey(0)


def _cfg():
    """The reduced DeepSeek-V2-Lite in float32, its MoE layers holding 4 of
    8 experts: the held layer is dropless, so a token's output does not
    depend on the others' routing and a forward over a sequence equals
    the same tokens fed a chunk or a step at a time."""
    cfg = reduced_config("deepseek-v2-lite")
    return cfg.replace(dtype="float32",
                       moe=dataclasses.replace(cfg.moe, held=(0, 4)))


@pytest.mark.parametrize("mode", ["chunk", "decode"])
def test_absorbed_attention_equals_decompressed(mode):
    """Through the model: the train forward attends decompressed, chunk
    and decode absorbed over the stacked latent cache.  Float32 on the
    CPU: the two forms differ by the order of float32 sums only, so 1e-5
    of the logits' scale holds them together."""
    cfg = _cfg()
    params = api.build_params(KEY, cfg)
    B, T = 2, 24
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, T), 0,
                              cfg.vocab_size)
    want, _, _ = api.forward(params, {"tokens": toks}, cfg, mode="train",
                             remat="none")
    caches = api.init_caches(cfg, B, 32)
    caches["len"] = jnp.zeros(B, jnp.int32)
    step = 8 if mode == "chunk" else 1
    fwd = jax.jit(functools.partial(api.forward, cfg=cfg, mode=mode,
                                    remat="none"))
    outs = []
    for t in range(0, T, step):
        y, _, caches = fwd(params, {"tokens": toks[:, t:t + step]},
                           caches=caches)
        outs.append(y)
    got = jnp.concatenate(outs, axis=1)
    scale = float(jnp.abs(want).max())
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5 * scale, rtol=0)
    n = cfg.num_layers - 1
    assert caches["stage1"]["ckv"].shape == (n, B, 32, cfg.mla.kv_lora_rank)
    assert caches["stage1"]["kpe"].shape == (n, B, 32,
                                             cfg.mla.qk_rope_head_dim)


def test_masked_lane_keeps_its_latent_cache():
    """Decode through the model with a selected lane at the cache's last
    row: it writes that row of both leaves in every layer, and the lane
    that sits out keeps all of its rows."""
    cfg = _cfg()
    params = api.build_params(KEY, cfg)
    caches = api.init_caches(cfg, 2, 16)
    caches = jax.tree.map(lambda a: a + 1.0 if a.ndim else a, caches)
    caches["len"] = jnp.array([15, 5], jnp.int32)
    caches["lanes"] = jnp.array([True, False])
    toks = jnp.full((2, 1), 7, jnp.int32)
    _, _, new = api.forward(params, {"tokens": toks}, cfg, mode="decode",
                            caches=caches, remat="none")
    for stage in ("stage0", "stage1"):
        for k in ("ckv", "kpe"):
            a, b = np.asarray(new[stage][k]), np.asarray(caches[stage][k])
            np.testing.assert_array_equal(a[:, 1], b[:, 1])
            np.testing.assert_array_equal(a[:, 0, :15], b[:, 0, :15])
            assert not np.array_equal(a[:, 0, 15], b[:, 0, 15])


@pytest.mark.parametrize("mode", ["chunk", "decode"])
def test_masked_lane_keeps_its_stacked_latent_cache(mode):
    """Through the model, where the latent is written in place in the
    stacked cache: a lane that sits out keeps every layer's rows, also
    one whose chunk would run past the cache's end."""
    cfg = _cfg()
    params = api.build_params(KEY, cfg)
    caches = api.init_caches(cfg, 3, 32)
    caches = jax.tree.map(lambda a: a + 1.0 if a.ndim else a, caches)
    caches["len"] = jnp.array([3, 5, 30], jnp.int32)
    caches["lanes"] = jnp.array([True, False, False])
    S = 8 if mode == "chunk" else 1
    toks = jnp.full((3, S), 7, jnp.int32)
    _, _, new = api.forward(params, {"tokens": toks}, cfg, mode=mode,
                            caches=caches, remat="none")
    for stage in ("stage0", "stage1"):
        for k in ("ckv", "kpe"):
            a, b = np.asarray(new[stage][k]), np.asarray(caches[stage][k])
            np.testing.assert_array_equal(a[:, 1:], b[:, 1:])
            assert not np.array_equal(a[:, 0, 3:3 + S], b[:, 0, 3:3 + S])
            np.testing.assert_array_equal(a[:, 0, :3], b[:, 0, :3])


# ------------------------------------------------------------------ YaRN

def _published_yarn(dim, base, factor, orig, beta_fast, beta_slow):
    """DeepseekV2YarnRotaryEmbedding's inverse frequencies, written out."""
    def corr_dim(rot):
        return (dim * math.log(orig / (rot * 2 * math.pi))) / (
            2 * math.log(base))
    low = max(math.floor(corr_dim(beta_fast)), 0)
    high = min(math.ceil(corr_dim(beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    mask = 1.0 - ramp
    extra = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    inter = 1.0 / (factor * base ** (np.arange(0, dim, 2) / dim))
    return inter * (1 - mask) + extra * mask, (low, high)


def test_yarn_frequencies_and_scale_match_published_formulas():
    cfg = get_config("deepseek-v2-lite")
    s = cfg.rope_scaling
    want, (low, high) = _published_yarn(64, 10000.0, 40, 4096, 32, 1)
    assert (low, high) == (10, 23)
    got = np.asarray(common.yarn_freqs(64, cfg.rope_theta, s))
    np.testing.assert_allclose(got, want, rtol=1e-6)
    m = 0.1 * 0.707 * math.log(40) + 1
    assert common.yarn_mscale(40, 0.707) == pytest.approx(m)
    assert mla.softmax_scale(cfg) == pytest.approx(192 ** -0.5 * m * m)
    assert m * m == pytest.approx(1.590, abs=1e-3)


def test_rotary_acts_on_interleaved_pairs():
    """The checkpoint's convention: pair (x[2i], x[2i+1]) turns by
    position * freq[i]; the program keeps the result in halves order."""
    cfg = get_config("deepseek-v2-lite")
    x = jax.random.normal(KEY, (1, 5, 1, 64))
    pos = jnp.arange(5)[None]
    got = np.asarray(mla._rope(x, pos, cfg))[0, :, 0]
    freqs = np.asarray(common.yarn_freqs(64, 10000.0, cfg.rope_scaling))
    xn = np.asarray(x)[0, :, 0]
    ang = np.arange(5)[:, None] * freqs[None]
    even, odd = xn[:, 0::2], xn[:, 1::2]
    want = np.concatenate([even * np.cos(ang) - odd * np.sin(ang),
                           odd * np.cos(ang) + even * np.sin(ang)], -1)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


# ------------------------------------------------- latent leaves end to end

def test_latent_leaves_grow_and_declare_their_axes():
    cfg = reduced_config("deepseek-v2-lite").replace(num_layers=2)
    assert api.seq_axis("ckv", 4) == 2 and api.seq_axis("kpe", 3) == 1
    assert api.seq_axis("k", 5) == 2 and api.seq_axis("v_scale", 4) == 2
    assert api.seq_axis("xk", 5) is None and api.seq_axis("ssm", 5) is None
    params = api.build_params(KEY, cfg)
    toks = jnp.ones((2, 8), jnp.int32)
    _, _, caches = api.forward(params, {"tokens": toks}, cfg, mode="prefill",
                               remat="none")
    grown = api.grow_caches(cfg, caches, 32)
    assert grown["stage1"]["ckv"].shape == (1, 2, 32, 32)
    assert grown["stage0"]["kpe"].shape == (1, 2, 32, 8)
    from repro.configs.base import ShapeConfig
    axes = api.cache_logical_axes(cfg, ShapeConfig("d", 64, 2, "decode"))
    assert axes["stage1"]["ckv"] == (None, "batch", "kv_seq", None)


def test_engine_serves_latent_cache_and_counts_held_pairs():
    """The engine's decode program returns the per-layer held pairs in
    the tick's one readback; prefix snapshots crop the latent leaves to
    the prefix; a dense model's decode program returns no stats."""
    from repro.serving.engine import Engine
    cfg = _cfg().replace(dtype="bfloat16")
    params = api.build_params(KEY, cfg)
    # a zero router ties every expert; top-k then takes experts 0 and 1,
    # both held
    params["stage1"]["moe"]["router"] = jnp.zeros_like(
        params["stage1"]["moe"]["router"])
    eng = Engine(cfg, params, n_slots=2, max_len=64, prefill_chunk=8,
                 eos_id=-1)
    for n in (3, 11, 17):
        eng.submit(list(range(2, 2 + n)), max_new=4)
    eng.run()
    snap = eng.metrics_snapshot()
    ticks = snap["serving.decode_ticks"]["value"]
    pairs = snap["serving.moe.held_pairs"]["value"]
    # 3 MoE layers, top-2 of 8 experts, both held: 2 pairs a lane a layer
    assert pairs == 3 * 2 * snap["serving.decode_lanes_selected"]["value"]
    assert ticks > 0
    snap16 = eng._read_slot(0, 16)
    assert snap16["stage1"]["ckv"].shape == (3, 1, 16, 32)
    dense = Engine(reduced_config("phi3-mini-3.8b"), api.build_params(
        KEY, reduced_config("phi3-mini-3.8b")), n_slots=2, max_len=32,
        prefill_chunk=8, eos_id=-1)
    out = dense._decode_fn(dense.params, dense.caches,
                           jnp.zeros(2, jnp.int32), KEY,
                           jnp.ones(2, bool), jnp.int32(0))
    assert out[3] == {}
