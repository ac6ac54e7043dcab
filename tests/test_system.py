"""End-to-end system behaviour: per-arch smoke tests (reduced configs),
train/prefill/decode paths, loss descent, VLM/audio stubs."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import ARCH_IDS, reduced_config
from repro.configs.base import ShapeConfig, TrainConfig
from repro.models import api
from repro.training import loop as tl

KEY = jax.random.PRNGKey(0)
TRAIN_SHAPE = ShapeConfig("t", seq_len=32, global_batch=2, kind="train")
PREFILL_SHAPE = ShapeConfig("p", seq_len=16, global_batch=2, kind="prefill")


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_arch_smoke_forward(arch):
    """One forward train step on CPU: output shapes + finite values."""
    cfg = reduced_config(arch)
    params = api.build_params(KEY, cfg)
    batch = api.synthesize_batch(cfg, TRAIN_SHAPE)
    logits, aux, _ = api.forward(params, batch, cfg, mode="train",
                                 remat="none")
    B = TRAIN_SHAPE.global_batch
    assert logits.shape[0] == B
    assert logits.shape[-1] >= cfg.vocab_size
    assert bool(jnp.isfinite(logits.astype(jnp.float32)).all())
    assert bool(jnp.isfinite(aux))


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_arch_smoke_train_step(arch):
    cfg = reduced_config(arch)
    tc = TrainConfig(microbatch=None, remat="none", warmup_steps=1,
                     total_steps=4)
    state = tl.init_train_state(KEY, cfg, tc)
    step = jax.jit(tl.make_train_step(cfg, tc))
    batch = api.synthesize_batch(cfg, TRAIN_SHAPE)
    state, m = step(state, batch)
    assert bool(jnp.isfinite(m["loss"]))
    assert float(m["grad_norm"]) > 0


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_arch_prefill_then_decode(arch):
    cfg = reduced_config(arch)
    params = api.build_params(KEY, cfg)
    batch = api.synthesize_batch(cfg, PREFILL_SHAPE, include_labels=False)
    logits, _, caches = api.forward(params, batch, cfg, mode="prefill",
                                    remat="none")
    caches = api.grow_caches(cfg, caches, 32)
    tok = jnp.argmax(logits[:, -1, :cfg.vocab_size], -1)[:, None]
    tok = tok.astype(jnp.int32)
    logits2, _, caches2 = api.forward(params, {"tokens": tok}, cfg,
                                      mode="decode", caches=caches,
                                      remat="none")
    assert logits2.shape[1] == 1
    assert bool(jnp.isfinite(logits2.astype(jnp.float32)).all())


def test_loss_decreases_tiny_model():
    cfg = reduced_config("phi3-mini-3.8b").replace(num_layers=2)
    tc = TrainConfig(learning_rate=1e-3, warmup_steps=2, total_steps=30,
                     remat="none")
    state = tl.init_train_state(KEY, cfg, tc)
    step = jax.jit(tl.make_train_step(cfg, tc), donate_argnums=(0,))
    batch = api.synthesize_batch(cfg, TRAIN_SHAPE)   # fixed batch: memorize
    first = None
    for i in range(30):
        state, m = step(state, batch)
        if first is None:
            first = float(m["loss"])
    assert float(m["loss"]) < first * 0.7


def test_grad_accumulation_matches_full_batch():
    cfg = reduced_config("h2o-danube-1.8b").replace(num_layers=2)
    batch = api.synthesize_batch(cfg, ShapeConfig("t", 16, 4, "train"))
    tc_full = TrainConfig(remat="none")
    tc_acc = TrainConfig(microbatch=2, remat="none")
    s0 = tl.init_train_state(KEY, cfg, tc_full)
    s1 = tl.init_train_state(KEY, cfg, tc_acc)
    s0n, m0 = jax.jit(tl.make_train_step(cfg, tc_full))(s0, batch)
    s1n, m1 = jax.jit(tl.make_train_step(cfg, tc_acc))(s1, batch)
    assert abs(float(m0["loss"]) - float(m1["loss"])) < 1e-4
    import numpy as np
    for a, b in zip(jax.tree.leaves(s0n.params), jax.tree.leaves(s1n.params)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=5e-3)


def test_long_context_applicability_table():
    """DESIGN.md §Arch-applicability: exactly h2o/xlstm/zamba run
    long_500k."""
    from repro.configs import applicable_shapes, get_config
    runs_long = {a for a in ARCH_IDS
                 if any(s.name == "long_500k"
                        for s in applicable_shapes(get_config(a)))}
    assert runs_long == {"h2o-danube-1.8b", "xlstm-125m", "zamba2-7b"}


def test_param_counts_near_nameplate():
    """Sanity: analytic N lands near each arch's nameplate (loose bands)."""
    from repro.configs import get_config
    from repro.models.api import count_params_analytic
    expect = {"phi3-mini-3.8b": (3.0e9, 4.5e9),
              "qwen2.5-32b": (28e9, 36e9),
              "h2o-danube-1.8b": (1.4e9, 2.2e9),
              "olmoe-1b-7b": (5.5e9, 8.5e9),
              "deepseek-moe-16b": (13e9, 20e9),
              # 15.5 B analytic at 64 experts, embedding excluded
              "deepseek-v2-lite": (14e9, 17.5e9),
              "zamba2-7b": (5.5e9, 9e9)}
    for arch, (lo, hi) in expect.items():
        n = count_params_analytic(get_config(arch))
        assert lo < n < hi, (arch, n)
