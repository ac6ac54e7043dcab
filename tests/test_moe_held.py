"""One chip's share of an expert layer (DeepSeek-V2's: 64 routed experts,
softmax top-6 gates left as they are, 2 shared) at a small width on the
CPU, in float32: the shares of 8 chips against the uncut layer, gates
renormalised only where the config asks, no token dropped under a router
that sends every token to one expert, and the held pairs counted over
the lanes that write."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced_config
from repro.models import common, moe

KEY = jax.random.PRNGKey(0)


def _layer_cfg(**over):
    """One expert layer of 64 experts, top-6 softmax gates left as they
    are, 2 shared experts, at a small width."""
    base = get_config("deepseek-v2-lite").moe
    return reduced_config("deepseek-v2-lite").replace(
        d_model=32, dtype="float32",
        moe=dataclasses.replace(base, d_ff=16, **over))


def test_eight_shares_add_up_to_the_uncut_layer():
    """Each of 8 chips holds 8 of the 64 experts and computes the shared
    experts alike; the shares' routed parts, with the shared experts
    counted once, add up to the whole layer."""
    full_cfg = _layer_cfg()
    p = moe.init_moe(KEY, full_cfg, jnp.float32)
    # logits that spread by about 3, so the top-6 gates carry weight
    p["router"] = p["router"] * 3.0
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 5, 32))
    want = _manual(p, x, full_cfg, False, held=(0, 64))
    shared = common.swiglu(x @ p["shared"]["w_gate"],
                           x @ p["shared"]["w_up"]) @ p["shared"]["w_down"]
    total, pairs = shared, 0
    for i in range(8):
        cfg = _layer_cfg(held=(8 * i, 8 * i + 8))
        share = {k: (v[8 * i:8 * i + 8] if k.startswith("w_") else v)
                 for k, v in p.items()}
        y, _, n = moe.held_forward(share, x, cfg)
        total = total + (y - shared)
        pairs += int(n)
    np.testing.assert_allclose(np.asarray(total).reshape(10, 32), want,
                               atol=1e-4, rtol=0)
    assert pairs == 2 * 5 * 6


def _top(p, x, k):
    """The experts each token's router puts first, [T, k]."""
    probs = jax.nn.softmax(x.reshape(-1, x.shape[-1]) @ p["router"], -1)
    return np.argsort(-np.asarray(probs), -1)[:, :k]


def _manual(p, x, cfg, norm, held=None):
    """Per token, per chosen expert among `held` (`p` holds their
    weights; the config's slice by default), gate times the expert's
    output, plus the shared experts: no capacity, no batching."""
    lo, hi = held or cfg.moe.held
    xf = np.asarray(x.reshape(-1, x.shape[-1]), np.float64)
    probs = np.asarray(jax.nn.softmax(jnp.asarray(xf) @ p["router"], -1))
    out = np.zeros_like(xf)
    for t in range(len(xf)):
        top = np.argsort(-probs[t])[:cfg.moe.top_k]
        g = probs[t, top] / (probs[t, top].sum() if norm else 1.0)
        for e, ge in zip(top, g):
            if lo <= e < hi:
                w = {k: np.asarray(p[k][e - lo], np.float64)
                     for k in ("w_gate", "w_up", "w_down")}
                a = xf[t] @ w["w_gate"]
                out[t] += ge * ((a / (1 + np.exp(-a))) * (xf[t] @ w["w_up"])
                                @ w["w_down"])
    sh = {k: np.asarray(v, np.float64) for k, v in p["shared"].items()}
    a = xf @ sh["w_gate"]
    return out + ((a / (1 + np.exp(-a))) * (xf @ sh["w_up"])) @ sh["w_down"]


@pytest.mark.parametrize("norm", [False, True])
def test_gates_renormalised_only_where_asked(norm):
    cfg = _layer_cfg(held=(0, 16), norm_topk_prob=norm)
    p = moe.init_moe(KEY, cfg, jnp.float32)
    p["router"] = p["router"] * 3.0
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 6, 32))
    y, _, _ = moe.held_forward(p, x, cfg)
    np.testing.assert_allclose(np.asarray(y).reshape(6, 32),
                               _manual(p, x, cfg, norm), atol=1e-4)


def test_no_token_dropped_when_one_expert_takes_every_token():
    """A router skewed so that expert 0 leads for all 64 tokens (top-2 of
    16, experts 0-7 held): the held layer gives every token its share,
    where the capacity dispatch over all 16 (room for 1.25 x the mean
    load, 10) drops most of them."""
    full = _layer_cfg(num_experts=16, top_k=2)
    p = moe.init_moe(KEY, full, jnp.float32)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(5), (8, 8, 32)))
    p["router"] = p["router"].at[:, 0].set(5.0)
    top = _top(p, x, 2)
    assert (top[:, 0] == 0).all()
    cfg = _layer_cfg(num_experts=16, top_k=2, held=(0, 8))
    share = {k: (v[:8] if k.startswith("w_") else v) for k, v in p.items()}
    y, _, pairs = moe.held_forward(share, x, cfg)
    np.testing.assert_allclose(np.asarray(y).reshape(64, 32),
                               _manual(share, x, cfg, False), atol=1e-4)
    assert int(pairs) == int((top < 8).sum())
    assert moe.moe_capacity(64, full) < 64
    dropped, _ = moe.moe_forward(p, x, full)
    assert not np.allclose(np.asarray(dropped).reshape(64, 32),
                           _manual(p, x, full, False, held=(0, 16)),
                           atol=1e-3)


def test_held_pairs_count_only_the_lanes_that_write():
    cfg = _layer_cfg(held=(0, 32))
    p = moe.init_moe(KEY, cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(6), (4, 1, 32))
    _, _, n = moe.held_forward(p, x, cfg, lanes=jnp.array([1, 0, 1, 0],
                                                          bool))
    top = _top(p, x, 6)
    assert int(n) == int((top[[0, 2]] < 32).sum()) > 0


def test_held_is_a_strict_slice():
    """`held` names a real share; every expert here is held=(), the
    capacity dispatch."""
    for held in ((0, 64), (8, 8), (60, 68)):
        with pytest.raises(AssertionError):
            _layer_cfg(held=held)
