"""Plain float32 reference of a dense decoder-only transformer (RMSNorm,
rotary positions, grouped-query attention with an optional sliding
window, SwiGLU MLP, untied LM head), as the Llama / Mistral / Phi-3 /
H2O-Danube papers and their published configs describe it.

Straightforward `jax.numpy` in float32, every matrix product at
`Precision.HIGHEST`, no cache, no batching: one sequence, all positions
at once, causal mask.  It imports nothing of the program under test.
It runs layer by layer, upcasting one layer's bf16 weights at a time,
so that it fits on the chip beside them.

Weights come in a flat dict of the benchmark's own making:

    embed [V', d]  lm_head [d, V']  final_norm [d]
    attn_norm, mlp_norm [L, d]  wq [L, d, H*hd]  wk, wv [L, d, KV*hd]
    wo [L, H*hd, d]  w_gate, w_up [L, d, F]  w_down [L, F, d]

(V' >= V: rows or columns past the vocabulary are ignored.)

`quant="fp8"` is the control: the same forward with both operands of
every projection (q, k, v, o, MLP, LM head) rounded to float8 e4m3,
scaled per row of activations and per output column of weights, the
step below the configuration's bfloat16.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def _fake_quant(x, axis):
    """Round x to float8 e4m3 with one scale per slice along `axis`."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / F8_MAX, 1.0)
    return (x / scale).astype(F8).astype(jnp.float32) * scale


def _mm(a, b, quant: Optional[str]):
    """a [..., k] @ b [k, n] in float32."""
    if quant == "fp8":
        a = _fake_quant(a, -1)
        b = _fake_quant(b, 0)
    return jnp.matmul(a, b, precision=HIGHEST)


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    """x [T, heads, hd]; rotates the two halves of each head (the
    `rotate_half` convention of the published checkpoints)."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _layer(x, w, i, *, m, quant):
    """One decoder layer on x [T, d] (float32), weights of layer i."""
    lw = {k: jax.lax.dynamic_index_in_dim(w[k], i, 0, False)
          .astype(jnp.float32) for k in w}
    T = x.shape[0]
    H, KV, hd = m["num_attention_heads"], m["num_key_value_heads"], \
        m["head_dim"]
    pos = jnp.arange(T)
    h = _rmsnorm(x, lw["attn_norm"], m["rms_norm_eps"])
    q = _rope(_mm(h, lw["wq"], quant).reshape(T, H, hd), pos,
              m["rope_theta"])
    k = _rope(_mm(h, lw["wk"], quant).reshape(T, KV, hd), pos,
              m["rope_theta"])
    v = _mm(h, lw["wv"], quant).reshape(T, KV, hd)
    G = H // KV
    qg = q.reshape(T, KV, G, hd)
    s = jnp.einsum("qkgd,skd->kgqs", qg, k, precision=HIGHEST) / hd ** 0.5
    allowed = pos[None, :] <= pos[:, None]
    if m.get("sliding_window"):
        allowed &= pos[None, :] > pos[:, None] - m["sliding_window"]
    s = jnp.where(allowed[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("kgqs,skd->qkgd", p, v, precision=HIGHEST)
    x = x + _mm(o.reshape(T, H * hd), lw["wo"], quant)
    h = _rmsnorm(x, lw["mlp_norm"], m["rms_norm_eps"])
    ff = jax.nn.silu(_mm(h, lw["w_gate"], quant)) * _mm(h, lw["w_up"], quant)
    return x + _mm(ff, lw["w_down"], quant)


LAYER_KEYS = ("attn_norm", "mlp_norm", "wq", "wk", "wv", "wo", "w_gate",
              "w_up", "w_down")


@functools.lru_cache(maxsize=None)
def _fns(model_items, quant):
    m = dict(model_items)
    V = m["vocab_size"]

    @jax.jit
    def embed(table, tokens):
        return jnp.take(table, tokens, axis=0).astype(jnp.float32)

    layer = jax.jit(functools.partial(_layer, m=m, quant=quant))

    @jax.jit
    def head(x, norm, lm_head):
        x = _rmsnorm(x, norm.astype(jnp.float32), m["rms_norm_eps"])
        return _mm(x, lm_head[:, :V].astype(jnp.float32), quant)

    return embed, layer, head


def logits(weights: Dict[str, jax.Array], model: Dict, tokens,
           quant: Optional[str] = None) -> jax.Array:
    """Float32 logits [T, V] of the sequence `tokens` [T]: row t predicts
    the token after tokens[t]."""
    embed, layer, head = _fns(tuple(sorted(
        (k, v) for k, v in model.items()
        if isinstance(v, (int, float, str, type(None))))), quant)
    x = embed(weights["embed"], jnp.asarray(tokens, jnp.int32))
    lw = {k: weights[k] for k in LAYER_KEYS}
    for i in range(model["num_hidden_layers"]):
        x = layer(x, lw, jnp.int32(i))
    return head(x, weights["final_norm"], weights["lm_head"])


@jax.jit
def _gaps(ref, rows, toks):
    r = ref[rows]
    return jnp.max(r, -1) - jnp.take_along_axis(r, toks[:, None], 1)[:, 0]


def served_gaps(weights, model, prompt, served, pad_to: int,
                control: bool = False) -> np.ndarray:
    """For each served token, how far its reference logit lies below the
    reference's best at that position (0 where it is the reference's
    argmax).  With `control`, the token is instead the one the fp8
    forward puts first at that position, fed the same prompt and served
    tokens.  The sequence is padded to `pad_to` positions so one compile
    serves every request (padding sits after every position read)."""
    seq = list(prompt) + list(served[:-1])
    T = len(seq)
    if T > pad_to:
        raise ValueError(f"sequence of {T} tokens exceeds {pad_to}")
    toks = np.zeros(pad_to, np.int32)
    toks[:T] = seq
    rows = np.arange(len(prompt) - 1, T, dtype=np.int32)
    ref = logits(weights, model, toks)
    if control:
        first = jnp.argmax(logits(weights, model, toks, quant="fp8")[rows],
                           -1).astype(jnp.int32)
    else:
        first = jnp.asarray(np.asarray(served, np.int32))
    return np.asarray(_gaps(ref, jnp.asarray(rows), first))
