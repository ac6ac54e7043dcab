"""Plain float32 reference of DeepSeek-V2 (arXiv:2405.04434) as its
published config and modelling code describe it: RMSNorm; multi-head
latent attention in its decompressed form (per head, key [c W_UK,
rope(k_pe)] and value c W_UV from the normed latent c, query [q_nope,
rope(q_pe)], no q compression); YaRN rotary on the checkpoint's
interleaved pairs, with its attention temperature in the softmax scale;
a dense SwiGLU in the first `first_k_dense_replace` layers; then
softmax routing over every routed expert, greedy top-k, gates left
unnormalised unless `norm_topk_prob`, and the fused shared experts.

This chip's share of the routed experts (`held_experts`, out of the
router's `published` count): each token adds the gated output of those
of its chosen experts that are held here; the rest would be computed on
other chips and are left out, as the program leaves them out.

Straightforward `jax.numpy` in float32, every matrix product at
`Precision.HIGHEST`, no cache, no batching: one sequence, all positions
at once, causal mask.  It imports nothing of the program under test.  It
runs layer by layer, upcasting one layer's bf16 weights at a time, so
that it fits on the chip beside them.

Weights come in a flat dict of the benchmark's own making:

    embed [V', d]  lm_head [d, V']  final_norm [d]
    dense, moe: per-layer stacks over their layers, each with
      attn_norm, mlp_norm [L, d]  wq [L, d, H*(nope+rope)]
      wkv_a [L, d, r+rope]  kv_norm [L, r]  wkv_b [L, r, H*(nope+v)]
      wo [L, H*v, d]
    dense: w_gate, w_up [L, d, F]  w_down [L, F, d]
    moe: router [L, d, E]  w_gate, w_up [L, E', d, f]  w_down [L, E', f, d]
         shared_gate, shared_up [L, d, S*f]  shared_down [L, S*f, d]

(V' >= V: rows or columns past the vocabulary are ignored.)

Controls, each put in the program's place (`served_gaps(control=...)`):
"fp8" (or True): both operands of every projection (attention, MLP,
experts, LM head) rounded to float8 e4m3, scaled per row of activations
and per output column of weights, the step below the configuration's
bfloat16; "plain_rope": the rotary with its plain frequencies and the
softmax scale without YaRN's temperature; "no_routed": the routed
experts left out.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
PAD = 512                   # served_gaps pads sequences to multiples of it
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0
ATTN_KEYS = ("attn_norm", "mlp_norm", "wq", "wkv_a", "kv_norm", "wkv_b",
             "wo")
DENSE_KEYS = ATTN_KEYS + ("w_gate", "w_up", "w_down")
MOE_KEYS = ATTN_KEYS + ("router", "w_gate", "w_up", "w_down",
                        "shared_gate", "shared_up", "shared_down")


def _fake_quant(x, axis):
    """Round x to float8 e4m3 with one scale per slice along `axis`."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / F8_MAX, 1.0)
    return (x / scale).astype(F8).astype(jnp.float32) * scale


def _mm(a, b, quant: Optional[str]):
    """a [T, k] @ b [k, n] in float32; with experts, a [T, k] or [E, T, k]
    and b [E, k, n] -> [E, T, n]."""
    if quant == "fp8":
        a = _fake_quant(a, -1)
        b = _fake_quant(b, -2)
    if b.ndim == 3:
        return jnp.einsum("tk,ekn->etn" if a.ndim == 2 else "etk,ekn->etn",
                          a, b, precision=HIGHEST)
    return jnp.matmul(a, b, precision=HIGHEST)


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _swiglu_mlp(h, wg, wu, wd, quant):
    return _mm(jax.nn.silu(_mm(h, wg, quant)) * _mm(h, wu, quant), wd, quant)


def _yarn_mscale(scale, mscale):
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def _inv_freq(m, plain: bool):
    """The rotary's inverse frequencies [rope/2]: YaRN's
    (DeepseekV2YarnRotaryEmbedding), or the plain ones."""
    dim, base = m["qk_rope_head_dim"], float(m["rope_theta"])
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    rs = m.get("rope_scaling")
    if plain or not rs:
        return extra
    factor = rs["factor"]
    orig = rs["original_max_position_embeddings"]

    def corr(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(base))
    low = max(math.floor(corr(rs["beta_fast"])), 0)
    high = min(math.ceil(corr(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    mask = 1.0 - ramp
    return extra / factor * (1 - mask) + extra * mask


def _rope(x, pos, m, plain: bool):
    """x [T, heads, rope] in the checkpoint's interleaved pairs: permuted
    to halves, then q * cos + rotate_half(q) * sin (apply_rotary_pos_emb
    of the published code), cos and sin carrying YaRN's
    mscale(mscale) / mscale(mscale_all_dim)."""
    T, nh, d = x.shape
    x = x.reshape(T, nh, d // 2, 2).transpose(0, 1, 3, 2).reshape(T, nh, d)
    inv = jnp.asarray(_inv_freq(m, plain), jnp.float32)
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]
    emb = jnp.concatenate([ang, ang], -1)[:, None, :]
    k = 1.0
    rs = m.get("rope_scaling")
    if rs and not plain:
        k = (_yarn_mscale(rs["factor"], rs["mscale"])
             / _yarn_mscale(rs["factor"], rs["mscale_all_dim"]))
    cos, sin = jnp.cos(emb) * k, jnp.sin(emb) * k
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    rot = jnp.concatenate([-x2, x1], -1)
    return x * cos + rot * sin


def _softmax_scale(m, plain: bool):
    scale = (m["qk_nope_head_dim"] + m["qk_rope_head_dim"]) ** -0.5
    rs = m.get("rope_scaling")
    if rs and rs.get("mscale_all_dim") and not plain:
        scale *= _yarn_mscale(rs["factor"], rs["mscale_all_dim"]) ** 2
    return scale


def _attention(x, lw, *, m, quant, plain):
    """x + MLA(x), decompressed, causal."""
    T = x.shape[0]
    H = m["num_attention_heads"]
    nope, rope = m["qk_nope_head_dim"], m["qk_rope_head_dim"]
    r, vd = m["kv_lora_rank"], m["v_head_dim"]
    eps = m["rms_norm_eps"]
    pos = jnp.arange(T)
    h = _rmsnorm(x, lw["attn_norm"], eps)
    q = _mm(h, lw["wq"], quant).reshape(T, H, nope + rope)
    kv = _mm(h, lw["wkv_a"], quant)
    c = _rmsnorm(kv[:, :r], lw["kv_norm"], eps)
    k_pe = _rope(kv[:, None, r:], pos, m, plain)             # [T, 1, rope]
    kvb = _mm(c, lw["wkv_b"], quant).reshape(T, H, nope + vd)
    k = jnp.concatenate(
        [kvb[..., :nope], jnp.broadcast_to(k_pe, (T, H, rope))], -1)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], pos, m, plain)],
                        -1)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) \
        * _softmax_scale(m, plain)
    s = jnp.where((pos[None, :] <= pos[:, None])[None], s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), kvb[..., nope:],
                   precision=HIGHEST)
    return x + _mm(o.reshape(T, H * vd), lw["wo"], quant)


def _layer_weights(w, i):
    return {k: jax.lax.dynamic_index_in_dim(v, i, 0, False)
            .astype(jnp.float32) for k, v in w.items()}


def _dense_layer(x, w, i, *, m, quant, plain):
    lw = _layer_weights(w, i)
    x = _attention(x, lw, m=m, quant=quant, plain=plain)
    h = _rmsnorm(x, lw["mlp_norm"], m["rms_norm_eps"])
    return x + _swiglu_mlp(h, lw["w_gate"], lw["w_up"], lw["w_down"], quant)


def _moe_layer(x, w, i, *, m, quant, plain, routed):
    lw = _layer_weights(w, i)
    x = _attention(x, lw, m=m, quant=quant, plain=plain)
    h = _rmsnorm(x, lw["mlp_norm"], m["rms_norm_eps"])
    probs = jax.nn.softmax(jnp.matmul(h, lw["router"], precision=HIGHEST),
                           -1)
    gates, idx = jax.lax.top_k(probs, m["num_experts_per_tok"])
    if m["norm_topk_prob"]:
        gates = gates / gates.sum(-1, keepdims=True)
    gates = gates * m["routed_scaling_factor"]
    lo, hi = m["held_experts"]
    # [T, E']: the gate of each held expert a token chose, else 0
    g = jnp.sum(jnp.where(idx[..., None] == jnp.arange(lo, hi),
                          gates[..., None], 0.0), axis=1)
    y = _swiglu_mlp(h, lw["shared_gate"], lw["shared_up"],
                    lw["shared_down"], quant)
    if routed:
        out = _swiglu_mlp(h, lw["w_gate"], lw["w_up"], lw["w_down"], quant)
        y = y + jnp.einsum("te,etd->td", g, out, precision=HIGHEST)
    return x + y


@functools.lru_cache(maxsize=None)
def _fns(model_items, quant, plain, routed):
    m = dict(model_items)
    m["rope_scaling"] = dict(m["rope_scaling"] or ())
    V = m["vocab_size"]

    @jax.jit
    def embed(table, tokens):
        return jnp.take(table, tokens, axis=0).astype(jnp.float32)

    dense = jax.jit(functools.partial(_dense_layer, m=m, quant=quant,
                                      plain=plain))
    moe = jax.jit(functools.partial(_moe_layer, m=m, quant=quant,
                                    plain=plain, routed=routed))

    @jax.jit
    def head(x, norm, lm_head):
        x = _rmsnorm(x, norm.astype(jnp.float32), m["rms_norm_eps"])
        return _mm(x, lm_head[:, :V].astype(jnp.float32), quant)

    return embed, dense, moe, head


def _model_items(model: Dict):
    """The configuration's scalar keys, rope_scaling and the held slice,
    hashable (the compiled functions are cached by them)."""
    out = []
    for k, v in sorted(model.items()):
        if isinstance(v, (int, float, str, bool, type(None))):
            out.append((k, v))
        elif k == "rope_scaling":
            out.append((k, tuple(sorted(v.items()))))
        elif k == "held_experts":
            out.append((k, tuple(v)))
    return tuple(out)


def logits(weights, model: Dict, tokens, quant: Optional[str] = None,
           plain_rope: bool = False, routed: bool = True) -> jax.Array:
    """Float32 logits [T, V] of the sequence `tokens` [T]: row t predicts
    the token after tokens[t]."""
    embed, dense, moe, head = _fns(_model_items(model), quant, plain_rope,
                                   routed)
    x = embed(weights["embed"], jnp.asarray(tokens, jnp.int32))
    dw = {k: weights["dense"][k] for k in DENSE_KEYS}
    mw = {k: weights["moe"][k] for k in MOE_KEYS}
    for i in range(model["first_k_dense_replace"]):
        x = dense(x, dw, jnp.int32(i))
    for i in range(model["num_hidden_layers"]
                   - model["first_k_dense_replace"]):
        x = moe(x, mw, jnp.int32(i))
    return head(x, weights["final_norm"], weights["lm_head"])


CONTROLS = {True: dict(quant="fp8"), "fp8": dict(quant="fp8"),
            "plain_rope": dict(plain_rope=True),
            "no_routed": dict(routed=False)}


@jax.jit
def _gaps(ref, rows, toks):
    r = ref[rows]
    return jnp.max(r, -1) - jnp.take_along_axis(r, toks[:, None], 1)[:, 0]


def served_gaps(weights, model, prompt, served, pad_to: int,
                control=False) -> np.ndarray:
    """For each served token, how far its reference logit lies below the
    reference's best at that position (0 where it is the reference's
    argmax).  With `control` (True or "fp8", "plain_rope", "no_routed"),
    the token is instead the one that control puts first at that
    position, fed the same prompt and served tokens.  The sequence is
    padded to a multiple of PAD positions, at most `pad_to`, so a few
    compiles serve every request (padding sits after every position
    read)."""
    seq = list(prompt) + list(served[:-1])
    T = len(seq)
    if T > pad_to:
        raise ValueError(f"sequence of {T} tokens exceeds {pad_to}")
    toks = np.zeros(min(pad_to, -(-T // PAD) * PAD), np.int32)
    toks[:T] = seq
    rows = np.arange(len(prompt) - 1, T, dtype=np.int32)
    ref = logits(weights, model, toks)
    if control:
        first = jnp.argmax(logits(weights, model, toks,
                                  **CONTROLS[control])[rows],
                           -1).astype(jnp.int32)
    else:
        first = jnp.asarray(np.asarray(served, np.int32))
    return np.asarray(_gaps(ref, jnp.asarray(rows), first))
