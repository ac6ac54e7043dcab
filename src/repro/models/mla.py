"""Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434 §2.1).

Each position is projected to one latent c of `kv_lora_rank` (RMS-normed)
and one rotary key k_pe of `qk_rope_head_dim` that every head shares.
Head h's key is [c @ W_UK_h, k_pe] and its value c @ W_UV_h; its query is
[q_nope_h, q_pe_h].  The cache holds only c and k_pe, without a head
axis: `ckv` [B, S, kv_lora_rank] and `kpe` [B, S, qk_rope_head_dim].

Two forms of the same mathematics:

- decompressed (train, prefill): K and V per head from the latent, then
  the chunked flash attention, with V padded to the query/key width
  (192 against 128 at DeepSeek-V2's sizes) and cut back after;
- absorbed (decode, chunk): W_UK folded into the query, q_lat_h =
  q_nope_h @ W_UK_h^T, so scores are q_lat . c + q_pe . k_pe, and W_UV
  applied after the weighted sum of latents, so attention runs over the
  latent cache and K and V are never formed per position.

In the model's layer scan the latent's new rows are scattered straight
into the stage's stacked `ckv` (`_stack_write`), which the attention
then reads in place, so no layer's 512-wide slice is copied out and
written back.  The 64-wide `kpe` goes through the layer's slice
(`_slice_write`): held stacked, XLA gives it the transposed layout of
the score einsum and copies the whole stack into it and back around the
layer loop, which the compiler's cost estimates for a v5e put above the
slice's turnaround (PERF.md section 5).

The rotary acts on the checkpoint's interleaved pairs (x0, x1), (x2, x3),
...: the published code permutes them to halves before `rotate_half`,
as `_rope` does, and the same permutation on queries and keys leaves
q . k unchanged.  Frequencies and the softmax temperature follow YaRN
(common.yarn_freqs, common.yarn_mscale) where the config sets
`rope_scaling`.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention, common
from repro.models.common import dense_init, fold, ones_init, rmsnorm


def init_mla(key, cfg: ModelConfig, dtype) -> Dict[str, Any]:
    d, m, H = cfg.d_model, cfg.mla, cfg.num_heads
    qk = m.qk_nope_head_dim + m.qk_rope_head_dim
    r = m.kv_lora_rank
    return {
        "wq": dense_init(fold(key, "wq"), (d, H * qk), dtype, fan_in=d),
        "wkv_a": dense_init(fold(key, "wkv_a"),
                            (d, r + m.qk_rope_head_dim), dtype, fan_in=d),
        "kv_norm": ones_init(None, (r,), dtype),
        # per head: the key's no-rope part, then the value
        "wkv_b": dense_init(fold(key, "wkv_b"),
                            (r, H * (m.qk_nope_head_dim + m.v_head_dim)),
                            dtype, fan_in=r),
        "wo": dense_init(fold(key, "wo"), (H * m.v_head_dim, d), dtype,
                         fan_in=H * m.v_head_dim),
    }


def mla_specs(cfg: ModelConfig) -> Dict[str, Any]:
    del cfg
    return {"wq": ("embed", "qkv"), "wkv_a": ("embed", None),
            "kv_norm": (None,), "wkv_b": (None, "qkv"),
            "wo": ("qkv", "embed")}


def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype
               ) -> Dict[str, Any]:
    m = cfg.mla
    return {"ckv": jnp.zeros((batch, max_len, m.kv_lora_rank), dtype),
            "kpe": jnp.zeros((batch, max_len, m.qk_rope_head_dim), dtype),
            "len": jnp.int32(0)}


def softmax_scale(cfg: ModelConfig) -> float:
    """1/sqrt(qk head dim), times YaRN's mscale(factor, mscale_all_dim)
    squared where the config scales its rotary."""
    m, s = cfg.mla, cfg.rope_scaling
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    if s is not None and s.mscale_all_dim:
        scale *= common.yarn_mscale(s.factor, s.mscale_all_dim) ** 2
    return scale


def _rope(x, positions, cfg: ModelConfig):
    """x [B, S, heads, D] with the checkpoint's interleaved pairs ->
    rotated, in halves order."""
    x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    s = cfg.rope_scaling
    d = x.shape[-1]
    if s is None:
        return common.apply_rope(x, positions, cfg.rope_theta)
    out = common.apply_rope(x, positions, cfg.rope_theta,
                            common.yarn_freqs(d, cfg.rope_theta, s))
    # the published cos and sin carry mscale(mscale) / mscale(all_dim)
    k = (common.yarn_mscale(s.factor, s.mscale)
         / common.yarn_mscale(s.factor, s.mscale_all_dim))
    return out if k == 1.0 else (out.astype(jnp.float32) * k).astype(x.dtype)


def _project(p, x, positions, cfg: ModelConfig):
    """-> q_nope [B,S,H,nope], q_pe [B,S,H,rope] (rotated), the normed
    latent ckv [B,S,r] and the rotated shared key kpe [B,S,rope]."""
    B, S, _ = x.shape
    m, H = cfg.mla, cfg.num_heads
    q = (x @ p["wq"]).reshape(B, S, H, -1)
    q_nope, q_pe = q[..., :m.qk_nope_head_dim], q[..., m.qk_nope_head_dim:]
    kv = x @ p["wkv_a"]
    ckv = rmsnorm(kv[..., :m.kv_lora_rank], p["kv_norm"], cfg.norm_eps)
    kpe = _rope(kv[..., None, m.kv_lora_rank:], positions, cfg)[:, :, 0]
    return q_nope, _rope(q_pe, positions, cfg), ckv, kpe


def _decompressed(p, q_nope, q_pe, ckv, kpe, cfg: ModelConfig):
    """Causal attention with K and V formed per head: [B,S,H,v]."""
    B, S, H, _ = q_nope.shape
    m = cfg.mla
    kvb = (ckv @ p["wkv_b"]).reshape(B, S, H, -1)
    k_nope, v = kvb[..., :m.qk_nope_head_dim], kvb[..., m.qk_nope_head_dim:]
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(kpe[:, :, None], (B, S, H, kpe.shape[-1]))],
        axis=-1)
    qk = k.shape[-1]
    # the flash path scales by 1/sqrt(qk); the rest of the scale goes on q
    extra = softmax_scale(cfg) * qk ** 0.5
    q = jnp.concatenate([q_nope, q_pe], axis=-1)
    if extra != 1.0:
        q = (q.astype(jnp.float32) * extra).astype(q_nope.dtype)
    v = jnp.pad(v, [(0, 0)] * 3 + [(0, qk - v.shape[-1])])
    out = attention.flash_attention_jnp(q, k, v, causal=True)
    return out[..., :m.v_head_dim]


def _absorbed(p, q_nope, q_pe, ckv, kpe, lens, cfg: ModelConfig):
    """Attention of S new queries at positions lens + i over the latent
    cache (ckv [B,Smax,r], kpe [B,Smax,rope], the new rows written):
    query i sees positions < lens + i + 1.  -> [B,S,H,v]."""
    B, S, H, _ = q_nope.shape
    m = cfg.mla
    dt = q_nope.dtype
    f32 = jnp.float32
    wkv_b = p["wkv_b"].reshape(m.kv_lora_rank, H, -1)
    w_uk = wkv_b[..., :m.qk_nope_head_dim]                 # [r, H, nope]
    w_uv = wkv_b[..., m.qk_nope_head_dim:]                 # [r, H, v]
    q_lat = jnp.einsum("bshn,rhn->bshr", q_nope, w_uk,
                       preferred_element_type=f32).astype(dt)
    s = (jnp.einsum("bshr,btr->bsht", q_lat, ckv, preferred_element_type=f32)
         + jnp.einsum("bshe,bte->bsht", q_pe, kpe,
                      preferred_element_type=f32)) * softmax_scale(cfg)
    end = jnp.broadcast_to(lens, (B,))[:, None] + jnp.arange(S)[None] + 1
    valid = jnp.arange(ckv.shape[1])[None, None] < end[..., None]  # [B,S,T]
    s = jnp.where(valid[:, :, None, :], s, attention.NEG_INF)
    pr = jax.nn.softmax(s, axis=-1)
    o_lat = jnp.einsum("bsht,btr->bshr", pr.astype(dt), ckv,
                       preferred_element_type=f32).astype(dt)
    return jnp.einsum("bshr,rhv->bshv", o_lat, w_uv,
                      preferred_element_type=f32).astype(dt)


def _slice_write(cache, new, lens, lanes):
    """Write new rows [B, C, D] into one layer's leaf [B, S, D] at each
    lane's `lens`, vmapped over the lanes; a lane where `lanes` is False
    writes back the rows it would overwrite."""
    C = new.shape[1]
    lens = jnp.broadcast_to(lens, (new.shape[0],))
    new = new.astype(cache.dtype)
    if lanes is not None:
        old = jax.vmap(lambda c, i: jax.lax.dynamic_slice_in_dim(
            c, i, C))(cache, lens)
        new = jnp.where(lanes[:, None, None], new, old)
    return jax.vmap(lambda c, n, i: jax.lax.dynamic_update_slice_in_dim(
        c, n, i, axis=0))(cache, new, lens)


def _stack_write(stack, new, layer, lens, lanes):
    """Write new rows [B, C, D] into layer `layer` of a stacked cache
    [L, B, S, D] at each lane's `lens`: one scatter, in place on the
    carry, so neither the layer's slice nor the stack is copied (a
    layer's slice read and written back costs 2 x 134 MB a layer at 64 x
    2048).  Lanes where `lanes` is False write back their old rows; rows
    past the cache (a lane that sits out a chunk near its end) are
    dropped."""
    B, C = new.shape[:2]
    pos = jnp.broadcast_to(lens, (B,))[:, None] + jnp.arange(C)[None]  # [B,C]
    b = jnp.broadcast_to(jnp.arange(B)[:, None], (B, C))
    new = new.astype(stack.dtype)
    if lanes is not None:
        old = stack.at[layer, b, pos].get(mode="clip")
        new = jnp.where(lanes[:, None, None], new, old)
    return stack.at[layer, b, pos].set(new, mode="drop")


def mla_forward(p: Dict[str, Any], x: jax.Array, positions: jax.Array, *,
                cfg: ModelConfig, mode: str,
                cache: Optional[Dict[str, Any]] = None):
    """Returns (out [B,S,d], new_cache); modes as attention.attn_forward.
    Decode and chunk take, in `cache`, the stage's stacked latent `ckv`
    [L,B,S,r] with this layer's index `layer`, and the layer's own
    rotary keys `kpe` [B,S,rope]; they write the new rows at each lane's
    `len` (masked by `lanes` where the cache has it) and attend absorbed
    over the layer's rows."""
    B, S, _ = x.shape
    with jax.named_scope("mla"):
        q_nope, q_pe, ckv, kpe = _project(p, x, positions, cfg)
        new_cache = cache
        if mode in ("decode", "chunk"):
            i, lens, lanes = cache["layer"], cache["len"], cache.get("lanes")
            with jax.named_scope("kv_write"):
                cs = _stack_write(cache["ckv"], ckv, i, lens, lanes)
                pc = _slice_write(cache["kpe"], kpe, lens, lanes)
            out = _absorbed(p, q_nope, q_pe,
                            jax.lax.dynamic_index_in_dim(cs, i, 0, False),
                            pc, lens, cfg)
            new_cache = {"ckv": cs, "kpe": pc, "len": lens + S}
        else:
            out = _decompressed(p, q_nope, q_pe, ckv, kpe, cfg)
            if mode == "prefill":
                new_cache = {"ckv": ckv, "kpe": kpe, "len": jnp.int32(S)}
        y = out.reshape(B, S, -1) @ p["wo"]
    return y, new_cache
