"""Decoder-only transformer assembly: dense, MoE and VLM-backbone families.

Layers are *stacked* along a leading axis and executed with ``jax.lax.scan``
so the lowered HLO is O(1) in depth — this is what keeps the 64/80-layer
dry-run compiles tractable and is also the standard production layout
(MaxText does the same).

Heterogeneous stacks (DeepSeek-MoE's first-k-dense) are two scans: a dense
prefix stage and the main stage.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import constrain
from repro.models import attention, mla, moe as moe_mod
from repro.models.common import (dense_init, embed_init, fold, ones_init,
                                 padded_vocab, rmsnorm)
from repro.models.mlp import init_mlp, mlp_forward, mlp_specs


# ---------------------------------------------------------------------------
# layer init / specs
# ---------------------------------------------------------------------------

def _init_layer(key, cfg: ModelConfig, tp: int, dtype, kind: str,
                dense_ff: Optional[int] = None) -> Dict[str, Any]:
    p = {
        "norm1": ones_init(None, (cfg.d_model,), dtype),
        "norm2": ones_init(None, (cfg.d_model,), dtype),
        "attn": (mla.init_mla(fold(key, "attn"), cfg, dtype) if cfg.is_mla
                 else attention.init_attention(fold(key, "attn"), cfg, tp,
                                               dtype)),
    }
    if kind == "moe":
        p["moe"] = moe_mod.init_moe(fold(key, "moe"), cfg, dtype)
    else:
        p["mlp"] = init_mlp(fold(key, "mlp"), cfg.d_model,
                            dense_ff or cfg.d_ff, dtype)
    return p


def _layer_specs(cfg: ModelConfig, kind: str) -> Dict[str, Any]:
    s = {"norm1": ("embed",), "norm2": ("embed",),
         "attn": (mla.mla_specs(cfg) if cfg.is_mla
                  else attention.attention_specs(cfg))}
    if kind == "moe":
        s["moe"] = moe_mod.moe_specs(cfg)
    else:
        s["mlp"] = mlp_specs()
    return s


def _stack_init(key, n: int, init_fn) -> Any:
    keys = jax.random.split(key, n)
    return jax.vmap(init_fn)(keys)


def _stage_plan(cfg: ModelConfig):
    """[(stage_name, num_layers, kind, dense_ff)]"""
    if cfg.is_moe and cfg.moe.first_k_dense:
        return [("stage0", cfg.moe.first_k_dense, "dense", cfg.moe.dense_d_ff),
                ("stage1", cfg.num_layers - cfg.moe.first_k_dense, "moe", None)]
    kind = "moe" if cfg.is_moe else "dense"
    return [("stage1", cfg.num_layers, kind, None)]


def init_lm(key, cfg: ModelConfig, tp: int, dtype) -> Dict[str, Any]:
    vp = padded_vocab(cfg.vocab_size)
    params: Dict[str, Any] = {
        "embed": embed_init(fold(key, "embed"), (vp, cfg.d_model), dtype),
        "final_norm": ones_init(None, (cfg.d_model,), dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(fold(key, "lm_head"),
                                       (cfg.d_model, vp), dtype,
                                       fan_in=cfg.d_model)
    for name, n, kind, dff in _stage_plan(cfg):
        params[name] = _stack_init(
            fold(key, name), n,
            lambda k: _init_layer(k, cfg, tp, dtype, kind, dff))
    return params


def lm_specs(cfg: ModelConfig) -> Dict[str, Any]:
    def stacked(tree):
        return jax.tree.map(lambda spec: (None,) + tuple(spec), tree,
                            is_leaf=lambda x: isinstance(x, tuple))
    s: Dict[str, Any] = {
        "embed": ("vocab", "embed"),
        "final_norm": ("embed",),
    }
    if not cfg.tie_embeddings:
        s["lm_head"] = ("embed", "vocab")
    for name, _n, kind, _dff in _stage_plan(cfg):
        s[name] = stacked(_layer_specs(cfg, kind))
    return s


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _block(x, lp, positions, *, cfg, tp, mode, kind, cache, remat: str):
    """-> (x, new_cache, aux, stats): stats is {} but for a held-experts
    layer, {"moe_held_pairs": int32} (moe.held_forward)."""
    def inner(x, lp, positions, cache):
        # named scopes label the operations in profiles; no effect on
        # the compiled program
        with jax.named_scope("attention"):
            h = rmsnorm(x, lp["norm1"], cfg.norm_eps)
            if cfg.is_mla:
                h, new_cache = mla.mla_forward(lp["attn"], h, positions,
                                               cfg=cfg, mode=mode,
                                               cache=cache)
            else:
                h, new_cache = attention.attn_forward(
                    lp["attn"], h, positions, cfg=cfg, tp=tp, mode=mode,
                    cache=cache)
        x = x + h
        stats = {}
        with jax.named_scope("mlp"):
            h2 = rmsnorm(x, lp["norm2"], cfg.norm_eps)
            if kind == "moe" and cfg.moe.held:
                lanes = None if cache is None else cache.get("lanes")
                y, aux, pairs = moe_mod.held_forward(lp["moe"], h2, cfg,
                                                     lanes)
                stats = {"moe_held_pairs": pairs}
            elif kind == "moe":
                y, aux = moe_mod.moe_forward(lp["moe"], h2, cfg)
            else:
                y, aux = mlp_forward(lp["mlp"], h2), jnp.float32(0.0)
        # residual-stream layout: "seq" -> sequence parallelism (Megatron-SP
        # style), "act_embed" -> hidden-dim sharding; both default to None
        x = constrain(x + y, ("batch", "seq", "act_embed"))
        return x, new_cache, aux, stats

    if remat == "full" and mode == "train":
        inner = jax.checkpoint(inner)
    elif remat == "dots" and mode == "train":
        inner = jax.checkpoint(
            inner, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return inner(x, lp, positions, cache)


def _scan_stage(x, stage_params, positions, *, cfg, tp, mode, kind,
                caches, remat):
    """Scan a homogeneous stage.  caches: stacked cache pytree or None.

    Decode keeps the stacked KV cache in the scan CARRY and updates layer
    slices with dynamic_update_index_in_dim — XLA aliases the carry in
    place.  (Passing caches as xs/ys allocates a second full cache in
    temps: +2x cache bytes per device, observed 16.6 GB on phi3
    decode_32k.)  Chunked prefill ('chunk') appends S positions into the
    same carried pool cache at the ragged per-slot offset.  A [B] bool
    `lanes` beside `len` masks each layer's write: lanes where False
    keep their cache (attention.cache_update).  Decode and chunk also
    return the layers' stats (_block) stacked over the stage."""
    if mode in ("decode", "chunk") and caches is not None:
        kv = {k: v for k, v in caches.items() if k not in ("len", "lanes")}
        lens = caches["len"]          # scalar or [B] (ragged serving)
        lanes = caches.get("lanes")   # [B] bool: lanes that may write

        # MLA's latent is written and read in place in the stacked carry
        # (mla.mla_forward, given the layer); other leaves go through the
        # layer's slice
        stacked = ("ckv",) if cfg.is_mla else ()

        def step(carry, inp):
            x, aux, kv = carry
            lp, i = inp
            cache = {k: v if k in stacked
                     else jax.lax.dynamic_index_in_dim(v, i, 0, False)
                     for k, v in kv.items()}
            if stacked:
                cache["layer"] = i
            cache["len"] = lens
            if lanes is not None:
                cache["lanes"] = lanes
            x, nc, aux_i, stats = _block(x, lp, positions, cfg=cfg, tp=tp,
                                         mode=mode, kind=kind, cache=cache,
                                         remat=remat)
            with jax.named_scope("kv_write"):
                kv = {k: nc[k] if k in stacked
                      else jax.lax.dynamic_update_index_in_dim(v, nc[k], i, 0)
                      for k, v in kv.items()}
            return (x, aux + aux_i, kv), stats

        n = jax.tree.leaves(stage_params)[0].shape[0]
        (x, aux, kv), stats = jax.lax.scan(
            step, (x, jnp.float32(0.0), kv),
            (stage_params, jnp.arange(n)))
        return x, aux, dict(kv, len=lens), stats

    def step(carry, inp):
        x, aux = carry
        lp, cache = inp
        x, new_cache, aux_i, _ = _block(x, lp, positions, cfg=cfg, tp=tp,
                                        mode=mode, kind=kind, cache=cache,
                                        remat=remat)
        return (x, aux + aux_i), new_cache

    (x, aux), new_caches = jax.lax.scan(
        step, (x, jnp.float32(0.0)), (stage_params, caches))
    return x, aux, new_caches, {}


def lm_forward(params: Dict[str, Any], batch: Dict[str, Any],
               cfg: ModelConfig, *, tp: int = 1, mode: str = "train",
               caches: Optional[Dict[str, Any]] = None,
               remat: str = "full", with_stats: bool = False):
    """Returns (logits [B,S,Vp], aux_loss, new_caches), and with
    `with_stats` a 4th output: the layers' stats, {} but for decode and
    chunk through held-experts layers, {"moe_held_pairs": [MoE layers]
    int32}, the (token, held expert) pairs of the lanes in `lanes`.

    batch: {"tokens": [B,St]} (+ "patch_embeds": [B,P,d] for VLM prefill/train)
    mode 'decode': tokens is [B,1]; caches required; positions from cache len.
    mode 'chunk':  tokens is [B,C]; caches required; chunk-append prefill —
    position of token i is caches["len"] + i (ragged per-slot lens).
    Decode and chunk take an optional caches["lanes"] ([B] bool): lanes
    where False keep their KV cache bit-for-bit (their logits are still
    computed); the returned `len` is advanced for every lane.
    """
    tokens = batch["tokens"]
    B = tokens.shape[0]
    x = jnp.take(params["embed"], tokens, axis=0)
    if cfg.family == "vlm" and mode not in ("decode", "chunk"):
        patches = batch["patch_embeds"].astype(x.dtype)
        x = jnp.concatenate([patches, x], axis=1)
    S = x.shape[1]

    if mode == "decode":
        positions = jnp.broadcast_to(caches["len"], (B,)).reshape(B, 1)
    elif mode == "chunk":
        lens = jnp.broadcast_to(caches["len"], (B,))
        positions = lens[:, None] + jnp.arange(S)[None, :]
    else:
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    x = constrain(x, ("batch", None, "act_embed"))

    aux_total = jnp.float32(0.0)
    new_caches: Dict[str, Any] = {}
    stats: Dict[str, Any] = {}
    for name, _n, kind, _dff in _stage_plan(cfg):
        stage_caches = None if caches is None else caches[name]
        if caches is not None:
            # per-layer KV caches share one len (scalar or per-slot [B])
            # and one lane mask
            stage_caches = dict(caches[name])
            stage_caches["len"] = caches["len"]
            if "lanes" in caches:
                stage_caches["lanes"] = caches["lanes"]
        x, aux, nc, st = _scan_stage(x, params[name], positions, cfg=cfg,
                                     tp=tp, mode=mode, kind=kind,
                                     caches=stage_caches, remat=remat)
        for k, v in st.items():      # stacked over the stages' layers
            stats[k] = jnp.concatenate([stats[k], v]) if k in stats else v
        aux_total = aux_total + aux
        if nc is not None and mode in ("prefill", "decode", "chunk"):
            new_caches[name] = {k: v for k, v in nc.items() if k != "len"}
    if mode in ("prefill", "decode", "chunk"):
        prev_len = jnp.int32(0) if caches is None else caches["len"]
        new_caches["len"] = prev_len + (jnp.int32(1) if mode == "decode"
                                        else jnp.int32(S))

    with jax.named_scope("lm_head"):
        x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
        head = (params["embed"].T if cfg.tie_embeddings
                else params["lm_head"])
        logits = x @ head
        logits = constrain(logits, ("batch", None, "vocab"))
    if with_stats:
        return logits, aux_total, (new_caches or None), stats
    return logits, aux_total, (new_caches or None)


def init_lm_caches(cfg: ModelConfig, batch: int, max_len: int, tp: int,
                   dtype, window: Optional[int] = None,
                   quantized: bool = False) -> Dict[str, Any]:
    caches: Dict[str, Any] = {"len": jnp.int32(0)}
    for name, n, _kind, _dff in _stage_plan(cfg):
        if cfg.is_mla:
            assert window is None and not quantized, \
                "the latent cache has no window and no int8 form"
            one = mla.init_cache(cfg, batch, max_len, dtype)
        else:
            one = attention.init_kv_cache(cfg, batch, max_len, tp, dtype,
                                          window=window, quantized=quantized)
        caches[name] = {
            k: jnp.broadcast_to(v[None], (n,) + v.shape)
            for k, v in one.items() if k != "len"}
    return caches
