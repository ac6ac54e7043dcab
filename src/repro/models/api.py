"""Public model API: one entry point per concern, dispatching on family.

  build_params(key, cfg, tp, dtype)   -> param pytree
  param_specs(cfg)                    -> logical-axis spec tree (same shape)
  forward(params, batch, cfg, ...)    -> (logits, aux_loss, new_caches)
  init_caches(cfg, batch, max_len, .) -> decode-state pytree
  input_specs(cfg, shape)             -> {name: ShapeDtypeStruct} dry-run stand-ins
  cache_specs(cfg, shape)             -> ShapeDtypeStruct tree for decode caches
  count_params_analytic(cfg)          -> N (and N_active) for MODEL_FLOPS

Families: dense / moe / vlm  -> transformer.lm_*
          hybrid (zamba2)    -> zamba.*
          xlstm              -> xlstm_lm.*
          audio (whisper)    -> whisper.*

Modality frontends are STUBS per the brief: `input_specs` provides
precomputed patch embeddings (vlm) / frame embeddings (audio) directly.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeConfig
from repro.models import transformer, whisper, xlstm_lm, zamba
from repro.models.common import dtype_of

# windowed shared-attention width used by zamba2's long_500k cell
ZAMBA_LONG_WINDOW = 4096


# ---------------------------------------------------------------------------
# dispatch tables
# ---------------------------------------------------------------------------

def build_params(key, cfg: ModelConfig, tp: int = 1, dtype=None):
    dtype = dtype or dtype_of(cfg.dtype)
    if cfg.family == "hybrid":
        return zamba.init_zamba(key, cfg, tp, dtype)
    if cfg.family == "xlstm":
        return xlstm_lm.init_xlstm_lm(key, cfg, tp, dtype)
    if cfg.family == "audio":
        return whisper.init_whisper(key, cfg, tp, dtype)
    return transformer.init_lm(key, cfg, tp, dtype)


def param_specs(cfg: ModelConfig):
    if cfg.family == "hybrid":
        return zamba.zamba_specs(cfg)
    if cfg.family == "xlstm":
        return xlstm_lm.xlstm_lm_specs(cfg)
    if cfg.family == "audio":
        return whisper.whisper_specs(cfg)
    return transformer.lm_specs(cfg)


def forward(params, batch: Dict[str, Any], cfg: ModelConfig, *,
            tp: int = 1, mode: str = "train",
            caches: Optional[Dict[str, Any]] = None, remat: str = "full",
            long_context: bool = False, with_stats: bool = False):
    """Returns (logits, aux_loss, new_caches), and with `with_stats` a 4th
    output, the layers' stats (transformer.lm_forward; {} for the other
    families).

    mode 'chunk' (chunked prefill: append S tokens into an existing cache
    at its ragged per-slot offset) is only implemented for the
    transformer families — gate on `supports_chunked_prefill`.
    """
    if mode == "chunk" and not supports_chunked_prefill(cfg):
        raise NotImplementedError(
            f"chunked prefill is not supported for family {cfg.family!r}")
    if cfg.family == "hybrid":
        wo = ZAMBA_LONG_WINDOW if long_context else None
        out = zamba.zamba_forward(params, batch, cfg, tp=tp, mode=mode,
                                  caches=caches, remat=remat,
                                  window_override=wo)
    elif cfg.family == "xlstm":
        out = xlstm_lm.xlstm_lm_forward(params, batch, cfg, tp=tp, mode=mode,
                                        caches=caches, remat=remat)
    elif cfg.family == "audio":
        out = whisper.whisper_forward(params, batch, cfg, tp=tp, mode=mode,
                                      caches=caches, remat=remat)
    else:
        return transformer.lm_forward(params, batch, cfg, tp=tp, mode=mode,
                                      caches=caches, remat=remat,
                                      with_stats=with_stats)
    return (*out, {}) if with_stats else out


def supports_chunked_prefill(cfg: ModelConfig) -> bool:
    """True when `forward(..., mode="chunk")` works for this config: the
    transformer KV-cache families whose decode state is a positional KV
    cache a chunk can be appended into.  Recurrent-state families
    (hybrid/xlstm) and the stub-frontend families (vlm/audio, whose
    prefill needs precomputed embeddings) fall back to bucketed prefill
    in the serving engine."""
    return cfg.family in ("dense", "moe")


def init_caches(cfg: ModelConfig, batch: int, max_len: int, *, tp: int = 1,
                dtype=None, long_context: bool = False,
                kv_quant: bool = False):
    dtype = dtype or dtype_of(cfg.dtype)
    if cfg.family == "hybrid":
        wo = ZAMBA_LONG_WINDOW if long_context else None
        return zamba.init_zamba_caches(cfg, batch, max_len, tp, dtype,
                                       window=wo)
    if cfg.family == "xlstm":
        return xlstm_lm.init_xlstm_caches(cfg, batch, dtype)
    if cfg.family == "audio":
        return whisper.init_whisper_caches(cfg, batch, max_len, tp, dtype)
    return transformer.init_lm_caches(cfg, batch, max_len, tp, dtype,
                                      quantized=kv_quant)


# ---------------------------------------------------------------------------
# dry-run stand-ins (no allocation)
# ---------------------------------------------------------------------------

def input_specs(cfg: ModelConfig, shape: ShapeConfig,
                *, include_labels: Optional[bool] = None) -> Dict[str, Any]:
    """ShapeDtypeStruct stand-ins for every model input of this cell.

    train:   tokens [B,S] + labels [B,S] (+ stub frontend embeddings)
    prefill: tokens [B,S]                (+ stub frontend embeddings)
    decode:  tokens [B,1]  (KV/state caches come from `cache_specs`)

    For VLM the text length is seq_len - num_patch_tokens so the assigned
    seq_len is the *total* context the backbone sees.
    """
    B, S = shape.global_batch, shape.seq_len
    dt = dtype_of(cfg.dtype)
    if include_labels is None:
        include_labels = shape.kind == "train"
    tok = jnp.int32

    if shape.kind == "decode":
        return {"tokens": jax.ShapeDtypeStruct((B, 1), tok)}

    specs: Dict[str, Any] = {}
    if cfg.family == "vlm":
        St = S - cfg.num_patch_tokens
        specs["tokens"] = jax.ShapeDtypeStruct((B, St), tok)
        specs["patch_embeds"] = jax.ShapeDtypeStruct(
            (B, cfg.num_patch_tokens, cfg.d_model), dt)
    elif cfg.family == "audio":
        specs["tokens"] = jax.ShapeDtypeStruct((B, S), tok)
        specs["frames"] = jax.ShapeDtypeStruct(
            (B, cfg.encoder_ctx, cfg.d_model), dt)
    else:
        specs["tokens"] = jax.ShapeDtypeStruct((B, S), tok)
    if include_labels:
        specs["labels"] = jax.ShapeDtypeStruct((B, S), tok)
    return specs


# Decode-cache leaves over the decoded positions, by leaf name: how many
# axes lie right of the sequence axis.  K/V [.., B, S, KV, D]; MLA's latent
# and rotary key [.., B, S, C]; int8 scales [.., B, S, KV] end in `_scale`.
SEQ_LEAVES = {"k": 2, "v": 2, "ckv": 1, "kpe": 1}


def seq_axis(name: str, ndim: int) -> Optional[int]:
    """The sequence axis of a decode-cache leaf named `name` with `ndim`
    axes (stacked or not), or None for leaves without one: recurrent
    state, `len`, and the encoder positions of cross-attention."""
    after = 1 if name.endswith("_scale") else SEQ_LEAVES.get(name)
    if after is None or ndim < after + 2:
        return None
    return ndim - 1 - after


def grow_caches(cfg: ModelConfig, caches, max_len: int):
    """Pad prefill-returned KV caches (capacity == prompt length) out to
    `max_len` capacity so decode writes don't clamp at the boundary.
    The sequence axis of each leaf comes from `seq_axis`; recurrent-state
    and cross-attention leaves pass through unchanged."""
    def grow(path, leaf):
        names = [str(getattr(p, "key", "")) for p in path]
        last = names[-1] if names else ""
        seq_ax = seq_axis(last, leaf.ndim)
        if seq_ax is not None:
            pad = max_len - leaf.shape[seq_ax]
            if pad > 0:
                w = [(0, 0)] * leaf.ndim
                w[seq_ax] = (0, pad)
                return jnp.pad(leaf, w)
        return leaf
    return jax.tree_util.tree_map_with_path(grow, caches)


def cache_specs(cfg: ModelConfig, shape: ShapeConfig, *, tp: int = 1,
                kv_quant: bool = False):
    """Abstract decode-cache tree for a decode cell (cache holds seq_len)."""
    long_ctx = shape.name == "long_500k"
    return jax.eval_shape(
        lambda: init_caches(cfg, shape.global_batch, shape.seq_len, tp=tp,
                            long_context=long_ctx, kv_quant=kv_quant))


def cache_logical_axes(cfg: ModelConfig, shape: ShapeConfig, *, tp: int = 1,
                       kv_quant: bool = False):
    """Logical-axis tree matching cache_specs' structure.

    KV caches shard batch over DP and the *sequence* dim over the model axis
    (context-parallel decode: GSPMD turns the softmax over the sharded axis
    into the online-softmax all-reduce).  SSM/recurrent states shard batch
    and, where divisible, heads / inner dims.
    """
    structs = cache_specs(cfg, shape, tp=tp, kv_quant=kv_quant)

    def rule(path, leaf):
        names = [str(getattr(p, "key", "")) for p in path]
        nd = len(leaf.shape)
        last = names[-1] if names else ""
        if last == "len":
            return None
        if last in ("k", "v", "xk", "xv") and nd < 4:
            return None
        ax = seq_axis("k" if last in ("xk", "xv") else last, nd)
        if ax is not None:                  # K/V, scales, latent leaves
            return ((None,) * (ax - 1) + ("batch", "kv_seq")
                    + (None,) * (nd - ax - 1))
        if last == "ssm":           # [.., B, H, N, P]
            base = ("batch", "state_heads", None, None)
            return (None,) * (nd - 4) + base
        if last.startswith("conv"):  # [.., B, W-1, C]
            return (None,) * (nd - 3) + ("batch", None, "ssm_inner")
        # xlstm cell states: [B, H, ...] — batch only (tiny model)
        return ("batch",) + (None,) * (nd - 1)

    return jax.tree_util.tree_map_with_path(rule, structs)


def synthesize_batch(cfg: ModelConfig, shape: ShapeConfig, key=None,
                     *, include_labels: Optional[bool] = None):
    """Concrete random batch matching input_specs (smoke tests, examples)."""
    key = key if key is not None else jax.random.PRNGKey(0)
    out = {}
    for name, s in input_specs(cfg, shape, include_labels=include_labels).items():
        key, k = jax.random.split(key)
        if jnp.issubdtype(s.dtype, jnp.integer):
            out[name] = jax.random.randint(k, s.shape, 0, cfg.vocab_size, s.dtype)
        else:
            out[name] = jax.random.normal(k, s.shape, jnp.float32).astype(s.dtype)
    return out


# ---------------------------------------------------------------------------
# parameter counting (for MODEL_FLOPS = 6 N D)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _param_tree_shapes(cfg: ModelConfig):
    return jax.eval_shape(
        lambda k: build_params(k, cfg, tp=1), jax.random.PRNGKey(0))


def count_params_analytic(cfg: ModelConfig, active_only: bool = False) -> int:
    """Parameter count from the real builders (eval_shape; no allocation).

    Convention (documented in EXPERIMENTS.md): the input embedding table is
    excluded unless tied (gathers do no FLOPs); the LM head is included.
    `active_only` scales routed-expert weights by top_k/num_experts.
    """
    shapes = _param_tree_shapes(cfg)
    total = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(shapes)[0]:
        names = [getattr(p, "key", getattr(p, "name", "")) for p in path]
        n = 1
        for d in leaf.shape:
            n *= d
        if "embed" in names and not cfg.tie_embeddings:
            continue
        if active_only and cfg.is_moe and any(
                x in ("w_gate", "w_up", "w_down") for x in names) \
                and "moe" in names:
            n = int(n * cfg.moe.top_k / cfg.moe.num_experts)
        total += n
    return total


def model_flops(cfg: ModelConfig, shape: ShapeConfig) -> float:
    """MODEL_FLOPS = 6 N D (dense) / 6 N_active D (MoE) per step of the cell.

    D = tokens processed: B*S for train/prefill, B for decode (one token)."""
    n = count_params_analytic(cfg, active_only=cfg.is_moe)
    d = shape.tokens if shape.kind != "decode" else shape.global_batch
    mult = 6 if shape.kind == "train" else 2     # fwd-only = 2ND
    return float(mult) * n * d
