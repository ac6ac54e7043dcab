"""Attention: GQA with RoPE, sliding window, chunked-flash train path and
KV-cache decode path.

The train/prefill path is a pure-jnp chunked flash attention (fp32 running
max/sum, O(chunk^2) temporaries) so that the 32k-context cells compile with
bounded memory; the Pallas kernel in ``repro.kernels.flash_attention`` is the
TPU hot-spot implementation validated against the same math.

Head counts are kept paper-exact; tensor parallelism shards the flattened
qkv projection dim (heads*head_dim), which divides the model axis for every
assigned config.  See DESIGN.md §5.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import common
from repro.models.common import dense_init, zeros_init, fold

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# parameter construction
# ---------------------------------------------------------------------------

def attn_dims(cfg: ModelConfig, tp: int) -> Tuple[int, int, int]:
    """(q_heads, kv_heads, head_dim).

    True (paper-exact) head counts.  Sharding happens on the *flattened*
    qkv dim (heads*hd), which is divisible by the model axis for every
    assigned config; GSPMD re-shards internally around the per-head
    reshape.  (An earlier pad/duplicate scheme broke GQA grouping when
    padded_q %% padded_kv != 0 — e.g. whisper 6H at tp=16.)
    """
    del tp
    return cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim


def init_attention(key, cfg: ModelConfig, tp: int, dtype) -> Dict[str, Any]:
    d = cfg.d_model
    hq, kv, hd = attn_dims(cfg, tp)
    p = {
        "wq": dense_init(fold(key, "wq"), (d, hq * hd), dtype, fan_in=d),
        "wk": dense_init(fold(key, "wk"), (d, kv * hd), dtype, fan_in=d),
        "wv": dense_init(fold(key, "wv"), (d, kv * hd), dtype, fan_in=d),
        "wo": dense_init(fold(key, "wo"), (hq * hd, d), dtype, fan_in=hq * hd),
    }
    if cfg.qkv_bias:
        p["bq"] = zeros_init(None, (hq * hd,), dtype)
        p["bk"] = zeros_init(None, (kv * hd,), dtype)
        p["bv"] = zeros_init(None, (kv * hd,), dtype)
    return p


def attention_specs(cfg: ModelConfig) -> Dict[str, Any]:
    s = {"wq": ("embed", "qkv"), "wk": ("embed", "qkv"), "wv": ("embed", "qkv"),
         "wo": ("qkv", "embed")}
    if cfg.qkv_bias:
        s.update({"bq": ("qkv",), "bk": ("qkv",), "bv": ("qkv",)})
    return s


# ---------------------------------------------------------------------------
# chunked flash attention (train / prefill)
# ---------------------------------------------------------------------------

def _pick_chunk(S: int, target: int) -> int:
    """Largest divisor of S that is <= target (sequence lengths like 1500
    don't divide by powers of two)."""
    c = min(target, S)
    while S % c:
        c -= 1
    return c


def _chunk_mask(off, q_chunk: int, k_chunk: int, causal: bool,
                window: Optional[int]):
    """[Qc, Kc] bool mask of *allowed* pairs for a block at scalar offset
    `off` = iq*q_chunk - ik*k_chunk.

    Built from a CONSTANT relative-index matrix plus one scalar, never from
    absolute positions: if it depended on (iq, ik) data, XLA hoists a
    per-(iq,ik) mask tensor out of the scan and materializes
    O(nq*nk*Qc*Kc) bytes (observed: a 537 MB pred buffer in the phi3
    train_4k dry-run).  rel+off == qpos - kpos exactly.
    """
    rel = (jnp.arange(q_chunk)[:, None] - jnp.arange(k_chunk)[None, :])
    delta = rel + off
    m = jnp.ones((q_chunk, k_chunk), bool)
    if causal:
        m &= delta >= 0
    if window is not None:
        m &= delta < window
    return m



def _constrain_blocks(q6, a, b, KV: int):
    """Pin the flash scan inputs' KV-head axis to the model axis — but only
    when KV divides it.  Without the constraint GSPMD reshards q/k/v blocks
    inside the kv scan (67 MB gathers x 1024 iterations on olmoe);
    with a non-divisible constraint it falls into involuntary full
    rematerialization (observed on internvl, kv=8 on a 16-way axis)."""
    from repro.distributed.sharding import current_context, constrain
    ctx = current_context()
    if ctx is None:
        return q6, a, b
    mesh, rules = ctx
    axis = rules.get("kv_heads")
    size = mesh.shape.get(axis, 1) if axis else 1
    if size <= 1:
        return q6, a, b
    if KV % size == 0:
        q6 = constrain(q6, (None, "batch", "kv_heads")
                       + (None,) * (q6.ndim - 3))
        if a is not None:
            a = constrain(a, (None, "batch", "kv_heads")
                          + (None,) * (a.ndim - 3))
        if b is not None:
            b = constrain(b, (None, "batch", "kv_heads")
                          + (None,) * (b.ndim - 3))
        return q6, a, b
    # kv heads don't divide the model axis (e.g. internvl kv=8 on 16):
    # leave the layout to GSPMD — both a padded head constraint and an
    # explicit context-parallel (Qc-sharded, k/v-replicated) layout
    # measured WORSE (EXPERIMENTS.md internvl it1/it4: involuntary remat,
    # +44% collective respectively).
    return q6, a, b


def _flash_fwd(q, k, v, causal, window, q_chunk, k_chunk):
    """Forward flash pass.  q: [B,S,Hq,D]; k,v: [B,Sk,KV,D].
    Returns (out [B,S,Hq,D], lse [nq,B,KV,G,Qc] fp32)."""
    B, S, Hq, D = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    G = Hq // KV
    nq, nk = S // q_chunk, Sk // k_chunk
    scale = 1.0 / (D ** 0.5)

    # explicit head sharding on the scan inputs: without it, GSPMD reshards
    # q/k/v blocks INSIDE the kv scan (observed: 67 MB f32 all-gathers per
    # block-iteration x 1024 iterations on the olmoe train cell)
    qs = q.reshape(B, nq, q_chunk, KV, G, D).transpose(1, 0, 3, 4, 2, 5)
    ks = k.reshape(B, nk, k_chunk, KV, D).transpose(1, 0, 3, 2, 4)
    vs = v.reshape(B, nk, k_chunk, KV, D).transpose(1, 0, 3, 2, 4)
    qs, ks, vs = _constrain_blocks(qs, ks, vs, KV)
    # qs: [nq, B, KV, G, Qc, D]; ks/vs: [nk, B, KV, Kc, D]

    def q_step(_, qi_and_idx):
        qi, iq = qi_and_idx

        def k_step(carry, ki_and_idx):
            m, l, acc = carry
            (kc, vc), ik = ki_and_idx
            # bf16 operands, fp32 accumulation on the MXU — an explicit
            # astype materializes fp32 copies of every block in HBM
            s = jnp.einsum("bkgqd,bkcd->bkgqc", qi, kc,
                           preferred_element_type=jnp.float32) * scale
            if causal or window is not None:
                off = iq * q_chunk - ik * k_chunk
                mask = _chunk_mask(off, q_chunk, k_chunk, causal, window)
                s = jnp.where(mask[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + p.sum(axis=-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bkgqc,bkcd->bkgqd", p.astype(vc.dtype), vc,
                preferred_element_type=jnp.float32)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, KV, G, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, KV, G, q_chunk), jnp.float32)
        a0 = jnp.zeros((B, KV, G, q_chunk, D), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            k_step, (m0, l0, a0), ((ks, vs), jnp.arange(nk)))
        l_safe = jnp.maximum(l, 1e-30)
        out = acc / l_safe[..., None]
        lse = m + jnp.log(l_safe)
        return None, (out.astype(q.dtype), lse)

    _, (outs, lses) = jax.lax.scan(q_step, None, (qs, jnp.arange(nq)))
    out = outs.transpose(1, 0, 4, 2, 3, 5).reshape(B, S, Hq, D)
    return out, lses


def _flash_bwd(q, k, v, out, lse, do, causal, window, q_chunk, k_chunk):
    """FlashAttention-2-style backward: recomputes every block from
    (q,k,v,lse) — no stacked per-block residuals (the naive autodiff of the
    forward scans stacks O(nq*nk*Qc*Kc) masks/probabilities, which is what
    blew the dry-run memory budget)."""
    B, S, Hq, D = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    G = Hq // KV
    nq, nk = S // q_chunk, Sk // k_chunk
    scale = 1.0 / (D ** 0.5)

    qs = q.reshape(B, nq, q_chunk, KV, G, D).transpose(1, 0, 3, 4, 2, 5)
    dos = do.reshape(B, nq, q_chunk, KV, G, D).transpose(1, 0, 3, 4, 2, 5)
    os_ = out.reshape(B, nq, q_chunk, KV, G, D).transpose(1, 0, 3, 4, 2, 5)
    ks = k.reshape(B, nk, k_chunk, KV, D).transpose(1, 0, 3, 2, 4)
    vs = v.reshape(B, nk, k_chunk, KV, D).transpose(1, 0, 3, 2, 4)
    qs, ks, vs = _constrain_blocks(qs, ks, vs, KV)
    dos, os_, _ = _constrain_blocks(dos, os_, None, KV)
    # delta_i = rowsum(do * o)  [nq, B, KV, G, Qc]
    delta = jnp.sum(dos.astype(jnp.float32) * os_.astype(jnp.float32), -1)

    def q_step(carry, inp):
        dk, dv = carry                      # [nk,B,KV,Kc,D] fp32
        qi, doi, lse_i, d_i, iq = inp

        def k_step(dq_acc, ki):
            (kc, vc, dk_j, dv_j), ik = ki
            # bf16 operands, fp32 accumulation on the MXU — an explicit
            # astype materializes fp32 copies of every block in HBM
            s = jnp.einsum("bkgqd,bkcd->bkgqc", qi, kc,
                           preferred_element_type=jnp.float32) * scale
            if causal or window is not None:
                off = iq * q_chunk - ik * k_chunk
                mask = _chunk_mask(off, q_chunk, k_chunk, causal, window)
                s = jnp.where(mask[None, None, None], s, NEG_INF)
            p = jnp.exp(s - lse_i[..., None])                 # [B,KV,G,Qc,Kc]
            dv_new = dv_j + jnp.einsum("bkgqc,bkgqd->bkcd", p,
                                       doi.astype(jnp.float32))
            dp = jnp.einsum("bkgqd,bkcd->bkgqc", doi.astype(jnp.float32),
                            vc.astype(jnp.float32))
            ds = p * (dp - d_i[..., None]) * scale
            dq_new = dq_acc + jnp.einsum("bkgqc,bkcd->bkgqd", ds,
                                         kc.astype(jnp.float32))
            dk_new = dk_j + jnp.einsum("bkgqc,bkgqd->bkcd", ds,
                                       qi.astype(jnp.float32))
            return dq_new, (dk_new, dv_new)

        dq0 = jnp.zeros((B, KV, G, q_chunk, D), jnp.float32)
        dq_i, (dk, dv) = jax.lax.scan(
            lambda c, x: k_step(c, x),
            dq0, ((ks, vs, dk, dv), jnp.arange(nk)))
        return (dk, dv), dq_i

    dk0 = jnp.zeros((nk, B, KV, k_chunk, D), jnp.float32)
    dv0 = jnp.zeros((nk, B, KV, k_chunk, D), jnp.float32)
    (dk, dv), dqs = jax.lax.scan(
        q_step, (dk0, dv0), (qs, dos, lse, delta, jnp.arange(nq)))

    dq = dqs.transpose(1, 0, 4, 2, 3, 5).reshape(B, S, Hq, D).astype(q.dtype)
    dk_out = dk.transpose(1, 0, 3, 2, 4).reshape(B, Sk, KV, D).astype(k.dtype)
    dv_out = dv.transpose(1, 0, 3, 2, 4).reshape(B, Sk, KV, D).astype(v.dtype)
    return dq, dk_out, dv_out


@functools.lru_cache(maxsize=None)
def _make_flash(causal: bool, window: Optional[int], q_chunk: int,
                k_chunk: int):
    @jax.custom_vjp
    def flash(q, k, v):
        out, _ = _flash_fwd(q, k, v, causal, window, q_chunk, k_chunk)
        return out

    def fwd(q, k, v):
        out, lse = _flash_fwd(q, k, v, causal, window, q_chunk, k_chunk)
        return out, (q, k, v, out, lse)

    def bwd(res, do):
        q, k, v, out, lse = res
        return _flash_bwd(q, k, v, out, lse, do, causal, window,
                          q_chunk, k_chunk)

    flash.defvjp(fwd, bwd)
    return flash


def flash_attention_jnp(q, k, v, *, causal: bool = True,
                        window: Optional[int] = None,
                        bidirectional: bool = False,
                        q_chunk: int = 512, k_chunk: int = 512) -> jax.Array:
    """q: [B,S,Hq,D]; k,v: [B,Sk,KV,D] -> [B,S,Hq,D].

    Double-scan flash with custom VJP: the forward keeps running
    (max, denom, acc) in fp32 per chunk; the backward recomputes each block
    from (q,k,v,out,lse).  Memory is O(B * chunk^2) per step regardless of
    S — this is what lets the 32k-context cells compile inside the dry-run
    memory budget.
    """
    B, S, Hq, D = q.shape
    Sk = k.shape[1]
    causal = causal and not bidirectional
    assert S == Sk or (not causal and window is None), \
        "cross-attention must be unmasked"
    q_chunk = _pick_chunk(S, q_chunk)
    k_chunk = _pick_chunk(Sk, k_chunk)
    return _make_flash(causal, window, q_chunk, k_chunk)(q, k, v)


# ---------------------------------------------------------------------------
# decode attention over a KV cache
# ---------------------------------------------------------------------------

def decode_attention(q, k_cache, v_cache, cache_len, *,
                     window: Optional[int] = None) -> jax.Array:
    """q: [B,1,Hq,D]; caches: [B,Smax,KV,D]; cache_len: current length
    (includes the token being decoded) — a scalar or a per-slot [B] vector
    (the serving engine's continuous batching uses ragged lengths).  For
    windowed caches the buffer is a ring of size `window` and every slot
    is valid once full."""
    B, _, Hq, D = q.shape
    Smax, KV = k_cache.shape[1], k_cache.shape[2]
    G = Hq // KV
    scale = 1.0 / (D ** 0.5)
    lens = jnp.broadcast_to(cache_len, (B,))
    qg = q.reshape(B, KV, G, D)
    s = jnp.einsum("bkgd,bskd->bkgs", qg.astype(jnp.float32),
                   k_cache.astype(jnp.float32)) * scale
    pos = jnp.arange(Smax)
    if window is not None and Smax == window:
        valid = pos[None] < jnp.minimum(lens, Smax)[:, None]   # ring
    else:
        valid = pos[None] < lens[:, None]
        if window is not None:
            valid &= pos[None] >= (lens - window)[:, None]
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", p, v_cache.astype(jnp.float32))
    return out.reshape(B, 1, Hq, D).astype(q.dtype)


def chunk_attention(q, k_cache, v_cache, cache_len, *,
                    window: Optional[int] = None) -> jax.Array:
    """Chunk-append attention for chunked prefill.

    q: [B,C,Hq,D] — C new query positions appended after `cache_len`
    tokens already in the cache; caches: [B,Smax,KV,D]; cache_len: [B]
    (or scalar) length *before* this chunk.  Query i (0-based within the
    chunk) sits at absolute position cache_len + i and attends causally
    over cache[0 : cache_len + i + 1].  Ring (windowed, Smax == window)
    caches are not supported — callers gate on layout (the serving
    engine falls back to bucketed prefill for ring caches)."""
    B, C, Hq, D = q.shape
    Smax, KV = k_cache.shape[1], k_cache.shape[2]
    G = Hq // KV
    scale = 1.0 / (D ** 0.5)
    lens = jnp.broadcast_to(cache_len, (B,))
    qg = q.reshape(B, C, KV, G, D)
    s = jnp.einsum("bckgd,bskd->bckgs", qg.astype(jnp.float32),
                   k_cache.astype(jnp.float32)) * scale
    pos = jnp.arange(Smax)
    # end[b, c] = absolute position of chunk-query c, exclusive bound
    end = lens[:, None] + jnp.arange(C)[None, :] + 1        # [B, C]
    valid = pos[None, None] < end[..., None]                # [B, C, Smax]
    if window is not None:
        valid &= pos[None, None] >= (end - window)[..., None]
    s = jnp.where(valid[:, :, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bckgs,bskd->bckgd", p, v_cache.astype(jnp.float32))
    return out.reshape(B, C, Hq, D).astype(q.dtype)


def chunk_cache_update(k_cache, v_cache, k_new, v_new, cache_len,
                       lanes=None):
    """Insert a C-token chunk ([B,C,...]) at per-slot offset `cache_len`
    (no ring support — see chunk_attention).  Callers must guarantee
    cache_len + C <= Smax per slot (dynamic_update_slice clamps the
    start index, which would silently corrupt earlier positions).

    `lanes` ([B] bool or None): a lane where False writes back the rows
    it would have overwritten, read from the same (clamped) start, so
    its cache stays bit-for-bit unchanged.  That write is unrolled over
    the slots, one slab at a time: on the TPU a vmapped read of the old
    rows adds per-layer copies of the cache slice (chunk program 73 ms
    against 51 ms at phi3-mini width, 8 x 1024, on a v5e)."""
    B = k_cache.shape[0]
    lens = jnp.broadcast_to(cache_len, (B,))

    def put(cache, new):
        if lanes is None:
            return jax.vmap(
                lambda c, n, i: jax.lax.dynamic_update_slice_in_dim(
                    c, n.astype(c.dtype), i, axis=0))(cache, new, lens)
        for b in range(B):
            slab = cache[b]
            old = jax.lax.dynamic_slice_in_dim(slab, lens[b], new.shape[1])
            rows = jnp.where(lanes[b], new[b].astype(cache.dtype), old)
            cache = cache.at[b].set(jax.lax.dynamic_update_slice_in_dim(
                slab, rows, lens[b], axis=0))
        return cache

    return put(k_cache, k_new), put(v_cache, v_new)


def cache_update(k_cache, v_cache, k_new, v_new, cache_len,
                 window: Optional[int] = None, lanes=None):
    """Insert one position ([B,1,...]) at cache_len (ring write if
    windowed).  cache_len: scalar or per-slot [B] vector.  Works for both
    KV payloads [B,S,KV,D] and quantization scales [B,S,KV].

    `lanes` ([B] bool or None): a lane where False writes back the row
    it would have overwritten, read at the same index, so its cache
    stays bit-for-bit unchanged.  That write stays vmapped: unrolled over
    the slots it moves each layer's slab through the TPU's on-chip
    memory (decode program 326 ms against 43 ms at phi3-mini width,
    8 x 1024, on a v5e)."""
    B, Smax = k_cache.shape[0], k_cache.shape[1]
    lens = jnp.broadcast_to(cache_len, (B,))
    idx = lens % Smax if (window is not None and Smax == window) else lens

    def put(cache, new):
        if lanes is not None:
            old = jax.vmap(lambda c, i: jax.lax.dynamic_slice_in_dim(
                c, i, 1))(cache, idx)
            keep = lanes.reshape((B,) + (1,) * (new.ndim - 1))
            new = jnp.where(keep, new.astype(cache.dtype), old)
        return jax.vmap(
            lambda c, n, i: jax.lax.dynamic_update_slice_in_dim(
                c, n.astype(c.dtype), i, axis=0))(cache, new, idx)

    return put(k_cache, k_new), put(v_cache, v_new)


def decode_attention_q8(q, cache, cache_len, *,
                        window: Optional[int] = None) -> jax.Array:
    """Decode attention over an int8-quantized KV cache.

    Scores run as int8 x int8 dots with int32 accumulation — the cache is
    READ at one byte per element and no dequantized full-cache buffer ever
    materializes (folding v's per-token scale into the probabilities keeps
    the combine an int8 dot too)."""
    B, _, Hq, D = q.shape
    Smax, KV = cache["k"].shape[1], cache["k"].shape[2]
    G = Hq // KV
    scale = 1.0 / (D ** 0.5)
    lens = jnp.broadcast_to(cache_len, (B,))

    qg = q.reshape(B, KV, G, D)
    qq, qs = _quantize_kv(qg)                         # int8 [B,KV,G,D]
    s_i32 = jnp.einsum("bkgd,bskd->bkgs", qq, cache["k"],
                       preferred_element_type=jnp.int32)
    k_s = cache["k_scale"].astype(jnp.float32)        # [B,S,KV]
    s = (s_i32.astype(jnp.float32)
         * qs.astype(jnp.float32)[..., None]
         * k_s.transpose(0, 2, 1)[:, :, None, :]) * scale

    pos = jnp.arange(Smax)
    if window is not None and Smax == window:
        valid = pos[None] < jnp.minimum(lens, Smax)[:, None]
    else:
        valid = pos[None] < lens[:, None]
        if window is not None:
            valid &= pos[None] >= (lens - window)[:, None]
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)                    # [B,KV,G,S] f32
    # fold v's per-token scale into p, then quantize p per row
    v_s = cache["v_scale"].astype(jnp.float32).transpose(0, 2, 1)
    pv = p * v_s[:, :, None, :]
    ps = jnp.max(jnp.abs(pv), axis=-1) / 127.0        # [B,KV,G]
    ps = jnp.maximum(ps, 1e-20)
    pq = jnp.clip(jnp.round(pv / ps[..., None]), -127, 127).astype(jnp.int8)
    o_i32 = jnp.einsum("bkgs,bskd->bkgd", pq, cache["v"],
                       preferred_element_type=jnp.int32)
    out = o_i32.astype(jnp.float32) * ps[..., None]
    return out.reshape(B, 1, Hq, D).astype(q.dtype)


# ---------------------------------------------------------------------------
# full attention block forward
# ---------------------------------------------------------------------------

def attn_forward(p: Dict[str, Any], x: jax.Array, positions: jax.Array, *,
                 cfg: ModelConfig, tp: int, mode: str,
                 cache: Optional[Dict[str, Any]] = None,
                 kv_override: Optional[Tuple[jax.Array, jax.Array]] = None,
                 bidirectional: bool = False,
                 use_rope: bool = True,
                 window: Optional[int] = None,
                 q_chunk: int = 512, k_chunk: int = 512):
    """Returns (out [B,S,d], new_cache).

    mode: 'train' | 'prefill' | 'decode'.
    kv_override: (k, v) already in [B,Skv,KV,D] — used for cross-attention
    (the cache holds precomputed encoder K/V; no cache writes).
    """
    B, S, d = x.shape
    hq, kvh, hd = attn_dims(cfg, tp)
    wdw = window if window is not None else cfg.sliding_window

    q = x @ p["wq"]
    if "bq" in p:
        q = q + p["bq"]
    q = q.reshape(B, S, hq, hd)

    if kv_override is None:
        k = x @ p["wk"]
        v = x @ p["wv"]
        if "bk" in p:
            k, v = k + p["bk"], v + p["bv"]
        k = k.reshape(B, S, kvh, hd)
        v = v.reshape(B, S, kvh, hd)
        if use_rope:
            q = common.apply_rope(q, positions, cfg.rope_theta)
            k = common.apply_rope(k, positions, cfg.rope_theta)
    else:
        k, v = kv_override
        if use_rope:
            q = common.apply_rope(q, positions, cfg.rope_theta)

    new_cache = cache
    # serving's lane mask ([B] bool): lanes where False keep their cache
    lanes = None if cache is None else cache.get("lanes")
    if mode == "chunk":
        # chunk-append prefill: write S new positions at the ragged
        # per-slot offset, attend over the whole (masked) cache.  The
        # int8 cache layout is decode-only; the serving engine gates
        # chunked prefill on an unquantized, non-ring cache.
        assert cache is not None and kv_override is None
        assert "k_scale" not in cache, \
            "chunked prefill does not support int8 KV caches"
        with jax.named_scope("kv_write"):
            kc, vc = chunk_cache_update(cache["k"], cache["v"], k, v,
                                        cache["len"], lanes)
        out = chunk_attention(q, kc, vc, cache["len"], window=wdw)
        new_cache = {"k": kc, "v": vc, "len": cache["len"] + S}
    elif mode == "decode" and kv_override is None:
        assert cache is not None
        if "k_scale" in cache:                      # int8-quantized cache
            kq, ks_ = _quantize_kv(k)
            vq, vs_ = _quantize_kv(v)
            with jax.named_scope("kv_write"):
                kc, vc = cache_update(cache["k"], cache["v"], kq, vq,
                                      cache["len"], window=wdw, lanes=lanes)
                ksc, vsc = cache_update(cache["k_scale"], cache["v_scale"],
                                        ks_, vs_, cache["len"], window=wdw,
                                        lanes=lanes)
            qc = {"k": kc, "v": vc, "k_scale": ksc, "v_scale": vsc}
            out = decode_attention_q8(q, qc, cache["len"] + 1, window=wdw)
            new_cache = dict(qc, len=cache["len"] + 1)
        else:
            with jax.named_scope("kv_write"):
                kc, vc = cache_update(cache["k"], cache["v"], k, v,
                                      cache["len"], window=wdw, lanes=lanes)
            out = decode_attention(q, kc, vc, cache["len"] + 1, window=wdw)
            new_cache = {"k": kc, "v": vc, "len": cache["len"] + 1}
    elif mode == "decode":
        # cross-attention during decode: attend over the fixed encoder ctx
        out = decode_attention(q, k, v, jnp.int32(k.shape[1]), window=None)
    else:
        out = flash_attention_jnp(
            q, k, v, causal=(kv_override is None), window=wdw,
            bidirectional=bidirectional, q_chunk=q_chunk, k_chunk=k_chunk)
        if mode == "prefill" and kv_override is None:
            new_cache = {"k": k, "v": v, "len": jnp.int32(S)}

    y = out.reshape(B, S, hq * hd) @ p["wo"]
    return y, new_cache


def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int, tp: int,
                  dtype, window: Optional[int] = None,
                  quantized: bool = False) -> Dict[str, Any]:
    _, kvh, hd = attn_dims(cfg, tp)
    wdw = window if window is not None else cfg.sliding_window
    size = min(max_len, wdw) if wdw is not None else max_len
    if quantized:
        # int8 payload + per-(token, head) fp16 scales: ~2x less HBM per
        # decode step (decode cells are pure cache-bandwidth)
        return {
            "k": jnp.zeros((batch, size, kvh, hd), jnp.int8),
            "v": jnp.zeros((batch, size, kvh, hd), jnp.int8),
            "k_scale": jnp.zeros((batch, size, kvh), jnp.float16),
            "v_scale": jnp.zeros((batch, size, kvh), jnp.float16),
            "len": jnp.int32(0),
        }
    return {
        "k": jnp.zeros((batch, size, kvh, hd), dtype),
        "v": jnp.zeros((batch, size, kvh, hd), dtype),
        "len": jnp.int32(0),
    }


def _quantize_kv(x):
    """x: [B,1,KV,D] -> (int8 [B,1,KV,D], scale fp16 [B,1,KV])."""
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1) / 127.0
    scale = jnp.maximum(scale, 1e-8)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float16)


def _dequantize_kv(q, scale):
    return q.astype(jnp.float32) * scale.astype(jnp.float32)[..., None]
