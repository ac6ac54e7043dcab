"""Token sampling: greedy / temperature / top-k, vocab-mask aware."""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class SamplerConfig:
    temperature: float = 0.0       # 0 => greedy
    top_k: int = 0                 # 0 => full softmax
    seed: int = 0


def sample(logits: jax.Array, vocab_size: int, cfg: SamplerConfig,
           key: Optional[jax.Array] = None) -> jax.Array:
    """logits: [B, Vp] -> tokens [B] int32 (padded vocab masked out)."""
    lf = logits.astype(jnp.float32)
    vp = lf.shape[-1]
    if vp > vocab_size:
        col = jax.lax.broadcasted_iota(jnp.int32, (vp,), 0)
        lf = jnp.where(col < vocab_size, lf, -1e30)
    if cfg.temperature <= 0.0:
        return jnp.argmax(lf, axis=-1).astype(jnp.int32)
    lf = lf / cfg.temperature
    if cfg.top_k:
        kth = jax.lax.top_k(lf, cfg.top_k)[0][..., -1:]
        lf = jnp.where(lf < kth, -1e30, lf)
    assert key is not None, "stochastic sampling needs a PRNG key"
    return jax.random.categorical(key, lf, axis=-1).astype(jnp.int32)


def sample_guarded(logits: jax.Array, vocab_size: int, cfg: SamplerConfig,
                   key: Optional[jax.Array] = None
                   ) -> tuple[jax.Array, jax.Array]:
    """`sample` with an in-jit NaN/Inf guard: rows containing any
    non-finite logit fall back to GREEDY over sanitized logits (every
    non-finite entry clamped to -1e30) instead of emitting garbage
    tokens.  Returns (tokens [B], bad_rows [B] bool).

    Rows whose logits are all finite take the exact `sample` result —
    bit-identical to the unguarded path — so the guard is free on
    healthy traffic and the serving contract tests keep passing."""
    lf = logits.astype(jnp.float32)
    finite = jnp.isfinite(lf)
    bad = ~jnp.all(finite, axis=-1)
    clean = jnp.where(finite, lf, -1e30)
    vp = clean.shape[-1]
    if vp > vocab_size:
        col = jax.lax.broadcasted_iota(jnp.int32, (vp,), 0)
        clean = jnp.where(col < vocab_size, clean, -1e30)
    greedy = jnp.argmax(clean, axis=-1).astype(jnp.int32)
    tok = sample(logits, vocab_size, cfg, key)
    return jnp.where(bad, greedy, tok), bad

