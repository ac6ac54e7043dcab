"""Shared building blocks for the model zoo.

Pure-functional parameter trees: every module is an ``init_*`` returning a
dict pytree plus an apply function.  A parallel "spec tree" of *logical axis
names* (same structure, tuples of strings) is produced by ``*_specs``
functions; ``repro.distributed.sharding`` maps logical names onto the mesh.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

Params = Dict[str, Any]

# ---------------------------------------------------------------------------
# dtype helpers
# ---------------------------------------------------------------------------

def dtype_of(name: str):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32,
            "float16": jnp.float16}[name]


# ---------------------------------------------------------------------------
# initializers (fan-in scaled, deterministic per-path folding)
# ---------------------------------------------------------------------------

def dense_init(key, shape, dtype, fan_in: Optional[int] = None):
    fan_in = fan_in if fan_in is not None else shape[-2] if len(shape) >= 2 else shape[-1]
    scale = 1.0 / math.sqrt(max(fan_in, 1))
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def embed_init(key, shape, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(dtype)


def zeros_init(_key, shape, dtype):
    return jnp.zeros(shape, dtype)


def ones_init(_key, shape, dtype):
    return jnp.ones(shape, dtype)


def fold(key, *path) -> jax.Array:
    """Deterministically derive a subkey from a string path.

    Uses crc32, not python hash() (which is salted per-process and would
    make initialization non-reproducible across restarts)."""
    import zlib
    for p in path:
        h = zlib.crc32(p.encode()) % (2**31 - 1)
        key = jax.random.fold_in(key, h)
    return key


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def rmsnorm(x: jax.Array, scale: jax.Array, eps: float = 1e-5) -> jax.Array:
    """RMSNorm with fp32 statistics (model path; Pallas kernel in kernels/)."""
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32)).astype(dt)


def layernorm(x: jax.Array, scale: jax.Array, bias: jax.Array,
              eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dt)


# ---------------------------------------------------------------------------
# rotary position embeddings
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature: 0.1 * mscale * ln(factor) + 1."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_freqs(head_dim: int, theta: float, s) -> jax.Array:
    """YaRN inverse frequencies [D/2] (`s` a configs.base.YaRN), as the
    published DeepSeek-V2 code computes them: the plain frequencies
    above the correction range of beta_fast / beta_slow rotations over
    the original context, the frequencies divided by `factor` below it,
    and a linear ramp between."""
    def corr_dim(rotations):
        return (head_dim * math.log(s.original_max_position_embeddings
                                    / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))
    lo = max(math.floor(corr_dim(s.beta_fast)), 0)
    hi = min(math.ceil(corr_dim(s.beta_slow)), head_dim - 1)
    if lo == hi:
        hi += 0.001
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - lo)
                    / (hi - lo), 0.0, 1.0)
    extra = rope_freqs(head_dim, theta)
    return extra / s.factor * ramp + extra * (1.0 - ramp)


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               freqs: Optional[jax.Array] = None) -> jax.Array:
    """x: [..., S, H, D]; positions: broadcastable to [..., S].  Rotates
    the two halves of each head (`rotate_half`), by `freqs` [D/2] where
    given (YaRN), else by the plain frequencies of `theta`."""
    d = x.shape[-1]
    if freqs is None:
        freqs = rope_freqs(d, theta)                   # [D/2]
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # [..., S, D/2]
    cos = jnp.cos(angles)[..., None, :]                # [..., S, 1, D/2]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(length: int, dim: int) -> jax.Array:
    """Whisper-style fixed sinusoidal embeddings [length, dim]."""
    log_timescale = math.log(10_000.0) / (dim // 2 - 1)
    inv = jnp.exp(-log_timescale * jnp.arange(dim // 2, dtype=jnp.float32))
    scaled = jnp.arange(length, dtype=jnp.float32)[:, None] * inv[None, :]
    return jnp.concatenate([jnp.sin(scaled), jnp.cos(scaled)], axis=-1)


# ---------------------------------------------------------------------------
# activations
# ---------------------------------------------------------------------------

def swiglu(gate: jax.Array, up: jax.Array) -> jax.Array:
    return jax.nn.silu(gate.astype(jnp.float32)).astype(gate.dtype) * up


def gelu(x: jax.Array) -> jax.Array:
    return jax.nn.gelu(x, approximate=True)


# ---------------------------------------------------------------------------
# TP-divisibility padding (see DESIGN.md §5)
#
# Head counts that don't divide the tensor-model axis are padded (q heads)
# or duplicated (kv heads — mathematically exact for GQA).  Padding lives
# entirely inside the layer builders; configs keep the paper's true values.
# ---------------------------------------------------------------------------

def pad_to_multiple(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


def padded_heads(num_heads: int, tp: int) -> int:
    return pad_to_multiple(num_heads, tp) if tp > 1 else num_heads


def dup_factor_kv(num_kv: int, tp: int) -> int:
    """KV-head duplication factor so padded kv count divides tp (exact math)."""
    if tp <= 1 or num_kv % tp == 0:
        return 1
    return pad_to_multiple(num_kv, tp) // num_kv


def padded_vocab(vocab: int, multiple: int = 128) -> int:
    return pad_to_multiple(vocab, multiple)
