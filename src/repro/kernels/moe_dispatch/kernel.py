"""Pallas kernel for MoE dispatch: capacity-buffer gather.

Builds the [E, C, d] expert send-buffers from token rows and slot indices —
the scatter half of the routing "divergence".  Each grid cell copies one
expert's C rows: a SIMT gather where the per-slot valid flag is the thread
mask (invalid slots — capacity overflow or unfilled — write zeros instead
of garbage, the predicated-off lane).

The token matrix block sits in VMEM (local-shard T x d after the a2a
layout, <= a few MB); slot->token indices arrive via scalar prefetch (SMEM).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(idx_ref, x_ref, out_ref, *, C: int, T: int):
    e = pl.program_id(0)

    def body(c, _):
        tok = idx_ref[e * C + c]
        valid = jnp.logical_and(tok >= 0, tok < T)
        row = jnp.where(valid, tok, 0)
        data = x_ref[pl.ds(row, 1), :]                            # [1, d]
        out_ref[0, pl.ds(c, 1), :] = jnp.where(valid, data,
                                               jnp.zeros_like(data))
        return ()

    jax.lax.fori_loop(0, C, body, ())


def moe_gather_fwd(x, slot_token, E: int, C: int, *,
                   interpret: bool = False):
    """x: [T, d]; slot_token: [E*C] int32 (token id per slot, -1 = empty)
    -> buf [E, C, d]."""
    T, d = x.shape
    # Mosaic cannot load one dynamically indexed row of a packed 16-bit
    # VMEM block ("index ... is a multiple of 8"); 16-bit rows are gathered
    # as float32, which round-trips bf16/f16 exactly
    dtype = x.dtype
    if dtype.itemsize < 4:
        x = x.astype(jnp.float32)
    kern = functools.partial(_kernel, C=C, T=T)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(E,),
        in_specs=[pl.BlockSpec((T, d), lambda e, idx: (0, 0))],
        out_specs=pl.BlockSpec((1, C, d), lambda e, idx: (e, 0, 0)),
    )
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((E, C, d), x.dtype),
        interpret=interpret,
    )(slot_token, x)
    return out.astype(dtype)
