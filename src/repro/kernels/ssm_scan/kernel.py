"""Pallas kernel for the SSD intra-chunk block (Mamba2 / zamba2 hot spot).

Per grid cell (batch, chunk, head): given the chunk's log-decay cumsum,
gated inputs, and B/C projections, compute

  y_intra[t] = sum_{j<=t} (C_t . B_j) exp(cum_t - cum_j) xdt_j      [Q, P]
  S_chunk    = sum_j exp(cum_last - cum_j) B_j xdt_j^T              [N, P]

entirely in VMEM — the jnp path materializes the [B,nc,Q,Q,H] decay tensor
in HBM, which made zamba2's train cell memory-bound by 30x (dry-run log).
The inter-chunk recurrence (tiny, sequential over nc) stays in jnp.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(cum_ref, xdt_ref, b_ref, c_ref, y_ref, s_ref):
    cum = cum_ref[0, 0, :, 0]                      # [Q]
    xdt = xdt_ref[0, 0]                            # [Q, P]
    Bc = b_ref[0]                                  # [Q, N]
    Cc = c_ref[0]                                  # [Q, N]
    Q = cum.shape[0]

    CB = jax.lax.dot_general(Cc, Bc, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)   # [Q,Q]
    decay = jnp.exp(cum[:, None] - cum[None, :])
    tri = (jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
           >= jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1))
    M = jnp.where(tri, CB * decay, 0.0)
    y_ref[0, 0, :, 0] = jax.lax.dot_general(
        M, xdt, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(y_ref.dtype)

    dec_end = jnp.exp(cum[Q - 1:] - cum)           # [Q] (static slice)
    s_ref[0, 0] = jax.lax.dot_general(
        Bc * dec_end[:, None], xdt, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(s_ref.dtype)    # [N, P]


def ssd_intra_fwd(cum, xdt, Bc, Cc, *, interpret: bool = False):
    """cum: [B,nc,Q,H] fp32; xdt: [B,nc,Q,H,P]; Bc/Cc: [B,nc,Q,N].
    Returns (y_intra [B,nc,Q,H,P], S_chunk [B,nc,H,N,P]) in fp32."""
    B, nc, Q, H = cum.shape
    P = xdt.shape[-1]
    N = Bc.shape[-1]
    # head-minor layouts for per-(b,c,h) blocks
    cum_h = cum.transpose(0, 1, 3, 2)[..., None]           # [B,nc,H,Q,1]
    xdt_h = xdt.transpose(0, 1, 3, 2, 4)                   # [B,nc,H,Q,P]
    grid = (B * nc, H)

    cum_r = cum_h.reshape(B * nc, H, Q, 1)
    xdt_r = xdt_h.reshape(B * nc, H, Q, P)
    b_r = Bc.reshape(B * nc, Q, N)
    c_r = Cc.reshape(B * nc, Q, N)

    y, s = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, Q, 1), lambda i, h: (i, h, 0, 0)),
            pl.BlockSpec((1, 1, Q, P), lambda i, h: (i, h, 0, 0)),
            pl.BlockSpec((1, Q, N), lambda i, h: (i, 0, 0)),
            pl.BlockSpec((1, Q, N), lambda i, h: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, Q, 1, P), lambda i, h: (i, h, 0, 0, 0)),
            pl.BlockSpec((1, 1, N, P), lambda i, h: (i, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * nc, H, Q, 1, P), jnp.float32),
            jax.ShapeDtypeStruct((B * nc, H, N, P), jnp.float32),
        ],
        interpret=interpret,
    )(cum_r, xdt_r, b_r, c_r)
    y = y.reshape(B, nc, H, Q, P).transpose(0, 1, 3, 2, 4)
    s = s.reshape(B, nc, H, N, P)
    return y, s
