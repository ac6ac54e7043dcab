"""Mixture-of-Experts block: top-k routing, capacity-based sort dispatch,
shared experts, aux load-balancing loss.

Vortex framing (DESIGN.md §2): expert routing *is* control divergence.
Tokens disagree on which "path" (expert) to take; the dispatch below is the
IPDOM-style serialization — each divergent path executes with its lane mask
(the capacity buffer), then paths reconverge at the combine (the `join`).
Shared experts are the uniform path: every lane agrees, so no dispatch
machinery is needed — Vortex's "split acts like a nop".

Baseline implementation is pjit-friendly sort-based dispatch with *global*
capacity (argsort over (token, slot) pairs -> scatter into per-expert
buffers -> grouped GEMM -> combine).  The shard_map all-to-all variant used
by the perf pass lives in `repro.models.moe_a2a`.

A layer told that this chip holds experts [lo, hi) (`MoEConfig.held`,
one chip's share under expert parallelism) takes `held_forward`
instead: it routes over every expert, computes its held experts' part
for every token routed there, drops nothing, and leaves the gates as
they are where `norm_topk_prob` is false (DeepSeek-V2).
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import constrain
from repro.models.common import dense_init, fold, swiglu
from repro.models.mlp import init_mlp, mlp_forward, mlp_specs


def moe_capacity(num_tokens: int, cfg: ModelConfig) -> int:
    m = cfg.moe
    c = int(num_tokens * m.top_k / m.num_experts * m.capacity_factor)
    return max(8, ((c + 7) // 8) * 8)


def init_moe(key, cfg: ModelConfig, dtype) -> Dict[str, Any]:
    """The router over every expert; the weights of the experts held
    here (all of them unless `moe.held` names a slice)."""
    d, m = cfg.d_model, cfg.moe
    E = m.n_held
    p = {
        "router": dense_init(fold(key, "router"), (d, m.num_experts),
                             jnp.float32, fan_in=d),
        "w_gate": dense_init(fold(key, "w_gate"), (E, d, m.d_ff),
                             dtype, fan_in=d),
        "w_up": dense_init(fold(key, "w_up"), (E, d, m.d_ff),
                           dtype, fan_in=d),
        "w_down": dense_init(fold(key, "w_down"), (E, m.d_ff, d),
                             dtype, fan_in=m.d_ff),
    }
    if m.num_shared:
        # shared experts fused into one dense MLP of width num_shared * d_ff
        p["shared"] = init_mlp(fold(key, "shared"), d, m.num_shared * m.d_ff, dtype)
    return p


def moe_specs(cfg: ModelConfig) -> Dict[str, Any]:
    s = {
        "router": (None, None),     # replicated: d x E fp32 is tiny and the
                                    # a2a dispatch needs it whole per shard
        "w_gate": ("experts", "embed", None),
        "w_up": ("experts", "embed", None),
        "w_down": ("experts", None, "embed"),
    }
    if cfg.moe.num_shared:
        s["shared"] = mlp_specs()
    return s


def moe_forward(p: Dict[str, Any], x: jax.Array, cfg: ModelConfig
                ) -> Tuple[jax.Array, jax.Array]:
    """x: [B, S, d] -> (y [B, S, d], aux_loss scalar fp32).

    Dispatch selection: the expert-parallel all-to-all path (moe_a2a.py)
    whenever a mesh is active and shapes permit; the pjit global-sort path
    otherwise (CPU tests, decode's S=1) or when rules["moe_dispatch"] ==
    "sort" (the baseline knob)."""
    from repro.models import moe_a2a
    if moe_a2a.a2a_applicable(x):
        return moe_a2a.moe_forward_a2a(p, x, cfg)
    B, S, d = x.shape
    m = cfg.moe
    T = B * S
    k = m.top_k
    E = m.num_experts
    C = moe_capacity(T, cfg)

    xf = x.reshape(T, d)
    xf = constrain(xf, ("batch", None))

    # --- routing (fp32) ---------------------------------------------------
    gates, eidx, aux = _route(xf, p["router"], cfg)        # [T, k]

    # --- dispatch: sort (token, slot) pairs by expert ----------------------
    flat_e = eidx.reshape(T * k)                           # [Tk]
    sort_idx = jnp.argsort(flat_e)                         # stable
    sorted_e = jnp.take(flat_e, sort_idx)
    counts = jnp.zeros(E, jnp.int32).at[flat_e].add(1)
    starts = jnp.cumsum(counts) - counts
    pos = jnp.arange(T * k, dtype=jnp.int32) - jnp.take(starts, sorted_e)
    keep = pos < C
    dest = jnp.where(keep, sorted_e * C + pos, E * C)      # E*C = drop slot
    token_of = sort_idx // k

    buf = jnp.zeros((E * C + 1, d), x.dtype)
    buf = buf.at[dest].set(jnp.take(xf, token_of, axis=0), mode="drop")
    buf = buf[: E * C].reshape(E, C, d)
    buf = constrain(buf, ("experts", "expert_cap", None))

    # --- grouped expert GEMMs (the divergent paths, lane-masked) -----------
    h = swiglu(jnp.einsum("ecd,edf->ecf", buf, p["w_gate"]),
               jnp.einsum("ecd,edf->ecf", buf, p["w_up"]))
    out = jnp.einsum("ecf,efd->ecd", h, p["w_down"])
    out = constrain(out, ("experts", "expert_cap", None))

    # --- combine (the `join`) ----------------------------------------------
    out_flat = jnp.concatenate(
        [out.reshape(E * C, d), jnp.zeros((1, d), x.dtype)], axis=0)
    gathered = jnp.take(out_flat, dest, axis=0)            # [Tk, d] sorted order
    sorted_gates = jnp.take(gates.reshape(T * k), sort_idx)
    y = jnp.zeros((T, d), jnp.float32).at[token_of].add(
        gathered.astype(jnp.float32) * sorted_gates[:, None])

    # --- shared experts: the uniform path (split-is-a-nop) -----------------
    if "shared" in p:
        y = y + mlp_forward(p["shared"], xf).astype(jnp.float32)

    y = constrain(y.astype(x.dtype), ("batch", None))
    return y.reshape(B, S, d), aux


def _route(xf, router, cfg: ModelConfig, precision=None):
    """Softmax over every expert, greedy top-k, gates renormalised only
    where `norm_topk_prob`.  -> (gates [T,k], experts [T,k], aux): the
    Switch-style load-balancing loss E * sum_e f_e * P_e."""
    m = cfg.moe
    logits = jnp.matmul(xf.astype(jnp.float32), router,
                        precision=precision)               # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    gates, eidx = jax.lax.top_k(probs, m.top_k)
    if m.norm_topk_prob:
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    T = xf.shape[0]
    f_e = jnp.zeros(m.num_experts, jnp.float32).at[eidx.reshape(-1)].add(
        1.0) / (T * m.top_k)
    aux = m.num_experts * jnp.sum(f_e * probs.mean(0)) * m.router_aux_coef
    return gates, eidx, aux


def held_forward(p: Dict[str, Any], x: jax.Array, cfg: ModelConfig,
                 lanes=None) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One chip's share of an expert layer: experts [lo, hi) of
    `moe.held`.  Routes every token over all experts and adds, for each
    (token, held expert) pair the router chose, the gated expert output;
    no token is dropped.  Each held expert runs over every token with the
    gates of the tokens it was not chosen for at 0, which at decode
    reads each held expert's weights once.  The shared experts run on
    every token.

    x: [B, S, d] -> (y [B, S, d], aux_loss, held pairs): the number of
    (token, held expert) pairs routed, over the lanes where `lanes` ([B]
    bool; every lane if None)."""
    B, S, d = x.shape
    lo, hi = cfg.moe.held
    xf = x.reshape(B * S, d)
    with jax.named_scope("moe.router"):
        # float32 logits in full (a TPU's default f32 matmul rounds its
        # operands to bf16), so near-tied experts are chosen as the
        # published float32 router chooses them
        gates, eidx, aux = _route(xf, p["router"], cfg,
                                  jax.lax.Precision.HIGHEST)
        mine = eidx[..., None] == jnp.arange(lo, hi)       # [T, k, E_held]
        gate = jnp.sum(jnp.where(mine, gates[..., None], 0.0), axis=1)
        routed = jnp.sum(mine, axis=(1, 2), dtype=jnp.int32)   # [T]
        if lanes is not None:
            routed = routed * jnp.repeat(lanes, S).astype(jnp.int32)
        pairs = jnp.sum(routed)
    with jax.named_scope("moe.experts"):
        h = swiglu(jnp.einsum("td,edf->etf", xf, p["w_gate"]),
                   jnp.einsum("td,edf->etf", xf, p["w_up"]))
        h = (h.astype(jnp.float32) * gate.T[:, :, None]).astype(x.dtype)
        y = jnp.einsum("etf,efd->td", h, p["w_down"],
                       preferred_element_type=jnp.float32)
    if "shared" in p:
        with jax.named_scope("moe.shared"):
            y = y + mlp_forward(p["shared"], xf).astype(jnp.float32)
    return y.astype(x.dtype).reshape(B, S, d), aux, pairs
