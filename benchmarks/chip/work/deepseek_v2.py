"""The least work of a plain forward of a DeepSeek-V2 configuration on
this chip's share of the experts, from its published sizes alone:
operations and bytes, whatever program computes it.

`model` is a configuration file (the published config's keys, with
`n_routed_experts` the experts held here out of `published`'s count).
Counts:

- a token's operations: 2 per weight of every projection it passes: q,
  the latent and rotary-key projection, and the output projection; the
  absorbed W_UK and W_UV (H * nope * r and H * r * v, per token); the
  dense MLP of the first `first_k_dense_replace` layers; per MoE layer
  the router, the shared experts, and the routed experts at
  num_experts_per_tok * held / published of an expert per token (the
  pairs that land here on average); the LM head.  Attention over the
  latent cache, absorbed: 2 * H * (r + rope) per position for the
  scores and 2 * H * r for the weighted sum, per layer.  (A prefill
  that forms K and V per head needs 2 * H * (nope + rope + v) per
  position instead: 3.2 % less than this count for a token at position
  128, the longest prompt of the benchmark's mix, so prompt tokens are
  counted alike.)
- a decode step's bytes: every layer weight, every held expert's
  weights and the LM head read once, the embedding rows of the batch,
  and the live latent and rotary-key rows of the lanes that decode read
  once, plus the row each lane writes.  Weights and cache in the
  configuration's dtype.
"""
from __future__ import annotations

from typing import Dict, Iterable, Tuple

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def _attn_params(m: Dict) -> int:
    """Per layer: q, latent + rotary key, the latent's up-projection
    (absorbed into q and the output, the same count), the output."""
    d, H, r = m["hidden_size"], m["num_attention_heads"], m["kv_lora_rank"]
    nope, rope, v = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                     m["v_head_dim"])
    return (d * H * (nope + rope) + d * (r + rope) + r * H * (nope + v)
            + H * v * d)


def _expert_params(m: Dict) -> int:
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def _layers(m: Dict) -> Tuple[int, int]:
    """(dense layers, MoE layers)."""
    k = m["first_k_dense_replace"]
    return k, m["num_hidden_layers"] - k


def weight_bytes(m: Dict) -> int:
    """Layer weights (attention, both norms and the latent's norm, the
    dense MLP or the router, shared and held experts), the final norm and
    the LM head over the vocabulary."""
    d = m["hidden_size"]
    nd, nm = _layers(m)
    attn = _attn_params(m) + 2 * d + m["kv_lora_rank"]
    dense = 3 * d * m["intermediate_size"]
    moe = (d * m["published"]["n_routed_experts"]
           + (m["n_shared_experts"] + m["n_routed_experts"])
           * _expert_params(m))
    n = (m["num_hidden_layers"] * attn + nd * dense + nm * moe
         + d + d * m["vocab_size"])
    return DTYPE_BYTES[m["torch_dtype"]] * n


def cache_bytes_per_position(m: Dict) -> int:
    return (DTYPE_BYTES[m["torch_dtype"]] * m["num_hidden_layers"]
            * (m["kv_lora_rank"] + m["qk_rope_head_dim"]))


def token_flops(m: Dict, span: int) -> int:
    """One token through the model, attending over `span` positions (its
    own included)."""
    d, H, r = m["hidden_size"], m["num_attention_heads"], m["kv_lora_rank"]
    nd, nm = _layers(m)
    routed = (m["num_experts_per_tok"] * m["n_routed_experts"]
              / m["published"]["n_routed_experts"])
    moe = (d * m["published"]["n_routed_experts"]
           + m["n_shared_experts"] * _expert_params(m)
           + routed * _expert_params(m))
    per_pos = H * (2 * r + m["qk_rope_head_dim"])
    return int(2 * (m["num_hidden_layers"] * _attn_params(m)
                    + nd * 3 * d * m["intermediate_size"] + nm * moe
                    + d * m["vocab_size"])
               + 2 * m["num_hidden_layers"] * per_pos * span)


def decode_step(m: Dict, spans: Iterable[int]) -> Tuple[int, int]:
    """(operations, bytes) of one decode step whose lanes attend over
    `spans` positions each (the token being decoded included)."""
    spans = list(spans)
    flops = sum(token_flops(m, s) for s in spans)
    esize = DTYPE_BYTES[m["torch_dtype"]]
    row = cache_bytes_per_position(m)
    nbytes = (weight_bytes(m) + len(spans) * m["hidden_size"] * esize
              + row * sum(s - 1 for s in spans) + row * len(spans))
    return flops, nbytes


def least_seconds(flops: int, nbytes: int, peaks: Dict) -> float:
    """The least time the chip could take: the larger of operations over
    peak FLOP/s and bytes over peak bandwidth."""
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])
