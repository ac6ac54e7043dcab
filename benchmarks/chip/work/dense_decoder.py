"""The work a plain forward of a dense decoder needs, from its published
sizes alone: operations and bytes, whatever program computes it.

`model` is the `model` block of a configuration file (the published
config's keys).  Counts:

- a token's operations: 2 per weight of every projection it passes
  (q, k, v, o, the three MLP matrices, the LM head over the vocabulary),
  plus attention, 4 * heads * head_dim per position it attends over
  (q.k and p.v), per layer;
- a decode step's bytes: every layer weight and the LM head once, the
  embedding rows of the batch, and the live keys and values of the
  lanes that decode, at the logical head_dim, read once, plus the new
  key and value each lane writes.  Weights and cache in the
  configuration's dtype.
"""
from __future__ import annotations

from typing import Dict, Iterable, Tuple

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def _dims(m: Dict):
    return (m["num_hidden_layers"], m["hidden_size"],
            m["num_attention_heads"], m["num_key_value_heads"],
            m["head_dim"], m["intermediate_size"], m["vocab_size"])


def layer_matmul_params(m: Dict) -> int:
    L, d, H, KV, hd, F, V = _dims(m)
    return d * H * hd + 2 * d * KV * hd + H * hd * d + 3 * d * F


def weight_bytes(m: Dict) -> int:
    """Layer weights (projections and both norms), the final norm and the
    LM head over the vocabulary."""
    L, d, H, KV, hd, F, V = _dims(m)
    per = layer_matmul_params(m) + 2 * d
    return DTYPE_BYTES[m["torch_dtype"]] * (L * per + d + d * V)


def kv_bytes_per_position(m: Dict) -> int:
    L, d, H, KV, hd, F, V = _dims(m)
    return DTYPE_BYTES[m["torch_dtype"]] * L * 2 * KV * hd


def token_flops(m: Dict, span: int) -> int:
    """One token through the model, attending over `span` positions (its
    own included)."""
    L, d, H, KV, hd, F, V = _dims(m)
    if m.get("sliding_window"):
        span = min(span, m["sliding_window"])
    return 2 * (L * layer_matmul_params(m) + d * V) + L * 4 * H * hd * span


def decode_step(m: Dict, spans: Iterable[int]) -> Tuple[int, int]:
    """(operations, bytes) of one decode step whose lanes attend over
    `spans` positions each (the token being decoded included)."""
    spans = [min(s, m["sliding_window"]) if m.get("sliding_window") else s
             for s in spans]
    flops = sum(token_flops(m, s) for s in spans)
    esize = DTYPE_BYTES[m["torch_dtype"]]
    kv = kv_bytes_per_position(m)
    nbytes = (weight_bytes(m) + len(spans) * m["hidden_size"] * esize
              + kv * sum(s - 1 for s in spans) + kv * len(spans))
    return flops, nbytes


def least_seconds(flops: int, nbytes: int, peaks: Dict) -> float:
    """The least time the chip could take: the larger of operations over
    peak FLOP/s and bytes over peak bandwidth."""
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])
