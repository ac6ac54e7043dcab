"""Config system for the Vortex-JAX framework.

Every assigned architecture is a `ModelConfig`; every assigned input shape is
a `ShapeConfig`.  The cross product (arch x shape) defines the dry-run /
roofline cells.  Configs are frozen dataclasses so they hash and can key
caches.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Optional, Tuple


# ---------------------------------------------------------------------------
# Model configuration
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class MoEConfig:
    num_experts: int = 0          # routed experts
    top_k: int = 0
    num_shared: int = 0           # shared (always-on) experts (DeepSeek-MoE)
    d_ff: int = 0                 # per-expert hidden size (fine-grained)
    first_k_dense: int = 0        # first K layers use a dense FFN instead
    dense_d_ff: int = 0           # hidden size of those dense layers
    capacity_factor: float = 1.25
    router_aux_coef: float = 0.01
    norm_topk_prob: bool = True   # renormalise the top-k gates to sum 1
    # experts [lo, hi) of num_experts that this chip holds, a strict
    # slice: the layer routes over all of them, computes its held
    # experts' part for every token routed there (dropless) and leaves
    # out the rest, as one chip's share under expert parallelism
    # (moe.held_forward).  () = every expert here, with the
    # capacity-based dispatch (moe.moe_forward).
    held: Tuple[int, ...] = ()

    def __post_init__(self):
        if self.held:
            lo, hi = self.held
            assert 0 <= lo < hi <= self.num_experts and \
                hi - lo < self.num_experts, \
                f"held={self.held} is no strict slice of " \
                f"{self.num_experts} experts; () holds every expert"

    @property
    def n_held(self) -> int:
        return self.held[1] - self.held[0] if self.held else self.num_experts


@dataclass(frozen=True)
class MLAConfig:
    """Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434): K and
    V come from one latent of `kv_lora_rank` per position, which is what
    the cache holds, beside one rotary key of `qk_rope_head_dim` shared
    by every head.  kv_lora_rank 0 = ordinary attention."""
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0


@dataclass(frozen=True)
class YaRN:
    """YaRN rotary scaling (arXiv:2309.00071) with DeepSeek-V2's
    `rope_scaling` keys: frequencies interpolated by `factor` below the
    correction range set by beta_fast / beta_slow, and the attention
    temperature mscale(factor, mscale_all_dim) squared folded into the
    softmax scale."""
    factor: float
    original_max_position_embeddings: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


@dataclass(frozen=True)
class SSMConfig:
    d_state: int = 0              # Mamba2 SSM state size
    d_conv: int = 4               # depthwise causal conv width
    head_dim: int = 64            # SSD head dim (P)
    expand: int = 2               # d_inner = expand * d_model
    chunk: int = 256              # SSD chunk length


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                   # dense | moe | ssm | hybrid | vlm | audio | xlstm
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: Optional[int] = None
    qkv_bias: bool = False
    sliding_window: Optional[int] = None   # SWA window (h2o-danube)
    rope_theta: float = 10_000.0
    norm_eps: float = 1e-5
    tie_embeddings: bool = False
    moe: MoEConfig = field(default_factory=MoEConfig)
    mla: MLAConfig = field(default_factory=MLAConfig)
    rope_scaling: Optional[YaRN] = None    # MLA's rotary key only
    ssm: SSMConfig = field(default_factory=SSMConfig)
    # hybrid (zamba2): one weight-shared attention block applied every
    # `attn_every` mamba blocks.
    attn_every: int = 0
    # xlstm: block kinds, cycled over layers ('m' = mLSTM, 's' = sLSTM)
    xlstm_pattern: Tuple[str, ...] = ()
    # encoder-decoder (whisper)
    is_encoder_decoder: bool = False
    encoder_layers: int = 0
    encoder_ctx: int = 1500       # whisper: 30s of audio at 50 fps
    # vlm: number of prepended patch-embedding tokens provided by the
    # (stubbed) vision frontend.
    num_patch_tokens: int = 0
    dtype: str = "bfloat16"

    # ---- derived ---------------------------------------------------------
    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim if self.head_dim is not None else self.d_model // self.num_heads

    @property
    def is_moe(self) -> bool:
        return self.moe.num_experts > 0

    @property
    def is_mla(self) -> bool:
        return self.mla.kv_lora_rank > 0

    @property
    def is_attention_free(self) -> bool:
        return self.family == "xlstm"

    @property
    def supports_long_context(self) -> bool:
        """Sub-quadratic decode path exists (SSM state, recurrence, or SWA)."""
        return (self.family in ("ssm", "hybrid", "xlstm")
                or self.sliding_window is not None)

    def param_count(self) -> int:
        """Total parameters (exact, mirrors the builders in models/)."""
        from repro.models.api import count_params_analytic
        return count_params_analytic(self)

    def active_param_count(self) -> int:
        from repro.models.api import count_params_analytic
        return count_params_analytic(self, active_only=True)

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)


# ---------------------------------------------------------------------------
# Input-shape configuration
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str          # train | prefill | decode

    @property
    def tokens(self) -> int:
        return self.seq_len * self.global_batch


TRAIN_4K = ShapeConfig("train_4k", seq_len=4_096, global_batch=256, kind="train")
PREFILL_32K = ShapeConfig("prefill_32k", seq_len=32_768, global_batch=32, kind="prefill")
DECODE_32K = ShapeConfig("decode_32k", seq_len=32_768, global_batch=128, kind="decode")
LONG_500K = ShapeConfig("long_500k", seq_len=524_288, global_batch=1, kind="decode")

ALL_SHAPES = (TRAIN_4K, PREFILL_32K, DECODE_32K, LONG_500K)
SHAPES_BY_NAME = {s.name: s for s in ALL_SHAPES}


def applicable_shapes(cfg: ModelConfig) -> Tuple[ShapeConfig, ...]:
    """The shape cells that are well-defined for an architecture.

    long_500k needs a sub-quadratic decode path (SSM / recurrence / SWA);
    pure full-attention archs skip it (documented in DESIGN.md
    Arch-applicability).
    """
    out = []
    for s in ALL_SHAPES:
        if s.name == "long_500k" and not cfg.supports_long_context:
            continue
        out.append(s)
    return tuple(out)


# ---------------------------------------------------------------------------
# Training hyperparameters (substrate defaults; used by examples and the
# end-to-end driver, not by the dry-run)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class TrainConfig:
    learning_rate: float = 3e-4
    warmup_steps: int = 100
    total_steps: int = 1_000
    weight_decay: float = 0.1
    beta1: float = 0.9
    beta2: float = 0.95
    eps: float = 1e-8
    grad_clip: float = 1.0
    microbatch: Optional[int] = None   # grad-accum microbatch (None = off)
    remat: str = "full"                # full | dots | none
    seed: int = 0
    # fault tolerance
    checkpoint_dir: Optional[str] = None
    checkpoint_every: int = 100
    keep_checkpoints: int = 3
    # skip the optimizer update when the global grad norm is non-finite
    # or exceeds this threshold (0.0 = spike skipping disabled; non-finite
    # grads are still applied as-is when disabled, preserving old behavior)
    grad_skip_threshold: float = 0.0
    # gradient compression across the pod (DP) axis
    grad_compression: str = "none"     # none | int8
