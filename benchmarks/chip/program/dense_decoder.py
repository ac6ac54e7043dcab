"""The serving harness's adapter for a dense decoder-only configuration
(`family` "dense_decoder"): the program's `ModelConfig` built from the
published config's keys, and the reference's flat names for the arrays
of the program's parameter tree (reference/dense_decoder.py)."""
from __future__ import annotations

from typing import Any, Dict


def model_config(cfg: Dict[str, Any]):
    """The program's ModelConfig for a configuration file."""
    from repro.configs.base import ModelConfig
    return ModelConfig(
        name=cfg["name"], family="dense",
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        head_dim=cfg["head_dim"], sliding_window=cfg.get("sliding_window"),
        rope_theta=cfg["rope_theta"], norm_eps=cfg["rms_norm_eps"],
        tie_embeddings=cfg["tie_word_embeddings"], dtype=cfg["torch_dtype"])


def reference_weights(params) -> Dict[str, Any]:
    """The reference's flat names for the same arrays."""
    s = params["stage1"]
    return {"embed": params["embed"], "lm_head": params["lm_head"],
            "final_norm": params["final_norm"],
            "attn_norm": s["norm1"], "mlp_norm": s["norm2"],
            "wq": s["attn"]["wq"], "wk": s["attn"]["wk"],
            "wv": s["attn"]["wv"], "wo": s["attn"]["wo"],
            "w_gate": s["mlp"]["w_gate"], "w_up": s["mlp"]["w_up"],
            "w_down": s["mlp"]["w_down"]}
