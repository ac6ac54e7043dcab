"""The serving harness's adapter for DeepSeek-V2 configurations (`family`
"deepseek_v2"): the program's `ModelConfig` built from the published
config's keys and this chip's share of the experts (`held_experts`, out
of the router's `published` count), and the reference's names for the
arrays of the program's parameter tree (reference/deepseek_v2.py).

A program without the latent-attention, YaRN and held-experts fields of
`ModelConfig` fails here, at set-up, before any weights are drawn."""
from __future__ import annotations

from typing import Any, Dict


def model_config(cfg: Dict[str, Any]):
    """The program's ModelConfig for a configuration file."""
    from repro.configs.base import MLAConfig, ModelConfig, MoEConfig, YaRN
    if cfg["q_lora_rank"] is not None:
        raise ValueError("q compression (q_lora_rank) is not supported")
    if cfg["topk_method"] != "greedy" or cfg["scoring_func"] != "softmax" \
            or cfg["routed_scaling_factor"] != 1:
        raise ValueError("only softmax, greedy top-k, unscaled routing")
    lo, hi = cfg["held_experts"]
    if hi - lo != cfg["n_routed_experts"]:
        raise ValueError("held_experts must hold n_routed_experts experts")
    rs = cfg["rope_scaling"]
    return ModelConfig(
        name=cfg["name"], family="moe",
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["moe_intermediate_size"], vocab_size=cfg["vocab_size"],
        rope_theta=float(cfg["rope_theta"]), norm_eps=cfg["rms_norm_eps"],
        tie_embeddings=cfg["tie_word_embeddings"], dtype=cfg["torch_dtype"],
        moe=MoEConfig(num_experts=cfg["published"]["n_routed_experts"],
                      top_k=cfg["num_experts_per_tok"],
                      num_shared=cfg["n_shared_experts"],
                      d_ff=cfg["moe_intermediate_size"],
                      first_k_dense=cfg["first_k_dense_replace"],
                      dense_d_ff=cfg["intermediate_size"],
                      norm_topk_prob=cfg["norm_topk_prob"],
                      held=(lo, hi)),
        mla=MLAConfig(kv_lora_rank=cfg["kv_lora_rank"],
                      qk_nope_head_dim=cfg["qk_nope_head_dim"],
                      qk_rope_head_dim=cfg["qk_rope_head_dim"],
                      v_head_dim=cfg["v_head_dim"]),
        rope_scaling=YaRN(
            factor=float(rs["factor"]),
            original_max_position_embeddings=rs[
                "original_max_position_embeddings"],
            beta_fast=float(rs["beta_fast"]),
            beta_slow=float(rs["beta_slow"]),
            mscale=float(rs["mscale"]),
            mscale_all_dim=float(rs["mscale_all_dim"])))


ATTN = ("wq", "wkv_a", "kv_norm", "wkv_b", "wo")


def reference_weights(params) -> Dict[str, Any]:
    """The reference's names for the same arrays: `dense` for the first
    first_k_dense_replace layers, `moe` for the rest."""
    def attn(s):
        return dict({"attn_norm": s["norm1"], "mlp_norm": s["norm2"]},
                    **{k: s["attn"][k] for k in ATTN})
    d, m = params["stage0"], params["stage1"]
    return {"embed": params["embed"], "lm_head": params["lm_head"],
            "final_norm": params["final_norm"],
            "dense": dict(attn(d), **{k: d["mlp"][k] for k in
                                      ("w_gate", "w_up", "w_down")}),
            "moe": dict(attn(m), router=m["moe"]["router"],
                        w_gate=m["moe"]["w_gate"], w_up=m["moe"]["w_up"],
                        w_down=m["moe"]["w_down"],
                        shared_gate=m["moe"]["shared"]["w_gate"],
                        shared_up=m["moe"]["shared"]["w_up"],
                        shared_down=m["moe"]["shared"]["w_down"])}
