"""The published keys of a ``xing4_0`` ``config.json`` (Xing4.0-29B-A4B) under
the names the program's ``TransformerConfig`` takes.

What the program does not compute is refused here, not passed over: grouped
routing (``n_group`` / ``topk_group`` other than 1), un-normalised top-k
weights, another score than the sigmoid, experts on other layers than all
behind the leading dense ones (``moe_layer_freq``), a rope scaling that is not
YaRN's, attention biases. The multi-token-prediction layer
(``num_nextn_predict_layers``) is a draft head beyond ``num_hidden_layers``; a
deployment without self-drafting does not run it, and nothing of it is built
(the configuration's ``left_out`` says so).

A program whose ``TransformerConfig`` lacks a field this architecture needs (a
commit from before hyper-connections and YaRN: ``hc_mult``, ``rope_scaling``)
is refused in the driver process, at once, instead of inside a replica that
Serve would start again and again: the fields are read from the source of
``ray_tpu/models/transformer.py``, because this process must never import jax.
"""

from __future__ import annotations

import ast
import os

FIXED = {
    "n_group": 1, "topk_group": 1, "norm_topk_prob": True, "topk_method": "noaux_tc", "scoring_func": "sigmoid",
    "moe_layer_freq": 1, "ep_size": 1, "attention_bias": False, "hidden_act": "silu",
}
YARN = ("factor", "original_max_position_embeddings", "beta_fast", "beta_slow", "mscale", "mscale_all_dim")


def _program_fields() -> set:
    import ray_tpu

    path = os.path.join(os.path.dirname(os.path.abspath(ray_tpu.__file__)), "models", "transformer.py")
    with open(path) as f:
        tree = ast.parse(f.read())
    for node in tree.body:
        if isinstance(node, ast.ClassDef) and node.name == "TransformerConfig":
            return {s.target.id for s in node.body if isinstance(s, ast.AnnAssign)}
    raise ValueError(f"{path} defines no TransformerConfig")


def model_config(cfg: dict, max_seq_len: int, param_dtype: str) -> dict:
    for key, value in FIXED.items():
        if cfg[key] != value:
            raise ValueError(f"{key} = {cfg[key]!r}: the program computes {value!r} only")
    scaling = cfg["rope_scaling"]
    if scaling["type"] != "yarn" or set(scaling) != {"type", *YARN}:
        raise ValueError(f"rope_scaling = {scaling!r}: the program computes type 'yarn' with {', '.join(YARN)} only")
    model = dict(
        vocab_size=cfg["vocab_size"],
        d_model=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"],
        rope_theta=float(cfg["rope_theta"]),
        rope_scaling={key: float(scaling[key]) for key in YARN},
        norm_eps=cfg["rms_norm_eps"],
        tie_embeddings=cfg["tie_word_embeddings"],
        dtype=cfg["torch_dtype"],
        param_dtype=param_dtype,
        max_seq_len=max_seq_len,
        q_lora_rank=cfg["q_lora_rank"],
        kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"],
        num_experts=cfg["n_routed_experts"],
        experts_per_token=cfg["num_experts_per_tok"],
        d_expert=cfg["moe_intermediate_size"],
        num_shared_experts=cfg["n_shared_experts"],
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        first_dense_layers=cfg["first_k_dense_replace"],
        hc_mult=cfg["hc_mult"],
        hc_sinkhorn_iters=cfg["hc_sinkhorn_iters"],
        hc_eps=cfg["hc_eps"],
        hc_res_clamp=[float(cfg["mhc_h_res_clamp_min"]), float(cfg["mhc_h_res_clamp_max"])],
    )
    lacking = sorted(set(model) - _program_fields())
    if lacking:
        raise NotImplementedError(
            f"this program's TransformerConfig has no {', '.join(lacking)}: it cannot run "
            "a residual path of several streams under YaRN-scaled latent attention"
        )
    return model
