"""The published keys of an ``afmoe`` ``config.json`` (Trinity-Mini) under the
names the program's ``TransformerConfig`` takes.

What the program does not compute is refused here, not passed over: grouped
routing (``n_group``, ``topk_group``, ``num_limited_groups``,
``num_expert_groups`` other than 1), a score function other than the sigmoid,
un-normalised top-k weights (``route_norm`` false), a rope scaling, a layer
type other than ``sliding_attention`` / ``full_attention``. ``use_grouped_mm``
and ``load_balance_coeff`` say how the published code multiplies and how it was
trained, not what it computes: they are carried and not read.

What no key carries and the public modelling code states (the configuration's
``assumed`` lists each): the gate on the attention output, the RMSNorm of
queries and keys over a head, the second norm of each branch, rotary in the
window layers only. They are this architecture's, so they are switched on here.

A program whose ``TransformerConfig`` lacks a field this architecture needs (a
commit from before the layer pattern) is refused in the driver process, at
once, instead of inside a replica that Serve would start again and again: the
fields are read from the source of ``ray_tpu/models/transformer.py``, because
this process must never import jax.
"""

from __future__ import annotations

import ast
import os

FIXED = {
    "n_group": 1, "topk_group": 1, "num_limited_groups": 1, "num_expert_groups": 1,
    "score_func": "sigmoid", "route_norm": True, "rope_scaling": None, "hidden_act": "silu",
}
KINDS = {"sliding_attention": "window", "full_attention": "full"}


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
    types = cfg["layer_types"]
    unknown = sorted(set(types) - set(KINDS))
    if unknown or len(types) != cfg["num_hidden_layers"]:
        raise ValueError(
            f"layer_types names {len(types)} layers for num_hidden_layers = {cfg['num_hidden_layers']}"
            + (f", of kinds the program has not: {unknown}" if unknown else "")
        )
    model = dict(
        vocab_size=cfg["vocab_size"],
        d_model=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"],
        rope_theta=float(cfg["rope_theta"]),
        norm_eps=cfg["rms_norm_eps"],
        tie_embeddings=cfg["tie_word_embeddings"],
        dtype=cfg["torch_dtype"],
        param_dtype=param_dtype,
        max_seq_len=max_seq_len,
        sliding_window=cfg["sliding_window"],
        layer_kinds=[KINDS[t] for t in types],
        attn_gate=True,
        qk_norm=True,
        post_norms=True,
        embed_multiplier=float(cfg["hidden_size"]) ** 0.5 if cfg["mup_enabled"] else 1.0,
        num_experts=cfg["num_experts"],
        experts_per_token=cfg["num_experts_per_tok"],
        d_expert=cfg["moe_intermediate_size"],
        num_shared_experts=cfg["num_shared_experts"],
        routed_scaling_factor=cfg["route_scale"],
        first_dense_layers=cfg["num_dense_layers"],
    )
    lacking = sorted(set(model) - _program_fields())
    if lacking:
        raise NotImplementedError(
            f"this program's TransformerConfig has no {', '.join(lacking)}: it cannot run "
            "a layer pattern over a cache with two kinds of layer, nor gated QK-normed attention"
        )
    return model
