"""The published keys of an ``lfm2_moe`` ``config.json`` (LFM2-24B-A2B) under the
names the program's ``TransformerConfig`` takes.

``layer_types`` names a layer ``conv`` (a gated short convolution over
``conv_L_cache`` tokens) or ``full_attention`` (QK-normed GQA, rotary over the
whole head at ``rope_parameters.rope_theta``); every layer is its mixer and then
an MLP, dense in the first ``num_dense_layers`` layers and routed experts behind
them. The width of a head is ``hidden_size / num_attention_heads``: the
published file has no ``head_dim``.

What the program does not compute is refused here, not passed over: a bias on
the convolution (``conv_bias``), un-normalised top-k weights (``norm_topk_prob``
false), a router without its choosing bias (``use_expert_bias`` false: the
program's leaf would be drawn all the same), a ``rope_type`` other than
``default``, a filter shorter than two tokens, a layer type of another name.
What no key carries (the configuration's ``assumed`` lists each): RMSNorm of
queries and keys over a head, the order ``B | C | u`` of the input projection,
no activation in the conv mixer, tied embeddings.

A program whose ``TransformerConfig`` lacks a field this architecture needs (a
commit from before the ``conv`` kind of layer) is refused in the driver process,
at once and with a non-zero exit, instead of inside a replica that Serve would
start again and again: the fields are read from the source of
``ray_tpu/models/transformer.py``, because this process must never import jax.
"""

from __future__ import annotations

import ast
import os

FIXED = {"conv_bias": False, "norm_topk_prob": True, "use_expert_bias": True}
KINDS = {"conv": "conv", "full_attention": "full"}


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
    rope = cfg["rope_parameters"]
    if rope.get("rope_type", "default") != "default":
        raise ValueError(f"rope_parameters.rope_type = {rope['rope_type']!r}: the program ropes these layers plainly ('default') only")
    if cfg["conv_L_cache"] < 2:
        raise ValueError(f"conv_L_cache = {cfg['conv_L_cache']}: a filter over fewer than two tokens carries no row")
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
        head_dim=cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"],
        d_ff=cfg["intermediate_size"],
        rope_theta=float(rope["rope_theta"]),
        norm_eps=cfg["norm_eps"],
        tie_embeddings=cfg["tie_word_embeddings"],
        dtype=cfg["torch_dtype"],
        param_dtype=param_dtype,
        max_seq_len=max_seq_len,
        layer_kinds=[KINDS[t] for t in types],
        conv_cache=cfg["conv_L_cache"],
        full_layers_rope=True,
        qk_norm=True,
        num_experts=cfg["num_experts"],
        experts_per_token=cfg["num_experts_per_tok"],
        d_expert=cfg["moe_intermediate_size"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        first_dense_layers=cfg["num_dense_layers"],
    )
    lacking = sorted(set(model) - _program_fields())
    if lacking:
        raise NotImplementedError(
            f"this program's TransformerConfig has no {', '.join(lacking)}: it cannot run gated short-convolution "
            "layers, nor rope the full layers of a pattern plainly"
        )
    return model
