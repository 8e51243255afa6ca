"""The published keys of an ``olmo_hybrid`` ``config.json`` (Olmo-Hybrid-7B)
under the names the program's ``TransformerConfig`` takes.

What the program does not compute is refused here, not passed over: a rotary
base (``rope_parameters.rope_theta`` other than null: the program's full layers
of a pattern carry no positional encoding), attention biases, an activation
other than SiLU, fewer key heads than value heads in the linear layers, a layer
type other than ``linear_attention`` / ``full_attention``.

What no key carries and the family's public modelling code states (the
configuration's ``assumed`` lists each): a norm on each branch's output and none
on its input, the RMSNorm of queries and keys over the whole projection. They
are this architecture's, so they are switched on here.

A program whose ``TransformerConfig`` lacks a field this architecture needs (a
commit from before the linear layers) is refused in the driver process, at once
and with a non-zero exit, instead of inside a replica that Serve would start
again and again: the fields are read from the source of
``ray_tpu/models/transformer.py``, because this process must never import jax.
"""

from __future__ import annotations

import ast
import os

FIXED = {"attention_bias": False, "hidden_act": "silu", "rope_parameters": {"rope_theta": None}}
KINDS = {"linear_attention": "linear", "full_attention": "full"}


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
    if cfg["linear_num_key_heads"] != cfg["linear_num_value_heads"]:
        raise ValueError("linear_num_key_heads != linear_num_value_heads: the program has one head count a linear layer")
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
        d_ff=cfg["intermediate_size"],
        norm_eps=cfg["rms_norm_eps"],
        tie_embeddings=cfg["tie_word_embeddings"],
        dtype=cfg["torch_dtype"],
        param_dtype=param_dtype,
        max_seq_len=max_seq_len,
        layer_kinds=[KINDS[t] for t in types],
        pre_norms=False,
        post_norms=True,
        qk_norm_whole=True,
        linear_heads=cfg["linear_num_value_heads"],
        linear_key_dim=cfg["linear_key_head_dim"],
        linear_value_dim=cfg["linear_value_head_dim"],
        linear_conv=cfg["linear_conv_kernel_dim"],
        linear_neg_eigval=cfg["linear_allow_neg_eigval"],
    )
    lacking = sorted(set(model) - _program_fields())
    if lacking:
        raise NotImplementedError(
            f"this program's TransformerConfig has no {', '.join(lacking)}: it cannot run "
            "linear-attention layers, whose recurrent state a serving slot keeps beside the paged cache"
        )
    return model
