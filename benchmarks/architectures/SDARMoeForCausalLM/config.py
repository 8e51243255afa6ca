"""The published keys of an ``sdar_moe`` ``config.json`` (SDAR-30B-A3B-Chat) under
the names the program's ``TransformerConfig`` takes.

Every layer is QK-normed GQA (``head_dim`` apart from the quotient, rotary over
the whole head at ``rope_theta``, no window) and then ``num_experts`` routed
experts of width ``moe_intermediate_size``, softmax top-``num_experts_per_tok``
with ``norm_topk_prob``, no shared expert and no leading dense layer; the head
is untied. The model is GENERATED by diffusion over blocks: ``block_length``
positions a block, ``mask_token_id`` at the unknown ones (the configuration's
``assumed`` says where those two come from: the catalog's row does not carry
them), and how many denoising passes a block takes is the deployment's
(``deployment.engine.denoising_steps``).

What the program does not compute is refused here, not passed over: a sliding
window (``use_sliding_window``), a layer that is not routed (``mlp_only_layers``,
``decoder_sparse_step`` other than 1), un-normalised top-k weights, a bias on
the attention projections, a scaled rotary, tied embeddings.

A program whose ``TransformerConfig`` lacks a field this architecture needs (a
commit from before generation by diffusion over blocks) is refused in the
driver process, at once and with a non-zero exit, instead of inside a replica
that Serve would start again and again: the fields are read from the source of
``ray_tpu/models/transformer.py``, because this process must never import jax.
"""

from __future__ import annotations

import ast
import os

FIXED = {
    "use_sliding_window": False, "norm_topk_prob": True, "attention_bias": False, "decoder_sparse_step": 1,
    "mlp_only_layers": [], "tie_word_embeddings": False, "rope_scaling": None,
}


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
    if cfg["block_length"] < 1 or not 0 <= cfg["mask_token_id"] < cfg["vocab_size"]:
        raise ValueError(f"block_length {cfg['block_length']} with mask_token_id {cfg['mask_token_id']}: a block and an id of the vocabulary")
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
        qk_norm=True,
        num_experts=cfg["num_experts"],
        experts_per_token=cfg["num_experts_per_tok"],
        d_expert=cfg["moe_intermediate_size"],
        router_score="softmax",
        router_bias=False,
        block_diffusion=cfg["block_length"],
        mask_token_id=cfg["mask_token_id"],
    )
    lacking = sorted(set(model) - _program_fields())
    if lacking:
        raise NotImplementedError(
            f"this program's TransformerConfig has no {', '.join(lacking)}: it cannot generate by diffusion over "
            "blocks (attention two-way inside a block, passes that feed a block a row and commit it later)"
        )
    return model
