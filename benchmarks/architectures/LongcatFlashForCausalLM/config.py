"""The published keys of a ``longcat_flash`` ``config.json`` (LongCat-Flash-Omni's
language model) under the names the program's ``TransformerConfig`` takes.

``num_layers`` counts DOUBLE layers: each is two latent-attention sub-layers,
two dense FFNs of ``ffn_hidden_size`` and one branch of routed experts of
``expert_ffn_hidden_size`` joined a sub-layer late (``shortcut_moe``). The
router has ``n_routed_experts`` columns for experts and ``zero_expert_num`` more
for identity experts; ``moe_topk`` of all of them are picked a token.

The configuration is one chip's share of an expert-parallel deployment
(``deployment.expert_parallel``: ``chips`` that share every layer's experts, this
one the ``index``-th): ``n_routed_experts`` counts the experts HELD here, the
router is as wide as all of them (``published.n_routed_experts``, which must be
``chips`` times as many) and the identities, and the program is told its share
(``TransformerConfig.expert_share``). ``vocab_size`` is the slice of the
vocabulary held here; the program needs nothing more for it than the number.

What the program does not compute is refused here, not passed over: a bias on
a projection, a zero expert that is no identity, an attention other than latent
attention, a rope scaling. ``max_position_embeddings`` is carried and not read
(rotary at ``rope_theta``, no scaling, is the same at every length). The Omni
towers are not among the catalog row's keys and nothing of them is built (the
configuration's ``left_out``).

A program whose ``TransformerConfig`` lacks a field this architecture needs (a
commit from before the double layer and identity experts) is refused in the
driver process, at once and with a non-zero exit, instead of inside a replica
that Serve would start again and again: the fields are read from the source of
``ray_tpu/models/transformer.py``, because this process must never import jax.
"""

from __future__ import annotations

import ast
import os

FIXED = {"attention_bias": False, "zero_expert_type": "identity", "attention_method": "MLA"}


def _program_fields() -> set:
    import ray_tpu

    path = os.path.join(os.path.dirname(os.path.abspath(ray_tpu.__file__)), "models", "transformer.py")
    with open(path) as f:
        tree = ast.parse(f.read())
    for node in tree.body:
        if isinstance(node, ast.ClassDef) and node.name == "TransformerConfig":
            return {s.target.id for s in node.body if isinstance(s, ast.AnnAssign)}
    raise ValueError(f"{path} defines no TransformerConfig")


def expert_share(cfg: dict) -> tuple:
    """(index, of): this chip's share of every layer's experts."""
    ep = cfg["deployment"]["expert_parallel"]
    return int(ep["index"]), int(ep["chips"])


def model_config(cfg: dict, max_seq_len: int, param_dtype: str) -> dict:
    for key, value in FIXED.items():
        if cfg[key] != value:
            raise ValueError(f"{key} = {cfg[key]!r}: the program computes {value!r} only")
    if cfg.get("rope_scaling"):
        raise ValueError(f"rope_scaling = {cfg['rope_scaling']!r}: the program ropes this architecture plainly at rope_theta")
    index, of = expert_share(cfg)
    experts = cfg["n_routed_experts"] * of
    if experts != cfg["published"]["n_routed_experts"]:
        raise ValueError(
            f"n_routed_experts = {cfg['n_routed_experts']} held on each of {of} chips is not the published "
            f"{cfg['published']['n_routed_experts']}"
        )
    model = dict(
        vocab_size=cfg["vocab_size"],
        d_model=cfg["hidden_size"],
        n_layers=cfg["num_layers"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_attention_heads"],
        d_ff=cfg["ffn_hidden_size"],
        rope_theta=float(cfg["rope_theta"]),
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
        mla_scale_q_lora=cfg["mla_scale_q_lora"],
        mla_scale_kv_lora=cfg["mla_scale_kv_lora"],
        shortcut_moe=True,
        num_experts=experts,
        zero_experts=cfg["zero_expert_num"],
        experts_per_token=cfg["moe_topk"],
        d_expert=cfg["expert_ffn_hidden_size"],
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        router_score="softmax",
        router_normalize=False,
        expert_share=[index, of],
    )
    lacking = sorted(set(model) - _program_fields())
    if lacking:
        raise NotImplementedError(
            f"this program's TransformerConfig has no {', '.join(lacking)}: it cannot run the shortcut-connected "
            "double layer, a router with identity experts, chosen weights that are not normalised or latent "
            "attention's two constants"
        )
    return model
