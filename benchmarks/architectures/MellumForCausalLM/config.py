"""The published keys of a ``mellum`` ``config.json`` (Mellum2-12B-A2.5B) under
the names the program's ``TransformerConfig`` takes.

``layer_types`` names a layer ``sliding_attention`` or ``full_attention`` and
``rope_parameters`` says, BY KIND of layer, how it turns its queries and keys:
the window layers by the plain rotary at ``rope_theta``, the full layers by
YaRN at the same ``rope_theta`` (``factor``, ``original_max_position_embeddings``,
``beta_fast``, ``beta_slow``; ``attention_factor`` multiplies cos and sin).
``mlp_layer_types`` names every layer's MLP ``sparse``: ``num_experts`` routed
SwiGLU experts of ``moe_intermediate_size``, ``num_experts_per_tok`` a token by
softmax probabilities over all of them, divided by their sum over the chosen
(``norm_topk_prob``); no shared expert, no router bias. ``intermediate_size``,
``max_window_layers`` and ``use_sliding_window`` are carried and not read (no
layer is dense; every layer's type is stated).

The configuration is one chip's share of an expert-parallel job
(``deployment.expert_parallel``: ``chips`` that share every layer's experts,
this one the ``index``-th): ``num_experts`` counts the experts HELD here, the
router is as wide as all of them (``published.num_experts``, which must be
``chips`` times as many), and the program is told its share
(``TransformerConfig.expert_share``). ``vocab_size`` is the slice of the
vocabulary held here; the program needs nothing more for it than the number.

What the program does not compute is refused here, not passed over: a bias in
attention, an activation other than SiLU, un-normalised top-k weights, a dense
MLP layer, a rope of another type or of two bases, an ``attention_factor``
other than YaRN's own ``0.1 ln(factor) + 1`` (the program's tables take the
amplitude from ``mscale`` 1 and ``mscale_all_dim`` 0, which is that number).

What no key carries and the lineage's public modelling code states (the
configuration's ``assumed`` lists each): the RMSNorm of queries and keys over
a head's width, softmax scores without a bias, the balance loss and its
coefficient (``deployment.balance_loss_coef``).

A program whose ``TransformerConfig`` lacks a field this architecture needs (a
commit from before the routed experts trained: no score function to state) is
refused in the driver process, at once and with a non-zero exit, instead of
inside a train worker: the fields are read from the source of
``ray_tpu/models/transformer.py``, because this process must never import jax.
"""

from __future__ import annotations

import ast
import math
import os

FIXED = {"attention_bias": False, "hidden_act": "silu", "norm_topk_prob": True, "tie_word_embeddings": False}
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


def expert_share(cfg: dict) -> tuple:
    """(index, of): this chip's share of every layer's experts."""
    ep = cfg["deployment"]["expert_parallel"]
    return int(ep["index"]), int(ep["chips"])


def yarn(cfg: dict) -> dict:
    """The full layers' YaRN numbers under the program's names."""
    full, window = cfg["rope_parameters"]["full_attention"], cfg["rope_parameters"]["sliding_attention"]
    if full["rope_type"] != "yarn" or window["rope_type"] != "default" or full["rope_theta"] != window["rope_theta"]:
        raise ValueError(f"rope_parameters {cfg['rope_parameters']!r}: the program ropes window layers plainly and full layers by YaRN, at one theta")
    if abs(full["attention_factor"] - (0.1 * math.log(full["factor"]) + 1.0)) > 1e-9:
        raise ValueError(f"attention_factor {full['attention_factor']!r}: the program computes 0.1 ln(factor) + 1 only")
    return dict(
        factor=full["factor"], original_max_position_embeddings=full["original_max_position_embeddings"],
        beta_fast=full["beta_fast"], beta_slow=full["beta_slow"], mscale=1.0, mscale_all_dim=0.0,
    )


def model_config(cfg: dict, max_seq_len: int, param_dtype: str) -> dict:
    for key, value in FIXED.items():
        if cfg[key] != value:
            raise ValueError(f"{key} = {cfg[key]!r}: the program computes {value!r} only")
    types, n = cfg["layer_types"], cfg["num_hidden_layers"]
    unknown = sorted(set(types) - set(KINDS))
    if unknown or len(types) != n or cfg["mlp_layer_types"] != ["sparse"] * n:
        raise ValueError(
            f"layer_types names {len(types)} layers and mlp_layer_types {cfg['mlp_layer_types']!r} for num_hidden_layers = {n}"
            + (f", of kinds the program has not: {unknown}" if unknown else "") + ": every layer is sparse here"
        )
    index, of = expert_share(cfg)
    if cfg["num_experts"] * of != cfg["published"]["num_experts"]:
        raise ValueError(
            f"{cfg['num_experts']} experts held by each of {of} chips are not the published {cfg['published']['num_experts']}"
        )
    model = dict(
        vocab_size=cfg["vocab_size"],
        d_model=cfg["hidden_size"],
        n_layers=n,
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"],
        rope_theta=float(cfg["rope_parameters"]["sliding_attention"]["rope_theta"]),
        norm_eps=cfg["rms_norm_eps"],
        tie_embeddings=cfg["tie_word_embeddings"],
        dtype=cfg["torch_dtype"],
        param_dtype=param_dtype,
        max_seq_len=max_seq_len,
        sliding_window=cfg["sliding_window"],
        layer_kinds=[KINDS[t] for t in types],
        rope_scaling=yarn(cfg),
        qk_norm=True,
        num_experts=cfg["published"]["num_experts"],
        experts_per_token=cfg["num_experts_per_tok"],
        d_expert=cfg["moe_intermediate_size"],
        router_score="softmax",
        router_bias=False,
        expert_share=[index, of],
        balance_loss_coef=float(cfg["deployment"]["balance_loss_coef"]),
    )
    lacking = sorted(set(model) - _program_fields())
    if lacking:
        raise NotImplementedError(
            f"this program's TransformerConfig has no {', '.join(lacking)}: it cannot state a router's score function, "
            "and its routed experts, layer pattern and scaled rotary have no training block"
        )
    return model
