"""The published keys of a ``nemotron_h`` ``config.json`` (Nemotron-3-Nano-30B-A3B)
under the names the program's ``TransformerConfig`` takes.

``hybrid_override_pattern`` names a block a character: ``M`` a Mamba-2
state-space block, ``E`` an experts block, ``*`` an attention block; every block
is ONE mixer behind one RMSNorm. ``-`` (a dense MLP block, which other models of
the family have) is refused: the program has none.

The configuration is one chip's share of an expert-parallel deployment
(``deployment.expert_parallel``: ``chips`` that share every experts block, this
one the ``index``-th): ``n_routed_experts`` counts the experts HELD here, the
router is as wide as all of them (``published.n_routed_experts``, which must be
``chips`` times as many), and the program is told its share
(``TransformerConfig.expert_share``). ``vocab_size`` is the slice of the
vocabulary held here; the program needs nothing more for it than the number.

What the program does not compute is refused here, not passed over: a bias on
a projection, grouped routing (``n_group`` / ``topk_group`` other than 1),
un-normalised top-k weights, an expert activation other than ``relu2``, a
Mamba activation other than SiLU, a convolution without bias, a time-step
initialisation other than the program's (``transformer._MAMBA_DT_RANGE``,
``_MAMBA_DT_FLOOR``), a sub-chunk other than ``ops/ssm.SUB_CHUNK``.
``rope_theta`` and ``partial_rotary_factor`` are carried and not read: the
family's attention blocks have no positional encoding (``assumed``). So are
``expand`` (the inner width is ``mamba_num_heads x mamba_head_dim``),
``use_mamba_kernels``, ``num_logits_to_keep``, ``residual_in_fp32`` (false) and
``rescale_prenorm_residual`` (how the published weights were initialised).

A program whose ``TransformerConfig`` lacks a field this architecture needs (a
commit from before state-space blocks and a held share of the experts) is
refused in the driver process, at once and with a non-zero exit, instead of
inside a replica that Serve would start again and again: the fields are read
from the source of ``ray_tpu/models/transformer.py``, because this process must
never import jax.
"""

from __future__ import annotations

import ast
import os

FIXED = {
    "attention_bias": False, "mamba_proj_bias": False, "mlp_bias": False, "use_bias": False, "use_conv_bias": True,
    "n_group": 1, "topk_group": 1, "norm_topk_prob": True, "mamba_hidden_act": "silu", "mlp_hidden_act": "relu2",
    "time_step_min": 0.001, "time_step_max": 0.1, "time_step_floor": 0.0001, "chunk_size": 128,
    "sliding_window": None, "residual_in_fp32": False,
}
KINDS = {"M": "mamba", "E": "experts", "*": "full"}


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
    """(index, of): this chip's share of every experts block."""
    ep = cfg["deployment"]["expert_parallel"]
    return int(ep["index"]), int(ep["chips"])


def model_config(cfg: dict, max_seq_len: int, param_dtype: str) -> dict:
    for key, value in FIXED.items():
        if cfg[key] != value:
            raise ValueError(f"{key} = {cfg[key]!r}: the program computes {value!r} only")
    pattern = cfg["hybrid_override_pattern"]
    unknown = sorted(set(pattern) - set(KINDS))
    if unknown or len(pattern) != cfg["num_hidden_layers"]:
        raise ValueError(
            f"hybrid_override_pattern names {len(pattern)} blocks for num_hidden_layers = {cfg['num_hidden_layers']}"
            + (f", of kinds the program has not: {unknown}" if unknown else "")
        )
    index, of = expert_share(cfg)
    experts = cfg["n_routed_experts"] * of
    if experts != cfg["published"]["n_routed_experts"]:
        raise ValueError(
            f"n_routed_experts = {cfg['n_routed_experts']} held on each of {of} chips is not the published "
            f"{cfg['published']['n_routed_experts']}"
        )
    shared, width = cfg["n_shared_experts"] * cfg["moe_shared_expert_intermediate_size"], cfg["moe_intermediate_size"]
    if shared % width:
        raise ValueError("the shared expert's width is no multiple of a routed expert's")
    model = dict(
        vocab_size=cfg["vocab_size"],
        d_model=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"],
        norm_eps=cfg["layer_norm_epsilon"],
        tie_embeddings=cfg["tie_word_embeddings"],
        dtype=cfg["torch_dtype"],
        param_dtype=param_dtype,
        max_seq_len=max_seq_len,
        layer_kinds=[KINDS[c] for c in pattern],
        mamba_heads=cfg["mamba_num_heads"],
        mamba_head_dim=cfg["mamba_head_dim"],
        ssm_state=cfg["ssm_state_size"],
        ssm_groups=cfg["n_groups"],
        mamba_conv=cfg["conv_kernel"],
        num_experts=experts,
        experts_per_token=cfg["num_experts_per_tok"],
        d_expert=width,
        # One shared expert of twice a routed expert's width is, matrix for matrix, two of that width side by side.
        num_shared_experts=shared // width,
        routed_scaling_factor=cfg["routed_scaling_factor"],
        expert_activation=cfg["mlp_hidden_act"],
        expert_share=[index, of],
    )
    lacking = sorted(set(model) - _program_fields())
    if lacking:
        raise NotImplementedError(
            f"this program's TransformerConfig has no {', '.join(lacking)}: it cannot run Mamba-2 state-space "
            "blocks, blocks of a single mixer, experts without a gate matrix or a held share of the experts"
        )
    return model
