"""Flagship model: decoder-only transformer (Llama-family architecture).

Pure-JAX with explicit parameter pytrees and per-leaf logical sharding axes —
the flagship for every parallelism strategy in parallel/ (dp/fsdp/tp/pp/sp/ep)
and the model behind __graft_entry__.py and the benchmark's cells.

TPU-first choices:
- layer parameters are *stacked* [L, ...] so the layer loop is a lax.scan
  (O(1) compile in depth) and pipeline parallelism is just sharding the stack
  over the ``pp`` axis (parallel/pipeline.py)
- attention runs the Pallas flash kernel on TPU (ops/attention.py), ring
  attention over the ``sp`` axis for long context (parallel/ring_attention.py)
- the training block holds q, k and v heads before tokens, ``[B, H or KV, T,
  Dh]``, from the projections' matmuls to ``wo``'s: the layout the flash kernels
  read in place (a head's ``[T, Dh]`` contiguous, tokens on the sublanes). K and
  V stay at ``n_kv_heads`` forward and backward, nothing is transposed between,
  and the rotary swaps a head's halves on the MXU (``_rope_rotate``)
- bf16 activations/params by default; f32 RMSNorm epsilon path and logits
- rotary embeddings, GQA (n_kv_heads <= n_heads), SwiGLU MLP, optional
  mixture-of-experts MLP (parallel/moe.py) sharded over ``ep``
- remat (``jax.checkpoint``) around each layer trades FLOPs for HBM. A layer
  keeps its input and, by a policy over names (``_KEPT_UNDER_REMAT``), what its
  attention core was given and gave back: q, k and v behind the norms and the
  rotary (K and V at ``n_kv_heads``), the flash kernel's output and its
  log-sum-exp: ``B x T x (2 H + 2 KV) x Dh`` values of ``dtype`` and ``B x H x
  T`` float32 a layer, ~2.5x a layer's input for Mistral-7B's widths and ~4x for
  Mellum's; under ``qk_norm`` also q and k as projected, which the norms'
  backward pass reads (``(H + KV) x Dh`` more a token: ~6x for Mellum's). An
  eighth or less of what ``remat=False`` keeps. The backward pass then runs the
  two flash backward kernels on them; the forward kernel, the q/k/v
  projections, their norms and their rotary run once a step. A layer whose
  routed experts run the bounded path (a trained share, ``moe.held_rows``) also
  keeps what ``parallel/moe.py`` names there (``moe.KEPT_OF_A_BOUNDED_BLOCK``):
  the router's scores, its choice and the chosen experts' scores, the sort's
  order and group sizes, and, of the sorted rows under the bound, the two hidden
  products and the down projection's result (``rows x (2 d_expert + d_model)``
  values of ``dtype``: 336 MB a layer for Mellum's share), so that the router,
  the sort and the three grouped matmuls run once a step. The block's other
  norms, ``wo``, a dense MLP (the d_ff-wide rows) and, of the experts, the
  gather of the sorted rows, the matrices' casts and the SiLU product are
  recomputed. One policy for every configuration, sized by the shapes alone.

(The reference has no in-tree model zoo for LLMs — its Train/RLlib models are
torch modules; SURVEY.md §2.3/§5.7. This module is the TPU-native equivalent
of what it delegates to HF/DeepSpeed.)
"""

from __future__ import annotations

import contextlib
import dataclasses
import math
from functools import partial
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.ops.attention import FLASH_LSE, FLASH_OUT, flash_attention, repeat_kv
from ray_tpu.parallel.moe import KEPT_OF_A_BOUNDED_BLOCK


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    d_model: int = 512
    n_layers: int = 4
    n_heads: int = 8
    n_kv_heads: int = 8
    d_ff: int = 1376  # ~8/3 * d_model rounded
    max_seq_len: int = 2048
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    # MoE: 0 = dense MLP; >0 = experts sharded over ep. Without
    # ``experts_per_token`` it is the Switch layer (top-1, two-matrix GELU
    # experts with a capacity: parallel/moe.moe_layer), which trains.
    num_experts: int = 0
    expert_capacity_factor: float = 1.25
    # Dropless routed experts (parallel/moe.routed_experts):
    # ``experts_per_token`` of ``num_experts`` SwiGLU experts of width
    # ``d_expert`` a token, chosen by the router's scores (``router_score``:
    # ``"sigmoid"``, or ``"softmax"`` over all the experts) plus, with
    # ``router_bias``, a bias that chooses and does not weigh (a leaf of its own;
    # nothing here updates it, so it is inference only), their weights normalised
    # and scaled by ``routed_scaling_factor``; ``num_shared_experts`` more of that
    # width see every token (inference only). The first ``first_dense_layers``
    # layers keep the dense MLP of width ``d_ff`` and are stacked apart
    # (``params["dense_layers"]``; inference only). Training adds
    # ``balance_loss_coef`` times the layers' mean balance term to the loss
    # (``parallel/moe.balance_term``; the Switch layer's summed term likewise).
    experts_per_token: int = 0
    d_expert: int = 0
    num_shared_experts: int = 0
    routed_scaling_factor: float = 1.0
    first_dense_layers: int = 0
    router_score: str = "sigmoid"
    router_bias: bool = True
    balance_loss_coef: float = 0.01
    # Latent attention (MLA; inference only), selected by ``kv_lora_rank`` > 0:
    # queries through a ``q_lora_rank`` bottleneck; keys and values expanded
    # per head from one normed latent of ``kv_lora_rank`` a token, beside one
    # rotary key of ``qk_rope_head_dim`` shared by all heads. The cache holds
    # the latent and the rotary key, not keys and values (models/generate.py).
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # Each layer under ``jax.checkpoint``: kept for the backward pass are the layer's input [B, T, D] and the attention
    # core's q, k, v, output and log-sum-exp ((2 H + 2 KV) Dh values of ``dtype`` + H float32 a token: 84 MB a layer at
    # Mistral's 1 x 4096 tokens) and, under ``qk_norm``, q and k as projected ((H + KV) Dh more: 455 MB a layer at
    # Mellum's 2 x 8192); the rest of the layer is recomputed (the module's docstring).
    remat: bool = True
    tie_embeddings: bool = False
    # Mistral-style sliding-window causal attention (0 = full causal):
    # row i attends keys (i-sliding_window, i]. Rides the flash kernel's
    # k-block pruning in training and the decode position mask at
    # inference; not combinable with ring/Ulysses sequence parallelism.
    sliding_window: int = 0
    # A layer pattern: one of ``"window"`` / ``"full"`` a layer, ``n_layers`` of
    # them, or empty for a model whose layers are all alike. A window layer
    # attends within ``sliding_window`` and ropes its queries and keys plainly; a
    # full layer attends over the whole context and carries NO positional
    # encoding (the published ``afmoe`` layer) unless ``rope_scaling`` is set,
    # which then scales the full layers' rotary and theirs alone (the published
    # ``mellum`` layer). ``layer_rope`` is the one place that says so. A paged
    # cache then holds the two kinds apart: the full layers' blocks grow with
    # the row, a window layer holds a ring no longer than the window and a
    # prefill chunk (models/generate.py, serve/llm/engine.py). Training scans
    # whole periods of the pattern, a period's layers unrolled.
    layer_kinds: tuple = ()
    # Width of a head; 0: ``d_model // n_heads`` (``__post_init__`` fills it in).
    head_dim: int = 0
    # What the ``afmoe`` layer adds to the GQA block (inference only), each a
    # flag with its leaves in ``_layer_leaves``: the attention output is
    # multiplied by ``sigmoid(h wg_attn)`` before ``wo``; queries and keys are
    # RMSNorm-ed over the head's width with a learned weight before the rotary;
    # each branch is RMSNorm-ed once more before it is added to the residual.
    attn_gate: bool = False
    qk_norm: bool = False
    post_norms: bool = False
    # The ``olmo`` family's block (inference only): ``pre_norms`` False takes the
    # norm off each branch's INPUT (with ``post_norms`` the layer is ``x +
    # norm(mixer(x))``, ``h + norm(mlp(h))``); ``qk_norm_whole`` norms queries
    # and keys over the whole projection, a learned [H * Dh] and [KV * Dh]
    # weight, before the split into heads.
    pre_norms: bool = True
    qk_norm_whole: bool = False
    # Linear-attention layers (inference only): the kind ``"linear"`` of
    # ``layer_kinds``, a gated delta rule (ops/linear_attention.py) over
    # ``linear_heads`` heads, keys and queries ``linear_key_dim`` wide, values
    # ``linear_value_dim``, each behind a causal depthwise convolution over
    # ``linear_conv`` tokens. ``linear_neg_eigval``: the write strength runs over
    # (0, 2), not (0, 1). Such a layer keeps no positions: a serving slot holds
    # its state (models/generate.py, serve/llm/engine.py). Its leaves are its
    # own, so the layers of a pattern with it are stacked by kind
    # (``_layer_stacks``), and the pattern is whole periods of it.
    linear_heads: int = 0
    linear_key_dim: int = 0
    linear_value_dim: int = 0
    linear_conv: int = 4
    linear_neg_eigval: bool = False
    # Blocks of ONE mixer (inference only; the ``nemotron_h`` family): under the
    # kinds ``"mamba"`` and ``"experts"`` of ``layer_kinds`` every block is ``x +
    # mixer(RMSNorm(x))``: a ``"mamba"`` block a Mamba-2 state-space mixer
    # (ops/ssm.py) of ``mamba_heads`` heads of ``mamba_head_dim``, a state
    # ``ssm_state`` wide a head channel, B and C shared by the heads of each of
    # ``ssm_groups`` groups, behind a causal depthwise convolution over
    # ``mamba_conv`` tokens with a bias; an ``"experts"`` block the routed
    # experts and nothing else; a ``"full"`` block attention without rotary and
    # without an MLP. A slot keeps a Mamba block's state as it keeps a linear
    # layer's; the three kinds' leaves are stacked apart (``_layer_stacks``).
    mamba_heads: int = 0
    mamba_head_dim: int = 0
    ssm_state: int = 0
    ssm_groups: int = 1
    mamba_conv: int = 4
    # Gated short-convolution layers (inference only; the ``lfm2`` family): the
    # kind ``"conv"`` of ``layer_kinds``, a mixer-then-MLP layer whose mixer is
    # ``[B | C | u] = h W_in``, ``y = C * conv(B * u)`` with a causal depthwise
    # filter over ``conv_cache`` tokens (no bias, no activation), then ``W_out``:
    # no softmax and no positions. A serving slot carries the last ``conv_cache -
    # 1`` rows of ``B * u`` a layer and nothing else (models/generate.py). Its
    # leaves are its own, so the pattern's layers are stacked by kind
    # (``_layer_stacks``: ``"conv_layers"`` beside ``"layers"``), behind the
    # leading dense layers, which are conv layers here, and the MLP of either
    # kind is whatever the configuration says: dense, or routed experts.
    conv_cache: int = 0
    # A pattern's full layers rope their queries and keys plainly at
    # ``rope_theta`` (``layer_rope``; without it and without ``rope_scaling``
    # they carry no positions).
    full_layers_rope: bool = False
    # What an expert computes, routed and shared alike: ``"swiglu"`` (three
    # matrices) or ``"relu2"``, ``relu(x W_up)^2 W_down``, with no gate matrix.
    expert_activation: str = "swiglu"
    # (index, of): the share of every expert block's routed experts that this
    # program HOLDS, experts ``index * E / of ..`` of ``num_experts``, one chip's
    # of an expert-parallel deployment over ``of`` chips. The router stays
    # ``num_experts`` wide and chooses among all; an assignment to an expert
    # not held adds nothing here (parallel/moe.routed_experts: the result is
    # this chip's part of the sum), the shared expert is computed whole.
    expert_share: tuple = (0, 1)
    # Embeddings are multiplied by this as they are read (muP: sqrt(d_model));
    # the table is drawn that much smaller, so that a layer's branch weighs as
    # much beside the residual as without it.
    embed_multiplier: float = 1.0
    # Manifold-constrained hyper-connections (inference only; ops/hyper_connection.py):
    # the residual path is ``hc_mult`` streams (0: the plain residual, ``x +
    # F(norm(x))``). A sub-layer reads a learned, input-dependent mixture of them
    # and writes back through a doubly stochastic ``hc_mult`` x ``hc_mult``
    # matrix that ``hc_sinkhorn_iters`` Sinkhorn iterations (denominators +
    # ``hc_eps``) make of ``exp`` of logits clipped to ``hc_res_clamp``; three
    # float32 leaves a sub-layer (``_layer_leaves``).
    hc_mult: int = 0
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_res_clamp: tuple = (-30.0, 30.0)
    # YaRN: the six numbers of a published ``rope_scaling`` of that type
    # (``factor``, ``original_max_position_embeddings``, ``beta_fast``,
    # ``beta_slow``, ``mscale``, ``mscale_all_dim``) as sorted pairs, or empty
    # for none. They change the rotary frequencies and amplitude
    # (``_rope_tables``) of latent attention (inference only; its softmax scale
    # too, ``latent_softmax_scale``), of every layer of a model without a
    # pattern, and of a pattern's full layers (``layer_rope``).
    rope_scaling: tuple = ()
    # Generation by diffusion over blocks (inference only; the ``sdar`` family): the length of a block, 0 for an
    # autoregressive model. Attention is causal BETWEEN blocks of ``block_diffusion`` aligned positions and two-way
    # INSIDE one (``generate._cache_mask``); a block is generated from ``mask_token_id`` at every unknown position by
    # denoising passes that each feed the whole block and keep some of what they draw, then committed to the cache by
    # one more pass (serve/llm/engine.py). The logits at a position predict that position's own token.
    block_diffusion: int = 0
    mask_token_id: int = 0
    # The shortcut-connected DOUBLE layer (inference only; the ``longcat_flash`` family), selected by ``shortcut_moe``
    # over latent attention and routed experts: a layer is TWO latent-attention sub-layers and TWO dense SwiGLU FFNs of
    # width ``d_ff``, and one branch of routed experts that reads the first FFN's normed input and joins the residual
    # path a sub-layer LATE, behind the second FFN (``generate._shortcut_layer`` has the equations). ``n_layers`` counts
    # double layers: the cache is ``2 * n_layers`` layers deep (``attention_sublayers``). A sub-layer's leaves (its two
    # norms, the attention's matrices, the FFN's) are stacked ``[n_layers, 2, ...]`` under the names a plain layer
    # gives them, sub-layer 0 first, the branch's ``[n_layers, ...]``: ONE stack and one scan body a layer.
    shortcut_moe: bool = False
    # The router is WIDER than the experts it routes to: ``zero_experts`` more columns behind the ``num_experts`` are
    # identity experts, scored and chosen with the others; a pick of one adds the branch's own input times the pick's
    # weight and reaches no matrix (``parallel/moe.routed_experts(identity=)``; inference only). ``expert_share``
    # divides the ``num_experts``. ``router_normalize`` False: the chosen weights are ``routed_scaling_factor * s`` as
    # they stand, not divided by their sum (inference only).
    zero_experts: int = 0
    router_normalize: bool = True
    # Latent attention's two constants (the ``longcat_flash`` family): the normed query latent is multiplied by
    # ``sqrt(d_model / q_lora_rank)`` and the normed key-value latent by ``sqrt(d_model / kv_lora_rank)`` (the rotary
    # key is not), where they are normed: the cache holds the SCALED latent (``generate._project_latent``).
    mla_scale_q_lora: bool = False
    mla_scale_kv_lora: bool = False
    # Fuse the LM-head projection into a chunked cross-entropy
    # (ops/losses.fused_lm_loss) so the [B*T, V] f32 logits tensor never
    # hits HBM — loss_fn only; forward() still returns full logits for
    # generation/eval paths.
    fused_loss: bool = True

    def __post_init__(self):
        # Frozen: derived values are set past the freeze, once. A list from a
        # JSON configuration becomes the tuple a static jit argument must be.
        if not self.head_dim:
            object.__setattr__(self, "head_dim", self.d_model // self.n_heads)
        kinds = tuple(self.layer_kinds)
        object.__setattr__(self, "layer_kinds", kinds)
        object.__setattr__(self, "expert_share", tuple(self.expert_share))
        object.__setattr__(self, "hc_res_clamp", tuple(float(v) for v in self.hc_res_clamp))
        scaling = dict(self.rope_scaling)
        if scaling.pop("type", "yarn") != "yarn" or (scaling and set(scaling) != set(_YARN_KEYS)):
            raise ValueError(f"rope_scaling {dict(self.rope_scaling)!r}: type 'yarn' with {', '.join(_YARN_KEYS)}, or none")
        object.__setattr__(self, "rope_scaling", tuple(sorted((k, float(v)) for k, v in scaling.items())))
        if self.router_score not in ("sigmoid", "softmax"):
            raise ValueError(f"router_score {self.router_score!r}: 'sigmoid' or 'softmax'")
        for field, what in (
            (self.hc_mult and not self.latent_attention, "hyper-connections (hc_mult > 0) without latent attention (kv_lora_rank > 0)"),
            (self.hc_mult and kinds, "hyper-connections (hc_mult > 0) under a layer pattern (layer_kinds)"),
            (self.rope_scaling and kinds and "full" not in kinds and not self.latent_attention, "rope_scaling under a layer pattern without full layers, the ones it scales"),
            (self.block_diffusion and set(kinds) & {"linear", "mamba", "conv"}, "generation by diffusion over blocks (block_diffusion > 0) beside layers that keep a state a slot (layer_kinds has 'linear', 'mamba' or 'conv'), which a pass over a block would move before the block is final"),
            (self.block_diffusion and kinds, "generation by diffusion over blocks (block_diffusion > 0) under a layer pattern (layer_kinds)"),
            (self.block_diffusion and self.latent_attention, "generation by diffusion over blocks (block_diffusion > 0) over a latent pool (kv_lora_rank > 0)"),
            (self.block_diffusion and self.sliding_window, "generation by diffusion over blocks (block_diffusion > 0) under a sliding window"),
            (self.block_diffusion and self.hc_mult, "generation by diffusion over blocks (block_diffusion > 0) over hyper-connections (hc_mult > 0)"),
        ):
            if field:
                raise ValueError(f"{what}: has not run and is not built")
        if self.shortcut_moe:
            for field, what in (
                (not (self.latent_attention and self.routed_experts), "the shortcut-connected double layer (shortcut_moe) without latent attention (kv_lora_rank > 0) and routed experts (experts_per_token > 0)"),
                (kinds, "the shortcut-connected double layer (shortcut_moe) under a layer pattern (layer_kinds)"),
                (self.hc_mult, "the shortcut-connected double layer (shortcut_moe) over hyper-connections (hc_mult > 0)"),
                (self.first_dense_layers or self.num_shared_experts, "the shortcut-connected double layer (shortcut_moe) with leading dense layers or shared experts"),
                (self.post_norms or not self.pre_norms, "the shortcut-connected double layer (shortcut_moe) with post_norms or without pre_norms"),
            ):
                if field:
                    raise ValueError(f"{what}: has not run and is not built")
        if self.zero_experts < 0 or (self.zero_experts and not self.routed_experts):
            raise ValueError(f"zero_experts {self.zero_experts}: identity experts beside routed experts (experts_per_token > 0), or none")
        if (self.mla_scale_q_lora or self.mla_scale_kv_lora) and not self.latent_attention:
            raise ValueError("mla_scale_q_lora / mla_scale_kv_lora without latent attention (kv_lora_rank > 0)")
        if self.block_diffusion < 0 or (self.block_diffusion and not 0 <= self.mask_token_id < self.vocab_size):
            raise ValueError(f"block_diffusion {self.block_diffusion} with mask_token_id {self.mask_token_id}: a block of at least one position and a mask id of the vocabulary")
        if kinds and (len(kinds) != self.n_layers or set(kinds) - {"window", "full", "linear", "mamba", "experts", "conv"}):
            raise ValueError(
                f"layer_kinds names {len(kinds)} layers {sorted(set(kinds))}: need "
                f"n_layers = {self.n_layers} of 'window' / 'full' / 'linear' / 'mamba' / 'experts' / 'conv'"
            )
        if "window" in kinds and not self.sliding_window:
            raise ValueError("layer_kinds has window layers and sliding_window is 0")
        if "linear" in kinds:
            if not (self.linear_heads and self.linear_key_dim and self.linear_value_dim and self.linear_conv > 1):
                raise ValueError(
                    "layer_kinds has linear layers: linear_heads, linear_key_dim and linear_value_dim "
                    "must be set, and linear_conv at least 2"
                )
            if len(kinds) % _period(kinds):
                raise ValueError(
                    f"layer_kinds with linear layers must be whole periods: {len(kinds)} layers, period {_period(kinds)}"
                )
            for field, what in (
                ("window" in kinds, "window layers beside linear layers (layer_kinds)"),
                (self.latent_attention, "latent attention (kv_lora_rank > 0) beside linear layers"),
                (self.num_experts > 0, "experts (num_experts > 0) in a pattern with linear layers"),
                (self.first_dense_layers > 0, "leading dense layers (first_dense_layers) before linear layers"),
            ):
                if field:
                    raise ValueError(f"{what}: has not run and is not built")
        if "conv" in kinds:
            if self.conv_cache < 2:
                raise ValueError("layer_kinds has conv layers: conv_cache (the filter's tokens) must be at least 2")
            dense, rest = kinds[: self.first_dense_layers], kinds[self.first_dense_layers :]
            if set(dense) - {"conv"} or not rest or len(rest) % _period(rest):
                raise ValueError(
                    f"layer_kinds with conv layers: the {self.first_dense_layers} leading dense layers must be conv layers "
                    f"and the {len(rest)} layers behind them whole periods (period {_period(rest) if rest else 0})"
                )
            for field, what in (
                ("window" in kinds, "window layers beside conv layers (layer_kinds)"),
                ("linear" in kinds, "linear-attention layers beside conv layers (layer_kinds)"),
                (self.single_mixer, "single-mixer blocks beside conv layers (layer_kinds)"),
                (self.latent_attention, "latent attention (kv_lora_rank > 0) beside conv layers"),
            ):
                if field:
                    raise ValueError(f"{what}: has not run and is not built")
        if self.single_mixer:
            if "mamba" in kinds and not (
                self.mamba_heads and self.mamba_head_dim and self.ssm_state and self.mamba_conv > 1
                and self.mamba_heads % self.ssm_groups == 0
            ):
                raise ValueError(
                    "layer_kinds has mamba blocks: mamba_heads, mamba_head_dim and ssm_state must be set, "
                    "mamba_conv at least 2, and ssm_groups must divide mamba_heads"
                )
            if "experts" in kinds and not self.routed_experts:
                raise ValueError("layer_kinds has experts blocks: experts_per_token (and num_experts, d_expert) must be set")
            if len(kinds) % _period(kinds):
                raise ValueError(
                    f"layer_kinds of single-mixer blocks must be whole periods: {len(kinds)} blocks, period {_period(kinds)}"
                )
            for field, what in (
                ("window" in kinds, "window layers beside single-mixer blocks (layer_kinds)"),
                ("linear" in kinds, "linear-attention layers beside single-mixer blocks (layer_kinds)"),
                (self.latent_attention, "latent attention (kv_lora_rank > 0) in a pattern of single-mixer blocks"),
                (self.first_dense_layers > 0, "leading dense layers (first_dense_layers) before single-mixer blocks"),
                (self.attn_gate or self.qk_norm or self.qk_norm_whole or self.post_norms or not self.pre_norms,
                 "attn_gate, qk_norm, qk_norm_whole, post_norms or pre_norms=False in a single-mixer block"),
            ):
                if field:
                    raise ValueError(f"{what}: has not run and is not built")
        if self.expert_activation not in ("swiglu", "relu2"):
            raise ValueError(f"expert_activation {self.expert_activation!r}: 'swiglu' or 'relu2'")
        index, of = self.expert_share if len(self.expert_share) == 2 else (-1, 0)
        if not (of >= 1 and 0 <= index < of) or (of > 1 and (not self.routed_experts or self.num_experts % of)):
            raise ValueError(
                f"expert_share {self.expert_share!r}: (index, of) with 0 <= index < of, of dividing "
                f"num_experts = {self.num_experts} of a model with routed experts"
            )

    @property
    def latent_attention(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def single_mixer(self) -> bool:
        """Every block is one mixer behind one norm (a pattern with ``"mamba"``
        or ``"experts"`` blocks), not a mixer and then an MLP."""
        return "mamba" in self.layer_kinds or "experts" in self.layer_kinds

    @property
    def attention_sublayers(self) -> int:
        """Attention sub-layers, each a layer of the cache, in one of ``n_layers`` (2 under ``shortcut_moe``)."""
        return 2 if self.shortcut_moe else 1

    @property
    def router_width(self) -> int:
        """Columns of a router: the experts and, behind them, the identity experts (``zero_experts``)."""
        return self.num_experts + self.zero_experts

    @property
    def held_experts(self) -> int:
        """Routed experts of a block whose weights this program holds (``expert_share``)."""
        return self.num_experts // self.expert_share[1]

    @property
    def routed_experts(self) -> bool:
        return self.experts_per_token > 0

    @property
    def inference_only(self) -> str:
        """What of this configuration the training path lacks ('' if nothing)."""
        missing = []
        if self.latent_attention:
            missing.append("latent attention (kv_lora_rank > 0) has no training block")
        if self.routed_experts:
            for field, what in (
                (self.router_bias, "a router's bias that chooses (router_bias) has no bias update rule"),
                (self.num_shared_experts, "shared experts (num_shared_experts) have no training block"),
                (self.first_dense_layers, "leading dense layers (first_dense_layers) have no training block"),
            ):
                if field:
                    missing.append(what)
        if set(self.layer_kinds) - {"window", "full"}:
            missing.append("a layer pattern (layer_kinds) of other kinds than 'window' and 'full' has no training block")
        for field, what in (
            ("attn_gate", "gated attention (attn_gate)"),
            ("post_norms", "post-branch norms (post_norms)"),
            ("qk_norm_whole", "query and key norms over the whole projection (qk_norm_whole)"),
            ("linear_heads", "linear-attention layers (linear_heads)"),
            ("linear_key_dim", "linear-attention layers (linear_key_dim)"),
            ("linear_value_dim", "linear-attention layers (linear_value_dim)"),
            ("linear_neg_eigval", "a write strength over (0, 2) (linear_neg_eigval)"),
            ("mamba_heads", "Mamba-2 state-space blocks (mamba_heads)"),
            ("mamba_head_dim", "Mamba-2 state-space blocks (mamba_head_dim)"),
            ("ssm_state", "Mamba-2 state-space blocks (ssm_state)"),
        ):
            if getattr(self, field):
                missing.append(f"{what} has no training block")
        if not self.pre_norms:
            missing.append("a block without input norms (pre_norms) has no training block")
        if self.linear_conv != 4:
            missing.append("a linear layer's convolution (linear_conv) has no training block")
        if self.ssm_groups != 1:
            missing.append("a state-space block's groups (ssm_groups) have no training block")
        if self.mamba_conv != 4:
            missing.append("a state-space block's convolution (mamba_conv) has no training block")
        if self.conv_cache:
            missing.append("gated short-convolution layers (conv_cache) have no training block")
        if self.expert_activation != "swiglu":
            missing.append("experts without a gate matrix (expert_activation) have no training block")
        if self.embed_multiplier != 1.0:
            missing.append("an embedding multiplier (embed_multiplier) has no training block")
        if self.hc_mult:
            missing.append("a residual path of several streams (hc_mult) has no training block")
        if self.shortcut_moe:
            missing.append("the shortcut-connected double layer (shortcut_moe) has no training block")
        if self.zero_experts:
            missing.append("identity experts in the router (zero_experts) have no training block")
        if not self.router_normalize:
            missing.append("chosen weights that are not normalised (router_normalize) have no training block")
        if self.mla_scale_q_lora or self.mla_scale_kv_lora:
            missing.append("latent attention's constants (mla_scale_q_lora, mla_scale_kv_lora) have no training block")
        if self.block_diffusion:
            missing.append("generation by diffusion over blocks (block_diffusion) trains on a doubled sequence, noisy blocks beside clean ones, which has no training block")
        return "; ".join(missing)


_YARN_KEYS = ("factor", "original_max_position_embeddings", "beta_fast", "beta_slow", "mscale", "mscale_all_dim")


def _period(kinds: tuple) -> int:
    """The shortest p with ``kinds[i] == kinds[i - p]`` throughout."""
    return next(p for p in range(1, len(kinds) + 1) if all(kinds[i] == kinds[i - p] for i in range(p, len(kinds))))


# Elements _draw_normal makes per loop iteration, and the largest slice it
# draws in one piece.
_DRAW_ELEMENTS = 1 << 22
_DRAW_SLICE_MAX = 1 << 27


@partial(jax.jit, static_argnames=("shape", "scale", "dtype", "post"))
def _draw_normal(key, shape, scale, dtype, post=None):
    """One parameter leaf: normal(0, scale) of ``shape`` in ``dtype``, drawn a
    slice of the leading axis at a time inside one compiled loop. Drawn whole
    and eagerly, a stacked [16, 4096, 14336] leaf is 3.8 GB in f32, twice over
    (draw, scaled copy), beside the weights already made. Drawn whole under
    jit it fits, but each such program takes the TPU compiler 7-14 s (v5e,
    PR 21: 116 s for the nine leaves of 16 Mistral-7B layers, which then run
    in 0.03 s each); the loop body compiles in about a second at any depth.

    Two things keep that second a second (PR 32, timed with the TPU's compiler
    off the chip): small slices are drawn several at a time, and that number
    DIVIDES the number of slices, because a remainder is a second loop body
    (the [4096, 32000] head compiled for 16 s with 131 at a time, for 0.5 s
    with 128; the values are the same, each slice has its own key); and a leaf
    whose slices are larger than ``_DRAW_SLICE_MAX`` elements ([7, 64, 1536,
    2048]: 7 s) is drawn over its leading axes together.

    ``post`` (a function, optional): what the scaled normal values go through
    before the cast, for a leaf whose published initialisation is no normal
    (``_uniform_log_A``, ``_log_uniform_dt_bias``)."""
    lead = 1
    while len(shape) - lead > 2 and math.prod(shape[lead:]) > _DRAW_SLICE_MAX:
        lead += 1
    n, rest = math.prod(shape[:lead]), shape[lead:]

    def draw(k):
        values = jax.random.normal(k, rest) * scale
        return (values if post is None else post(values)).astype(dtype)

    fit = min(n, max(1, _DRAW_ELEMENTS // math.prod(rest)))
    at_a_time = next(b for b in range(fit, 0, -1) if n % b == 0)
    return lax.map(draw, jax.random.split(key, n), batch_size=at_a_time).reshape(shape)


class _Leaf(NamedTuple):
    """How one leaf of a layer is made and sharded."""

    key: Any  # index into the stack's keys; None: a constant (a norm weight: ones)
    shape: tuple
    scale: Any  # of the normal draw; with ``key`` None the constant, None for 1
    axes: tuple  # logical axis names (parallel/mesh.logical_to_spec)
    dtype: Any = None  # None: the configuration's ``param_dtype``
    post: Any = None  # ``_draw_normal``'s: what the drawn values go through


# Mamba-2's published initialisation of a head's decay and time step (``A_init_
# range``, ``time_step_min`` / ``_max`` / ``_floor`` of a ``nemotron_h``
# configuration, the defaults of the Mamba-2 code): A uniform over (1, 16), the
# time step log-uniform over (0.001, 0.1) and no smaller than 1e-4. With both a
# head forgets between a fifth and a thousandth of its state a token.
_MAMBA_A_RANGE = (1.0, 16.0)
_MAMBA_DT_RANGE = (1e-3, 1e-1)
_MAMBA_DT_FLOOR = 1e-4


def _uniform_log_A(z):
    """Unit normal values -> ``A_log``: log of a uniform draw over ``_MAMBA_A_RANGE``."""
    lo, hi = _MAMBA_A_RANGE
    return jnp.log(lo + (hi - lo) * jax.scipy.stats.norm.cdf(z))


def _log_uniform_dt_bias(z):
    """Unit normal values -> ``dt_bias``: the inverse softplus of a time step
    drawn log-uniformly over ``_MAMBA_DT_RANGE``."""
    lo, hi = (math.log(v) for v in _MAMBA_DT_RANGE)
    dt = jnp.maximum(jnp.exp(lo + (hi - lo) * jax.scipy.stats.norm.cdf(z)), _MAMBA_DT_FLOOR)
    return dt + jnp.log(-jnp.expm1(-dt))


def _hc_bias(z):
    """Drawn values [2n + n^2] -> a hyper-connection's ``b``: + 2 on the diagonal
    of ``H_res``'s logits (the last n^2, row-major), so that a stream keeps most
    of itself and the matrix is neither the identity nor uniform."""
    n = math.isqrt(z.shape[-1] + 1) - 1
    return z.at[..., 2 * n + (n + 1) * jnp.arange(n)].add(2.0)


def _layer_leaves(cfg: TransformerConfig, mlp, mixer="attention") -> dict:
    """One layer's leaves by name. ``mlp`` is ``"dense"``, ``"switch"``,
    ``"routed"`` or None (a block that is a mixer alone); ``mixer`` is
    ``"attention"``, ``"linear"`` (a linear-attention layer's), ``"mamba"`` (a
    state-space block's), ``"conv"`` (a gated short convolution's) or None (a
    block that is its experts alone). Stacked,
    every leaf gains a leading layer axis."""
    D, H, KV, Dh, F, E = (
        cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.d_ff, cfg.num_experts
    )
    s = D**-0.5
    # Residual branches shrink with depth: two a layer, or one a block, or (the double layer) five.
    out = (cfg.n_layers if cfg.single_mixer else (5 if cfg.shortcut_moe else 2) * cfg.n_layers) ** -0.5
    leaves = {}
    if cfg.pre_norms:
        leaves = {
            **({"attn_norm": _Leaf(None, (D,), None, (None,))} if mixer else {}),
            **({"mlp_norm": _Leaf(None, (D,), None, (None,))} if mlp else {}),
        }
    if cfg.hc_mult:
        # A sub-layer's hyper-connection (ops/hyper_connection.py), float32 like a
        # router: ``phi`` stored [2n + n^2, n D] (the stream's width on the lanes;
        # columns in the order [pre | post | res]), ``b``, and ``alpha`` = 1. A
        # trained model starts at ``alpha`` near 0, a constant mixing: here ``m``
        # is of order 1, so that the mixing depends on the token.
        n = cfg.hc_mult
        width = 2 * n + n * n
        for sub, k in ([("attn", 14)] if mixer else []) + ([("mlp", 16)] if mlp else []):
            leaves[f"hc_{sub}_phi"] = _Leaf(k, (width, n * D), (n * D) ** -0.5, (None, None), jnp.float32)
            leaves[f"hc_{sub}_b"] = _Leaf(k + 1, (width,), 0.5, (None,), jnp.float32, _hc_bias)
            leaves[f"hc_{sub}_alpha"] = _Leaf(None, (3,), None, (None,), jnp.float32)
    if mixer == "mamba":
        Hm, P, N, G, K = cfg.mamba_heads, cfg.mamba_head_dim, cfg.ssm_state, cfg.ssm_groups, cfg.mamba_conv
        inner, channels = Hm * P, Hm * P + 2 * G * N
        # The input projection is three leaves, the gate's, the convolution's
        # channels' and the time step's: whole, its 2 Hm P + 2 G N + Hm columns
        # (10,304 at the published widths) are no multiple of the TPU's 128
        # lanes, and every slice of the product would start inside a tile.
        # ``A_log``, ``dt_bias`` and ``D`` stay float32 like a router: a decay
        # is exponentiated twice.
        leaves.update(
            {
                "w_z": _Leaf(0, (D, inner), s, ("embed", "heads")),
                "w_xbc": _Leaf(1, (D, channels), s, ("embed", "heads")),
                "w_dt": _Leaf(2, (D, Hm), s, ("embed", None)),
                "conv_w": _Leaf(8, (K, channels), K**-0.5, (None, "heads")),
                "conv_b": _Leaf(9, (channels,), 0.1, ("heads",)),
                "A_log": _Leaf(10, (Hm,), 1.0, (None,), jnp.float32, _uniform_log_A),
                "dt_bias": _Leaf(11, (Hm,), 1.0, (None,), jnp.float32, _log_uniform_dt_bias),
                "D": _Leaf(None, (Hm,), None, (None,), jnp.float32),
                "ssm_norm": _Leaf(None, (inner,), None, (None,)),
                "wo": _Leaf(3, (inner, D), inner**-0.5 * out, ("heads", "embed")),
            }
        )
    elif mixer == "linear":
        Hl, dk, dv = cfg.linear_heads, cfg.linear_key_dim, cfg.linear_value_dim
        # Queries, keys and values are one matrix: the convolution runs over
        # its 2 Hl dk + Hl dv columns as they lie, and a slot carries the last
        # rows of the product. The two gates' projections are drawn small and
        # ``dt_bias`` low, because a branch's input is not normed here and the
        # residual grows with depth: a decay exp(-exp(A_log) softplus(x w_a +
        # dt_bias)) that is neither 0 nor 1 and a write strength that does not
        # saturate, at every depth (benchmarks/configs/olmo-hybrid-7b-serve16.json,
        # ``assumed``, has the range). ``A_log`` and ``dt_bias`` stay float32
        # whatever the weights' dtype, like a router: a decay is exponentiated twice.
        leaves.update(
            {
                "w_qkv": _Leaf(0, (D, Hl * (2 * dk + dv)), s, ("embed", "heads")),
                "conv_w": _Leaf(1, (cfg.linear_conv, Hl * (2 * dk + dv)), cfg.linear_conv**-0.5, (None, "heads")),
                "w_a": _Leaf(2, (D, Hl), 0.2 * s, ("embed", None)),
                "w_b": _Leaf(8, (D, Hl), 0.5 * s, ("embed", None)),
                "A_log": _Leaf(9, (Hl,), 0.5, (None,), jnp.float32),
                "dt_bias": _Leaf(None, (Hl,), -4.0, (None,), jnp.float32),
                "wg_lin": _Leaf(13, (D, Hl * dv), s, ("embed", "heads")),
                "o_norm": _Leaf(None, (dv,), None, (None,)),
                "wo": _Leaf(3, (Hl * dv, D), (Hl * dv) ** -0.5 * out, ("heads", "embed")),
            }
        )
    elif mixer == "conv":
        # ``w_in``'s columns are [B | C | u], D each; the filter one tap a token a channel, no bias.
        leaves.update(
            {
                "w_in": _Leaf(0, (D, 3 * D), s, ("embed", "heads")),
                "conv_w": _Leaf(1, (cfg.conv_cache, D), cfg.conv_cache**-0.5, (None, "heads")),
                "wo": _Leaf(3, (D, D), s * out, ("heads", "embed")),
            }
        )
    elif mixer is None:
        pass
    elif cfg.latent_attention:
        R, Rq = cfg.kv_lora_rank, cfg.q_lora_rank
        N, P, Vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
        leaves.update(
            {
                "wq_a": _Leaf(0, (D, Rq), s, ("embed", None)),
                "q_norm": _Leaf(None, (Rq,), None, (None,)),
                "wq_b": _Leaf(1, (Rq, H * (N + P)), Rq**-0.5, (None, "heads")),
                "wkv_a": _Leaf(2, (D, R + P), s, ("embed", None)),
                "kv_norm": _Leaf(None, (R,), None, (None,)),
                "wkv_b": _Leaf(8, (R, H * (N + Vd)), R**-0.5, (None, "heads")),
                "wo": _Leaf(3, (H * Vd, D), (H * Vd) ** -0.5 * out, ("heads", "embed")),
            }
        )
    else:
        leaves.update(
            {
                "wq": _Leaf(0, (D, H * Dh), s, ("embed", "heads")),
                "wk": _Leaf(1, (D, KV * Dh), s, ("embed", "kv")),
                "wv": _Leaf(2, (D, KV * Dh), s, ("embed", "kv")),
                "wo": _Leaf(3, (H * Dh, D), (H * Dh) ** -0.5 * out, ("heads", "embed")),
            }
        )
        if cfg.attn_gate:
            leaves["wg_attn"] = _Leaf(13, (D, H * Dh), s, ("embed", "heads"))
        if cfg.qk_norm:
            leaves["q_norm"] = _Leaf(None, (Dh,), None, (None,))
            leaves["k_norm"] = _Leaf(None, (Dh,), None, (None,))
        if cfg.qk_norm_whole:
            leaves["q_norm"] = _Leaf(None, (H * Dh,), None, (None,))
            leaves["k_norm"] = _Leaf(None, (KV * Dh,), None, (None,))
    if cfg.post_norms:
        leaves["attn_post_norm"] = _Leaf(None, (D,), None, (None,))
        leaves["mlp_post_norm"] = _Leaf(None, (D,), None, (None,))
    if mlp == "dense":
        leaves.update(
            {
                "wi": _Leaf(5, (D, F), s, ("embed", "mlp")),
                "wg": _Leaf(6, (D, F), s, ("embed", "mlp")),
                "wo_mlp": _Leaf(7, (F, D), F**-0.5 * out, ("mlp", "embed")),
            }
        )
        return leaves
    if mlp is None:
        return leaves
    gated = cfg.expert_activation == "swiglu"  # else no gate matrix, routed or shared
    if mlp == "routed":
        F = cfg.d_expert
        Fs = cfg.num_shared_experts * F
        # The router stays float32 whatever the weights' dtype, as published:
        # a near-tie between two experts must not turn on a rounding.
        W = cfg.router_width
        # The bias is small beside the scores it is added to: sigmoids of order 1, or (identity experts: a softmax over
        # hundreds of columns) of order 1 / W, where neighbours in rank near the k-th lie a fifth of that apart.
        bias = 1.0 / W if cfg.zero_experts else 0.01
        leaves.update(
            {
                "gate": _Leaf(4, (D, W), s, ("embed", None), jnp.float32),
                # A leaf only where the router has one: one that no gradient reaches would still be decayed by AdamW.
                **({"gate_bias": _Leaf(9, (W,), bias, (None,), jnp.float32)} if cfg.router_bias else {}),
            }
        )
        if Fs:
            leaves.update(
                {
                    "wi_s": _Leaf(10, (D, Fs), s, ("embed", "mlp")),
                    **({"wg_s": _Leaf(11, (D, Fs), s, ("embed", "mlp"))} if gated else {}),
                    "wo_s": _Leaf(12, (Fs, D), Fs**-0.5 * out, ("mlp", "embed")),
                }
            )
        E = cfg.held_experts  # the router above is as wide as all the experts; the matrices below are those held
    else:
        leaves["gate"] = _Leaf(4, (D, E), s, ("embed", None))
    leaves.update(
        {
            "wi_e": _Leaf(5, (E, D, F), s, ("expert", "embed", "mlp")),
            **({"wg_e": _Leaf(6, (E, D, F), s, ("expert", "embed", "mlp"))} if gated else {}),
            "wo_e": _Leaf(7, (E, F, D), F**-0.5 * out, ("expert", "mlp", "embed")),
        }
    )
    if cfg.shortcut_moe:
        # What a sub-layer has of its own gains an axis of 2 behind the layers' (the branch's leaves above do not); the
        # dense FFNs take the keys a shared expert's leaves would (the double layer has none).
        Fd = cfg.d_ff
        own = {name: leaf for name, leaf in leaves.items() if name not in SHORTCUT_BRANCH_LEAVES}
        own.update(
            {
                "wi": _Leaf(10, (D, Fd), s, ("embed", "mlp")),
                "wg": _Leaf(11, (D, Fd), s, ("embed", "mlp")),
                "wo_mlp": _Leaf(12, (Fd, D), Fd**-0.5 * out, ("mlp", "embed")),
            }
        )
        leaves.update({name: leaf._replace(shape=(2, *leaf.shape), axes=(None, *leaf.axes)) for name, leaf in own.items()})
    return leaves


# The leaves of a double layer (``shortcut_moe``) that are its ONE branch of routed experts'; every other leaf is a
# sub-layer's and is stacked ``[n_layers, 2, ...]``.
SHORTCUT_BRANCH_LEAVES = ("gate", "gate_bias", "wi_e", "wg_e", "wo_e")


LINEAR_LAYERS = "linear_layers"
MAMBA_LAYERS = "mamba_layers"
EXPERT_LAYERS = "expert_layers"
CONV_LAYERS = "conv_layers"


class _Stack(NamedTuple):
    """A stack of layers: its depth, its kind of MLP and of mixer
    (``_layer_leaves``' arguments), and the kind of ``layer_kinds`` whose layers
    it holds, each at its rank among its kind, in the order they come in the
    model, which runs the kinds interleaved. ``kind`` None: a run of
    consecutive layers whatever their kinds, each at its position."""

    depth: int
    mlp: Any
    mixer: Any
    kind: Any = None


def _layer_stacks(cfg: TransformerConfig) -> dict:
    """``params`` key -> ``_Stack``, in the order the stacks run: the leading
    dense layers (where the configuration has any), then ``"layers"``. A
    pattern with linear-attention layers, whose leaves are not the attention
    layers', is stacked by kind: its linear layers in ``"linear_layers"`` and
    the others in ``"layers"``. A pattern of single-mixer blocks is three such
    stacks: the state-space blocks (``"mamba_layers"``), the experts blocks
    (``"expert_layers"``) and the attention blocks (``"layers"``). A pattern
    with conv layers is stacked by kind too (``"conv_layers"``, ``"layers"``),
    behind its leading dense layers, conv layers all, which stay a stack of
    their own. A configuration of double layers (``shortcut_moe``) is ONE stack,
    ``"layers"``, whose leaves ``_layer_leaves`` gives an axis of 2 where a
    sub-layer owns them. What a kind of layer keeps in a cache is
    ``generate._layer_plan``'s to say."""
    mlp = "routed" if cfg.routed_experts else "switch" if cfg.num_experts > 0 else "dense"
    count = cfg.layer_kinds.count
    if cfg.single_mixer:
        stacks = {MAMBA_LAYERS: _Stack(count("mamba"), None, "mamba", "mamba"), EXPERT_LAYERS: _Stack(count("experts"), mlp, None, "experts"),
                  "layers": _Stack(count("full"), None, "attention", "full")}
        return {name: stack for name, stack in stacks.items() if stack.depth}
    n_dense, n_linear = cfg.first_dense_layers, count("linear")
    if count("conv"):
        stacks = {"dense_layers": _Stack(n_dense, "dense", "conv")} if n_dense else {}
        stacks[CONV_LAYERS] = _Stack(count("conv") - n_dense, mlp, "conv", "conv")
        stacks["layers"] = _Stack(count("full"), mlp, "attention", "full")
        return stacks
    stacks = {"dense_layers": _Stack(n_dense, "dense", "attention")} if n_dense else {}
    if n_linear:
        stacks[LINEAR_LAYERS] = _Stack(n_linear, mlp, "linear", "linear")
    stacks["layers"] = _Stack(cfg.n_layers - n_dense - n_linear, mlp, "attention", "full" if n_linear else None)
    return stacks


def init_params(key, cfg: TransformerConfig) -> dict:
    ks = jax.random.split(key, 10)
    D, V, dt = cfg.d_model, cfg.vocab_size, cfg.param_dtype
    # Two key schedules, because one cannot serve both: ``ks`` has ten keys,
    # eight of them the leaves of the one GQA / Switch stack, and every seeded
    # value in the tests and the benchmark's accepted cells comes from it (a
    # seed's weights are the same before and after this change: checked value
    # for value against the parent's). A latent or routed stack has up to
    # sixteen leaves and there may be two stacks, more than ``ks`` holds: each
    # stack then splits sixteen keys of its own from ``fold_in(key, stack)``.
    own_keys = (
        cfg.latent_attention or cfg.routed_experts or cfg.first_dense_layers > 0 or cfg.attn_gate
        or "linear" in cfg.layer_kinds or cfg.single_mixer or cfg.hc_mult > 0 or "conv" in cfg.layer_kinds
    )

    # Every drawn leaf is a program of its own to compile (about a second each
    # on the TPU, PR 32) and there are up to forty: they are drawn side by side
    # (the compiler runs outside the GIL), the values those of one after
    # another, each leaf having its own key. Cold, an expert model's replica
    # spent 34 of its 73 s here (v5e, PR 35), under a Serve that gives it 90.
    drawn = _Drawn(key)

    def stack(i, of: _Stack):
        keys = jax.random.split(jax.random.fold_in(key, i), 18 if cfg.hc_mult else 16) if own_keys else ks
        return {
            name: jnp.full((of.depth, *leaf.shape), 1.0 if leaf.scale is None else leaf.scale, leaf.dtype or dt)
            if leaf.key is None
            else drawn.later(keys[leaf.key], (of.depth, *leaf.shape), leaf.scale, leaf.dtype or dt, leaf.post)
            for name, leaf in _layer_leaves(cfg, of.mlp, of.mixer).items()
        }

    # A tied table is the head too, and is drawn at the head's scale, D^-1/2: drawn at 1, a tied head's logits have
    # standard deviation sqrt(D) and a token's OWN logit (E[t] . E[t] = D, carried to the head by the residual path)
    # stands 30-45 deviations over every other, so that a random tied model repeats its last token whatever its layers
    # compute (found on the chip, PR 54: a float32 reference agreed with the served tokens at 3072 of 3072 positions,
    # and would have with any layer broken). The first layer's norm brings the small rows back to order 1.
    embed_scale = (D**-0.5 if cfg.tie_embeddings else 1.0) / cfg.embed_multiplier
    params = {
        "embed": drawn.later(ks[8], (V, D), embed_scale, dt),
        "norm_f": jnp.ones((D,), dt),
    }
    for i, (name, of) in enumerate(_layer_stacks(cfg).items()):
        params[name] = stack(i, of)
    if not cfg.tie_embeddings:
        params["lm_head"] = drawn.later(ks[9], (D, V), D**-0.5, dt)
    return drawn.now(params)


class _Drawn:
    """The draws of a parameter tree, asked for leaf by leaf (``later`` leaves
    a placeholder in the tree) and made together (``now`` fills them in)."""

    def __init__(self, key):
        # Under a trace (``jax.eval_shape(init_params)``) the key belongs to the
        # tracing thread: the draws are then made where they are asked for.
        self.traced = isinstance(key, jax.core.Tracer)
        self.jobs: list = []

    def later(self, *draw):
        if self.traced:
            return _draw_normal(*draw)
        self.jobs.append(draw)
        return _Pending(len(self.jobs) - 1)

    def now(self, tree):
        if not self.jobs:
            return tree
        from concurrent.futures import ThreadPoolExecutor

        with ThreadPoolExecutor(max_workers=8) as pool:
            leaves = list(pool.map(lambda draw: _draw_normal(*draw), self.jobs))
        return jax.tree.map(lambda leaf: leaves[leaf.job] if isinstance(leaf, _Pending) else leaf, tree)


class _Pending:
    """Stands in a parameter tree for the leaf that draw ``job`` will give
    (a plain object: a leaf to ``jax.tree.map``, which a tuple is not)."""

    def __init__(self, job: int):
        self.job = job


def param_logical_axes(cfg: TransformerConfig) -> dict:
    """Per-leaf logical axis names (mapped to mesh axes by
    parallel/mesh.logical_to_spec)."""
    axes = {"embed": ("vocab", "embed"), "norm_f": (None,)}
    for stack, of in _layer_stacks(cfg).items():
        axes[stack] = {name: ("layers", *leaf.axes) for name, leaf in _layer_leaves(cfg, of.mlp, of.mixer).items()}
    if not cfg.tie_embeddings:
        axes["lm_head"] = ("embed", "vocab")
    return axes


def _rms_norm(x, weight, eps):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps)).astype(x.dtype) * weight.astype(x.dtype)


def _yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1.0 else 1.0


def latent_softmax_scale(cfg: TransformerConfig) -> float:
    """THE softmax scale of latent attention: ``(nope + rope)^-1/2``, under
    YaRN times ``(0.1 mscale_all_dim ln factor + 1)^2`` (the view path, the
    kernel's ``sm_scale``; the benchmark's reference states its own)."""
    scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    yarn = dict(cfg.rope_scaling)
    return scale * _yarn_mscale(yarn["factor"], yarn["mscale_all_dim"]) ** 2 if yarn.get("mscale_all_dim") else scale


def _rope_tables(positions, Dh: int, theta, scaling: tuple = ()):
    """cos/sin rotation tables [B, T, Dh/2] for the given positions. The
    training path computes these ONCE per step (forward_hidden) instead of
    per layer per projection — positions are layer-invariant, and 16 sin+cos
    sweeps per step over [B,T,Dh/2] is pure wasted VPU time.

    ``scaling`` (``TransformerConfig.rope_scaling``; YaRN): pair i turns at its
    own frequency where it completes more than ``beta_fast`` turns over the
    original positions, at a ``factor``-th of it where fewer than
    ``beta_slow``, and in between by a linear ramp over the pairs; cos and sin
    are multiplied by the ratio of the two ``mscale`` terms."""
    half = Dh // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    yarn, amplitude = dict(scaling), 1.0
    if yarn:
        turns_at = lambda r: Dh * math.log(yarn["original_max_position_embeddings"] / (2 * math.pi * r)) / (2 * math.log(theta))  # noqa: E731
        low, high = max(math.floor(turns_at(yarn["beta_fast"])), 0), min(math.ceil(turns_at(yarn["beta_slow"])), Dh - 1)
        ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low) / max(high - low, 1e-3), 0.0, 1.0)
        freqs = freqs * (1.0 - ramp) + freqs / yarn["factor"] * ramp
        amplitude = _yarn_mscale(yarn["factor"], yarn["mscale"]) / _yarn_mscale(yarn["factor"], yarn["mscale_all_dim"])
    angles = positions[:, :, None].astype(jnp.float32) * freqs[None, None, :]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    return (cos, sin) if amplitude == 1.0 else (cos * amplitude, sin * amplitude)


def _rope_apply(x, cos, sin):
    # x: [B, T, H, Dh]; cos/sin: [B, T, Dh/2]
    x1, x2 = jnp.split(x, 2, axis=-1)
    rx1 = x1 * cos[:, :, None, :] - x2 * sin[:, :, None, :]
    rx2 = x2 * cos[:, :, None, :] + x1 * sin[:, :, None, :]
    return jnp.concatenate([rx1, rx2], axis=-1).astype(x.dtype)


def _whole_width(cos, sin):
    """``_rope_tables``' [B, T, Dh/2] as ``_rope_rotate`` takes them: ([cos, cos], [sin, sin]), [B, T, Dh]."""
    return jnp.concatenate([cos, cos], axis=-1), jnp.concatenate([sin, sin], axis=-1)


def _swap_halves(x, sign):
    """x [..., Dh] -> ``sign`` x [-x2, x1], exactly: a matmul with the signed permutation (each output is one input
    times +-1, summed with zeros in float32). The MXU does what a split and a concatenation, or ``jnp.roll``, make the
    compiler write to memory a half [.., Dh/2] at a time, forward and backward (Mellum's step, v5e, PR 53: 404.3 ms
    with the split, 401.2 with the roll, 390.9 with this)."""
    half = x.shape[-1] // 2
    swap = jnp.eye(2 * half, k=half) - jnp.eye(2 * half, k=-half)  # [i, i + half] = 1: out[i + half] = x[i]
    return jnp.einsum("...k,kj->...j", x, (sign * swap).astype(x.dtype), precision=lax.Precision.HIGHEST, preferred_element_type=jnp.float32)


@jax.custom_vjp
def _rope_rotate(x, cos, sin):
    """``_rope_apply`` for the training block's x [B, H, T, Dh] and ``_whole_width`` tables: the same float32
    products and sums, so the same values and cotangents, written over whole heads: ``x1 * cos - x2 * sin`` beside
    ``x2 * cos + x1 * sin`` is x [cos, cos] + [-x2, x1] [sin, sin]. The cotangent is the rotation back (its own rule:
    autodiff would swap the halves of a float32 product, a matmul the MXU rounds)."""
    return (x * cos[:, None] + _swap_halves(x, 1.0) * sin[:, None]).astype(x.dtype)


def _rope_rotate_fwd(x, cos, sin):
    return _rope_rotate(x, cos, sin), (cos, sin)


def _rope_rotate_bwd(tables, dy):
    cos, sin = tables
    # Each term rounded to x's dtype before the sum, as autodiff rounds the cotangents of ``_rope_apply``'s two uses of x.
    dx = (dy * cos[:, None]).astype(dy.dtype) + (_swap_halves(dy, -1.0) * sin[:, None]).astype(dy.dtype)
    return dx, jnp.zeros_like(cos), jnp.zeros_like(sin)  # the tables are functions of the positions alone


_rope_rotate.defvjp(_rope_rotate_fwd, _rope_rotate_bwd)


def _rope(x, positions, theta, scaling: tuple = ()):
    # Convenience form (decode paths in models/generate.py use this).
    cos, sin = _rope_tables(positions, x.shape[-1], theta, scaling)
    return _rope_apply(x, cos, sin)


def layer_rope(cfg: TransformerConfig, kind):
    """What positions a GQA layer of ``kind`` (of ``layer_kinds``; None: a model
    without a pattern) gives its queries and keys, THE one place that says so,
    for the training block and the cached layers (models/generate.py) alike:
    None for none, else the ``scaling`` of ``_rope_tables`` at ``rope_theta``
    (() for the plain rotary, ``rope_scaling`` for YaRN's frequencies and
    amplitude). A window layer ropes plainly; a full layer of a pattern by
    ``rope_scaling`` or, without it, plainly under ``full_layers_rope`` and else
    not at all; a model without a pattern by ``rope_scaling`` or, without it,
    plainly."""
    if kind == "full":
        return cfg.rope_scaling or (() if cfg.full_layers_rope else None)
    return () if kind else cfg.rope_scaling


# What a layer under ``cfg.remat`` keeps for its backward pass beside its input (the module's docstring): the
# attention core's inputs, named in ``_attention_block``, the flash kernel's results, named in its forward rule, and
# what ``moe.routed_experts`` names on the path a trained share takes.
_KEPT_INPUTS = ("attention_q", "attention_k", "attention_v")
_KEPT_NORM_INPUTS = ("attention_q_projected", "attention_k_projected")
_KEPT_UNDER_REMAT = jax.checkpoint_policies.save_only_these_names(
    *_KEPT_INPUTS, *_KEPT_NORM_INPUTS, FLASH_OUT, FLASH_LSE, *KEPT_OF_A_BOUNDED_BLOCK
)


def _attention_block(lp, x, rope_cs, cfg: TransformerConfig, mesh, attn_impl: str, kind=None):
    """``kind``: the layer's of ``layer_kinds`` (None without a pattern): a full
    layer attends over the whole context, the others within
    ``sliding_window``; ``rope_cs`` is its kind's ``_whole_width`` tables, None for no positions."""
    B, T, D = x.shape
    H, KV, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    h = _rms_norm(x, lp["attn_norm"], cfg.norm_eps)
    # q, k, v leave their projections heads before tokens, [B, H or KV, T, Dh]: a head's [T, Dh] contiguous, which
    # is how the flash kernels read it, so that nothing between a projection and ``wo`` transposes or repeats. The
    # matmul writes that layout itself (the compiler folds the transpose into it); the weight stays [D, H * Dh] in
    # it, so that its gradient leaves the backward matmul in the leaf's layout (over the weight seen as [D, H, Dh]
    # the gradients of wq, wk, wv were relaid in float32 before they were stacked: +96 MB in ``train2.dp4-4k``).
    project = lambda w, heads: (h @ w.astype(h.dtype)).reshape(B, T, heads, Dh).transpose(0, 2, 1, 3)  # noqa: E731
    q, k, v = project(lp["wq"], H), project(lp["wk"], KV), project(lp["wv"], KV)
    if cfg.qk_norm:
        # A norm's backward pass needs its input: kept too, or the projections run again for it (Mellum's cell, v5e,
        # PR 51: 435.4 ms a step with q and k kept behind the norms alone, 408.7 with both).
        q, k = (checkpoint_name(a, name) for a, name in zip((q, k), _KEPT_NORM_INPUTS))
        q, k = _rms_norm(q, lp["q_norm"], cfg.norm_eps), _rms_norm(k, lp["k_norm"], cfg.norm_eps)
    if rope_cs is not None:
        cos, sin = rope_cs
        q = _rope_rotate(q, cos, sin)
        k = _rope_rotate(k, cos, sin)
    # What the attention core is given, as ``_run_layers``' checkpoint policy keeps it: behind the norms and the
    # rotary, K and V at ``KV`` heads (``flash_attention`` reads a group's KV head in place, forward and backward).
    q, k, v = (checkpoint_name(a, name) for a, name in zip((q, k, v), _KEPT_INPUTS))
    window = 0 if kind == "full" else cfg.sliding_window
    if attn_impl == "ring" and mesh is not None and mesh.shape.get("sp", 1) > 1:
        if window:
            raise NotImplementedError("sliding_window + ring attention not supported")
        from ray_tpu.parallel.ring_attention import ring_attention

        # its chunks are [B, T, H, Dh] and pair head with head
        tokens_major = lambda a: repeat_kv(a, H, axis=1).transpose(0, 2, 1, 3)  # noqa: E731
        o = ring_attention(tokens_major(q), tokens_major(k), tokens_major(v), mesh, causal=True).transpose(0, 2, 1, 3)
    else:
        attn = partial(flash_attention, causal=True, window=window)
        if mesh is not None and mesh.size > 1:
            # The compiler cannot partition a Mosaic kernel ("wrap the call
            # in a shard_map"): each device runs it on its own batch rows
            # and heads, the axes the model's sharding rules already split.
            from ray_tpu.parallel.mesh import logical_to_spec

            spec = logical_to_spec(("batch", "heads", None, None))
            # A device's query heads must find their KV heads on it: where the mesh splits the heads further than
            # the KV heads go round, K and V are repeated up to the least width it does divide.
            split = math.prod(mesh.shape[axis] for axis in jax.tree.leaves(spec[1]))
            k, v = (repeat_kv(a, math.lcm(KV, split), axis=1) for a in (k, v))
            attn = jax.shard_map(
                attn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
                check_vma=False,
            )
        with jax.named_scope(f"attention_{kind}") if kind else contextlib.nullcontext():
            o = attn(q, k, v)
    return x + jnp.einsum("bhtk,hkd->btd", o, lp["wo"].astype(o.dtype).reshape(H, Dh, D))


def _moe_mlp(lp, h, capacity_factor: float):
    """The one MoE dispatch call both the training block and KV-cache decode
    share (they differ only in capacity: training drops over-capacity
    tokens as an efficiency trade, inference runs lossless)."""
    from ray_tpu.parallel.moe import moe_layer

    return moe_layer(
        {
            "gate": lp["gate"].astype(h.dtype),
            "wi": lp["wi_e"].astype(h.dtype),
            "wo": lp["wo_e"].astype(h.dtype),
        },
        h,
        capacity_factor=capacity_factor,
    )


def _experts_block(lp, x, cfg: TransformerConfig):
    """The dropless routed experts in the MLP's place: (x + this program's
    share of the experts' sum, the layer's balance term over the model's depth,
    the assignments sent to each of ALL the experts [E] int32)."""
    from ray_tpu.parallel import moe

    B, T, D = x.shape
    h = _rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
    out, _, chosen, scores = moe.routed_experts(
        lp, h.reshape(B * T, D), k=cfg.experts_per_token, scale=cfg.routed_scaling_factor, share=cfg.expert_share,
        score=cfg.router_score, rows=moe.held_rows(B * T * cfg.experts_per_token, cfg.expert_share),
    )
    with jax.named_scope("moe_router"):
        balance, sent = moe.balance_term(scores, chosen, cfg.router_score)
    return x + out.reshape(B, T, D), balance / cfg.n_layers, sent


def _mlp_block(lp, x, cfg: TransformerConfig):
    if cfg.routed_experts:
        return _experts_block(lp, x, cfg)
    h = _rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
    if cfg.num_experts > 0:
        out, aux = _moe_mlp(lp, h, cfg.expert_capacity_factor)
        # SwiGLU-ish gate path folded into experts (wg_e unused in moe path
        # to keep dispatch einsums lean; kept in params for parity).
        return x + out, aux, None
    gate = jax.nn.silu(h @ lp["wg"].astype(h.dtype))
    up = h @ lp["wi"].astype(h.dtype)
    return x + (gate * up) @ lp["wo_mlp"].astype(h.dtype), 0.0, None


def _layer(lp, x, rope_cs, cfg: TransformerConfig, mesh, attn_impl: str, kind=None):
    """-> (x, the layer's term of the balance loss, the assignments its routed
    experts were sent [E] int32 or None)."""
    x = _attention_block(lp, x, rope_cs, cfg, mesh, attn_impl, kind)
    return _mlp_block(lp, x, cfg)


def _refuse_inference_only(cfg: TransformerConfig, what: str):
    if cfg.inference_only:
        raise NotImplementedError(
            f"{what} cannot run this configuration: {cfg.inference_only}. "
            "It is served through models/generate.py (prefill and decode over a cache) only."
        )


def _run_layers(params: dict, tokens, cfg: TransformerConfig, mesh, attn_impl: str):
    """tokens [B, T] int32 -> (final hidden [B, T, D], moe aux, the assignments
    of each layer's routed experts [L, E] int32 or None)."""
    B, T = tokens.shape
    x = params["embed"].astype(cfg.dtype)[tokens]
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (B, T))
    # One period of the pattern (a model without one: one layer of kind None) is what the scan's body runs,
    # its layers unrolled, each with the window and the rotary tables of its kind.
    kinds = cfg.layer_kinds[: _period(cfg.layer_kinds)] if cfg.layer_kinds else (None,)
    # Rope tables are layer-invariant: one sin+cos sweep per step and kind,
    # shared by every such layer's q and k (vs 2·n_layers recomputations inside the scan).
    def table_of(kind):
        rope = layer_rope(cfg, kind)
        return None if rope is None else _whole_width(*_rope_tables(positions, cfg.head_dim, cfg.rope_theta, rope))

    def layer_of(kind):
        layer_fn = partial(_layer, cfg=cfg, mesh=mesh, attn_impl=attn_impl, kind=kind)
        return jax.checkpoint(layer_fn, policy=_KEPT_UNDER_REMAT) if cfg.remat else layer_fn

    # Each kind once, in the pattern's order: a set's order changes from process to process, the traced program's
    # text with it, and a compile cache then misses every other run (setup_s 160 s for 60, v5e, PR 50).
    tables, layer_fns = ({kind: of(kind) for kind in dict.fromkeys(kinds)} for of in (table_of, layer_of))

    def scan_body(carry, lps):
        x, aux = carry
        sent = []
        if len(kinds) > 1:
            # A period's leaves [p, ...] parted ONCE, so that their gradients are joined once: p indexings
            # would each hand back a cotangent as large as the period's (an expert stack: 528 MB) to be summed.
            lps = jax.tree.map(lambda leaf: [part[0] for part in jnp.split(leaf, len(kinds))], lps)
        for i, kind in enumerate(kinds):
            lp = lps if len(kinds) == 1 else jax.tree.map(lambda parts: parts[i], lps, is_leaf=lambda node: isinstance(node, list))
            x, a, s = layer_fns[kind](lp, x, tables[kind])
            aux = aux + a
            sent.append(s)
        return (x, aux), None if sent[0] is None else jnp.stack(sent)

    layers = params["layers"]
    if len(kinds) > 1:  # [L, ...] -> [periods, layers of a period, ...]
        layers = jax.tree.map(lambda leaf: leaf.reshape(-1, len(kinds), *leaf.shape[1:]), layers)
    (x, aux), sent = lax.scan(scan_body, (x, 0.0), layers)
    return _rms_norm(x, params["norm_f"], cfg.norm_eps), aux, None if sent is None else sent.reshape(cfg.n_layers, -1)


def forward_hidden(
    params: dict,
    tokens,
    cfg: TransformerConfig,
    mesh=None,
    attn_impl: str = "auto",
):
    """tokens [B, T] int32 -> (final hidden [B, T, D], moe aux)."""
    _refuse_inference_only(cfg, "forward_hidden")
    return _run_layers(params, tokens, cfg, mesh, attn_impl)[:2]


def moe_stats(params: dict, batch, cfg: TransformerConfig, mesh=None, attn_impl: str = "auto") -> dict:
    """What a training loop reports of its routed experts every N steps: one
    forward pass's worth (no loss, no gradient), jit-able. batch: {"tokens": [B,
    T+1]} as ``loss_fn`` takes it. Returns ``assignments`` [L, E] int32 (the
    tokens x experts a token each layer sent to each of ALL the experts),
    ``balance`` (the mean over the layers of ``moe.balance_term``: 1.0 where
    routing is uniform), ``held_share`` (the share of the assignments sent to
    the experts this program holds, ``expert_share``) and ``fullest_over_mean``
    (the fullest held expert's rows over the held experts' mean, the worst
    layer's)."""
    _refuse_inference_only(cfg, "moe_stats")
    if not cfg.routed_experts:
        raise ValueError("moe_stats: the configuration has no routed experts (experts_per_token = 0)")
    _, balance, sent = _run_layers(params, batch["tokens"][:, :-1], cfg, mesh, attn_impl)
    first = cfg.expert_share[0] * cfg.held_experts
    held = sent[:, first : first + cfg.held_experts].astype(jnp.float32)
    return {
        "assignments": sent,
        "balance": balance,
        "held_share": jnp.sum(held) / jnp.sum(sent),
        "fullest_over_mean": jnp.max(jnp.max(held, axis=1) / jnp.maximum(jnp.mean(held, axis=1), 1.0)),
    }


def _head(params):
    return params["lm_head"] if "lm_head" in params else params["embed"].T


def _logits(params, x):
    """Hidden states x [..., D] -> f32 logits [..., V] through the head."""
    return (x @ _head(params).astype(x.dtype)).astype(jnp.float32)


def forward(
    params: dict,
    tokens,
    cfg: TransformerConfig,
    mesh=None,
    attn_impl: str = "auto",
):
    """tokens [B, T] int32 -> logits [B, T, V] (f32)."""
    x, aux = forward_hidden(params, tokens, cfg, mesh=mesh, attn_impl=attn_impl)
    return _logits(params, x), aux


def loss_fn(params, batch, cfg: TransformerConfig, mesh=None, attn_impl: str = "auto"):
    """batch: {"tokens": [B, T+1]} next-token LM loss."""
    tokens = batch["tokens"]
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    if cfg.fused_loss:
        from ray_tpu.ops.losses import fused_lm_loss

        x, aux = forward_hidden(params, inputs, cfg, mesh=mesh, attn_impl=attn_impl)
        return fused_lm_loss(x, _head(params), targets, mesh=mesh) + cfg.balance_loss_coef * aux
    logits, aux = forward(params, inputs, cfg, mesh=mesh, attn_impl=attn_impl)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return nll.mean() + cfg.balance_loss_coef * aux


def make_train_step(cfg: TransformerConfig, optimizer, mesh=None, attn_impl: str = "auto"):
    """Returns train_step(params, opt_state, batch) -> (params, opt_state, loss).

    Pure function — callers jit it with in/out shardings, and with
    ``donate_argnums=(0, 1)`` where they rebind parameters and optimizer
    state to the result (see train/jax/ and __graft_entry__.py). Gradients
    are averaged over the batch; under a dp/fsdp-sharded batch the
    partitioner inserts their all-reduce. The loss is not left to it: given
    a ``mesh`` that splits the tokens, the fused loss scans each device's own
    rows inside a shard_map and sums one scalar and the head's gradient
    across devices (ops/losses.py; a mesh with ``tp`` > 1 keeps the
    partitioner's path), as the flash call runs on each device's own rows.
    """
    _refuse_inference_only(cfg, "make_train_step")

    def train_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch, cfg, mesh, attn_impl)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        import optax

        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    return train_step


def num_params(params) -> int:
    return sum(int(p.size) for p in jax.tree.leaves(params))
