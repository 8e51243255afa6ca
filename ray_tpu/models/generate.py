"""KV-cache inference for the flagship transformer: prefill + decode + sample.

The serving-side counterpart of models/transformer.py's training path. The
reference delegates inference to external frameworks (its Serve examples
wrap HF pipelines; SURVEY.md §5.7) — this is the TPU-native equivalent:

- static shapes throughout: the cache is preallocated at ``max_len`` and
  masked by position, so one compiled prefill + one compiled decode step
  serve every request length (no per-length recompiles);
- the whole generation loop is a ``lax.scan`` under one jit — no
  host→device round trip per token;
- prefill attends densely over the prompt rows only (MXU-bound, masked for
  causality + per-row padding; the unwritten generation region of the
  cache is never scored), decode attends one query row against the cache
  with a position mask (HBM-bandwidth-bound, as it should be) and GQA
  caches are read at KV width via grouped einsums — never repeated to H;
- bf16 cache, f32 logits/sampling; greedy, temperature, and top-k.

The layer over a cache is stated once (``_cached_layers``); the dense cache
and the block pool differ only in how a chunk's rows are written and which
rows are viewed, and an attention kind in what it writes and how it attends
over the view: keys and values per KV head (GQA: ``_project_qkv``,
``_cache_attention``), or one latent and one rotary key a token (MLA:
``_project_latent``, ``_latent_attention``, in absorbed form: the cache is
attended over as it lies, never expanded to per-head keys and values; a
decode step over a paged latent pool on a TPU does not gather a view either,
nor does a prefill chunk over one, nor a decode step over a paged pool of one
group of keys and values: ``kernel_reads``, ``_latent_attention_in_place``,
``_cache_attention_in_place``). Which kinds of layer a configuration has, in
which stack of ``params`` each lies and what each keeps in a cache is one
table: ``_KINDS``, ``_layer_plan``. Its
math intentionally mirrors transformer._attention_block/_mlp_block on the same
param pytree — decode diverges (cache writes, position masking) enough that
sharing one function would tangle the training hot path. A layer's MLP is what
its leaves say: dense SwiGLU; dropless routed experts with a shared expert
(``parallel/moe.routed_experts``: top-k of sigmoid scores, grouped matmuls, no
capacity, behind ``first_dense_layers`` dense layers stacked apart); or the
Switch layer the training tests keep (``parallel/moe.moe_layer``, top-1 with a
capacity, run lossless here). One layer form is not a mixer and then an MLP:
the shortcut-connected double layer (``_shortcut_layer``), two latent-attention
sub-layers and two dense FFNs with one branch of routed experts carried past
the second of each before it joins the residual path.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ray_tpu.models.transformer import (
    EXPERT_LAYERS,
    LINEAR_LAYERS,
    MAMBA_LAYERS,
    SHORTCUT_BRANCH_LEAVES,
    TransformerConfig,
    _logits,
    _layer_stacks,
    _period,
    _rms_norm,
    _rope,
    latent_softmax_scale,
    layer_rope,
)
from ray_tpu.ops import attention as _attention_ops
from ray_tpu.ops import hyper_connection
from ray_tpu.ops.latent_attention import paged_latent_attention, paged_latent_chunk_attention
from ray_tpu.ops.paged_attention import paged_attention
from ray_tpu.parallel import moe


_LANES = 128


def _latent_row_width(cfg: TransformerConfig) -> int:
    """A cached latent row: ``kv_lora_rank + qk_rope_head_dim`` values, then
    zeros up to a multiple of the TPU's 128 lanes. The tiled layout pads the
    minor axis to that anyway; a leaf whose minor axis is NOT such a multiple
    (576) is laid out with another axis minor-most, and every program that
    carries the pool through its layer scan then copies the whole pool in and
    out (two pool-sized copies a decode step; seen in the compiled decode
    program of GLM-4.7-Flash, PR 32). The padding is never written or read as
    anything but zeros: queries are zero there."""
    return -(-(cfg.kv_lora_rank + cfg.qk_rope_head_dim) // _LANES) * _LANES


_SUBLANES = 8


def _cache_heads(cfg: TransformerConfig) -> int:
    """KV heads of a cached row. Plain multi-head attention over more than 8
    heads that are not a multiple of 8 (30) caches zero heads up to the next
    multiple (32): the TPU tiles a leaf's two minor axes (8, 128), the padded
    tiles are allocated either way, and with a head axis that does not fill
    them the compiler lays the leaf out with the block's rows inside the heads
    for the gather and the other way round for the scatter, and copies the
    whole pool between the two, twice a layer a step (seen in the compiled
    decode program of Olmo-Hybrid's full layers, PR 41: 2 GB a copy; the same
    trap as ``_latent_row_width``). Queries get zero heads to match
    (``_project_qkv``) and their outputs are dropped (``_cache_attention``)."""
    KV = cfg.n_kv_heads
    if KV == cfg.n_heads and KV > _SUBLANES and KV % _SUBLANES:
        return -(-KV // _SUBLANES) * _SUBLANES
    return KV


def _heads_paired(cfg: TransformerConfig) -> bool:
    """TWO KV heads in one cached row, ``[head 2i | head 2i + 1]``, where two fill
    the TPU's 128 lanes and one does not: heads 64 wide, in the groups of a
    layer pattern (gathered as views; a pool of one group keeps the rows
    ``ops/paged_attention.py``'s kernel reads). A leaf ``[.., KV, 64]`` is laid
    out one way round for the gather and another for the scatter, and the whole
    pool is copied between them, six times a decode step at LFM2-24B-A2B's
    widths (2 x 1.07 GB a copy, 4.4 GB of temporaries; compiled for the v5e off
    the chip, PR 54: the trap of ``_latent_row_width`` and ``_cache_heads``
    again). Paired, nothing is padded and a token's bytes are the same; a query
    head carries zeros beside its own KV head's half of the row
    (``_project_qkv``), and of its weighted sum over such rows its half is kept
    (``_paired_half``)."""
    return bool(cfg.layer_kinds) and not cfg.latent_attention and 2 * cfg.head_dim == _LANES and cfg.n_kv_heads % 2 == 0


def _second_of_pair(cfg: TransformerConfig):
    """[H] bool: the query heads whose KV head is the second of its cached row (``_heads_paired``)."""
    return (jnp.arange(cfg.n_heads) // (cfg.n_heads // cfg.n_kv_heads)) % 2 == 1


def _paired_half(o, cfg: TransformerConfig):
    """o [B, T, H, 2 Dh], weighted sums over rows of two heads' values -> [B, T, H, Dh], each head's own half."""
    Dh = cfg.head_dim
    return jnp.where(_second_of_pair(cfg)[:, None], o[..., Dh:], o[..., :Dh])


def _cache_rows(cfg: TransformerConfig) -> dict:
    """What one token leaves in one layer of a cache, leaf name -> trailing
    shape: keys and values per KV head (per two heads where ``_heads_paired``),
    or (latent attention) one leaf holding the normed latent followed by the
    rotary key, as the absorbed form reads them."""
    if cfg.latent_attention:
        return {"ckv": (_latent_row_width(cfg),)}
    if _heads_paired(cfg):
        return {"k": (cfg.n_kv_heads // 2, 2 * cfg.head_dim), "v": (cfg.n_kv_heads // 2, 2 * cfg.head_dim)}
    return {"k": (_cache_heads(cfg), cfg.head_dim), "v": (_cache_heads(cfg), cfg.head_dim)}


_WINDOW, _FULL, _LINEAR, _MAMBA, _EXPERTS, _CONV = "window", "full", "linear", "mamba", "experts", "conv"

# THE table of kinds: kind of layer (None: every layer of a model without a
# pattern) -> (the suffix of the names of its group of cache leaves; how a call
# reaches the group). ``"table"``: a row's block table (a dense cache: the row
# itself), growing with the row, leaves ``_cache_rows``; ``"ring"``: the same
# leaves, named ``k_win`` / ``v_win``, of which a paged row holds no more than
# a ring (``_ring_access``); ``"state"``: no token's rows, what a serving slot
# carries whatever its row's length (``state_rows``, ``_StateAccess``: a
# recurrent state, a short convolution's last rows); None: the kind caches nothing.
# A new kind is a row here, its stack in ``transformer._layer_stacks``, its
# mixer, its ``state_rows`` and its branch of ``_cached_layers``' ``run_layer``.
_KINDS = {
    None: ("", "table"), _FULL: ("", "table"), _WINDOW: ("_win", "ring"),
    _LINEAR: ("", "state"), _MAMBA: ("", "state"), _EXPERTS: (None, None), _CONV: ("", "state"),
}


class _Kind(NamedTuple):
    """What the layer stack knows of one kind of a configuration's layers: the
    ``params`` key of the stack that holds them; whether that stack is the
    kind's ``own`` (a layer at its rank among its kind, which is its index into
    its group of cache leaves too) or shared (a layer at its position); how
    many ``layers`` of the kind the model has, the depth of its group; the
    group's suffix and how it is reached (``_KINDS``)."""

    stack: str
    own: bool
    layers: int
    group: Any
    reach: Any


class _Segment(NamedTuple):
    """Layers ``first .. first + depth`` of the model, which run as one scan:
    their ``kinds`` in order (``()`` without a pattern) and each kind's row."""

    first: int
    depth: int
    kinds: tuple
    rows: dict


def _layer_plan(cfg: TransformerConfig) -> list:
    """The segments of the layer stack in the order the model runs them, from
    ``transformer._layer_stacks``: a stack that holds no one kind (the leading
    dense layers, the layers of a model without stacks by kind) is a segment of
    its own, its kinds sharing it; the stacks that each hold one kind are ONE
    segment of all the layers behind those, the kinds interleaved as
    ``layer_kinds`` says. A kind's group of cache leaves is as deep as the
    model has layers of the kind, whichever segments they lie in."""
    stacks = _layer_stacks(cfg)
    layers = lambda kind: cfg.layer_kinds.count(kind) if kind else cfg.n_layers * cfg.attention_sublayers  # noqa: E731
    plan, first = [], 0
    for name, of in stacks.items():
        if of.kind:
            continue
        kinds = cfg.layer_kinds[first : first + of.depth]
        rows = {kind: _Kind(name, False, layers(kind), *_KINDS[kind]) for kind in dict.fromkeys(kinds or (None,))}
        plan.append(_Segment(first, of.depth, kinds, rows))
        first += of.depth
    own = {of.kind: _Kind(name, True, layers(of.kind), *_KINDS[of.kind]) for name, of in stacks.items() if of.kind}
    if own:
        plan.append(_Segment(first, cfg.n_layers - first, cfg.layer_kinds[first:], own))
    return plan


def _kinds(cfg: TransformerConfig) -> dict:
    """Kind -> its row of ``_layer_plan``, over all segments (of a kind that lies
    in two, the later one's: the group and how it is reached are the same)."""
    return {kind: row for segment in _layer_plan(cfg) for kind, row in segment.rows.items()}


def _group_rows(cfg: TransformerConfig, row: _Kind) -> dict:
    """What one layer of a kind's group of cache leaves holds, leaf name ->
    (trailing shape, dtype): a token's rows, or a slot's state."""
    if row.reach == "state":
        return state_rows(cfg)
    return {name + row.group: (shape, cfg.dtype) for name, shape in _cache_rows(cfg).items()}


def pool_reach(cfg: TransformerConfig) -> set:
    """The ways this configuration's groups of cache leaves are reached
    (``_KINDS``): what a paged call must be handed beside the block table, a
    ring a row (``"ring"``) or a slot a row (``"state"``)."""
    return {row.reach for row in _kinds(cfg).values() if row.reach}


def state_kind(cfg: TransformerConfig):
    """The kind of layer of which a serving slot keeps something whatever its
    row's length (``state_rows``): ``"linear"``, ``"mamba"``, ``"conv"``, or
    None without one."""
    return next((kind for kind, row in _kinds(cfg).items() if row.reach == "state"), None)


def state_rows(cfg: TransformerConfig) -> dict:
    """What one SLOT holds in one linear-attention layer, whatever its row's
    length, leaf name -> (trailing shape, dtype): the gated delta rule's state a
    head in float32 (it is summed into for a whole context), and the last
    ``linear_conv - 1`` rows of the query / key / value projection, which the
    next token's convolution reads. A Mamba-2 block's: the state [heads, head
    channels, ssm_state] in float32 and the last ``mamba_conv - 1`` rows of the
    convolution's channels (x, B and C ahead of it). A gated short convolution's:
    the last ``conv_cache - 1`` rows of ``B * u`` and no state. Empty without
    such layers."""
    if _CONV in cfg.layer_kinds:
        return {"conv": ((cfg.conv_cache - 1, cfg.d_model), cfg.dtype)}
    if _MAMBA in cfg.layer_kinds:
        Hm, P, N = cfg.mamba_heads, cfg.mamba_head_dim, cfg.ssm_state
        return {
            "state": ((Hm, P, N), jnp.float32),
            "conv": ((cfg.mamba_conv - 1, Hm * P + 2 * cfg.ssm_groups * N), cfg.dtype),
        }
    if _LINEAR not in cfg.layer_kinds:
        return {}
    Hl, dk, dv = cfg.linear_heads, cfg.linear_key_dim, cfg.linear_value_dim
    return {
        "state": ((Hl, dk, dv), jnp.float32),
        "conv": ((cfg.linear_conv - 1, Hl * (2 * dk + dv)), cfg.dtype),
    }


def _group_bytes(cfg: TransformerConfig, row: _Kind) -> int:
    """Bytes of a kind's group, all its layers, a token or (a state) a slot."""
    return row.layers * sum(math.prod(shape) * jnp.dtype(dtype).itemsize for shape, dtype in _group_rows(cfg, row).values())


def state_slot_bytes(cfg: TransformerConfig) -> int:
    """Bytes one slot holds in the group of the layers that keep a state, all
    its layers: beside ``cache_token_bytes``, which grows with a row, this does not."""
    return sum(_group_bytes(cfg, row) for row in _kinds(cfg).values() if row.reach == "state")


def cache_token_bytes(cfg: TransformerConfig) -> dict:
    """Bytes one token holds in each group of cache leaves, all the group's
    layers: ``{"full": n}``, and under a layer pattern ``"window"`` beside it."""
    return {kind or _FULL: _group_bytes(cfg, row) for kind, row in _kinds(cfg).items() if row.reach in ("table", "ring")}


_NO_DENSE_CACHE = {
    _LINEAR: "linear-attention layers", _MAMBA: "Mamba-2 state-space blocks", _EXPERTS: "single-mixer blocks",
    _CONV: "gated short-convolution layers",
}


def init_cache(cfg: TransformerConfig, batch: int, max_len: int):
    """Preallocated cache, every leaf [L, B, max_len, ...] (``_cache_rows``):
    k/v [.., KV, Dh], or ckv [.., the latent and the rotary key] (bf16 on
    TPU — cache reads are the decode bandwidth bill). Under a layer pattern
    the leaves of each kind's layers (``_KINDS``); a dense cache keeps
    ``max_len`` rows for a window layer too and masks them. Layers that keep a
    state are refused: it cannot be rewound to a position, which
    ``speculative_generate`` and ``decode_chunk``'s callers count on; they and
    single-mixer blocks are served through the paged cache only (``init_paged_cache``)."""
    kinds = _kinds(cfg)
    for kind, row in kinds.items():
        if row.reach not in ("table", "ring"):
            raise NotImplementedError(
                "a dense cache (init_cache: prefill / decode_step / generate / speculative_generate) cannot hold "
                f"{_NO_DENSE_CACHE[kind]} (layer_kinds has {kind!r}): they run over the paged cache only "
                "(init_paged_cache, serve/llm/engine.py)"
            )
    return {
        name: jnp.zeros((row.layers, batch, max_len, *shape), dtype)
        for row in kinds.values()
        for name, (shape, dtype) in _group_rows(cfg, row).items()
    }


def _project_qkv(lp, x, positions, cfg, rope: tuple | None = ()):
    """GQA: (q [B, T, H, Dh], the rows to cache {"k", "v"}: [B, T, KV, Dh]; where
    ``_heads_paired``, q [B, T, H, 2 Dh] and rows [B, T, KV / 2, 2 Dh]).
    ``cfg.qk_norm``: queries and keys normed over the head's width first.
    ``rope``: what ``transformer.layer_rope`` says of the layer's kind, None for
    no positional encoding, else the scaling of its rotary tables (() plain)."""
    B, T, _ = x.shape
    H, KV, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    h = _rms_norm(x, lp["attn_norm"], cfg.norm_eps) if cfg.pre_norms else x

    def heads(w, norm, n):  # ``cfg.qk_norm_whole``: normed over the whole projection, before it is split into heads
        y = h @ lp[w].astype(h.dtype)
        if norm and cfg.qk_norm_whole:
            y = _rms_norm(y, lp[norm], cfg.norm_eps)
        return y.reshape(B, T, n, Dh)

    q, k, v = heads("wq", "q_norm", H), heads("wk", "k_norm", KV), heads("wv", None, KV)
    if cfg.qk_norm:
        q, k = _rms_norm(q, lp["q_norm"], cfg.norm_eps), _rms_norm(k, lp["k_norm"], cfg.norm_eps)
    if _cache_heads(cfg) != KV:  # zero heads up to what a cached row holds
        q, k, v = (jnp.pad(a, ((0, 0), (0, 0), (0, _cache_heads(cfg) - KV), (0, 0))) for a in (q, k, v))
    if rope is not None:
        q, k = _rope(q, positions, cfg.rope_theta, rope), _rope(k, positions, cfg.rope_theta, rope)
    if _heads_paired(cfg):  # a row of two heads, and each query zeros beside its own head's half
        zeros = jnp.zeros_like(q)
        q = jnp.where(_second_of_pair(cfg)[:, None], jnp.concatenate([zeros, q], axis=-1), jnp.concatenate([q, zeros], axis=-1))
        k, v = (a.reshape(B, T, KV // 2, 2 * Dh) for a in (k, v))
    return q, {"k": k, "v": v}


def _latent_kv_up(lp, cfg):
    """``wkv_b`` by head: (W_uk [R, H, nope], W_uv [R, H, v])."""
    w = lp["wkv_b"].reshape(cfg.kv_lora_rank, cfg.n_heads, -1)
    return w[..., : cfg.qk_nope_head_dim], w[..., cfg.qk_nope_head_dim :]


def _project_latent(lp, x, positions, cfg):
    """MLA: (q in absorbed form [B, T, H, W], the row to cache {"ckv": [B, T,
    W]}), W the padded row width (``_latent_row_width``): ``[c, k_r, 0..]``
    against ``[q~, q_rope, 0..]``. ``q_nope`` is carried through W_uk into the
    latent's space here, once a query, so that a score is one dot product
    with the cached row: ``q~ . c + q_rope . k_r``."""
    B, T, _ = x.shape
    H, R, P = cfg.n_heads, cfg.kv_lora_rank, cfg.qk_rope_head_dim
    h = _rms_norm(x, lp["attn_norm"], cfg.norm_eps)
    c_q = _rms_norm(h @ lp["wq_a"].astype(h.dtype), lp["q_norm"], cfg.norm_eps)
    if cfg.mla_scale_q_lora:  # on the latent, so on both halves of every head's query
        c_q = c_q * (cfg.d_model / cfg.q_lora_rank) ** 0.5
    q = (c_q @ lp["wq_b"].astype(h.dtype)).reshape(B, T, H, -1)
    q_nope, q_rope = q[..., : cfg.qk_nope_head_dim], q[..., cfg.qk_nope_head_dim :]
    kv = h @ lp["wkv_a"].astype(h.dtype)
    c = _rms_norm(kv[..., :R], lp["kv_norm"], cfg.norm_eps)
    if cfg.mla_scale_kv_lora:  # the cached row is the SCALED latent: the absorbed form and both kernels read it as it lies
        c = c * (cfg.d_model / R) ** 0.5
    k_rope = _rope(kv[..., None, R:], positions, cfg.rope_theta, cfg.rope_scaling)[:, :, 0]
    w_uk, _ = _latent_kv_up(lp, cfg)
    q_abs = jnp.einsum("bthn,rhn->bthr", q_nope, w_uk.astype(h.dtype))
    pad = _latent_row_width(cfg) - R - P
    q_full = jnp.concatenate(
        [q_abs, _rope(q_rope, positions, cfg.rope_theta, cfg.rope_scaling), jnp.zeros((B, T, H, pad), q.dtype)], axis=-1
    )
    return q_full, {"ckv": jnp.concatenate([c, k_rope, jnp.zeros((B, T, pad), c.dtype)], axis=-1)}


def _latent_attention(lp, q, view, pos_mask, cfg):
    """Absorbed-form attention over cached latents: q [B, T, H, W]
    against ``view["ckv"]`` [B, S, W] -> [B, T, H * v]. The weighted
    sum is taken over the latents (R wide) and only then carried through
    W_uv, so no per-head key or value of a cached token is ever formed."""
    ckv = view["ckv"]
    R = cfg.kv_lora_rank
    scale = latent_softmax_scale(cfg)
    s = jnp.einsum("bqhr,bkr->bhqk", q, ckv, preferred_element_type=jnp.float32)
    s = jnp.where(pos_mask[:, None], s * scale, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    # Over the whole row, the rotary key and the padding with it, and the
    # latent's columns cut from the small result: a slice of the view would
    # be one more copy of it.
    o = jnp.einsum("bhqk,bkr->bhqr", p.astype(ckv.dtype), ckv,
                   preferred_element_type=jnp.float32)[..., :R].astype(q.dtype)
    return _latent_values(lp, o, cfg)


def _latent_values(lp, o, cfg):
    """The heads' weighted sums of latents o [B, H, q, R] through W_uv: [B, q, H * v]."""
    _, w_uv = _latent_kv_up(lp, cfg)
    o = jnp.einsum("bhqr,rhv->bqhv", o, w_uv.astype(o.dtype))
    return o.reshape(*o.shape[:2], -1)


def one_kv_group(cfg: TransformerConfig) -> bool:
    """A pool of ONE group of key and value leaves: standard attention and no
    layer pattern. What ``ops/paged_attention.py``'s kernel reads in place and
    what a decode step can carry a chunk over (``paged_decode_step_with_chunk``)."""
    return not cfg.latent_attention and not cfg.layer_kinds


def kernel_reads(cfg: TransformerConfig, paged: bool, q: int) -> bool:
    """Whether a call of the layer stack reads its cache in place, through a
    kernel that walks each row's block table to the row's length, by what the
    code can see: a pool such a kernel is written for, PAGED, a TPU backend.
    A latent pool (``ops/latent_attention.py``) at ANY ``q`` queries a row: a
    decode row and a prefill chunk each have their kernel over the one walk;
    ``one_kv_group`` (``ops/paged_attention.py``) at one query a row, its
    chunk keeps the view. Everything else (a pattern's groups, the dense
    cache, any CPU run) gathers ``_paged_view`` and attends over it. Such a
    call's cost does not grow with the table's width: ``_cached_layers`` asks
    for the program, ``LLMEngine`` for the table it hands a decode step (one
    width, one program) and for what it counts, and they cannot disagree."""
    written = bool(cfg.latent_attention) or (one_kv_group(cfg) and q == 1)
    return written and paged and _attention_ops._on_tpu()


def latent_kernel_reads(cfg: TransformerConfig, paged: bool, q: int) -> bool:
    """``kernel_reads`` of a latent pool."""
    return bool(cfg.latent_attention) and kernel_reads(cfg, paged, q)


def kv_kernel_reads(cfg: TransformerConfig, paged: bool, q: int) -> bool:
    """``kernel_reads`` of a pool of one group of key and value leaves."""
    return one_kv_group(cfg) and kernel_reads(cfg, paged, q)


def experts_run(cfg: TransformerConfig, rows: int) -> str | None:
    """``moe.experts_run`` of a call of ``rows`` rows (tokens) through ``cfg``'s
    routed experts, None without them: ``_cached_layers`` asks it for how the
    program holds the expert stacks, ``LLMEngine`` for what it counts."""
    if not cfg.routed_experts:
        return None
    return moe.experts_run(rows * cfg.experts_per_token, cfg.num_experts, cfg.d_model, cfg.d_expert)


def _decode_lengths(tables, positions):
    """Rows a decode row's query at ``positions`` [B, 1] sees through
    ``tables`` [B, n_max]: its own and those before it; of a row whose table
    starts at the null block (an inactive slot) none, and it attends to zeros."""
    return jnp.where(tables[:, 0] != 0, positions[:, 0] + 1, 0)


def _latent_attention_in_place(lp, q, ckv, at, tables, positions, ends, cfg):
    """``_latent_attention`` of q [B, T, H, W] at ``positions`` [B, T] over
    layer ``at`` of the pool leaf ``ckv`` [L, N, Bs, W] through ``tables`` [B,
    n_max], with no view: either kernel returns what the ``bhqk,bkr->bhqr``
    product does. One query a row (``_decode_lengths``), or a chunk whose rows
    under ``ends`` [B] are real (None: all): causal among themselves behind
    what the row held, and nothing of an inactive row (``_decode_lengths``' test)."""
    scale = latent_softmax_scale(cfg)
    if q.shape[1] == 1:
        o = paged_latent_attention(q, ckv, at, tables, _decode_lengths(tables, positions), sm_scale=scale)
    else:
        fed = positions[:, -1] + 1
        real = fed if ends is None else jnp.minimum(fed, jnp.asarray(ends, jnp.int32))
        o = paged_latent_chunk_attention(
            q, ckv, at, tables, positions[:, 0], jnp.where(tables[:, 0] != 0, real, 0), sm_scale=scale,
            value_width=cfg.kv_lora_rank,
        )
    return _latent_values(lp, o[..., : cfg.kv_lora_rank], cfg)


def _swiglu(h, wg, wi, wo):
    return (jax.nn.silu(h @ wg.astype(h.dtype)) * (h @ wi.astype(h.dtype))) @ wo.astype(h.dtype)


def _relu2(h, wi, wo):
    """An expert without a gate matrix: ``relu(h W_up)^2 W_down``."""
    return jnp.square(jax.nn.relu(h @ wi.astype(h.dtype))) @ wo.astype(h.dtype)


# The leaves of a routed-expert stack that the layer scan does not slice.
_EXPERT_STACKS = ("wg_e", "wi_e", "wo_e")


def _residual(lp, x, sub, cfg, branch):
    """THE join of a sub-layer's branch with the residual path, every kind of
    layer's: ``branch(u) -> (out, whatever else it returns)`` reads the path's
    value a token ``u`` [B, q, D] (and norms it itself) and its ``out`` [B, q, D]
    joins the path. Returns (the path after the join, what else the branch
    returned). The plain residual: ``u`` is ``x`` and the join ``x + out``.
    Hyper-connections (``cfg.hc_mult``; ops/hyper_connection.py): ``x`` is the
    stream [B, q, hc_mult * D], ``u`` a mixture of its rows by the leaves
    ``hc_<sub>_*`` of ``lp`` (``sub``: ``"attn"``, the mixer's, or ``"mlp"``),
    and ``out`` is written back to every row beside a mixing of the rows."""
    if not cfg.hc_mult:
        out, rest = branch(x)
        return x + out, rest
    with jax.named_scope("hyper_connection_mix"):
        u, post, res = hyper_connection.mix(x, lp[f"hc_{sub}_phi"], lp[f"hc_{sub}_b"], lp[f"hc_{sub}_alpha"], cfg)
    out, rest = branch(u)
    with jax.named_scope("hyper_connection_join"):
        return hyper_connection.join(x, out, post, res), rest


def _routed(lp, h, cfg, valid, layer):
    """``moe.routed_experts`` of the normed rows h [B, q, D] as ``cfg`` says: (this
    program's part of the routed result [B, q, D], the tokens sent to each held
    expert, behind them two numbers more where the router has identity experts
    (``_picks_apart``), each row's picks among the router's columns [B * q, k])."""
    B, q, D = h.shape
    out, sent, chosen, _ = moe.routed_experts(
        lp, h.reshape(B * q, D), k=cfg.experts_per_token, scale=cfg.routed_scaling_factor,
        valid=None if valid is None else valid.reshape(B * q), layer=layer, share=cfg.expert_share,
        score=cfg.router_score, identity=cfg.zero_experts, normalize=cfg.router_normalize,
    )
    if cfg.zero_experts:
        sent = _picks_apart(sent, chosen, valid, cfg)
    return out.reshape(B, q, D), sent, chosen


def _picks_apart(sent, chosen, valid, cfg):
    """``sent`` [held experts] with two numbers behind it, for the counters of a
    router with identity experts (``MOE_COUNTS``): the real rows' picks that were
    identities, and the real rows none of whose picks was an expert held here
    (whose branch costs this program the identity term at most). chosen [N, k]
    among the router's columns, ``valid`` [B, q] or None."""
    real = jnp.ones(chosen.shape[:1], bool) if valid is None else valid.reshape(-1)
    first = cfg.expert_share[0] * cfg.held_experts
    here = (chosen >= first) & (chosen < first + cfg.held_experts)
    identities = jnp.sum((chosen >= cfg.num_experts) & real[:, None], dtype=sent.dtype)
    without = jnp.sum(real & ~jnp.any(here, axis=-1), dtype=sent.dtype)
    return jnp.concatenate([sent, jnp.stack([identities, without])])


def _mlp(lp, x, cfg, valid=None, layer=None):
    """(the residual path joined with MLP(norm(.)) (``_residual``), the MLP this
    layer's leaves hold; with routed experts the tokens sent to each expert [E]
    int32 and each row's experts [B, q, k] int32, else None and None).
    ``valid`` [B, q] marks the rows that are real tokens (routed experts
    only: padding reaches no expert and is not counted). ``layer``: the
    expert leaves of ``lp`` are whole stacks and this is the layer to run
    (``routed_experts``)."""

    def branch(u):
        h = _rms_norm(u, lp["mlp_norm"], cfg.norm_eps) if cfg.pre_norms else u

        def post(out):  # ``cfg.post_norms``: the branch is normed once more before it joins the residual
            return _rms_norm(out, lp["mlp_post_norm"], cfg.norm_eps) if cfg.post_norms else out

        if "gate" not in lp:
            return post(_swiglu(h, lp["wg"], lp["wi"], lp["wo_mlp"])), (None, None)
        if cfg.routed_experts:
            out, sent, chosen = _routed(lp, h, cfg, valid, layer)
            if "wg_s" in lp:
                out = out + _swiglu(h, lp["wg_s"], lp["wi_s"], lp["wo_s"])
            elif "wi_s" in lp:
                out = out + _relu2(h, lp["wi_s"], lp["wo_s"])
            return post(out), (sent, chosen)
        from ray_tpu.models.transformer import _moe_mlp

        # LOSSLESS dispatch at inference: capacity_factor=E gives every
        # token a slot (capacity == T), so routing is per-token and
        # independent of batch padding — ragged rows behave exactly like
        # solo rows, and prefill agrees with T=1 decode. Training's
        # capacity drops (expert_capacity_factor) are an efficiency
        # approximation that inference deliberately does not replicate.
        # Aux loss is meaningless at inference and discarded.
        out, _aux = _moe_mlp(lp, h, float(cfg.num_experts))
        return post(out), (None, None)

    x, (sent, chosen) = _residual(lp, x, "mlp", cfg, branch)
    return x, sent, None if chosen is None else chosen.reshape(*x.shape[:2], -1)


def _shortcut_layer(twice, l, x, pool, cfg, attention_0, attention_1, experts):
    """THE shortcut-connected double layer (``cfg.shortcut_moe``), one scan body:

        h0 = x  + MLA_0(norm(x))
        u0 = norm(h0)
        m  = experts(u0)                    the shortcut branch: read here ...
        h1 = h0 + FFN_0(u0)
        h2 = h1 + MLA_1(norm(h1))
        y  = h2 + FFN_1(norm(h2)) + m       ... and joined here, an attention and an FFN later

    ``twice``: the WHOLE stack of what a sub-layer owns, ``[layers, 2, ...]``
    (sub-layer 0 first), of which this is layer ``l`` (traced). Each of a
    sub-layer's leaves is one dynamic slice of its stack that one operation
    reads: sliced by the layer scan, a layer's ``[2, ...]`` pair has two readers
    and is materialised first, both FFNs' matrices copied every layer of every
    step (13 of a decode step's 43 ms at the published widths, v5e, PR 61: the
    trap of ``_EXPERT_STACKS`` again). ``attention_i(u, pool=, lp=)``: sub-layer
    i's attention branch over its own layer of the cache -> (out, pool);
    ``experts(u0, pool)`` -> (m, pool, sent). In a deployment ``m`` is what the
    exchange between the chips that share the experts brings back while the dense
    path runs; here it is this program's part (``expert_share``) and the identity
    term, carried beside the residual path and nothing stands in for the rest."""
    def own(leaf, i):
        return lax.dynamic_slice(leaf, (l, i) + (0,) * (leaf.ndim - 2), (1, 1, *leaf.shape[2:])).reshape(leaf.shape[2:])

    sub = [{name: own(leaf, i) for name, leaf in twice.items()} for i in range(2)]

    def ffn(i, u):
        return _swiglu(u, sub[i]["wg"], sub[i]["wi"], sub[i]["wo_mlp"])

    x, pool = _residual(sub[0], x, "attn", cfg, partial(attention_0, pool=pool, lp=sub[0]))
    u0 = _rms_norm(x, sub[0]["mlp_norm"], cfg.norm_eps)
    m, pool, sent = experts(u0, pool)
    x = x + ffn(0, u0)
    x, pool = _residual(sub[1], x, "attn", cfg, partial(attention_1, pool=pool, lp=sub[1]))
    out = ffn(1, _rms_norm(x, sub[1]["mlp_norm"], cfg.norm_eps))
    with jax.named_scope("moe_shortcut_join"):
        return x + out + m, pool, sent


def _cache_attention(q, ck, cv, pos_mask, cfg):
    """q: [B, T, H, Dh] against cache rows ck/cv: [B, S, KV, Dh], masked by
    pos_mask [B, T, S] (True = attend). GQA uses grouped einsums so K/V are
    READ at KV width — never physically repeated to H heads (the cache read
    is the decode bandwidth bill; repeating would multiply it by H/KV)."""
    B, T, H, Dh = q.shape
    KV = ck.shape[2]
    scale = cfg.head_dim ** -0.5
    if KV != H:
        rep = H // KV
        qg = q.reshape(B, T, KV, rep, Dh)
        s = jnp.einsum("bqgrd,bkgd->bgrqk", qg, ck, preferred_element_type=jnp.float32)
        s = jnp.where(pos_mask[:, None, None], s * scale, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bgrqk,bkgd->bqgrd", p.astype(cv.dtype), cv,
                       preferred_element_type=jnp.float32)
        return o.reshape(B, T, H, Dh).astype(q.dtype)
    # Plain multi-head attention (a cached head a query head), one formulation
    # whatever the configuration. One query row a head is no matmul to the
    # TPU's compiler: it casts the whole view to float32, writes it out and
    # multiplies and sums on the vector unit (1 GB a layer for keys, 1 GB for
    # values at 8 x 8192 tokens x 32 heads, PR 41). Eight rows, the added ones
    # zeros, go to the matrix unit in bfloat16 as a grouped query's rows do.
    if T < _SUBLANES:
        q = jnp.pad(q, ((0, 0), (0, _SUBLANES - T), (0, 0), (0, 0)))
        pos_mask = jnp.pad(pos_mask, ((0, 0), (0, _SUBLANES - T), (0, 0)), constant_values=True)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, ck, preferred_element_type=jnp.float32)
    s = jnp.where(pos_mask[:, None], s * scale, -jnp.inf)
    # Normalised AFTER the weighted sum, a [B, q, H] division where ``softmax``
    # divides [B, H, q, S]: the TPU's compiler turns that division by a
    # broadcast sum into a reduce-window over all S keys (160 of a 238 ms
    # prefill chunk at 512 queries x 8192 keys, v5e, PR 41), and the scores are
    # written once less. The weights go to the matrix unit in the cache's dtype
    # either way; their sum is float32.
    e = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    o = jnp.einsum("bhqk,bkhd->bqhd", e.astype(cv.dtype), cv, preferred_element_type=jnp.float32)
    o = o / jnp.moveaxis(jnp.sum(e, axis=-1), 1, 2)[..., None]
    # Without the added rows, and without the zero heads a cached row may carry (``_cache_heads``).
    return o[:, :T, : cfg.n_heads].astype(q.dtype)


def _cache_attention_in_place(q, k, v, at, tables, positions, cfg):
    """``_cache_attention`` of one query a row, q [B, 1, H, Dh] at
    ``positions`` [B, 1], over layer ``at`` of the pool leaves ``k``, ``v``
    [L, N, Bs, KV, Dh] through ``tables`` [B, n_max], with no view and under
    ``_cache_mask``'s mask (``_decode_lengths``; the sliding window, where a
    row the table can hold may outgrow it: Mistral's 4096 over 2560 tokens is none)."""
    window = cfg.sliding_window if cfg.sliding_window < tables.shape[1] * k.shape[2] else 0
    o = paged_attention(
        q, k, v, at, tables, _decode_lengths(tables, positions), sm_scale=cfg.head_dim ** -0.5, window=window
    )
    return o[:, :, : cfg.n_heads]  # without the zero heads a cached row may carry (``_cache_heads``)


def _embed_chunk(params, tokens, pos, cfg):
    """tokens [B, q] fed at positions pos[b].. (``pos`` [B] or a scalar):
    what the residual path starts from [B, q, D] (``_residual``) and positions [B, q]."""
    B, q = tokens.shape
    pos_b = jnp.broadcast_to(pos, (B,))
    x = params["embed"].astype(cfg.dtype)[tokens]
    if cfg.embed_multiplier != 1.0:
        x = x * cfg.embed_multiplier
    if cfg.hc_mult:  # the residual path starts as hc_mult rows of the embedding: [B, q, hc_mult * D]
        x = hyper_connection.widen(x, cfg.hc_mult)
    offs = jnp.arange(q, dtype=jnp.int32)
    return x, pos_b[:, None] + offs[None, :]


def _cache_mask(positions, n_keys: int, window: int, key_len=None, key_pos=None, block: int = 0):
    """[B, q, n_keys], True = attend. Causal against the cache: the query at
    positions[b, j] sees rows at positions <= its own — under ``key_len[b]``
    where given (a ragged prompt's padding) and within the sliding window.
    View row i holds position i, or ``key_pos[b, i]`` where given (a ring's
    rows, ``_ring_access``; negative: a row nothing was written to yet).
    ``block`` (``cfg.block_diffusion``): causal BETWEEN blocks of that many
    aligned positions and two-way INSIDE one: the query at position p sees the
    rows under the end of p's own block, ``(p // block + 1) * block``, in a
    prompt's chunk and in a block's pass alike."""
    if key_pos is None:
        k_pos = jnp.arange(n_keys, dtype=jnp.int32)
        keys = lambda: k_pos[None, None, :]  # noqa: E731
    else:
        keys = lambda: key_pos[:, None, :]  # noqa: E731
    if block:
        mask = keys() < ((positions // block + 1) * block)[:, :, None]
    else:
        mask = keys() <= positions[:, :, None]
    if key_len is not None:
        mask = mask & (keys() < key_len[:, None, None])
    if window:
        mask &= positions[:, :, None] - keys() < window
    if key_pos is not None:
        mask &= keys() >= 0
    return mask


# A cache may carry, beside its pool leaves, this one: int32 [2, expert layers,
# E + 3] counters that the layer stack adds each call's routing to, so that a
# serving engine reads them when asked and not a step. Axis 0: calls that fed
# one token a row (decode steps), then all others (prefill chunks). Columns
# 0..E-1: tokens sent to each expert; E: experts touched; E + 1: tokens on the
# fullest expert; E + 2: calls that routed any token. Where the program holds a
# share of the experts (``cfg.expert_share``) E is the experts HELD, the columns
# count assignments to them, and one more column, E + 3, counts every
# assignment the router made, held or not.
MOE_COUNTS = "moe_counts"
# And this one: int32 [expert layers, B, S] or [expert layers, num_blocks,
# block_size], beside each cached token's row the experts that token took in
# each expert layer, written where its row is written and kept as long (a
# block found again in a prefix cache brings its tokens' choices with it). One
# word a token a layer, the k expert ids in ``_expert_bits`` bits each
# (``unpack_experts``): a minor axis of k would be padded to the TPU's 128
# lanes. A choice that one word cannot hold (8 of 128: 56 bits) takes as many
# words as it needs, on a LEADING axis: [words, expert layers, ...]. Under a
# layer pattern the words lie beside the full layers' blocks, which hold every
# token of a row. What a rollout hands its trainer for routing replay, and what
# the benchmark's float32 reference is held to where two scores nearly tie.
MOE_CHOICE = "moe_choice"


def expert_layers(cfg: TransformerConfig) -> int:
    """Layers (or single-mixer blocks) with routed experts."""
    return sum(of.depth for of in _layer_stacks(cfg).values() if of.mlp == "routed")


def counts_every_pick(cfg: TransformerConfig) -> bool:
    """Whether ``MOE_COUNTS`` has a column for every pick the router made: where the held experts' own do not add up
    to them, a program that holds a share of the experts or whose router has identity experts."""
    return cfg.expert_share[1] > 1 or cfg.zero_experts > 0


def init_moe_counts(cfg: TransformerConfig):
    """[steps | chunks, expert layers, columns]: the tokens sent to each held expert, the experts touched, the fullest
    expert's load, whether the call routed a token, every pick (``counts_every_pick``) and, where the router has
    identity experts, the picks that were identities and the rows with no pick held here (``_picks_apart``)."""
    return jnp.zeros((2, expert_layers(cfg), cfg.held_experts + 3 + counts_every_pick(cfg) + 2 * (cfg.zero_experts > 0)), jnp.int32)


def _expert_bits(cfg: TransformerConfig) -> int:
    return max(1, (cfg.router_width - 1).bit_length())


def _choice_words(cfg: TransformerConfig) -> tuple:
    """(int32 words a token a layer of ``MOE_CHOICE``, expert ids a word)."""
    per_word = min(cfg.experts_per_token, 31 // _expert_bits(cfg))
    return -(-cfg.experts_per_token // per_word), per_word


def init_moe_choice(cfg: TransformerConfig, *rows: int):
    """``rows``: (batch, max_len) beside ``init_cache``, (num_blocks,
    block_size) beside ``init_paged_cache``."""
    words, _ = _choice_words(cfg)
    lead = (words,) if words > 1 else ()
    return jnp.zeros((*lead, expert_layers(cfg), *rows), jnp.int32)


def unpack_experts(words, cfg: TransformerConfig):
    """Words of a ``MOE_CHOICE`` leaf, [...] or (a choice of several words)
    [words, ...] -> expert ids [..., k] (NumPy or jax)."""
    bits = _expert_bits(cfg)
    n_words, per_word = _choice_words(cfg)
    if n_words == 1:
        return (words[..., None] >> (bits * np.arange(cfg.experts_per_token))) & ((1 << bits) - 1)
    ids = (words[..., None] >> (bits * np.arange(per_word))) & ((1 << bits) - 1)  # [words, ..., per word]
    ids = np.moveaxis(np.asarray(ids), 0, -2)
    return ids.reshape(*ids.shape[:-2], -1)[..., : cfg.experts_per_token]


class _Access(NamedTuple):
    """How the layers of one group reach their part of a cache:
    ``write(c, l, rows)`` puts a chunk's rows [B, q, ...] of one leaf into
    layer l of the group, ``view(c, l)`` takes the rows [B, S, ...] to attend
    over, ``key_pos`` [B, S] is the position each view row holds (None: row i
    holds position i), ``tables`` [B, n_max] the block tables the view gathers
    through (a paged pool's; None: a dense cache, a ring), ``ends`` [B] the
    position the fed rows that ``write`` keeps stop short of (``valid_to``;
    None: it keeps them all)."""

    write: Any
    view: Any
    key_pos: Any = None
    tables: Any = None
    ends: Any = None


class _Part(NamedTuple):
    """One of several batches that share a call of ``_cached_layers``: rows
    ``start : start + B * q`` of its flat input are this part's [B, q] tokens at
    ``positions`` [B, q], reaching the cache through ``access``."""

    start: int
    positions: Any
    access: _Access


class _StateAccess(NamedTuple):
    """How the linear layers reach their group of a cache: no positions and no
    table. Row b of the call is slot ``slots[b]`` of the group's leaves (None:
    row b IS slot b, and the call has a row for every slot: a decode step);
    ``fresh[b]``: the row's state starts from zero (a request's first chunk,
    whoever held the slot before); ``n_valid[b]``: how many of the row's q
    tokens are real, from the first. The others move neither the state nor the
    convolution's carried rows (a padded last chunk, an inactive slot)."""

    slots: Any
    fresh: Any
    n_valid: Any


def _slot_rows(pool, name, at, acc: _StateAccess, B):
    """Leaf ``name`` of the state group, layer ``at``, the call's rows' slots:
    zeros where a row's state starts afresh."""
    leaf = pool[name]
    rows = lax.dynamic_index_in_dim(leaf, at, 0, keepdims=False) if acc.slots is None else leaf[at, acc.slots]
    return jnp.where(acc.fresh.reshape(B, *[1] * (rows.ndim - 1)), jnp.zeros_like(rows), rows)


def _put_slot_rows(pool, name, at, acc: _StateAccess, rows):
    leaf = pool[name]
    if acc.slots is None:
        return lax.dynamic_update_index_in_dim(leaf, rows.astype(leaf.dtype), at, 0)
    return leaf.at[at, acc.slots].set(rows.astype(leaf.dtype))


def _rows_to_carry(seen, n_valid, K):
    """Of ``seen`` [B, K - 1 + q, C] (the rows a convolution over K tokens was
    carried, then the call's own) the K - 1 before each row's first token that
    is not real: the chunk's last real ones, or (none real) the rows carried in."""
    return jax.vmap(lambda rows, n: lax.dynamic_slice_in_dim(rows, n, K - 1, axis=0))(seen, n_valid)


def _mamba_mixer(lp, x, pool, at, acc: _StateAccess, cfg):
    """A Mamba-2 block's mixer over layer ``at`` of the state group: x [B, q, D]
    -> (the gated, normed heads [B, q, heads * head channels] that ``wo`` takes,
    the pool with the rows' state and carried convolution rows moved on).

    ``z = h w_z``, ``u = h w_xbc``, ``dt = softplus(h w_dt + dt_bias)`` of the
    normed input h; each column of u through a causal convolution over the
    last ``mamba_conv`` tokens (those before the chunk are the slot's carried
    rows, zeros ahead of a request's first token), its bias and SiLU; ``[x | B
    | C] = u`` (heads * channels, groups * state, groups * state); the
    recurrence with ``A = -exp(A_log)`` and the skip ``D`` (ops/ssm.py: the
    chunked form for q > 1, the step for q == 1); ``y * silu(z)``, then RMSNorm
    over each GROUP's channels with a learned weight. From the convolution on
    everything is float32."""
    from ray_tpu.ops.ssm import mamba2_chunk, mamba2_step

    B, q, _ = x.shape
    Hm, P, N, G, K = cfg.mamba_heads, cfg.mamba_head_dim, cfg.ssm_state, cfg.ssm_groups, cfg.mamba_conv
    inner = Hm * P
    f32 = jnp.float32
    h = _rms_norm(x, lp["attn_norm"], cfg.norm_eps)
    z = h @ lp["w_z"].astype(h.dtype)
    u = h @ lp["w_xbc"].astype(h.dtype)
    # Rounded to its dtype HERE, whatever the compiler fuses the product into: the convolution must read of a
    # token that is the call's own what it will read of it as a carried row of the slot, in the next call.
    u = lax.reduce_precision(u, jnp.finfo(u.dtype).nexp, jnp.finfo(u.dtype).nmant)
    dt = jnp.einsum("bqd,dh->bqh", h, lp["w_dt"].astype(h.dtype), preferred_element_type=f32)
    dt = jax.nn.softplus(dt + lp["dt_bias"].astype(f32))
    A = -jnp.exp(lp["A_log"].astype(f32))
    with jax.named_scope("ssm_step" if q == 1 else "ssm_scan"):
        seen = jnp.concatenate([_slot_rows(pool, "conv", at, acc, B), u], axis=1)  # [B, K - 1 + q, C]: row j + K - 1 is token j
        taps = lp["conv_w"].astype(f32)
        y = jax.nn.silu(sum(seen[:, i : i + q].astype(f32) * taps[i] for i in range(K)) + lp["conv_b"].astype(f32))
        xs, Bm, Cm = jnp.split(y, [inner, inner + G * N], axis=-1)
        xs, Bm, Cm = xs.reshape(B, q, Hm, P), Bm.reshape(B, q, G, N), Cm.reshape(B, q, G, N)
        S = _slot_rows(pool, "state", at, acc, B)
        if q == 1:
            o, S = mamba2_step(xs[:, 0], dt[:, 0], A, Bm[:, 0], Cm[:, 0], lp["D"], S, acc.n_valid > 0)
            o = o[:, None]
        else:
            o, S = mamba2_chunk(xs, dt, A, Bm, Cm, lp["D"], S, acc.n_valid)
        tail = _rows_to_carry(seen, acc.n_valid, K)
        pool = {**pool, "state": _put_slot_rows(pool, "state", at, acc, S), "conv": _put_slot_rows(pool, "conv", at, acc, tail)}
        gated = (o.reshape(B, q, inner) * jax.nn.silu(z.astype(f32))).reshape(B, q, G, inner // G)
        gated = gated * lax.rsqrt(jnp.mean(jnp.square(gated), axis=-1, keepdims=True) + cfg.norm_eps)
    return (gated.reshape(B, q, inner) * lp["ssm_norm"].astype(f32)).astype(x.dtype), pool


def _linear_mixer(lp, x, pool, at, acc: _StateAccess, cfg):
    """A linear-attention layer's mixer over layer ``at`` of the state group:
    x [B, q, D] -> (the gated, normed heads [B, q, Hl * dv] that ``wo`` takes,
    the pool with the rows' state and carried convolution rows moved on).

    ``u = x w_qkv``; each column through a causal convolution over the last
    ``linear_conv`` tokens (those before the chunk are the slot's carried rows,
    zeros ahead of a request's first token) and SiLU; per head ``q / |q| *
    dk^-1/2``, ``k / |k|``; ``beta = sigmoid(x w_b)`` (twice that under
    ``linear_neg_eigval``), ``g = -exp(A_log) softplus(x w_a + dt_bias)``; the
    gated delta rule (ops/linear_attention.py: the chunked form for q > 1, the
    step for q == 1); ``RMSNorm_dv(o) * silu(x wg_lin)``. From the convolution
    on everything is float32."""
    from ray_tpu.ops.linear_attention import gated_delta_chunk, gated_delta_step

    B, q, _ = x.shape
    Hl, dk, dv, K = cfg.linear_heads, cfg.linear_key_dim, cfg.linear_value_dim, cfg.linear_conv
    h = _rms_norm(x, lp["attn_norm"], cfg.norm_eps) if cfg.pre_norms else x

    held = partial(_slot_rows, pool, at=at, acc=acc, B=B)
    put = partial(_put_slot_rows, pool, at=at, acc=acc)

    u = h @ lp["w_qkv"].astype(h.dtype)
    f32 = jnp.float32
    gates = jnp.einsum("bqd,dh->bqh", h, jnp.concatenate([lp["w_a"], lp["w_b"]], axis=1).astype(h.dtype),
                       preferred_element_type=f32)
    g = -jnp.exp(lp["A_log"].astype(f32)) * jax.nn.softplus(gates[..., :Hl] + lp["dt_bias"].astype(f32))
    beta = jax.nn.sigmoid(gates[..., Hl:]) * (2.0 if cfg.linear_neg_eigval else 1.0)
    with jax.named_scope("linear_attention_step" if q == 1 else "linear_attention_scan"):
        seen = jnp.concatenate([held("conv"), u], axis=1)  # [B, K - 1 + q, C]: row j + K - 1 is token j
        taps = lp["conv_w"].astype(f32)
        y = jax.nn.silu(sum(seen[:, i : i + q].astype(f32) * taps[i] for i in range(K)))
        qh, kh, vh = (
            part.reshape(B, q, Hl, -1) for part in jnp.split(y, [Hl * dk, 2 * Hl * dk], axis=-1)
        )
        qh = qh * lax.rsqrt(jnp.sum(qh * qh, axis=-1, keepdims=True) + 1e-6) * dk**-0.5
        kh = kh * lax.rsqrt(jnp.sum(kh * kh, axis=-1, keepdims=True) + 1e-6)
        if q == 1:
            o, S = gated_delta_step(qh[:, 0], kh[:, 0], vh[:, 0], g[:, 0], beta[:, 0], held("state"), acc.n_valid > 0)
            o = o[:, None]
        else:
            o, S = gated_delta_chunk(qh, kh, vh, g, beta, held("state"), acc.n_valid)
        tail = _rows_to_carry(seen, acc.n_valid, K)
        pool = {**pool, "state": put("state", rows=S), "conv": put("conv", rows=tail)}
    o = _rms_norm(o, lp["o_norm"], cfg.norm_eps).astype(x.dtype).reshape(B, q, Hl * dv)
    return o * jax.nn.silu(h @ lp["wg_lin"].astype(h.dtype)), pool


def _conv_mixer(lp, x, pool, at, acc: _StateAccess, cfg):
    """A gated short convolution over layer ``at`` of the state group: x [B, q, D]
    -> (the mixer's output [B, q, D], the pool with the rows' carried rows moved on).

    ``[B | C | u] = h w_in`` of the normed input h; ``v = B * u``; each channel
    of v through a causal filter over the last ``conv_cache`` tokens (those
    before the chunk are the slot's carried rows, zeros ahead of a request's
    first token), no bias and no activation; ``y = C * conv(v)``; ``y wo``. The
    filter and the gate are float32."""
    B, q, D = x.shape
    K = cfg.conv_cache
    f32 = jnp.float32
    with jax.named_scope("short_conv_step" if q == 1 else "short_conv_chunk"):
        h = _rms_norm(x, lp["attn_norm"], cfg.norm_eps)
        gate_in, gate_out, u = jnp.split(h @ lp["w_in"].astype(h.dtype), 3, axis=-1)
        # Rounded to its dtype HERE, whatever the compiler fuses the product into: the filter must read of a token
        # that is the call's own what it will read of it as a carried row of the slot, in the next call.
        v = lax.reduce_precision(gate_in * u, jnp.finfo(u.dtype).nexp, jnp.finfo(u.dtype).nmant)
        seen = jnp.concatenate([_slot_rows(pool, "conv", at, acc, B), v], axis=1)  # [B, K - 1 + q, D]: row j + K - 1 is token j
        taps = lp["conv_w"].astype(f32)
        y = gate_out.astype(f32) * sum(seen[:, i : i + q].astype(f32) * taps[i] for i in range(K))
        pool = {**pool, "conv": _put_slot_rows(pool, "conv", at, acc, _rows_to_carry(seen, acc.n_valid, K))}
        return y.astype(x.dtype) @ lp["wo"].astype(x.dtype), pool


def _cached_layers(params, x, cache, positions, access, cfg, key_len=None, valid=None, parts=None, step=None):
    """THE layer stack over a cache, dense or paged: x [B, q, D] (``_embed_chunk``'s: under
    hyper-connections the stream, [B, q, hc_mult * D], which the layer scan carries) at
    ``positions`` [B, q] -> (final normed hidden states [B, q, D], cache).

    ``parts`` (in place of ``access``; ``paged_decode_step_with_chunk``): x is
    [1, n, D], several batches of different shapes laid side by side
    (``_Part``). The matmuls of a layer, which read the weights, run once over
    all n rows; only the cache's write, view, mask and attention run a part
    at a time, each as its own [B, q], and their outputs are laid side by
    side again (every part's rows are written first; a part of decode rows
    then reads the pool in place where ``kernel_reads`` says so, the chunk
    its own gathered view). Carried for one group of key and value leaves only.

    The whole cache rides the layer scan as its CARRY (never xs -> ys, which
    are distinct buffers of the loop) and a layer reaches its part through
    its index within its kind's group of leaves and ``access``: how each way
    of reaching a group (``_KINDS``: ``"table"``, ``"ring"``, ``"state"``) is
    taken in this call, an ``_Access`` or a ``_StateAccess``. A caller that
    donates ``cache`` gets it updated in place.
    Masked (p == 0) entries contribute nothing, so stale rows past a
    position, padding and null-block garbage stay invisible. ``valid`` [B, q]:
    the rows that are real tokens (read by routed experts only). ``step``:
    whether the expert counters (``MOE_COUNTS``) take this call for a step or
    for a chunk, told by the caller where q does not say (None: a step feeds
    one token a row; a pass over a block of ``cfg.block_diffusion`` positions
    a row is a step too).

    Which stacks of ``params`` run, in what order and which kind lies where
    is ``_layer_plan``'s to say; a layer's index into its group counts through
    the segments (a conv layer among the leading dense layers is the first of
    its kind's group, whichever stack holds it). A segment without a pattern is one homogeneous scan over
    its layers. One with a pattern is scanned over the PERIODS of its kinds
    (``_period``): the kind of a layer, which decides its mask, its rotary and
    its group, is static inside the body, which runs one period; what is left
    past the last whole period is unrolled behind the scan."""
    B, q = positions.shape
    counts = cache.get(MOE_COUNTS)
    pool = {name: leaf for name, leaf in cache.items() if name != MOE_COUNTS}
    latent = cfg.latent_attention
    n_words, per_word = _choice_words(cfg) if cfg.routed_experts else (1, 0)
    kinds_of = _kinds(cfg)

    # Kernel or view, asked once a call, of each batch of it (its one batch, or each of its ``parts``):
    # ``run_layer`` and ``attend_parts`` read the answer.
    batches = [(part.access, part.positions.shape[1]) for part in parts] if parts else [(access["table"], q)]
    in_place = [kernel_reads(cfg, acc.tables is not None, n) for acc, n in batches]

    def attend_parts(pool, qh, rows, at):
        """Every part's rows written, then every part attended over its own
        view: (attention output [1, n, H * Dh], pool)."""

        def of(part, flat):  # the part's rows of a [1, n, ...] array, as [B, q, ...]
            n = math.prod(part.positions.shape)
            return flat[0, part.start : part.start + n].reshape(*part.positions.shape, *flat.shape[2:])

        for part in parts:
            pool = {**pool, **{name: part.access.write(pool[name], at, of(part, row)) for name, row in rows.items()}}
        out = []
        with jax.named_scope("cache_attention"):
            for part, walked in zip(parts, in_place):
                acc = part.access
                if walked:  # the decode rows, in place
                    o = _cache_attention_in_place(of(part, qh), pool["k"], pool["v"], at, acc.tables, part.positions, cfg)
                else:
                    ck, cv = (acc.view(pool[name], at) for name in ("k", "v"))
                    mask = _cache_mask(part.positions, ck.shape[1], cfg.sliding_window, None, acc.key_pos, cfg.block_diffusion)
                    o = _cache_attention(of(part, qh), ck, cv, mask, cfg)  # the query heads: not the zero heads a cached row may carry
                out.append(o.reshape(1, -1, o.shape[2] * o.shape[3]))
        return jnp.concatenate(out, axis=1), pool

    def record_choice(pool, chosen, l, first, nth):
        """The pool with the experts ``chosen`` [B, q, k] beside the rows' tokens
        in expert layer ``nth`` (None: ``l - first``) of ``MOE_CHOICE``, where the pool keeps them."""
        if chosen is None or MOE_CHOICE not in pool:
            return pool
        layer = lambda: l - first if nth is None else nth  # noqa: E731  (where it is used: an accepted program's operations keep their order)
        put = access["table"].write
        if n_words == 1:
            words = jnp.sum(chosen << (_expert_bits(cfg) * jnp.arange(chosen.shape[-1])), axis=-1)
            return {**pool, MOE_CHOICE: put(pool[MOE_CHOICE], layer(), words)}
        # [words, expert layers, ...] written as [words * expert layers, ...]
        leaf = pool[MOE_CHOICE]
        flat = leaf.reshape(-1, *leaf.shape[2:])
        shifts = _expert_bits(cfg) * jnp.arange(per_word)
        for w in range(n_words):
            ids = chosen[..., w * per_word : (w + 1) * per_word]
            word = jnp.sum(ids << shifts[: ids.shape[-1]], axis=-1)
            flat = put(flat, w * leaf.shape[1] + layer(), word)
        return {**pool, MOE_CHOICE: flat.reshape(leaf.shape)}

    def run_layer(x, pool, lp, kind, at, l, first, held, nth=None):
        """One layer of kind ``kind`` (None: no pattern), layer ``at`` of its
        group, layer ``l - first`` of its stack and, where its stack's layers
        have routed experts, the ``nth`` of the model's layers that have (None:
        ``l - first`` too, the one stack that has them)."""
        row = kinds_of[kind]
        acc, sfx = access.get(row.reach) if access else None, row.group

        def then_mlp(x, pool):  # the layer's MLP, and the experts it took kept beside the rows' tokens
            x, sent, chosen = _mlp(lp, x, cfg, valid, layer=l - first if held else None)
            return x, record_choice(pool, chosen, l, first, nth), sent

        def then_branch(u, pool):  # a double layer's routed experts over ``u``, their picks kept beside the rows' tokens
            m, sent, chosen = _routed(lp, u, cfg, valid, l - first if held else None)
            return m, record_choice(pool, chosen.reshape(B, q, -1), l, first, nth), sent

        if kind == _MAMBA:  # a block that is this mixer and nothing else
            def mamba(u):
                o, moved = _mamba_mixer(lp, u, pool, at, acc, cfg)
                return o @ lp["wo"].astype(o.dtype), moved

            return *_residual(lp, x, "attn", cfg, mamba), None
        if kind == _EXPERTS:  # a block that is its experts and nothing else
            return then_mlp(x, pool)

        def post(a):  # ``cfg.post_norms``: the branch is normed once more before it joins the residual
            return _rms_norm(a, lp["attn_post_norm"], cfg.norm_eps) if cfg.post_norms else a

        if kind == _LINEAR:
            def linear(u):
                o, moved = _linear_mixer(lp, u, pool, at, acc, cfg)
                return post(o @ lp["wo"].astype(o.dtype)), moved

            x, pool = _residual(lp, x, "attn", cfg, linear)
            return _mlp(lp, x, cfg)[0], pool, None
        if kind == _CONV:
            return then_mlp(*_residual(lp, x, "attn", cfg, lambda u: _conv_mixer(lp, u, pool, at, acc, cfg)))

        def attention(u, pool=pool, lp=lp, at=at):
            project = _project_latent if latent else partial(_project_qkv, rope=layer_rope(cfg, kind))
            qh, rows = project(lp, u, positions, cfg)
            if parts is not None:
                o, pool = attend_parts(pool, qh, rows, at)
            else:
                pool = {**pool, **{name + sfx: acc.write(pool[name + sfx], at, row) for name, row in rows.items()}}
                with jax.named_scope("cache_attention" + (f"_{kind}" if kind else "")):
                    if in_place[0] and row.reach == "table" and latent:  # the rows just written are read where they lie
                        o = _latent_attention_in_place(lp, qh, pool["ckv"], at, acc.tables, positions, acc.ends, cfg)
                    elif in_place[0] and row.reach == "table":
                        o = _cache_attention_in_place(qh, pool["k"], pool["v"], at, acc.tables, positions, cfg).reshape(B, q, -1)
                    else:
                        seen = {name: acc.view(pool[name + sfx], at) for name in rows}
                        n_keys = next(iter(seen.values())).shape[1]
                        window = 0 if kind == _FULL else cfg.sliding_window
                        mask = _cache_mask(positions, n_keys, window, key_len, acc.key_pos, cfg.block_diffusion)
                        if latent:
                            o = _latent_attention(lp, qh, seen, mask, cfg)
                        else:
                            o = _cache_attention(qh, seen["k"], seen["v"], mask, cfg)
                            o = (_paired_half(o, cfg) if _heads_paired(cfg) else o).reshape(B, q, -1)
            if cfg.attn_gate:
                gate = _rms_norm(u, lp["attn_norm"], cfg.norm_eps) @ lp["wg_attn"].astype(u.dtype)
                o = o * jax.nn.sigmoid(gate)
            return post(o @ lp["wo"].astype(o.dtype)), pool

        if cfg.shortcut_moe:
            return _shortcut_layer(twice, l - first, x, pool, cfg, partial(attention, at=2 * at), partial(attention, at=2 * at + 1), then_branch)
        x, pool = _residual(lp, x, "attn", cfg, attention)
        if cfg.single_mixer:  # an attention block: no MLP behind it
            return x, pool, None
        return then_mlp(x, pool)

    def body(first, held, carry, layer):
        x, pool = carry
        lp, l = layer
        x, pool, sent = run_layer(x, pool, {**lp, **held}, None, l, l, first, held)
        return (x, pool), sent

    def scan_periods(segment, held, stacks, x, pool):
        """The layers of ``segment``, a run of the model's under a layer pattern.
        ``stacks``: by kind, the stack of leaves that holds the kind's layers:
        its own, or one that the segment's kinds share (``_Kind.own``), and
        ``held`` by kind what of it the scans do not slice. One
        scan over the PERIODS; inside a period a run of layers
        of one kind that lie in a stack of their own is a scan of its own
        (three linear layers: one body, not three), any other layer a call (a
        shared stack's program is as it was measured: Trinity's lowered text is
        the guard, PR 41). Every body indexes the WHOLE stacks,
        which the scans close over: handed to the outer scan as xs, a period's
        slice of every matrix is copied out before an inner scan may read it
        (15 ms of a 46 ms decode step at Olmo-Hybrid's widths, v5e, PR 41)."""
        first, kinds, rows = segment.first, segment.kinds, segment.rows
        P = _period(kinds)
        before = {kind: cfg.layer_kinds[:first].count(kind) for kind in set(kinds)}
        a_period = {kind: kinds[:P].count(kind) for kind in set(kinds)}
        runs, j = [], 0  # (first layer, layers) of each run of one kind through one period
        while j < P:
            n = next((i for i in range(j, P) if kinds[i] != kinds[j]), P) - j if rows[kinds[j]].own else 1
            runs.append((j, n))
            j += n
        # Stacks of their own of which more than one holds routed experts: a layer's index among the model's layers that
        # have them (``MOE_CHOICE``, the counters) is then not its index in its stack.
        routed = [kind for kind in rows if "gate" in stacks[kind] and rows[kind].own]
        routed = routed if len(routed) > 1 else []

        def take(stack, index):
            return {n: lax.dynamic_index_in_dim(leaf, index, 0, keepdims=False) for n, leaf in stack.items()}

        def layer_at(x, pool, period, j, i=0):
            # Layer ``j`` of period ``period``, or (``i``, traced: a run's scan)
            # the i-th layer of the run of one kind that starts there.
            # ``period`` traced (the scan's) or static (the remainder's).
            kind = kinds[j]
            rank = lambda: before[kind] + period * a_period[kind] + kinds[:j].count(kind)  # noqa: E731
            # In a stack of its kind's own at its rank among its kind, as in its group of cache leaves; in a shared
            # stack at its position (a shared stack's runs are not scanned: i is 0).
            own = rows[kind].own
            at = s = rank() + i if own else period * P + j
            if own and before[kind]:  # those of its kind ahead of the segment lie in another stack
                s = s - before[kind]
            lp = take(stacks[kind], s)
            at = at if own else rank()
            nth = period * sum(kinds[:P].count(k) for k in routed) + sum(kinds[:j].count(k) for k in routed) + i if kind in routed else None
            return run_layer(x, pool, {**lp, **held[kind]}, kind, at, first + s, first, held[kind], nth)

        def one_period(carry, period):
            sent = []
            for j, n in runs:
                if n == 1:
                    *carry, s = layer_at(*carry, period, j)
                else:
                    def one(c, i, j=j):
                        *c, s = layer_at(*c, period, j, i)
                        return tuple(c), s

                    carry, s = lax.scan(one, tuple(carry), jnp.arange(n, dtype=jnp.int32))
                sent.append(s)
            sent = [s if n > 1 else jnp.expand_dims(s, 0) for s, (_, n) in zip(sent, runs) if s is not None]
            return tuple(carry), jnp.concatenate(sent) if sent else None  # of the period's layers with experts, in order

        whole = len(kinds) // P
        (x, pool), sent = lax.scan(one_period, (x, pool), jnp.arange(whole, dtype=jnp.int32))
        sent = [] if sent is None else [sent.reshape(-1, sent.shape[-1])]
        for j in range(len(kinds) % P):
            x, pool, s = layer_at(x, pool, whole, j)
            if s is not None:
                sent.append(s[None])
        return x, pool, jnp.concatenate(sent) if sent else None

    sent = None
    # Routed experts' matrices stay whole, outside the scanned leaves, where
    # their matmuls run grouped: the TPU's compiler copies a scan's slice of
    # them before the grouped matmul reads it, a layer's 64 experts every
    # layer of every step (tests/test_tpu_lowering.py holds both halves: the
    # slice is copied, the whole stack is not; when the first fails, ``held``
    # and ``routed_experts(layer=)`` can go). Widths that neither grouped kernel
    # takes at this call's rows (``experts_run``) run batched over every held
    # expert and are sliced like any leaf: held whole for a grouped matmul,
    # Nemotron's [6, 64, 2688, 1856] was copied into the kernel's layout every
    # step (3.8 GB, PR 43).
    hold = experts_run(cfg, x.shape[0] * x.shape[1]) in ("kernel", "ragged_dot")
    twice = {}  # a double layer's stack of what a sub-layer owns, whole too (``_shortcut_layer``); its one segment's
    for segment in _layer_plan(cfg):
        stacks = {kind: params[row.stack] for kind, row in segment.rows.items()}
        held = {kind: {n: stack[n] for n in _EXPERT_STACKS if hold and n in stack} for kind, stack in stacks.items()}
        stacks = {kind: {n: leaf for n, leaf in stack.items() if n not in held[kind]} for kind, stack in stacks.items()}
        if cfg.shortcut_moe:
            twice = {n: leaf for n, leaf in stacks[None].items() if n not in SHORTCUT_BRANCH_LEAVES}
            stacks = {None: {n: leaf for n, leaf in stacks[None].items() if n not in twice}}
        if segment.kinds:
            x, pool, sent = scan_periods(segment, held, stacks, x, pool)
        else:
            layer_ids = jnp.arange(segment.first, segment.first + segment.depth, dtype=jnp.int32)
            (x, pool), sent = lax.scan(partial(body, segment.first, held[None]), (x, pool), (stacks[None], layer_ids))
    if counts is not None:  # sent [expert layers, E]: the routed stack's, which runs last
        apart = []
        if cfg.zero_experts:  # the two numbers ``_picks_apart`` laid behind the held experts' rows
            sent, apart = sent[:, :-2], [sent[:, -2:]]
        touched = jnp.sum(sent > 0, axis=-1, keepdims=True)
        if counts_every_pick(cfg):  # E the experts held; a call counts by what it routed, held or not, and says how much
            routed = (B * q if valid is None else jnp.sum(valid)) * cfg.experts_per_token
            routed = jnp.broadcast_to(routed, touched.shape).astype(sent.dtype)
            columns = [sent, touched, jnp.max(sent, axis=-1, keepdims=True), jnp.minimum(routed, 1), routed]
        else:
            columns = [sent, touched, jnp.max(sent, axis=-1, keepdims=True), jnp.minimum(touched, 1)]
        routed_now = jnp.concatenate(columns + apart, axis=-1)
        pool[MOE_COUNTS] = counts.at[0 if (q == 1 if step is None else step) else 1].add(routed_now.astype(counts.dtype))
    if cfg.hc_mult:  # the stream ends as the sum of its rows
        x = hyper_connection.collapse(x, cfg.hc_mult)
    return _rms_norm(x, params["norm_f"], cfg.norm_eps), pool


def last_row_logits(params, x, row):
    """Logits [B, V] f32 of ONE row ``row[b]`` (traced: no recompile per
    position) of hidden states x [B, q, D]: a prompt needs its last real
    token's only, not the [B, q, V] head matmul."""
    return _logits(params, jnp.take_along_axis(x, row[:, None, None], axis=1)[:, 0])


def _dense_write(pos, positions):
    """Into a dense cache leaf [L, B, S, ...] at row ``pos``: a scalar (aligned
    batch) is one dynamic_update_slice, ``pos`` [B] one scatter of every fed
    row to its own ``positions`` [B, q]. Its view is the layer, ``c[l]``
    (prefill: the first T rows of it)."""
    if pos.ndim == 0:
        return lambda c, l, rows: lax.dynamic_update_slice(
            c, rows[None], (l, 0, pos) + (0,) * (c.ndim - 3)
        )
    batch = jnp.arange(positions.shape[0], dtype=jnp.int32)[:, None]
    return lambda c, l, rows: c.at[l, batch, positions].set(rows)


def _dense_access(write, view):
    """A dense cache is reached the same way whatever a layer's kind: a window
    layer keeps every row and its mask leaves out those behind the window."""
    return dict.fromkeys(("table", "ring"), _Access(write, view))


def prefill(params, tokens, cache, cfg: TransformerConfig, prompt_lens=None):
    """Run the prompt through the model, filling cache[:, :, :T].

    tokens: [B, T] int32. ``prompt_lens`` [B] int32 enables RAGGED batches:
    each row's real prompt occupies tokens[b, :prompt_lens[b]] (padding at
    the end, any values) — padded key rows are masked out of attention and
    the returned logits come from each row's LAST REAL token. Shapes stay
    static, so one compile serves every length mix (the batched-serving
    shape). Returns (logits_last [B, V] f32, cache, next_pos [B] int32).
    """
    B, T = tokens.shape
    if prompt_lens is None:
        prompt_lens = jnp.full((B,), T, jnp.int32)
    else:
        # Empty rows are undefined (all-masked softmax -> NaN, gather at
        # -1); clamp to 1 so a stray len-0 row behaves as "prompt is
        # tokens[b, :1]" instead of silently poisoning the whole batch.
        prompt_lens = jnp.maximum(jnp.asarray(prompt_lens, jnp.int32), 1)
    pos = jnp.int32(0)
    x, positions = _embed_chunk(params, tokens, pos, cfg)
    # Attend only over the prompt's T rows — the generation region of the
    # cache is not written yet; scoring it would waste S/T the FLOPs/HBM.
    # Causal within the prompt; per-row padding invisible.
    access = _dense_access(_dense_write(pos, positions), lambda c, l: c[l][:, :T])
    x, cache = _cached_layers(
        params, x, cache, positions, access, cfg, key_len=prompt_lens,
        valid=positions < prompt_lens[:, None] if cfg.routed_experts else None,
    )
    return last_row_logits(params, x, prompt_lens - 1), cache, prompt_lens


def _decode_chunk_hidden(params, tokens, cache, pos, cfg: TransformerConfig):
    """decode_chunk without the head projection: returns the final normed
    hidden states [B, q, D] + cache. Callers that need logits for only a
    subset of rows (chunked prefill needs just the final one) project
    themselves (``last_row_logits``) instead of paying [B, q, V]."""
    pos = jnp.asarray(pos, jnp.int32)
    x, positions = _embed_chunk(params, tokens, pos, cfg)
    access = _dense_access(_dense_write(pos, positions), lambda c, l: c[l])
    return _cached_layers(params, x, cache, positions, access, cfg)


def decode_chunk(params, tokens, cache, pos, cfg: TransformerConfig):
    """q tokens per row against the cache: tokens [B, q] int32 written at
    per-row positions pos[b]..pos[b]+q-1 (pos [B] int32 or scalar).

    Returns (logits [B, q, V] f32 — one next-token distribution per fed
    token — and the updated cache). The position mask makes any stale cache
    rows beyond pos invisible, so callers may freely re-write positions
    (speculative decoding rejects; chunked prefill) without a cache rewind.
    """
    x, cache = _decode_chunk_hidden(params, tokens, cache, pos, cfg)
    return _logits(params, x), cache


def prefill_chunked(params, tokens, cache, cfg: TransformerConfig, chunk: int = 512):
    """Prefill long prompts in fixed-size chunks: peak attention-score
    memory is [B, H, chunk, S] instead of [B, H, T, T] — the bounded-memory
    path for long-context serving. Aligned (non-ragged) prompts only.

    Returns (logits_last [B, V], cache, next_pos [B]) like prefill().
    """
    B, T = tokens.shape
    if T % chunk:
        # Clean tiling keeps one compiled chunk shape; callers pad prompts
        # to a chunk multiple (the serving idiom) or use prefill().
        raise ValueError(f"prompt length {T} not divisible by chunk {chunk}")
    n = T // chunk
    tok_chunks = tokens.reshape(B, n, chunk).transpose(1, 0, 2)  # [n, B, chunk]

    def body(carry, tok):
        cache, pos = carry
        # Hidden states only: projecting every chunk row to [chunk, V]
        # logits would waste head FLOPs on a path whose point is bounding
        # memory — only the final row's logits are needed.
        x, cache = _decode_chunk_hidden(params, tok, cache, pos, cfg)
        return (cache, pos + chunk), x[:, -1:]

    (cache, pos), last = lax.scan(body, (cache, jnp.int32(0)), tok_chunks)
    logits = last_row_logits(params, last[-1], jnp.zeros((B,), jnp.int32))
    return logits, cache, jnp.full((B,), T, jnp.int32)


def decode_step(params, token, cache, pos, cfg: TransformerConfig):
    """One token per row: token [B] int32 written at per-row position
    ``pos`` ([B] int32, or a scalar for aligned batches). The q=1 case of
    decode_chunk. Returns (logits [B, V] f32, updated cache)."""
    logits, cache = decode_chunk(params, token[:, None], cache, pos, cfg)
    return logits[:, 0], cache


def init_paged_cache(
    cfg: TransformerConfig, num_blocks: int, block_size: int, window_blocks: int = 0, state_slots: int = 0
):
    """Block-pool cache for continuous-batching serving, every leaf
    [L, num_blocks, block_size, ...] (``_cache_rows``): k/v [.., KV, Dh], or with
    latent attention the one leaf ckv [.., the latent and the rotary key].
    Physical block 0 is RESERVED as the null block — allocators must never
    hand it out. Inactive decode slots and write-masked prefill padding rows
    are routed there, so the compiled step never needs a dynamic shape or a
    conditional write.

    Under a layer pattern a kind of layer has a group of leaves of its own
    (``_KINDS``), each with a null block of its own: the full layers' ``[full
    layers, num_blocks, ...]``, reached through a row's block table as ever,
    and the window layers' ``[window layers, window_blocks, ...]``, reached
    through a row's RING (``_ring_access``): ``window_blocks`` is 1 + rings x
    blocks a ring. The group of layers that keep a state holds no token's rows
    and has no blocks: ``[such layers, state_slots, ...]`` (``state_rows``),
    what each of ``state_slots`` serving slots carries from program to
    program, reached by the slot's index (``_StateAccess``)."""
    held = {"table": (num_blocks, block_size), "ring": (window_blocks, block_size), "state": (state_slots,)}
    return {
        name: jnp.zeros((row.layers, *held[row.reach], *shape), dtype)
        for row in _kinds(cfg).values() if row.reach
        for name, (shape, dtype) in _group_rows(cfg, row).items()
    }


def _paged_write(block_tables, positions, valid_to, block_size: int):
    """Into a block pool leaf [L, N, Bs, ...] through the physical write
    coordinates of every fed row (computed once, reused per layer).
    Out-of-table positions clamp to the last entry; engines validate lengths
    so this only guards compiler-visible bounds."""
    blk_idx = jnp.minimum(positions // block_size, block_tables.shape[1] - 1)
    blk_phys = jnp.take_along_axis(block_tables, blk_idx, axis=1)  # [B, q]
    row_off = positions % block_size
    if valid_to is not None:
        writable = positions < jnp.asarray(valid_to, jnp.int32)[:, None]
        blk_phys = jnp.where(writable, blk_phys, 0)
    return lambda c, l, rows: c.at[l, blk_phys, row_off].set(rows)


def _paged_view(block_tables):
    """Each row's logical cache [B, n_max * Bs, ...], gathered through its
    block table with the layer index in the same indexing op: no
    [N, Bs, ...] layer slice is materialised."""
    B, n_max = block_tables.shape
    return lambda c, l: c[l, block_tables].reshape(B, n_max * c.shape[2], *c.shape[3:])


def ring_blocks(window: int, chunk: int, block_size: int) -> int:
    """Blocks of a window layer's ring: the window and the widest chunk fed
    at once, rounded up to blocks, and one more because neither starts on a
    block's edge. Then no row a query of the chunk may still read (behind it
    by less than the window) lies a whole ring behind the chunk's last row,
    which is what overwrites it."""
    return -(-(window + chunk) // block_size) + 1


def _ring_access(ring_tables, positions, valid_to, block_size: int, n_view: int) -> _Access:
    """The window layers' way into their group of a paged cache: a row's
    ``ring_tables[b]`` names the R physical blocks of its ring, and logical
    block j of the row lies at ring index ``j mod R``. Rows are written
    through that; the view is the ring's first ``n_view`` blocks (all of it,
    or as many as the step's longest row has touched where that is fewer),
    and ring index i holds the newest logical block <= the last one written
    that is congruent to i: ``key_pos`` says so to the mask, by position. A
    part of a ring block not yet overwritten in this turn of the ring gets
    the position it will hold: ahead of every query, so causality masks it."""
    B, R = ring_tables.shape
    ring_idx = (positions // block_size) % R
    blk_phys = jnp.take_along_axis(ring_tables, ring_idx, axis=1)  # [B, q]
    row_off = positions % block_size
    if valid_to is not None:
        blk_phys = jnp.where(positions < jnp.asarray(valid_to, jnp.int32)[:, None], blk_phys, 0)
    newest = positions[:, -1:] // block_size  # [B, 1]: the logical block of the last row fed
    held = newest - (newest - jnp.arange(n_view, dtype=jnp.int32)[None, :]) % R  # [B, n_view] logical blocks
    key_pos = held[:, :, None] * block_size + jnp.arange(block_size, dtype=jnp.int32)[None, None, :]
    return _Access(
        lambda c, l, rows: c.at[l, blk_phys, row_off].set(rows),
        _paged_view(ring_tables[:, :n_view]),
        key_pos.reshape(B, n_view * block_size),
    )


def paged_decode_chunk_hidden(
    params, tokens, cache, block_tables, pos, cfg: TransformerConfig, valid_to=None, ring_tables=None,
    state_slots=None, state_fresh=None, step=None,
):
    """``paged_decode_chunk`` without the head projection: returns the final
    normed hidden states [B, q, D] + cache. Chunked prefill consumes logits
    for at most ONE row per prompt — callers project that row themselves
    (``last_row_logits``) instead of paying [B, q, V]. ``ring_tables`` [B, R]
    (a layer pattern only): each row's ring in the window layers' group.
    Linear-attention layers only: ``state_slots`` [B] int32, each row's slot in
    their group (None: row b is slot b, B the group's slots), and
    ``state_fresh`` [B] bool, the rows whose state starts from zero (None:
    none). A row moves its slot's state by the tokens that are real: those
    under ``valid_to``, of a live row (one whose table starts at a real block).
    ``step``: ``_cached_layers``' (a pass over a block a row says True)."""
    pos = jnp.asarray(pos, jnp.int32)
    block_tables = jnp.asarray(block_tables, jnp.int32)
    x, positions = _embed_chunk(params, tokens, pos, cfg)
    blocked = next(row for row in _kinds(cfg).values() if row.reach in ("table", "ring"))
    block_size = cache[next(iter(_group_rows(cfg, blocked)))].shape[2]
    access = {"table": _Access(
        _paged_write(block_tables, positions, valid_to, block_size), _paged_view(block_tables), tables=block_tables,
        ends=valid_to,
    )}
    reach = pool_reach(cfg)
    if "state" in reach:
        B, q = positions.shape
        real = q if valid_to is None else jnp.clip(jnp.asarray(valid_to, jnp.int32) - pos, 0, q)
        live = block_tables[:, 0] != 0
        fresh = jnp.zeros((B,), bool) if state_fresh is None else jnp.asarray(state_fresh, bool)
        access["state"] = _StateAccess(state_slots, fresh, jnp.where(live, real, 0).astype(jnp.int32))
    if "ring" in reach:
        ring_tables = jnp.asarray(ring_tables, jnp.int32)
        n_view = min(ring_tables.shape[1], block_tables.shape[1])
        access["ring"] = _ring_access(ring_tables, positions, valid_to, block_size, n_view)
    valid = None
    if cfg.routed_experts:
        # A live row's table starts at a real block; an inactive slot's, and
        # nothing else's, at the null block.
        valid = jnp.broadcast_to(block_tables[:, :1] != 0, positions.shape)
        if valid_to is not None:
            valid &= positions < jnp.asarray(valid_to, jnp.int32)[:, None]
    return _cached_layers(params, x, cache, positions, access, cfg, valid=valid, step=step)


def paged_decode_chunk(
    params, tokens, cache, block_tables, pos, cfg: TransformerConfig, valid_to=None, ring_tables=None,
    state_slots=None, state_fresh=None,
):
    """``decode_chunk`` over a PAGED cache: tokens [B, q] written at per-row
    positions pos[b]..pos[b]+q-1, where logical position p of row b lives in
    physical block ``block_tables[b, p // block_size]`` at row offset
    ``p % block_size``.

    - ``block_tables`` [B, n_max] int32: per-sequence physical block ids in
      logical order; entries beyond the sequence's allocation are 0 (the
      null block) and stay invisible behind the position mask. Shapes are
      STATIC — one compile serves every schedule the engine can produce
      (any mix of sequences, fragmentation, or mid-stream admissions).
    - ``valid_to`` [B] int32 (optional): rows at positions >= valid_to[b]
      have their K/V writes routed to the null block (used by chunked
      prefill so a padded final chunk never touches unallocated blocks).
      Their logits are garbage and must be ignored by the caller.
    - An INACTIVE slot is (token 0, pos 0, all-zero block table): it writes
      and attends only null-block row 0 — finite garbage, never NaN (an
      all-masked softmax would poison MoE dispatch for the whole batch).

    Returns (logits [B, q, V] f32, updated cache). Attention math is the
    dense ``_cache_attention`` over the GATHERED logical view, so outputs
    match the dense-cache path row for row (the serving oracle).
    """
    x, cache = paged_decode_chunk_hidden(
        params, tokens, cache, block_tables, pos, cfg, valid_to=valid_to, ring_tables=ring_tables,
        state_slots=state_slots, state_fresh=state_fresh,
    )
    return _logits(params, x), cache


def paged_decode_step(params, token, cache, block_tables, pos, cfg: TransformerConfig, ring_tables=None):
    """One token per slot against the paged cache: token [B] int32 at
    per-slot positions ``pos`` [B]. The q=1 case of ``paged_decode_chunk``
    — the continuous-batching decode hot loop. Returns (logits [B, V] f32,
    updated cache)."""
    logits, cache = paged_decode_chunk(
        params, token[:, None], cache, block_tables, pos, cfg, ring_tables=ring_tables
    )
    return logits[:, 0], cache


def paged_decode_step_with_chunk(
    params, token, chunk_tokens, cache, block_tables, pos, chunk_tables, chunk_pos, valid_to, cfg: TransformerConfig
):
    """``paged_decode_step`` of ``token`` [S] at ``pos`` [S] over
    ``block_tables`` [S, w] AND ``paged_decode_chunk_hidden`` of
    ``chunk_tokens`` [1, q] at ``chunk_pos`` [1].. over ``chunk_tables``
    [1, w], writable below ``valid_to`` [1], as ONE pass over the layers: the
    S + q rows go through each layer's matmuls together, so the weights are
    read once where the two calls read them twice. Each part writes and views
    the pool through its own table and is masked by its own positions
    (``_cached_layers``' ``parts``), so a row's arithmetic is that of the call
    it would have been in (on a TPU the decode rows read the pool in place,
    as ``paged_decode_step``'s do: ``kernel_reads``). Returns (final normed hidden states [S + q, D],
    the S decode rows first, and the cache).

    For a cache of one group of key and value leaves (``one_kv_group``). Not
    carried: a latent pool, the groups of a layer pattern, and routed experts,
    whose counters keep decode steps and chunks apart where such a pass is both."""
    if not one_kv_group(cfg) or cfg.routed_experts:
        raise NotImplementedError("a decode step carries a chunk over one group of key and value leaves only")
    S = token.shape[0]
    block_size = next(iter(cache.values())).shape[2]
    x_step, at_step = _embed_chunk(params, token[:, None], jnp.asarray(pos, jnp.int32), cfg)
    x_chunk, at_chunk = _embed_chunk(params, chunk_tokens, jnp.asarray(chunk_pos, jnp.int32), cfg)
    block_tables, chunk_tables = jnp.asarray(block_tables, jnp.int32), jnp.asarray(chunk_tables, jnp.int32)
    parts = (
        _Part(0, at_step, _Access(
            _paged_write(block_tables, at_step, None, block_size), _paged_view(block_tables), tables=block_tables)),
        _Part(S, at_chunk, _Access(
            _paged_write(chunk_tables, at_chunk, valid_to, block_size), _paged_view(chunk_tables), tables=chunk_tables)),
    )
    x = jnp.concatenate([x_step.reshape(1, S, -1), x_chunk], axis=1)
    positions = jnp.concatenate([at_step.reshape(1, S), at_chunk], axis=1)
    x, cache = _cached_layers(params, x, cache, positions, None, cfg, parts=parts)
    return x[0], cache


def _kth_largest(x, k):
    """Per row of ``x`` [B, V] float32, its ``k[b]``-th largest value
    (``k`` [B] int32 in 1..V), exactly and for any k, without a sort: floats
    map to uint32 keys in the same order, and the largest key that at least k
    entries reach is built bit by bit, 32 counting passes over the row."""
    u = lax.bitcast_convert_type(x, jnp.uint32)
    keys = jnp.where(u >> 31 == 1, ~u, u | jnp.uint32(1 << 31))

    def settle_bit(i, t):
        cand = t | lax.shift_left(jnp.uint32(1), (31 - i).astype(jnp.uint32))
        reached = jnp.sum(keys >= cand[:, None], axis=-1, dtype=jnp.int32)
        return jnp.where(reached >= k, cand, t)

    t = lax.fori_loop(0, 32, settle_bit, jnp.zeros(x.shape[:1], jnp.uint32))
    u = jnp.where(t >> 31 == 1, t ^ jnp.uint32(1 << 31), ~t)
    return lax.bitcast_convert_type(u, jnp.float32)


def draw_tokens(logits, temperature, top_k, seed, counter, with_prob: bool = False):
    """The next token of each row, drawn inside the program that computed
    ``logits`` [B, V] float32, every row by its own rule (all traced, so one
    compiled program serves every mix of rows):

    - ``temperature[b] <= 0``: ``argmax`` of the raw logits, first index on ties.
    - otherwise a draw from ``softmax(logits / temperature)``, restricted for
      ``top_k[b] > 0`` to the entries not under the row's ``top_k``-th largest
      value (ties at the threshold stay, a ``top_k`` of V or more cuts
      nothing), as ``argmax(logits / T + Gumbel noise)`` in float32. The
      threshold search sits under a ``lax.cond`` on "some row of this batch
      has a ``top_k``": a batch without one does not pay for it.

    The noise of row b comes from ``fold_in(key(seed[b]), counter[b])`` alone,
    where ``seed`` [B, 2] uint32 holds the (low, high) halves of the request's
    64-bit seed, which ARE the threefry key, and ``counter`` [B] int32 is the
    index of the token drawn. Nothing is carried or split, so a row draws the
    same token alone or in a batch, in any row, from any program, on any host.
    Returns [B] int32; ``with_prob``: (that, [B] float32: the probability the
    distribution a row drew from gave its token: of ``softmax(logits)`` for a
    greedy row, of the tempered and ``top_k``-restricted softmax for a sampled
    one), what generation by diffusion over blocks calls a position's confidence."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    sampled = temperature > 0.0
    scaled = logits / jnp.where(sampled, temperature, 1.0)[:, None]
    capped = sampled & (top_k > 0)
    kth = lax.cond(
        jnp.any(capped),
        lambda: jnp.where(
            capped, _kth_largest(scaled, jnp.clip(top_k, 1, logits.shape[-1])), -jnp.inf
        ),
        lambda: jnp.full(top_k.shape, -jnp.inf, jnp.float32),
    )
    scaled = jnp.where(scaled < kth[:, None], -jnp.inf, scaled)

    def draw_row(halves, n, row):
        key = jax.random.wrap_key_data(halves[::-1], impl="threefry2x32")
        return jax.random.categorical(jax.random.fold_in(key, n), row)

    drawn = jax.vmap(draw_row)(seed, counter, scaled).astype(jnp.int32)
    ids = jnp.where(sampled, drawn, greedy)
    if not with_prob:
        return ids
    taken = jnp.take_along_axis(scaled, ids[:, None], axis=-1)[:, 0]
    return ids, jnp.exp(taken - jax.nn.logsumexp(scaled, axis=-1))


# In a block's ids (generation by diffusion over blocks): a position still masked. The bitmap of what is masked IS
# ``ids < 0``: the model is fed ``cfg.mask_token_id`` there, and a position that drew that very id is not masked again.
BLOCK_MASKED = -1


def transfer_block(ids, drawn, confidence, n_transfer):
    """One denoising pass's transfer: ``ids`` [S, B] int32, ``BLOCK_MASKED`` where a position is still masked,
    ``drawn`` [S, B] what the pass drew at every position and ``confidence`` [S, B] float32 the probability it drew
    it with -> the block's ids after the pass. Only masked positions take what they drew, and a position that did is
    never masked or drawn again. Row s takes the ``n_transfer[s]`` masked positions of largest confidence (the
    leftmost of equals; all that are left where fewer are; 0: none, a commit pass)."""
    masked = ids < 0
    c = jnp.where(masked, confidence, -jnp.inf)
    at = jnp.arange(ids.shape[1])
    ahead = (c[:, None, :] > c[:, :, None]) | ((c[:, None, :] == c[:, :, None]) & (at[None, None, :] < at[None, :, None]))
    rank = jnp.sum(ahead, axis=-1)  # [S, B]: positions of the row ahead of this one, by confidence and then from the left
    return jnp.where(masked & (rank < n_transfer[:, None]), drawn, ids)


def _sample(logits, key, temperature: float, top_k: int):
    if temperature == 0.0:
        return logits.argmax(axis=-1).astype(jnp.int32)
    logits = logits / temperature
    if top_k > 0:
        kth = jnp.sort(logits, axis=-1)[:, -top_k][:, None]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    return jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)


@partial(jax.jit, static_argnames=("cfg", "max_new_tokens", "temperature", "top_k"))
def generate(
    params,
    prompt,
    cfg: TransformerConfig,
    max_new_tokens: int = 32,
    temperature: float = 0.0,
    top_k: int = 0,
    key=None,
    prompt_lens=None,
):
    """prompt [B, T] int32 -> generated [B, max_new_tokens] int32.

    One jit: prefill + a lax.scan of decode steps (no per-token host
    round trips). temperature=0 is greedy; top_k=0 disables truncation.
    ``prompt_lens`` [B] batches RAGGED prompts (rows padded at the end to
    T): row b continues from its real prompt tokens[b, :prompt_lens[b]].
    """
    if key is None:
        key = jax.random.PRNGKey(0)
    B, T = prompt.shape
    cache = init_cache(cfg, B, T + max_new_tokens)
    logits, cache, pos = prefill(params, prompt, cache, cfg, prompt_lens=prompt_lens)
    if prompt_lens is None:
        # Aligned batch: a SCALAR position keeps decode's cache write a
        # single fused dynamic_update_slice instead of a per-row scatter.
        pos = jnp.int32(T)

    def step(carry, k):
        logits, cache, pos = carry
        tok = _sample(logits, k, temperature, top_k)
        logits, cache = decode_step(params, tok, cache, pos, cfg)
        return (logits, cache, pos + 1), tok

    keys = jax.random.split(key, max_new_tokens)
    _, toks = lax.scan(step, (logits, cache, pos), keys)
    return toks.T  # [B, max_new_tokens]


def _processed_probs(logits, temperature: float, top_p: float):
    """Temperature + nucleus(top-p) processed distribution [..., V] (f32).
    Spec-decode exactness is defined W.R.T. this processed distribution —
    the same processing applies to target and draft."""
    logits = logits.astype(jnp.float32) / max(temperature, 1e-6)
    probs = jax.nn.softmax(logits, axis=-1)
    if top_p < 1.0:
        sorted_probs = jnp.flip(jnp.sort(probs, axis=-1), axis=-1)
        cum = jnp.cumsum(sorted_probs, axis=-1)
        # keep the smallest prefix with mass >= top_p (ties at the cutoff
        # prob all kept — standard nucleus caveat)
        n_keep = jnp.sum(cum - sorted_probs < top_p, axis=-1)
        cutoff = jnp.take_along_axis(
            sorted_probs, jnp.maximum(n_keep - 1, 0)[..., None], axis=-1
        )
        probs = jnp.where(probs >= cutoff, probs, 0.0)
        probs = probs / jnp.maximum(probs.sum(-1, keepdims=True), 1e-30)
    return probs


@partial(
    jax.jit,
    static_argnames=("cfg", "draft_cfg", "max_new_tokens", "k", "temperature", "top_p"),
)
def speculative_generate(
    params,
    draft_params,
    prompt,
    cfg: TransformerConfig,
    draft_cfg: TransformerConfig,
    max_new_tokens: int = 32,
    k: int = 4,
    temperature: float = 0.0,
    top_p: float = 1.0,
    key=None,
):
    """Speculative decoding: a small draft model proposes ``k`` tokens per
    round from its own cache; the target verifies all of them in ONE
    ``decode_chunk`` and commits the accepted prefix plus one more token
    (1..k+1 tokens per target pass).

    ``temperature == 0`` is greedy-exact: output is EXACTLY
    ``generate(params, prompt, cfg, temperature=0.0)`` — a draft token is
    accepted iff it equals the target argmax at that position.

    ``temperature > 0`` is sampling-exact IN DISTRIBUTION via the standard
    accept-reject scheme (Leviathan et al. 2023; Chen et al. 2023): the
    draft SAMPLES x_i ~ q_i, the target accepts with prob
    min(1, p_i(x_i)/q_i(x_i)), and the first rejection resamples from the
    leftover distribution norm(max(p_i - q_i, 0)); a fully-accepted round
    samples its bonus token from p_{k+1}. Each emitted token is marginally
    distributed exactly as temperature/top-p sampling from the target.
    Both models must share the vocab. No cache rewind on rejection: stale
    rows past the committed position are invisible to the position mask and
    simply overwritten next round.

    Returns (tokens [B, max_new_tokens] int32, rounds int32 — target
    passes spent; rounds << max_new_tokens when the draft agrees often).
    """
    sampling = temperature > 0.0
    if key is None:
        key = jax.random.PRNGKey(0)
    B, T = prompt.shape
    S = T + max_new_tokens + k + 1
    t_cache = init_cache(cfg, B, S)
    d_cache = init_cache(draft_cfg, B, S)
    t_logits, t_cache, pos = prefill(params, prompt, t_cache, cfg)
    _, d_cache, _ = prefill(draft_params, prompt, d_cache, draft_cfg)
    # The two caches are position-locked: one pos drives both (they commit
    # the identical token sequence every round).
    key, k0 = jax.random.split(key)
    if sampling:
        p0 = _processed_probs(t_logits, temperature, top_p)
        cur = jax.random.categorical(k0, jnp.log(p0 + 1e-30), axis=-1).astype(jnp.int32)
    else:
        cur = t_logits.argmax(axis=-1).astype(jnp.int32)  # first emitted token

    out = jnp.zeros((B, max_new_tokens), jnp.int32)
    out = out.at[:, 0].set(cur)
    n = jnp.ones((B,), jnp.int32)  # tokens emitted so far

    def draft_propose(d_cache, cur, d_pos, kd):
        # k+1 steps so the draft cache holds rows for cur AND all k
        # proposals (including d_k): a fully-accepted round advances by
        # k+1 rows, and every one of them must be written. The (k+1)-th
        # prediction is discarded.
        def body(carry, kk):
            cache, tok, pos = carry
            logits, cache = decode_step(draft_params, tok, cache, pos, draft_cfg)
            if sampling:
                q = _processed_probs(logits, temperature, top_p)
                nxt = jax.random.categorical(kk, jnp.log(q + 1e-30), axis=-1)
                nxt = nxt.astype(jnp.int32)
            else:
                q = jnp.zeros((B, logits.shape[-1]), jnp.float32)
                nxt = logits.argmax(axis=-1).astype(jnp.int32)
            return (cache, nxt, pos + 1), (nxt, q)

        (d_cache, _, d_pos), (drafts, qs) = lax.scan(
            body, (d_cache, cur, d_pos), jax.random.split(kd, k + 1)
        )
        # proposals [B, k]; their processed draft distributions [B, k, V]
        return d_cache, drafts.T[:, :k], qs.transpose(1, 0, 2)[:, :k], d_pos

    def round_body(state):
        out, n, cur, pos, t_cache, d_cache, rounds, key = state
        key, kd, ka, kb = jax.random.split(key, 4)
        d_cache, drafts, qs, _ = draft_propose(d_cache, cur, pos, kd)
        fed = jnp.concatenate([cur[:, None], drafts], axis=1)  # [B, k+1]
        logits, t_cache = decode_chunk(params, fed, t_cache, pos, cfg)
        if sampling:
            ps = _processed_probs(logits, temperature, top_p)  # [B, k+1, V]
            p_at = jnp.take_along_axis(ps[:, :k], drafts[..., None], axis=-1)[..., 0]
            q_at = jnp.take_along_axis(qs, drafts[..., None], axis=-1)[..., 0]
            u = jax.random.uniform(ka, (B, k))
            # accept x_i iff u < p(x_i)/q(x_i)  (u*q < p is div-by-zero safe)
            accept = u * q_at < p_at
            accepted = jnp.sum(jnp.cumprod(accept.astype(jnp.int32), axis=1), axis=1)
            # Rejection at position r = accepted: resample from the leftover
            # norm(max(p_r - q_r, 0)); full acceptance: sample from p_k.
            p_r = jnp.take_along_axis(
                ps, accepted[:, None, None], axis=1
            )[:, 0]  # [B, V]
            q_r = jnp.take_along_axis(
                qs, jnp.minimum(accepted, k - 1)[:, None, None], axis=1
            )[:, 0]
            q_r = jnp.where((accepted < k)[:, None], q_r, 0.0)
            resid = jnp.maximum(p_r - q_r, 0.0)
            z = resid.sum(-1, keepdims=True)
            # Degenerate residual (p <= q everywhere, numerically) -> p_r.
            resid = jnp.where(z > 1e-30, resid / jnp.maximum(z, 1e-30), p_r)
            bonus = jax.random.categorical(
                kb, jnp.log(resid + 1e-30), axis=-1
            ).astype(jnp.int32)
        else:
            preds = logits.argmax(axis=-1).astype(jnp.int32)  # [B, k+1]
            # accepted[b] = longest prefix of drafts matching target argmax.
            match = drafts == preds[:, :k]  # [B, k]
            accepted = jnp.sum(jnp.cumprod(match.astype(jnp.int32), axis=1), axis=1)
            bonus = jnp.take_along_axis(preds, accepted[:, None], axis=1)[:, 0]
        # Emit d1..d_accepted then the bonus token at the divergence (or
        # after all k when fully accepted): k+1 candidate slots.
        emit = jnp.where(
            jnp.arange(k + 1)[None, :] < accepted[:, None],
            jnp.concatenate([drafts, jnp.zeros((B, 1), jnp.int32)], axis=1),
            0,
        )
        emit = emit.at[jnp.arange(B), accepted].set(bonus)  # slot `accepted`
        n_emit_raw = accepted + 1
        room = jnp.maximum(max_new_tokens - n, 0)
        n_emit = jnp.minimum(n_emit_raw, room)
        # Scatter emit[:, :n_emit] into out at per-row offset n.
        for i in range(k + 1):  # static k: unrolled masked writes
            idx = jnp.clip(n + i, 0, max_new_tokens - 1)
            valid = i < n_emit
            prev = out[jnp.arange(B), idx]
            out = out.at[jnp.arange(B), idx].set(
                jnp.where(valid, emit[:, i], prev)
            )
        # Advance: committed rows are cur + accepted drafts. Rows already
        # at capacity advance nothing (their writes were masked anyway).
        adv = jnp.where(room > 0, accepted + 1, 0)
        new_cur = jnp.where(
            n_emit > 0,
            jnp.take_along_axis(emit, jnp.maximum(n_emit - 1, 0)[:, None], axis=1)[:, 0],
            cur,
        )
        return (out, n + n_emit, new_cur, pos + adv, t_cache, d_cache, rounds + 1, key)

    def round_cond(state):
        _, n, *_rest = state
        return jnp.any(n < max_new_tokens)

    state = (out, n, cur, pos, t_cache, d_cache, jnp.int32(0), key)
    out, n, *_r, rounds, _key = lax.while_loop(round_cond, round_body, state)
    return out, rounds
