"""The architecture in plain ``jax.numpy``: what the system is compared with.

LongCat-Flash-Omni's language model (``longcat_flash``), one SHORTCUT-CONNECTED
DOUBLE LAYER for hidden states ``x`` [T, D]; every projection without bias,
RMSNorm with the configuration's eps:

    h0 = x  + MLA_0(norm_in0(x))
    u0 = norm_post0(h0)
    m  = MoE(u0)                          the shortcut branch: read here ...
    h1 = h0 + FFN_0(u0)                   dense SwiGLU of width ffn_hidden_size
    h2 = h1 + MLA_1(norm_in1(h1))
    y  = h2 + FFN_1(norm_post1(h2)) + m   ... and joined here, an attention and an FFN later

``MoE(u)``: ``s = softmax(u W_r)`` over ALL the router's columns, the
``n_routed_experts`` experts and behind them ``zero_expert_num`` identity
experts; the ``moe_topk`` columns with the largest ``s + b`` are chosen (``b``:
the score correction bias, which chooses and does not weigh); a pick weighs
``routed_scaling_factor * s`` AS IT STANDS, not divided by the sum over the
chosen; ``MoE(u) = sum over the picks that are experts of w SwiGLU_e(u) + (sum
over the picks that are identities of w) u``. No shared expert.

``MLA(h)``, in EXPANDED form (the program attends in absorbed form over cached
latents; the two are the same function): ``c_q = norm(h W_qa)``, ``q = (c_q
W_qb) * sqrt(hidden_size / q_lora_rank)`` per head ``[q_nope | q_rope]``,
``q_rope <- rope``; ``[c_kv | k_r] = h W_kva``, ``c = norm(c_kv) *
sqrt(hidden_size / kv_lora_rank)``, ``k_r <- rope`` (one for all heads, not
scaled); per head ``[k_nope | v] = c W_kvb``; scores ``(q_nope . k_nope + q_rope
. k_r) / sqrt(nope + rope)``, causal softmax, ``o = sum p v``, ``concat(o) W_o``.

**The share.** The parameter tree holds ONE chip's experts of an
expert-parallel deployment (``deployment.expert_parallel``: ``chips`` that
share every layer's experts, this one the ``index``-th): ``n_routed_experts``
experts of the published count, those from ``index * n_routed_experts`` on. The
router is as wide as all the published experts and the identities and chooses
among all; a pick of an expert that is not held adds nothing here, as in the
program, and that partial branch joins the residual path. The identity term is
the layer's own and is added whole. With ``chips`` 1 this is the whole layer.

Float32 throughout, ``default_matmul_precision("highest")``, no kernel, no
cache, no sorting, no grouped matmul, nothing of ``ray_tpu/parallel/moe.py`` or
``ray_tpu/ops/``: every held expert is run over every token, one at a time, and
weighted (zero where not chosen). Departures, all to fit beside the system under
test on the chip: heads are processed a group at a time, a dense FFN a block of
its width at a time and experts one at a time (each cast to float32 as it is
used), the head a block of the vocabulary at a time, and the serving check runs
a layer at a time. Rotary halves are rotated (``rotate_half``), the program's
layout (the configuration's ``assumed``).

**Near-ties of the router** (``make_layerwise_logits``). Top-k routing is a
discontinuous function of the hidden state: where the k-th and (k+1)-th biased
scores of a token nearly tie, a system that computes in bfloat16 and this
float32 reference choose differently, both rightly, and a pick that changes
from an identity to an expert of another chip takes its whole weight out of the
branch here. So the serving check computes the logits UNDER THE SYSTEM'S
ROUTING: the engine keeps beside each cached token the columns it picked
(``submit(return_routed_experts=True)``), the check asks it to serve the same
prompt once more, greedy, and takes the picks of every token, prompt and
generated, if the tokens come out as given. One plain forward pass follows, in
which a token of a layer takes the system's picks IF THIS REFERENCE ADMITS
THEM: each must score, by the reference's own float32 biased scores, within
``ROUTER_TIE`` of the reference's k-th best. Otherwise, and where the system has
no answer, the reference's own top-k stands, and the logits show it. The
weights are always the reference's own scores of the columns taken.

The names below are the one adapter to the program: where each weight sits in
its parameter tree (``models/transformer.py:init_params``; one stack of double
layers, a sub-layer's leaves [layers, 2, ...], the branch's [layers, ...],
matrices stored [in, out], expert matrices [experts held, in, out]).
"""

from __future__ import annotations

import sys

import jax
import jax.numpy as jnp
import numpy as np

EMBED, FINAL_NORM, HEAD = "embed", "norm_f", "lm_head"
LAYERS = "layers"
ATTENTION_LEAVES = {
    "attn_norm": "attn_norm", "w_qa": "wq_a", "q_norm": "q_norm", "w_qb": "wq_b",
    "w_kva": "wkv_a", "kv_norm": "kv_norm", "w_kvb": "wkv_b", "w_o": "wo", "mlp_norm": "mlp_norm",
}
DENSE_LEAVES = {"w_gate": "wg", "w_up": "wi", "w_down": "wo_mlp"}
ROUTER_LEAVES = {"w_router": "gate", "router_bias": "gate_bias"}
EXPERT_LEAVES = {"w_gate": "wg_e", "w_up": "wi_e", "w_down": "wo_e"}
F32 = jnp.float32
VOCAB_BLOCKS = 8
FFN_BLOCKS = 4


def _take(stack: dict, names: dict, *index) -> dict:
    """Leaves of one sub-layer (or of one expert of one layer), in float32."""
    out = {}
    for ours, theirs in names.items():
        leaf = stack[theirs]
        for i in index:
            leaf = jax.lax.dynamic_index_in_dim(leaf, i, 0, keepdims=False)
        out[ours] = leaf.astype(F32)
    return out


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def rope(x, positions, theta):
    """x [T, H, d]; rotate_half convention."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=F32) / half)
    angles = positions.astype(F32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def swiglu(w: dict, h):
    return (jax.nn.silu(h @ w["w_gate"]) * (h @ w["w_up"])) @ w["w_down"]


def dense_ffn(stack: dict, index, sub, h):
    """A sub-layer's dense SwiGLU over h [T, D], a block of its width at a time
    (the sum over the blocks is the whole product: SwiGLU is elementwise across
    the width)."""
    names = {ours: stack[theirs] for ours, theirs in DENSE_LEAVES.items()}
    F = names["w_up"].shape[-1]
    blocks = FFN_BLOCKS if F % FFN_BLOCKS == 0 else 1
    width = F // blocks

    def block(acc, i):
        def cut(name, axis):
            leaf = jax.lax.dynamic_index_in_dim(jax.lax.dynamic_index_in_dim(names[name], index, 0, False), sub, 0, False)
            return jax.lax.dynamic_slice_in_dim(leaf, i * width, width, axis=axis).astype(F32)

        return acc + swiglu({"w_gate": cut("w_gate", 1), "w_up": cut("w_up", 1), "w_down": cut("w_down", 0)}, h), None

    return jax.lax.scan(block, jnp.zeros_like(h), jnp.arange(blocks))[0]


def latent_scales(m: dict) -> tuple:
    """(what multiplies a head's whole query, what multiplies the normed key-value latent)."""
    D = m["hidden_size"]
    return ((D / m["q_lora_rank"]) ** 0.5 if m["mla_scale_q_lora"] else 1.0,
            (D / m["kv_lora_rank"]) ** 0.5 if m["mla_scale_kv_lora"] else 1.0)


def attention(w: dict, x, positions, m: dict, head_group: int = 4):
    """x [T, D] -> the attention branch [T, D] of one whole sequence (token j
    sits at position j and sees j' <= j). Keys and values are expanded per head."""
    T = x.shape[0]
    H, R = m["num_attention_heads"], m["kv_lora_rank"]
    N, P, Vd = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    eps, theta = m["rms_norm_eps"], float(m["rope_theta"])
    scale_q, scale_kv = latent_scales(m)
    h = rms_norm(x, w["attn_norm"], eps)
    q = (rms_norm(h @ w["w_qa"], w["q_norm"], eps) @ w["w_qb"]).reshape(T, H, N + P) * scale_q
    q = jnp.concatenate([q[..., :N], rope(q[..., N:], positions, theta)], axis=-1)
    kv = h @ w["w_kva"]
    c = rms_norm(kv[:, :R], w["kv_norm"], eps) * scale_kv
    k_r = rope(kv[:, None, R:], positions, theta)
    kv = (c @ w["w_kvb"]).reshape(T, H, N + Vd)
    k = jnp.concatenate([kv[..., :N], jnp.broadcast_to(k_r, (T, H, P))], axis=-1)
    v = kv[..., N:]
    mask = jnp.arange(T)[None, :] <= positions[:, None]

    @jax.checkpoint
    def group(args):
        qg, kg, vg = args  # [T, g, .]
        s = jnp.einsum("tgd,sgd->gts", qg, kg) * (N + P) ** -0.5
        p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("gts,sgd->tgd", p, vg)

    g = min(head_group, H)
    split = lambda a: jnp.moveaxis(a.reshape(a.shape[0], H // g, g, a.shape[-1]), 1, 0)  # noqa: E731
    o = jax.lax.map(group, (split(q), split(k), split(v)))  # [H/g, T, g, Vd]
    return jnp.moveaxis(o, 0, 1).reshape(T, H * Vd) @ w["w_o"]


# A system's picks are admitted where each scores, by this reference's own
# biased scores, at most this far under the reference's k-th best (module
# docstring). Scores are a softmax over 768 columns of logits of standard
# deviation 1 (the router is drawn at the program's ``hidden_size ** -0.5``):
# the mean is 0.0013, the 12th best lies near 0.007 and its neighbour in rank
# 0.0002 under it. Measured on the v5e (PR 61; every token of a run's three
# checked sequences in every layer, 10,724 decisions a run, twenty runs): the
# served bfloat16 system's picks lay under by more than 0.001 in 22-36 of a
# run's, 0.0015 in 1-6, 0.002 in one each of three runs', 0.0025 in none, at
# most 0.00236; the misplaced join's (a planted fault) by up to 0.0044, 5-9 of
# a run's over 0.003; the 3-mantissa-bit control's by up to 0.0081, 2,700 of a
# run's over 0.003 and 340-385 over 0.005 (the configuration's
# ``check.logit_gap_tol_why``).
ROUTER_TIE = 0.0035
DEFICIT_STEPS = (0.0002, 0.0004, 0.0006, 0.001, 0.0015, 0.002, 0.0025, 0.003, 0.0035, 0.005)


def share(m: dict) -> tuple:
    """(index, chips): which run of the experts the parameter tree holds."""
    ep = m["deployment"]["expert_parallel"]
    return int(ep["index"]), int(ep["chips"])


def experts_routed_among(m: dict) -> int:
    """The experts the router chooses among, held here or not: the identities' columns start behind them."""
    return m["n_routed_experts"] * share(m)[1]


def routing_weights(w: dict, u, m: dict, served=None):
    """([T, W]: each token's weight on each of ALL the router's W columns, experts
    then identities, zero where not picked; [T]: how far the lowest of the system's
    picks lies under this reference's k-th best biased score, 0 without
    ``served``). ``served`` [T, k] int32 (optional): the columns the system picked,
    a row of -1 where it has no answer; admitted as the module docstring says."""
    k = m["moe_topk"]
    s = jax.nn.softmax(u @ w["w_router"], axis=-1)
    biased = s + w["router_bias"]
    top, chosen = jax.lax.top_k(biased, k)
    deficit = jnp.zeros(u.shape[:1], F32)
    if served is not None:
        theirs = jnp.take_along_axis(biased, jnp.maximum(served, 0), axis=-1)
        answered = jnp.all(served >= 0, axis=-1)
        deficit = jnp.where(answered, jnp.max(top[:, -1:] - theirs, axis=-1), 0.0)
        chosen = jnp.where((answered & (deficit <= ROUTER_TIE))[:, None], served, chosen)
    picked = jnp.max(jax.nn.one_hot(chosen, s.shape[-1], dtype=F32), axis=1)  # [T, W] of 0 / 1
    return m["routed_scaling_factor"] * s * picked, deficit


def shortcut_branch(stack: dict, index, u, m: dict, served=None):
    """u [T, D], the first FFN's normed input -> (``MoE(u)``: the held experts'
    part and the identity term [T, D], each token's deficit: ``routing_weights``)."""
    if m["zero_expert_type"] != "identity":
        raise ValueError(f"zero_expert_type {m['zero_expert_type']!r}: this reference computes 'identity' only")
    weights, deficit = routing_weights(_take(stack, ROUTER_LEAVES, index), u, m, served)
    held, experts = m["n_routed_experts"], experts_routed_among(m)
    first = share(m)[0] * held

    def one_expert(acc, e):
        out = swiglu(_take(stack, EXPERT_LEAVES, index, e), u)
        return acc + jax.lax.dynamic_index_in_dim(weights, first + e, 1, keepdims=True) * out, None

    routed, _ = jax.lax.scan(one_expert, jnp.zeros_like(u), jnp.arange(held))
    return routed + jnp.sum(weights[:, experts:], axis=-1, keepdims=True) * u, deficit


def double_layer(stack: dict, index, x, positions, m: dict, served=None):
    """Layer ``index``: x [T, D] -> ([T, D], each token's deficit)."""
    eps = m["rms_norm_eps"]
    w0, w1 = _take(stack, ATTENTION_LEAVES, index, 0), _take(stack, ATTENTION_LEAVES, index, 1)
    h0 = x + attention(w0, x, positions, m)
    u0 = rms_norm(h0, w0["mlp_norm"], eps)
    branch, deficit = shortcut_branch(stack, index, u0, m, served)
    h1 = h0 + dense_ffn(stack, index, 0, u0)
    h2 = h1 + attention(w1, h1, positions, m)
    return h2 + dense_ffn(stack, index, 1, rms_norm(h2, w1["mlp_norm"], eps)) + branch, deficit


def head_logits(params: dict, x, m: dict):
    """x [n, D] -> [n, V], the head a block of the vocabulary at a time."""
    x = rms_norm(x, params[FINAL_NORM].astype(F32), m["rms_norm_eps"])
    V = m["vocab_size"]
    blocks = VOCAB_BLOCKS if V % VOCAB_BLOCKS == 0 else 1
    width = V // blocks

    def block(i):
        return x @ jax.lax.dynamic_slice_in_dim(params[HEAD], i * width, width, axis=1).astype(F32)

    return jnp.moveaxis(jax.lax.map(block, jnp.arange(blocks)), 0, 1).reshape(x.shape[0], V)


def sequence_logits(params: dict, tokens, m: dict):
    """tokens [T] -> logits [T, V]: the whole forward pass of one sequence, the
    reference's own routing choice everywhere."""
    with jax.default_matmul_precision("highest"):
        positions = jnp.arange(len(tokens))
        x = params[EMBED][jnp.asarray(tokens, jnp.int32)].astype(F32)
        for index in range(m["num_layers"]):
            x, _ = double_layer(params[LAYERS], index, x, positions, m)
        return head_logits(params, x, m)


def serving_engine(params):
    """The engine in this process that serves ``params``, or None."""
    from ray_tpu.serve.llm import stats

    return next((e for e in stats.ENGINES if e.params is params), None)


def served_routing(engine, prompt: list, new: list):
    """The columns the serving system picked for every token it was fed when it
    answered ``prompt`` with ``new`` (greedy): int [len(prompt) + len(new) - 1,
    layers, k]. None where it now answers otherwise (a system that does not
    repeat itself is held to the reference's own choices)."""
    request = engine.submit(prompt, max_new_tokens=len(new), return_routed_experts=True)
    return request.routed_experts if request.result(timeout=300.0) == list(new) else None


def served_deficits(deficits, fed: int) -> dict:
    """Of the [layers, T] deficits of one sequence's first ``fed`` tokens: the
    largest, and how many lie over each of ``DEFICIT_STEPS``."""
    d = np.asarray(deficits)[:, :fed]
    return {"decisions": int(d.size), "max": float(d.max(initial=0.0)),
            "over": {str(t): int((d > t).sum()) for t in DEFICIT_STEPS}}


def make_layerwise_logits(m: dict):
    """Serving check: a layer at a time, so that only one float32 layer's worth
    sits beside the replica's weights. Returns ``logits(params, tokens, rows)``
    giving the logits [len(rows), V] of one sequence at the given positions,
    ``rows`` the positions that predict the tokens the system generated: under
    the system's routing where this reference admits it (module docstring)."""

    @jax.jit
    def embedded(params, tokens):
        return params[EMBED][tokens].astype(F32)

    @jax.jit
    def one_layer(params, index, x, served):
        with jax.default_matmul_precision("highest"):
            return double_layer(params[LAYERS], index, x, jnp.arange(x.shape[0]), m, served)

    @jax.jit
    def head(params, x, rows):
        with jax.default_matmul_precision("highest"):
            return head_logits(params, x[rows], m)

    def logits(params, tokens, rows):
        tokens = [int(t) for t in tokens]
        fed = rows[-1] + 1  # what the system was fed when it answered: all but the last token it drew
        engine = serving_engine(params)
        theirs = None if engine is None else served_routing(engine, tokens[: rows[0] + 1], tokens[rows[0] + 1 : fed + 1])
        served = np.full((len(tokens), m["num_layers"], m["moe_topk"]), -1, np.int32)
        if theirs is not None:
            served[: len(theirs)] = theirs
        x = embedded(params, jnp.asarray(tokens, jnp.int32))
        deficits = []
        for index in range(m["num_layers"]):
            x, deficit = one_layer(params, jnp.int32(index), x, jnp.asarray(served[:, index]))
            deficits.append(deficit)
        if engine is not None:
            print(f"[reference] {fed} tokens fed: served picks under the k-th best: "
                  f"{theirs is not None and served_deficits(deficits, len(theirs))}", file=sys.stderr, flush=True)
        return head(params, x, jnp.asarray(rows, jnp.int32))

    return logits
