"""The architecture in plain ``jax.numpy``: what the system is compared with.

Trinity-Mini (``afmoe``), one layer for hidden states ``x`` [T, D]; every
projection without bias, RMSNorm with the configuration's eps. As the public
modelling code states it; what no key of ``config.json`` carries is listed in
the configuration's ``assumed``.

``x0 = embed[token] * sqrt(hidden_size)`` (``mup_enabled``).

Attention, ``h = norm_in(x)``: ``q = h W_q`` as [H, head_dim], ``k = h W_k``,
``v = h W_v`` as [KV, head_dim], ``g = h W_g`` [H * head_dim]; ``q`` and ``k``
RMSNorm-ed over the head's width with a learned weight each; rotary on ``q``
and ``k`` in ``sliding_attention`` layers ONLY (``full_attention`` layers carry
no positional encoding); scores ``q . k / sqrt(head_dim)``, causal, and in a
sliding layer ``q_pos - k_pos < sliding_window``; softmax; ``o = (P v) *
sigmoid(g)``; ``x += norm_post_attn(o W_o)``.

Feed-forward, ``h = norm_pre_mlp(x)``. The leading ``num_dense_layers`` layers:
SwiGLU of width ``intermediate_size``. The others: ``s = sigmoid(h W_r)``; the
``num_experts_per_tok`` experts with the largest ``s + b`` (``b``: the expert
bias, which chooses and does not weigh); weights ``route_scale * s[chosen] /
(sum s[chosen] + 1e-20)``; ``m = SwiGLU_shared(h) + sum_e w_e SwiGLU_e(h)``.
``x += norm_post_mlp(m)``. No token is dropped: every expert is run over every
token and weighted (zero where not chosen). Final RMSNorm, untied head.

Float32 throughout, ``default_matmul_precision("highest")``, no kernel, no
cache, no ring, no sorting, no grouped matmul: a window is a band of the
[T, T] mask. Departures, all to fit beside the system under test on the chip:
attention is computed a key-value head's group of query heads at a time (the
scores of 3200 tokens are 0.33 GB a group), experts one at a time (each cast to
float32 as it is used), the head a block of the vocabulary at a time (1.64 GB
whole), and the serving check runs a layer at a time. Rotary halves are rotated
(``rotate_half``), the program's layout and, as far as recalled, the published
one.

**Near-ties of the router** (``make_layerwise_logits``): PR 32's method, as
``Glm4MoeLiteForCausalLM/reference.py`` sets it out. Top-k routing is a
discontinuous function of the hidden state: where the k-th and (k+1)-th biased
scores of a token nearly tie, a system that computes in bfloat16 and this
float32 reference choose differently, both rightly, and the expert at the
boundary carries about an eighth of the routed output. So the serving check
computes the logits UNDER THE SYSTEM'S ROUTING: the engine keeps beside each
cached token the experts it took (``submit(return_routed_experts=True)``; two
int32 words a token a layer here), the check asks it to serve the same prompt
once more, greedy, and takes the choices of every token, prompt and generated,
if the tokens come out as given. One plain forward pass follows, in which a
token of a layer goes to the system's experts IF THIS REFERENCE ADMITS THEM:
each must score, by the reference's own float32 biased scores, within
``ROUTER_TIE`` of the reference's k-th best. Otherwise, and where the system has
no answer, the reference's own top-k stands, and the logits show it. The
weights are always the reference's own scores of the experts taken.

The names below are the one adapter to the program: where each weight sits in
its parameter tree (``models/transformer.py:init_params``; two stacks of
layers, matrices stored [in, out], expert matrices [E, in, out]).
"""

from __future__ import annotations

import sys

import jax
import jax.numpy as jnp
import numpy as np

EMBED, FINAL_NORM, HEAD = "embed", "norm_f", "lm_head"
DENSE_LAYERS, EXPERT_LAYERS = "dense_layers", "layers"
ATTENTION_LEAVES = {
    "attn_norm": "attn_norm", "w_q": "wq", "w_k": "wk", "w_v": "wv", "w_gate": "wg_attn", "w_o": "wo",
    "q_norm": "q_norm", "k_norm": "k_norm", "attn_post_norm": "attn_post_norm",
    "mlp_norm": "mlp_norm", "mlp_post_norm": "mlp_post_norm",
}
DENSE_LEAVES = {"w_gate": "wg", "w_up": "wi", "w_down": "wo_mlp"}
ROUTER_LEAVES = {"w_router": "gate", "router_bias": "gate_bias"}
SHARED_LEAVES = {"w_gate": "wg_s", "w_up": "wi_s", "w_down": "wo_s"}
EXPERT_LEAVES = {"w_gate": "wg_e", "w_up": "wi_e", "w_down": "wo_e"}
SLIDING = "sliding_attention"
F32 = jnp.float32
VOCAB_BLOCKS = 8


def _take(stack: dict, names: dict, *index) -> dict:
    """Leaves of one layer (or of one expert of one layer), in float32."""
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


def attention(w: dict, x, positions, m: dict, sliding: bool):
    """x [T, D] -> the attention branch [T, D] of one whole sequence (token j
    at ``positions[j]``), its second norm included. ``sliding``: a
    ``sliding_attention`` layer (rotary, and a window) or a ``full_attention``
    one (neither)."""
    T = x.shape[0]
    H, KV, Dh = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    eps = m["rms_norm_eps"]
    h = rms_norm(x, w["attn_norm"], eps)
    q = rms_norm((h @ w["w_q"]).reshape(T, H, Dh), w["q_norm"], eps)
    k = rms_norm((h @ w["w_k"]).reshape(T, KV, Dh), w["k_norm"], eps)
    v = (h @ w["w_v"]).reshape(T, KV, Dh)
    behind = positions[:, None] - positions[None, :]  # query's position - key's
    mask = behind >= 0
    if sliding:
        q, k = rope(q, positions, float(m["rope_theta"])), rope(k, positions, float(m["rope_theta"]))
        mask &= behind < m["sliding_window"]

    def group(args):
        qg, kg, vg = args  # the query heads [T, H / KV, Dh] that share one key-value head [T, Dh]
        s = jnp.einsum("trd,sd->rts", qg, kg) * Dh**-0.5
        p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("rts,sd->trd", p, vg)

    by_group = jnp.moveaxis(q.reshape(T, KV, H // KV, Dh), 1, 0)
    o = jax.lax.map(group, (by_group, jnp.moveaxis(k, 1, 0), jnp.moveaxis(v, 1, 0)))  # [KV, T, H / KV, Dh]
    o = jnp.moveaxis(o, 0, 1).reshape(T, H * Dh) * jax.nn.sigmoid(h @ w["w_gate"])
    return rms_norm(o @ w["w_o"], w["attn_post_norm"], eps)


def biased_scores(w: dict, h):
    """(s, s + b): what weighs, and what chooses."""
    s = jax.nn.sigmoid(h @ w["w_router"])
    return s, s + w["router_bias"]


# A system's choice of experts is admitted where each scores, by this
# reference's own biased scores, at most this far under the reference's k-th
# best (module docstring). Calibrated for top-8 of 128 on the v5e (PR 35): the
# configuration's ``check.logit_gap_tol_why`` gives the readings. The served
# bfloat16 system's experts lay at most 0.0068 under (266,000 decisions), the
# count falling ~4.5x per 0.001; with 3-mantissa-bit operands 31 % of the
# decisions lie further under than this.
ROUTER_TIE = 0.01
# Thresholds the deficit of the served system's experts is counted over, for
# the line ``served_deficits`` logs: the next calibration reads it.
DEFICIT_STEPS = (0.002, 0.003, 0.004, 0.005, 0.0075, 0.01, 0.015)


def routing_weights(w: dict, h, m: dict, served=None):
    """([T, E]: each token's weight on each expert, zero where not taken; [T]:
    how far the lowest of the system's experts lies under this reference's k-th
    best biased score, 0 without ``served``). ``served`` [T, k] int32
    (optional): the experts the system took, a row of -1 where it has no
    answer; admitted as the module docstring says."""
    E, k = m["num_experts"], m["num_experts_per_tok"]
    s, biased = biased_scores(w, h)
    top, chosen = jax.lax.top_k(biased, k)
    deficit = jnp.zeros(h.shape[:1], F32)
    if served is not None:
        theirs = jnp.take_along_axis(biased, jnp.maximum(served, 0), axis=-1)
        answered = jnp.all(served >= 0, axis=-1)
        deficit = jnp.where(answered, jnp.max(top[:, -1:] - theirs, axis=-1), 0.0)
        admitted = (answered & (deficit <= ROUTER_TIE))[:, None]
        chosen = jnp.where(admitted, served, chosen)
    picked = jnp.max(jax.nn.one_hot(chosen, E, dtype=F32), axis=1)  # [T, E] of 0 / 1
    kept = s * picked
    return m["route_scale"] * kept / (jnp.sum(kept, axis=-1, keepdims=True) + 1e-20), deficit


def dense_layer(stack: dict, index, x, positions, m: dict, sliding: bool):
    w = _take(stack, ATTENTION_LEAVES, index)
    x = x + attention(w, x, positions, m, sliding)
    h = rms_norm(x, w["mlp_norm"], m["rms_norm_eps"])
    return x + rms_norm(swiglu(_take(stack, DENSE_LEAVES, index), h), w["mlp_post_norm"], m["rms_norm_eps"])


def expert_layer(stack: dict, index, x, positions, m: dict, sliding: bool, served=None):
    """-> (x, each token's deficit: ``routing_weights``)."""
    w = _take(stack, ATTENTION_LEAVES, index)
    x = x + attention(w, x, positions, m, sliding)
    h = rms_norm(x, w["mlp_norm"], m["rms_norm_eps"])
    weights, deficit = routing_weights(_take(stack, ROUTER_LEAVES, index), h, m, served)

    def one_expert(acc, e):
        out = swiglu(_take(stack, EXPERT_LEAVES, index, e), h)
        return acc + jax.lax.dynamic_index_in_dim(weights, e, 1, keepdims=True) * out, None

    routed, _ = jax.lax.scan(one_expert, jnp.zeros_like(h), jnp.arange(m["num_experts"]))
    if m["num_shared_experts"]:
        routed = routed + swiglu(_take(stack, SHARED_LEAVES, index), h)
    return x + rms_norm(routed, w["mlp_post_norm"], m["rms_norm_eps"]), deficit


def embed(params: dict, tokens, m: dict):
    x = params[EMBED][tokens].astype(F32)
    return x * float(m["hidden_size"]) ** 0.5 if m["mup_enabled"] else x


def head_logits(params: dict, x, m: dict):
    """x [n, D] -> [n, V], the head a block of the vocabulary at a time."""
    x = rms_norm(x, params[FINAL_NORM].astype(F32), m["rms_norm_eps"])
    V = m["vocab_size"]
    blocks = VOCAB_BLOCKS if V % VOCAB_BLOCKS == 0 else 1
    width = V // blocks

    def block(i):
        return x @ jax.lax.dynamic_slice_in_dim(params[HEAD], i * width, width, axis=1).astype(F32)

    return jnp.moveaxis(jax.lax.map(block, jnp.arange(blocks)), 0, 1).reshape(x.shape[0], V)


def _sliding(m: dict) -> list:
    return [t == SLIDING for t in m["layer_types"]]


def sequence_logits(params: dict, tokens, m: dict):
    """tokens [T] -> logits [T, V]: the whole forward pass of one sequence, the
    reference's own routing choice everywhere."""
    with jax.default_matmul_precision("highest"):
        positions = jnp.arange(len(tokens))
        x = embed(params, jnp.asarray(tokens, jnp.int32), m)
        n_dense, sliding = m["num_dense_layers"], _sliding(m)
        for index in range(n_dense):
            x = dense_layer(params[DENSE_LAYERS], index, x, positions, m, sliding[index])
        for index in range(m["num_hidden_layers"] - n_dense):
            x, _ = expert_layer(params[EXPERT_LAYERS], index, x, positions, m, sliding[n_dense + index])
        return head_logits(params, x, m)


def served_routing(params, prompt: list, new: list):
    """The experts the serving system took for every token it was fed when it
    answered ``prompt`` with ``new`` (greedy): int [len(prompt) + len(new) - 1,
    expert layers, k], asked of the engine in this process that serves
    ``params``. None where there is no such engine or where it now answers
    otherwise (a system that does not repeat itself is held to the reference's
    own choices)."""
    from ray_tpu.serve.llm import stats

    engine = next((e for e in stats.ENGINES if e.params is params), None)
    if engine is None:
        return None
    request = engine.submit(prompt, max_new_tokens=len(new), return_routed_experts=True)
    return request.routed_experts if request.result(timeout=300.0) == list(new) else None


def served_deficits(deficits, fed: int) -> dict:
    """Of the [expert layers, T] deficits of one sequence's first ``fed``
    tokens: the largest, and how many lie over each of ``DEFICIT_STEPS``."""
    d = np.asarray(deficits)[:, :fed]
    return {"decisions": int(d.size), "max": float(d.max(initial=0.0)),
            "over": {str(t): int((d > t).sum()) for t in DEFICIT_STEPS}}


def make_layerwise_logits(m: dict):
    """Serving check: a layer at a time, so that only one float32 layer's
    worth sits beside the replica's weights. Returns ``logits(params, tokens,
    rows)`` giving the logits [len(rows), V] of one sequence at the given
    positions, ``rows`` the positions that predict the tokens the system
    generated: under the system's routing where this reference admits it
    (module docstring)."""
    n_dense = m["num_dense_layers"]
    n_expert = m["num_hidden_layers"] - n_dense
    sliding = _sliding(m)

    @jax.jit
    def embedded(params, tokens):
        return embed(params, tokens, m)

    @jax.jit
    def head(params, x, rows):
        with jax.default_matmul_precision("highest"):
            return head_logits(params, x[rows], m)

    def one_dense(params, index, x, kind):
        with jax.default_matmul_precision("highest"):
            return dense_layer(params[DENSE_LAYERS], index, x, jnp.arange(x.shape[0]), m, kind)

    def one_expert(params, index, x, served, kind):
        with jax.default_matmul_precision("highest"):
            return expert_layer(params[EXPERT_LAYERS], index, x, jnp.arange(x.shape[0]), m, kind, served)

    # One program a kind of layer: the index is traced, the kind is not.
    one_dense = jax.jit(one_dense, static_argnums=3)
    one_expert = jax.jit(one_expert, static_argnums=4)

    def logits(params, tokens, rows):
        tokens = [int(t) for t in tokens]
        served = np.full((len(tokens), n_expert, m["num_experts_per_tok"]), -1, np.int32)
        theirs = served_routing(params, tokens[: rows[0] + 1], tokens[rows[0] + 1 : rows[-1] + 2])
        if theirs is not None:
            served[: len(theirs)] = theirs
        x = embedded(params, jnp.asarray(tokens, jnp.int32))
        for index in range(n_dense):
            x = one_dense(params, jnp.int32(index), x, sliding[index])
        deficits = []
        for index in range(n_expert):
            x, deficit = one_expert(params, jnp.int32(index), x, jnp.asarray(served[:, index]), sliding[n_dense + index])
            deficits.append(deficit)
        if theirs is not None:
            print(f"[reference] served experts under the k-th best: {served_deficits(deficits, len(theirs))}",
                  file=sys.stderr, flush=True)
        return head(params, x, jnp.asarray(rows, jnp.int32))

    return logits
