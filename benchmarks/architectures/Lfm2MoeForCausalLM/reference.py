"""The architecture in plain ``jax.numpy``: what the system is compared with.

LFM2-24B-A2B (``lfm2_moe``): gated short-convolution layers and QK-normed GQA
layers in the order ``layer_types`` gives (``conv``, ``full_attention``), every
layer a mixer and then an MLP. One layer for hidden states ``x`` [T, D] of one
whole sequence, ``h = RMSNorm(x; operator_norm, norm_eps)``:

``conv`` mixer: ``[B | C | u] = h W_in`` (three D-wide parts in that order);
``v_t = B_t * u_t``; ``c_t = sum_i w[i] * v_{t - (L - 1) + i}`` over ``conv_L_cache``
= L tokens, one filter a channel, no bias, zeros before the first token; ``y_t
= C_t * c_t``; ``y W_out``. No activation anywhere in it.

``full_attention`` mixer: ``q = h W_q`` as [heads, head_dim], ``k = h W_k``, ``v =
h W_v`` as [KV heads, head_dim]; RMSNorm over each head of q and of k with a
learned [head_dim] weight; rotary over the whole head at ``rope_theta``
(rotate-half); causal softmax at ``head_dim^-1/2``, a KV head's group of query
heads at a time; ``W_o``.

Every layer: ``x <- x + mixer(h)``, ``g = RMSNorm(x; ffn_norm)``, ``x <- x +
ffn(g)``. The first ``num_dense_layers`` layers: ``(silu(g W_1) * (g W_3)) W_2``
at ``intermediate_size``. The others: ``s = sigmoid(g W_r)``; the
``num_experts_per_tok`` experts with the largest ``s + expert_bias`` (the bias
chooses and does not weigh); ``w = routed_scaling_factor * s[chosen] / (sum
s[chosen] + 1e-6)``; ``sum_e w_e (silu(g W_1e) * (g W_3e)) W_2e``. Every expert is
run over every token and weighted (zero where not chosen): no token is dropped,
nothing is sorted or grouped. After the last layer RMSNorm (``embedding_norm``),
then the embedding's transpose (tied). What no key of ``config.json`` carries is
listed in the configuration's ``assumed``.

Float32 throughout, ``default_matmul_precision("highest")``, no kernel, no
cache, no chunk, no batching; nothing of ``ray_tpu/ops`` or
``ray_tpu/parallel`` is imported here. Departures, all to fit beside the system
under test on the chip: the experts one at a time (each cast to float32 as it
is used), the head a block of the vocabulary at a time, and the serving check
runs a layer at a time.

**Near-ties of the router** (``make_layerwise_logits``): PR 32's method, as
``Glm4MoeLiteForCausalLM/reference.py`` sets it out. The serving check asks the
engine in this process to serve the sequence once more
(``submit(return_routed_experts=True, return_state=True)``) and computes the
logits under the system's choices where this reference admits them: each chosen
expert must score, by the reference's own float32 biased scores, within
``ROUTER_TIE`` of the reference's k-th best. Otherwise the reference's own top-k
stands. The weights are always the reference's own scores.

**The rows a slot carries.** A conv layer keeps no state but the last L - 1 rows
of ``v`` ahead of its filter. From the same request the check takes what the
slot holds after the last token fed and compares the FIRST layer's (layer 0 is
a conv layer fed by the embeddings: the same numbers here and there) with this
file's own ``v_{fed-2}, v_{fed-1}``: ``|v_served - v| / |v|`` over the layer's
rows, held to ``check.state_gap_tol``; the other conv layers' gaps are printed
and not held. Where it is over the limit, or the engine answers otherwise than
it did, the sequence's logits come back NaN, which the harness reads as not
finite and not correct. One departure, for this comparison alone (``rounded``):
the rows are held to ``v`` over the normed input, the projection and the product
ROUNDED to the configuration's ``torch_dtype`` where the served model rounds
them (``lax.reduce_precision``, not a cast there and back, which the TPU's
compiler drops where it can: ``NemotronHForCausalLM/reference.py`` has the
story). The logits are computed without the rounding.

The names below are the one adapter to the program: where each weight sits in
its parameter tree (``models/transformer.py:init_params``: the leading dense
layers, then two stacks by kind, each in the order its kind's layers come in the
model; matrices stored [in, out], expert matrices [experts, in, out], the
filter [taps, channels]).
"""

from __future__ import annotations

import sys

import jax
import jax.numpy as jnp
import numpy as np

EMBED, FINAL_NORM = "embed", "norm_f"
CONV, ATTENTION = "conv", "full_attention"
DENSE_STACK, STACKS = "dense_layers", {CONV: "conv_layers", ATTENTION: "layers"}
CONV_LEAVES = {"operator_norm": "attn_norm", "w_in": "w_in", "filter": "conv_w", "w_out": "wo", "ffn_norm": "mlp_norm"}
ATTENTION_LEAVES = {
    "operator_norm": "attn_norm", "w_q": "wq", "w_k": "wk", "w_v": "wv", "w_o": "wo", "q_norm": "q_norm", "k_norm": "k_norm",
    "ffn_norm": "mlp_norm",
}
DENSE_LEAVES = {"w_1": "wg", "w_3": "wi", "w_2": "wo_mlp"}
ROUTER_LEAVES = {"w_router": "gate", "router_bias": "gate_bias"}
EXPERT_LEAVES = {"w_1": "wg_e", "w_3": "wi_e", "w_2": "wo_e"}
F32 = jnp.float32
VOCAB_BLOCKS = 8
NORM_TOPK_EPS = 1e-6


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
    """x [T, H, d]; rotate-half convention, the whole head."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=F32) / half)
    angles = positions.astype(F32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def swiglu(w: dict, g):
    return (jax.nn.silu(g @ w["w_1"]) * (g @ w["w_3"])) @ w["w_2"]


def gated_rows(w: dict, h, rounded=None):
    """h [T, D], normed -> (v [T, D] = B * u, the gate C [T, D]). ``rounded`` (a
    dtype, optional): the normed input, the projection and the product take
    that dtype's values on the way, as the served model's do (module
    docstring: the carried rows' comparison only)."""
    bits = jnp.finfo(rounded) if rounded else None
    as_served = (lambda a: jax.lax.reduce_precision(a, bits.nexp, bits.nmant)) if rounded else (lambda a: a)
    D = h.shape[-1]
    bcu = as_served(as_served(h) @ w["w_in"])
    return as_served(bcu[:, :D] * bcu[:, 2 * D :]), bcu[:, D : 2 * D]


def conv_mixer(w: dict, h, fed=None, rounded=None):
    """h [T, D], normed -> (the mixer's output [T, D], the rows ``v_{fed-(L-1)} ..
    v_{fed-1}`` [L - 1, D] that a slot carries after the first ``fed`` tokens;
    None: after them all), a token's filter from the L rows it names."""
    T, L = h.shape[0], w["filter"].shape[0]
    v, gate = gated_rows(w, h, rounded)
    padded = jnp.pad(v, ((L - 1, 0), (0, 0)))  # row t + L - 1 is token t: zeros before the first
    c = sum(w["filter"][i] * padded[i : i + T] for i in range(L))
    carried = jax.lax.dynamic_slice_in_dim(padded, T if fed is None else fed, L - 1, axis=0)
    return (gate * c) @ w["w_out"], carried


def attention_mixer(w: dict, h, m: dict):
    """h [T, D], normed -> [T, D]: QK-normed, roped, causal softmax attention of one whole sequence."""
    T = h.shape[0]
    H, KV = m["num_attention_heads"], m["num_key_value_heads"]
    Dh = m.get("head_dim") or m["hidden_size"] // H  # the published file has no head_dim: the quotient
    theta = float(m["rope_parameters"]["rope_theta"])
    positions = jnp.arange(T)
    q = rope(rms_norm((h @ w["w_q"]).reshape(T, H, Dh), w["q_norm"], m["norm_eps"]), positions, theta)
    k = rope(rms_norm((h @ w["w_k"]).reshape(T, KV, Dh), w["k_norm"], m["norm_eps"]), positions, theta)
    v = (h @ w["w_v"]).reshape(T, KV, Dh)
    mask = positions[:, None] >= positions[None, :]

    def group(args):
        qg, kg, vg = args  # the query heads [T, H / KV, Dh] that share one key-value head [T, Dh]
        s = jnp.einsum("trd,sd->rts", qg, kg) * Dh**-0.5
        p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("rts,sd->trd", p, vg)

    by_group = jnp.moveaxis(q.reshape(T, KV, H // KV, Dh), 1, 0)
    o = jax.lax.map(group, (by_group, jnp.moveaxis(k, 1, 0), jnp.moveaxis(v, 1, 0)))  # [KV, T, H / KV, Dh]
    return jnp.moveaxis(o, 0, 1).reshape(T, H * Dh) @ w["w_o"]


# A system's choice of experts is admitted where each scores, by this
# reference's own biased scores, at most this far under the reference's k-th
# best (module docstring). The value of the other expert architectures (PR 32,
# 35, 43); the configuration's ``check.logit_gap_tol_why`` gives this one's readings.
ROUTER_TIE = 0.01
DEFICIT_STEPS = (0.002, 0.003, 0.004, 0.005, 0.0075, 0.01, 0.015)


def routing_weights(w: dict, g, m: dict, served=None):
    """([T, E]: each token's weight on each expert, zero where not taken; [T]:
    how far the lowest of the system's experts lies under this reference's k-th
    best biased score, 0 without ``served``). ``served`` [T, k] int32
    (optional): the experts the system took, a row of -1 where it has no
    answer; admitted as the module docstring says."""
    E, k = m["num_experts"], m["num_experts_per_tok"]
    s = jax.nn.sigmoid(g @ w["w_router"])
    biased = s + w["router_bias"] if m["use_expert_bias"] else s
    top, chosen = jax.lax.top_k(biased, k)
    deficit = jnp.zeros(g.shape[:1], F32)
    if served is not None:
        theirs = jnp.take_along_axis(biased, jnp.maximum(served, 0), axis=-1)
        answered = jnp.all(served >= 0, axis=-1)
        deficit = jnp.where(answered, jnp.max(top[:, -1:] - theirs, axis=-1), 0.0)
        admitted = (answered & (deficit <= ROUTER_TIE))[:, None]
        chosen = jnp.where(admitted, served, chosen)
    picked = jnp.max(jax.nn.one_hot(chosen, E, dtype=F32), axis=1)  # [T, E] of 0 / 1
    kept = s * picked
    return m["routed_scaling_factor"] * kept / (jnp.sum(kept, axis=-1, keepdims=True) + NORM_TOPK_EPS), deficit


def experts_ffn(stack: dict, index, g, m: dict, served=None):
    """g [T, D], normed -> (the routed experts' sum [T, D], each token's deficit: ``routing_weights``)."""
    weights, deficit = routing_weights(_take(stack, ROUTER_LEAVES, index), g, m, served)

    def one_expert(acc, e):
        out = swiglu(_take(stack, EXPERT_LEAVES, index, e), g)
        return acc + jax.lax.dynamic_index_in_dim(weights, e, 1, keepdims=True) * out, None

    routed, _ = jax.lax.scan(one_expert, jnp.zeros_like(g), jnp.arange(m["num_experts"]))
    return routed, deficit


def layer(params: dict, at: tuple, index, x, m: dict, fed=None, served=None, rounded=None):
    """One layer, ``at`` = (its type, the stack that holds it, whether its MLP
    is dense), layer ``index`` of that stack: x [T, D] -> ([T, D], a conv
    layer's carried rows after the first ``fed`` tokens else None, an expert
    layer's deficits else None). ``rounded``: ``gated_rows``'s."""
    kind, stack_name, dense = at
    stack = params[stack_name]
    w = _take(stack, CONV_LEAVES if kind == CONV else ATTENTION_LEAVES, index)
    h = rms_norm(x, w["operator_norm"], m["norm_eps"])
    if kind == CONV:
        out, carried = conv_mixer(w, h, fed, rounded)
    else:
        out, carried = attention_mixer(w, h, m), None
    x = x + out
    g = rms_norm(x, w["ffn_norm"], m["norm_eps"])
    if dense:
        return x + swiglu(_take(stack, DENSE_LEAVES, index), g), carried, None
    routed, deficit = experts_ffn(stack, index, g, m, served)
    return x + routed, carried, deficit


def head_logits(params: dict, x, m: dict):
    """x [n, D] -> [n, V]: the embedding's transpose, a block of the vocabulary at a time."""
    x = rms_norm(x, params[FINAL_NORM].astype(F32), m["norm_eps"])
    V = m["vocab_size"]
    blocks = VOCAB_BLOCKS if V % VOCAB_BLOCKS == 0 else 1
    width = V // blocks

    def part(i):
        return x @ jax.lax.dynamic_slice_in_dim(params[EMBED], i * width, width, axis=0).astype(F32).T

    return jnp.moveaxis(jax.lax.map(part, jnp.arange(blocks)), 0, 1).reshape(x.shape[0], V)


def embed(params: dict, tokens, m: dict):
    """What the residual path starts from."""
    return params[EMBED][tokens].astype(F32)


def _placed(m: dict) -> list:
    """((type, stack, dense), index in that stack) of every layer, in the order they run."""
    seen, out = {}, []
    for i, kind in enumerate(m["layer_types"]):
        dense = i < m["num_dense_layers"]
        name = DENSE_STACK if dense else STACKS[kind]
        out.append(((kind, name, dense), seen.get(name, 0)))
        seen[name] = seen.get(name, 0) + 1
    return out


def sequence_logits(params: dict, tokens, m: dict):
    """tokens [T] -> logits [T, V]: the whole forward pass of one sequence, the
    reference's own routing choice everywhere."""
    with jax.default_matmul_precision("highest"):
        x = embed(params, jnp.asarray(tokens, jnp.int32), m)
        for at, index in _placed(m):
            x, _, _ = layer(params, at, index, x, m)
        return head_logits(params, x, m)


def serving_engine(params):
    """The engine in this process that serves ``params``, or None."""
    from ray_tpu.serve.llm import stats

    return next((e for e in stats.ENGINES if e.params is params), None)


def served_again(engine, prompt: list, new: list):
    """What the serving system took and holds when it answers ``prompt`` with
    ``new`` (greedy) once more: (the experts of every token it was fed, int
    [len(prompt) + len(new) - 1, expert layers, k]; the conv layers' carried
    rows [conv layers, L - 1, D] after the last token fed). (None, None) where
    it now answers otherwise."""
    request = engine.submit(prompt, max_new_tokens=len(new), return_routed_experts=True, return_state=True)
    if request.result(timeout=300.0) != list(new):
        return None, None
    return request.routed_experts, np.asarray(request.state, np.float32)


def state_gaps(served, own: list) -> list:
    """``|v_served - v| / |v|`` of every conv layer, its carried rows together."""
    return [float(np.linalg.norm(a - np.asarray(b)) / np.linalg.norm(np.asarray(b))) for a, b in zip(served, own)]


def served_deficits(deficits, fed: int) -> dict:
    """Of the [expert layers, T] deficits of one sequence's first ``fed``
    tokens: the largest, and how many lie over each of ``DEFICIT_STEPS``."""
    d = np.asarray(deficits)[:, :fed]
    return {"decisions": int(d.size), "max": float(d.max(initial=0.0)),
            "over": {str(t): int((d > t).sum()) for t in DEFICIT_STEPS}}


def make_layerwise_logits(m: dict):
    """Serving check: a layer at a time, so that only one float32 layer's worth
    sits beside the replica's weights. Returns ``logits(params, tokens, rows)``
    giving the logits [len(rows), V] of one sequence at the given positions,
    ``rows`` the positions that predict the tokens the system generated: under
    the system's routing where this reference admits it, and NaN where the
    first layer's carried rows are out of ``check.state_gap_tol`` (module docstring)."""

    @jax.jit
    def embedded(params, tokens):
        return embed(params, tokens, m)

    @jax.jit
    def head(params, x, rows):
        with jax.default_matmul_precision("highest"):
            return head_logits(params, x[rows], m)

    def one_layer(params, index, x, fed, served, at, rounded=None):
        with jax.default_matmul_precision("highest"):
            return layer(params, at, index, x, m, fed, served, rounded)

    one_layer = jax.jit(one_layer, static_argnums=(5, 6))  # one program a kind of layer: index and fed are traced
    served_dtype = jnp.dtype(m.get("torch_dtype", "float32"))
    tol = m["check"]["state_gap_tol"]
    k, n_dense = m["num_experts_per_tok"], m["num_dense_layers"]
    n_expert = m["num_hidden_layers"] - n_dense

    def logits(params, tokens, rows):
        tokens = [int(t) for t in tokens]
        fed = rows[-1] + 1  # what the system was fed when it answered: all but the last token it drew
        engine = serving_engine(params)
        theirs = state = None
        if engine is not None:
            theirs, state = served_again(engine, tokens[: rows[0] + 1], tokens[rows[0] + 1 : fed + 1])
        served = np.full((len(tokens), n_expert, k), -1, np.int32)
        if theirs is not None:
            served[: len(theirs)] = theirs
        x = embedded(params, jnp.asarray(tokens, jnp.int32))
        own, deficits = [], []
        for number, (at, index) in enumerate(_placed(m)):
            routed = None if at[2] else jnp.asarray(served[:, number - n_dense])
            args = (params, jnp.int32(index), x, jnp.int32(fed), routed, at)
            if at[0] == CONV and state is not None and not own and served_dtype != F32:
                # The first layer's rows once more, rounded as the served model rounds them (its embeddings too): what they are held to.
                bits = jnp.finfo(served_dtype)
                _, carried, _ = one_layer(*args[:2], jax.lax.reduce_precision(x, bits.nexp, bits.nmant), *args[3:], served_dtype)
                x, _, deficit = one_layer(*args)
            else:
                x, carried, deficit = one_layer(*args)
            if at[0] == CONV and state is not None:
                own.append(np.asarray(carried))
            if deficit is not None:
                deficits.append(deficit)
        out = head(params, x, jnp.asarray(rows, jnp.int32))
        if engine is None:
            return out
        gaps = None if state is None else state_gaps(state, own)
        held = gaps is not None and gaps[0] <= tol
        print(f"[reference] {fed} tokens fed: conv layers' carried-row gaps {gaps and [float(f'{g:.3g}') for g in gaps]}, "
              f"the first held to {tol}: {held}; served experts under the k-th best: "
              f"{theirs is not None and served_deficits(deficits, len(theirs))}", file=sys.stderr, flush=True)
        return out if held else jnp.full_like(out, jnp.nan)

    return logits
