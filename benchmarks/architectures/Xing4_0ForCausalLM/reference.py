"""The architecture in plain ``jax.numpy``: what the system is compared with.

Xing4.0-29B-A4B (``xing4_0``). A token's state is ``X`` [n, D], n = ``hc_mult``
streams; ``X_0`` is the token's embedding in each of the n rows, and after the
last layer ``x = sum_i X[i]``, RMSNorm, the untied head. A layer is two
sub-layers ``F`` (attention, then feed-forward), each joined to the streams by
a manifold-constrained hyper-connection (arXiv:2512.24880 over
arXiv:2409.19606) with its own ``phi`` [n D, 2n + n^2], ``b`` [2n + n^2] and
``alpha`` [3], all float32:

    x~ = vec(X);  m = (x~ phi) * rsqrt(mean(x~^2) + rms_norm_eps)
    H_pre = sigmoid(alpha_0 m[0:n] + b[0:n]);  H_post = 2 sigmoid(alpha_1 m[n:2n] + b[n:2n])
    M_0 = exp(clip(alpha_2 mat(m[2n:]) + mat(b[2n:]), mhc_h_res_clamp_min, _max))   (n x n, row-major)
    hc_sinkhorn_iters times:  M <- M / (rowsum(M) + hc_eps);  M <- M / (colsum(M) + hc_eps);   H_res = M
    u = sum_i H_pre[i] X[i];  y = F(RMSNorm(u));  X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y

Attention (``F`` of the first sub-layer), in EXPANDED form (the program attends
in absorbed form over cached latents; the two are the same function): ``h =
norm(u)``; ``c_q = norm(h W_qa)``, ``q = c_q W_qb`` per head ``[q_nope,
q_rope]``, ``q_rope <- rope``; ``[c_kv, k_r] = h W_kva``, ``c = norm(c_kv)``,
``k_r <- rope`` (one for all heads); per head ``[k_nope, v] = c W_kvb``, ``k =
[k_nope, k_r]``; scores ``q . k * scale``, causal softmax, ``o = sum p v``;
``y = concat(o) W_o``. The rotary frequencies are YaRN's, from the six
published numbers of ``rope_scaling``: pair i of P/2 turns at ``f_i =
theta^(-2i/P)``; ``ramp_i = clip((i - low) / (high - low), 0, 1)`` with ``low =
floor``, ``high = ceil`` of ``P ln(original / (2 pi r)) / (2 ln theta)`` at r =
``beta_fast``, ``beta_slow``; ``f'_i = f_i (1 - ramp_i) + f_i / factor *
ramp_i``; cos and sin times ``ms(mscale) / ms(mscale_all_dim)``, ``ms(a) = 0.1
a ln factor + 1``; ``scale = (nope + rope)^-1/2 * ms(mscale_all_dim)^2``.

Feed-forward (``F`` of the second): ``h = norm(u)``. The leading
``first_k_dense_replace`` layers: SwiGLU of width ``intermediate_size``. The
others: ``s = sigmoid(h W_g)``; the ``num_experts_per_tok`` experts with the
largest ``s + b`` (``b``: the score correction bias, which chooses and does not
weigh); weights ``routed_scaling_factor * s[chosen] / sum s[chosen]``; ``y =
sum_e w_e SwiGLU_e(h) + SwiGLU_shared(h)``. No token is dropped.

Float32 throughout, ``default_matmul_precision("highest")``, no kernel, no
cache, no sorting, no grouped matmul; the streams as ``[T, n, D]`` and Sinkhorn
as the plain iterations over ``[T, n, n]``. Nothing of ``ops/hyper_connection
.py``, ``ops/latent_attention.py``, ``parallel/moe.py`` or the program's rotary
tables is imported. Departures, all to fit beside the system under test on the
chip: heads are processed a group and a block of queries at a time and
experts one at a time (each cast to float32 as it is used), the head a block of the vocabulary at a time,
and the serving check runs a layer at a time. Rotary halves are rotated
(``rotate_half``), the program's layout (the configuration's ``assumed``).

**Near-ties of the router** (``make_layerwise_logits``): as GLM-4.7-Flash's
reference (``../Glm4MoeLiteForCausalLM/reference.py``). Top-k routing is a
discontinuous function of the hidden state; the serving check computes the
logits UNDER THE SYSTEM'S ROUTING where this reference admits it: the engine
keeps beside each cached token the experts it took (``submit(
return_routed_experts=True)``), and a token of a layer goes to the system's
experts if each scores, by the reference's own float32 biased scores, within
``ROUTER_TIE`` of the reference's k-th best. Otherwise, and where the system
has no answer, the reference's own top-k stands, and the logits show it.

The names below are the one adapter to the program: where each weight sits in
its parameter tree (``models/transformer.py:init_params``; two stacks of
layers, matrices stored [in, out], expert matrices [E, in, out], a
hyper-connection's ``phi`` stored [2n + n^2, n D]: transposed here).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

EMBED, FINAL_NORM, HEAD = "embed", "norm_f", "lm_head"
DENSE_LAYERS, EXPERT_LAYERS = "dense_layers", "layers"
ATTENTION_LEAVES = {
    "attn_norm": "attn_norm", "w_qa": "wq_a", "q_norm": "q_norm", "w_qb": "wq_b",
    "w_kva": "wkv_a", "kv_norm": "kv_norm", "w_kvb": "wkv_b", "w_o": "wo", "mlp_norm": "mlp_norm",
}
DENSE_LEAVES = {"w_gate": "wg", "w_up": "wi", "w_down": "wo_mlp"}
ROUTER_LEAVES = {"w_router": "gate", "router_bias": "gate_bias"}
SHARED_LEAVES = {"w_gate": "wg_s", "w_up": "wi_s", "w_down": "wo_s"}
EXPERT_LEAVES = {"w_gate": "wg_e", "w_up": "wi_e", "w_down": "wo_e"}
HC_LEAVES = {  # by sub-layer; ``phi_t`` is phi transposed
    "attention": {"phi_t": "hc_attn_phi", "b": "hc_attn_b", "alpha": "hc_attn_alpha"},
    "feed_forward": {"phi_t": "hc_mlp_phi", "b": "hc_mlp_b", "alpha": "hc_mlp_alpha"},
}
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


# -- the residual path -------------------------------------------------------


def hyper_coefficients(w: dict, X, m: dict):
    """X [T, n, D] -> (H_pre [T, n], H_post [T, n], H_res [T, n, n]); ``w``:
    ``phi_t`` [2n + n^2, n D], ``b`` [2n + n^2], ``alpha`` [3]."""
    T, n, D = X.shape
    flat = X.reshape(T, n * D)
    coef = (flat @ w["phi_t"].T) * jax.lax.rsqrt(jnp.mean(flat * flat, axis=-1, keepdims=True) + m["rms_norm_eps"])
    alpha, b = w["alpha"], w["b"]
    pre = jax.nn.sigmoid(alpha[0] * coef[:, :n] + b[:n])
    post = 2.0 * jax.nn.sigmoid(alpha[1] * coef[:, n : 2 * n] + b[n : 2 * n])
    logits = alpha[2] * coef[:, 2 * n :].reshape(T, n, n) + b[2 * n :].reshape(n, n)
    M = jnp.exp(jnp.clip(logits, m["mhc_h_res_clamp_min"], m["mhc_h_res_clamp_max"]))
    for _ in range(m["hc_sinkhorn_iters"]):
        M = M / (jnp.sum(M, axis=-1, keepdims=True) + m["hc_eps"])
        M = M / (jnp.sum(M, axis=-2, keepdims=True) + m["hc_eps"])
    return pre, post, M


def hyper_connection(w: dict, X, m: dict, branch):
    """One sub-layer: X [T, n, D] -> X' [T, n, D]; ``branch``: u [T, D] -> y [T, D]
    (its norm inside)."""
    pre, post, res = hyper_coefficients(w, X, m)
    y = branch(jnp.einsum("ti,tid->td", pre, X))
    return jnp.einsum("tij,tjd->tid", res, X) + post[:, :, None] * y[:, None, :]


# -- attention ----------------------------------------------------------------


def _yarn_mscale(factor, mscale):
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn(m: dict):
    """(the P/2 rotary frequencies, what cos and sin are multiplied by, the
    softmax scale), from ``rope_theta`` and the six numbers of ``rope_scaling``."""
    P, theta, s = m["qk_rope_head_dim"], float(m["rope_theta"]), m["rope_scaling"]
    i = np.arange(P // 2, dtype=np.float64)
    f = theta ** (-2.0 * i / P)
    turns_at = lambda r: P * math.log(s["original_max_position_embeddings"] / (2 * math.pi * r)) / (2 * math.log(theta))  # noqa: E731
    low, high = max(math.floor(turns_at(s["beta_fast"])), 0), min(math.ceil(turns_at(s["beta_slow"])), P - 1)
    ramp = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    f = f * (1.0 - ramp) + f / s["factor"] * ramp
    amplitude = _yarn_mscale(s["factor"], s["mscale"]) / _yarn_mscale(s["factor"], s["mscale_all_dim"])
    scale = (m["qk_nope_head_dim"] + P) ** -0.5
    if s["mscale_all_dim"]:
        scale *= _yarn_mscale(s["factor"], s["mscale_all_dim"]) ** 2
    return jnp.asarray(f, F32), float(amplitude), float(scale)


def rope(x, positions, m: dict):
    """x [T, H, P]; rotate_half convention, YaRN's frequencies."""
    half = x.shape[-1] // 2
    freqs, amplitude, _ = yarn(m)
    angles = positions.astype(F32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(angles)[:, None, :] * amplitude, jnp.sin(angles)[:, None, :] * amplitude
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def swiglu(w: dict, h):
    return (jax.nn.silu(h @ w["w_gate"]) * (h @ w["w_up"])) @ w["w_down"]


def attention(w: dict, u, positions, m: dict, head_group: int = 4, query_block: int = 1024):
    """u [T, D] -> the attention branch [T, D] of one whole sequence; keys and
    values are expanded per head. The scores are computed for a group of heads
    and a block of queries at a time ([g, block, T] float32: 0.17 GB at the
    10,128 tokens of the serving check, where a group's whole [g, T, T] is 1.6)."""
    T = u.shape[0]
    H, R = m["num_attention_heads"], m["kv_lora_rank"]
    N, P, Vd = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    eps, scale = m["rms_norm_eps"], yarn(m)[2]
    h = rms_norm(u, w["attn_norm"], eps)
    q = (rms_norm(h @ w["w_qa"], w["q_norm"], eps) @ w["w_qb"]).reshape(T, H, N + P)
    q = jnp.concatenate([q[..., :N], rope(q[..., N:], positions, m)], axis=-1)
    kv = h @ w["w_kva"]
    c, k_r = rms_norm(kv[:, :R], w["kv_norm"], eps), rope(kv[:, None, R:], positions, m)[:, 0]
    kv = (c @ w["w_kvb"]).reshape(T, H, N + Vd)
    k = jnp.concatenate([kv[..., :N], jnp.broadcast_to(k_r[:, None, :], (T, H, P))], axis=-1)
    v = kv[..., N:]
    mask = jnp.arange(T)[None, :] <= positions[:, None]

    def block(qb, mb, kg, vg):
        s = jnp.einsum("tgd,sgd->gts", qb, kg) * scale
        p = jax.nn.softmax(jnp.where(mb[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("gts,sgd->tgd", p, vg)

    # the rows padded to whole blocks see the first key (as every row does), so their softmax is finite; cut off below
    blocks = lambda a: jnp.pad(a, [(0, -T % query_block)] + [(0, 0)] * (a.ndim - 1)).reshape(-1, query_block, *a.shape[1:])  # noqa: E731
    mask_blocks = blocks(mask).at[:, :, 0].set(True)

    def group(args):
        qg, kg, vg = args  # [T, g, .]
        o = jax.lax.map(lambda qm: block(*qm, kg, vg), (blocks(qg), mask_blocks))
        return o.reshape(-1, *o.shape[2:])[:T]

    g = min(head_group, H)
    split = lambda a: jnp.moveaxis(a.reshape(a.shape[0], H // g, g, a.shape[-1]), 1, 0)  # noqa: E731
    o = jax.lax.map(group, (split(q), split(k), split(v)))  # [H/g, T, g, Vd]
    return jnp.moveaxis(o, 0, 1).reshape(T, H * Vd) @ w["w_o"]


# -- feed-forward ---------------------------------------------------------------


def biased_scores(w: dict, h):
    """(s, s + b): what weighs, and what chooses."""
    s = jax.nn.sigmoid(h @ w["w_router"])
    return s, s + w["router_bias"]


# A system's choice of experts is admitted where each scores, by this
# reference's own biased scores, at most this far under the reference's k-th
# best (module docstring): GLM-4.7-Flash's margin, whose router this is (64
# experts, 4 a token, sigmoid scores in (0, 1); measured there on the v5e, PR 32).
ROUTER_TIE = 0.0075


def routing_weights(w: dict, h, m: dict, served=None):
    """[T, E]: each token's weight on each expert, zero where not taken.
    ``served`` [T, k] int32 (optional): the experts the system took, a row of
    -1 where it has no answer; admitted as the module docstring says."""
    E, k = m["n_routed_experts"], m["num_experts_per_tok"]
    s, biased = biased_scores(w, h)
    top, chosen = jax.lax.top_k(biased, k)
    if served is not None:
        theirs = jnp.take_along_axis(biased, jnp.maximum(served, 0), axis=-1)
        admitted = jnp.all((served >= 0) & (theirs >= top[:, -1:] - ROUTER_TIE), axis=-1, keepdims=True)
        chosen = jnp.where(admitted, served, chosen)
    picked = jnp.max(jax.nn.one_hot(chosen, E, dtype=F32), axis=1)  # [T, E] of 0 / 1
    kept = s * picked
    return m["routed_scaling_factor"] * kept / jnp.sum(kept, axis=-1, keepdims=True)


def experts(stack: dict, index, u, m: dict, served=None):
    """u [T, D] -> the routed and the shared experts' branch [T, D], the experts one at a time."""
    h = rms_norm(u, _take(stack, {"mlp_norm": "mlp_norm"}, index)["mlp_norm"], m["rms_norm_eps"])
    weights = routing_weights(_take(stack, ROUTER_LEAVES, index), h, m, served)

    def one_expert(acc, e):
        out = swiglu(_take(stack, EXPERT_LEAVES, index, e), h)
        return acc + jax.lax.dynamic_index_in_dim(weights, e, 1, keepdims=True) * out, None

    routed, _ = jax.lax.scan(one_expert, jnp.zeros_like(h), jnp.arange(m["n_routed_experts"]))
    if m["n_shared_experts"]:
        routed = routed + swiglu(_take(stack, SHARED_LEAVES, index), h)
    return routed


# -- layers ---------------------------------------------------------------------


def _attention_sublayer(stack: dict, index, X, positions, m: dict):
    w = _take(stack, ATTENTION_LEAVES, index)
    return hyper_connection(_take(stack, HC_LEAVES["attention"], index), X, m, lambda u: attention(w, u, positions, m))


def dense_layer(stack: dict, index, X, positions, m: dict):
    X = _attention_sublayer(stack, index, X, positions, m)
    w = {**_take(stack, DENSE_LEAVES, index), **_take(stack, {"mlp_norm": "mlp_norm"}, index)}
    dense = lambda u: swiglu(w, rms_norm(u, w["mlp_norm"], m["rms_norm_eps"]))  # noqa: E731
    return hyper_connection(_take(stack, HC_LEAVES["feed_forward"], index), X, m, dense)


def expert_layer(stack: dict, index, X, positions, m: dict, served=None):
    X = _attention_sublayer(stack, index, X, positions, m)
    return hyper_connection(
        _take(stack, HC_LEAVES["feed_forward"], index), X, m, lambda u: experts(stack, index, u, m, served)
    )


def embed_streams(params: dict, tokens, m: dict):
    """tokens [T] -> X_0 [T, n, D]: the embedding in each of the n rows."""
    x = params[EMBED][tokens].astype(F32)
    return jnp.broadcast_to(x[:, None, :], (x.shape[0], m["hc_mult"], x.shape[1]))


def head_logits(params: dict, X, m: dict):
    """X [T', n, D] -> [T', V]: the rows summed, normed, the head a block of the vocabulary at a time."""
    x = rms_norm(jnp.sum(X, axis=1), params[FINAL_NORM].astype(F32), m["rms_norm_eps"])
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
        X = embed_streams(params, jnp.asarray(tokens, jnp.int32), m)
        for index in range(m["first_k_dense_replace"]):
            X = dense_layer(params[DENSE_LAYERS], index, X, positions, m)
        for index in range(m["num_hidden_layers"] - m["first_k_dense_replace"]):
            X = expert_layer(params[EXPERT_LAYERS], index, X, positions, m)
        return head_logits(params, X, m)


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


def make_layerwise_logits(m: dict):
    """Serving check: a layer at a time, so that only one float32 layer's
    worth sits beside the replica's weights. Returns ``logits(params, tokens,
    rows)`` giving the logits [len(rows), V] of one sequence at the given
    positions, ``rows`` the positions that predict the tokens the system
    generated: under the system's routing where this reference admits it
    (module docstring)."""
    n_dense = m["first_k_dense_replace"]
    n_expert = m["num_hidden_layers"] - n_dense

    @jax.jit
    def embed(params, tokens):
        return embed_streams(params, tokens, m)

    @jax.jit
    def one_dense(params, index, X):
        with jax.default_matmul_precision("highest"):
            return dense_layer(params[DENSE_LAYERS], index, X, jnp.arange(X.shape[0]), m)

    @jax.jit
    def one_expert(params, index, X, served):
        with jax.default_matmul_precision("highest"):
            return expert_layer(params[EXPERT_LAYERS], index, X, jnp.arange(X.shape[0]), m, served)

    @jax.jit
    def head(params, X, rows):
        with jax.default_matmul_precision("highest"):
            return head_logits(params, X[rows], m)

    def logits(params, tokens, rows):
        tokens = [int(t) for t in tokens]
        served = np.full((len(tokens), n_expert, m["num_experts_per_tok"]), -1, np.int32)
        theirs = served_routing(params, tokens[: rows[0] + 1], tokens[rows[0] + 1 : rows[-1] + 2])
        if theirs is not None:
            served[: len(theirs)] = theirs
        X = embed(params, jnp.asarray(tokens, jnp.int32))
        for index in range(n_dense):
            X = one_dense(params, jnp.int32(index), X)
        for index in range(n_expert):
            X = one_expert(params, jnp.int32(index), X, jnp.asarray(served[:, index]))
        return head(params, X, jnp.asarray(rows, jnp.int32))

    return logits
