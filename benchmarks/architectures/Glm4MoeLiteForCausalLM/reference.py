"""The architecture in plain ``jax.numpy``: what the system is compared with.

GLM-4.7-Flash (``glm4_moe_lite``), one layer for hidden states ``x`` [T, D];
every projection without bias, RMSNorm with the configuration's eps.

Attention, in EXPANDED form (the program attends in absorbed form over cached
latents; the two are the same function): ``h = norm(x)``; ``c_q = norm(h W_qa)``,
``q = c_q W_qb`` per head ``[q_nope, q_rope]``, ``q_rope <- rope``;
``[c_kv, k_r] = h W_kva``, ``c = norm(c_kv)``, ``k_r <- rope`` (one for all heads);
per head ``[k_nope, v] = c W_kvb``, ``k = [k_nope, k_r]``; scores ``q . k /
sqrt(nope + rope)``, causal softmax, ``o = sum p v``; ``x += concat(o) W_o``.

Feed-forward: ``h = norm(x)``. The leading ``first_k_dense_replace`` layers:
SwiGLU of width ``intermediate_size``. The others: ``s = sigmoid(h W_g)``; the
``num_experts_per_tok`` experts with the largest ``s + b`` (``b``: the score
correction bias, which chooses and does not weigh); weights
``routed_scaling_factor * s[chosen] / sum s[chosen]``; ``x += sum_e w_e
SwiGLU_e(h) + SwiGLU_shared(h)``. No token is dropped: every expert is run
over every token and weighted (zero where not chosen). Final RMSNorm, untied head.

Float32 throughout, ``default_matmul_precision("highest")``, no kernel, no
cache, no sorting, no grouped matmul. Departures, all to fit beside the system
under test on the chip: heads are processed a group at a time and experts one
at a time (each cast to float32 as it is used: a whole float32 expert layer is
2.4 GB), the head a block of the vocabulary at a time (1.27 GB whole), and the
serving check runs a layer at a time. Rotary halves are rotated
(``rotate_half``), the program's layout; the published one interleaves: a fixed
permutation of columns of ``W_qb`` and ``W_kva``, the same family of functions
under random weights (the configuration's ``assumed``).

**Near-ties of the router** (``make_layerwise_logits``). Top-k routing is a
discontinuous function of the hidden state: where the k-th and (k+1)-th
biased scores of a token nearly tie, a system that computes in bfloat16 and
this float32 reference choose differently, both rightly, and with sigmoid
scores normalised over four experts the expert at the boundary carries about
0.4 of the routed output, so one such choice moves that token's logits by
tenths (on the v5e, PR 32: 14 seeds, the system's token up to 0.85 under this
reference's best where the reference took its own choice everywhere). So the
serving check computes the logits UNDER THE SYSTEM'S ROUTING: the engine keeps
beside each cached token the experts it took (``submit(return_routed_experts=
True)``), the check asks it to serve the same prompt once more, greedy, and
takes the choices of every token, prompt and generated, if the tokens come out
as given. One plain forward pass follows, in which a token of a layer goes to
the system's experts IF THIS REFERENCE ADMITS THEM: each must score, by the
reference's own float32 biased scores, within ``ROUTER_TIE`` of the reference's
k-th best. Otherwise, and where the system has no answer, the reference's own
top-k stands, and the logits show it. The weights are always the reference's
own scores of the experts taken.

The names below are the one adapter to the program: where each weight sits in
its parameter tree (``models/transformer.py:init_params``; two stacks of
layers, matrices stored [in, out], expert matrices [E, in, out]).
"""

from __future__ import annotations

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
F32 = jnp.float32
VOCAB_BLOCKS = 10


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


def latent_rows(w: dict, x, positions, m: dict):
    """x [T, D] -> what a token leaves for later ones: (c [T, R], k_r [T, P])."""
    R = m["kv_lora_rank"]
    kv = rms_norm(x, w["attn_norm"], m["rms_norm_eps"]) @ w["w_kva"]
    c = rms_norm(kv[:, :R], w["kv_norm"], m["rms_norm_eps"])
    return c, rope(kv[:, None, R:], positions, float(m["rope_theta"]))[:, 0]


def attend(w: dict, x, positions, c, k_r, m: dict, head_group: int = 4):
    """Queries x [n, D] at ``positions`` [n] over the tokens whose latents are
    c [T, R] and k_r [T, P] (token j sits at position j; a query sees j <= its
    own position) -> the attention branch [n, D]. Keys and values are expanded
    per head."""
    n, T = x.shape[0], c.shape[0]
    H = m["num_attention_heads"]
    N, P, Vd = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    eps, theta = m["rms_norm_eps"], float(m["rope_theta"])
    h = rms_norm(x, w["attn_norm"], eps)
    q = (rms_norm(h @ w["w_qa"], w["q_norm"], eps) @ w["w_qb"]).reshape(n, H, N + P)
    q = jnp.concatenate([q[..., :N], rope(q[..., N:], positions, theta)], axis=-1)
    kv = (c @ w["w_kvb"]).reshape(T, H, N + Vd)
    k = jnp.concatenate([kv[..., :N], jnp.broadcast_to(k_r[:, None, :], (T, H, P))], axis=-1)
    v = kv[..., N:]
    mask = jnp.arange(T)[None, :] <= positions[:, None]

    @jax.checkpoint
    def group(args):
        qg, kg, vg = args  # [n or T, g, .]
        s = jnp.einsum("tgd,sgd->gts", qg, kg) * (N + P) ** -0.5
        p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("gts,sgd->tgd", p, vg)

    g = min(head_group, H)
    split = lambda a: jnp.moveaxis(a.reshape(a.shape[0], H // g, g, a.shape[-1]), 1, 0)  # noqa: E731
    o = jax.lax.map(group, (split(q), split(k), split(v)))  # [H/g, n, g, Vd]
    return jnp.moveaxis(o, 0, 1).reshape(n, H * Vd) @ w["w_o"]


def attention(w: dict, x, positions, m: dict, head_group: int = 4):
    """x [T, D] -> the attention branch [T, D] of one whole sequence."""
    return attend(w, x, positions, *latent_rows(w, x, positions, m), m, head_group)


def biased_scores(w: dict, h):
    """(s, s + b): what weighs, and what chooses."""
    s = jax.nn.sigmoid(h @ w["w_router"])
    return s, s + w["router_bias"]


# A system's choice of experts is admitted where each scores, by this
# reference's own biased scores, at most this far under the reference's k-th
# best (module docstring). Measured on the v5e (PR 32; 14 seeds, 232,000
# decisions: every token of the check's sequences in every expert layer): the
# served bfloat16 system's experts lay under by more than 0.003 in 83 of them,
# 0.004 in 14, 0.005 in 2, at most 0.0056: a sixth per 0.001. The same program
# with 3-mantissa-bit matmul operands: by more than 0.005 in ~190 of a run's
# 16,600, 0.0075 in ~65, 0.01 in ~25. At 0.0075 a run's 2688 decisions of
# checked tokens hold an unadmitted one of the served system's about once in
# 4000 runs (extrapolated) and 2-7 of the 8-bit program's in every run. Scores
# are sigmoids in (0, 1); neighbours in rank near the boundary lie ~0.02 apart.
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


def dense_layer(stack: dict, index, x, positions, m: dict):
    w = _take(stack, ATTENTION_LEAVES, index)
    x = x + attention(w, x, positions, m)
    h = rms_norm(x, w["mlp_norm"], m["rms_norm_eps"])
    return x + swiglu(_take(stack, DENSE_LEAVES, index), h)


def expert_layer(stack: dict, index, x, positions, m: dict, served=None):
    w = _take(stack, ATTENTION_LEAVES, index)
    x = x + attention(w, x, positions, m)
    h = rms_norm(x, w["mlp_norm"], m["rms_norm_eps"])
    weights = routing_weights(_take(stack, ROUTER_LEAVES, index), h, m, served)

    def one_expert(acc, e):
        out = swiglu(_take(stack, EXPERT_LEAVES, index, e), h)
        return acc + jax.lax.dynamic_index_in_dim(weights, e, 1, keepdims=True) * out, None

    routed, _ = jax.lax.scan(one_expert, jnp.zeros_like(h), jnp.arange(m["n_routed_experts"]))
    if m["n_shared_experts"]:
        routed = routed + swiglu(_take(stack, SHARED_LEAVES, index), h)
    return x + routed


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
        for index in range(m["first_k_dense_replace"]):
            x = dense_layer(params[DENSE_LAYERS], index, x, positions, m)
        for index in range(m["num_hidden_layers"] - m["first_k_dense_replace"]):
            x = expert_layer(params[EXPERT_LAYERS], index, x, positions, m)
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
        return params[EMBED][tokens].astype(F32)

    @jax.jit
    def one_dense(params, index, x):
        with jax.default_matmul_precision("highest"):
            return dense_layer(params[DENSE_LAYERS], index, x, jnp.arange(x.shape[0]), m)

    @jax.jit
    def one_expert(params, index, x, served):
        with jax.default_matmul_precision("highest"):
            return expert_layer(params[EXPERT_LAYERS], index, x, jnp.arange(x.shape[0]), m, served)

    @jax.jit
    def head(params, x, rows):
        with jax.default_matmul_precision("highest"):
            return head_logits(params, x[rows], m)

    def logits(params, tokens, rows):
        tokens = [int(t) for t in tokens]
        served = np.full((len(tokens), n_expert, m["num_experts_per_tok"]), -1, np.int32)
        theirs = served_routing(params, tokens[: rows[0] + 1], tokens[rows[0] + 1 : rows[-1] + 2])
        if theirs is not None:
            served[: len(theirs)] = theirs
        x = embed(params, jnp.asarray(tokens, jnp.int32))
        for index in range(n_dense):
            x = one_dense(params, jnp.int32(index), x)
        for index in range(n_expert):
            x = one_expert(params, jnp.int32(index), x, jnp.asarray(served[:, index]))
        return head(params, x, jnp.asarray(rows, jnp.int32))

    return logits
