"""The architecture in plain ``jax.numpy``: what the system is compared with.

Olmo-Hybrid-7B (``olmo_hybrid``): linear-attention layers and full-attention
layers three to one, in the Olmo 2/3 family's block. One layer for hidden
states ``x`` [T, D] of one whole sequence; every projection without bias,
RMSNorm with the configuration's eps. What no key of ``config.json`` carries is
listed in the configuration's ``assumed``.

Block: ``h = x + RMSNorm(mixer(x))``, ``y = h + RMSNorm(mlp(h))``: a norm on
each branch's OUTPUT and none on its input; SwiGLU MLP; final RMSNorm before
an untied head.

Full layer: ``q = RMSNorm(x W_q)``, ``k = RMSNorm(x W_k)`` over the WHOLE
projection (a learned weight as wide as it), then split into heads; ``v = x
W_v``; no rotary (``rope_theta`` null: the linear layers carry position);
causal softmax attention at scale ``head_dim^-1/2``; ``W_o``.

Linear layer (gated delta rule; H heads, keys ``dk`` wide, values ``dv``):
``q~ = x W_q``, ``k~ = x W_k``, ``v~ = x W_v``; each channel through a causal
convolution over the last ``linear_conv_kernel_dim`` tokens (zeros before the
first) and SiLU; per head ``q = q / |q| * dk^-1/2``, ``k = k / |k|`` (the norm as
``rsqrt(sum of squares + 1e-6)``); ``beta = 2 sigmoid(x W_b)`` (the 2:
``linear_allow_neg_eigval``); ``g = -exp(A_log) softplus(x W_a + dt_bias)``;
a state ``S`` [dk, dv] a head from zero,

    S_t = exp(g_t) S_{t-1} + beta_t k_t (v_t - exp(g_t) S_{t-1}^T k_t)^T,   o_t = S_t^T q_t

``y = [RMSNorm_dv(o) * silu(x W_g)] W_o`` (one learned [dv] weight, every head).

Float32 throughout, ``default_matmul_precision("highest")``, no kernel, no
cache, no chunk: the recurrence runs a token at a time (``lax.scan`` over T),
which is its definition, where the program solves 64 tokens together
(``ray_tpu/ops/linear_attention.py``; nothing of it is imported here).
Departures, all to fit beside the system under test on the chip: the softmax
attention is computed a block of ``QUERY_BLOCK`` queries at a time (the scores
of 6272 tokens are 4.7 GB for 30 heads), the head a block of the vocabulary at
a time, and the serving check runs a layer at a time.

**The state a slot carries** (``make_layerwise_logits``). The mechanism this
architecture brings is the state ``S`` that a serving slot keeps from program to
program, in float32. Which token a greedy system takes does not show its
precision: the served system, bfloat16 everywhere else, already takes the
runner-up of a near-tie as often as one that also rounds ``S`` to bfloat16 at
every step (PERF.md section 6, PR 41). So the serving check reads the state
itself: it asks the engine in this process to serve the sequence once more
(``submit(return_state=True)``, as ``AfmoeForCausalLM/reference.py`` asks for the
experts taken) and compares what the slot holds after the last token fed with
this recurrence's own state there, ``|S_served - S| / |S|`` over all heads of a
layer. The FIRST linear layer's is held to ``check.state_gap_tol``: its input is
the embedding of the tokens, the same numbers here and there, so the gap is the
arithmetic from the projection to the state and nothing handed down from other
layers; the other layers' gaps are printed and not held (their inputs already
differ by the bfloat16 layers under them). The harness judges a sequence by
two things it computes from the logits returned, the largest gap and whether
all are finite, and takes nothing else from a reference: where the state is
out of tolerance, or the engine answers otherwise than it did, the sequence's
logits come back NaN, which the harness reports as not finite and not correct,
and the numbers are on stderr. Without an engine that serves ``params`` in this
process (a test of the reference alone) there is no state to read and the
logits stand as computed.

The names below are the one adapter to the program: where each weight sits in
its parameter tree (``models/transformer.py:init_params``: two stacks of layers
by kind, each in the order its kind's layers come in the model; matrices stored
[in, out]; the linear layers' q, k and v projections are the column blocks, in
that order, of one matrix and their convolution filters the columns of one [K,
channels] leaf; W_a and W_b apart).
"""

from __future__ import annotations

import sys

import jax
import jax.numpy as jnp
import numpy as np

EMBED, FINAL_NORM, HEAD = "embed", "norm_f", "lm_head"
FULL_LAYERS, LINEAR_LAYERS = "layers", "linear_layers"
BLOCK_LEAVES = {
    "mixer_post_norm": "attn_post_norm", "mlp_post_norm": "mlp_post_norm",
    "w_gate": "wg", "w_up": "wi", "w_down": "wo_mlp",
}
FULL_LEAVES = {"w_q": "wq", "w_k": "wk", "w_v": "wv", "w_o": "wo", "q_norm": "q_norm", "k_norm": "k_norm"}
LINEAR_LEAVES = {
    "w_qkv": "w_qkv", "conv": "conv_w", "w_a": "w_a", "w_b": "w_b", "A_log": "A_log", "dt_bias": "dt_bias",
    "w_g": "wg_lin", "o_norm": "o_norm", "w_o": "wo",
}
LINEAR, FULL = "linear_attention", "full_attention"
F32 = jnp.float32
QUERY_BLOCK = 512
VOCAB_BLOCKS = 8
L2_EPS = 1e-6


def _take(stack: dict, names: dict, index) -> dict:
    """Leaves of one layer, in float32."""
    return {
        ours: jax.lax.dynamic_index_in_dim(stack[theirs], index, 0, keepdims=False).astype(F32)
        for ours, theirs in names.items()
    }


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def full_attention(w: dict, x, m: dict):
    """x [T, D] -> the mixer's output [T, D], before the branch's norm."""
    T = x.shape[0]
    H, KV = m["num_attention_heads"], m["num_key_value_heads"]
    Dh = m.get("head_dim") or m["hidden_size"] // H
    eps = m["rms_norm_eps"]
    q = rms_norm(x @ w["w_q"], w["q_norm"], eps).reshape(T, H, Dh)
    k = rms_norm(x @ w["w_k"], w["k_norm"], eps).reshape(T, KV, Dh)
    v = (x @ w["w_v"]).reshape(T, KV, Dh)
    if KV != H:
        k, v = jnp.repeat(k, H // KV, axis=1), jnp.repeat(v, H // KV, axis=1)
    block = min(QUERY_BLOCK, T)
    pad = -T % block
    rows = jnp.arange(T + pad).reshape(-1, block)
    keys = jnp.arange(T)

    def queries(args):
        qb, at = args  # [block, H, Dh], [block]
        s = jnp.einsum("qhd,khd->hqk", qb, k) * Dh**-0.5
        p = jax.nn.softmax(jnp.where((keys[None, :] <= at[:, None])[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v)

    qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, block, H, Dh)
    o = jax.lax.map(queries, (qp, rows)).reshape(T + pad, H * Dh)[:T]
    return o @ w["w_o"]


def causal_conv(u, taps):
    """u [T, C], taps [K, C]: ``y_t = sum_i taps[i] u_{t - (K - 1) + i}``, zeros before the first token."""
    K, T = taps.shape[0], u.shape[0]
    padded = jnp.pad(u, ((K - 1, 0), (0, 0)))
    return sum(padded[i : i + T] * taps[i] for i in range(K))


def delta_rule(q, k, v, g, beta, fed=None):
    """The recurrence, a token at a time from a zero state: q, k [T, H, dk], v
    [T, H, dv], g, beta [T, H] -> (o [T, H, dv], the state [H, dk, dv] after
    the first ``fed`` tokens; None: after them all)."""
    T, H, dk, dv = *q.shape, v.shape[2]
    last = T - 1 if fed is None else fed - 1

    def token(carry, xs):
        S, kept = carry
        t, qt, kt, vt, gt, bt = xs
        S = S * jnp.exp(gt)[:, None, None]
        write = vt - jnp.einsum("hkv,hk->hv", S, kt)
        S = S + bt[:, None, None] * kt[:, :, None] * write[:, None, :]
        return (S, jnp.where(t == last, S, kept)), jnp.einsum("hkv,hk->hv", S, qt)

    zero = jnp.zeros((H, dk, dv), F32)
    (_, kept), o = jax.lax.scan(token, (zero, zero), (jnp.arange(T), q, k, v, g, beta))
    return o, kept


def linear_attention(w: dict, x, m: dict, fed=None):
    """x [T, D] -> (the mixer's output [T, D], before the branch's norm, the
    state after the first ``fed`` tokens)."""
    T = x.shape[0]
    H, dk, dv = m["linear_num_value_heads"], m["linear_key_head_dim"], m["linear_value_head_dim"]
    if m["linear_num_key_heads"] != H:
        raise ValueError("the reference computes as many key heads as value heads")
    u = jax.nn.silu(causal_conv(x @ w["w_qkv"], w["conv"]))
    q = u[:, : H * dk].reshape(T, H, dk)
    k = u[:, H * dk : 2 * H * dk].reshape(T, H, dk)
    v = u[:, 2 * H * dk :].reshape(T, H, dv)
    q = q * jax.lax.rsqrt(jnp.sum(q * q, axis=-1, keepdims=True) + L2_EPS) * dk**-0.5
    k = k * jax.lax.rsqrt(jnp.sum(k * k, axis=-1, keepdims=True) + L2_EPS)
    beta = jax.nn.sigmoid(x @ w["w_b"]) * (2.0 if m["linear_allow_neg_eigval"] else 1.0)
    g = -jnp.exp(w["A_log"]) * jax.nn.softplus(x @ w["w_a"] + w["dt_bias"])
    o, state = delta_rule(q, k, v, g, beta, fed)
    o = rms_norm(o, w["o_norm"], m["rms_norm_eps"]).reshape(T, H * dv)
    return (o * jax.nn.silu(x @ w["w_g"])) @ w["w_o"], state


def layer(params: dict, kind: str, index, x, m: dict, fed=None):
    """Layer ``index`` of its kind: x [T, D] -> ([T, D], a linear layer's state
    after the first ``fed`` tokens or None)."""
    eps = m["rms_norm_eps"]
    w = _take(params[LINEAR_LAYERS if kind == LINEAR else FULL_LAYERS],
              {**BLOCK_LEAVES, **(LINEAR_LEAVES if kind == LINEAR else FULL_LEAVES)}, index)
    mixed, state = linear_attention(w, x, m, fed) if kind == LINEAR else (full_attention(w, x, m), None)
    h = x + rms_norm(mixed, w["mixer_post_norm"], eps)
    mlp = (jax.nn.silu(h @ w["w_gate"]) * (h @ w["w_up"])) @ w["w_down"]
    return h + rms_norm(mlp, w["mlp_post_norm"], eps), state


def head_logits(params: dict, x, m: dict):
    """x [n, D] -> [n, V], the head a block of the vocabulary at a time."""
    x = rms_norm(x, params[FINAL_NORM].astype(F32), m["rms_norm_eps"])
    V = m["vocab_size"]
    blocks = VOCAB_BLOCKS if V % VOCAB_BLOCKS == 0 else 1
    width = V // blocks

    def block(i):
        return x @ jax.lax.dynamic_slice_in_dim(params[HEAD], i * width, width, axis=1).astype(F32)

    return jnp.moveaxis(jax.lax.map(block, jnp.arange(blocks)), 0, 1).reshape(x.shape[0], V)


def _ranked(m: dict) -> list:
    """(kind, rank among its kind) of every layer, in the order they run."""
    seen, out = {}, []
    for kind in m["layer_types"]:
        out.append((kind, seen.get(kind, 0)))
        seen[kind] = seen.get(kind, 0) + 1
    return out


def sequence_logits(params: dict, tokens, m: dict):
    """tokens [T] -> logits [T, V]: the whole forward pass of one sequence."""
    with jax.default_matmul_precision("highest"):
        x = params[EMBED][jnp.asarray(tokens, jnp.int32)].astype(F32)
        for kind, index in _ranked(m):
            x, _ = layer(params, kind, index, x, m)
        return head_logits(params, x, m)


def serving_engine(params):
    """The engine in this process that serves ``params``, or None."""
    from ray_tpu.serve.llm import stats

    return next((e for e in stats.ENGINES if e.params is params), None)


def served_state(engine, prompt: list, new: list):
    """The linear layers' state [linear layers, H, dk, dv] that the serving
    system holds after it answered ``prompt`` with ``new`` (greedy) and was fed
    all of it but the last token. None where it now answers otherwise."""
    request = engine.submit(prompt, max_new_tokens=len(new), return_state=True)
    return np.asarray(request.state, np.float32) if request.result(timeout=300.0) == list(new) else None


def state_gaps(served, own: list) -> list:
    """``|S_served - S| / |S|`` of every linear layer, all its heads together."""
    return [float(np.linalg.norm(a - np.asarray(b)) / np.linalg.norm(np.asarray(b))) for a, b in zip(served, own)]


def make_layerwise_logits(m: dict):
    """Serving check: a layer at a time, so that only one float32 layer's worth
    sits beside the replica's weights. Returns ``logits(params, tokens, rows)``
    giving the logits [len(rows), V] of one sequence at the given positions."""

    @jax.jit
    def embedded(params, tokens):
        return params[EMBED][tokens].astype(F32)

    @jax.jit
    def head(params, x, rows):
        with jax.default_matmul_precision("highest"):
            return head_logits(params, x[rows], m)

    def one_layer(params, index, x, fed, kind):
        with jax.default_matmul_precision("highest"):
            return layer(params, kind, index, x, m, fed)

    one_layer = jax.jit(one_layer, static_argnums=4)  # one program a kind of layer: index and fed are traced
    tol = m["check"]["state_gap_tol"]

    def logits(params, tokens, rows):
        tokens = [int(t) for t in tokens]
        fed = rows[-1] + 1  # what the system was fed when it answered: all but the last token it drew
        engine = serving_engine(params)
        served = engine and served_state(engine, tokens[: rows[0] + 1], tokens[rows[0] + 1 : fed + 1])
        x = embedded(params, jnp.asarray(tokens, jnp.int32))
        own = []
        for kind, index in _ranked(m):
            x, state = one_layer(params, jnp.int32(index), x, jnp.int32(fed), kind)
            if state is not None and served is not None:
                own.append(np.asarray(state))  # to the host: twelve of them would sit beside the replica
        out = head(params, x, jnp.asarray(rows, jnp.int32))
        if engine is None:
            return out
        gaps = None if served is None else state_gaps(served, own)
        held = gaps is not None and gaps[0] <= tol
        print(f"[reference] {fed} tokens fed: linear layers' state gaps "
              f"{gaps and [round(g, 5) for g in gaps]}, the first held to {tol}: {held}", file=sys.stderr, flush=True)
        return out if held else jnp.full_like(out, jnp.nan)

    return logits
