"""The architecture in plain ``jax.numpy``: what the system is compared with.

Mistral-7B-v0.1 as ``transformers``' ``MistralForCausalLM`` computes it: token
embedding; per layer RMSNorm -> q, k, v projections without bias -> rotary
embedding (halves rotated, ``rotate_half``) -> grouped-query causal attention
with a sliding window (query i sees keys i-window < j <= i) -> output
projection, residual; RMSNorm -> SwiGLU (silu(gate) * up -> down), residual;
final RMSNorm; an untied head. Mean next-token cross-entropy as the loss.

Float32 throughout, ``default_matmul_precision("highest")`` (on a TPU a float32
matmul otherwise runs in bf16 passes), no kernel, no cache, no batching tricks.
Departures from the published model, both to fit beside the system under test:
heads are processed a group at a time under ``jax.checkpoint`` (same numbers,
less memory), and the serving check runs a layer at a time.

``EMBED`` ... ``LAYER_LEAVES`` are the one adapter to the program: where each weight sits in its
parameter tree (``models/transformer.py:init_params``; layer weights stacked on
a leading axis, matrices stored [in, out]).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

EMBED, FINAL_NORM, HEAD, LAYERS = "embed", "norm_f", "lm_head", "layers"
LAYER_LEAVES = {
    "attn_norm": "attn_norm", "wq": "wq", "wk": "wk", "wv": "wv", "wo": "wo",
    "mlp_norm": "mlp_norm", "w_gate": "wg", "w_up": "wi", "w_down": "wo_mlp",
}
F32 = jnp.float32


def layer_weights(params: dict, index) -> dict:
    """One layer's weights, in float32, under the reference's names."""
    stack = params[LAYERS]
    return {
        ours: jax.lax.dynamic_index_in_dim(stack[theirs], index, 0, keepdims=False).astype(F32)
        for ours, theirs in LAYER_LEAVES.items()
    }


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def rope(x, positions, theta):
    """x [T, H, Dh]; rotate_half convention."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=F32) / half)
    angles = positions.astype(F32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(q, k, v, window: int, head_group: int):
    """q [T, H, Dh], k and v [T, KV, Dh] -> [T, H, Dh]."""
    T, H, Dh = q.shape
    KV = k.shape[1]
    rep = H // KV
    i = jnp.arange(T)[:, None]
    j = jnp.arange(T)[None, :]
    mask = j <= i
    if window:
        mask &= (i - j) < window

    @jax.checkpoint
    def group(args):
        qg, kg, vg = args  # [T, g, Dh]
        s = jnp.einsum("tgd,sgd->gts", qg, kg) * Dh**-0.5
        p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("gts,sgd->tgd", p, vg)

    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    g = min(head_group, H)
    split = lambda x: jnp.moveaxis(x.reshape(T, H // g, g, Dh), 1, 0)  # noqa: E731
    out = jax.lax.map(group, (split(q), split(k), split(v)))  # [H/g, T, g, Dh]
    return jnp.moveaxis(out, 0, 1).reshape(T, H, Dh)


def layer(w: dict, x, positions, m: dict, head_group: int = 4):
    """x [T, D] -> [T, D] for one sequence."""
    T = x.shape[0]
    H, KV = m["num_attention_heads"], m["num_key_value_heads"]
    Dh = m.get("head_dim") or m["hidden_size"] // H
    eps, theta = m["rms_norm_eps"], m["rope_theta"]
    h = rms_norm(x, w["attn_norm"], eps)
    q = rope((h @ w["wq"]).reshape(T, H, Dh), positions, theta)
    k = rope((h @ w["wk"]).reshape(T, KV, Dh), positions, theta)
    v = (h @ w["wv"]).reshape(T, KV, Dh)
    o = attention(q, k, v, int(m.get("sliding_window") or 0), head_group)
    x = x + o.reshape(T, H * Dh) @ w["wo"]
    h = rms_norm(x, w["mlp_norm"], eps)
    return x + (jax.nn.silu(h @ w["w_gate"]) * (h @ w["w_up"])) @ w["w_down"]


def logits_one(params: dict, tokens, m: dict):
    """tokens [T] -> logits [T, V], all layers inside one program (training)."""
    positions = jnp.arange(tokens.shape[0])
    x = params[EMBED][tokens].astype(F32)
    for index in range(m["num_hidden_layers"]):
        x = jax.checkpoint(lambda x, i=index: layer(layer_weights(params, i), x, positions, m))(x)
    x = rms_norm(x, params[FINAL_NORM].astype(F32), m["rms_norm_eps"])
    return x @ params[HEAD].astype(F32)


def loss(params: dict, tokens, m: dict):
    """tokens [B, T+1] -> mean next-token cross-entropy."""
    with jax.default_matmul_precision("highest"):
        def one(seq):
            logp = jax.nn.log_softmax(logits_one(params, seq[:-1], m), axis=-1)
            return -jnp.take_along_axis(logp, seq[1:, None], axis=-1)[:, 0]

        return jnp.mean(jax.vmap(one)(tokens))


def make_layerwise_logits(m: dict):
    """Serving check: a layer at a time, so that only one float32 layer sits
    beside the replica's weights. Returns ``logits(params, tokens, rows)`` giving
    the logits [len(rows), V] of one sequence at the given positions."""

    @jax.jit
    def embed(params, tokens):
        return params[EMBED][tokens].astype(F32)

    @jax.jit
    def one_layer(params, index, x):
        with jax.default_matmul_precision("highest"):
            return layer(layer_weights(params, index), x, jnp.arange(x.shape[0]), m)

    @jax.jit
    def head(params, x, rows):
        with jax.default_matmul_precision("highest"):
            x = rms_norm(x[rows], params[FINAL_NORM].astype(F32), m["rms_norm_eps"])
            return x @ params[HEAD].astype(F32)

    def logits(params, tokens, rows):
        x = embed(params, jnp.asarray(tokens, jnp.int32))
        for index in range(m["num_hidden_layers"]):
            x = one_layer(params, jnp.int32(index), x)
        return head(params, x, jnp.asarray(rows, jnp.int32))

    return logits
