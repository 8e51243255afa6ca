"""The architecture in plain ``jax.numpy``: what the system is compared with.

Mellum2-12B-A2.5B as the keys of its ``config.json`` state it (the
configuration's ``assumed`` lists what no key states): token embedding; a layer
is RMSNorm -> q, k, v projections without bias -> RMSNorm of queries and keys
over a head's width -> rotary embedding (halves rotated, ``rotate_half``) by the
layer's KIND (``rope_parameters``: a ``sliding_attention`` layer at the plain
frequencies, a ``full_attention`` layer at YaRN's, cos and sin times
``attention_factor``) -> grouped-query causal attention, a window layer's
query i seeing keys i - window < j <= i -> output projection, residual;
RMSNorm -> router (softmax over ALL the experts in float32, the
``num_experts_per_tok`` largest, their probabilities divided by their sum) ->
the chosen SwiGLU experts' weighted sum, residual; final RMSNorm; an untied
head. The loss is the mean next-token cross-entropy plus
``deployment.balance_loss_coef`` times the layers' mean of ``E sum_e f_e
P_e`` (``f_e`` the share of the assignments sent to expert e, a count; ``P_e``
the mean of the router's probability for e).

THE SHARE. The configuration is one chip of ``deployment.expert_parallel.chips``
that share every layer's experts: of the ``published.num_experts`` the router
chooses among, this chip holds ``num_experts`` from ``index x num_experts``
on. A layer's expert sum runs over the chosen experts THAT ARE HELD (weights
still normalised over all the chosen); what the others would add is left out,
here as in the program, and that partial sum goes on. The balance term counts
all the experts, held or not.

Float32 throughout, ``default_matmul_precision("highest")``, no kernel, no sort,
no grouped matmul: each held expert runs over EVERY row and is weighted by the
row's weight for it (zero where the row did not choose it). Departures from
the published model, to fit beside the system under test (same numbers, less
memory): attention a group of heads and a block of queries at a time, the
experts one at a time and the head a block of rows at a time, each under
``jax.checkpoint``; the sequences of a batch one after the other.

``EMBED`` ... ``LAYER_LEAVES`` are the one adapter to the program: where each
weight sits in its parameter tree (``models/transformer.py:init_params``;
layer weights stacked on a leading axis, matrices stored [in, out], the
experts' [experts held, in, out]).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

EMBED, FINAL_NORM, HEAD, LAYERS = "embed", "norm_f", "lm_head", "layers"
LAYER_LEAVES = {
    "attn_norm": "attn_norm", "wq": "wq", "wk": "wk", "wv": "wv", "q_norm": "q_norm", "k_norm": "k_norm", "wo": "wo",
    "mlp_norm": "mlp_norm", "w_router": "gate", "w_gate": "wg_e", "w_up": "wi_e", "w_down": "wo_e",
}
F32 = jnp.float32
QUERY_BLOCK, HEAD_ROWS = 1024, 2048


def layer_weights(params: dict, index: int) -> dict:
    """One layer's weights, in float32, under the reference's names."""
    return {ours: params[LAYERS][theirs][index].astype(F32) for ours, theirs in LAYER_LEAVES.items()}


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def inv_freq(rope: dict, dim: int):
    """(the ``dim / 2`` rotary frequencies of a kind of layer, the factor on cos
    and sin) from its group of ``rope_parameters``. YaRN: pair i keeps its
    frequency where it turns more than ``beta_fast`` times over the original
    positions, turns ``factor`` times slower where fewer than ``beta_slow``,
    and in between by a linear ramp over the pairs."""
    base = rope["rope_theta"] ** (-jnp.arange(0, dim, 2, dtype=F32) / dim)
    if rope["rope_type"] == "default":
        return base, 1.0

    def pair_turning(turns):
        return dim * math.log(rope["original_max_position_embeddings"] / (turns * 2 * math.pi)) / (2 * math.log(rope["rope_theta"]))

    low, high = max(math.floor(pair_turning(rope["beta_fast"])), 0), min(math.ceil(pair_turning(rope["beta_slow"])), dim - 1)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=F32) - low) / max(high - low, 0.001), 0.0, 1.0)
    return base * (1.0 - ramp) + base / rope["factor"] * ramp, rope["attention_factor"]


def rope(x, positions, rope_of_kind: dict):
    """x [T, H, Dh]; rotate_half convention over the whole head."""
    half = x.shape[-1] // 2
    freqs, amplitude = inv_freq(rope_of_kind, x.shape[-1])
    angles = positions.astype(F32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(angles)[:, None, :] * amplitude, jnp.sin(angles)[:, None, :] * amplitude
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(q, k, v, window: int):
    """q [T, H, Dh], k and v [T, KV, Dh] -> [T, H, Dh]: the query heads of one
    key/value head and a block of queries at a time."""
    T, H, Dh = q.shape
    KV = k.shape[1]
    block = min(QUERY_BLOCK, T)
    if T % block:
        raise ValueError(f"{T} queries are no whole blocks of {block}")

    @jax.checkpoint
    def one(args):
        qb, kg, vg, first = args  # [block, H/KV, Dh], [T, Dh], [T, Dh]
        i, j = first + jnp.arange(block)[:, None], jnp.arange(T)[None, :]
        mask = (j <= i) & ((i - j) < window) if window else j <= i
        s = jnp.einsum("thd,sd->hts", qb, kg) * Dh**-0.5
        p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hts,sd->thd", p, vg)

    def group(args):
        qg, kg, vg = args  # [T, H/KV, Dh], [T, Dh], [T, Dh]
        blocks = qg.reshape(T // block, block, H // KV, Dh)
        firsts = jnp.arange(T // block) * block
        return jax.lax.map(lambda a: one((a[0], kg, vg, a[1])), (blocks, firsts)).reshape(T, H // KV, Dh)

    by_group = jnp.moveaxis(q.reshape(T, KV, H // KV, Dh), 1, 0)
    out = jax.lax.map(group, (by_group, jnp.moveaxis(k, 1, 0), jnp.moveaxis(v, 1, 0)))  # [KV, T, H/KV, Dh]
    return jnp.moveaxis(out, 0, 1).reshape(T, H, Dh)


def held_range(m: dict) -> tuple:
    """(the first expert this chip holds, how many)."""
    held = m["num_experts"]
    return int(m["deployment"]["expert_parallel"]["index"]) * held, held


def experts(w: dict, u, m: dict):
    """u [T, D] normed -> (this chip's part of the experts' sum [T, D], the share
    of these rows' assignments sent to each of ALL the experts [E] (a count: no
    gradient), the rows' mean probability for each [E])."""
    E, k = m["published"]["num_experts"], m["num_experts_per_tok"]
    probs = jax.nn.softmax(u @ w["w_router"], axis=-1)  # [T, E]
    top, chosen = jax.lax.top_k(probs, k)
    by_expert = jnp.sum(jax.nn.one_hot(chosen, E, dtype=F32) * (top / jnp.sum(top, axis=-1, keepdims=True))[..., None], axis=1)
    first, held = held_range(m)

    @jax.checkpoint
    def one(y, args):
        w_gate, w_up, w_down, weight = args  # the expert's three matrices, each row's weight for it [T]
        return y + weight[:, None] * ((jax.nn.silu(u @ w_gate) * (u @ w_up)) @ w_down), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(u), (w["w_gate"], w["w_up"], w["w_down"], by_expert[:, first : first + held].T))
    sent = jax.lax.stop_gradient(jnp.sum(jax.nn.one_hot(chosen, E, dtype=F32), axis=(0, 1)) / chosen.size)
    return y, sent, jnp.mean(probs, axis=0)


def layer(w: dict, x, positions, kind: str, m: dict):
    """x [T, D] -> ([T, D], ``experts``' two [E]) for one sequence."""
    T = x.shape[0]
    H, KV, Dh, eps = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"], m["rms_norm_eps"]
    turn = m["rope_parameters"][kind]
    h = rms_norm(x, w["attn_norm"], eps)
    q = rope(rms_norm((h @ w["wq"]).reshape(T, H, Dh), w["q_norm"], eps), positions, turn)
    k = rope(rms_norm((h @ w["wk"]).reshape(T, KV, Dh), w["k_norm"], eps), positions, turn)
    v = (h @ w["wv"]).reshape(T, KV, Dh)
    o = attention(q, k, v, m["sliding_window"] if kind == "sliding_attention" else 0)
    x = x + o.reshape(T, H * Dh) @ w["wo"]
    y, sent, probs = experts(w, rms_norm(x, w["mlp_norm"], eps), m)
    return x + y, (sent, probs)


def nll_one(params: dict, seq, m: dict):
    """seq [T + 1] -> (the T next-token negative log-likelihoods summed, each
    layer's ``experts``' two [L, E])."""
    tokens, targets = seq[:-1], seq[1:]
    positions = jnp.arange(tokens.shape[0])
    x, routed = params[EMBED][tokens].astype(F32), []
    for index, kind in enumerate(m["layer_types"]):
        x, r = jax.checkpoint(lambda x, i=index, kind=kind: layer(layer_weights(params, i), x, positions, kind, m))(x)
        routed.append(r)
    x = rms_norm(x, params[FINAL_NORM].astype(F32), m["rms_norm_eps"])
    head = params[HEAD].astype(F32)
    rows = min(HEAD_ROWS, x.shape[0])

    @jax.checkpoint
    def block(args):
        xb, tb = args
        logp = jax.nn.log_softmax(xb @ head, axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, tb[:, None], axis=-1))

    nll = jnp.sum(jax.lax.map(block, (x.reshape(-1, rows, x.shape[-1]), targets.reshape(-1, rows))))
    return nll, jnp.stack([r[0] for r in routed]), jnp.stack([r[1] for r in routed])


def loss(params: dict, tokens, m: dict):
    """tokens [B, T+1] -> mean next-token cross-entropy plus the balance term,
    whose shares and mean probabilities are the BATCH's (the sequences are as
    long as each other: the mean of theirs)."""
    with jax.default_matmul_precision("highest"):
        B, T = tokens.shape[0], tokens.shape[1] - 1
        nll, sent, probs = jax.lax.map(lambda seq: nll_one(params, seq, m), tokens)
        E = m["published"]["num_experts"]
        balance = jnp.mean(E * jnp.sum(jnp.mean(sent, axis=0) * jnp.mean(probs, axis=0), axis=-1))
        return jnp.sum(nll) / (B * T) + m["deployment"]["balance_loss_coef"] * balance
