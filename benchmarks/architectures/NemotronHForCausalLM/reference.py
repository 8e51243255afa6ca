"""The architecture in plain ``jax.numpy``: what the system is compared with.

Nemotron-3-Nano-30B-A3B (``nemotron_h``): Mamba-2 state-space blocks, experts
blocks and attention blocks in the order ``hybrid_override_pattern`` gives (``M``,
``E``, ``*``). One block for hidden states ``x`` [T, D] of one whole sequence.
Every block is ONE mixer: ``x <- x + mixer(RMSNorm(x; w, eps))``; after the last
block RMSNorm, then an untied head. No bias anywhere but the convolution's.
What no key of ``config.json`` carries is listed in the configuration's ``assumed``.

Mamba-2 block (H heads of P channels, a state N wide a channel, G groups; the
inner width is H P, the convolution's channels H P + 2 G N): ``[z | xBC | dt] =
h W_in`` (three leaves of the program's tree); ``xBC <- silu(conv(xBC) + b)``,
causal depthwise over the last ``conv_kernel`` tokens, zeros before the first;
``[x | B | C] = xBC``, ``x_t`` as [H, P], ``B_t``, ``C_t`` as [G, N], head h reads
group ``h // (H / G)``; ``dt_t = softplus(dt_t + dt_bias)``, ``A = -exp(A_log)``; a
state ``S`` [P, N] a head from zero,

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T,    y_t = S_t C_t + D x_t

``y <- GroupRMSNorm(y * silu(z))`` (the gate first, then the norm over each of
the G groups of H P / G channels, one learned weight a channel); ``out = y W_out``.

Attention block: ``q = h W_q`` as [heads, head_dim], ``k = h W_k``, ``v = h W_v``
as [KV heads, head_dim]; NO rotary; causal softmax at ``head_dim^-1/2``; ``W_o``.

Experts block: ``s = sigmoid(h W_r)`` over ALL the experts of the deployment
(the router's width); the ``num_experts_per_tok`` experts with the largest ``s +
b`` (the bias chooses and does not weigh); ``w = routed_scaling_factor *
s[chosen] / (sum s[chosen] + 1e-20)``; ``out = sum_e w_e relu(h W_up,e)^2 W_down,e
+ relu(h W_up,s)^2 W_down,s``. **The share.** The parameter tree holds the
experts of ONE chip of ``deployment.expert_parallel.chips`` (the ``index``-th
run of ``E / chips`` experts): the sum runs over the chosen experts that are
held, what the others would have added is left out, as in the program, and
that partial result goes on to the next block. Every held expert is run over
every token and weighted (zero where not chosen or not held): no token is
dropped, nothing is sorted or grouped. With ``chips`` 1 it is the uncut block.

Float32 throughout, ``default_matmul_precision("highest")``, no kernel, no
cache, no chunk: the recurrence runs a token at a time (``lax.scan`` over T),
which is its definition, where the program takes 128 tokens together
(``ray_tpu/ops/ssm.py``); nothing of it or of ``ray_tpu/parallel/moe.py`` is
imported here. Departures, all to fit beside the system under test on the
chip: attention is computed a key-value head's group of query heads at a time,
experts one at a time (each cast to float32 as it is used), the head a block
of the vocabulary at a time, and the serving check runs a block at a time.

**Near-ties of the router** (``make_layerwise_logits``): PR 32's method, as
``Glm4MoeLiteForCausalLM/reference.py`` sets it out. Where the k-th and
(k+1)-th biased scores of a token nearly tie, a bfloat16 system and this
float32 reference choose differently, both rightly. The serving check asks the
engine in this process to serve the sequence once more
(``submit(return_routed_experts=True, return_state=True)``) and computes the
logits under the system's choices where this reference admits them: each
chosen expert must score, by the reference's own float32 biased scores, within
``ROUTER_TIE`` of the reference's k-th best. Otherwise the reference's own
top-k stands. The weights are always the reference's own scores.

**The state a slot carries.** From the same request the check takes the float32
state the slot holds after the last token fed and compares the FIRST block's
(block 0 is a Mamba block fed by the embeddings: the same numbers here and
there) with this recurrence's own there, ``|S_served - S| / |S|`` over the
block's heads, held to ``check.state_gap_tol``; the other Mamba blocks' gaps
are printed and not held. Where it is over the limit, or the engine answers
otherwise than it did, the sequence's logits come back NaN, which the harness
reads as not finite and not correct (``OlmoHybridForCausalLM/reference.py``
sets out why the logits cannot show a state's precision). One departure, for
this comparison alone (``rounded``): the state it is held to is the
recurrence's over the normed input and the convolution's channels ROUNDED to
the configuration's ``torch_dtype`` where the served model rounds them (the
block's input norm, which Olmo-Hybrid's block has not, and the projection's
output, which a slot also carries as such between programs; the program pins
that rounding with ``lax.reduce_precision`` and so does this file: a cast there
and back is one the TPU's compiler drops where it can, here under
``jit`` with the weights as arguments, and the "rounded" recurrence was then
the unrounded one). Held to the unrounded float32 recurrence the served
system read 0.0035-0.0047 and a system that keeps the state in bfloat16
0.0062-0.0070 (my chip runs, PR 43): the activations' rounding, which the
logits' limit already judges, hid half of what this limit is there to see.
Held to the rounded one the served system reads 2e-5 after a prompt's chunks
and 1.2e-4 after 127 steps more on one Mamba block alone (my chip runs, PR 43);
the configuration's ``check.state_gap_tol_why`` has the runs' readings. The
logits are computed without the rounding.

The names below are the one adapter to the program: where each weight sits in
its parameter tree (``models/transformer.py:init_params``: three stacks of
blocks by kind, each in the order its kind's blocks come in the model; matrices
stored [in, out], expert matrices [experts held, in, out]).
"""

from __future__ import annotations

import sys

import jax
import jax.numpy as jnp
import numpy as np

EMBED, FINAL_NORM, HEAD = "embed", "norm_f", "lm_head"
MAMBA, EXPERTS, ATTENTION = "M", "E", "*"
STACKS = {MAMBA: "mamba_layers", EXPERTS: "expert_layers", ATTENTION: "layers"}
MAMBA_LEAVES = {
    "norm": "attn_norm", "w_z": "w_z", "w_xbc": "w_xbc", "w_dt": "w_dt", "conv": "conv_w", "conv_bias": "conv_b",
    "A_log": "A_log", "dt_bias": "dt_bias", "D": "D", "gate_norm": "ssm_norm", "w_o": "wo",
}
ATTENTION_LEAVES = {"norm": "attn_norm", "w_q": "wq", "w_k": "wk", "w_v": "wv", "w_o": "wo"}
ROUTER_LEAVES = {"norm": "mlp_norm", "w_router": "gate", "router_bias": "gate_bias"}
SHARED_LEAVES = {"w_up": "wi_s", "w_down": "wo_s"}
EXPERT_LEAVES = {"w_up": "wi_e", "w_down": "wo_e"}
F32 = jnp.float32
VOCAB_BLOCKS = 8


def _take(stack: dict, names: dict, *index) -> dict:
    """Leaves of one block (or of one expert of one block), in float32."""
    out = {}
    for ours, theirs in names.items():
        leaf = stack[theirs]
        for i in index:
            leaf = jax.lax.dynamic_index_in_dim(leaf, i, 0, keepdims=False)
        out[ours] = leaf.astype(F32)
    return out


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def causal_conv(u, taps, bias):
    """u [T, C], taps [K, C]: ``y_t = sum_i taps[i] u_{t - (K - 1) + i} + bias``, zeros before the first token."""
    K, T = taps.shape[0], u.shape[0]
    padded = jnp.pad(u, ((K - 1, 0), (0, 0)))
    return sum(padded[i : i + T] * taps[i] for i in range(K)) + bias


def ssm_recurrence(x, dt, A, B, C, D, fed=None):
    """A token at a time from a zero state: x [T, H, P], dt [T, H], A, D [H],
    B, C [T, H, N] (each head its group's) -> (y [T, H, P], the state [H, P, N]
    after the first ``fed`` tokens; None: after them all)."""
    T, H, P = x.shape
    last = T - 1 if fed is None else fed - 1

    def token(carry, xs):
        S, kept = carry
        t, xt, dtt, Bt, Ct = xs
        S = S * jnp.exp(dtt * A)[:, None, None] + (dtt[:, None] * xt)[:, :, None] * Bt[:, None, :]
        return (S, jnp.where(t == last, S, kept)), jnp.einsum("hpn,hn->hp", S, Ct) + D[:, None] * xt

    zero = jnp.zeros((H, P, B.shape[-1]), F32)
    (_, kept), y = jax.lax.scan(token, (zero, zero), (jnp.arange(T), x, dt, B, C))
    return y, kept


def mamba_mixer(w: dict, h, m: dict, fed=None, rounded=None):
    """h [T, D], normed -> (the mixer's output [T, D], the state after the first
    ``fed`` tokens). ``rounded`` (a dtype, optional): the normed input and the
    convolution's channels take that dtype's values on the way, as the served
    model's do (module docstring: the state's comparison only)."""
    T = h.shape[0]
    H, P, N, G = m["mamba_num_heads"], m["mamba_head_dim"], m["ssm_state_size"], m["n_groups"]
    inner = H * P
    # ``reduce_precision`` and not a cast there and back, which the compiler may drop (``xla_allow_excess_precision``).
    bits = jnp.finfo(rounded) if rounded else None
    as_served = (lambda a: jax.lax.reduce_precision(a, bits.nexp, bits.nmant)) if rounded else (lambda a: a)
    h = as_served(h)
    z = h @ w["w_z"]
    xbc = jax.nn.silu(causal_conv(as_served(h @ w["w_xbc"]), w["conv"], w["conv_bias"]))
    dt = jax.nn.softplus(h @ w["w_dt"] + w["dt_bias"])
    x = xbc[:, :inner].reshape(T, H, P)
    B = jnp.repeat(xbc[:, inner : inner + G * N].reshape(T, G, N), H // G, axis=1)
    C = jnp.repeat(xbc[:, inner + G * N :].reshape(T, G, N), H // G, axis=1)
    y, state = ssm_recurrence(x, dt, -jnp.exp(w["A_log"]), B, C, w["D"], fed)
    gated = (y.reshape(T, inner) * jax.nn.silu(z)).reshape(T, G, inner // G)
    gated = gated * jax.lax.rsqrt(jnp.mean(gated * gated, axis=-1, keepdims=True) + m["layer_norm_epsilon"])
    return (gated.reshape(T, inner) * w["gate_norm"]) @ w["w_o"], state


def attention_mixer(w: dict, h, m: dict):
    """h [T, D], normed -> [T, D]: causal softmax attention without any positional encoding."""
    T = h.shape[0]
    H, KV, Dh = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    q = (h @ w["w_q"]).reshape(T, KV, H // KV, Dh)
    k, v = (h @ w["w_k"]).reshape(T, KV, Dh), (h @ w["w_v"]).reshape(T, KV, Dh)
    mask = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]

    def group(args):
        qg, kg, vg = args  # the query heads [T, H / KV, Dh] that share one key-value head [T, Dh]
        s = jnp.einsum("trd,sd->rts", qg, kg) * Dh**-0.5
        p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("rts,sd->trd", p, vg)

    o = jax.lax.map(group, (jnp.moveaxis(q, 1, 0), jnp.moveaxis(k, 1, 0), jnp.moveaxis(v, 1, 0)))  # [KV, T, H / KV, Dh]
    return jnp.moveaxis(o, 0, 1).reshape(T, H * Dh) @ w["w_o"]


def relu2(w: dict, h):
    return jnp.square(jax.nn.relu(h @ w["w_up"])) @ w["w_down"]


# A system's choice of experts is admitted where each scores, by this
# reference's own biased scores, at most this far under the reference's k-th
# best (module docstring). The value of the other two expert architectures
# (top-8 of 128 and top-4 of 64, PR 32 and 35); the configuration's
# ``check.logit_gap_tol_why`` gives this architecture's readings.
ROUTER_TIE = 0.01
DEFICIT_STEPS = (0.002, 0.003, 0.004, 0.005, 0.0075, 0.01, 0.015)


def share(m: dict) -> tuple:
    """(index, chips): which run of the experts the parameter tree holds."""
    ep = m["deployment"]["expert_parallel"]
    return int(ep["index"]), int(ep["chips"])


def routing_weights(w: dict, h, m: dict, served=None):
    """([T, E]: each token's weight on each of ALL the experts, zero where not
    taken; [T]: how far the lowest of the system's experts lies under this
    reference's k-th best biased score, 0 without ``served``). ``served`` [T, k]
    int32 (optional): the experts the system took, a row of -1 where it has no
    answer; admitted as the module docstring says."""
    k = m["num_experts_per_tok"]
    s = jax.nn.sigmoid(h @ w["w_router"])
    biased = s + w["router_bias"]
    top, chosen = jax.lax.top_k(biased, k)
    deficit = jnp.zeros(h.shape[:1], F32)
    if served is not None:
        theirs = jnp.take_along_axis(biased, jnp.maximum(served, 0), axis=-1)
        answered = jnp.all(served >= 0, axis=-1)
        deficit = jnp.where(answered, jnp.max(top[:, -1:] - theirs, axis=-1), 0.0)
        admitted = (answered & (deficit <= ROUTER_TIE))[:, None]
        chosen = jnp.where(admitted, served, chosen)
    picked = jnp.max(jax.nn.one_hot(chosen, s.shape[-1], dtype=F32), axis=1)  # [T, E] of 0 / 1
    kept = s * picked
    return m["routed_scaling_factor"] * kept / (jnp.sum(kept, axis=-1, keepdims=True) + 1e-20), deficit


def experts_mixer(stack: dict, index, w: dict, h, m: dict, served=None):
    """h [T, D], normed -> (the held experts' part of the block's output plus
    the shared expert's [T, D], each token's deficit: ``routing_weights``)."""
    weights, deficit = routing_weights(w, h, m, served)
    held = stack[EXPERT_LEAVES["w_up"]].shape[1]
    first = share(m)[0] * held

    def one_expert(acc, e):
        out = relu2(_take(stack, EXPERT_LEAVES, index, e), h)
        return acc + jax.lax.dynamic_index_in_dim(weights, first + e, 1, keepdims=True) * out, None

    routed, _ = jax.lax.scan(one_expert, jnp.zeros_like(h), jnp.arange(held))
    return routed + relu2(_take(stack, SHARED_LEAVES, index), h), deficit


def block(params: dict, kind: str, index, x, m: dict, fed=None, served=None, rounded=None):
    """Block ``index`` of its kind: x [T, D] -> ([T, D], a Mamba block's state
    after the first ``fed`` tokens or an experts block's deficits, else None).
    ``rounded``: ``mamba_mixer``'s."""
    stack = params[STACKS[kind]]
    names = {MAMBA: MAMBA_LEAVES, EXPERTS: ROUTER_LEAVES, ATTENTION: ATTENTION_LEAVES}[kind]
    w = _take(stack, names, index)
    h = rms_norm(x, w["norm"], m["layer_norm_epsilon"])
    if kind == MAMBA:
        out, extra = mamba_mixer(w, h, m, fed, rounded)
    elif kind == EXPERTS:
        out, extra = experts_mixer(stack, index, w, h, m, served)
    else:
        out, extra = attention_mixer(w, h, m), None
    return x + out, extra


def head_logits(params: dict, x, m: dict):
    """x [n, D] -> [n, V], the head a block of the vocabulary at a time."""
    x = rms_norm(x, params[FINAL_NORM].astype(F32), m["layer_norm_epsilon"])
    V = m["vocab_size"]
    blocks = VOCAB_BLOCKS if V % VOCAB_BLOCKS == 0 else 1
    width = V // blocks

    def part(i):
        return x @ jax.lax.dynamic_slice_in_dim(params[HEAD], i * width, width, axis=1).astype(F32)

    return jnp.moveaxis(jax.lax.map(part, jnp.arange(blocks)), 0, 1).reshape(x.shape[0], V)


def _ranked(m: dict) -> list:
    """(kind, rank among its kind) of every block, in the order they run."""
    seen, out = {}, []
    for kind in m["hybrid_override_pattern"]:
        out.append((kind, seen.get(kind, 0)))
        seen[kind] = seen.get(kind, 0) + 1
    return out


def sequence_logits(params: dict, tokens, m: dict):
    """tokens [T] -> logits [T, V]: the whole forward pass of one sequence, the
    reference's own routing choice everywhere."""
    with jax.default_matmul_precision("highest"):
        x = params[EMBED][jnp.asarray(tokens, jnp.int32)].astype(F32)
        for kind, index in _ranked(m):
            x, _ = block(params, kind, index, x, m)
        return head_logits(params, x, m)


def serving_engine(params):
    """The engine in this process that serves ``params``, or None."""
    from ray_tpu.serve.llm import stats

    return next((e for e in stats.ENGINES if e.params is params), None)


def served_again(engine, prompt: list, new: list):
    """What the serving system took and holds when it answers ``prompt`` with
    ``new`` (greedy) once more: (the experts of every token it was fed, int
    [len(prompt) + len(new) - 1, experts blocks, k]; the Mamba blocks' state
    [Mamba blocks, H, P, N] after the last token fed). (None, None) where it now
    answers otherwise."""
    request = engine.submit(prompt, max_new_tokens=len(new), return_routed_experts=True, return_state=True)
    if request.result(timeout=300.0) != list(new):
        return None, None
    return request.routed_experts, np.asarray(request.state, np.float32)


def state_gaps(served, own: list) -> list:
    """``|S_served - S| / |S|`` of every Mamba block, all its heads together."""
    return [float(np.linalg.norm(a - np.asarray(b)) / np.linalg.norm(np.asarray(b))) for a, b in zip(served, own)]


def served_deficits(deficits, fed: int) -> dict:
    """Of the [experts blocks, T] deficits of one sequence's first ``fed``
    tokens: the largest, and how many lie over each of ``DEFICIT_STEPS``."""
    d = np.asarray(deficits)[:, :fed]
    return {"decisions": int(d.size), "max": float(d.max(initial=0.0)),
            "over": {str(t): int((d > t).sum()) for t in DEFICIT_STEPS}}


def make_layerwise_logits(m: dict):
    """Serving check: a block at a time, so that only one float32 block's worth
    sits beside the replica's weights. Returns ``logits(params, tokens, rows)``
    giving the logits [len(rows), V] of one sequence at the given positions,
    ``rows`` the positions that predict the tokens the system generated: under
    the system's routing where this reference admits it, and NaN where the
    first block's served state is out of ``check.state_gap_tol`` (module docstring)."""

    @jax.jit
    def embedded(params, tokens):
        return params[EMBED][tokens].astype(F32)

    @jax.jit
    def head(params, x, rows):
        with jax.default_matmul_precision("highest"):
            return head_logits(params, x[rows], m)

    def one_block(params, index, x, fed, served, kind, rounded=None):
        with jax.default_matmul_precision("highest"):
            return block(params, kind, index, x, m, fed, served, rounded)

    one_block = jax.jit(one_block, static_argnums=(5, 6))  # one program a kind of block: index and fed are traced
    served_dtype = jnp.dtype(m.get("torch_dtype", "float32"))
    tol = m["check"]["state_gap_tol"]
    k = m["num_experts_per_tok"]

    def logits(params, tokens, rows):
        tokens = [int(t) for t in tokens]
        fed = rows[-1] + 1  # what the system was fed when it answered: all but the last token it drew
        engine = serving_engine(params)
        theirs = state = None
        if engine is not None:
            theirs, state = served_again(engine, tokens[: rows[0] + 1], tokens[rows[0] + 1 : fed + 1])
        n_experts = m["hybrid_override_pattern"].count(EXPERTS)
        served = np.full((len(tokens), n_experts, k), -1, np.int32)
        if theirs is not None:
            served[: len(theirs)] = theirs
        x = embedded(params, jnp.asarray(tokens, jnp.int32))
        own, deficits = [], []
        for kind, index in _ranked(m):
            routed = jnp.asarray(served[:, index]) if kind == EXPERTS else None
            if kind == MAMBA and state is not None and not own and served_dtype != F32:
                # The first block's state once more, over inputs rounded as the served model rounds them: what it is held to.
                _, extra = one_block(params, jnp.int32(index), x, jnp.int32(fed), None, kind, served_dtype)
                x, _ = one_block(params, jnp.int32(index), x, jnp.int32(fed), None, kind)
            else:
                x, extra = one_block(params, jnp.int32(index), x, jnp.int32(fed), routed, kind)
            if kind == MAMBA and state is not None:
                own.append(np.asarray(extra))  # to the host: one a block would sit beside the replica
            elif kind == EXPERTS:
                deficits.append(extra)
        out = head(params, x, jnp.asarray(rows, jnp.int32))
        if engine is None:
            return out
        gaps = None if state is None else state_gaps(state, own)
        held = gaps is not None and gaps[0] <= tol
        print(f"[reference] {fed} tokens fed: Mamba blocks' state gaps {gaps and [float(f'{g:.3g}') for g in gaps]}, "
              f"the first held to {tol}: {held}; served experts under the k-th best: "
              f"{theirs is not None and served_deficits(deficits, len(theirs))}", file=sys.stderr, flush=True)
        return out if held else jnp.full_like(out, jnp.nan)

    return logits
