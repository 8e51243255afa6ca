"""The architecture in plain ``jax.numpy``: what the system is compared with.

SDAR-30B-A3B-Chat (``sdar_moe``): one kind of layer, and a way of GENERATING.

**The layer**, for hidden states ``x`` [N, D]: ``h = RMSNorm(x; input_layernorm,
rms_norm_eps)``; ``q = h W_q`` as [heads, head_dim], ``k = h W_k``, ``v = h W_v`` as
[KV heads, head_dim], no bias; RMSNorm with a learned [head_dim] weight over each
head of q and of k; rotary over the whole head at ``rope_theta`` (rotate-half);
softmax attention at ``head_dim^-1/2``, a KV head's group of query heads at a
time, under the mask below; ``x <- x + o W_o``. ``g = RMSNorm(x;
post_attention_layernorm)``; ``p = softmax(g W_r)`` over all ``num_experts`` in
float32; the ``num_experts_per_tok`` largest; ``w = p[chosen] / sum p[chosen]``
(``norm_topk_prob``); ``x <- x + sum_e w_e (silu(g W_gate,e) * (g W_up,e))
W_down,e``. Every expert is run over every token and weighted (zero where not
chosen). After the last layer RMSNorm, then the untied head. The logits AT a
position predict that position's own token (no shift).

**The mask**, ``B = block_length``, blocks aligned to absolute positions: the
query at position p sees the key at position s iff ``s < (p // B + 1) * B``:
causal between blocks, two-way inside one.

**Generation** of the block at ``[a, a + B)`` over final tokens at positions
``< a``: the block starts as what is known of it (a prompt's tail) and ``MASK``
(``mask_token_id``) elsewhere; a denoising pass runs the model over the block's
current ids, draws at every masked position from the logits at that position,
notes the probability of what it drew (its confidence) and TRANSFERS some
masked positions (they keep what they drew for good); when none is masked a
commit pass runs the model over the final ids. This file computes the logits of
any pass WITHOUT a cache, as the family trains: one forward over ``[the final
sequence ; every noisy copy of a block]``, where a noisy copy of block j (the
block's ids before one of its passes) sees the final blocks before j and
itself (``visible``), and its rotary positions are the block's own.

Float32 throughout, ``default_matmul_precision("highest")``, no kernel, no
cache, no chunk, no batching; nothing of ``ray_tpu/ops`` or
``ray_tpu/parallel`` is imported here. Departures, all to fit beside the system
under test on the chip: the experts one at a time (each cast to float32 as it
is used), the head a block of the vocabulary at a time, and the serving check
runs a layer at a time.

**The serving check** (``make_layerwise_logits``). The harness hands over a
prompt and the tokens the system answered it with, greedy, and asks for the
logits "that predicted" each of them. Here that is the logits at the token's
own position IN THE PASS THAT TRANSFERRED IT, and which pass that was only the
system knows: the engine in this process serves the request once more
(``submit(return_block_passes=True, return_routed_experts=True)``) and hands
back, for every pass of every block, the block's ids after it and the experts
each of its positions took in it. From those this file rebuilds each pass's
input and computes its logits. HOW MANY positions each pass transfers and when
a block commits are not the system's to say: they are the configuration's
schedule (``off_schedule``: ``deployment.engine.denoising_steps`` passes a block,
pass s transferring ``min(n_s, masks left)``, one commit pass behind them), and
a sequence with one pass off it comes back NaN whatever its logits. Two things
of the system's are followed, each only as far as this reference's own float32
numbers admit it:

- *the experts* (PR 32's method): a position's experts in a pass are the
  system's where each scores, by the reference's own router LOGITS (softmax is
  monotone in them, and a probability over 128 experts is too small a number
  to set a margin on), at most ``check.router_tie_tol`` under the reference's
  k-th best. The weights are always the reference's own probabilities. Unlike
  PR 32's, a decision outside the margin fails the sequence: with random
  weights the logit gaps of this model's greedy tokens move little under a
  coarser precision (the configuration's ``check.logit_gap_tol_why`` has the
  readings), while the router's decisions, taken in float32 on both sides
  from hidden states that carry the layers' error, move a lot.
- *the transfers*: which masked positions a pass kept. Each transferred
  position's confidence, by the reference's own probabilities (greedy: the
  largest softmax probability at the position), must lie within the relative
  margin ``check.transfer_margin_tol`` of the reference's own n-th best among the
  positions masked before the pass, n the schedule's number for the pass.

A sequence off the schedule, outside either margin, or whose second serving
differs from the first, comes back NaN, which the harness reads as not finite and not correct.

The names below are the one adapter to the program: where each weight sits in
its parameter tree (``models/transformer.py:init_params``; matrices stored [in,
out], expert matrices [experts, in, out]).
"""

from __future__ import annotations

import sys

import jax
import jax.numpy as jnp
import numpy as np

EMBED, HEAD, FINAL_NORM, STACK = "embed", "lm_head", "norm_f", "layers"
LAYER_LEAVES = {
    "input_layernorm": "attn_norm", "w_q": "wq", "w_k": "wk", "w_v": "wv", "w_o": "wo", "q_norm": "q_norm", "k_norm": "k_norm",
    "post_attention_layernorm": "mlp_norm", "w_router": "gate",
}
EXPERT_LEAVES = {"w_gate": "wg_e", "w_up": "wi_e", "w_down": "wo_e"}
F32 = jnp.float32
VOCAB_BLOCKS = 8
MASKED = -1  # in a block's ids: a position still masked (the model is fed ``mask_token_id`` there)
FINAL = 0  # ``segment`` of the final sequence's positions; a noisy copy's is its own number, from 1
PAD = -1  # ``segment`` of a padding row: it sees itself only

DEFICIT_STEPS = (0.005, 0.01, 0.02, 0.04, 0.06, 0.1)


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
    """x [N, H, d]; rotate-half convention, the whole head."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=F32) / half)
    angles = positions.astype(F32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def visible(segment, positions, block: int):
    """[N, N] bool: row i sees row j. Final rows (``segment`` ``FINAL``) see the
    final rows under the end of their own block; a noisy copy's rows see the
    final rows of the blocks BEFORE theirs and the rows of their own copy; a
    padding row (``PAD``) sees itself."""
    start = (positions // block) * block
    q_seg, k_seg = segment[:, None], segment[None, :]
    limit = jnp.where(segment == FINAL, start + block, start)[:, None]  # a query's: the end of its block, or (a copy's) the start
    final = (k_seg == FINAL) & (positions[None, :] < limit)
    own = (k_seg == q_seg) & (q_seg > FINAL)
    return jnp.where(q_seg == PAD, jnp.eye(segment.shape[0], dtype=bool), final | own)


def attention(w: dict, h, positions, mask, m: dict):
    """h [N, D], normed -> [N, D]: QK-normed, roped softmax attention under ``mask`` [N, N]."""
    N = h.shape[0]
    H, KV, Dh = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    theta, eps = float(m["rope_theta"]), m["rms_norm_eps"]
    q = rope(rms_norm((h @ w["w_q"]).reshape(N, H, Dh), w["q_norm"], eps), positions, theta)
    k = rope(rms_norm((h @ w["w_k"]).reshape(N, KV, Dh), w["k_norm"], eps), positions, theta)
    v = (h @ w["w_v"]).reshape(N, KV, Dh)

    def group(args):
        qg, kg, vg = args  # the query heads [N, H / KV, Dh] that share one key-value head [N, Dh]
        s = jnp.einsum("trd,sd->rts", qg, kg) * Dh**-0.5
        p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("rts,sd->trd", p, vg)

    by_group = jnp.moveaxis(q.reshape(N, KV, H // KV, Dh), 1, 0)
    o = jax.lax.map(group, (by_group, jnp.moveaxis(k, 1, 0), jnp.moveaxis(v, 1, 0)))  # [KV, N, H / KV, Dh]
    return jnp.moveaxis(o, 0, 1).reshape(N, H * Dh) @ w["w_o"]


def routing_weights(w_router, g, m: dict, served=None):
    """([N, E]: each row's weight on each expert, zero where not taken; [N]: how
    far the lowest of the system's experts lies under this reference's k-th
    best router logit, 0 without ``served``). ``served`` [N, k] int32
    (optional): the experts the system took, a row of -1 where it has no
    answer; admitted within ``check.router_tie_tol`` as the module docstring says."""
    E, k = m["num_experts"], m["num_experts_per_tok"]
    scores = g @ w_router
    p = jax.nn.softmax(scores, axis=-1)
    top, chosen = jax.lax.top_k(scores, k)
    deficit = jnp.zeros(g.shape[:1], F32)
    if served is not None:
        theirs = jnp.take_along_axis(scores, jnp.maximum(served, 0), axis=-1)
        answered = jnp.all(served >= 0, axis=-1)
        deficit = jnp.where(answered, jnp.max(top[:, -1:] - theirs, axis=-1), 0.0)
        admitted = (answered & (deficit <= m["check"]["router_tie_tol"]))[:, None]
        chosen = jnp.where(admitted, served, chosen)
    picked = jnp.max(jax.nn.one_hot(chosen, E, dtype=F32), axis=1)  # [N, E] of 0 / 1
    kept = p * picked
    return kept / jnp.sum(kept, axis=-1, keepdims=True), deficit


def experts_ffn(stack: dict, index, w_router, g, m: dict, served=None):
    """g [N, D], normed -> (the routed experts' sum [N, D], each row's deficit: ``routing_weights``)."""
    weights, deficit = routing_weights(w_router, g, m, served)

    def one_expert(acc, e):
        w = _take(stack, EXPERT_LEAVES, index, e)
        out = (jax.nn.silu(g @ w["w_gate"]) * (g @ w["w_up"])) @ w["w_down"]
        return acc + jax.lax.dynamic_index_in_dim(weights, e, 1, keepdims=True) * out, None

    routed, _ = jax.lax.scan(one_expert, jnp.zeros_like(g), jnp.arange(m["num_experts"]))
    return routed, deficit


def layer(params: dict, index, x, positions, mask, m: dict, served=None):
    """Layer ``index``: x [N, D] -> ([N, D], each row's deficit)."""
    stack = params[STACK]
    w = _take(stack, LAYER_LEAVES, index)
    x = x + attention(w, rms_norm(x, w["input_layernorm"], m["rms_norm_eps"]), positions, mask, m)
    g = rms_norm(x, w["post_attention_layernorm"], m["rms_norm_eps"])
    routed, deficit = experts_ffn(stack, index, w["w_router"], g, m, served)
    return x + routed, deficit


def head_logits(params: dict, x, m: dict):
    """x [n, D] -> [n, V]: the final norm and the untied head, a block of the vocabulary at a time."""
    x = rms_norm(x, params[FINAL_NORM].astype(F32), m["rms_norm_eps"])
    V = m["vocab_size"]
    blocks = VOCAB_BLOCKS if V % VOCAB_BLOCKS == 0 else 1
    width = V // blocks

    def part(i):
        return x @ jax.lax.dynamic_slice_in_dim(params[HEAD], i * width, width, axis=1).astype(F32)

    return jnp.moveaxis(jax.lax.map(part, jnp.arange(blocks)), 0, 1).reshape(x.shape[0], V)


def laid_out(final: list, copies: list, m: dict, pad_to: int = 0):
    """The rows of one forward: the final sequence, then every noisy copy
    ``(start, ids)`` of a block (``MASKED`` where masked). NumPy int32 arrays
    (tokens, positions, segment), padded with ``PAD`` rows to ``pad_to``."""
    B, mask_id = m["block_length"], m["mask_token_id"]
    tokens, positions, segment = list(final), list(range(len(final))), [FINAL] * len(final)
    for number, (start, ids) in enumerate(copies, 1):
        tokens += [mask_id if t == MASKED else t for t in ids]
        positions += range(start, start + B)
        segment += [number] * B
    pad = max(0, pad_to - len(tokens))
    return (np.asarray(tokens + [0] * pad, np.int32), np.asarray(positions + [0] * pad, np.int32),
            np.asarray(segment + [PAD] * pad, np.int32))


def pass_logits(params: dict, final: list, copies: list, m: dict):
    """Logits [len(copies), B, V] of every noisy copy's positions, and [len(final), V] of the final sequence's, in
    one forward, the reference's own routing everywhere: what the tests hold the paged path to, pass by pass."""
    tokens, positions, segment = laid_out(final, copies, m)
    with jax.default_matmul_precision("highest"):
        x = params[EMBED][jnp.asarray(tokens)].astype(F32)
        mask = visible(jnp.asarray(segment), jnp.asarray(positions), m["block_length"])
        for index in range(m["num_hidden_layers"]):
            x, _ = layer(params, index, x, jnp.asarray(positions), mask, m)
        logits = head_logits(params, x, m)
    T = len(final)
    return logits[T:].reshape(len(copies), m["block_length"], -1), logits[:T]


def serving_engine(params):
    """The engine in this process that serves ``params``, or None."""
    from ray_tpu.serve.llm import stats

    return next((e for e in stats.ENGINES if e.params is params), None)


def served_again(engine, prompt: list, new: list):
    """The serving system's record of answering ``prompt`` with ``new`` (greedy)
    once more: (its passes, ``LLMRequest.block_passes``; the experts of every
    token it has cached, int [len(prompt) + len(new) - 1, layers, k]). (None,
    None) where it now answers otherwise."""
    request = engine.submit(prompt, max_new_tokens=len(new), return_block_passes=True, return_routed_experts=True)
    if request.result(timeout=600.0) != list(new):
        return None, None
    return request.block_passes, request.routed_experts


def rebuilt(prompt: list, passes: list, m: dict):
    """From the system's passes: (the final sequence, prompt and every committed
    block; the noisy copies ``(start, ids before the pass)`` of every denoising
    pass, in the passes' order; for each copy the block's ids after it)."""
    B = m["block_length"]
    first = min(rec["start"] for rec in passes)
    final, copies, after, state = list(prompt[:first]), [], [], {}
    for rec in passes:
        start = rec["start"]
        if start not in state:  # the block's first pass: what is known of it, and MASK
            known = prompt[start : start + B]
            state[start] = known + [MASKED] * (B - len(known))
        if rec["commit"]:
            final += rec["ids"]
        else:
            copies.append((start, list(state[start])))
            after.append(list(rec["ids"]))
        state[start] = list(rec["ids"])
    return final, copies, after


def off_schedule(prompt: list, passes: list, m: dict):
    """What of the system's passes departs from the configuration's schedule,
    in words, or None. ``S = deployment.engine.denoising_steps`` (0: one a
    position): pass s of a block transfers ``min(n_s, masks left)`` positions,
    ``n_s = B // S`` and one more in the first ``B mod S`` passes; the pass
    after the one that leaves no mask is the commit, which moves nothing; a
    position that holds a token keeps it; a block's passes are numbered from 0
    and the blocks follow each other from the prompt's last edge."""
    B = m["block_length"]
    S = m["deployment"]["engine"]["denoising_steps"] or B
    at, state, nth = None, None, 0
    for rec in passes:
        start, ids = rec["start"], list(rec["ids"])
        if state is None:  # the block's first pass: what is known of it, and MASK
            if start != (len(prompt) - len(prompt) % B if at is None else at + B):
                return f"a block at {start} behind the one at {at}"
            known = prompt[start : start + B]
            at, state, nth = start, known + [MASKED] * (B - len(known)), 0
        if start != at or rec["pass"] != nth:
            return f"pass {rec['pass']} of the block at {start} where pass {nth} of the block at {at} is due"
        if any(t != MASKED and t != now for t, now in zip(state, ids)):
            return f"pass {nth} of the block at {at} changed a position that held a token"
        masks = sum(t == MASKED for t in state)
        due = min(B // S + (nth < B % S), masks)
        if bool(rec["commit"]) != (masks == 0) or masks - sum(t == MASKED for t in ids) != due:
            return (f"pass {nth} of the block at {at} (commit: {rec['commit']}) took {masks} masks to "
                    f"{sum(t == MASKED for t in ids)} where the schedule of {S} passes a block transfers {due}")
        state, nth = (None, 0) if rec["commit"] else (ids, nth + 1)
    return None


def served_deficits(deficits, rows) -> dict:
    """Of the [layers, N] deficits of one forward, over ``rows``: the largest, and how many lie over each of ``DEFICIT_STEPS``."""
    d = np.asarray(deficits)[:, rows]
    return {"decisions": int(d.size), "max": float(d.max(initial=0.0)),
            "over": {str(t): int((d > t).sum()) for t in DEFICIT_STEPS}}


def transfer_gap(confidence, before, after) -> float:
    """How far outside the reference's own choice a pass's transfers lie: of the
    positions masked ``before`` the pass that hold a token ``after`` it, n of
    them, the largest ``(t - c) / t``, c the position's confidence by the
    reference and t the reference's n-th best confidence among the masked; 0
    where every transferred position is among the reference's n best."""
    masked = [j for j, t in enumerate(before) if t == MASKED]
    moved = [j for j in masked if after[j] != MASKED]
    if not moved:
        return 0.0
    nth = sorted((float(confidence[j]) for j in masked), reverse=True)[len(moved) - 1]
    return max(0.0, max((nth - float(confidence[j])) / nth for j in moved))


def make_layerwise_logits(m: dict):
    """Serving check: a layer at a time, so that only one float32 layer's worth
    sits beside the replica's weights. Returns ``logits(params, tokens, rows)``
    giving [len(rows), V]: for the token at position ``r + 1`` of each ``r`` in
    ``rows`` (the harness names the position BEFORE each generated token, an
    autoregressive model's), the logits at ``r + 1`` in the pass that
    transferred it, under the system's routing where admitted, and NaN where
    the transfers are outside their margin (module docstring)."""
    B, L, k = m["block_length"], m["num_hidden_layers"], m["num_experts_per_tok"]
    margin, tie = m["check"]["transfer_margin_tol"], m["check"]["router_tie_tol"]

    @jax.jit
    def start(params, tokens, positions, segment):
        return params[EMBED][tokens].astype(F32), visible(segment, positions, B)

    @jax.jit
    def one_layer(params, index, x, positions, mask, served):
        with jax.default_matmul_precision("highest"):
            return layer(params, index, x, positions, mask, m, served)

    @jax.jit
    def head(params, x, rows, noisy):
        """(logits of ``rows`` [n, V], the largest softmax probability at each of ``noisy`` [c])."""
        with jax.default_matmul_precision("highest"):
            return head_logits(params, x[rows], m), jnp.max(jax.nn.softmax(head_logits(params, x[noisy], m), axis=-1), axis=-1)

    def logits(params, tokens, rows):
        tokens = [int(t) for t in tokens]
        n, G = rows[0] + 1, len(rows)
        prompt, new = tokens[:n], tokens[n : n + G]
        engine = serving_engine(params)
        if engine is None:
            raise RuntimeError("the reference of a model generated by diffusion over blocks needs the engine that served it: which pass transferred a token is the system's to say")
        passes, cached = served_again(engine, prompt, new)
        nan = jnp.full((G, m["vocab_size"]), jnp.nan, F32)
        if passes is None:
            print(f"[reference] {n}-token prompt: the engine answers otherwise than it did", file=sys.stderr, flush=True)
            return nan
        fault = off_schedule(prompt, passes, m)
        if fault is not None:
            print(f"[reference] {n}-token prompt: off the configuration's schedule: {fault}", file=sys.stderr, flush=True)
            return nan
        final, copies, after = rebuilt(prompt, passes, m)
        T = len(final)
        # One shape for every sequence of a run: the harness pads their tokens alike, and G tokens are at most
        # G // B + 2 blocks of as many denoising passes each as this sequence's longest block took.
        most = (G // B + 2) * (1 + max(rec["pass"] for rec in passes if not rec["commit"]))
        pad_to = -(-(len(tokens) + B + B * max(len(copies), most)) // 256) * 256
        laid = laid_out(final, copies, m, pad_to)
        served = np.full((pad_to, L, k), -1, np.int32)
        first = min(rec["start"] for rec in passes)
        served[: min(first, len(cached))] = cached[:first]
        at_copy = T
        for rec in passes:
            if "experts" not in rec:
                continue
            if rec["commit"]:
                served[rec["start"] : rec["start"] + B] = rec["experts"]
            else:
                served[at_copy : at_copy + B] = rec["experts"]
                at_copy += B
        # The row of the forward that drew each generated token: its position in the copy of the pass that transferred it.
        drew = {}
        for number, ((start_at, before), ids) in enumerate(zip(copies, after)):
            for j in range(B):
                if before[j] == MASKED and ids[j] != MASKED:
                    drew[start_at + j] = T + number * B + j
        picked = [drew[n + i] for i in range(G)]
        x, mask = start(params, *(jnp.asarray(a) for a in laid))
        positions, deficits = jnp.asarray(laid[1]), []
        for index in range(L):
            x, deficit = one_layer(params, jnp.int32(index), x, positions, mask, jnp.asarray(served[:, index]))
            deficits.append(deficit)
        noisy = np.arange(T, T + B * len(copies))
        out, confidence = head(params, x, jnp.asarray(picked, jnp.int32), jnp.asarray(noisy, jnp.int32))
        confidence = np.asarray(confidence).reshape(len(copies), B)
        gaps = [transfer_gap(c, before, ids) for c, (_, before), ids in zip(confidence, copies, after)]
        routed = served_deficits(deficits, np.arange(T + B * len(copies)))
        held = max(gaps, default=0.0) <= margin and routed["max"] <= tie
        print(f"[reference] {n}-token prompt, {len(copies)} denoising passes of {len(passes) - len(copies)} blocks: "
              f"transfers outside the reference's own by {max(gaps, default=0.0):.4g} at most ({sum(g > 0 for g in gaps)} passes), "
              f"held to {margin}; served experts under the k-th best: {routed}, held to {tie}; both held: {held}",
              file=sys.stderr, flush=True)
        return out if held else nan

    return logits
