"""Mixture-of-experts with expert parallelism over the ``ep`` mesh axis.

Absent from the reference (SURVEY.md §2.3: EP nowhere in-tree); TPU-native
version: Switch-style top-1/top-k routing with a capacity factor, dispatch and
combine expressed as einsums against a one-hot dispatch tensor. Experts'
weights are sharded over ``ep``; under pjit the dispatch einsum lowers to an
all_to_all over ICI. No data-dependent shapes — capacity is static, overflow
tokens drop (standard Switch semantics), so the whole layer jits cleanly.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def top_k_routing(gate_logits, num_experts: int, capacity: int, k: int = 1):
    """Returns (dispatch [B,T,E,C] one-hot, combine [B,T,E,C] weights).

    Tokens beyond an expert's capacity are dropped (combine weight 0).
    """
    B, T, E = gate_logits.shape
    probs = jax.nn.softmax(gate_logits, axis=-1)
    combine = jnp.zeros((B, T, E, capacity), probs.dtype)
    dispatch = jnp.zeros((B, T, E, capacity), jnp.bool_)
    remaining = probs
    # Track how many tokens each expert has accepted so far (per batch).
    for _ in range(k):
        expert_idx = jnp.argmax(remaining, axis=-1)  # [B,T]
        onehot = jax.nn.one_hot(expert_idx, E, dtype=probs.dtype)  # [B,T,E]
        gate = (remaining * onehot).sum(-1)  # [B,T]
        # Position of each token within its expert's queue.
        pos = jnp.cumsum(onehot, axis=1) * onehot - 1.0  # [B,T,E], -1 where unrouted
        pos = pos.max(-1)  # [B,T]
        in_cap = pos < capacity
        pos_clamped = jnp.clip(pos, 0, capacity - 1).astype(jnp.int32)
        cap_onehot = jax.nn.one_hot(pos_clamped, capacity, dtype=probs.dtype)  # [B,T,C]
        contrib = (
            onehot[..., None] * cap_onehot[:, :, None, :] * (gate * in_cap)[..., None, None]
        )
        combine = combine + contrib
        dispatch = dispatch | (contrib > 0)
        remaining = remaining * (1.0 - onehot)
    return dispatch.astype(probs.dtype), combine


def moe_layer(params, x, *, capacity_factor: float = 1.25, k: int = 1):
    """params: {"gate": [D,E], "wi": [E,D,F], "wo": [E,F,D]} (E sharded on ep).

    x: [B, T, D]. Returns [B, T, D] plus the load-balancing aux loss.
    """
    B, T, D = x.shape
    E = params["gate"].shape[-1]
    capacity = max(1, int(capacity_factor * T * k / E))
    logits = jnp.einsum("btd,de->bte", x, params["gate"])
    dispatch, combine = top_k_routing(logits, E, capacity, k)
    # Dispatch tokens: [B,T,E,C] x [B,T,D] -> [E, B*C? ] — keep batch dim:
    expert_in = jnp.einsum("btec,btd->ebcd", dispatch, x)  # [E,B,C,D]
    h = jnp.einsum("ebcd,edf->ebcf", expert_in, params["wi"])
    h = jax.nn.gelu(h)
    expert_out = jnp.einsum("ebcf,efd->ebcd", h, params["wo"])
    out = jnp.einsum("btec,ebcd->btd", combine, expert_out)
    # Load-balance aux loss (Switch): E * sum_e f_e * p_e.
    probs = jax.nn.softmax(logits, axis=-1)
    frac_tokens = dispatch.sum(axis=(1, 3)) / jnp.maximum(dispatch.sum(), 1.0)  # [B,E]
    frac_probs = probs.mean(axis=1)
    aux = E * jnp.mean(jnp.sum(frac_tokens * frac_probs, axis=-1))
    return out, aux


def init_moe_params(key, d_model: int, d_ff: int, num_experts: int, dtype=jnp.float32):
    k1, k2, k3 = jax.random.split(key, 3)
    scale = d_model**-0.5
    return {
        "gate": jax.random.normal(k1, (d_model, num_experts), dtype) * scale,
        "wi": jax.random.normal(k2, (num_experts, d_model, d_ff), dtype) * scale,
        "wo": jax.random.normal(k3, (num_experts, d_ff, d_model), dtype) * (d_ff**-0.5),
    }


def routed_experts(params, x, *, k: int, scale: float = 1.0, valid=None, layer=None):
    """Dropless top-``k`` routing over SwiGLU experts: every token reaches its
    ``k`` experts, whatever the load. No capacity, so no ``[tokens, E, C]``
    tensor: assignments are sorted by expert and the three matmuls run
    grouped (``jax.lax.ragged_dot``), each touched expert's weights read once.

    params: ``gate`` [D, E] and ``gate_bias`` [E] (float32), ``wg_e`` / ``wi_e``
    [E, D, F], ``wo_e`` [E, F, D]. x: [N, D]. The router runs in float32:
    ``s = sigmoid(x gate)``; the ``k`` experts with the largest ``s + gate_bias``
    are chosen (the bias chooses, it does not weigh), weighted
    ``scale * s / sum(s over the chosen)``. ``valid`` [N] bool (optional):
    rows that are padding; they reach no expert, add zeros, and are not counted.

    ``layer`` (a traced int32, optional): the three expert leaves are then
    whole STACKS ``[L, E, ...]`` and this call runs layer ``layer`` of them, as
    groups ``layer * E .. layer * E + E - 1`` of ``L * E`` (the others empty).
    A layer scan passes its expert stacks this way instead of slicing them:
    a slice that feeds a grouped matmul is materialised, all E experts of the
    layer copied every step for a kernel that reads the few it touches.

    Returns (out [N, D] in x's dtype, assignments [E] int32: tokens sent to
    each expert, chosen [N, k] int32: each row's experts, padding rows' too)."""
    N, D = x.shape
    E = params["gate"].shape[-1]
    s = jax.nn.sigmoid(x.astype(jnp.float32) @ params["gate"].astype(jnp.float32))  # [N, E]
    _, chosen = jax.lax.top_k(s + params["gate_bias"].astype(jnp.float32), k)  # [N, k]
    w = jnp.take_along_axis(s, chosen, axis=-1)
    w = scale * w / jnp.sum(w, axis=-1, keepdims=True)
    expert = chosen.reshape(N * k)
    if valid is not None:
        # Past the last expert: sorted behind every group, in none of them.
        expert = jnp.where(jnp.repeat(valid, k), expert, E)
    order = jnp.argsort(expert)  # stable: assignment ids grouped by expert
    sizes = jnp.zeros((E,), jnp.int32).at[expert].add(1, mode="drop")
    xs = x[order // k]  # [N * k, D]
    groups = sizes
    if layer is not None:
        stacked = params["wg_e"].shape[0]
        groups = jax.lax.dynamic_update_slice(jnp.zeros((stacked * E,), jnp.int32), sizes, (layer * E,))

    def grouped(a, w_e):
        w_e = w_e.reshape(-1, *w_e.shape[-2:])  # [L, E, in, out] -> [L * E, in, out]: no copy
        return jax.lax.ragged_dot(a, w_e.astype(a.dtype), groups)

    with jax.named_scope("moe_experts"):
        h = jax.nn.silu(grouped(xs, params["wg_e"])) * grouped(xs, params["wi_e"])
        ys = grouped(h, params["wo_e"])  # [N * k, D], rows of no group are zero
    # Un-sort by gather (assignment a sits at sorted row inverse[a]), combine in float32.
    inverse = jnp.zeros((N * k,), jnp.int32).at[order].set(jnp.arange(N * k, dtype=jnp.int32))
    y = ys[inverse].reshape(N, k, D).astype(jnp.float32)
    if valid is not None:
        w = jnp.where(valid[:, None], w, 0.0)
    return jnp.einsum("nk,nkd->nd", w, y).astype(x.dtype), sizes, chosen
