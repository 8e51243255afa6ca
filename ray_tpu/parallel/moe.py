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


# ``jax.lax.ragged_dot``'s TPU kernel is tiled in 512s. On the v5e it reads 64
# touched experts' [2048, 1536] matrices at 63 % of the HBM peak (0.78 ms), but
# takes 7.5 ms for [2688, 1920] (11 %) and 2.6 ms for [2688, 2048], whatever the
# rows, the empty groups or the rows of no group (my chip run, PR 43).
_GROUPED_TILE = 512


def grouped_matmul_tiles(d_model: int, d_expert: int) -> bool:
    """Whether ``routed_experts`` runs an expert's matmuls grouped
    (``jax.lax.ragged_dot``): where the kernel's tiles divide both of an
    expert's widths, or a width is smaller than a tile (nothing to divide: the
    sizes tests run at). Where they do not (Nemotron-3-Nano's 2688 and 1856)
    every held expert runs over every row as plain batched matmuls, the
    routing weights zero where a row did not choose it: more operations by
    experts held over experts a row, and for a decode step, which touches
    nearly all its experts anyway, the same bytes read at 90 % of the HBM peak
    (1.7 ms for both matrices of 64 experts at 64 rows, 3.5 ms at 512 rows, where
    the grouped kernel takes 15 and 20)."""
    return all(n % _GROUPED_TILE == 0 or n < _GROUPED_TILE for n in (d_model, d_expert))


def _every_expert(params, x, weights, layer):
    """Every held expert over every row: x [N, D], ``weights`` [N, experts held]
    float32 (zero where a row did not choose the expert) -> [N, D] float32. The
    weights go in ahead of the down projection, which then sums over experts
    and their width in one contraction."""
    wi, wo, wg = params["wi_e"], params["wo_e"], params.get("wg_e")
    if layer is not None:
        wi, wo, wg = (w if w is None else jax.lax.dynamic_index_in_dim(w, layer, 0, keepdims=False) for w in (wi, wo, wg))
    xe = jnp.broadcast_to(x, (wi.shape[0], *x.shape))
    h = jnp.einsum("end,edf->enf", xe, wi.astype(x.dtype))
    if wg is None:
        h = jnp.square(jax.nn.relu(h))
    else:
        h = jax.nn.silu(jnp.einsum("end,edf->enf", xe, wg.astype(x.dtype))) * h
    h = (h.astype(jnp.float32) * weights.T[:, :, None]).astype(x.dtype)
    return jnp.einsum("enf,efd->nd", h, wo.astype(x.dtype), preferred_element_type=jnp.float32)


def routed_experts(params, x, *, k: int, scale: float = 1.0, valid=None, layer=None, share=(0, 1)):
    """Dropless top-``k`` routing over SwiGLU experts: every token reaches its
    ``k`` experts, whatever the load. No capacity, so no ``[tokens, E, C]``
    tensor: assignments are sorted by expert and the three matmuls run
    grouped (``jax.lax.ragged_dot``), each touched expert's weights read once.

    params: ``gate`` [D, E] and ``gate_bias`` [E] (float32), ``wg_e`` / ``wi_e``
    [E, D, F], ``wo_e`` [E, F, D]; without ``wg_e`` an expert is ``relu(x
    W_up)^2 W_down``, two matrices. x: [N, D]. Widths that the grouped kernel
    does not tile take plain batched matmuls instead (``grouped_matmul_tiles``);
    routing, counts and result are the same. The router runs in float32:
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

    ``share`` (index, of): the expert leaves hold ``E / of`` of the E experts,
    those from ``index * E / of`` on: one chip's of an expert-parallel
    deployment. The router is as wide as all E and chooses among all of them,
    the weights are normalised over the ``k`` chosen; an assignment to an expert
    that is not held is sent past the last group, like a padding row's, and
    adds nothing: ``out`` is this share's PART of the layer's result (the
    shares' parts add up to it), and nothing stands in for the others'.

    Returns (out [N, D] in x's dtype, assignments [experts held] int32: tokens
    sent to each, chosen [N, k] int32: each row's experts among all E, padding
    rows' too)."""
    N, D = x.shape
    E = params["gate"].shape[-1]
    held = E // share[1]
    s = jax.nn.sigmoid(x.astype(jnp.float32) @ params["gate"].astype(jnp.float32))  # [N, E]
    _, chosen = jax.lax.top_k(s + params["gate_bias"].astype(jnp.float32), k)  # [N, k]
    w = jnp.take_along_axis(s, chosen, axis=-1)
    w = scale * w / jnp.sum(w, axis=-1, keepdims=True)
    expert = chosen.reshape(N * k)
    if held != E:  # by its rank among the experts held; one that is not held: past the last
        expert = expert - share[0] * held
        expert = jnp.where((expert >= 0) & (expert < held), expert, held)
    if valid is not None:
        # Past the last expert: sorted behind every group, in none of them.
        expert = jnp.where(jnp.repeat(valid, k), expert, held)
    if not grouped_matmul_tiles(D, params["wo_e"].shape[-2]):
        sizes = jnp.zeros((held,), jnp.int32).at[expert].add(1, mode="drop")
        rows = jnp.arange(N, dtype=jnp.int32)[:, None]
        by_expert = jnp.zeros((N, held), jnp.float32).at[rows, expert.reshape(N, k)].add(w, mode="drop")
        with jax.named_scope("moe_experts"):
            return _every_expert(params, x, by_expert, layer).astype(x.dtype), sizes, chosen
    order = jnp.argsort(expert)  # stable: assignment ids grouped by expert
    sizes = jnp.zeros((held,), jnp.int32).at[expert].add(1, mode="drop")
    xs = x[order // k]  # [N * k, D]
    groups = sizes
    if layer is not None:
        stacked = params["wi_e"].shape[0]
        groups = jax.lax.dynamic_update_slice(jnp.zeros((stacked * held,), jnp.int32), sizes, (layer * held,))

    def grouped(a, w_e):
        w_e = w_e.reshape(-1, *w_e.shape[-2:])  # [L, E, in, out] -> [L * E, in, out]: no copy
        return jax.lax.ragged_dot(a, w_e.astype(a.dtype), groups)

    with jax.named_scope("moe_experts"):
        if "wg_e" in params:
            h = jax.nn.silu(grouped(xs, params["wg_e"])) * grouped(xs, params["wi_e"])
        else:
            h = jnp.square(jax.nn.relu(grouped(xs, params["wi_e"])))
        ys = grouped(h, params["wo_e"])  # [N * k, D]; a row of no group holds whatever the kernel left there
    # Un-sort by gather (assignment a sits at sorted row inverse[a]), combine in float32.
    inverse = jnp.zeros((N * k,), jnp.int32).at[order].set(jnp.arange(N * k, dtype=jnp.int32))
    y = ys[inverse].reshape(N, k, D).astype(jnp.float32)
    if held != E:  # whatever the grouped matmul left in a row of no group
        first = share[0] * held
        y = jnp.where(((chosen >= first) & (chosen < first + held))[..., None], y, 0.0)
    if valid is not None:
        # A row that is no token (an inactive slot, a chunk's padding) is in no group, and what the grouped matmul
        # leaves in such a row is whatever was there: zeros at some widths, at others (3584 x 1024, v5e, PR 47) not
        # even finite, and a weight of 0 does not mask that. Such a row's output is read by nobody, but its cached
        # row lands in the null block, which every gathered view holds behind its mask, where 0 x NaN is NaN.
        y = jnp.where(valid[:, None, None], y, 0.0)
        w = jnp.where(valid[:, None], w, 0.0)
    return jnp.einsum("nk,nkd->nd", w, y).astype(x.dtype), sizes, chosen
