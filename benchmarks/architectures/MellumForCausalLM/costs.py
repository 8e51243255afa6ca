"""Operations and bytes the algorithm needs, from shapes alone.

``m`` is the configuration's file: the published keys as cut, ``published``
and ``deployment.expert_parallel``. A multiply-add is two operations. Nothing
recomputed (remat) and no embedding lookup is counted: this is what the
mathematics requires of ONE chip of the expert-parallel job, not what the
program does.

The configuration does not serve: ``kv_bytes_per_token`` and
``decode_step_bytes`` are not defined, and no serving metric lists its cell.
"""

from __future__ import annotations


def _dims(m: dict):
    return (m["hidden_size"], m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"],
            m["moe_intermediate_size"], m["vocab_size"], m["num_hidden_layers"])


def attention_params(m: dict) -> int:
    D, H, KV, Dh, _, _, _ = _dims(m)
    return D * H * Dh + 2 * D * KV * Dh + H * Dh * D


def expert_params(m: dict) -> int:
    D, _, _, _, F, _, _ = _dims(m)
    return 3 * D * F


def router_params(m: dict) -> int:
    """As wide as ALL the experts, held here or not."""
    return m["hidden_size"] * m["published"]["num_experts"]


def layer_params(m: dict) -> int:
    """Attention, norms and router whole, the experts HELD here (``num_experts`` as cut)."""
    D, _, _, Dh, _, _, _ = _dims(m)
    return attention_params(m) + m["num_experts"] * expert_params(m) + router_params(m) + 2 * D + 2 * Dh


def n_params(m: dict) -> int:
    D, _, _, _, _, V, L = _dims(m)
    return L * layer_params(m) + 2 * V * D + D


def weight_bytes(m: dict, itemsize: int = 4) -> int:
    """The float32 master copy a trainer holds (AdamW's two moments and the gradients are three more of it)."""
    return n_params(m) * itemsize


def causal_pairs(T: int, window: int = 0) -> int:
    """(query, key) pairs a causal mask keeps, row i seeing keys (i-window, i]."""
    if not window or window >= T:
        return T * (T + 1) // 2
    return window * (window + 1) // 2 + (T - window) * window


def attention_fwd_flops(m: dict, T: int) -> int:
    """QK^T and PV of one sequence over all the layers, each by its kind: 4 x
    head_dim per kept pair and head."""
    _, H, _, Dh, _, _, _ = _dims(m)
    pairs = sum(causal_pairs(T, m["sliding_window"] if t == "sliding_attention" else 0) for t in m["layer_types"])
    return 4 * Dh * H * pairs


def held_experts_per_token(m: dict) -> float:
    """The EXPECTED number of a token's experts that this chip holds:
    ``num_experts_per_tok x held / published experts`` (2 of 8). Routing is
    data; the count is its mean, which a balance loss keeps the job near."""
    return m["num_experts_per_tok"] * m["num_experts"] / m["published"]["num_experts"]


def moe_train_flops(m: dict, T: int, batch: int) -> float:
    """The held experts' part of ``train_step_flops``: 6 per weight of an expert
    a token reaches here, over all layers."""
    return batch * T * 6 * m["num_hidden_layers"] * held_experts_per_token(m) * expert_params(m)


def moe_train_bytes(m: dict, itemsize: int = 4) -> int:
    """The least the experts' matmuls of a step move: the held matrices read
    once and their gradients written once, in the leaves' dtype."""
    return 2 * m["num_hidden_layers"] * m["num_experts"] * expert_params(m) * itemsize


def train_step_flops(m: dict, T: int, batch: int) -> float:
    """Forward plus backward of one optimizer step on this chip: 6 per matmul
    weight and token over attention, the router, the EXPECTED held experts of
    a token (``held_experts_per_token``) and the head's slice, plus attention's
    score and value products by kind of layer three times over (forward, and
    twice in the backward)."""
    D, _, _, _, _, V, L = _dims(m)
    dense = L * (attention_params(m) + router_params(m)) + D * V
    return batch * (T * 6 * dense + 3 * attention_fwd_flops(m, T)) + moe_train_flops(m, T, batch)
