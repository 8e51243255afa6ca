"""Operations and bytes the algorithm needs, from shapes alone.

``m`` is a configuration's ``model`` group as published: hidden_size,
num_attention_heads, num_key_value_heads, head_dim, intermediate_size,
vocab_size, num_hidden_layers (as cut), sliding_window. A multiply-add is two
operations. Nothing recomputed (remat) and no embedding lookup is counted:
this is what the mathematics requires, not what the program does.
"""

from __future__ import annotations


def _dims(m: dict):
    D, H, KV = m["hidden_size"], m["num_attention_heads"], m["num_key_value_heads"]
    Dh = m.get("head_dim") or D // H
    return D, H, KV, Dh, m["intermediate_size"], m["vocab_size"], m["num_hidden_layers"]


def matmul_params_per_layer(m: dict) -> int:
    D, H, KV, Dh, F, _, _ = _dims(m)
    return D * H * Dh + 2 * D * KV * Dh + H * Dh * D + 3 * D * F


def n_params(m: dict) -> int:
    D, _, _, _, _, V, L = _dims(m)
    return L * (matmul_params_per_layer(m) + 2 * D) + 2 * V * D + D


def causal_pairs(T: int, window: int = 0) -> int:
    """(query, key) pairs a causal mask keeps, row i seeing keys (i-window, i]."""
    if not window or window >= T:
        return T * (T + 1) // 2
    return window * (window + 1) // 2 + (T - window) * window


def attention_fwd_flops(m: dict, T: int) -> int:
    """QK^T and PV of one sequence in one layer: 4 x head_dim per kept pair
    and head."""
    _, H, _, Dh, _, _, _ = _dims(m)
    return 4 * Dh * H * causal_pairs(T, m.get("sliding_window") or 0)


def train_step_flops(m: dict, T: int, batch: int) -> int:
    """Forward plus backward of one optimizer step: 6 per matmul weight and
    token (layers and the vocabulary head), and attention's score and value
    products three times over (forward, and twice in the backward)."""
    D, _, _, _, _, V, L = _dims(m)
    per_token = 6 * (L * matmul_params_per_layer(m) + D * V)
    return batch * (T * per_token + 3 * L * attention_fwd_flops(m, T))


def kv_bytes_per_token(m: dict, itemsize: int = 2) -> int:
    """Keys and values of one token over all layers."""
    _, _, KV, Dh, _, _, L = _dims(m)
    return 2 * L * KV * Dh * itemsize


def weight_bytes(m: dict, itemsize: int = 2) -> int:
    return n_params(m) * itemsize


def decode_step_bytes(m: dict, context_tokens: int, itemsize: int = 2) -> int:
    """The least one decode step must read: every matmul weight once (the
    embedding table is indexed, not read; the head is), and the cached keys
    and values of the tokens in context over all active rows."""
    D, _, _, _, _, V, L = _dims(m)
    weights = (L * matmul_params_per_layer(m) + D * V) * itemsize
    return weights + context_tokens * kv_bytes_per_token(m, itemsize)
