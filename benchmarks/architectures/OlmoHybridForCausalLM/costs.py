"""Operations and bytes the algorithm needs, from shapes alone.

``m`` is the configuration as published (``olmo_hybrid``): hidden_size,
num_attention_heads, num_key_value_heads, intermediate_size, vocab_size,
layer_types, num_hidden_layers (as cut), and the linear layers' own:
linear_num_key_heads / linear_num_value_heads, linear_key_head_dim,
linear_value_head_dim, linear_conv_kernel_dim. The two kernels' work
(``linear_state_*``, ``linear_scan_*``) is what the mathematics needs, whatever
implements it: a step reads and writes every slot's state once, a chunk's scan
does seven multiply-adds a state element a token.
"""

from __future__ import annotations

LINEAR, FULL = "linear_attention", "full_attention"
STATE_ITEMSIZE = 4  # the state is float32 whatever the weights are served in


def head_dim(m: dict) -> int:
    return m.get("head_dim") or m["hidden_size"] // m["num_attention_heads"]


def kind_layers(m: dict, kind: str) -> int:
    return m["layer_types"].count(kind)


def linear_channels(m: dict) -> int:
    """Columns of the query / key / value projection, which the convolution runs over."""
    return m["linear_num_value_heads"] * (2 * m["linear_key_head_dim"] + m["linear_value_head_dim"])


def mlp_params(m: dict) -> int:
    return 3 * m["hidden_size"] * m["intermediate_size"]


def linear_mixer_matmul_params(m: dict) -> int:
    """q, k, v and the output gate in, the output projection, the two gates' [D, H]."""
    D, H, dv = m["hidden_size"], m["linear_num_value_heads"], m["linear_value_head_dim"]
    return D * linear_channels(m) + 2 * D * H * dv + 2 * D * H


def linear_layer_params(m: dict) -> int:
    D, H = m["hidden_size"], m["linear_num_value_heads"]
    small = m["linear_conv_kernel_dim"] * linear_channels(m) + 2 * H + m["linear_value_head_dim"] + 2 * D
    return linear_mixer_matmul_params(m) + mlp_params(m) + small  # filters, A_log and dt_bias, the [dv] norm, two branch norms


def full_mixer_matmul_params(m: dict) -> int:
    D, H, KV, Dh = m["hidden_size"], m["num_attention_heads"], m["num_key_value_heads"], head_dim(m)
    return 2 * D * H * Dh + 2 * D * KV * Dh


def full_layer_params(m: dict) -> int:
    D, H, KV, Dh = m["hidden_size"], m["num_attention_heads"], m["num_key_value_heads"], head_dim(m)
    return full_mixer_matmul_params(m) + mlp_params(m) + (H + KV) * Dh + 2 * D  # q and k norms, two branch norms


def n_params(m: dict) -> int:
    D, V = m["hidden_size"], m["vocab_size"]
    return (kind_layers(m, LINEAR) * linear_layer_params(m) + kind_layers(m, FULL) * full_layer_params(m)
            + 2 * V * D + D)


def weight_bytes(m: dict, itemsize: int = 2) -> int:
    """As served: A_log and dt_bias stay float32."""
    return n_params(m) * itemsize + kind_layers(m, LINEAR) * 2 * m["linear_num_value_heads"] * (4 - itemsize)


def kv_bytes_per_token(m: dict, itemsize: int = 2) -> int:
    """Keys and values of one token: the full layers hold them, a linear layer holds no token's rows."""
    return kind_layers(m, FULL) * 2 * m["num_key_value_heads"] * head_dim(m) * itemsize


def state_bytes_per_slot(m: dict, itemsize: int = 2) -> int:
    """What a slot keeps in the linear layers whatever its context: the float32
    state a head and the last ``kernel - 1`` rows ahead of the convolution."""
    H, dk, dv = m["linear_num_value_heads"], m["linear_key_head_dim"], m["linear_value_head_dim"]
    a_layer = H * dk * dv * STATE_ITEMSIZE + (m["linear_conv_kernel_dim"] - 1) * linear_channels(m) * itemsize
    return kind_layers(m, LINEAR) * a_layer


def linear_state_bytes(m: dict, rows: float, itemsize: int = 2) -> float:
    """The least the state's step of ONE decode step must move: each running
    row's state and carried rows read once and written once, every linear layer."""
    return rows * 2 * state_bytes_per_slot(m, itemsize)


def linear_scan_flops(m: dict, tokens: int) -> int:
    """The recurrence over ``tokens`` tokens of a prefill chunk, every linear
    layer: a state element a token is decayed (1), enters ``S^T k`` (2), takes
    the rank-1 update (2) and enters ``S^T q`` (2)."""
    H, dk, dv = m["linear_num_value_heads"], m["linear_key_head_dim"], m["linear_value_head_dim"]
    return tokens * kind_layers(m, LINEAR) * H * 7 * dk * dv


def linear_scan_bytes(m: dict, tokens: int, itemsize: int = 2) -> int:
    """What that scan must move: q, k, v ahead of their convolutions, g and beta
    in, o out (the activations' width), the state once in and once out."""
    H, dv = m["linear_num_value_heads"], m["linear_value_head_dim"]
    a_token = (linear_channels(m) + H * dv) * itemsize + 2 * H * 4
    return kind_layers(m, LINEAR) * tokens * a_token + 2 * state_bytes_per_slot(m, itemsize)


def decode_step_bytes(m: dict, context_tokens: int, itemsize: int = 2) -> int:
    """The least one decode step must move: every matrix once (the embedding
    table is indexed, not read), the keys and values of the tokens in context
    in the full layers, and the state of every slot of the deployment read and
    written (``context_tokens`` is spread over them; a step of fewer rows moves
    less state, and ``linear_state_roofline`` counts the rows a step had)."""
    D, V = m["hidden_size"], m["vocab_size"]
    slots = m["deployment"]["engine"]["num_slots"]
    matrices = (kind_layers(m, LINEAR) * (linear_mixer_matmul_params(m) + mlp_params(m))
                + kind_layers(m, FULL) * (full_mixer_matmul_params(m) + mlp_params(m)) + D * V)
    return int(matrices * itemsize + context_tokens * kv_bytes_per_token(m, itemsize)
               + linear_state_bytes(m, slots, itemsize))


def cache_attention_bytes(m: dict, context_tokens: float, window_tokens: float = 0.0, itemsize: int = 2) -> float:
    """The least the attention of one decode step must read of the cache: the
    keys and values, in the full layers, of every token the running rows hold
    (``context_tokens``, summed over the rows). There is no window layer;
    ``window_tokens`` is what the reader shared with a pattern that has them
    passes, and is not read."""
    return context_tokens * kv_bytes_per_token(m, itemsize)
