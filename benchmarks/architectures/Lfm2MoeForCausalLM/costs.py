"""Operations and bytes the algorithm needs, from shapes alone.

``m`` is the configuration as published (``lfm2_moe``) and as cut: hidden_size,
layer_types (``conv``, ``full_attention``), conv_L_cache, num_attention_heads,
num_key_value_heads (a head is hidden_size / num_attention_heads wide where no head_dim says otherwise),
intermediate_size (the leading dense layers), num_dense_layers,
moe_intermediate_size, num_experts, num_experts_per_tok, vocab_size. The
embeddings are tied: the head reads the table a decode step indexes.
"""

from __future__ import annotations

CONV, ATTENTION = "conv", "full_attention"


def kind_layers(m: dict, kind: str) -> int:
    return m["layer_types"].count(kind)


def expert_layers(m: dict) -> int:
    return m["num_hidden_layers"] - m["num_dense_layers"]


def head_dim(m: dict) -> int:
    return m.get("head_dim") or m["hidden_size"] // m["num_attention_heads"]


def conv_matmul_params(m: dict) -> int:
    """The input projection [B | C | u] and the output projection."""
    D = m["hidden_size"]
    return D * 3 * D + D * D


def conv_mixer_params(m: dict) -> int:
    return conv_matmul_params(m) + m["conv_L_cache"] * m["hidden_size"]  # and a filter a channel


def attention_matmul_params(m: dict) -> int:
    D, H, KV, Dh = m["hidden_size"], m["num_attention_heads"], m["num_key_value_heads"], head_dim(m)
    return 2 * D * H * Dh + 2 * D * KV * Dh


def attention_mixer_params(m: dict) -> int:
    return attention_matmul_params(m) + 2 * head_dim(m)  # and the norms of q and k over a head


def expert_params(m: dict) -> int:
    """One routed expert: gate, up and down."""
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def expert_block_params(m: dict) -> int:
    """The experts, the router and its bias."""
    return m["num_experts"] * (expert_params(m) + m["hidden_size"] + 1)


def dense_mlp_params(m: dict) -> int:
    return 3 * m["hidden_size"] * m["intermediate_size"]


def n_params(m: dict) -> int:
    """Every layer its mixer, its MLP and two norms; the embedding (tied) and the final norm."""
    D, V = m["hidden_size"], m["vocab_size"]
    mixers = kind_layers(m, CONV) * conv_mixer_params(m) + kind_layers(m, ATTENTION) * attention_mixer_params(m)
    mlps = m["num_dense_layers"] * dense_mlp_params(m) + expert_layers(m) * expert_block_params(m)
    return mixers + mlps + m["num_hidden_layers"] * 2 * D + V * D + D


def weight_bytes(m: dict, itemsize: int = 2) -> int:
    """As served: the router (weights and bias) stays float32."""
    router = expert_layers(m) * m["num_experts"] * (m["hidden_size"] + 1)
    return n_params(m) * itemsize + router * (4 - itemsize)


def kv_bytes_per_token(m: dict, itemsize: int = 2) -> int:
    """Keys and values of one token: the attention layers hold them, a conv layer holds no token's rows."""
    return kind_layers(m, ATTENTION) * 2 * m["num_key_value_heads"] * head_dim(m) * itemsize


def state_bytes_per_slot(m: dict, itemsize: int = 2) -> int:
    """What a slot keeps in the conv layers whatever its context: the last
    ``conv_L_cache - 1`` rows of ``B * u`` ahead of each filter."""
    return kind_layers(m, CONV) * (m["conv_L_cache"] - 1) * m["hidden_size"] * itemsize


def short_conv_step_bytes(m: dict, rows: float, itemsize: int = 2) -> float:
    """The least the conv mixers of ONE decode step must move, all conv layers:
    the two projections and the filter read once, and each running row's
    carried rows read once and written once."""
    weights = kind_layers(m, CONV) * conv_mixer_params(m) * itemsize
    return weights + rows * 2 * state_bytes_per_slot(m, itemsize)


def expected_experts_touched(m: dict, rows: float) -> float:
    """Distinct experts of one layer that ``rows`` tokens reach under uniform routing."""
    E, k = m["num_experts"], m["num_experts_per_tok"]
    return E * (1.0 - (1.0 - k / E) ** rows)


def moe_experts_bytes(m: dict, touched: float, itemsize: int = 2) -> float:
    """The least the routed experts of ONE decode step must read: the three
    matrices of each expert a layer touched, summed over the expert layers
    (``touched``: the mean number a layer)."""
    return expert_layers(m) * touched * expert_params(m) * itemsize


def moe_steps_alone(m: dict, traced: bool) -> dict:
    """The decode steps a run makes with ONE row before its traffic starts, and
    what each adds to the expert counters of every layer: the check's (each
    prompt is sent twice and served once more for the reference, but that is
    after the counters are read; a request's first token comes from its
    prompt's last chunk, every other from a step) and a traced run's probes of
    two tokens. The counters run from the replica's start and the harness reads
    them once, so a reader takes these out."""
    check = m["check"]
    steps = 2 * len(check["prompt_lens"]) * (check["new_tokens"] - 1)
    if traced:
        steps += 2 * int(check.get("probe_pairs", 5))
    return {"steps": steps, "experts_touched": m["num_experts_per_tok"], "fullest_expert_load": 1}


def cache_attention_bytes(m: dict, context_tokens: float, window_tokens: float = 0.0, itemsize: int = 2) -> float:
    """The least the attention of one decode step must read of the cache: the
    keys and values, in the attention layers, of every token the running rows
    hold (``context_tokens``, summed over the rows). There is no window layer;
    ``window_tokens`` is what the reader shared with a pattern that has them
    passes, and is not read."""
    return context_tokens * kv_bytes_per_token(m, itemsize)


def decode_step_bytes(m: dict, context_tokens: int, itemsize: int = 2) -> int:
    """The least one decode step must move: every matrix all tokens share once
    (the conv and attention mixers, the leading dense MLP, the router in
    float32, the tied head), the experts a step is expected to touch with every
    slot of the deployment full (uniform routing), the keys and values of the
    tokens in context, and the carried rows of every slot read and written."""
    D, V = m["hidden_size"], m["vocab_size"]
    slots = m["deployment"]["engine"]["num_slots"]
    router = expert_layers(m) * D * m["num_experts"]
    shared = (kind_layers(m, CONV) * conv_mixer_params(m) + kind_layers(m, ATTENTION) * attention_matmul_params(m)
              + m["num_dense_layers"] * dense_mlp_params(m) + D * V) * itemsize + router * 4
    return int(shared + moe_experts_bytes(m, expected_experts_touched(m, slots), itemsize)
               + cache_attention_bytes(m, context_tokens, 0.0, itemsize) + slots * 2 * state_bytes_per_slot(m, itemsize))
