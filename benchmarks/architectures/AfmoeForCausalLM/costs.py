"""Operations and bytes the algorithm needs, from shapes alone.

``m`` is the configuration as published (``afmoe``): hidden_size,
num_attention_heads, num_key_value_heads, head_dim, intermediate_size (the
leading dense layers), moe_intermediate_size, num_experts, num_experts_per_tok,
num_shared_experts, num_dense_layers, layer_types, sliding_window, vocab_size,
num_hidden_layers (as cut). The gate on the attention output, a projection as
large as the queries', is in no key and is counted: it is in the layer.
"""

from __future__ import annotations

WINDOW, FULL = "sliding_attention", "full_attention"


def attention_params(m: dict) -> int:
    """Queries and their gate, keys, values, output."""
    D, H, KV, Dh = m["hidden_size"], m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    return 2 * D * H * Dh + 2 * D * KV * Dh + H * Dh * D


def expert_params(m: dict) -> int:
    """One routed expert: gate, up and down."""
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def expert_layers(m: dict) -> int:
    return m["num_hidden_layers"] - m["num_dense_layers"]


def kind_layers(m: dict, kind: str) -> int:
    return m["layer_types"].count(kind)


def dense_layer_matmul_params(m: dict) -> int:
    return attention_params(m) + 3 * m["hidden_size"] * m["intermediate_size"]


def expert_layer_shared_matmul_params(m: dict) -> int:
    """What every token reads of an expert layer: attention, shared experts, router."""
    return (attention_params(m) + m["num_shared_experts"] * expert_params(m)
            + m["hidden_size"] * m["num_experts"])


def n_params(m: dict) -> int:
    D, V = m["hidden_size"], m["vocab_size"]
    norms = 4 * D + 2 * m["head_dim"]  # before and after each branch; queries' and keys' over a head
    dense = m["num_dense_layers"] * (dense_layer_matmul_params(m) + norms)
    sparse = expert_layers(m) * (
        expert_layer_shared_matmul_params(m) + m["num_experts"] * (expert_params(m) + 1) + norms
    )
    return dense + sparse + 2 * V * D + D


def weight_bytes(m: dict, itemsize: int = 2) -> int:
    """As served: the router (weights and bias) stays float32."""
    router = expert_layers(m) * m["num_experts"] * (m["hidden_size"] + 1)
    return n_params(m) * itemsize + router * (4 - itemsize)


def kv_bytes_per_token(m: dict, kind: str | None = None, itemsize: int = 2) -> int:
    """Keys and values of one token over the layers of one kind (``layer_types``'
    names), or over all layers. What a token HOLDS in the cache is this only in
    the full layers: a window layer keeps a ring a request, however long it is."""
    layers = m["num_hidden_layers"] if kind is None else kind_layers(m, kind)
    return layers * 2 * m["num_key_value_heads"] * m["head_dim"] * itemsize


def expected_experts_touched(m: dict, rows: int) -> float:
    """Distinct experts of one layer that ``rows`` tokens reach under uniform routing."""
    E, k = m["num_experts"], m["num_experts_per_tok"]
    return E * (1.0 - (1.0 - k / E) ** rows)


def moe_experts_bytes(m: dict, touched: float, itemsize: int = 2) -> float:
    """The least the routed experts of ONE decode step must read: the three
    matrices of each expert a layer touched, summed over the expert layers
    (``touched``: the mean number a layer). A decode step is bound by these
    bytes: 2 x rows x k / touched operations a weight, a handful."""
    return expert_layers(m) * touched * expert_params(m) * itemsize


def moe_steps_alone(m: dict, traced: bool) -> dict:
    """The decode steps a run makes with ONE row before its traffic starts, and
    what each adds to the expert counters of every layer: the check's (each
    prompt is sent twice; a request's first token comes from its prompt's last
    chunk, every other from a step) and a traced run's probes of two tokens.
    The counters run from the replica's start and the harness reads them once,
    so a reader takes these out."""
    check = m["check"]
    steps = 2 * len(check["prompt_lens"]) * (check["new_tokens"] - 1)
    if traced:
        steps += 2 * int(check.get("probe_pairs", 5))
    return {"steps": steps, "experts_touched": m["num_experts_per_tok"], "fullest_expert_load": 1}


def cache_attention_bytes(m: dict, context_tokens: float, window_tokens: float, itemsize: int = 2) -> float:
    """The least the attention of one decode step must read of the cache: in a
    full layer the keys and values of every token the running rows hold
    (``context_tokens``, summed over the rows), in a window layer those of the
    tokens inside each row's window (``window_tokens``: the sum over the rows of
    min(context, sliding_window))."""
    return (context_tokens * kv_bytes_per_token(m, FULL, itemsize)
            + window_tokens * kv_bytes_per_token(m, WINDOW, itemsize))


def decode_step_bytes(m: dict, context_tokens: int, itemsize: int = 2) -> int:
    """The least one decode step must read: every weight all tokens share once
    (attention, dense layer, shared experts, router, head; the embedding table
    is indexed, not read), the experts a step is expected to touch with every
    slot of the deployment full (uniform routing), and the cache of the tokens
    in context: ``context_tokens`` spread evenly over the deployment's slots,
    of which a window layer reads no more than its window a row."""
    D, V = m["hidden_size"], m["vocab_size"]
    slots = m["deployment"]["engine"]["num_slots"]
    shared = (m["num_dense_layers"] * dense_layer_matmul_params(m)
              + expert_layers(m) * expert_layer_shared_matmul_params(m) + D * V) * itemsize
    touched = expected_experts_touched(m, slots)
    window_tokens = slots * min(context_tokens / slots, m["sliding_window"])
    return int(shared + moe_experts_bytes(m, touched, itemsize)
               + cache_attention_bytes(m, context_tokens, window_tokens, itemsize))
