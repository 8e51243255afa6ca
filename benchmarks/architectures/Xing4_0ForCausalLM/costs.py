"""Operations and bytes the algorithm needs, from shapes alone.

``m`` is the configuration as published (``xing4_0``): hidden_size,
num_attention_heads, q_lora_rank, kv_lora_rank, qk_nope_head_dim,
qk_rope_head_dim, v_head_dim, intermediate_size (the leading dense layers),
moe_intermediate_size, n_routed_experts, num_experts_per_tok,
n_shared_experts, first_k_dense_replace, vocab_size, hc_mult,
num_hidden_layers (as cut). Nothing of the multi-token-prediction layer is
counted: it is not run.
"""

from __future__ import annotations


def attention_params(m: dict) -> int:
    D, H = m["hidden_size"], m["num_attention_heads"]
    Rq, R, N, P, Vd = (m["q_lora_rank"], m["kv_lora_rank"], m["qk_nope_head_dim"],
                       m["qk_rope_head_dim"], m["v_head_dim"])
    return D * Rq + Rq * H * (N + P) + D * (R + P) + R * H * (N + Vd) + H * Vd * D


def hyper_connection_params(m: dict) -> int:
    """One sub-layer's ``phi`` [n D, 2n + n^2], ``b`` [2n + n^2] and ``alpha`` [3], float32."""
    n = m["hc_mult"]
    return (n * m["hidden_size"] + 1) * (2 * n + n * n) + 3


def expert_params(m: dict) -> int:
    """One routed expert: gate, up and down."""
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def expert_layers(m: dict) -> int:
    return m["num_hidden_layers"] - m["first_k_dense_replace"]


def dense_layer_matmul_params(m: dict) -> int:
    return attention_params(m) + 3 * m["hidden_size"] * m["intermediate_size"]


def expert_layer_shared_matmul_params(m: dict) -> int:
    """What every token reads of an expert layer: attention, shared experts, router."""
    return (attention_params(m) + m["n_shared_experts"] * expert_params(m)
            + m["hidden_size"] * m["n_routed_experts"])


def n_params(m: dict) -> int:
    D, V = m["hidden_size"], m["vocab_size"]
    per_layer = 2 * D + m["q_lora_rank"] + m["kv_lora_rank"] + 2 * hyper_connection_params(m)  # the four norms, two sub-layers
    dense = m["first_k_dense_replace"] * (dense_layer_matmul_params(m) + per_layer)
    sparse = expert_layers(m) * (
        expert_layer_shared_matmul_params(m) + m["n_routed_experts"] * (expert_params(m) + 1) + per_layer
    )
    return dense + sparse + 2 * V * D + D


def weight_bytes(m: dict, itemsize: int = 2) -> int:
    """As served: the router (weights and bias) and the hyper-connections stay float32."""
    router = expert_layers(m) * m["n_routed_experts"] * (m["hidden_size"] + 1)
    hyper = m["num_hidden_layers"] * 2 * hyper_connection_params(m)
    return n_params(m) * itemsize + (router + hyper) * (4 - itemsize)


def kv_bytes_per_token(m: dict, itemsize: int = 2) -> int:
    """The latent and the one rotary key of one token over all layers."""
    return m["num_hidden_layers"] * (m["kv_lora_rank"] + m["qk_rope_head_dim"]) * itemsize


def expected_experts_touched(m: dict, rows: float) -> float:
    """Distinct experts of one layer that ``rows`` tokens reach under uniform routing."""
    E, k = m["n_routed_experts"], m["num_experts_per_tok"]
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


def latent_attention_bytes(m: dict, context_tokens: float, itemsize: int = 2) -> float:
    """The least the attention of one decode step must read of the cache: every
    context token's latent and rotary key, once a layer."""
    return context_tokens * kv_bytes_per_token(m, itemsize)


def hyper_connection_bytes(m: dict, rows: int, itemsize: int = 2) -> int:
    """The least the hyper-connections of one pass over ``rows`` tokens must
    move: the stream [rows, n D] read and written once a sub-layer, its ``phi``
    (float32) read once; the 2n + n^2 coefficients a token stay on the chip."""
    n, D = m["hc_mult"], m["hidden_size"]
    sub_layers = 2 * m["num_hidden_layers"]
    return sub_layers * (2 * rows * n * D * itemsize + n * D * (2 * n + n * n) * 4)


def _chunk_pairs(tokens: int, context_tokens: float) -> float:
    """(query, key) pairs of a chunk of ``tokens`` queries behind ``context_tokens`` cached ones, causal."""
    return tokens * context_tokens + tokens * (tokens + 1) / 2


def latent_prefill_flops(m: dict, tokens: int, context_tokens: float) -> float:
    """The least operations the attention of one prefill chunk needs, all
    layers: from the queries per head and the cached latents to the heads'
    outputs before ``W_o``, over the context the chunk's row really holds.
    The cheaper of the two forms of the same function: absorbed (scores and
    sums over the latent's 512 + 64 and 512 columns; ``q_nope`` through W_uk,
    the sums through W_uv) and expanded (keys and values of every token seen
    through W_kvb; scores over 192, sums over 128 columns)."""
    H, R = m["num_attention_heads"], m["kv_lora_rank"]
    N, P, Vd = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    pairs = _chunk_pairs(tokens, context_tokens)
    absorbed = 2 * H * (pairs * (2 * R + P) + tokens * R * (N + Vd))
    expanded = 2 * H * (pairs * (N + P + Vd) + (context_tokens + tokens) * R * (N + Vd))
    return m["num_hidden_layers"] * min(absorbed, expanded)


def latent_prefill_bytes(m: dict, tokens: int, context_tokens: float, itemsize: int = 2) -> float:
    """The least bytes it must move, all layers: the latents of every token
    seen, the chunk's queries in and its heads' outputs out, W_kvb once."""
    H, R = m["num_attention_heads"], m["kv_lora_rank"]
    N, P, Vd = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    a_layer = (context_tokens + tokens) * (R + P) + tokens * H * (N + P + Vd) + R * H * (N + Vd)
    return m["num_hidden_layers"] * a_layer * itemsize


def decode_step_bytes(m: dict, context_tokens: int, itemsize: int = 2) -> int:
    """The least one decode step must read: every weight all tokens share once
    (attention, dense layer, shared experts, router, head; the embedding table
    is indexed, not read), the experts a step is expected to touch (uniform
    routing), the cache of the tokens in context, and the residual streams with
    their ``phi`` (``hyper_connection_bytes``; the program steps every slot's
    row, a token or not).

    ``context_tokens`` is what ``decode_mfu_roofline``'s reader hands over: every
    HELD slot at the mix's mean length. A held slot is not a row that steps:
    the one whose prompt the prefill lane is working through, and those that
    wait for the lane, hold their slots and take no part in the step. So the
    experts are counted at ``deployment.stepping_rows``, the rows a step really
    had (``decode_rows_mean``, measured: the configuration file says where), and
    the context in that share of the slots: bytes the step does not read are
    not counted (with all ``num_slots`` rows they read 13 % too many)."""
    D, V = m["hidden_size"], m["vocab_size"]
    slots, rows = m["deployment"]["engine"]["num_slots"], m["deployment"]["stepping_rows"]
    shared = (m["first_k_dense_replace"] * dense_layer_matmul_params(m)
              + expert_layers(m) * expert_layer_shared_matmul_params(m) + D * V) * itemsize
    touched = expected_experts_touched(m, rows)
    return int(shared + moe_experts_bytes(m, touched, itemsize)
               + latent_attention_bytes(m, context_tokens * rows / slots, itemsize) + hyper_connection_bytes(m, slots, itemsize))
