"""Operations and bytes the algorithm needs, from shapes alone.

``m`` is the configuration as published (``glm4_moe_lite``): hidden_size,
num_attention_heads, q_lora_rank, kv_lora_rank, qk_nope_head_dim,
qk_rope_head_dim, v_head_dim, intermediate_size (the leading dense layers),
moe_intermediate_size, n_routed_experts, num_experts_per_tok,
n_shared_experts, first_k_dense_replace, vocab_size, num_hidden_layers (as
cut). Nothing of the multi-token-prediction layer is counted: it is not run.
"""

from __future__ import annotations


def attention_params(m: dict) -> int:
    D, H = m["hidden_size"], m["num_attention_heads"]
    Rq, R, N, P, Vd = (m["q_lora_rank"], m["kv_lora_rank"], m["qk_nope_head_dim"],
                       m["qk_rope_head_dim"], m["v_head_dim"])
    return D * Rq + Rq * H * (N + P) + D * (R + P) + R * H * (N + Vd) + H * Vd * D


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
    norms = 2 * D + m["q_lora_rank"] + m["kv_lora_rank"]
    dense = m["first_k_dense_replace"] * (dense_layer_matmul_params(m) + norms)
    sparse = expert_layers(m) * (
        expert_layer_shared_matmul_params(m) + m["n_routed_experts"] * (expert_params(m) + 1) + norms
    )
    return dense + sparse + 2 * V * D + D


def weight_bytes(m: dict, itemsize: int = 2) -> int:
    """As served: the router (weights and bias) stays float32."""
    router = expert_layers(m) * m["n_routed_experts"] * (m["hidden_size"] + 1)
    return n_params(m) * itemsize + router * (4 - itemsize)


def kv_bytes_per_token(m: dict, itemsize: int = 2) -> int:
    """The latent and the one rotary key of one token over all layers."""
    return m["num_hidden_layers"] * (m["kv_lora_rank"] + m["qk_rope_head_dim"]) * itemsize


def expected_experts_touched(m: dict, rows: int) -> float:
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
    so a reader takes these out (128 new tokens: 762 such steps among ~3500)."""
    check = m["check"]
    steps = 2 * len(check["prompt_lens"]) * (check["new_tokens"] - 1)
    if traced:
        steps += 2 * int(check.get("probe_pairs", 5))
    return {"steps": steps, "experts_touched": m["num_experts_per_tok"], "fullest_expert_load": 1}


def latent_attention_bytes(m: dict, context_tokens: float, itemsize: int = 2) -> float:
    """The least the attention of one decode step must read of the cache: every
    context token's latent and rotary key, once a layer."""
    return context_tokens * kv_bytes_per_token(m, itemsize)


def decode_step_bytes(m: dict, context_tokens: int, itemsize: int = 2) -> int:
    """The least one decode step must read: every weight all tokens share once
    (attention, dense layer, shared experts, router, head; the embedding table
    is indexed, not read), the experts a step is expected to touch with every
    slot of the deployment full (uniform routing), and the cache of the tokens
    in context."""
    D, V = m["hidden_size"], m["vocab_size"]
    shared = (m["first_k_dense_replace"] * dense_layer_matmul_params(m)
              + expert_layers(m) * expert_layer_shared_matmul_params(m) + D * V) * itemsize
    touched = expected_experts_touched(m, m["deployment"]["engine"]["num_slots"])
    return int(shared + moe_experts_bytes(m, touched, itemsize)
               + latent_attention_bytes(m, context_tokens, itemsize))
