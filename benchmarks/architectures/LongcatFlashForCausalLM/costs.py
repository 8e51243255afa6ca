"""Operations and bytes the algorithm needs, from shapes alone.

``m`` is the configuration as published (``longcat_flash``) and as cut:
hidden_size, num_attention_heads, q_lora_rank, kv_lora_rank, qk_nope_head_dim,
qk_rope_head_dim, v_head_dim, ffn_hidden_size (each of a layer's TWO dense
FFNs), expert_ffn_hidden_size, n_routed_experts (the experts HELD on this
chip), ``published.n_routed_experts`` (the experts the router chooses among),
zero_expert_num (the identity experts behind them in the router), moe_topk,
vocab_size (the slice held), num_layers (DOUBLE layers, as cut: each is two
attention sub-layers, two FFNs and one branch of experts). An identity pick
reads no weight and is counted nowhere below; the experts counted are those
HELD that a step TOUCHES. Nothing of the Omni towers is counted: none is run.
"""

from __future__ import annotations

SUBLAYERS = 2


def attention_params(m: dict) -> int:
    """One attention sub-layer's matrices."""
    D, H = m["hidden_size"], m["num_attention_heads"]
    Rq, R, N, P, Vd = (m["q_lora_rank"], m["kv_lora_rank"], m["qk_nope_head_dim"],
                       m["qk_rope_head_dim"], m["v_head_dim"])
    return D * Rq + Rq * H * (N + P) + D * (R + P) + R * H * (N + Vd) + H * Vd * D


def ffn_params(m: dict) -> int:
    """One dense FFN: gate, up and down."""
    return 3 * m["hidden_size"] * m["ffn_hidden_size"]


def expert_params(m: dict) -> int:
    """One routed expert: gate, up and down."""
    return 3 * m["hidden_size"] * m["expert_ffn_hidden_size"]


def experts_routed_among(m: dict) -> int:
    return m["published"]["n_routed_experts"]


def router_width(m: dict) -> int:
    """The experts, held anywhere, and the identity experts behind them."""
    return experts_routed_among(m) + m["zero_expert_num"]


def layer_shared_matmul_params(m: dict) -> int:
    """What every token reads of a double layer: both attentions, both FFNs, the router."""
    return SUBLAYERS * (attention_params(m) + ffn_params(m)) + m["hidden_size"] * router_width(m)


def n_params(m: dict) -> int:
    """As cut: the held experts, the held slice of the vocabulary."""
    D, V = m["hidden_size"], m["vocab_size"]
    norms = SUBLAYERS * (2 * D + m["q_lora_rank"] + m["kv_lora_rank"])
    layer = layer_shared_matmul_params(m) + router_width(m) + m["n_routed_experts"] * expert_params(m) + norms
    return m["num_layers"] * layer + 2 * V * D + D


def weight_bytes(m: dict, itemsize: int = 2) -> int:
    """As served: the router (weights and bias) stays float32."""
    router = m["num_layers"] * router_width(m) * (m["hidden_size"] + 1)
    return n_params(m) * itemsize + router * (4 - itemsize)


def kv_bytes_per_token(m: dict, itemsize: int = 2) -> int:
    """The latent and the one rotary key of one token over all the attention
    sub-layers, two a layer (the pool pads a row's 576 values to 640, the TPU's
    lanes: ``engine.stats()['kv_token_bytes']`` reads that)."""
    return SUBLAYERS * m["num_layers"] * (m["kv_lora_rank"] + m["qk_rope_head_dim"]) * itemsize


def expected_experts_touched(m: dict, rows: float) -> float:
    """Distinct HELD experts of one layer that ``rows`` tokens reach under
    uniform routing over all the router's columns, identities included."""
    return m["n_routed_experts"] * (1.0 - (1.0 - m["moe_topk"] / router_width(m)) ** rows)


def moe_experts_bytes(m: dict, touched: float, itemsize: int = 2) -> float:
    """The least the routed experts of ONE decode step must read: the three
    matrices of each held expert a layer touched, summed over the layers
    (``touched``: the mean number a layer). A decode step is bound by these
    bytes: a weight byte meets a handful of tokens. An identity pick reads
    none."""
    return m["num_layers"] * touched * expert_params(m) * itemsize


def moe_steps_alone(m: dict, traced: bool) -> dict:
    """The decode steps a run makes with ONE row before its traffic starts, and
    what each adds to the expert counters of every layer: the check's (each
    prompt is sent twice; a request's first token comes from its prompt's last
    chunk, every other from a step) and a traced run's probes of two tokens.
    The counters run from the replica's start and the harness reads them once,
    so a reader takes these out. The counters count the experts HELD: of a lone
    token's ``moe_topk`` picks the held share of the router's columns is
    touched on average (12 x 16 / 768 = a quarter of an expert), and the
    fullest held expert has one token where any pick is held: expected values
    under uniform routing."""
    check = m["check"]
    steps = 2 * len(check["prompt_lens"]) * (check["new_tokens"] - 1)
    if traced:
        steps += 2 * int(check.get("probe_pairs", 5))
    share = m["n_routed_experts"] / router_width(m)
    k = m["moe_topk"]
    return {"steps": steps, "experts_touched": k * share, "fullest_expert_load": 1.0 - (1.0 - share) ** k}


def latent_attention_bytes(m: dict, context_tokens: float, itemsize: int = 2) -> float:
    """The least the attention of one decode step must read of the cache: every
    context token's latent and rotary key, once an attention sub-layer."""
    return context_tokens * kv_bytes_per_token(m, itemsize)


def latent_attention_flops(m: dict, context_tokens: float) -> float:
    """The absorbed form's products of one decode step over ``context_tokens``
    cached rows (summed over the running rows), every sub-layer: a head scores a
    row's latent and rotary key (R + P multiply-adds) and sums its latent (R)."""
    R, P = m["kv_lora_rank"], m["qk_rope_head_dim"]
    return SUBLAYERS * m["num_layers"] * context_tokens * m["num_attention_heads"] * 2 * (2 * R + P)


def latent_prefill_flops(m: dict, tokens: int, context_tokens: float) -> float:
    """The least operations the attention of one prefill chunk needs, every
    sub-layer: from the queries per head and the cached latents to the heads'
    outputs before ``W_o``, ``tokens`` queries behind ``context_tokens`` cached
    ones, causal. The cheaper of the two forms of the same function: absorbed
    (scores over the latent's R + P columns and sums over its R; ``q_nope``
    through W_uk, the sums through W_uv) and expanded (keys and values of every
    token seen through W_kvb; scores over N + P, sums over Vd columns)."""
    H, R = m["num_attention_heads"], m["kv_lora_rank"]
    N, P, Vd = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    pairs = tokens * context_tokens + tokens * (tokens + 1) / 2
    absorbed = 2 * H * (pairs * (2 * R + P) + tokens * R * (N + Vd))
    expanded = 2 * H * (pairs * (N + P + Vd) + (context_tokens + tokens) * R * (N + Vd))
    return SUBLAYERS * m["num_layers"] * min(absorbed, expanded)


def latent_prefill_bytes(m: dict, tokens: int, context_tokens: float, itemsize: int = 2) -> float:
    """The least bytes it must move, every sub-layer: the latents of every token
    seen, the chunk's queries in and its heads' outputs out, W_kvb once."""
    H, R = m["num_attention_heads"], m["kv_lora_rank"]
    N, P, Vd = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    a_sublayer = (context_tokens + tokens) * (R + P) + tokens * H * (N + P + Vd) + R * H * (N + Vd)
    return SUBLAYERS * m["num_layers"] * a_sublayer * itemsize


def decode_step_bytes(m: dict, context_tokens: int, itemsize: int = 2) -> int:
    """The least one decode step must read: every matrix all tokens share once
    (both attentions and both FFNs of every layer, the router in float32, the
    head; the embedding table is indexed, not read), the held experts a step is
    expected to touch with every slot of the deployment full (uniform routing
    over experts and identities), and the cache of the tokens in context."""
    D, V = m["hidden_size"], m["vocab_size"]
    router = m["num_layers"] * D * router_width(m)
    shared = (m["num_layers"] * layer_shared_matmul_params(m) + D * V) * itemsize + router * (4 - itemsize)
    touched = expected_experts_touched(m, m["deployment"]["engine"]["num_slots"])
    return int(shared + moe_experts_bytes(m, touched, itemsize) + latent_attention_bytes(m, context_tokens, itemsize))
