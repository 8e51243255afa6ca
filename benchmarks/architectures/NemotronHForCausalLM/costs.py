"""Operations and bytes the algorithm needs, from shapes alone.

``m`` is the configuration as published (``nemotron_h``) and as cut:
hidden_size, hybrid_override_pattern (``M`` Mamba-2, ``E`` experts, ``*``
attention), mamba_num_heads, mamba_head_dim, ssm_state_size, n_groups,
conv_kernel, num_attention_heads, num_key_value_heads, head_dim,
n_routed_experts (the experts HELD on this chip), num_experts_per_tok,
moe_intermediate_size, moe_shared_expert_intermediate_size, n_shared_experts,
vocab_size (the slice held), and ``published.n_routed_experts`` (the router's
width). An expert is two matrices (``relu2``: no gate matrix). The two kernels'
work (``linear_state_*``, ``linear_scan_*``: the readers' names are a linear
recurrence's; this one is Mamba-2's) is what the mathematics needs, whatever
implements it: a step reads and writes every running row's state once, a
chunk's scan does five operations a state element a token.
"""

from __future__ import annotations

MAMBA, EXPERTS, ATTENTION = "M", "E", "*"
STATE_ITEMSIZE = 4  # the state is float32 whatever the weights are served in


def kind_layers(m: dict, kind: str) -> int:
    return m["hybrid_override_pattern"].count(kind)


def mamba_inner(m: dict) -> int:
    return m["mamba_num_heads"] * m["mamba_head_dim"]


def conv_channels(m: dict) -> int:
    """x, B and C: what the convolution runs over and a slot carries rows of."""
    return mamba_inner(m) + 2 * m["n_groups"] * m["ssm_state_size"]


def mamba_matmul_params(m: dict) -> int:
    """The input projection (gate, convolution channels, time step) and the output projection."""
    D = m["hidden_size"]
    return D * (mamba_inner(m) + conv_channels(m) + m["mamba_num_heads"]) + mamba_inner(m) * D


def mamba_block_params(m: dict) -> int:
    small = (m["conv_kernel"] + 1) * conv_channels(m) + 3 * m["mamba_num_heads"] + mamba_inner(m) + m["hidden_size"]
    return mamba_matmul_params(m) + small  # filters and bias; A_log, dt_bias, D; the gated norm's weight; the block's norm


def attention_matmul_params(m: dict) -> int:
    D, H, KV, Dh = m["hidden_size"], m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    return 2 * D * H * Dh + 2 * D * KV * Dh


def expert_params(m: dict) -> int:
    """One routed expert: up and down."""
    return 2 * m["hidden_size"] * m["moe_intermediate_size"]


def router_width(m: dict) -> int:
    return m["published"]["n_routed_experts"]


def experts_shared_matmul_params(m: dict) -> int:
    """What every token reads of an experts block: the shared expert and the router."""
    D = m["hidden_size"]
    return 2 * D * m["n_shared_experts"] * m["moe_shared_expert_intermediate_size"] + D * router_width(m)


def experts_block_params(m: dict) -> int:
    return (experts_shared_matmul_params(m) + router_width(m) + m["n_routed_experts"] * expert_params(m)
            + m["hidden_size"])


def n_params(m: dict) -> int:
    D, V = m["hidden_size"], m["vocab_size"]
    return (kind_layers(m, MAMBA) * mamba_block_params(m) + kind_layers(m, EXPERTS) * experts_block_params(m)
            + kind_layers(m, ATTENTION) * (attention_matmul_params(m) + D) + 2 * V * D + D)


def weight_bytes(m: dict, itemsize: int = 2) -> int:
    """As served: the router (weights and bias) and A_log, dt_bias, D stay float32."""
    f32 = (kind_layers(m, EXPERTS) * router_width(m) * (m["hidden_size"] + 1)
           + kind_layers(m, MAMBA) * 3 * m["mamba_num_heads"])
    return n_params(m) * itemsize + f32 * (4 - itemsize)


def kv_bytes_per_token(m: dict, itemsize: int = 2) -> int:
    """Keys and values of one token: the attention blocks hold them, no other block holds a token's rows."""
    return kind_layers(m, ATTENTION) * 2 * m["num_key_value_heads"] * m["head_dim"] * itemsize


def state_bytes_per_slot(m: dict, itemsize: int = 2) -> int:
    """What a slot keeps in the Mamba blocks whatever its context: the float32
    state a head and the last ``kernel - 1`` rows ahead of the convolution."""
    a_block = (mamba_inner(m) * m["ssm_state_size"] * STATE_ITEMSIZE
               + (m["conv_kernel"] - 1) * conv_channels(m) * itemsize)
    return kind_layers(m, MAMBA) * a_block


def linear_state_bytes(m: dict, rows: float, itemsize: int = 2) -> float:
    """The least the state's step of ONE decode step must move: each running
    row's state and carried rows read once and written once, every Mamba block."""
    return rows * 2 * state_bytes_per_slot(m, itemsize)


def linear_scan_flops(m: dict, tokens: int) -> int:
    """The recurrence over ``tokens`` tokens of a prefill chunk, every Mamba
    block: a state element a token is decayed (1), takes ``dt x B`` (2) and
    enters ``S C`` (2)."""
    return tokens * kind_layers(m, MAMBA) * 5 * mamba_inner(m) * m["ssm_state_size"]


def linear_scan_bytes(m: dict, tokens: int, itemsize: int = 2) -> int:
    """What that scan must move: x, B, C ahead of the convolution and the time
    step in, y out (the activations' width), the state once in and once out."""
    a_token = (conv_channels(m) + mamba_inner(m)) * itemsize + m["mamba_num_heads"] * 4
    return kind_layers(m, MAMBA) * tokens * a_token + 2 * state_bytes_per_slot(m, itemsize)


def expected_experts_touched(m: dict, rows: float) -> float:
    """Distinct HELD experts of one block that ``rows`` tokens reach under uniform routing over all experts."""
    return m["n_routed_experts"] * (1.0 - (1.0 - m["num_experts_per_tok"] / router_width(m)) ** rows)


def moe_experts_bytes(m: dict, touched: float, itemsize: int = 2) -> float:
    """The least the routed experts of ONE decode step must read: the two
    matrices of each held expert a block touched, summed over the experts
    blocks (``touched``: the mean number a block). A decode step is bound by
    these bytes: a weight byte meets a handful of tokens."""
    return kind_layers(m, EXPERTS) * touched * expert_params(m) * itemsize


def moe_steps_alone(m: dict, traced: bool) -> dict:
    """The decode steps a run makes with ONE row before its traffic starts, and
    what each adds to the expert counters of every block: the check's (each
    prompt is sent twice; a request's first token comes from its prompt's last
    chunk, every other from a step) and a traced run's probes of two tokens.
    The counters run from the replica's start and the harness reads them once,
    so a reader takes these out. The counters count the experts HELD: of a lone
    token's ``num_experts_per_tok`` experts the held share is touched on
    average (3 of 6 where half are held), not each time, and the fullest held
    expert has one token unless none of the six is held (1 in 64): expected
    values under uniform routing, where the other architectures' are exact."""
    check = m["check"]
    steps = 2 * len(check["prompt_lens"]) * (check["new_tokens"] - 1)
    if traced:
        steps += 2 * int(check.get("probe_pairs", 5))
    share = m["n_routed_experts"] / router_width(m)
    k = m["num_experts_per_tok"]
    return {"steps": steps, "experts_touched": k * share, "fullest_expert_load": 1.0 - (1.0 - share) ** k}


def cache_attention_bytes(m: dict, context_tokens: float, window_tokens: float = 0.0, itemsize: int = 2) -> float:
    """The least the attention of one decode step must read of the cache: the
    keys and values, in the attention blocks, of every token the running rows
    hold (``context_tokens``, summed over the rows). There is no window layer;
    ``window_tokens`` is what the reader shared with a pattern that has them
    passes, and is not read."""
    return context_tokens * kv_bytes_per_token(m, itemsize)


def decode_step_bytes(m: dict, context_tokens: int, itemsize: int = 2) -> int:
    """The least one decode step must move: every matrix all tokens share once
    (the Mamba and attention projections, the shared experts, the router in
    float32, the head; the embedding table is indexed, not read), the held
    experts a step is expected to touch with every slot of the deployment
    full (uniform routing), the keys and values of the tokens in context, and
    the state of every slot read and written."""
    D, V = m["hidden_size"], m["vocab_size"]
    slots = m["deployment"]["engine"]["num_slots"]
    router = kind_layers(m, EXPERTS) * D * router_width(m)
    shared = (kind_layers(m, MAMBA) * mamba_matmul_params(m) + kind_layers(m, ATTENTION) * attention_matmul_params(m)
              + kind_layers(m, EXPERTS) * experts_shared_matmul_params(m) + D * V) * itemsize + router * (4 - itemsize)
    return int(shared + moe_experts_bytes(m, expected_experts_touched(m, slots), itemsize)
               + cache_attention_bytes(m, context_tokens, 0.0, itemsize) + linear_state_bytes(m, slots, itemsize))
