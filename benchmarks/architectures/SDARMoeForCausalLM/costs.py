"""Operations and bytes the algorithm needs, from shapes alone.

``m`` is the configuration as published (``sdar_moe``) and as cut: hidden_size,
num_attention_heads, num_key_value_heads, head_dim, moe_intermediate_size,
num_experts, num_experts_per_tok, vocab_size, num_hidden_layers; every layer is
attention and then routed experts, the head is untied. ``block_length`` is the
positions a pass feeds a row: a "decode step" here is one PASS over every
running row's block, whether it denoises or commits.
"""

from __future__ import annotations


def attention_matmul_params(m: dict) -> int:
    D, H, KV, Dh = m["hidden_size"], m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    return 2 * D * H * Dh + 2 * D * KV * Dh


def expert_params(m: dict) -> int:
    """One routed expert: gate, up and down."""
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def expert_layers(m: dict) -> int:
    return m["num_hidden_layers"]


def layer_params(m: dict) -> int:
    """Attention with the norms of q and k over a head, the router (no bias), the experts, two norms."""
    D = m["hidden_size"]
    return (attention_matmul_params(m) + 2 * m["head_dim"] + D * m["num_experts"]
            + m["num_experts"] * expert_params(m) + 2 * D)


def n_params(m: dict) -> int:
    """The layers, the embedding and the untied head, the final norm."""
    D, V = m["hidden_size"], m["vocab_size"]
    return m["num_hidden_layers"] * layer_params(m) + 2 * V * D + D


def weight_bytes(m: dict, itemsize: int = 2) -> int:
    """As served: the router stays float32."""
    router = expert_layers(m) * m["num_experts"] * m["hidden_size"]
    return n_params(m) * itemsize + router * (4 - itemsize)


def kv_bytes_per_token(m: dict, itemsize: int = 2) -> int:
    """Keys and values of one token, all layers."""
    return m["num_hidden_layers"] * 2 * m["num_key_value_heads"] * m["head_dim"] * itemsize


def expected_experts_touched(m: dict, rows: float) -> float:
    """Distinct experts of one layer that ``rows`` positions reach under uniform routing."""
    E, k = m["num_experts"], m["num_experts_per_tok"]
    return E * (1.0 - (1.0 - k / E) ** rows)


def moe_experts_bytes(m: dict, touched: float, itemsize: int = 2) -> float:
    """The least the routed experts of ONE pass must read: the three matrices
    of each expert a layer touched, summed over the layers (``touched``: the
    mean number a layer; with 512 positions a pass, all of them)."""
    return expert_layers(m) * touched * expert_params(m) * itemsize


def moe_steps_alone(m: dict, traced: bool) -> dict:
    """The passes a run makes with ONE row before its traffic starts, and what
    each adds to the expert counters of every layer: the check's prompts, each
    sent twice (served once more for the reference, but after the counters are
    read), a block of ``block_length`` positions every ``denoising_steps + 1``
    passes (a first block that starts with some of the prompt's tokens may take
    fewer: counted as whole, an error of a pass a request), and a traced run's
    probes of two tokens, one block each. A pass of one row routes
    ``block_length`` positions, most of them fed the one ``MASK`` embedding, so
    they route nearly alike: between k experts touched (all alike) and
    ``block_length x k``; counted as 1.5 k, which is what the first chip runs
    give back (PR 56: 1,936 passes of which 624 alone read 90.2 experts a pass
    a layer, and a full pass cannot touch more than all 128: the lone ones
    touched 11-13), with 2 tokens on the fullest expert. The reader's
    correction is a few parts in a hundred of a window's passes either way."""
    check, B, k = m["check"], m["block_length"], m["num_experts_per_tok"]
    passes_a_block = (m["deployment"]["engine"].get("denoising_steps") or B) + 1  # the engine's default: a pass a position
    blocks = -(-check["new_tokens"] // B)
    steps = 2 * len(check["prompt_lens"]) * blocks * passes_a_block
    if traced:
        steps += 2 * int(check.get("probe_pairs", 5)) * passes_a_block
    return {"steps": steps, "experts_touched": min(m["num_experts"], B * k, k + k // 2), "fullest_expert_load": 2}


def cache_attention_bytes(m: dict, context_tokens: float, window_tokens: float = 0.0, itemsize: int = 2) -> float:
    """The least the attention of one pass must read of the cache: the keys and
    values, in every layer, of every token the running rows' blocks may see
    (``context_tokens``, summed over the rows: each row's length to the end of
    its block). There is no window layer; ``window_tokens`` is what the reader
    shared with a pattern that has them passes, and is not read."""
    return context_tokens * kv_bytes_per_token(m, itemsize)


def decode_step_bytes(m: dict, context_tokens: int, itemsize: int = 2) -> int:
    """The least one pass must move: every matrix all positions share once
    (attention, the router in float32, the head; the embedding table is
    indexed, not read), the experts a pass is expected to touch with every slot
    of the deployment full (``num_slots x block_length`` positions: all of
    them), and the keys and values of the tokens in context once."""
    D, V = m["hidden_size"], m["vocab_size"]
    slots = m["deployment"]["engine"]["num_slots"]
    router = expert_layers(m) * D * m["num_experts"]
    shared = (m["num_hidden_layers"] * (attention_matmul_params(m)) + D * V) * itemsize + router * 4
    touched = expected_experts_touched(m, slots * m["block_length"])
    return int(shared + moe_experts_bytes(m, touched, itemsize) + cache_attention_bytes(m, context_tokens, 0.0, itemsize))
