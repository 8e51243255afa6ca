"""The shortcut-connected double layer and a router wider than its experts (PR
61): the serving path of ``models/generate.py`` against the plain float32
reference of ``benchmarks/architectures/LongcatFlashForCausalLM`` at a toy
size, seeded weights, on the CPU; ``routed_experts`` with identity experts
against a token-at-a-time loop; that the shares of an expert-parallel
deployment add up to the uncut layer's branch; and that the defaults leave
``routed_experts`` what it was."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import registry
from ray_tpu.models.generate import (
    MOE_CHOICE,
    MOE_COUNTS,
    _project_latent,
    init_cache,
    init_moe_choice,
    init_moe_counts,
    init_paged_cache,
    paged_decode_chunk,
    paged_decode_step,
    prefill,
    unpack_experts,
)
from ray_tpu.models.transformer import TransformerConfig, forward, init_params, param_logical_axes
from ray_tpu.parallel.moe import routed_experts

ARCH = {"name": "these tests", "architecture": "LongcatFlashForCausalLM", "bench_dir": registry.BENCH_DIR}
# The published keys at a toy size: 16 experts of which chip 1 of 4 holds 4, 8 identity experts, 5 picks a token.
TOY = dict(
    attention_bias=False, vocab_size=128, hidden_size=64, ffn_hidden_size=96, expert_ffn_hidden_size=32, num_layers=2,
    num_attention_heads=4, kv_lora_rank=16, q_lora_rank=24, qk_rope_head_dim=8, v_head_dim=20, qk_nope_head_dim=12,
    mla_scale_q_lora=True, mla_scale_kv_lora=True, routed_scaling_factor=6, n_routed_experts=4, rms_norm_eps=1e-5,
    rope_theta=1e7, attention_method="MLA", zero_expert_num=8, zero_expert_type="identity", moe_topk=5,
    tie_word_embeddings=False, torch_dtype="float32",
    published={"n_routed_experts": 16}, deployment={"expert_parallel": {"chips": 4, "index": 1}},
)


def _toy(chips=4, index=1, **over):
    return dict(TOY, n_routed_experts=16 // chips, deployment={"expert_parallel": {"chips": chips, "index": index}}, **over)


@pytest.fixture(scope="module")
def reference():
    return registry.load_architecture(ARCH, "reference")


def _cfg(toy=TOY):
    model = registry.load_architecture(ARCH, "config").model_config(toy, 128, "float32")
    model.update(dtype=jnp.float32, param_dtype=jnp.float32)
    return TransformerConfig(**model)


@pytest.fixture(scope="module")
def model():
    cfg = _cfg()
    return cfg, init_params(jax.random.PRNGKey(61), cfg)


def test_the_double_layers_are_one_stack_with_an_axis_of_two_for_what_a_sub_layer_owns(model):
    cfg, params = model
    assert set(params) == {"embed", "layers", "norm_f", "lm_head"}
    layers = params["layers"]
    # what a sub-layer owns: [layers, 2, ...]
    assert layers["wq_a"].shape == (2, 2, 64, 24) and layers["wkv_b"].shape == (2, 2, 16, 4 * 32)
    assert layers["wo"].shape == (2, 2, 80, 64) and layers["attn_norm"].shape == layers["mlp_norm"].shape == (2, 2, 64)
    assert layers["wi"].shape == layers["wg"].shape == (2, 2, 64, 96) and layers["wo_mlp"].shape == (2, 2, 96, 64)
    # the one branch of experts: [layers, ...]; the router as wide as all 16 experts and the 8 identities, float32
    assert layers["gate"].shape == (2, 64, 24) and layers["gate_bias"].shape == (2, 24)
    assert layers["wi_e"].shape == (2, 4, 64, 32) and layers["wo_e"].shape == (2, 4, 32, 64)
    assert (cfg.router_width, cfg.held_experts, cfg.attention_sublayers) == (24, 4, 2)
    bf16 = init_params(jax.random.PRNGKey(0), dataclasses.replace(cfg, param_dtype=jnp.bfloat16))
    assert bf16["layers"]["gate"].dtype == bf16["layers"]["gate_bias"].dtype == jnp.float32
    assert bf16["layers"]["wi"].dtype == bf16["layers"]["wi_e"].dtype == jnp.bfloat16
    # the two sub-layers' matrices are two draws, and the two FFNs' are none of the experts'
    assert not np.allclose(np.asarray(layers["wi"][0, 0]), np.asarray(layers["wi"][0, 1]))
    assert not np.allclose(np.asarray(layers["wi"][0, 0, :, :32]), np.asarray(layers["wi_e"][0, 0]))
    axes = param_logical_axes(cfg)
    assert jax.tree.map(len, axes, is_leaf=lambda a: isinstance(a, tuple)) == jax.tree.map(lambda a: len(a.shape), params)


def test_the_training_path_refuses_the_double_layer_by_name(model):
    cfg, params = model
    for what in ("shortcut_moe", "zero_experts", "router_normalize", "mla_scale_q_lora"):
        assert what in cfg.inference_only
    with pytest.raises(NotImplementedError, match="shortcut_moe"):
        forward(params, jnp.zeros((1, 8), jnp.int32), cfg)
    plain = dict(vocab_size=64, d_model=32, n_layers=2, n_heads=4, n_kv_heads=4, d_ff=64)
    with pytest.raises(ValueError, match="shortcut_moe.*has not run and is not built"):
        TransformerConfig(**plain, shortcut_moe=True)
    with pytest.raises(ValueError, match="zero_experts"):
        TransformerConfig(**plain, zero_experts=4)
    with pytest.raises(ValueError, match="mla_scale"):
        TransformerConfig(**plain, mla_scale_kv_lora=True)


def _served_logits(cfg, params, tokens, pool=None):
    """Prefill in two chunks of 16 (the second's tail is padding beyond 30) then one token a step, through a paged
    latent pool whose blocks come out of order: (logits [45, V], the pool)."""
    pool = init_paged_cache(cfg, 9, 8) if pool is None else pool
    table = jnp.asarray([[5, 2, 7, 1, 3, 8]], jnp.int32)
    chunk = jax.jit(lambda fed, pool, start: paged_decode_chunk(params, fed, pool, table, start, cfg, valid_to=jnp.asarray([30], jnp.int32)))
    step = jax.jit(lambda token, pool, pos: paged_decode_step(params, token, pool, table, pos, cfg))
    got = []
    for start in (0, 16):
        logits, pool = chunk(jnp.asarray([tokens[start:start + 16]], jnp.int32), pool, jnp.asarray([start], jnp.int32))
        got.append(np.asarray(logits[0]))
    got = np.concatenate(got)[:30]
    for pos in range(30, len(tokens)):
        logits, pool = step(jnp.asarray([tokens[pos]], jnp.int32), pool, jnp.asarray([pos], jnp.int32))
        got = np.concatenate([got, np.asarray(logits)])
    return got, pool, table


def test_prefill_in_chunks_then_paged_decode_equals_the_references_full_forward(model, reference):
    cfg, params = model
    tokens = [int(t) for t in np.random.RandomState(1).randint(0, 128, size=45)]
    want = np.asarray(jax.jit(lambda p: reference.sequence_logits(p, tokens, TOY))(params))
    assert want.std() > 0.3
    pool = init_paged_cache(cfg, 9, 8)
    # two cached layers a double layer, each a row of 16 + 8 values padded to the lanes
    assert set(pool) == {"ckv"} and pool["ckv"].shape == (4, 9, 8, 128)
    pool.update({MOE_COUNTS: init_moe_counts(cfg), MOE_CHOICE: init_moe_choice(cfg, 9, 8)})
    got, pool, table = _served_logits(cfg, params, tokens, pool)
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=0)
    # the dense cache holds the same rows and gives the same logits
    last, dense, _ = prefill(params, jnp.asarray([tokens]), init_cache(cfg, 1, 48), cfg)
    np.testing.assert_allclose(np.asarray(last[0]), want[-1], atol=2e-4, rtol=0)
    assert dense["ckv"].shape == (4, 1, 48, 128)

    # What the pool keeps beside the rows: each token's 5 picks among the 24 columns a layer, one word (5 bits a pick) ...
    assert pool[MOE_CHOICE].shape == (2, 9, 8)
    words = np.asarray(pool[MOE_CHOICE])[:, np.asarray(table[0])].reshape(2, -1)[:, :45]
    picks = unpack_experts(words, cfg)  # [layers, tokens, 5]
    assert picks.shape == (2, 45, 5) and picks.max() >= 16 and picks.max() < 24 and all(len(set(p)) == 5 for p in picks[0])
    # ... and the counters of what the picks were, steps and chunks apart, recounted from the words
    counts = np.asarray(pool[MOE_COUNTS])  # [steps | chunks, layers, 4 held + touched, fullest, calls, every pick, identities, rows without]
    assert counts.shape == (2, 2, 4 + 3 + 1 + 2)
    for kind, rows in ((0, slice(30, 45)), (1, slice(0, 30))):
        for layer in range(2):
            mine = picks[layer, rows]
            held = (mine >= 4) & (mine < 8)  # chip 1 of 4 holds experts 4-7
            assert counts[kind, layer, :4].tolist() == [(mine == e).sum() for e in range(4, 8)]
            assert counts[kind, layer, 7] == mine.size and counts[kind, layer, 8] == (mine >= 16).sum()
            assert counts[kind, layer, 9] == (~held.any(axis=-1)).sum()
    assert counts[0, :, 6].tolist() == [15, 15] and counts[1, :, 6].tolist() == [2, 2]
    assert 0 < counts[..., 8].sum() < counts[..., 7].sum() and counts[..., 9].sum() > 0


def test_the_latents_two_constants_scale_the_query_latent_and_the_cached_latent_and_not_the_rotary_key(model):
    cfg, params = model
    T = 9
    x = jax.random.normal(jax.random.PRNGKey(2), (1, T, 64))
    positions = jnp.arange(T)[None]
    lp = {k: v[1, 0] for k, v in params["layers"].items() if v.ndim > 2 and v.shape[1] == 2 and not k.endswith("_e")}
    q, rows = _project_latent(lp, x, positions, cfg)
    plain = dataclasses.replace(cfg, mla_scale_q_lora=False, mla_scale_kv_lora=False)
    q0, rows0 = _project_latent(lp, x, positions, plain)
    R = cfg.kv_lora_rank
    np.testing.assert_allclose(np.asarray(rows["ckv"][..., :R]), (64 / 16) ** 0.5 * np.asarray(rows0["ckv"][..., :R]), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(rows["ckv"][..., R:]), np.asarray(rows0["ckv"][..., R:]))  # the rotary key, the padding
    np.testing.assert_allclose(np.asarray(q), (64 / 24) ** 0.5 * np.asarray(q0), rtol=1e-5, atol=1e-6)  # both halves of every head's query


# -- routed_experts with identity experts ------------------------------------------------------------------------------


def _experts(key, N=12, D=16, E=8, Z=4, F=24):
    """E experts with matrices, Z identity experts behind them in the router."""
    ks = jax.random.split(key, 6)
    params = dict(
        gate=jax.random.normal(ks[0], (D, E + Z)) * D**-0.5, gate_bias=jax.random.normal(ks[5], (E + Z,)) * 0.02,
        wg_e=jax.random.normal(ks[1], (E, D, F)) * D**-0.5, wi_e=jax.random.normal(ks[2], (E, D, F)) * D**-0.5,
        wo_e=jax.random.normal(ks[3], (E, F, D)) * F**-0.5,
    )
    return params, jax.random.normal(ks[4], (N, D))


def _expert(params, e, x):
    return (jax.nn.silu(x @ params["wg_e"][e]) * (x @ params["wi_e"][e])) @ params["wo_e"][e]


def _a_token_at_a_time(params, x, k, scale, Z, held=None, normalize=False):
    """The layer's branch by a plain loop: softmax over all columns, the k largest biased scores, each pick
    ``scale * s`` (divided by the picks' sum under ``normalize``), an expert's SwiGLU or, for an identity, the row."""
    E = params["gate"].shape[1] - Z
    held = range(E) if held is None else held
    s = np.asarray(jax.nn.softmax(x @ params["gate"], axis=-1))
    out, picks = np.zeros(x.shape, np.float32), []
    for n in range(x.shape[0]):
        chosen = np.argsort(-(s[n] + np.asarray(params["gate_bias"])), kind="stable")[:k]
        picks.append(chosen)
        total = s[n, chosen].sum() if normalize else 1.0
        for e in chosen:
            w = scale * s[n, e] / total
            if e >= E:
                out[n] += w * np.asarray(x[n])
            elif e in held:
                out[n] += w * np.asarray(_expert(params, int(e), x[n][None]))[0]
    return out, np.asarray(picks)


def test_a_router_wider_than_its_experts_against_a_token_at_a_time():
    params, x = _experts(jax.random.PRNGKey(7))
    # row 0's picks are all identities, row 1's all experts: rows whose logits are what the test says (16 >= 12 columns)
    logits = np.where(np.arange(12) >= 8, 4.0, -4.0), np.where(np.arange(12) < 3, 4.0, -4.0)
    x = x.at[:2].set(jnp.asarray(np.linalg.lstsq(np.array(params["gate"]).T, np.stack(logits).T, rcond=None)[0].T, jnp.float32))
    want, picks = _a_token_at_a_time(params, x, 3, 6.0, Z=4)
    assert (picks[0] >= 8).all() and (picks[1] < 8).all() and 0 < (picks >= 8).sum() < picks.size
    out, sent, chosen, scores = routed_experts(params, x, k=3, scale=6.0, score="softmax", identity=4, normalize=False)
    np.testing.assert_allclose(np.asarray(out), want, atol=2e-5)
    assert np.sort(np.asarray(chosen), axis=-1).tolist() == np.sort(picks, axis=-1).tolist()  # over all 12 columns
    assert scores.shape == (12, 12) and sent.shape == (8,) and int(sent.sum()) == int((picks < 8).sum())
    # a row of identities alone is its own input times the sum of its weights
    s0 = np.asarray(scores)[0, picks[0]].sum()
    np.testing.assert_allclose(np.asarray(out[0]), 6.0 * s0 * np.asarray(x[0]), atol=2e-5)
    # normalised over the chosen is another function (the fault the benchmark's check plants)
    normed, *_ = routed_experts(params, x, k=3, scale=6.0, score="softmax", identity=4)
    np.testing.assert_allclose(np.asarray(normed), _a_token_at_a_time(params, x, 3, 6.0, Z=4, normalize=True)[0], atol=2e-5)
    assert np.abs(np.asarray(normed) - want).max() > 0.1
    with pytest.raises(ValueError, match="identity"):
        routed_experts(params, x, k=3, score="softmax", identity=4, rows=8)


@pytest.mark.parametrize("stacked", [False, True], ids=["a layer's own leaves", "a stack run by layer"])
def test_identity_picks_and_padding_rows_are_selected_away_from_what_the_grouped_matmul_leaves(stacked, monkeypatch):
    """A pick of an identity, of an expert of another chip and every pick of a
    padding row lie past the last group, where ``ragged_dot`` and the Pallas
    interpreter leave NaN: with NaN in every such row of every grouped product
    the result is finite, a padding row's is zero (its identity term too, by
    ``valid``), a row with no held expert is its identity term, and a token's
    is what it was."""
    params, x = _experts(jax.random.PRNGKey(8))
    valid = jnp.arange(12) % 3 != 0
    held = {n: params[n][2:4] for n in ("wg_e", "wi_e", "wo_e")}  # chip 1 of 4 holds experts 2 and 3
    kwargs = dict(k=3, scale=6.0, score="softmax", identity=4, normalize=False, share=(1, 4), valid=valid)
    mine = {**params, **held}
    if stacked:
        others, _ = _experts(jax.random.PRNGKey(9))
        mine = {**params, **{n: jnp.stack([others[n][:2], held[n], others[n][4:6]]) for n in held}}
        kwargs["layer"] = jnp.int32(1)
    want, picks = _a_token_at_a_time(params, x, 3, 6.0, Z=4, held=(2, 3))
    here = ((picks == 2) | (picks == 3)).any(axis=-1)
    assert (~here & np.asarray(valid)).any() and (here & np.asarray(valid)).any()  # a real row with no held expert, and one with
    ragged_dot, dead = jax.lax.ragged_dot, []

    def poisoning(a, w_e, groups):
        in_a_group = jnp.arange(a.shape[0]) < jnp.sum(groups)
        dead.append(int(jnp.sum(~in_a_group)))
        return jnp.where(in_a_group[:, None], ragged_dot(a, w_e, groups), jnp.nan)

    monkeypatch.setattr(jax.lax, "ragged_dot", poisoning)
    out, sent, chosen, _ = routed_experts(mine, x.at[0].set(jnp.inf), **kwargs)  # row 0 is padding: whatever it holds
    in_groups = int(((picks == 2) | (picks == 3))[np.asarray(valid)].sum())
    assert dead == [36 - in_groups] * 3 and sent.tolist() == [int((picks[np.asarray(valid)] == e).sum()) for e in (2, 3)]
    out = np.asarray(out)
    assert np.isfinite(out).all() and not out[~np.asarray(valid)].any()
    np.testing.assert_allclose(out[np.asarray(valid)], want[np.asarray(valid)], atol=2e-5)


def test_the_shares_add_up_to_the_uncut_layers_branch(reference):
    """An expert-parallel deployment of 4 chips: each chip's part of the branch
    (``routed_experts(share=)``: its 4 of the 16 experts, and the identity
    term, which is the layer's and in every part) summed, the identity term
    counted once, is the uncut layer's whole branch by the reference, and the
    reference's own parts add up the same way."""
    whole_toy = _toy(chips=1, index=0)
    cfg = _cfg(whole_toy)
    params = init_params(jax.random.PRNGKey(61), cfg)
    u = jax.random.normal(jax.random.PRNGKey(3), (21, 64))
    with jax.default_matmul_precision("highest"):
        want, _ = reference.shortcut_branch(params["layers"], 1, u, whole_toy)
        router = reference._take(params["layers"], reference.ROUTER_LEAVES, 1)
        weights, _ = reference.routing_weights(router, u, whole_toy)
        identity = np.asarray(jnp.sum(weights[:, 16:], axis=-1, keepdims=True) * u)
    assert np.abs(identity).max() > 0.05 and np.abs(np.asarray(want) - identity).max() > 0.05  # both halves of the branch weigh
    lp = {name: leaf[1] for name, leaf in params["layers"].items() if name in ("gate", "gate_bias", "wg_e", "wi_e", "wo_e")}
    parts, ref_parts, seen = [], [], []
    for index in range(4):
        mine = {**lp, **{n: lp[n][4 * index: 4 * index + 4] for n in ("wg_e", "wi_e", "wo_e")}}
        out, sent, chosen, _ = routed_experts(
            mine, u, k=5, scale=6.0, score="softmax", identity=8, normalize=False, share=(index, 4)
        )
        parts.append(np.asarray(out))
        seen.append(int(sent.sum()))
        share_toy = _toy(chips=4, index=index)
        stack = {**params["layers"], **{n: params["layers"][n][:, 4 * index: 4 * index + 4] for n in ("wg_e", "wi_e", "wo_e")}}
        with jax.default_matmul_precision("highest"):
            ref_parts.append(np.asarray(reference.shortcut_branch(stack, 1, u, share_toy)[0]))
    np.testing.assert_allclose(sum(parts) - 3 * identity, np.asarray(want), atol=3e-5)
    np.testing.assert_allclose(sum(ref_parts) - 3 * identity, np.asarray(want), atol=3e-5)
    for mine, theirs in zip(parts, ref_parts):
        np.testing.assert_allclose(mine, theirs, atol=3e-5)
    assert sum(seen) == int((np.asarray(chosen) < 16).sum()) and all(seen)  # every pick of an expert lies in one share


def test_the_defaults_leave_routed_experts_what_it_was():
    """``identity=0, normalize=True`` stated or not is one program, text for text,
    and the one the parent built: its lowered text holds no select of an
    identity term and its digest is the parent's (computed beside a copy of
    PR 60's commit)."""
    import hashlib

    params, x = _experts(jax.random.PRNGKey(5), Z=0)

    def lowered(**kwargs):
        return jax.jit(lambda p, x: routed_experts(p, x, k=2, scale=1.8, valid=jnp.arange(12) % 3 != 0, share=(0, 2), **kwargs)).lower(
            {**params, **{n: params[n][:4] for n in ("wg_e", "wi_e", "wo_e")}}, x
        ).as_text()

    stated, plain = lowered(identity=0, normalize=True), lowered()
    assert stated == plain and "moe_identity" not in plain
    assert hashlib.sha1(plain.encode()).hexdigest() == _PARENTS_ROUTED_EXPERTS


_PARENTS_ROUTED_EXPERTS = "bb4a8cab9d77746d17cd00c9bcd74df1f33943d7"


def test_the_engine_counts_what_a_steps_picks_were(model):
    """``LLMEngine`` over the toy model: ``get_stats()["moe"]`` splits every pick
    into identities, experts held and experts of other chips, and counts the
    rows with no held expert; the sums are the router's."""
    from ray_tpu.serve.llm.engine import LLMEngine

    cfg, params = model
    engine = LLMEngine(params, cfg, num_slots=2, block_size=8, max_model_len=64, num_blocks=17, prefill_chunk=16)
    try:
        prompt = [int(t) for t in np.random.RandomState(2).randint(0, 128, size=20)]
        request = engine.submit(prompt, max_new_tokens=6, return_routed_experts=True)
        assert len(request.result(timeout=120.0)) == 6
        picks = np.asarray(request.routed_experts)  # [fed, layers, 5] over the 24 columns
        assert picks.shape == (25, 2, 5) and picks.max() >= 16 and picks.max() < 24
        moe = engine.stats()["moe"]
    finally:
        engine.shutdown()
    for kind, rows in (("prefill", slice(0, 20)), ("decode", slice(20, 25))):
        of = moe[kind]
        for layer in range(2):
            mine = picks[rows, layer]
            held = (mine >= 4) & (mine < 8)
            assert of["picks_identity"][layer] == (mine >= 16).sum() and sum(of["assignments"][layer]) == held.sum()
            assert of["rows_without_held"][layer] == (~held.any(axis=-1)).sum()
            assert of["assignments_all"][layer] == mine.size == 5 * len(mine)
            assert set(of) == {"steps", "assignments", "assignments_all", "experts_touched", "fullest_expert_load",
                               "picks_identity", "rows_without_held"}  # the picks of other chips' experts are the rest
