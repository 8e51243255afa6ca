"""Gated short-convolution layers (PR 54): the kind ``"conv"`` of ``layer_kinds``,
a mixer-then-MLP layer that keeps no keys and values and no recurrent state,
only the last ``conv_cache - 1`` rows ahead of its filter a slot. The model is
LFM2-24B-A2B's layer at a toy size over the benchmark's own pattern, one leading
dense layer (a conv layer) and two periods ``full, conv, conv, conv`` with routed
experts: a dense stack and two stacks by kind, each with experts of its own, a
scan of two periods behind a dense segment. Heads are 64 wide, as published, so
the attention layers cache two KV heads a row (``generate._heads_paired``). It is
held to the benchmark's plain float32 reference
(``benchmarks/architectures/Lfm2MoeForCausalLM/reference.py``), whose full
forward pass knows no cache, no chunk, no carried row and no sorting of
assignments: a token's filter from the three rows it names, every expert over
every token.

Tolerances: program and reference are both float32 here (the CPU's matmuls are
exact float32), so they differ by the order of their sums alone. ``LOGIT_TOL``
2e-4 on logits of standard deviation ~1 and ``GAP_TOL`` 1e-4 are the other
patterns' (``tests/test_serve_llm_mamba.py``); the carried rows are compared at
1e-5 of values of order 1 (one product of two float32 matmuls' outputs)."""

import dataclasses
import functools

import numpy as np
import pytest

TYPES = ["conv", "full_attention", "conv", "conv", "conv", "full_attention", "conv", "conv", "conv"]
KINDS = tuple("conv" if t == "conv" else "full" for t in TYPES)
MODEL = dict(
    vocab_size=128, d_model=64, n_layers=9, n_heads=4, n_kv_heads=2, head_dim=64, d_ff=96, max_seq_len=256,
    layer_kinds=KINDS, conv_cache=3, full_layers_rope=True, qk_norm=True, rope_theta=1e6, tie_embeddings=True,
    num_experts=8, experts_per_token=2, d_expert=32, first_dense_layers=1,
)
# The same model as its published ``config.json`` would state it: what the reference reads.
PUBLISHED = dict(
    hidden_size=64, num_hidden_layers=9, num_dense_layers=1, layer_types=TYPES, num_attention_heads=4, num_key_value_heads=2,
    head_dim=64, vocab_size=128, norm_eps=1e-5, conv_L_cache=3, intermediate_size=96, moe_intermediate_size=32, num_experts=8,
    num_experts_per_tok=2, use_expert_bias=True, routed_scaling_factor=1.0, rope_parameters={"rope_theta": 1e6, "rope_type": "default"},
)
# 47 blocks of 8 tokens for three slots: room for the requests of every test but the one that wants a preemption.
ENGINE = dict(num_slots=3, block_size=8, max_model_len=256, prefill_chunk=16, num_blocks=48)
LOGIT_TOL, GAP_TOL, ROW_TOL = 2e-4, 1e-4, 1e-5


def _scattered(params, seed=1):
    """Norm weights are drawn constant: scattered here, so that a norm left out or two swapped show."""
    import jax

    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 64))
    for stack in ("dense_layers", "conv_layers", "layers"):
        for name, leaf in params[stack].items():
            if name.endswith("norm"):
                params[stack][name] = leaf * jax.random.uniform(next(keys), leaf.shape, minval=0.5, maxval=1.5)
    return params


@pytest.fixture(scope="module")
def model():
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.transformer import TransformerConfig, init_params

    cfg = TransformerConfig(**MODEL, dtype=jnp.float32, param_dtype=jnp.float32)
    return _scattered(init_params(jax.random.PRNGKey(0), cfg)), cfg


@pytest.fixture(scope="module")
def reference():
    from benchmarks.harness import registry

    return registry.load_architecture(
        {"name": "this test", "architecture": "Lfm2MoeForCausalLM", "bench_dir": registry.BENCH_DIR}, "reference"
    )


@pytest.fixture(scope="module")
def engine(model):
    """One engine, its programs compiled once, for the tests that serve through it one after another."""
    from ray_tpu.serve.llm import LLMEngine

    params, cfg = model
    eng = LLMEngine(params, cfg, **ENGINE)
    yield eng
    eng.shutdown()


def _prompt(seed, n):
    return np.random.default_rng(seed).integers(0, MODEL["vocab_size"], n).tolist()


_REFERENCE = {}


def _logits(reference, params, tokens):
    """The reference's logits [len(tokens), V] of one sequence: ONE compiled
    program for the module, over the sequence padded to 256 tokens (causal: the
    tail changes nothing before it). Eagerly, a forward pass of eight scans over
    eight experts takes 7 s, and these tests make thirty."""
    import jax
    import jax.numpy as jnp

    if "fn" not in _REFERENCE:
        _REFERENCE["fn"] = jax.jit(lambda p, t: reference.sequence_logits(p, t, PUBLISHED))
    padded = np.zeros((256,), np.int32)
    padded[: len(tokens)] = tokens
    return np.asarray(_REFERENCE["fn"](params, jnp.asarray(padded)))[: len(tokens)]


def _gaps(reference, params, prompt, new):
    """How far the reference's logit of each token the system drew lies under
    the reference's largest, at the position that predicts it."""
    logits = _logits(reference, params, prompt + new)
    rows = np.arange(len(prompt) - 1, len(prompt) + len(new) - 1)
    return logits[rows].max(axis=-1) - logits[rows, np.asarray(new)]


@functools.lru_cache(maxsize=None)
def _programs(cfg, chunk):
    """(prefill chunk, decode step) of ``cfg``, jitted once a configuration and a chunk's width."""
    import jax

    from ray_tpu.models.generate import paged_decode_chunk, paged_decode_step

    prefill = jax.jit(lambda p, t, c, table, pos, valid_to, slot, fresh: paged_decode_chunk(
        p, t, c, table, pos, cfg, valid_to=valid_to, state_slots=slot, state_fresh=fresh))
    return prefill, jax.jit(lambda p, t, c, table, pos: paged_decode_step(p, t, c, table, pos, cfg))


def _serve_by_hand(model, rows, steps, chunk=16, pool=None):
    """The engine's two programs' arithmetic, driven by hand so that LOGITS
    come back: each of ``rows`` = (slot, prompt) prefilled in chunks of
    ``chunk`` (the last one padded), then ``steps`` decode steps of all rows at
    once, each row fed its own greedy token. Returns per row (logits at every
    prompt position [n, V], logits of each decode step [steps, V], tokens fed),
    and the pool."""
    import jax.numpy as jnp

    from ray_tpu.models.generate import MOE_CHOICE, MOE_COUNTS, init_moe_choice, init_moe_counts, init_paged_cache

    params, cfg = model
    bs, slots, n_max = 8, 3, 12
    if pool is None:
        pool = init_paged_cache(cfg, 1 + slots * n_max, bs, state_slots=slots)
        pool.update({MOE_COUNTS: init_moe_counts(cfg), MOE_CHOICE: init_moe_choice(cfg, 1 + slots * n_max, bs)})
    tables = np.zeros((slots, n_max), np.int32)
    prefill, step = _programs(cfg, chunk)
    out = {}
    for slot, prompt in rows:
        tables[slot] = 1 + slot * n_max + np.arange(n_max)
        got = []
        for pos in range(0, len(prompt), chunk):
            piece = prompt[pos : pos + chunk]
            fed = np.zeros((1, chunk), np.int32)
            fed[0, : len(piece)] = piece
            logits, pool = prefill(params, jnp.asarray(fed), pool, jnp.asarray(tables[slot : slot + 1]),
                                   jnp.asarray([pos], jnp.int32), jnp.asarray([len(prompt)], jnp.int32),
                                   jnp.asarray([slot], jnp.int32), jnp.asarray([pos == 0]))
            got.append(np.asarray(logits[0, : len(piece)]))
        out[slot] = [np.concatenate(got), [], [int(got[-1][-1].argmax())]]
    pos = np.zeros((slots,), np.int32)
    live = np.zeros_like(tables)
    for slot, prompt in rows:
        pos[slot], live[slot] = len(prompt), tables[slot]
    for _ in range(steps):
        tok = np.zeros((slots,), np.int32)
        for slot, _ in rows:
            tok[slot] = out[slot][2][-1]
        logits, pool = step(params, jnp.asarray(tok), pool, jnp.asarray(live), jnp.asarray(pos))
        for slot, _ in rows:
            out[slot][1].append(np.asarray(logits[slot]))
            out[slot][2].append(int(np.asarray(logits[slot]).argmax()))
            pos[slot] += 1
    return out, pool


def test_the_kinds_stacks_and_what_a_slot_and_a_token_hold(model):
    """A dense stack whose mixer is the conv mixer, then two stacks by kind;
    the plan runs a dense segment and then a by-kind segment; the state group
    is as deep as the model has conv layers, the dense one among them; a cached
    row holds two 64-wide heads; a dense cache refuses the kind by name."""
    import importlib

    import jax.numpy as jnp

    generate = importlib.import_module("ray_tpu.models.generate")
    params, cfg = model
    assert set(params) == {"embed", "norm_f", "dense_layers", "conv_layers", "layers"}
    assert params["dense_layers"]["w_in"].shape == (1, 64, 192) and "wq" not in params["dense_layers"] and "gate" not in params["dense_layers"]
    assert params["conv_layers"]["conv_w"].shape == (6, 3, 64) and params["conv_layers"]["wi_e"].shape == (6, 8, 64, 32)
    assert params["layers"]["wq"].shape == (2, 64, 256) and params["layers"]["q_norm"].shape == (2, 64) and params["layers"]["wi_e"].shape[0] == 2
    plan = generate._layer_plan(cfg)
    assert [(s.first, s.depth, s.kinds) for s in plan] == [(0, 1, ("conv",)), (1, 8, KINDS[1:])]
    assert plan[0].rows["conv"].stack == "dense_layers" and not plan[0].rows["conv"].own and plan[0].rows["conv"].layers == 7
    assert {k: (r.stack, r.own, r.layers, r.reach) for k, r in plan[1].rows.items()} == {
        "conv": ("conv_layers", True, 7, "state"), "full": ("layers", True, 2, "table")}
    assert generate.state_kind(cfg) == "conv" and generate.expert_layers(cfg) == 8 and generate.pool_reach(cfg) == {"table", "state"}
    assert generate.state_rows(cfg) == {"conv": ((2, 64), jnp.float32)}
    assert generate.state_slot_bytes(cfg) == 7 * 2 * 64 * 4
    assert generate._heads_paired(cfg) and generate._cache_rows(cfg) == {"k": (1, 128), "v": (1, 128)}
    assert generate.cache_token_bytes(cfg) == {"full": 2 * 2 * 2 * 64 * 4}  # nothing padded: two layers, k and v, two heads
    pool = generate.init_paged_cache(cfg, 5, 8, state_slots=3)
    assert {n: a.shape for n, a in pool.items()} == {"conv": (7, 3, 2, 64), "k": (2, 5, 8, 1, 128), "v": (2, 5, 8, 1, 128)}
    assert not generate._heads_paired(dataclasses.replace(cfg, head_dim=32)) and not generate._heads_paired(
        dataclasses.replace(cfg, layer_kinds=(), conv_cache=0, first_dense_layers=0, full_layers_rope=False))
    with pytest.raises(NotImplementedError, match="gated short-convolution layers.*paged cache only"):
        generate.init_cache(cfg, 1, 16)


@pytest.mark.parametrize("lengths", [(13,), (16,), (50,), (11, 77)], ids=lambda ls: "-".join(map(str, ls)))
def test_chunks_then_steps_through_a_dense_and_a_by_kind_segment_give_the_references_logits(model, reference, lengths):
    """One padded chunk; one whole chunk; several chunks with a padded last;
    two rows of unequal length at once (an inactive third slot beside them):
    every prompt position's logits and twelve decode steps' against the
    reference's full forward pass over prompt + generated. The slot that is not
    live moves nothing and nothing in the pool is NaN; the counters count eight
    expert layers, in the model's order across the two stacks."""
    params, cfg = model
    rows = [(2 - i, _prompt(20 + n, n)) for i, n in enumerate(lengths)]  # slots 2, 1: not the row's index in the call
    out, pool = _serve_by_hand(model, rows, steps=12)
    for slot, prompt in rows:
        prefill_logits, step_logits, fed = out[slot]
        want = _logits(reference, params, prompt + fed[:-1])
        assert 0.5 < want.std() < 2.0
        np.testing.assert_allclose(prefill_logits, want[: len(prompt)], atol=LOGIT_TOL, rtol=0)
        np.testing.assert_allclose(np.stack(step_logits), want[len(prompt) :], atol=LOGIT_TOL, rtol=0)
    idle = sorted(set(range(3)) - {slot for slot, _ in rows})
    assert not np.asarray(pool["conv"][:, idle]).any() and np.asarray(pool["conv"][:, rows[0][0]]).all(axis=(1, 2)).any()
    assert all(np.isfinite(np.asarray(leaf)).all() for name, leaf in pool.items() if leaf.dtype.kind == "f")
    counts = np.asarray(pool["moe_counts"])
    tokens = sum(len(p) for _, p in rows)
    assert counts.shape == (2, 8, 8 + 3)
    assert (counts[0, :, :8].sum(axis=-1) == 12 * len(rows) * 2).all() and (counts[1, :, :8].sum(axis=-1) == tokens * 2).all()
    assert (counts[0, :, 10] == 12).all()
    choice = np.asarray(pool["moe_choice"])  # a word a token an expert layer, beside the token's row: every layer wrote its own
    assert choice.shape[0] == 8 and len({choice[l].tobytes() for l in range(8)}) == 8


def test_a_prompt_split_into_chunks_carries_the_rows_of_the_unsplit_prompt(model, reference):
    """33 tokens as chunks of 16 (16, 16 and a chunk of ONE real token and 15 of
    padding) and as one padded chunk of 64 leave the same two rows a conv layer
    in the slot, the reference's ``v_31, v_32`` in the first layer, and the same
    logits at the last position; a chunk that holds no real token at all (an
    inactive row's) leaves them where they were."""
    import jax.numpy as jnp

    params, cfg = model
    prompt = _prompt(5, 33)
    split, pool_split = _serve_by_hand(model, [(1, prompt)], steps=0, chunk=16)
    whole, pool_whole = _serve_by_hand(model, [(1, prompt)], steps=0, chunk=64)
    np.testing.assert_allclose(np.asarray(pool_split["conv"][:, 1]), np.asarray(pool_whole["conv"][:, 1]), atol=ROW_TOL, rtol=0)
    np.testing.assert_allclose(split[1][0], whole[1][0], atol=LOGIT_TOL, rtol=0)
    w = reference._take(params["dense_layers"], reference.CONV_LEAVES, 0)
    x = reference.embed(params, jnp.asarray(prompt), PUBLISHED)
    _, carried = reference.conv_mixer(w, reference.rms_norm(x, w["operator_norm"], 1e-5), fed=33)
    assert np.abs(np.asarray(carried)).mean() > 0.1
    np.testing.assert_allclose(np.asarray(pool_split["conv"][0, 1]), np.asarray(carried), atol=ROW_TOL, rtol=0)
    prefill, _ = _programs(cfg, 16)
    _, after = prefill(params, jnp.zeros((1, 16), jnp.int32), pool_split, jnp.zeros((1, 12), jnp.int32), jnp.asarray([0], jnp.int32),
                       jnp.asarray([0], jnp.int32), jnp.asarray([1], jnp.int32), jnp.asarray([False]))
    np.testing.assert_array_equal(np.asarray(after["conv"]), np.asarray(pool_split["conv"]))


def test_two_heads_a_cached_row_are_the_heads_cached_apart(model, monkeypatch):
    """``head_dim`` 64: the pool holds two KV heads a 128-lane row, a query
    carries zeros beside its own head's half and keeps its half of the weighted
    sum. The same model with the pairing switched off (a row a head, 64 wide:
    the layout that makes the TPU's compiler copy the pool) gives the same
    logits, chunk and step."""
    import importlib

    generate = importlib.import_module("ray_tpu.models.generate")
    rows = [(0, _prompt(3, 21)), (2, _prompt(4, 40))]
    paired, pool = _serve_by_hand(model, rows, steps=5)
    assert pool["k"].shape[-2:] == (1, 128)
    monkeypatch.setattr(generate, "_heads_paired", lambda cfg: False)
    _programs.cache_clear()
    try:
        apart, pool = _serve_by_hand(model, rows, steps=5)
    finally:
        _programs.cache_clear()
    assert pool["k"].shape[-2:] == (2, 64)
    for slot, _ in rows:
        np.testing.assert_allclose(paired[slot][0], apart[slot][0], atol=2e-5, rtol=0)
        np.testing.assert_allclose(np.stack(paired[slot][1]), np.stack(apart[slot][1]), atol=2e-5, rtol=0)
        assert paired[slot][2] == apart[slot][2]


def test_the_engine_serves_the_references_tokens_and_names_its_state_group(engine, model, reference):
    """Chunked prefill, then the decode loop one step ahead, three requests on
    three slots and two more behind them: every token the engine drew (greedy)
    is the reference's best at its position, or within rounding of it."""
    params, _ = model
    prompts = [_prompt(i, n) for i, n in enumerate((37, 5, 90, 130, 16))]
    before = engine.stats()["state_resets"]
    reqs = [engine.submit(p, max_new_tokens=24) for p in prompts]
    for p, r in zip(prompts, reqs):
        assert _gaps(reference, params, p, r.result(timeout=300)).max() < GAP_TOL
    st = engine.stats()
    assert st["kv_pool_not_donated"] == 0 and st["host_logit_rows"] == 0
    assert st["decode_steps_with_chunk"] == 0  # groups of leaves beside keys and values: the chunk is a program of its own
    assert st["state_resets"] - before == 5
    groups = st["kv_groups"]
    assert set(groups) == {"full", "state"}
    assert groups["full"]["kv_token_bytes"] == st["kv_token_bytes"] == 2 * 2 * 2 * 64 * 4
    assert groups["state"] == dict(kind="conv", bytes_per_slot=7 * 2 * 64 * 4, num_slots=3, slots_in_use=0)
    moe = st["moe"]
    assert len(moe["decode"]["assignments"]) == len(moe["prefill"]["assignments"]) == 8
    with pytest.raises(ValueError, match="layer_kinds has 'conv'.*ROADMAP R5"):
        from ray_tpu.serve.llm import LLMEngine

        LLMEngine(params, model[1], role="prefill", **ENGINE)


def test_a_finished_request_hands_back_its_carried_rows_and_the_check_holds_them(engine, model, reference):
    """``submit(return_state=True, return_routed_experts=True)``: what the
    benchmark's serving check reads (``reference.make_layerwise_logits``, handed
    the engine's own ``params`` as the harness hands them): the rows [conv
    layers, 2, D] a slot carries after the last token fed, the first layer's
    within ``ROW_TOL`` of the reference's; a limit they do not meet turns the
    sequence's logits NaN."""
    params, _ = model
    prompt = _prompt(70, 45)
    beside = engine.submit(_prompt(71, 30), max_new_tokens=40)
    req = engine.submit(prompt, max_new_tokens=9, return_state=True, return_routed_experts=True)
    new = req.result(timeout=300)
    assert req.state.shape == (7, 2, 64) and str(req.state.dtype) == "float32" and req.state.any(axis=(1, 2)).all()
    assert req.routed_experts.shape == (45 + 8, 8, 2) and len({req.routed_experts[:, l].tobytes() for l in range(8)}) == 8
    padded = prompt + new + [0] * 7  # as the harness pads the shorter sequences of a check
    rows = list(range(len(prompt) - 1, len(prompt) + 8))
    got = np.asarray(reference.make_layerwise_logits({**PUBLISHED, "check": {"state_gap_tol": ROW_TOL}})(engine.params, padded, rows))
    want = _logits(reference, params, prompt + new)[len(prompt) - 1 : -1]
    np.testing.assert_allclose(got, want, atol=LOGIT_TOL, rtol=0)  # the same reference, a layer a program
    poisoned = reference.make_layerwise_logits({**PUBLISHED, "check": {"state_gap_tol": -1.0}})(engine.params, padded, rows)
    assert np.isnan(np.asarray(poisoned)).all()
    beside.result(timeout=300)


def test_a_slot_used_again_starts_from_zero_rows_and_a_preempted_request_recomputes_its_tokens(engine, model, reference):
    """Three requests after one another, the first the longest, each alone on
    the engine and so in the slot its predecessor left: each draws the
    reference's tokens, which know no predecessor, so nothing of the rows left
    in the slot is read (and in the other order too). Then three rows that
    want 54 blocks of the pool's 47: the youngest is preempted, gives its blocks
    back and is teacher-forced through prefill again, its chunks rebuilding the
    carried rows from zero in the slot it gets: the reference's tokens still."""
    params, _ = model
    before = engine.stats()
    prompts = [_prompt(40 + i, n) for i, n in enumerate((70, 9, 33))]
    got = [engine.submit(p, max_new_tokens=10).result(timeout=300) for p in prompts]
    assert [engine.submit(p, max_new_tokens=10).result(timeout=300) for p in prompts[::-1]] == got[::-1]
    assert engine.stats()["state_resets"] - before["state_resets"] == 6 and engine.stats()["preemptions"] == before["preemptions"]
    for p, new in zip(prompts, got):
        assert _gaps(reference, params, p, new).max() < GAP_TOL
    prompts = [_prompt(10 + i, 90 + i) for i in range(3)]
    reqs = [engine.submit(p, max_new_tokens=50) for p in prompts]
    want = [r.result(timeout=300) for r in reqs]
    after = engine.stats()
    preempted = after["preemptions"] - before["preemptions"]
    assert preempted >= 1 and after["state_resets"] - before["state_resets"] == 6 + 3 + preempted
    for p, new in zip(prompts, want):
        assert _gaps(reference, params, p, new).max() < GAP_TOL


def test_a_configuration_states_a_conv_pattern_whole_and_linear_layers_keep_their_refusals():
    from ray_tpu.models.transformer import TransformerConfig, layer_rope

    ok = TransformerConfig(**MODEL)
    assert "gated short-convolution layers (conv_cache)" in ok.inference_only
    assert layer_rope(ok, "full") == () and layer_rope(dataclasses.replace(ok, full_layers_rope=False), "full") is None
    for bad, why in (
        (dict(conv_cache=1), "conv_cache .* must be at least 2"),
        (dict(first_dense_layers=2), "leading dense layers must be conv layers"),
        (dict(n_layers=8, layer_kinds=KINDS[:8]), "whole periods"),
        (dict(layer_kinds=("conv", "window") + KINDS[2:], sliding_window=8), "window layers beside conv layers"),
        (dict(layer_kinds=("conv", "linear") + KINDS[2:], linear_heads=2, linear_key_dim=8, linear_value_dim=8, num_experts=0,
              experts_per_token=0, first_dense_layers=0), "whole periods|linear-attention layers beside conv layers"),
        (dict(kv_lora_rank=16), "latent attention .* beside conv layers"),
    ):
        with pytest.raises(ValueError, match=why):
            TransformerConfig(**{**MODEL, **bad})
    # What LFM2's shape needs of a conv pattern stays refused beside linear layers, which nothing runs that way.
    linear = dict(n_layers=4, layer_kinds=("linear", "linear", "linear", "full"), linear_heads=2, linear_key_dim=8, linear_value_dim=8)
    TransformerConfig(**linear)
    with pytest.raises(ValueError, match="experts \\(num_experts > 0\\) in a pattern with linear layers"):
        TransformerConfig(**linear, num_experts=8, experts_per_token=2, d_expert=16)
    with pytest.raises(ValueError, match="leading dense layers \\(first_dense_layers\\) before linear layers"):
        TransformerConfig(**linear, first_dense_layers=1)


def test_a_replica_admits_as_many_queries_as_its_engine_has_slots():
    """The router holds a replica to ``max_concurrent_queries`` in flight, 100 unless the deployment says otherwise:
    a bound ``LLMDeployment`` whose engine has more slots than that raises the limit to its slots (at 128 slots and
    128 clients the chip otherwise ran 100 rows a step), and no other deployment's limit moves."""
    from ray_tpu import serve
    from ray_tpu.serve.llm.deployment import LLMDeployment

    limit = lambda engine, **how: serve.deployment(**how)(LLMDeployment).bind({}, engine_config=engine).deployment.config.max_concurrent_queries  # noqa: E731
    assert limit(dict(num_slots=128)) == 128 and limit(dict(num_slots=64)) == 100 and limit(None) == limit({}) == 100
    assert limit(dict(num_slots=128), max_concurrent_queries=256) == 256 and limit(dict(num_slots=16), max_concurrent_queries=8) == 16

    class Plain:
        pass

    assert serve.deployment(Plain).bind(1, x=2).deployment.config.max_concurrent_queries == 100
