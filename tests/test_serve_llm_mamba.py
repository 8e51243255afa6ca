"""Single-mixer blocks (PR 43): Mamba-2 state-space blocks, experts blocks
without a gate matrix of which the program holds a share, and attention blocks
without rotary, each ``x + mixer(RMSNorm(x))``. The model is Nemotron-3-Nano's
block at a toy size over the pattern ``M E M * E`` x 2: three stacks by kind, a
scan of two periods. It is held to the benchmark's plain float32 reference
(``benchmarks/architectures/NemotronHForCausalLM/reference.py``), whose full
forward pass knows no cache, no chunk, no state carried from anywhere and no
sorting of assignments: the recurrence a token at a time from zero, every held
expert over every token.

Tolerances: program and reference are both float32 here (the CPU's matmuls are
exact float32), so they differ by the order of their sums alone: the chunked
scan takes 16-token chunks (of one sub-chunk) where the reference takes a token
at a time. ``LOGIT_TOL`` 2e-4 and ``GAP_TOL`` 1e-4 are the other patterns'. A
state kept in bfloat16 moves logits by far more (the test below)."""

import dataclasses

import numpy as np
import pytest

PATTERN = "MEM*E" * 2
KINDS = tuple({"M": "mamba", "E": "experts", "*": "full"}[c] for c in PATTERN)
MODEL = dict(
    vocab_size=128, d_model=64, n_layers=10, n_heads=4, n_kv_heads=2, head_dim=16, max_seq_len=256, layer_kinds=KINDS,
    mamba_heads=8, mamba_head_dim=8, ssm_state=16, ssm_groups=2, mamba_conv=4,
    num_experts=8, experts_per_token=3, d_expert=24, num_shared_experts=2, routed_scaling_factor=2.5,
    expert_activation="relu2", expert_share=(0, 2),
)
# The same model as its published ``config.json`` would state it, cut to this chip's share: what the reference reads.
PUBLISHED = dict(
    hidden_size=64, num_hidden_layers=10, hybrid_override_pattern=PATTERN, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, vocab_size=128, layer_norm_epsilon=1e-5, mamba_num_heads=8, mamba_head_dim=8, ssm_state_size=16,
    n_groups=2, conv_kernel=4, n_routed_experts=4, num_experts_per_tok=3, moe_intermediate_size=24,
    moe_shared_expert_intermediate_size=48, n_shared_experts=1, routed_scaling_factor=2.5,
    published={"n_routed_experts": 8}, deployment={"expert_parallel": {"chips": 2, "index": 0}},
)
ENGINE = dict(num_slots=3, block_size=8, max_model_len=256, prefill_chunk=16)
LOGIT_TOL, GAP_TOL = 2e-4, 1e-4


def _scattered(params, seed=1):
    """Norm weights, D and the convolution's bias are drawn constant or small:
    scattered here, so that a norm left out or two swapped show."""
    import jax

    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 64))
    for stack in ("mamba_layers", "expert_layers", "layers"):
        for name, leaf in params[stack].items():
            if name.endswith("norm") or name == "D":
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
        {"name": "this test", "architecture": "NemotronHForCausalLM", "bench_dir": registry.BENCH_DIR}, "reference"
    )


def _engine(model, **over):
    from ray_tpu.serve.llm import LLMEngine

    params, cfg = model
    return LLMEngine(params, cfg, **dict(ENGINE, **over))


def _prompt(seed, n):
    return np.random.default_rng(seed).integers(0, MODEL["vocab_size"], n).tolist()


def _gaps(reference, params, prompt, new):
    """How far the reference's logit of each token the system drew lies under
    the reference's largest, at the position that predicts it."""
    logits = np.asarray(reference.sequence_logits(params, prompt + new, PUBLISHED))
    rows = np.arange(len(prompt) - 1, len(prompt) + len(new) - 1)
    return logits[rows].max(axis=-1) - logits[rows, np.asarray(new)]


def _serve_by_hand(model, rows, steps):
    """The engine's two programs' arithmetic, driven by hand so that LOGITS
    come back: each of ``rows`` = (slot, prompt) prefilled in chunks of 16 (the
    last one padded), then ``steps`` decode steps of all rows at once, each row
    fed its own greedy token. Returns per row (logits at every prompt position
    [n, V], logits of each decode step [steps, V], tokens fed), and the pool."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.generate import (
        MOE_CHOICE, MOE_COUNTS, init_moe_choice, init_moe_counts, init_paged_cache, paged_decode_chunk, paged_decode_step,
    )

    params, cfg = model
    bs, chunk, slots, n_max = 8, 16, 3, 12
    pool = init_paged_cache(cfg, 1 + slots * n_max, bs, state_slots=slots)
    pool.update({MOE_COUNTS: init_moe_counts(cfg), MOE_CHOICE: init_moe_choice(cfg, 1 + slots * n_max, bs)})
    tables = np.zeros((slots, n_max), np.int32)
    prefill = jax.jit(lambda p, t, c, table, pos, valid_to, slot, fresh: paged_decode_chunk(
        p, t, c, table, pos, cfg, valid_to=valid_to, state_slots=slot, state_fresh=fresh))
    step = jax.jit(lambda p, t, c, table, pos: paged_decode_step(p, t, c, table, pos, cfg))
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


@pytest.mark.parametrize("lengths", [(13,), (16,), (50,), (11, 77)], ids=lambda ls: "-".join(map(str, ls)))
def test_chunks_then_steps_through_three_stacks_give_the_references_logits(model, reference, lengths):
    """One padded chunk; one whole chunk; several chunks with a padded last;
    two rows of unequal length at once (an inactive third slot beside them):
    every prompt position's logits and twelve decode steps' against the
    reference's full forward pass over prompt + generated, both holding experts
    0-3 of 8 and routing over all 8."""
    params, cfg = model
    rows = [(2 - i, _prompt(20 + n, n)) for i, n in enumerate(lengths)]  # slots 2, 1: not the row's index in the call
    out, pool = _serve_by_hand(model, rows, steps=12)
    for slot, prompt in rows:
        prefill_logits, step_logits, fed = out[slot]
        want = np.asarray(reference.sequence_logits(params, prompt + fed[:-1], PUBLISHED))
        np.testing.assert_allclose(prefill_logits, want[: len(prompt)], atol=LOGIT_TOL, rtol=0)
        np.testing.assert_allclose(np.stack(step_logits), want[len(prompt) :], atol=LOGIT_TOL, rtol=0)
    idle = sorted(set(range(3)) - {slot for slot, _ in rows})
    assert not np.asarray(pool["state"][:, idle]).any() and not np.asarray(pool["conv"][:, idle]).any()  # an inactive slot's row moved nothing
    assert np.asarray(pool["state"][:, rows[0][0]]).any()
    # The counters: decode steps and chunks apart, four experts blocks, assignments to the 4 experts held and to all 8.
    counts = np.asarray(pool["moe_counts"])
    assert counts.shape == (2, 4, 4 + 4)
    tokens = sum(len(p) for _, p in rows)
    assert (counts[0, :, 7] == 12 * len(rows) * 3).all() and (counts[1, :, 7] == tokens * 3).all()
    held = counts[:, :, :4].sum(axis=-1)
    assert (held < counts[:, :, 7]).all() and (held > 0).all()
    assert (counts[0, :, 6] == 12).all()  # a step counts by what it routed, held or not


@pytest.mark.parametrize("widths", [(64, 24), (640, 576)], ids=["grouped", "every-expert"])
def test_the_shares_parts_add_up_to_the_uncut_experts_block(reference, widths):
    """The share test of the model-configs guide: the parts of an experts
    block's result that shares (0, 2) and (1, 2) give, the shared expert (which
    every chip computes alike) counted once, add up to what the uncut reference
    gives for the whole block, all 8 experts held. And the (0, 1) program gives
    the uncut block itself. Both ways an expert's matmuls run: grouped
    (``jax.lax.ragged_dot``) at the toy widths, and every held expert over
    every row at widths the grouped kernel does not tile (640 and 576, as
    Nemotron-3-Nano's 2688 and 1856 are no multiples of 512)."""
    import importlib

    import jax
    import jax.numpy as jnp

    from ray_tpu.models.transformer import TransformerConfig, init_params
    from ray_tpu.parallel.moe import grouped_matmul_tiles

    generate = importlib.import_module("ray_tpu.models.generate")
    D, F = widths
    assert grouped_matmul_tiles(D, F) == (D == 64) and grouped_matmul_tiles(2048, 1536) and not grouped_matmul_tiles(2688, 1856)
    whole_cfg = TransformerConfig(**dict(MODEL, d_model=D, d_expert=F, expert_share=(0, 1)), dtype=jnp.float32, param_dtype=jnp.float32)
    whole = init_params(jax.random.PRNGKey(3), whole_cfg)["expert_layers"]
    assert whole["wi_e"].shape == (4, 8, D, F) and "wg_e" not in whole and "wg_s" not in whole
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 37, D))
    uncut = dict(PUBLISHED, hidden_size=D, n_routed_experts=8, deployment={"expert_parallel": {"chips": 1, "index": 0}})
    for block in range(4 if D == 64 else 1):
        take = lambda stack: {n: leaf[block] for n, leaf in stack.items()}  # noqa: E731
        with jax.default_matmul_precision("highest"):
            w = reference._take(whole, reference.ROUTER_LEAVES, block)
            h = reference.rms_norm(x.reshape(-1, D), w["norm"], 1e-5)
            want, _ = reference.experts_mixer(whole, block, w, h, uncut)
            shared = reference.relu2(reference._take(whole, reference.SHARED_LEAVES, block), h)
        parts, sent = [], []
        for index in range(2):
            cfg = dataclasses.replace(whole_cfg, expert_share=(index, 2))
            mine = {n: leaf[:, 4 * index : 4 * index + 4] if n in ("wi_e", "wo_e") else leaf for n, leaf in whole.items()}
            out, counts, chosen = generate._mlp(take(mine), x, cfg)
            parts.append(np.asarray(out - x).reshape(-1, D) - np.asarray(shared))
            sent.append(int(np.asarray(counts).sum()))
            assert counts.shape == (4,) and chosen.shape == (2, 37, 3) and int(chosen.max()) > 3  # all six ids, among all 8
        assert sum(sent) == 2 * 37 * 3 and min(sent) > 0  # every assignment is held by exactly one share
        assert max(np.abs(part).max() for part in parts) > 0.1
        np.testing.assert_allclose(parts[0] + parts[1] + np.asarray(shared), np.asarray(want), atol=2e-5, rtol=0)
        out, counts, _ = generate._mlp(take(whole), x, whole_cfg)
        np.testing.assert_allclose(np.asarray(out - x).reshape(-1, D), np.asarray(want), atol=2e-5, rtol=0)
        assert counts.shape == (8,) and int(counts.sum()) == 2 * 37 * 3


def test_a_state_kept_in_bfloat16_or_a_padded_tail_that_moves_it_is_found_out(model, reference, monkeypatch):
    """The comparison above is tight enough to see the state rounded to
    bfloat16 between programs, and a padded last chunk allowed to write. The
    rounding moves these logits by 5e-4 only, ten times what float32 leaves and
    a fortieth of what it does to a gated delta rule's: a time step of 0.001-0.1
    writes little into a state whose output the skip ``D x`` and the gate
    outweigh. Why the serving check reads the state itself (the test below)."""
    import importlib

    import jax.numpy as jnp

    generate = importlib.import_module("ray_tpu.models.generate")
    params, _ = model
    prompt = _prompt(7, 50)

    def worst():  # over the prompt's positions and four decode steps behind a padded last chunk
        out, _ = _serve_by_hand(model, [(0, prompt)], steps=4)
        got = np.concatenate([out[0][0], np.stack(out[0][1])])
        want = np.asarray(reference.sequence_logits(params, prompt + out[0][2][:-1], PUBLISHED))
        return np.abs(got - want).max()

    exact = worst()
    assert exact < LOGIT_TOL / 2
    rows = generate.state_rows
    monkeypatch.setattr(generate, "state_rows", lambda cfg: {**rows(cfg), "state": (rows(cfg)["state"][0], jnp.bfloat16)})
    assert worst() > max(2 * LOGIT_TOL, 5 * exact)
    monkeypatch.setattr(generate, "state_rows", rows)
    chunk = generate._StateAccess
    monkeypatch.setattr(generate, "_StateAccess", lambda slots, fresh, n_valid: chunk(slots, fresh, jnp.full_like(n_valid, 16)))
    assert worst() > 20 * LOGIT_TOL


def test_the_engine_serves_the_references_tokens_and_counts_what_it_holds(model, reference):
    """Chunked prefill, then the decode loop one step ahead, three requests on
    three slots and two more behind them: every token the engine drew (greedy)
    is the reference's best at its position, or within rounding of it. The
    expert counters count the held assignments and, beside them, all."""
    params, _ = model
    eng = _engine(model)
    try:
        prompts = [_prompt(i, n) for i, n in enumerate((37, 5, 90, 130, 16))]
        reqs = [eng.submit(p, max_new_tokens=24) for p in prompts]
        for p, r in zip(prompts, reqs):
            assert _gaps(reference, params, p, r.result(timeout=300)).max() < GAP_TOL
        st = eng.stats()
        assert st["kv_pool_not_donated"] == 0 and st["host_logit_rows"] == 0
        assert st["decode_steps_run_ahead"] > 0.5 * st["decode_steps"]  # the steps were in flight
        assert st["decode_steps_with_chunk"] == 0  # groups of leaves beside keys and values: the chunk is a program of its own
        assert st["state_resets"] == 5
        assert st["chunk_tokens_valid"] == sum(map(len, prompts))
        groups = st["kv_groups"]
        assert set(groups) == {"full", "state"}
        assert groups["full"]["kv_token_bytes"] == 2 * 2 * 2 * 16 * 4  # two attention blocks, k and v, two KV heads, float32 here
        assert groups["state"] == dict(
            bytes_per_slot=4 * (8 * 8 * 16 * 4 + 3 * (8 * 8 + 2 * 2 * 16) * 4), num_slots=3, slots_in_use=0, kind="mamba"
        )
        moe = st["moe"]
        for kind, tokens in (("decode", 5 * 23), ("prefill", sum(map(len, prompts)))):
            assert moe[kind]["assignments_all"] == [tokens * 3] * 4  # four experts blocks, three experts a token
            held = [sum(block) for block in moe[kind]["assignments"]]
            assert all(len(block) == 4 for block in moe[kind]["assignments"])  # the 4 experts held of 8
            assert all(0.25 * tokens * 3 < h < 0.75 * tokens * 3 for h in held), held
    finally:
        eng.shutdown()


def test_a_finished_request_hands_back_state_and_experts_and_the_check_holds_them(model, reference, monkeypatch):
    """``submit(return_state=True, return_routed_experts=True)``: what the
    benchmark's serving check reads (``reference.make_layerwise_logits``, handed
    the engine's own ``params`` as the harness hands them). Float32 here, block
    0's state lies within 2e-4 of the recurrence's own and the logits come back
    as the plain forward pass's; kept in bfloat16 between programs it lies
    further out and the sequence's logits come back NaN."""
    import importlib

    import jax.numpy as jnp

    generate = importlib.import_module("ray_tpu.models.generate")
    params, cfg = model
    prompt = _prompt(70, 45)
    logits_of = reference.make_layerwise_logits({**PUBLISHED, "check": {"state_gap_tol": 2e-4}})

    def served():
        eng = _engine(model)
        try:
            beside = eng.submit(_prompt(71, 30), max_new_tokens=40)
            req = eng.submit(prompt, max_new_tokens=9, return_state=True, return_routed_experts=True)
            new = req.result(timeout=300)
            assert req.state.shape == (4, 8, 8, 16) and req.state.any()
            assert req.routed_experts.shape == (45 + 8, 4, 3) and req.routed_experts.max() > 3  # ids among all 8, two words a token
            padded = prompt + new + [0] * 7  # as the harness pads the shorter sequences of a check
            got = np.asarray(logits_of(eng.params, padded, list(range(len(prompt) - 1, len(prompt) + 8))))
            beside.result(timeout=300)
            return req.state, new, got
        finally:
            eng.shutdown()

    state, new, got = served()
    assert str(state.dtype) == "float32" and got.shape == (9, MODEL["vocab_size"])
    want = np.asarray(reference.sequence_logits(params, prompt + new, PUBLISHED))[len(prompt) - 1 : -1]
    np.testing.assert_allclose(got, want, atol=LOGIT_TOL, rtol=0)  # the same reference, a block a program
    rows = generate.state_rows
    monkeypatch.setattr(generate, "state_rows", lambda cfg: {**rows(cfg), "state": (rows(cfg)["state"][0], jnp.bfloat16)})
    rounded, _, poisoned = served()
    assert str(rounded.dtype) == "bfloat16" and np.isnan(poisoned).all()


def test_a_slot_used_again_after_a_longer_request_reads_no_trace_of_it(model, reference):
    """One slot, three requests after one another, the first the longest: the
    second and third start from the state the first left in the slot's rows
    unless their first chunk zeroes it. Tokens equal a fresh engine's, and the
    reference's."""
    params, _ = model
    prompts = [_prompt(40 + i, n) for i, n in enumerate((70, 9, 33))]
    eng = _engine(model, num_slots=1)
    try:
        got = [eng.submit(p, max_new_tokens=10).result(timeout=300) for p in prompts]
        assert eng.stats()["state_resets"] == 3
    finally:
        eng.shutdown()
    for p, new in zip(prompts, got):
        fresh = _engine(model, num_slots=1)
        try:
            assert fresh.submit(p, max_new_tokens=10).result(timeout=300) == new
        finally:
            fresh.shutdown()
        assert _gaps(reference, params, p, new).max() < GAP_TOL


def test_preemption_and_readmission_rebuild_the_state(model, reference):
    """A full group of 17 blocks for three rows that want 27: the youngest is
    preempted and teacher-forced through prefill again: its chunks rebuild the
    state from zero in the slot it gets."""
    params, _ = model
    prompts = [_prompt(10 + i, n) for i, n in enumerate((30, 41, 52))]
    roomy = _engine(model)
    try:
        want = [roomy.submit(p, max_new_tokens=30).result(timeout=300) for p in prompts]
    finally:
        roomy.shutdown()
    tight = _engine(model, num_blocks=18)
    try:
        reqs = [tight.submit(p, max_new_tokens=30) for p in prompts]
        assert [r.result(timeout=300) for r in reqs] == want
        st = tight.stats()
        assert st["preemptions"] >= 1 and st["state_resets"] == 3 + st["preemptions"]
    finally:
        tight.shutdown()


def test_what_a_state_space_block_cannot_do_yet_is_refused_by_name(model):
    params, cfg = model
    from ray_tpu.serve.llm import LLMEngine

    for over, what in ((dict(role="prefill"), "role='prefill'"), (dict(role="decode"), "role='decode'"),
                       (dict(cluster_prefix=True), "cluster_prefix=True")):
        with pytest.raises(ValueError, match=f"{what} needs the KV transfer plane.*Mamba-2 state-space blocks.*recurrent state.*ROADMAP R5"):
            LLMEngine(params, cfg, **dict(ENGINE, **over))
    eng = _engine(model)
    try:
        with pytest.raises(ValueError, match="kv_import needs the KV transfer plane.*Mamba-2 state-space blocks"):
            eng.submit(_prompt(1, 20), max_new_tokens=2, kv_import={"oid": "x", "kv_pos": 16})
        # The block-hash prefix cache: the same prompt twice registers no block, takes no hit, counts no miss.
        prompt = _prompt(2, 50)
        first = eng.submit(prompt, max_new_tokens=6)
        got = first.result(timeout=300)
        again = eng.submit(prompt, max_new_tokens=6)
        assert again.result(timeout=300) == got and again.cached_tokens == 0 and first.cached_tokens == 0
        st = eng.stats()
        assert (st["prefix_hit_blocks"], st["prefix_miss_blocks"], st["cached_blocks"]) == (0, 0, 0)
    finally:
        eng.shutdown()


def test_the_dense_cache_refuses_the_new_kinds_by_name(model):
    import importlib

    import jax.numpy as jnp

    generate = importlib.import_module("ray_tpu.models.generate")
    params, cfg = model
    with pytest.raises(NotImplementedError, match="dense cache.*Mamba-2 state-space blocks.*'mamba'.*paged cache"):
        generate.init_cache(cfg, 1, 32)
    with pytest.raises(NotImplementedError, match="dense cache.*Mamba-2 state-space blocks"):
        generate.generate(params, jnp.zeros((1, 4), jnp.int32), cfg, max_new_tokens=2)
    no_mamba = dataclasses.replace(cfg, n_layers=2, layer_kinds=("full", "experts"))
    with pytest.raises(NotImplementedError, match="dense cache.*single-mixer blocks.*'experts'"):
        generate.init_cache(no_mamba, 1, 32)
    with pytest.raises(NotImplementedError, match="one group of key and value leaves only"):
        generate.paged_decode_step_with_chunk(params, None, None, {}, None, None, None, None, None, cfg)


def test_the_training_path_refuses_the_new_fields_by_name(model):
    import jax.numpy as jnp
    import optax

    from ray_tpu.models import transformer

    _, cfg = model
    with pytest.raises(NotImplementedError, match=r"layer pattern.*Mamba-2 state-space blocks \(mamba_heads\).*\(mamba_head_dim\).*"
                                                   r"\(ssm_state\).*\(ssm_groups\).*gate matrix \(expert_activation\)"):
        transformer.make_train_step(cfg, optax.sgd(0.1))
    plain = dict(layer_kinds=(), mamba_heads=0, mamba_head_dim=0, ssm_state=0, ssm_groups=1, mamba_conv=4,
                 experts_per_token=0, num_experts=0, d_expert=0, num_shared_experts=0, routed_scaling_factor=1.0,
                 expert_activation="swiglu", expert_share=(0, 1), head_dim=16, n_kv_heads=4)
    for field, value in (("mamba_heads", 8), ("mamba_head_dim", 8), ("ssm_state", 16), ("ssm_groups", 2), ("mamba_conv", 3),
                         ("expert_activation", "relu2")):
        one = dataclasses.replace(cfg, **{**plain, field: value})
        with pytest.raises(NotImplementedError, match="forward_hidden cannot run.*no training block"):
            transformer.forward_hidden({}, jnp.zeros((1, 4), jnp.int32), one)
    shared = dataclasses.replace(cfg, **{**plain, "num_experts": 8, "experts_per_token": 2, "d_expert": 8, "expert_share": (1, 2)})
    # Since PR 50 a held share of the experts trains (tests/test_moe_training.py: the shares add up, and the share
    # agrees with the benchmark's reference); what is still in such a configuration's way is its router's bias.
    assert "expert_share" not in shared.inference_only and "router_bias" in shared.inference_only
    transformer.make_train_step(dataclasses.replace(shared, router_bias=False), optax.sgd(0.1))
    transformer.make_train_step(dataclasses.replace(cfg, **plain), optax.sgd(0.1))  # and nothing else is in the way


def test_a_configuration_states_its_single_mixer_blocks_whole():
    from ray_tpu.models.transformer import TransformerConfig

    mamba = dict(mamba_heads=4, mamba_head_dim=8, ssm_state=16, ssm_groups=2)
    experts = dict(num_experts=8, experts_per_token=2, d_expert=16)
    ok = TransformerConfig(n_layers=4, layer_kinds=["mamba", "experts"] * 2, expert_share=[1, 4], **mamba, **experts)
    assert ok.layer_kinds == ("mamba", "experts", "mamba", "experts") and ok.single_mixer
    assert ok.expert_share == (1, 4) and ok.held_experts == 2
    assert not TransformerConfig().single_mixer and TransformerConfig(num_experts=8).held_experts == 8
    for bad, why in (
        (dict(n_layers=2, layer_kinds=("mamba", "full")), "mamba_heads, mamba_head_dim and ssm_state must be set"),
        (dict(n_layers=2, layer_kinds=("mamba", "full"), **dict(mamba, ssm_groups=3)), "ssm_groups must divide mamba_heads"),
        (dict(n_layers=2, layer_kinds=("experts", "full")), "experts blocks: experts_per_token"),
        (dict(n_layers=3, layer_kinds=("mamba", "full", "mamba"), **mamba), "whole periods: 3 blocks, period 2"),
        (dict(n_layers=2, layer_kinds=("mamba", "window"), sliding_window=8, **mamba), "window layers beside single-mixer blocks"),
        (dict(n_layers=2, layer_kinds=("mamba", "linear"), linear_heads=2, linear_key_dim=8, linear_value_dim=8, **mamba),
         "linear-attention layers beside single-mixer blocks"),
        (dict(n_layers=2, layer_kinds=("mamba", "full"), first_dense_layers=1, **mamba), "leading dense layers"),
        (dict(n_layers=2, layer_kinds=("mamba", "full"), kv_lora_rank=8, **mamba), "latent attention"),
        (dict(n_layers=2, layer_kinds=("mamba", "full"), post_norms=True, **mamba), "post_norms .* in a single-mixer block"),
        (dict(n_layers=2, layer_kinds=("mamba", "global"), **mamba), "'window' / 'full' / 'linear' / 'mamba' / 'experts'"),
        (dict(expert_activation="gelu"), "expert_activation 'gelu'"),
        (dict(expert_share=(2, 2), **experts), r"expert_share \(2, 2\)"),
        (dict(expert_share=(0, 3), **experts), "of dividing num_experts = 8"),
        (dict(expert_share=(0, 2)), "a model with routed experts"),
    ):
        with pytest.raises(ValueError, match=why):
            TransformerConfig(**bad)


def test_the_state_groups_bytes_are_what_the_pools_leaves_hold(model):
    import math

    from ray_tpu.models.generate import cache_token_bytes, init_paged_cache, state_kind, state_slot_bytes

    _, cfg = model
    pool = init_paged_cache(cfg, 11, 8, state_slots=5)
    assert pool["state"].shape == (4, 5, 8, 8, 16) and str(pool["state"].dtype) == "float32"
    assert pool["conv"].shape == (4, 5, 3, 8 * 8 + 2 * 2 * 16)
    assert set(pool) == {"k", "v", "state", "conv"} and pool["k"].shape == (2, 11, 8, 2, 16)
    held = sum(math.prod(pool[n].shape) * pool[n].dtype.itemsize for n in ("state", "conv"))
    assert state_slot_bytes(cfg) * 5 == held and state_kind(cfg) == "mamba"
    assert cache_token_bytes(cfg) == {"full": sum(math.prod(pool[n].shape[3:]) * 2 * 4 for n in ("k", "v"))}


def test_a_heads_decay_and_time_step_are_drawn_as_published(model):
    """``A_log`` and ``dt_bias``: A uniform over (1, 16), the time step
    log-uniform over (0.001, 0.1), both float32 whatever the weights' dtype, so
    that a head's decay a token lies between ~0.2 and 0.999: neither 0 nor 1."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.transformer import TransformerConfig, init_params

    cfg = TransformerConfig(**dict(MODEL, mamba_heads=64, ssm_groups=8), dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    stack = init_params(jax.random.PRNGKey(9), cfg)["mamba_layers"]
    assert {str(stack[n].dtype) for n in ("A_log", "dt_bias", "D")} == {"float32"} and str(stack["w_z"].dtype) == "bfloat16"
    A, dt = np.exp(np.asarray(stack["A_log"])), np.log1p(np.exp(np.asarray(stack["dt_bias"])))
    assert 1.0 <= A.min() < 3.0 and 13.0 < A.max() <= 16.0
    assert 0.001 <= dt.min() < 0.003 and 0.04 < dt.max() <= 0.1001
    decay = np.exp(-A * dt)
    assert 0.2 < decay.min() and decay.max() < 0.9995


def test_the_expert_counters_reach_the_metrics_endpoint(model):
    """Assignments to the experts held and to the others', folded from the
    device's counters into ``ray_tpu_serve_llm_moe_assignments_total{held=}``
    as the scheduler reads them (a flush asks, the next one finds them)."""
    import time

    from ray_tpu._private import self_metrics
    from ray_tpu.serve.llm.stats import LLM

    inst = self_metrics.instruments()
    self_metrics._collect_serve_llm_stats()  # whatever earlier tests left
    before = dict(inst["serve_llm_moe_assignments"]._values)
    held0, else0 = LLM.moe_assignments_held, LLM.moe_assignments_elsewhere
    eng = _engine(model)
    try:
        eng.submit(_prompt(3, 21), max_new_tokens=3).result(timeout=300)
        self_metrics._collect_serve_llm_stats()  # asks the scheduler, waits for nothing
        deadline = time.monotonic() + 30
        while LLM.moe_assignments_held + LLM.moe_assignments_elsewhere - held0 - else0 < (21 + 2) * 3 * 4 and time.monotonic() < deadline:
            time.sleep(0.02)
        assert LLM.moe_assignments_held - held0 + LLM.moe_assignments_elsewhere - else0 == (21 + 2) * 3 * 4
        assert LLM.moe_assignments_held > held0 and LLM.moe_assignments_elsewhere > else0
        self_metrics._collect_serve_llm_stats()
        moved = {k: v - before.get(k, 0) for k, v in inst["serve_llm_moe_assignments"]._values.items()}
        assert sum(moved.values()) == (21 + 2) * 3 * 4 and len(moved) == 2  # held=true, held=false
        assert inst["serve_llm_moe_assignments"].name == "ray_tpu_serve_llm_moe_assignments_total"
    finally:
        eng.shutdown()
