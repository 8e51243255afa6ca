"""Linear-attention layers (PR 41): a gated delta rule three to one among full
attention, a recurrent state a slot beside the paged cache. The model is
Olmo-Hybrid's block at a toy size (a norm on each branch's output only, queries
and keys normed over the whole projection, no rotary, kernel-4 convolutions,
write strength over (0, 2)) over a stack (l, l, l, f) x 2: a scan of two
periods, each with a scan over its run of three. It is held to the benchmark's
plain float32 reference (``benchmarks/architectures/OlmoHybridForCausalLM/
reference.py``), whose full forward pass knows no cache, no chunk and no state
carried from anywhere: the recurrence a token at a time from zero.

Tolerances: program and reference are both float32 here (the CPU's matmuls are
exact float32), so they differ by the order of their sums alone: the chunked
scan solves 16-token sub-chunks together where the reference takes a token at a
time. Logits of order 3 agree to ~3e-5 after eight layers and 170 tokens;
``LOGIT_TOL`` is 2e-4, a few times that, and a gap of the system's greedy token
under the reference's best is held under ``GAP_TOL`` 1e-4 as for the other
patterns. A state kept in bfloat16 moves logits by ~1e-2 (the test below)."""

import dataclasses
import time

import numpy as np
import pytest

KINDS = ("linear", "linear", "linear", "full") * 2
MODEL = dict(
    vocab_size=128, d_model=64, n_layers=8, n_heads=4, n_kv_heads=4, head_dim=16, d_ff=96, max_seq_len=256,
    layer_kinds=KINDS, linear_heads=4, linear_key_dim=8, linear_value_dim=16, linear_conv=4, linear_neg_eigval=True,
    pre_norms=False, post_norms=True, qk_norm_whole=True, norm_eps=1e-6,
)
# The same model as its published ``config.json`` would state it: what the reference reads.
PUBLISHED = dict(
    hidden_size=64, num_hidden_layers=8, num_attention_heads=4, num_key_value_heads=4, intermediate_size=96,
    vocab_size=128, rms_norm_eps=1e-6, linear_num_key_heads=4, linear_num_value_heads=4, linear_key_head_dim=8,
    linear_value_head_dim=16, linear_conv_kernel_dim=4, linear_allow_neg_eigval=True,
    layer_types=["linear_attention" if k == "linear" else "full_attention" for k in KINDS],
)
ENGINE = dict(num_slots=3, block_size=8, max_model_len=256, prefill_chunk=16)
LOGIT_TOL, GAP_TOL = 2e-4, 1e-4


@pytest.fixture(scope="module")
def model():
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.transformer import TransformerConfig, init_params

    cfg = TransformerConfig(**MODEL, dtype=jnp.float32, param_dtype=jnp.float32)
    params = init_params(jax.random.PRNGKey(0), cfg)
    # Norm weights and dt_bias are drawn constant: scattered here, so that a norm left out or two swapped show.
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 64))
    for stack in ("linear_layers", "layers"):
        for name, leaf in params[stack].items():
            if name.endswith("norm") or name == "dt_bias":
                params[stack][name] = leaf * jax.random.uniform(next(keys), leaf.shape, minval=0.5, maxval=1.5)
    return params, cfg


@pytest.fixture(scope="module")
def reference():
    from benchmarks.harness import registry

    return registry.load_architecture(
        {"name": "this test", "architecture": "OlmoHybridForCausalLM", "bench_dir": registry.BENCH_DIR}, "reference"
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
    fed the reference-free greedy token. Returns per row (logits at every
    prompt position [n, V], logits of each decode step [steps, V], tokens fed)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.generate import init_paged_cache, paged_decode_chunk, paged_decode_step

    params, cfg = model
    bs, chunk, slots, n_max = 8, 16, 3, 12
    pool = init_paged_cache(cfg, 1 + slots * n_max, bs, state_slots=slots)
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
    for slot, prompt in rows:
        pos[slot] = len(prompt)
    live = np.zeros_like(tables)
    for slot, _ in rows:
        live[slot] = tables[slot]
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
def test_chunks_then_steps_through_the_state_give_the_references_logits(model, reference, lengths):
    """One padded chunk; one whole chunk; several chunks with a padded last;
    two rows of unequal length at once (an inactive third slot beside them):
    every prompt position's logits and twelve decode steps' against the
    reference's full forward pass over prompt + generated."""
    params, _ = model
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


def test_a_state_kept_in_bfloat16_or_a_padded_tail_that_moves_it_is_found_out(model, reference, monkeypatch):
    """The comparison above is tight enough to see the state rounded to
    bfloat16 between programs, and a padded last chunk allowed to write."""
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

    assert worst() < LOGIT_TOL
    rows = generate.state_rows
    monkeypatch.setattr(generate, "state_rows", lambda cfg: {**rows(cfg), "state": (rows(cfg)["state"][0], jnp.bfloat16)})
    assert worst() > 20 * LOGIT_TOL
    monkeypatch.setattr(generate, "state_rows", rows)
    chunk = generate._StateAccess
    monkeypatch.setattr(generate, "_StateAccess", lambda slots, fresh, n_valid: chunk(slots, fresh, jnp.full_like(n_valid, 16)))
    assert worst() > 20 * LOGIT_TOL


def test_full_layers_of_twelve_heads_cached_as_sixteen_give_the_references_logits(reference):
    """The pattern's full layers over a head count that a cached row pads (12
    to 16, as Olmo-Hybrid's 30 to 32): zero heads beside keys, values and
    queries, a decode row as eight query rows, the zero heads' outputs dropped.
    Logits, chunks and steps, against the reference, which pads nothing."""
    import importlib

    import jax
    import jax.numpy as jnp

    from ray_tpu.models.transformer import TransformerConfig, init_params

    generate = importlib.import_module("ray_tpu.models.generate")
    cfg = TransformerConfig(**dict(MODEL, n_heads=12, n_kv_heads=12, head_dim=8), dtype=jnp.float32, param_dtype=jnp.float32)
    assert generate._cache_heads(cfg) == 16
    params = init_params(jax.random.PRNGKey(5), cfg)
    published = dict(PUBLISHED, num_attention_heads=12, num_key_value_heads=12, head_dim=8)
    rows = [(1, _prompt(31, 11)), (0, _prompt(32, 45))]
    out, pool = _serve_by_hand((params, cfg), rows, steps=6)
    assert pool["k"].shape[-2:] == (16, 8) and not np.asarray(pool["k"][..., 12:, :]).any()
    for slot, prompt in rows:
        prefill_logits, step_logits, fed = out[slot]
        want = np.asarray(reference.sequence_logits(params, prompt + fed[:-1], published))
        np.testing.assert_allclose(prefill_logits, want[: len(prompt)], atol=LOGIT_TOL, rtol=0)
        np.testing.assert_allclose(np.stack(step_logits), want[len(prompt) :], atol=LOGIT_TOL, rtol=0)


def test_a_finished_request_hands_back_its_slots_state_and_the_check_holds_it(model, reference, monkeypatch):
    """``submit(return_state=True)``: the state a request's slot holds after
    the last token fed, while another slot serves. It is what the benchmark's
    serving check reads (``reference.make_layerwise_logits``, handed the
    engine's own ``params`` as the harness hands them): float32 here, it lies
    within 2e-4 of the recurrence's own and the logits come back as the plain
    forward pass's; kept in bfloat16 between programs it lies ~1e-3 out, and
    the sequence's logits come back NaN, which the harness reads as not
    correct. A model without linear layers refuses the option by name."""
    import importlib

    import jax
    import jax.numpy as jnp

    from ray_tpu.models.transformer import init_params
    from ray_tpu.serve.llm import LLMEngine

    generate = importlib.import_module("ray_tpu.models.generate")
    params, cfg = model
    prompt = _prompt(70, 45)
    logits_of = reference.make_layerwise_logits({**PUBLISHED, "check": {"state_gap_tol": 2e-4}})

    def served():
        eng = _engine(model)
        try:
            beside = eng.submit(_prompt(71, 30), max_new_tokens=40)
            req = eng.submit(prompt, max_new_tokens=9, return_state=True)
            new = req.result(timeout=300)
            assert req.state.shape == (6, 4, 8, 16) and req.state.any()
            padded = prompt + new + [0] * 7  # as the harness pads the shorter sequences of a check
            got = np.asarray(logits_of(eng.params, padded, list(range(len(prompt) - 1, len(prompt) + 8))))
            beside.result(timeout=300)
            return req.state, new, got
        finally:
            eng.shutdown()

    state, new, got = served()
    assert str(state.dtype) == "float32" and got.shape == (9, MODEL["vocab_size"])
    want = np.asarray(reference.sequence_logits(params, prompt + new, PUBLISHED))[len(prompt) - 1 : -1]
    np.testing.assert_allclose(got, want, atol=LOGIT_TOL, rtol=0)  # the same reference, a layer a program
    rows = generate.state_rows
    monkeypatch.setattr(generate, "state_rows", lambda cfg: {**rows(cfg), "state": (rows(cfg)["state"][0], jnp.bfloat16)})
    rounded, _, poisoned = served()
    assert str(rounded.dtype) == "bfloat16" and np.isnan(poisoned).all()
    assert 2e-4 < np.linalg.norm(rounded.astype(np.float32) - state) / np.linalg.norm(state) < 2e-2
    monkeypatch.setattr(generate, "state_rows", rows)
    plain = dataclasses.replace(cfg, layer_kinds=(), pre_norms=True, post_norms=False, qk_norm_whole=False,
                                linear_heads=0, linear_key_dim=0, linear_value_dim=0)
    eng = LLMEngine(init_params(jax.random.PRNGKey(0), plain), plain, **ENGINE)
    try:
        with pytest.raises(ValueError, match="return_state needs a model with linear-attention layers"):
            eng.submit(prompt, max_new_tokens=2, return_state=True)
    finally:
        eng.shutdown()


def test_the_engine_serves_the_references_tokens_rows_of_unequal_length_at_once(model, reference):
    """Chunked prefill, then the decode loop one step ahead, three requests on
    three slots and two more behind them: every token the engine drew (greedy)
    is the reference's best at its position, or within rounding of it."""
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
        assert st["decode_steps_with_chunk"] == 0  # two groups of leaves: the chunk is a program of its own
        assert st["state_resets"] == 5
        chunks = sum(-(-len(p) // 16) for p in prompts)
        assert st["chunk_tokens_valid"] == sum(map(len, prompts))
        assert st["chunk_tokens_padded"] == 16 * chunks - sum(map(len, prompts))
        groups = st["kv_groups"]
        assert set(groups) == {"full", "state"} and "window" not in groups
        assert groups["full"]["kv_token_bytes"] == 2 * 2 * 4 * 16 * 4  # two full layers, k and v, float32 here
        assert groups["state"] == dict(
            bytes_per_slot=6 * (4 * 8 * 16 * 4 + 3 * 4 * (8 + 8 + 16) * 4), num_slots=3, slots_in_use=0, kind="linear"
        )
        assert st["kv_token_bytes"] == groups["full"]["kv_token_bytes"]
    finally:
        eng.shutdown()


def test_a_slot_used_again_gives_a_fresh_engines_logits(model, reference):
    """One slot, three requests after one another: the second and third start
    from the state the first left in the slot's rows unless their first chunk
    zeroes it. Tokens equal a fresh engine's, and the reference's."""
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
    preempted, gives its blocks back, and is teacher-forced through prefill
    again: its chunks rebuild the state from zero in the slot it gets."""
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
        assert st["preemptions"] >= 1 and sum(r.preemptions for r in reqs) >= 1
        assert st["state_resets"] == 3 + st["preemptions"]
    finally:
        tight.shutdown()
    for p, new in zip(prompts, want):
        assert _gaps(reference, params, p, new).max() < GAP_TOL


def test_a_cancelled_run_ahead_row_harms_no_later_request(model, reference):
    """A request cancelled while a step that carries its row is in flight: the
    step still moves its slot's state, and nobody reads it. The requests that
    take the slot afterwards, and the one that ran beside it, draw the
    reference's tokens."""
    params, _ = model
    eng = _engine(model, num_slots=2)
    try:
        beside = eng.submit(_prompt(60, 21), max_new_tokens=60)
        doomed = eng.submit(_prompt(61, 40), max_new_tokens=200)
        stream = iter(doomed)
        for _ in range(5):
            next(stream)
        eng.cancel(doomed)
        later = [eng.submit(_prompt(62 + i, n), max_new_tokens=12) for i, n in enumerate((18, 47))]
        for req in (beside, *later):
            assert _gaps(reference, params, req.prompt, req.result(timeout=300)).max() < GAP_TOL
        deadline = time.monotonic() + 30
        while eng.stats()["cancelled"] < 1 and time.monotonic() < deadline:
            time.sleep(0.05)
        st = eng.stats()
        assert st["cancelled"] == 1 and st["kv_pool_not_donated"] == 0
    finally:
        eng.shutdown()


def test_what_a_recurrent_state_cannot_do_yet_is_refused_by_name(model):
    params, cfg = model
    from ray_tpu.serve.llm import LLMEngine

    for over, what in ((dict(role="prefill"), "role='prefill'"), (dict(role="decode"), "role='decode'"),
                       (dict(cluster_prefix=True), "cluster_prefix=True")):
        with pytest.raises(ValueError, match=f"{what} needs the KV transfer plane.*linear-attention layers.*recurrent state.*ROADMAP R5"):
            LLMEngine(params, cfg, **dict(ENGINE, **over))
    eng = _engine(model)
    try:
        with pytest.raises(ValueError, match="kv_import needs the KV transfer plane.*linear-attention layers"):
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


def test_the_dense_cache_refuses_linear_layers_by_name(model):
    import importlib

    import jax.numpy as jnp

    generate = importlib.import_module("ray_tpu.models.generate")
    params, cfg = model
    with pytest.raises(NotImplementedError, match="dense cache.*linear-attention layers.*paged cache"):
        generate.init_cache(cfg, 1, 32)
    with pytest.raises(NotImplementedError, match="dense cache.*linear-attention layers"):
        generate.generate(params, jnp.zeros((1, 4), jnp.int32), cfg, max_new_tokens=2)
    with pytest.raises(NotImplementedError, match="one group of key and value leaves only"):
        generate.paged_decode_step_with_chunk(params, None, None, {}, None, None, None, None, None, cfg)


def test_the_training_path_refuses_the_new_fields_by_name(model):
    import jax.numpy as jnp
    import optax

    from ray_tpu.models import transformer

    _, cfg = model
    with pytest.raises(NotImplementedError, match="layer pattern.*post-branch norms.*whole projection.*linear-attention layers.*"
                                                   r"write strength over \(0, 2\).*without input norms"):
        transformer.make_train_step(cfg, optax.sgd(0.1))
    plain = dict(layer_kinds=(), post_norms=False, qk_norm_whole=False, pre_norms=True, linear_heads=0, linear_key_dim=0,
                 linear_value_dim=0, linear_conv=4, linear_neg_eigval=False)
    for field in plain:
        if field == "layer_kinds":  # since PR 50 a pattern of window and full layers trains (linear layers: the regex above)
            one = dataclasses.replace(cfg, **{**plain, "layer_kinds": ("full",) * 8})
            assert one.inference_only == ""
            transformer.make_train_step(one, optax.sgd(0.1))
            continue
        one = dataclasses.replace(cfg, **{**plain, field: 3 if field == "linear_conv" else getattr(cfg, field)})
        with pytest.raises(NotImplementedError, match="forward_hidden cannot run.*no training block"):
            transformer.forward_hidden({}, jnp.zeros((1, 4), jnp.int32), one)
    transformer.make_train_step(dataclasses.replace(cfg, **plain), optax.sgd(0.1))  # and nothing else is in the way


def test_a_configuration_states_its_linear_layers_whole():
    from ray_tpu.models.transformer import TransformerConfig

    dims = dict(linear_heads=2, linear_key_dim=8, linear_value_dim=16)
    ok = TransformerConfig(n_layers=4, layer_kinds=["linear", "full"] * 2, **dims)
    assert ok.layer_kinds == ("linear", "full", "linear", "full")
    for bad, why in (
        (dict(n_layers=2, layer_kinds=("linear", "full")), "linear_heads, linear_key_dim and linear_value_dim must be set"),
        (dict(n_layers=3, layer_kinds=("linear", "full", "linear"), **dims), "whole periods: 3 layers, period 2"),
        (dict(n_layers=2, layer_kinds=("linear", "window"), sliding_window=8, **dims), "window layers beside linear layers"),
        (dict(n_layers=2, layer_kinds=("linear", "full"), num_experts=4, **dims), "experts .* in a pattern with linear layers"),
        (dict(n_layers=2, layer_kinds=("linear", "full"), first_dense_layers=1, **dims), "leading dense layers"),
        (dict(n_layers=2, layer_kinds=("linear", "full"), kv_lora_rank=8, **dims), "latent attention"),
        (dict(n_layers=2, layer_kinds=("linear", "global"), **dims), "'window' / 'full' / 'linear'"),
    ):
        with pytest.raises(ValueError, match=why):
            TransformerConfig(**bad)


def test_the_state_groups_bytes_are_what_the_pools_leaves_hold(model):
    import math

    from ray_tpu.models.generate import cache_token_bytes, init_paged_cache, state_slot_bytes

    _, cfg = model
    pool = init_paged_cache(cfg, 11, 8, state_slots=5)
    assert pool["state"].shape == (6, 5, 4, 8, 16) and str(pool["state"].dtype) == "float32"
    assert pool["conv"].shape == (6, 5, 3, 4 * (8 + 8 + 16))
    assert set(pool) == {"k", "v", "state", "conv"} and pool["k"].shape == (2, 11, 8, 4, 16)
    held = sum(math.prod(pool[n].shape) * pool[n].dtype.itemsize for n in ("state", "conv"))
    assert state_slot_bytes(cfg) * 5 == held
    assert cache_token_bytes(cfg) == {"full": sum(math.prod(pool[n].shape[3:]) * 2 * 4 for n in ("k", "v"))}
    assert state_slot_bytes(dataclasses.replace(cfg, layer_kinds=(), pre_norms=True)) == 0


def test_counters_reach_the_metrics_endpoint(model):
    """``state_resets`` and the chunk's tokens beside the engine's other plain
    ints, folded into ``ray_tpu_serve_llm_*`` instruments with the state group's gauges."""
    from ray_tpu._private import self_metrics
    from ray_tpu.serve.llm.stats import LLM

    inst = self_metrics.instruments()
    total = lambda key: sum(inst[key]._values.values())  # noqa: E731
    self_metrics._collect_serve_llm_stats()  # whatever earlier tests left
    before = (LLM.state_resets, LLM.chunk_tokens_valid, LLM.chunk_tokens_padded)
    resets0, tokens0 = total("serve_llm_state_resets"), dict(inst["serve_llm_chunk_tokens"]._values)
    eng = _engine(model)
    try:
        eng.submit(_prompt(3, 21), max_new_tokens=3).result(timeout=300)
        assert (LLM.state_resets, LLM.chunk_tokens_valid, LLM.chunk_tokens_padded) == (before[0] + 1, before[1] + 21, before[2] + 11)
        self_metrics._collect_serve_llm_stats()
        assert total("serve_llm_state_resets") - resets0 == 1
        moved = {k: v - tokens0.get(k, 0) for k, v in inst["serve_llm_chunk_tokens"]._values.items()}
        assert sorted(moved.values()) == [11, 21] and len(moved) == 2  # kind=padded, kind=valid
        assert total("serve_llm_state_bytes") == eng.state_slot_bytes * 3 and total("serve_llm_state_slots") == 0
        assert inst["serve_llm_state_resets"].name == "ray_tpu_serve_llm_state_resets_total"
        assert inst["serve_llm_chunk_tokens"].name == "ray_tpu_serve_llm_prefill_chunk_tokens_total"
    finally:
        eng.shutdown()
