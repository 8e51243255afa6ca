"""Generation by diffusion over blocks (PR 56): ``cfg.block_diffusion = B``, a row
owns a block of B aligned positions, a pass feeds all of them under the mask
"causal between blocks, two-way inside one", transfers some of what it draws
at the positions still masked, and a commit pass makes the block the cache's
and emits up to B tokens at once. The model is SDAR-30B-A3B-Chat's layer at a
toy size (QK-normed GQA with ``head_dim`` apart from the quotient, softmax
top-2-of-8 experts without a router bias, an untied head), held to the
benchmark's plain float32 reference
(``benchmarks/architectures/SDARMoeForCausalLM/reference.py``), which knows no
cache and no chunk: one forward over the final sequence and every noisy copy
of a block.

Tolerances: program and reference are both float32 here (the CPU's matmuls
are exact float32), so they differ by the order of their sums alone:
``LOGIT_TOL`` 2e-4 on logits of standard deviation ~1, the other patterns'
(``tests/test_serve_llm_conv.py``)."""

import dataclasses
import functools

import numpy as np
import pytest

B, MASK = 4, 127
MODEL = dict(
    vocab_size=128, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2, head_dim=32, d_ff=96, max_seq_len=256,
    qk_norm=True, rope_theta=1e6, norm_eps=1e-6, num_experts=8, experts_per_token=2, d_expert=32,
    router_score="softmax", router_bias=False, block_diffusion=B, mask_token_id=MASK,
)
# The same model as its published ``config.json`` would state it: what the reference reads.
PUBLISHED = dict(
    hidden_size=64, num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2, head_dim=32, vocab_size=128,
    rms_norm_eps=1e-6, rope_theta=1e6, moe_intermediate_size=32, num_experts=8, num_experts_per_tok=2,
    block_length=B, mask_token_id=MASK,
)
ENGINE = dict(num_slots=3, block_size=8, max_model_len=128, prefill_chunk=16, num_blocks=48)
LOGIT_TOL = 2e-4


def _scattered(params, seed=1):
    """Norm weights are drawn constant: scattered here, so that a norm left out or two swapped show."""
    import jax

    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 16))
    for name, leaf in params["layers"].items():
        if name.endswith("norm"):
            params["layers"][name] = leaf * jax.random.uniform(next(keys), leaf.shape, minval=0.5, maxval=1.5)
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
        {"name": "this test", "architecture": "SDARMoeForCausalLM", "bench_dir": registry.BENCH_DIR}, "reference"
    )


def _engine(model, **over):
    from ray_tpu.serve.llm import LLMEngine

    params, cfg = model
    return LLMEngine(params, cfg, **{**ENGINE, "denoising_steps": 2, **over})


@pytest.fixture(scope="module")
def engine(model):
    """One engine, 2 denoising passes a block, for the tests that serve through it one after another."""
    eng = _engine(model)
    yield eng
    eng.shutdown()


def _prompt(n, seed=0):
    return np.random.default_rng(seed).integers(0, MASK, n).tolist()


def _n_static(nth, steps):
    return B // steps + (nth < B % steps)


@functools.lru_cache(maxsize=None)
def _jitted_chunk(cfg):
    """``paged_decode_chunk`` of this model, compiled once a shape: (params, tokens [1, q], cache, table, pos [1], valid_to [1])."""
    import jax

    from ray_tpu.models.generate import paged_decode_chunk

    return jax.jit(lambda p, t, c, table, pos, valid_to: paged_decode_chunk(p, t, c, table, pos, cfg, valid_to=valid_to))


def _by_hand(model, prompt, new, steps=2, chunk=16):
    """The generation written out over ``paged_decode_chunk`` alone, greedy: chunks to the last block's edge, then
    for each block its denoising passes (the transfer in NumPy, from the pass's logits) and a commit pass.
    Returns (tokens, the final sequence, [(start, ids before, ids after, logits [B, V])] of every denoising pass,
    the commit passes' [(start, logits)])."""
    import jax.numpy as jnp

    from ray_tpu.models.generate import init_paged_cache

    params, cfg = model
    paged_decode_chunk = _jitted_chunk(cfg)
    bs, n_blocks = 8, 16
    cache = init_paged_cache(cfg, n_blocks + 1, bs)
    table = jnp.arange(1, n_blocks + 1, dtype=jnp.int32)[None]
    n = len(prompt)
    edge = n - n % B
    for at in range(0, edge, chunk):
        piece = prompt[at : min(at + chunk, edge)]
        fed = jnp.asarray([piece + [0] * (chunk - len(piece))], jnp.int32)
        _, cache = paged_decode_chunk(params, fed, cache, table, jnp.asarray([at]), jnp.asarray([edge]))
    final, noisy, commits, start = list(prompt[:edge]), [], [], edge
    while len(final) < n + new:
        known = prompt[start : start + B]
        ids, nth = known + [-1] * (B - len(known)), 0
        while True:
            fed = jnp.asarray([[MASK if t < 0 else t for t in ids]], jnp.int32)
            logits, cache = paged_decode_chunk(params, fed, cache, table, jnp.asarray([start]), jnp.asarray([start + B]))
            logits = np.asarray(logits[0], np.float64)
            masked = [j for j in range(B) if ids[j] < 0]
            if not masked:
                commits.append((start, logits))
                break
            p = np.exp(logits - logits.max(-1, keepdims=True))
            p /= p.sum(-1, keepdims=True)
            drawn, conf = logits.argmax(-1), p.max(-1)
            order = sorted(masked, key=lambda j: (-conf[j], j))
            take = order[: _n_static(nth, steps)]
            after = [int(drawn[j]) if j in take else t for j, t in enumerate(ids)]
            noisy.append((start, list(ids), after, logits))
            ids, nth = after, nth + 1
        final += ids
        start += B
    return final[n : n + new], final, noisy, commits


# ------------------------------------------------------------------ (a) the paged path against the reference

CASES = [
    # (prompt tokens, new tokens, denoising steps): n mod B in {0, 1, 3}, a prompt shorter than a block, a prompt of
    # several chunks, 1 / 2 / 3 / 4 denoising passes (3: the first pass of a block moves two), a count that ends inside a block.
    (16, 9, 2), (17, 8, 2), (19, 6, 1), (3, 7, 4), (41, 10, 2), (17, 7, 3), (20, 5, 3),
]


@pytest.mark.parametrize("n,new,steps", CASES)
def test_chunks_then_block_passes_give_the_references_logits_pass_by_pass(model, reference, n, new, steps):
    params, _ = model
    prompt = _prompt(n, seed=n)
    tokens, final, noisy, commits = _by_hand(model, prompt, new, steps)
    assert len(tokens) == new and final[: len(prompt) - len(prompt) % B] == prompt[: len(prompt) - len(prompt) % B]
    by_copy, of_final = reference.pass_logits(params, final, [(start, before) for start, before, _, _ in noisy], PUBLISHED)
    for (start, before, _, logits), ref in zip(noisy, np.asarray(by_copy)):
        assert np.abs(logits - ref).max() < LOGIT_TOL, (start, before)
    # A commit pass feeds the final ids: its logits are the final sequence's own at the block's positions.
    for start, logits in commits:
        assert np.abs(logits - np.asarray(of_final[start : start + B])).max() < LOGIT_TOL, start
    passes = {}
    for start, _, _, _ in noisy:
        passes[start] = passes.get(start, 0) + 1
    # A block costs at most S denoising passes, fewer if its masks run out first.
    assert max(passes.values()) <= steps and any(v == min(steps, B) for v in passes.values())


def test_the_transfer_takes_the_most_confident_masked_positions_the_leftmost_of_equals():
    import jax.numpy as jnp

    from ray_tpu.models.generate import BLOCK_MASKED, transfer_block

    M = BLOCK_MASKED
    ids = jnp.asarray([[M, M, M, M], [7, M, M, M], [M, 7, M, 8], [M, M, M, M], [7, 8, 9, M]], jnp.int32)
    drawn = jnp.arange(20, dtype=jnp.int32).reshape(5, 4) + 100
    conf = jnp.asarray([[0.1, 0.4, 0.4, 0.2], [0.9, 0.1, 0.3, 0.2], [0.2, 0.9, 0.2, 0.9], [0.5, 0.6, 0.7, 0.8], [0.0, 0.0, 0.0, 0.0]])
    after = np.asarray(transfer_block(ids, drawn, conf, jnp.asarray([2, 1, 3, 0, 1])))
    assert after.tolist() == [
        [M, 101, 102, M],  # the two largest
        [7, M, 106, M],  # a position that holds a token is never drawn again, whatever its confidence
        [108, 7, 110, 8],  # fewer masked than the count: all that are left, ties from the left
        [M, M, M, M],  # 0: a commit pass moves nothing
        [7, 8, 9, 119],  # a confidence of 0 is still the largest of one
    ]


# ------------------------------------------------------------------ (b) - (e) the engine


@pytest.mark.parametrize("steps", [2, 1, 3])
def test_the_engine_serves_the_hand_written_generation_rows_in_different_phases_of_their_blocks(model, steps):
    requests = [(17, 10), (3, 7), (32, 9), (21, 12), (8, 5)]  # five requests on three slots: admitted at different times
    eng = _engine(model, denoising_steps=steps)
    try:
        handles = [eng.submit(_prompt(n, seed=n), max_new_tokens=g) for n, g in requests]
        served = [h.result(timeout=120) for h in handles]
        alone = [eng.submit(_prompt(n, seed=n), max_new_tokens=g).result(timeout=120) for n, g in requests]
        stats = eng.stats()
    finally:
        eng.shutdown()
    for (n, g), tokens, again in zip(requests, served, alone):
        by_hand, _, _, _ = _by_hand(model, _prompt(n, seed=n), g, steps)
        assert tokens == by_hand == again, (n, g)
    assert stats["block_tokens_emitted"] == 2 * sum(g for _, g in requests)
    assert stats["block_passes"] == stats["decode_steps"] and stats["kv_pool_not_donated"] == 0 and stats["host_logit_rows"] == 0
    assert stats["block_length"] == B and stats["denoising_steps"] == steps
    # By count the host stays a pass ahead, and fetches none before the next is out.
    assert stats["decode_steps_run_ahead"] > 0 and stats["block_passes_synced"] == 0
    # The experts' counters take a block pass for a step and a prompt's chunk for a chunk.
    assert stats["moe"]["decode"]["steps"] == stats["block_passes"] and stats["moe"]["prefill"]["steps"] > 0


@pytest.mark.parametrize("steps", [B, 0])  # 0, the engine's default: one denoising pass a position
def test_one_token_a_pass_where_the_schedule_says_so(model, steps):
    eng = _engine(model, denoising_steps=steps)
    try:
        req = eng.submit(_prompt(8), max_new_tokens=8, return_block_passes=True)
        assert len(req.result(timeout=120)) == 8
        moved = [rec for rec in req.block_passes if not rec["commit"]]
        stats = eng.stats()
    finally:
        eng.shutdown()
    assert len(moved) == 8 and [rec["pass"] for rec in moved] == [0, 1, 2, 3] * 2
    assert [sum(t >= 0 for t in rec["ids"]) for rec in moved] == [1, 2, 3, 4] * 2
    # A request that asked for its passes has each fetched before the next is dispatched.
    assert stats["block_passes_synced"] == stats["block_passes"] and stats["decode_steps_run_ahead"] == 0


def test_a_commit_emits_the_blocks_tokens_at_once_and_the_ring_says_what_a_pass_did(engine):
    from ray_tpu.serve.llm import stats

    before = engine.stats()
    req = engine.submit(_prompt(18), max_new_tokens=11)  # two of the prompt's tokens start the first block
    stamps = [t for _, t in req.stamped()]
    after = engine.stats()
    # Blocks at 16 (2 new tokens), 20, 24 (4 each) and 28 (1 of its 4): one stamp a commit.
    assert [stamps.count(t) for t in dict.fromkeys(stamps)] == [2, 4, 4, 1]
    assert after["block_commits"] - before["block_commits"] == 4
    assert after["block_tokens_emitted"] - before["block_tokens_emitted"] == 11
    # 2 of 4 positions a pass, then the commit: three passes a block, two for the first (its two masks go in one).
    assert after["block_passes"] - before["block_passes"] == 2 + 3 * 3
    names = stats.ITERATION_FIELDS
    assert names[-2:] == ("block_commits", "tokens_unmasked")
    flat = engine.spans.export()["iterations"]
    recs = [dict(zip(names, flat[i : i + len(names)])) for i in range(0, len(flat), len(names))][-11:]
    assert all(r["rows"] == 1 for r in recs)  # rows FED, whatever a pass emits
    assert sum(r["block_commits"] for r in recs) == 4 and sum(r["tokens_unmasked"] for r in recs) == 2 + 4 * 3


def test_a_slot_used_again_a_cancelled_stream_and_the_same_seed_twice(engine):
    prompts = [_prompt(n, seed=100 + n) for n in (9, 14, 22, 5)]
    sampled = dict(temperature=1.0, seed=1234)
    first = [engine.submit(p, max_new_tokens=9, **sampled) for p in prompts]  # four on three slots: one waits for a slot
    first = [r.result(timeout=120) for r in first]
    doomed = engine.submit(_prompt(12), max_new_tokens=64, temperature=1.0, seed=7)
    next(iter(doomed))
    engine.cancel(doomed)
    # Alone, in another slot, beside other rows: the same tokens, and not the greedy ones.
    again = [engine.submit(p, max_new_tokens=9, **sampled).result(timeout=120) for p in reversed(prompts)][::-1]
    greedy = [engine.submit(p, max_new_tokens=9).result(timeout=120) for p in prompts]
    assert first == again and first != greedy
    other_seed = engine.submit(prompts[0], max_new_tokens=9, temperature=1.0, seed=99).result(timeout=120)
    assert other_seed != first[0]
    assert len(list(doomed)) < 63 and engine.stats()["running"] == 0


def test_a_prefix_hit_on_committed_blocks_and_a_preempted_request_recomputed(model):
    eng = _engine(model, num_slots=2, num_blocks=9)  # 8 blocks of 8 tokens: two rows of 24 + 24 tokens cannot both finish
    try:
        prompt = _prompt(20, seed=5)
        alone = eng.submit(prompt, max_new_tokens=20).result(timeout=120)
        hits = eng.stats()["prefix_hit_blocks"]
        again = eng.submit(prompt, max_new_tokens=20)
        assert again.result(timeout=120) == alone and again.cached_tokens == 16
        assert eng.stats()["prefix_hit_blocks"] == hits + 2  # the prompt's two full cache blocks, prefilled: committed by construction
        a, b = _prompt(21, seed=6), _prompt(22, seed=7)
        want = [eng.submit(p, max_new_tokens=28).result(timeout=120) for p in (a, b)]
        before = eng.stats()["preemptions"]
        both = [eng.submit(p, max_new_tokens=28) for p in (a, b)]
        got = [r.result(timeout=120) for r in both]
        assert eng.stats()["preemptions"] > before and sum(r.preemptions for r in both) > 0
    finally:
        eng.shutdown()
    assert got == want  # emitted tokens teacher-forced under the same mask, the block in flight again from MASK with the same noise


def test_a_checked_requests_passes_rebuild_its_generation(model, engine, reference):
    """What the benchmark's reference is handed: every pass's ids and experts, from which each pass's input follows."""
    params, _ = model
    prompt = _prompt(18, seed=3)
    req = engine.submit(prompt, max_new_tokens=10, return_block_passes=True, return_routed_experts=True)
    tokens = req.result(timeout=120)
    final, copies, after = reference.rebuilt(prompt, req.block_passes, PUBLISHED)
    by_hand, final_by_hand, noisy, _ = _by_hand(model, prompt, 10)
    assert tokens == by_hand and final == final_by_hand
    assert copies == [(start, before) for start, before, _, _ in noisy] and after == [a for _, _, a, _ in noisy]
    assert all(rec["experts"].shape == (B, 2, 2) for rec in req.block_passes)
    assert req.routed_experts.shape == (18 + 10 - 1, 2, 2)
    # The commit passes' choices are what the cache keeps beside the final tokens.
    commit = next(rec for rec in req.block_passes if rec["commit"] and rec["start"] == 20)
    assert np.array_equal(commit["experts"], req.routed_experts[20:24])


CONFIGURED = {**PUBLISHED, "deployment": {"engine": {"denoising_steps": 2}},
              "check": {"transfer_margin_tol": 0.5, "router_tie_tol": 1e-3}}


@pytest.mark.parametrize("steps,says", [(2, None), (1, "took 4 masks to 0 where the schedule of 2 passes a block transfers 2"),
                                        (3, "took 2 masks to 1"), (4, "took 4 masks to 3")])
def test_the_reference_holds_the_passes_to_the_configurations_schedule(model, reference, steps, says):
    """How many positions a pass moves is the configuration's to say: an engine that runs another schedule than
    ``deployment.engine.denoising_steps`` names is told so, and its sequence comes back NaN whatever its logits."""
    params = dict(model[0])  # a tree of its own: the reference finds the engine that serves it by the tree (``serving_engine``)
    prompt = _prompt(16, seed=9)
    eng = _engine((params, model[1]), denoising_steps=steps)
    try:
        req = eng.submit(prompt, max_new_tokens=8, return_block_passes=True)
        tokens = req.result(timeout=120)
        fault = reference.off_schedule(prompt, req.block_passes, CONFIGURED)
        assert (fault is None) if says is None else (says in fault), fault
        logits = np.asarray(reference.make_layerwise_logits(CONFIGURED)(params, prompt + tokens, list(range(15, 23))))
    finally:
        eng.shutdown()
    assert logits.shape == (8, 128) and np.isnan(logits).all() == (says is not None) == np.isnan(logits).any()


def _block_of_passes():
    return [{"start": 8, "pass": 0, "commit": False, "ids": [5, 6, -1, -1]}, {"start": 8, "pass": 1, "commit": False, "ids": [5, 6, 7, 8]},
            {"start": 8, "pass": 2, "commit": True, "ids": [5, 6, 7, 8]}, {"start": 12, "pass": 0, "commit": False, "ids": [-1, 1, 2, -1]}]


@pytest.mark.parametrize("tamper,says", [
    (lambda p: None, None),
    (lambda p: p.pop(2), "where pass 2 of the block at 8 is due"),  # a block that never committed
    (lambda p: p[2].update(ids=[5, 6, 7, 9]), "changed a position that held a token"),
    (lambda p: p[1].update(**{"pass": 2}), "where pass 1 of the block at 8 is due"),
    (lambda p: p[1].update(commit=True), "commit: True"),  # a commit while positions were masked
    (lambda p: p[3].update(start=16), "a block at 16 behind the one at 8"),
    (lambda p: p[3].update(ids=[-1, 1, -1, -1]), "took 4 masks to 3"),
])
def test_a_pass_off_the_schedule_is_named(reference, tamper, says):
    passes = _block_of_passes()
    tamper(passes)
    fault = reference.off_schedule(_prompt(8), passes, CONFIGURED)
    assert (fault is None) if says is None else (says in fault), fault


# ------------------------------------------------------------------ (f) nothing of an autoregressive program moves


def test_block_diffusion_0_leaves_the_mask_and_an_autoregressive_program_what_they_were():
    import jax
    import jax.numpy as jnp

    import importlib

    from ray_tpu.models.transformer import TransformerConfig, init_params

    generate = importlib.import_module("ray_tpu.models.generate")  # ``ray_tpu.models.generate`` the attribute is a function
    positions = jnp.asarray([[3, 4, 5, 6], [8, 9, 10, 11]])
    causal = np.asarray(generate._cache_mask(positions, 16, 0))
    assert np.array_equal(causal, np.arange(16)[None, None] <= np.asarray(positions)[:, :, None])
    blocked = np.asarray(generate._cache_mask(positions, 16, 0, block=4))
    assert np.array_equal(blocked, np.arange(16)[None, None] < ((np.asarray(positions) // 4 + 1) * 4)[:, :, None])
    cfg = TransformerConfig(vocab_size=64, d_model=32, n_layers=2, n_heads=2, n_kv_heads=2, d_ff=64, max_seq_len=64,
                            dtype=jnp.float32, param_dtype=jnp.float32)  # a Mistral toy
    params = init_params(jax.random.PRNGKey(0), cfg)
    cache = generate.init_paged_cache(cfg, 5, 8)

    def step(p, t, c):
        return generate.paged_decode_step(p, t, c, jnp.asarray([[1, 2], [3, 4]]), jnp.asarray([3, 9]), cfg)

    def mask_before_pr_56(positions, n_keys, window, key_len=None, key_pos=None, block=0):
        assert not block and key_pos is None and key_len is None and not window
        return jnp.arange(n_keys, dtype=jnp.int32)[None, None, :] <= positions[:, :, None]

    text = str(jax.make_jaxpr(step)(params, jnp.asarray([1, 2]), cache))
    assert "le " in str(jax.make_jaxpr(lambda p: generate._cache_mask(p, 16, 0))(positions))  # the causal comparison, no block arithmetic
    now, generate._cache_mask = generate._cache_mask, mask_before_pr_56
    try:
        assert str(jax.make_jaxpr(step)(params, jnp.asarray([1, 2]), cache)) == text
    finally:
        generate._cache_mask = now


# ------------------------------------------------------------------ (g) the refusals, each by name


@pytest.mark.parametrize("over,says", [
    (dict(layer_kinds=("window", "full"), sliding_window=8), "under a layer pattern"),
    (dict(layer_kinds=("conv", "full"), conv_cache=3, full_layers_rope=True), "keep a state a slot"),
    (dict(kv_lora_rank=16, q_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8), "over a latent pool"),
    (dict(sliding_window=8), "under a sliding window"),
    (dict(mask_token_id=4096), "a mask id of the vocabulary"),
])
def test_a_configuration_refuses_what_a_block_pass_cannot_share_a_pass_with(over, says):
    from ray_tpu.models.transformer import TransformerConfig

    with pytest.raises(ValueError, match=says):
        TransformerConfig(**{**MODEL, **over})


def test_training_and_the_transfer_plane_refuse_block_diffusion_by_name(model):
    import jax.numpy as jnp

    from ray_tpu.models.transformer import forward_hidden
    from ray_tpu.serve.llm import LLMEngine

    params, cfg = model
    with pytest.raises(NotImplementedError, match="block_diffusion"):
        forward_hidden(params, jnp.zeros((1, 8), jnp.int32), cfg)
    for over in (dict(role="prefill"), dict(role="decode"), dict(cluster_prefix=True)):
        with pytest.raises(ValueError, match="block_diffusion"):
            LLMEngine(params, cfg, **{**ENGINE, **over})
    for over, says in ((dict(block_size=6), "must divide"), (dict(prefill_chunk=18), "must divide"),
                       (dict(denoising_steps=5), "between 1 and")):
        with pytest.raises(ValueError, match=says):
            LLMEngine(params, cfg, **{**ENGINE, **over})


def test_kv_import_and_passes_of_an_autoregressive_model_are_refused(engine):
    with pytest.raises(ValueError, match="block_diffusion"):
        engine.submit(_prompt(8), max_new_tokens=4, kv_import={"oid": "x"})
    from ray_tpu.models.transformer import TransformerConfig

    assert dataclasses.replace(TransformerConfig(**MODEL), block_diffusion=0).inference_only.count("block_diffusion") == 0
