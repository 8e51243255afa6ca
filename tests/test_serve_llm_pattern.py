"""A layer pattern (PR 35): window and full attention mixed by layer over a paged
cache with two kinds of layer. The model is Trinity-Mini's block at a toy size
(gated QK-normed GQA with a head wider than ``d_model / n_heads``, four norms a
layer, rotary in the window layers only, sigmoid top-8 of 128 experts behind a
leading dense layer), held to the benchmark's plain float32 reference
(``benchmarks/architectures/AfmoeForCausalLM/reference.py``): its full forward
pass knows no cache, no ring and no chunk."""

import time

import numpy as np
import pytest

KINDS = ("window", "window", "window", "full", "window", "window", "full")
MODEL = dict(
    vocab_size=128, d_model=64, n_layers=7, n_heads=4, n_kv_heads=2, head_dim=32, d_ff=96, max_seq_len=256,
    num_experts=128, experts_per_token=8, d_expert=16, num_shared_experts=1, routed_scaling_factor=2.826,
    first_dense_layers=1, sliding_window=24, layer_kinds=KINDS, attn_gate=True, qk_norm=True, post_norms=True,
    embed_multiplier=8.0,
)
# The same model as its published ``config.json`` would state it: what the reference reads.
PUBLISHED = dict(
    hidden_size=64, num_hidden_layers=7, num_attention_heads=4, num_key_value_heads=2, head_dim=32,
    intermediate_size=96, vocab_size=128, num_experts=128, num_experts_per_tok=8, moe_intermediate_size=16,
    num_shared_experts=1, route_scale=2.826, num_dense_layers=1, sliding_window=24, rope_theta=10000.0,
    rms_norm_eps=1e-5, mup_enabled=True,
    layer_types=["sliding_attention" if k == "window" else "full_attention" for k in KINDS],
)
# A ring of ceil((24 + 16) / 8) + 1 = 6 blocks, 48 tokens: contexts of up to 170 go round it three times.
ENGINE = dict(num_slots=4, block_size=8, max_model_len=256, prefill_chunk=16)
RING = 6


@pytest.fixture(scope="module")
def model():
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.transformer import TransformerConfig, init_params

    cfg = TransformerConfig(**MODEL, dtype=jnp.float32, param_dtype=jnp.float32)
    params = init_params(jax.random.PRNGKey(0), cfg)
    # Norm weights are drawn as ones: scattered here, so that a norm left out or two swapped show.
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 64))
    for stack in ("dense_layers", "layers"):
        for name, leaf in params[stack].items():
            if name.endswith("norm"):
                params[stack][name] = leaf * jax.random.uniform(next(keys), leaf.shape, minval=0.5, maxval=1.5)
    return params, cfg


@pytest.fixture(scope="module")
def reference():
    from benchmarks.harness import registry

    return registry.load_architecture(
        {"name": "this test", "architecture": "AfmoeForCausalLM", "bench_dir": registry.BENCH_DIR}, "reference"
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


def test_chunks_then_steps_over_rings_give_the_references_logits(model, reference):
    """The programs the engine runs, driven by hand so that logits come back:
    a prompt in chunks of 16 and then one token a step through
    ``paged_decode_chunk`` over the two groups of a paged cache, two rows of
    different lengths at once, against one plain forward pass a row. 100 and
    130 tokens: the 48-token ring wraps twice, the window of 24 bites from the
    second chunk on, and a padded last chunk leaves part of a ring block stale."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.generate import init_paged_cache, paged_decode_chunk, ring_blocks

    params, cfg = model
    bs, chunk, n_max = 8, 16, 20
    assert ring_blocks(cfg.sliding_window, chunk, bs) == RING
    cache = init_paged_cache(cfg, 2 * n_max + 1, bs, window_blocks=2 * RING + 1)
    assert {name: leaf.shape[:2] for name, leaf in cache.items()} == {
        "k": (2, 41), "v": (2, 41), "k_win": (5, 13), "v_win": (5, 13),
    }
    tables = 1 + np.arange(2 * n_max, dtype=np.int32).reshape(2, n_max)
    rings = 1 + np.arange(2 * RING, dtype=np.int32).reshape(2, RING)
    lens = [91, 77]
    seqs = [_prompt(3, 130), _prompt(4, 100)]
    step = jax.jit(paged_decode_chunk, static_argnames=("cfg",))
    got = [np.zeros((len(s), cfg.vocab_size), np.float32) for s in seqs]
    for pos in range(0, max(lens), chunk):  # the prompts, chunk by chunk, padded past their ends
        fed = np.zeros((2, chunk), np.int32)
        for b, (s, n) in enumerate(zip(seqs, lens)):
            piece = s[pos : min(pos + chunk, n)]
            fed[b, : len(piece)] = piece
        logits, cache = step(
            params, jnp.asarray(fed), cache, tables, jnp.full((2,), pos, jnp.int32), cfg=cfg,
            valid_to=jnp.asarray(lens, jnp.int32), ring_tables=rings,
        )
        for b, n in enumerate(lens):
            upto = max(0, min(pos + chunk, n) - pos)
            got[b][pos : pos + upto] = np.asarray(logits[b, :upto])
    at = list(lens)
    while any(p < len(s) for p, s in zip(at, seqs)):  # then a token a step, each row at its own position
        live = [p < len(s) for p, s in zip(at, seqs)]
        fed = np.asarray([[s[p] if ok else 0] for s, p, ok in zip(seqs, at, live)], np.int32)
        pos = np.asarray([p if ok else 0 for p, ok in zip(at, live)], np.int32)
        logits, cache = step(
            params, jnp.asarray(fed), cache, np.where(np.asarray(live)[:, None], tables, 0), pos, cfg=cfg,
            ring_tables=np.where(np.asarray(live)[:, None], rings, 0),
        )
        for b, ok in enumerate(live):
            if ok:
                got[b][at[b]] = np.asarray(logits[b, 0])
                at[b] += 1
    for s, ours in zip(seqs, got):
        want = np.asarray(reference.sequence_logits(params, s, PUBLISHED))
        assert want.std() > 0.5  # logits of unit scale: the embedding's multiplier has not drowned the layers
        np.testing.assert_allclose(ours, want, atol=2e-4, rtol=0)


def test_a_window_layer_without_its_window_or_a_full_layer_with_rotary_is_found_out(model, reference):
    """The reference tells the kinds apart: the same weights under another
    pattern give other logits, by tenths."""
    params, _ = model
    tokens = _prompt(5, 60)
    want = np.asarray(reference.sequence_logits(params, tokens, PUBLISHED))
    for other in (["full_attention"] * 7, ["sliding_attention"] * 7):
        got = np.asarray(reference.sequence_logits(params, tokens, dict(PUBLISHED, layer_types=other)))
        assert np.abs(got[40:] - want[40:]).max() > 0.05


def test_the_engine_serves_the_references_tokens_past_a_wrapped_ring(model, reference):
    """Chunked prefill, then the decode loop one step ahead, four requests at
    once on four slots: every token the engine drew (greedy) is the reference's
    best at its position, or within rounding of it. 40 new tokens on prompts of
    up to 130: every ring wraps, every rung of the full table is used."""
    params, _ = model
    eng = _engine(model)
    try:
        prompts = [_prompt(i, n) for i, n in enumerate((37, 5, 90, 130))]
        reqs = [eng.submit(p, max_new_tokens=40) for p in prompts]
        for p, r in zip(prompts, reqs):
            assert _gaps(reference, params, p, r.result(timeout=300)).max() < 1e-4
        st = eng.stats()
        assert st["kv_pool_not_donated"] == 0 and st["host_logit_rows"] == 0
        assert st["decode_steps_run_ahead"] > 0.8 * st["decode_steps"]  # the steps were in flight
        assert st["decode_width_steps"][16] > 0 and st["decode_width_steps"][32] > 0
    finally:
        eng.shutdown()


def test_preemption_and_readmission_continue_bit_for_bit(model, reference):
    """A full group of 17 blocks for three rows that want 27: the youngest is
    preempted, gives its blocks back, and is teacher-forced through prefill
    again into the ring its new slot owns."""
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
        assert tight.stats()["preemptions"] >= 1 and sum(r.preemptions for r in reqs) >= 1
    finally:
        tight.shutdown()
    for p, new in zip(prompts, want):
        assert _gaps(reference, params, p, new).max() < 1e-4


def test_no_window_layer_holds_or_gathers_more_than_a_ring_a_row(model):
    import jax.numpy as jnp

    from ray_tpu.models.generate import _ring_access

    eng = _engine(model, max_model_len=256)
    try:
        slots, bs = ENGINE["num_slots"], ENGINE["block_size"]
        assert eng.ring_blocks == RING and eng.n_max == 32
        pool = eng._cache
        # five window layers hold slots x ring blocks (+ the null block) whatever max_model_len is;
        # the two full layers hold every slot at full length
        assert pool["k_win"].shape[:3] == pool["v_win"].shape[:3] == (5, slots * RING + 1, bs)
        assert pool["k"].shape[:3] == (2, slots * 32 + 1, bs)
        groups = eng.stats()["kv_groups"]
        token = 2 * 2 * 32 * 4  # keys and values, 2 KV heads of 32, float32
        assert groups["window"] == dict(kv_token_bytes=5 * token, num_blocks=slots * RING, blocks_in_use=0, ring_blocks=RING)
        assert groups["full"] == dict(kv_token_bytes=2 * token, num_blocks=slots * 32, blocks_in_use=0)
        assert eng.stats()["kv_token_bytes"] == 7 * token
        assert pool["k_win"].nbytes == 5 * (slots * RING + 1) * bs * token // 2
        # a row of a program is the seven columns, the ring, the table; a slot's ring is its own for good
        assert eng._program_rows(slots, 16).shape == (slots, 7 + RING + 16)
        assert sorted(eng._rings.ravel().tolist()) == list(range(1, slots * RING + 1))
        # and what a step views of a window layer is the ring, or the rung where that is narrower
        positions = jnp.asarray([[200], [7]], jnp.int32)
        for rung, viewed in ((32, RING), (16, RING), (4, 4)):
            access = _ring_access(jnp.asarray(eng._rings[:2]), positions, None, bs, min(RING, rung))
            assert access.view(pool["k_win"], 0).shape[:2] == (2, viewed * bs)
            assert access.key_pos.shape == (2, viewed * bs)
        key_pos = np.asarray(_ring_access(jnp.asarray(eng._rings[:2]), positions, None, bs, RING).key_pos)
        # position 200 is block 25, ring index 1: the ring holds blocks 20..25, each where j mod 6 says
        assert key_pos[0].reshape(RING, bs)[:, 0].tolist() == [192, 200, 160, 168, 176, 184]
        # position 7 is block 0: the other five ring blocks hold nothing yet (negative: masked)
        assert key_pos[1].reshape(RING, bs)[:, 0].tolist() == [0, -40, -32, -24, -16, -8]
        long = eng.submit(_prompt(1, 200), max_new_tokens=20)
        deadline = time.monotonic() + 120
        while long.num_generated < 2 and time.monotonic() < deadline:
            time.sleep(0.01)
        running = eng.stats()["kv_groups"]
        assert running["window"]["blocks_in_use"] == RING  # 200 tokens and more: still one ring
        assert running["full"]["blocks_in_use"] >= 200 // bs
        long.result(timeout=300)
        assert eng.stats()["kv_groups"]["window"]["blocks_in_use"] == 0
        recs = eng.spans.export()
        names = recs["fields"]["iterations"]
        rows = [dict(zip(names, recs["iterations"][i : i + len(names)])) for i in range(0, len(recs["iterations"]), len(names))]
        decoding = [r for r in rows if r["rows"] == 1]
        assert decoding and all(r["window_tokens"] == 24 and r["context_tokens"] > 200 for r in decoding)
    finally:
        eng.shutdown()


def test_two_words_a_token_hold_eight_of_128_experts(model, reference):
    """``MOE_CHOICE`` under this router: 8 ids of 7 bits are 56 bits, two int32
    words a token a layer on a leading axis, four ids each. What a request
    returns is what the program chose, which is what the reference chooses."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.generate import MOE_CHOICE, _choice_words, init_cache, init_moe_choice, prefill, unpack_experts

    params, cfg = model
    assert _choice_words(cfg) == (2, 4)
    ids = np.random.default_rng(0).permuted(np.tile(np.arange(128), (5, 3, 1)), axis=-1)[..., :8]  # [5, 3, 8]
    shifts = 7 * np.arange(4)
    words = np.stack([(ids[..., :4] << shifts).sum(-1), (ids[..., 4:] << shifts).sum(-1)])
    assert np.array_equal(unpack_experts(words.astype(np.int32), cfg), ids)
    prompt = _prompt(7, 70)
    dense = {**init_cache(cfg, 1, 80), MOE_CHOICE: init_moe_choice(cfg, 1, 80)}
    assert dense[MOE_CHOICE].shape == (2, 6, 1, 80)
    _, dense, _ = jax.jit(prefill, static_argnames=("cfg",))(params, jnp.asarray([prompt]), dense, cfg=cfg)
    by_prefill = unpack_experts(np.asarray(dense[MOE_CHOICE])[:, :, 0, :70], cfg)  # [expert layers, 70, 8]
    eng = _engine(model)
    try:
        assert eng._cache[MOE_CHOICE].shape == (2, 6, 4 * 32 + 1, 8)  # beside the FULL group's blocks
        req = eng.submit(prompt, max_new_tokens=9, return_routed_experts=True)
        new = req.result(timeout=300)
        served = req.routed_experts
    finally:
        eng.shutdown()
    assert served.shape == (70 + 8, 6, 8) and served.min() >= 0 and served.max() < 128
    assert all(len(set(row)) == 8 for row in served.reshape(-1, 8).tolist())
    assert np.array_equal(np.sort(served[:70], axis=-1), np.sort(by_prefill.transpose(1, 0, 2), axis=-1))
    # the reference's own top-8, layer by layer, on the hidden states its own forward pass gives
    tokens = jnp.asarray(prompt + new[:-1], jnp.int32)
    x = reference.embed(params, tokens, PUBLISHED)
    positions = jnp.arange(len(tokens))
    sliding = [t == "sliding_attention" for t in PUBLISHED["layer_types"]]
    x = reference.dense_layer(params["dense_layers"], 0, x, positions, PUBLISHED, sliding[0])
    agree = []
    for layer in range(6):
        w = reference._take(params["layers"], reference.ATTENTION_LEAVES, layer)
        h = reference.rms_norm(x + reference.attention(w, x, positions, PUBLISHED, sliding[1 + layer]), w["mlp_norm"], 1e-5)
        _, biased = reference.biased_scores(reference._take(params["layers"], reference.ROUTER_LEAVES, layer), h)
        theirs = np.sort(np.asarray(jax.lax.top_k(biased, 8)[1]), axis=-1)
        agree.append((theirs == np.sort(served[:, layer], axis=-1)).all(axis=-1))
        x, deficit = reference.expert_layer(params["layers"], layer, x, positions, PUBLISHED, sliding[1 + layer],
                                            served=jnp.asarray(served[:, layer]))
        assert float(deficit.max()) < 1e-5  # float32 against float32: the system's experts ARE the reference's
    assert np.mean(agree) > 0.995


def test_what_a_layer_pattern_cannot_do_yet_is_refused_by_name(model):
    params, cfg = model
    from ray_tpu.serve.llm import LLMEngine

    for over, what in ((dict(role="prefill"), "role='prefill'"), (dict(role="decode"), "role='decode'"),
                       (dict(cluster_prefix=True), "cluster_prefix=True")):
        with pytest.raises(ValueError, match=f"{what} needs the KV transfer plane.*layer pattern.*ROADMAP R3"):
            LLMEngine(params, cfg, **dict(ENGINE, **over))
    eng = _engine(model)
    try:
        with pytest.raises(ValueError, match="kv_import needs the KV transfer plane.*layer pattern"):
            eng.submit(_prompt(1, 20), max_new_tokens=2, kv_import={"oid": "x", "kv_pos": 16})
        # The same prompt twice: no block is registered, no hit is taken, nothing is counted as a miss.
        prompt = _prompt(2, 50)
        first = eng.submit(prompt, max_new_tokens=6)
        got = first.result(timeout=300)
        again = eng.submit(prompt, max_new_tokens=6)
        assert again.result(timeout=300) == got and again.cached_tokens == 0 and first.cached_tokens == 0
        st = eng.stats()
        assert (st["prefix_hit_blocks"], st["prefix_miss_blocks"], st["cached_blocks"]) == (0, 0, 0)
    finally:
        eng.shutdown()


def test_the_training_path_refuses_the_new_fields_by_name(model):
    """Of the six fields this test held refused, three gained a training block
    in PR 50 (``layer_kinds`` of window and full layers, ``qk_norm``, ``head_dim``:
    each is a case of tests/test_moe_training.py::test_a_field_that_gained_a_block_trains,
    and together they agree with the benchmark's reference there); the other
    three still refuse by name."""
    import dataclasses

    import jax.numpy as jnp
    import optax

    from ray_tpu.models import transformer

    _, cfg = model
    with pytest.raises(NotImplementedError, match="gated attention.*post-branch norms.*embedding multiplier"):
        transformer.make_train_step(cfg, optax.sgd(0.1))
    dense = dict(num_experts=0, experts_per_token=0, num_shared_experts=0, first_dense_layers=0, d_expert=0)
    plain = dict(layer_kinds=(), attn_gate=False, qk_norm=False, post_norms=False, head_dim=16, embed_multiplier=1.0)
    for field in ("attn_gate", "post_norms", "embed_multiplier"):
        one = dataclasses.replace(cfg, **dense, **{**plain, field: getattr(cfg, field)})
        with pytest.raises(NotImplementedError, match="forward_hidden cannot run.*no training block"):
            transformer.forward_hidden({}, jnp.zeros((1, 4), jnp.int32), one)
    for field in ("layer_kinds", "qk_norm", "head_dim"):
        one = dataclasses.replace(cfg, **dense, **{**plain, field: getattr(cfg, field)})
        assert one.inference_only == "", field
        transformer.make_train_step(one, optax.sgd(0.1))
    transformer.make_train_step(dataclasses.replace(cfg, **dense, **plain), optax.sgd(0.1))  # and nothing else is in the way


def test_a_configuration_states_its_pattern_whole():
    from ray_tpu.models.transformer import TransformerConfig

    ok = TransformerConfig(n_layers=3, sliding_window=8, layer_kinds=["window", "full", "window"])
    assert ok.layer_kinds == ("window", "full", "window") and hash(ok) == hash(TransformerConfig(
        n_layers=3, sliding_window=8, layer_kinds=("window", "full", "window"), head_dim=64))
    assert TransformerConfig().head_dim == 64 and TransformerConfig(head_dim=128).head_dim == 128
    for bad, why in ((dict(n_layers=3, sliding_window=8, layer_kinds=("window", "full")), "need n_layers = 3"),
                     (dict(n_layers=2, sliding_window=8, layer_kinds=("window", "global")), "'window' / 'full'"),
                     (dict(n_layers=2, layer_kinds=("window", "full")), "sliding_window is 0")):
        with pytest.raises(ValueError, match=why):
            TransformerConfig(**bad)


@pytest.mark.parametrize("model", [
    dict(n_layers=3, n_heads=4, n_kv_heads=2, d_model=64),  # one group: every layer alike
    dict(n_layers=2, n_heads=4, d_model=64, q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=12, qk_rope_head_dim=8,
         v_head_dim=20),  # one group of one latent leaf, padded to the lanes
    dict(n_layers=7, n_heads=4, n_kv_heads=2, d_model=64, head_dim=32, sliding_window=24, layer_kinds=KINDS),
], ids=["alike", "latent", "pattern"])
def test_a_tokens_bytes_by_group_are_what_the_pools_leaves_hold(model):
    """``cache_token_bytes`` (what ``stats()["kv_groups"]`` and the benchmark's
    byte counts read) against the pool as ``init_paged_cache`` lays it out."""
    import jax.numpy as jnp

    from ray_tpu.models.generate import cache_token_bytes, init_paged_cache
    from ray_tpu.models.transformer import TransformerConfig

    cfg = TransformerConfig(vocab_size=64, d_ff=96, max_seq_len=64, dtype=jnp.bfloat16, **model)
    bs, blocks, window_blocks = 8, 11, 7 if cfg.layer_kinds else 0
    pool = init_paged_cache(cfg, blocks, bs, window_blocks)
    held = {"full": 0, "window": 0}
    for name, leaf in pool.items():
        group = "window" if name.endswith("_win") else "full"
        assert leaf.shape[1:3] == ((window_blocks if group == "window" else blocks), bs)
        held[group] += leaf.nbytes // (leaf.shape[1] * bs)
    assert cache_token_bytes(cfg) == {group: n for group, n in held.items() if n}
    assert set(cache_token_bytes(cfg)) == ({"full", "window"} if cfg.layer_kinds else {"full"})


@pytest.mark.parametrize("kinds, period", [
    ("w", 1), ("wwfw", 3), ("wwwf" * 2, 4), ("wwfwwf", 3), ("fwfwf", 2), ("wwwfw", 4),
])
def test_the_period_of_a_stacks_kinds(kinds, period):
    """``wwfw``, the benchmark's expert stack, is one period of three and a
    remainder of one; the published ``wwwf`` eight times over has period four."""
    from ray_tpu.models.generate import _period

    assert _period(tuple(kinds)) == period
