"""``LLMEngine`` over a latent pool with routed experts (PR 32): the scheduler,
the block pool, the rungs and the prefix cache as they are; expert counters
kept on the device and read when asked; ``context_tokens`` in the iteration
ring; and what needs a K/V-shaped payload refused by name."""

import threading
import time

import numpy as np
import pytest

from ray_tpu.serve.llm import stats

MODEL = dict(
    vocab_size=128, d_model=64, n_layers=3, n_heads=4, n_kv_heads=4, d_ff=96, max_seq_len=256,
    num_experts=8, experts_per_token=2, d_expert=32, num_shared_experts=1, routed_scaling_factor=1.8,
    first_dense_layers=1, q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=12, qk_rope_head_dim=8, v_head_dim=20,
)
ENGINE = dict(num_slots=4, block_size=8, max_model_len=256, prefill_chunk=16)


@pytest.fixture(scope="module")
def model():
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.transformer import TransformerConfig, init_params

    cfg = TransformerConfig(**MODEL, dtype=jnp.float32, param_dtype=jnp.float32)
    return init_params(jax.random.PRNGKey(0), cfg), cfg


def _engine(model, **over):
    from ray_tpu.serve.llm import LLMEngine

    params, cfg = model
    return LLMEngine(params, cfg, **dict(ENGINE, **over))


def _prompt(seed, n):
    return np.random.default_rng(seed).integers(0, MODEL["vocab_size"], n).tolist()


def test_the_engine_serves_what_generate_computes_and_donates_every_leaf(model):
    import jax.numpy as jnp

    from ray_tpu.models.generate import MOE_CHOICE, MOE_COUNTS, generate

    params, cfg = model
    eng = _engine(model)
    try:
        assert set(eng._cache) == {"ckv", MOE_COUNTS, MOE_CHOICE}
        prompts = [_prompt(i, n) for i, n in enumerate((37, 5, 50))]
        reqs = [eng.submit(p, max_new_tokens=12) for p in prompts]
        got = [r.result(timeout=120) for r in reqs]
        for p, tokens in zip(prompts, got):
            want = generate(params, jnp.asarray([p]), cfg, max_new_tokens=12)[0].tolist()
            assert tokens == want
        st = eng.stats()
        assert st["kv_pool_not_donated"] == 0 and st["host_logit_rows"] == 0
        # a CPU's engine: the view at every width, no kernel's counter moves
        assert st["latent_kernel_steps"] == 0 and st["latent_kernel_chunks"] == 0
        # the latent and the rotary key (16 + 8), padded to 128 lanes, float32, three layers
        assert st["kv_token_bytes"] == 3 * 128 * 4
        # A prompt sent again finds its full blocks in the prefix cache: a block is a block.
        again = eng.submit(prompts[2], max_new_tokens=12)
        assert again.result(timeout=120) == got[2] and again.cached_tokens == 48
        assert eng.stats()["prefix_hit_blocks"] == 6
    finally:
        eng.shutdown()


def test_expert_counters_live_on_the_device_and_are_read_when_asked(model):
    eng = _engine(model)
    try:
        before = eng.stats()["moe"]  # the rungs were built with inactive rows: nothing routed yet
        assert before["decode"]["steps"] == 0 and before["prefill"]["steps"] == 0
        assert not np.asarray(before["decode"]["assignments"]).any()
        a, b = eng.submit(_prompt(1, 20), max_new_tokens=6), eng.submit(_prompt(2, 33), max_new_tokens=9)
        a.result(timeout=120), b.result(timeout=120)
        moe = eng.stats()["moe"]
        decode, chunks = moe["decode"], moe["prefill"]
        # 20 + 33 prompt tokens in chunks of 16 (2 + 3 chunks); 5 + 8 fed tokens in decode steps; k = 2
        assert chunks["steps"] == 5 and [sum(layer) for layer in chunks["assignments"]] == [2 * 53] * 2
        assert [sum(layer) for layer in decode["assignments"]] == [2 * 13] * 2 and 8 <= decode["steps"] <= 13
        for kind in (decode, chunks):
            for touched, fullest, sent in zip(kind["experts_touched"], kind["fullest_expert_load"], kind["assignments"]):
                assert kind["steps"] * 2 <= touched <= kind["steps"] * 8  # at least k experts a step, at most all
                assert max(sent) / kind["steps"] <= fullest <= sum(sent)
        # A step fetches [num_slots] ids and nothing else: the counters came through stats() alone,
        # and a reading leaves nothing behind that keeps the next dispatch from donating the buffer.
        assert eng.stats()["host_logit_rows"] == 0
        eng.submit(_prompt(6, 9), max_new_tokens=3).result(timeout=120)
        assert eng.stats()["kv_pool_not_donated"] == 0
        moe = eng.stats()["moe"]
        # Several askers at once each get an answer.
        answers = []
        threads = [threading.Thread(target=lambda: answers.append(eng.stats()["moe"])) for _ in range(4)]
        [t.start() for t in threads], [t.join() for t in threads]
        assert answers == [moe] * 4
    finally:
        eng.shutdown()
    assert eng.stats()["moe"] == moe  # a stopped scheduler: the last reading stands, at once


def test_a_request_returns_the_experts_its_tokens_took(model):
    """``submit(return_routed_experts=True)``: what the program leaves beside a dense
    cache's rows for the same sequence, on a first serving and again when the prompt's
    blocks come from the prefix cache; the benchmark's reference asks the same way."""
    import jax.numpy as jnp

    from benchmarks.harness import registry
    from ray_tpu.models.generate import MOE_CHOICE, init_cache, init_moe_choice, prefill, unpack_experts

    params, cfg = model
    reference = registry.load_architecture(
        {"name": "this test", "architecture": "Glm4MoeLiteForCausalLM", "bench_dir": registry.BENCH_DIR}, "reference"
    )
    eng = _engine(model)
    try:
        prompt = _prompt(7, 37)
        first = eng.submit(prompt, max_new_tokens=9, return_routed_experts=True)
        new = first.result(timeout=120)
        fed = prompt + new[:-1]
        cache = {**init_cache(cfg, 1, 64), MOE_CHOICE: init_moe_choice(cfg, 1, 64)}
        _, cache, _ = prefill(params, jnp.asarray([fed]), cache, cfg)
        want = unpack_experts(np.asarray(cache[MOE_CHOICE])[:, 0, : len(fed)].T, cfg)  # [45, 2 layers, k = 2]
        assert first.routed_experts.shape == (45, 2, 2)
        assert (np.sort(first.routed_experts, -1) == np.sort(want, -1)).all()
        again = eng.submit(prompt, max_new_tokens=9, return_routed_experts=True)
        assert again.result(timeout=120) == new and again.cached_tokens == 32
        assert (again.routed_experts == first.routed_experts).all()
        assert eng.submit(prompt, max_new_tokens=9).result(timeout=120) == new  # not asked: nothing read
        assert (reference.served_routing(params, prompt, new) == first.routed_experts).all()
        assert reference.served_routing(params, prompt, new[:-1] + [(new[-1] + 1) % 128]) is None
        assert reference.served_routing(dict(params), prompt, new) is None  # no engine serves that tree
        assert eng.stats()["kv_pool_not_donated"] == 0
    finally:
        eng.shutdown()


def test_context_tokens_is_the_running_rows_length(model):
    eng = _engine(model)
    try:
        eng.submit(_prompt(3, 21), max_new_tokens=5).result(timeout=120)
        fields = {name: i for i, name in enumerate(stats.ITERATION_FIELDS)}
        deadline = time.monotonic() + 10.0  # the last pass's record is pushed after its token is queued
        while True:
            steps = [r for r in eng.spans.iterations.since() if r[fields["rows"]]]
            if len(steps) >= 4 or time.monotonic() > deadline:
                break
            time.sleep(0.01)
        # one row: the token fed at position 21, 22, .. with everything before it in context
        assert [r[fields["context_tokens"]] for r in steps] == [22, 23, 24, 25]
        assert all(r[fields["rows"]] == 1 and r[fields["view_blocks"]] == 16 for r in steps)
        chunks = [r for r in eng.spans.iterations.since() if not r[fields["rows"]]]
        assert chunks and all(r[fields["context_tokens"]] == 0 for r in chunks)
    finally:
        eng.shutdown()


def test_the_kv_utilisation_gauge_counts_blocks_whatever_a_block_holds(model):
    from ray_tpu._private import self_metrics

    inst = self_metrics.instruments()
    eng = _engine(model, num_blocks=41)
    try:
        hold = eng.submit(_prompt(4, 30), max_new_tokens=200)
        for _ in hold:  # first token: the prompt's four blocks are allocated
            break
        self_metrics._collect_serve_llm_stats()
        used = (eng.num_blocks - 1) - len(eng._free)
        assert used >= 4
        assert max(inst["serve_llm_kv_util"]._values.values()) == pytest.approx(used / 40, abs=2 / 40)
        eng.cancel(hold)
    finally:
        eng.shutdown()


@pytest.mark.parametrize("over, what", [
    (dict(role="prefill"), "role='prefill'"), (dict(role="decode"), "role='decode'"),
    (dict(cluster_prefix=True), "cluster_prefix=True"),
])
def test_what_needs_a_kv_shaped_payload_is_refused_at_construction(model, over, what):
    with pytest.raises(ValueError, match=f"{what} needs the KV transfer plane.*latent-attention pool.*ROADMAP D5"):
        _engine(model, **over)


def test_kv_import_is_refused_at_submit(model):
    eng = _engine(model)
    try:
        with pytest.raises(ValueError, match="kv_import needs the KV transfer plane"):
            eng.submit(_prompt(5, 9), kv_import={"oid": "x"})
        assert eng.submit(_prompt(5, 9), max_new_tokens=2).result(timeout=120)
    finally:
        eng.shutdown()
