"""The decode kernel over a paged pool of keys and values (PR 45,
``ops/paged_attention.py``) against the view it replaces (``generate._paged_view``
+ ``_cache_mask`` + ``_cache_attention``), interpreted on the CPU; and
``LLMEngine`` over a one-group K/V pool where the kernel is chosen: one decode
program, ``kv_kernel_steps``, the XLA path's tokens, a chunk riding the step."""

import importlib

import numpy as np
import pytest

L, N, BS, DH, H = 3, 64, 8, 128, 8


def _view_result(q, k, v, layer, tables, lengths, window):
    """``_cache_attention`` over ``_paged_view``'s rows under ``_cache_mask``, each query at ``lengths - 1``."""
    import jax.numpy as jnp

    from ray_tpu.models.generate import _cache_attention, _cache_mask, _paged_view
    from ray_tpu.models.transformer import TransformerConfig

    cfg = TransformerConfig(
        vocab_size=64, d_model=H * DH, n_layers=L, n_heads=H, n_kv_heads=k.shape[3], d_ff=64, max_seq_len=64,
        dtype=q.dtype.type, sliding_window=window,
    )
    ck, cv = _paged_view(tables)(k, layer), _paged_view(tables)(v, layer)
    mask = _cache_mask(lengths[:, None] - 1, ck.shape[1], window, None, None)
    return _cache_attention(q, ck, cv, mask, cfg)


def _tables(lengths, n_max, rng, shuffled=True):
    """Each slot's blocks, distinct and (``shuffled``) scattered over the pool;
    past a slot's last block the null block."""
    blocks = rng.permutation(np.arange(1, N)) if shuffled else np.arange(1, N)
    tables, j = np.zeros((len(lengths), n_max), np.int32), 0
    for b, n in enumerate(-(-np.asarray(lengths) // BS)):
        tables[b, :n] = blocks[j : j + n]
        j += n
    return tables


# name -> (lengths, n_max, blocks a compute step takes, sliding window)
CASES = {
    "ragged lengths": ([5, 33, 70, 18], 10, 4, 0),
    "a length of 1": ([1, 40], 8, 4, 0),
    "a length on a block's edge and one past it": ([16, 17, 32, 33], 8, 2, 0),
    "a length on a compute step's edge and one past it": ([32, 33, 64, 65], 10, 4, 0),
    "past one step": ([100, 49, 3], 13, 3, 0),
    "a slot of length 0": ([23, 0, 0, 9], 8, 4, 0),
    "a slot of length 0 first and last": ([0, 50, 0], 8, 2, 0),
    "a table in the pool's order": ([64, 30], 8, 4, 0),
    "a step as wide as the table": ([40, 7], 5, 32, 0),
    "every slot full": ([64, 64, 64], 8, 4, 0),
    "a window shorter than the row": ([70, 21, 20, 5, 0, 47], 10, 2, 20),
    "a window of one block's rows": ([8, 9, 33, 64], 8, 4, 8),
    "a window longer than every row": ([5, 33, 70], 10, 4, 4096),
}


@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("group", [1, 4, 8])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_kernel_reads_what_the_view_reads(case, group, dtype, monkeypatch):
    import jax.numpy as jnp

    from ray_tpu.ops import paged_attention

    lengths, n_max, pages, window = CASES[case]
    monkeypatch.setattr(paged_attention, "_PAGES", pages)
    rng = np.random.default_rng(len(case) + group)
    KV = H // group
    k, v = (jnp.asarray(rng.standard_normal((L, N, BS, KV, DH)), dtype) for _ in range(2))
    q = jnp.asarray(rng.standard_normal((len(lengths), 1, H, DH)), dtype)
    tables = jnp.asarray(_tables(lengths, n_max, rng, shuffled="pool's order" not in case))
    lengths = jnp.asarray(lengths, jnp.int32)
    got = paged_attention.paged_attention(
        q, k, v, jnp.int32(1), tables, lengths, sm_scale=DH**-0.5, window=window, interpret=True
    )
    assert got.shape == q.shape and got.dtype == q.dtype
    live = np.asarray(lengths) > 0
    want = _view_result(q, k, v, 1, tables, jnp.maximum(lengths, 1), window)
    tol = 2e-2 if dtype == "bfloat16" else 2e-6
    np.testing.assert_allclose(
        np.asarray(got, np.float32)[live], np.asarray(want, np.float32)[live], atol=tol, rtol=tol
    )
    assert not np.asarray(got, np.float32)[~live].any()  # a slot of length 0 read nothing: zeros


@pytest.mark.parametrize("window", [0, 24])
def test_the_kernel_reads_the_layer_it_is_told_and_no_block_outside_what_a_query_sees(window):
    """The layer is an operand; what lies behind a slot's length (the blocks
    behind it in its table) and, under a window, before the first block the
    window reaches moves nothing, NaNs included: it is never copied."""
    import jax.numpy as jnp

    from ray_tpu.ops.paged_attention import paged_attention

    rng = np.random.default_rng(7)
    lengths = np.array([19, 42, 57], np.int32)
    tables = _tables([64, 64, 64], 8, rng)  # every entry a real block; the lengths stop short of them
    k, v = (rng.standard_normal((L, N, BS, 2, DH)).astype(np.float32) for _ in range(2))
    q = jnp.asarray(rng.standard_normal((3, 1, H, DH)), jnp.float32)

    def run(k, v, layer):
        return paged_attention(
            q, jnp.asarray(k), jnp.asarray(v), jnp.int32(layer), jnp.asarray(tables), jnp.asarray(lengths),
            sm_scale=DH**-0.5, window=window, interpret=True,
        )

    clean = run(k, v, 2)
    want = _view_result(q, jnp.asarray(k), jnp.asarray(v), 2, jnp.asarray(tables), jnp.asarray(lengths), window)
    np.testing.assert_allclose(np.asarray(clean), np.asarray(want), atol=2e-6, rtol=2e-6)
    assert np.abs(np.asarray(run(k, v, 0)) - np.asarray(clean)).max() > 1e-3
    dirty_k, dirty_v = k.copy(), v.copy()
    for b, n in enumerate(lengths):
        unseen = list(range(-(-int(n) // BS), 8))  # whole blocks past the length
        if window:
            unseen += range((int(n) - window) // BS)  # and before the window's first
        for j in unseen:
            dirty_k[2, tables[b, j]] = dirty_v[2, tables[b, j]] = np.nan
    assert window == 0 or np.isnan(dirty_k[2, tables[2, 0]]).all()
    np.testing.assert_array_equal(np.asarray(run(dirty_k, dirty_v, 2)), np.asarray(clean))


# --- the engine over a one-group K/V pool, the kernel chosen ---

MODEL = dict(vocab_size=128, d_model=64, n_layers=3, n_heads=4, n_kv_heads=2, d_ff=96, max_seq_len=384, sliding_window=4096)
ENGINE = dict(num_slots=4, block_size=8, max_model_len=384, prefill_chunk=16)


@pytest.fixture(scope="module")
def model():
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.transformer import TransformerConfig, init_params

    cfg = TransformerConfig(**MODEL, dtype=jnp.float32, param_dtype=jnp.float32)
    return init_params(jax.random.PRNGKey(0), cfg), cfg


@pytest.mark.parametrize("window", [0, 24, 4096])
def test_a_decode_step_through_the_kernel_gives_the_views_logits(window, monkeypatch):
    """``paged_decode_step`` where the predicate holds against where it does
    not, over a pool of random rows: a window a row can outgrow is the kernel's,
    one the table cannot reach is none, and the pool is written the same (to rounding)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.transformer import TransformerConfig, init_params

    generate = importlib.import_module("ray_tpu.models.generate")
    cfg = TransformerConfig(**{**MODEL, "sliding_window": window}, dtype=jnp.float32, param_dtype=jnp.float32)
    params = init_params(jax.random.PRNGKey(1), cfg)
    rng = np.random.default_rng(window)
    pool = {
        name: jnp.asarray(rng.standard_normal(leaf.shape), leaf.dtype)
        for name, leaf in generate.init_paged_cache(cfg, N, BS).items()
    }
    lengths = np.array([5, 33, 70, 0])
    tables, pos = jnp.asarray(_tables(lengths, 12, rng)), jnp.asarray(np.maximum(lengths - 1, 0), jnp.int32)
    token = jnp.asarray(rng.integers(0, cfg.vocab_size, len(lengths)), jnp.int32)
    want, pool_view = generate.paged_decode_step(params, token, pool, tables, pos, cfg)
    monkeypatch.setattr(generate._attention_ops, "_on_tpu", lambda: True)
    got, pool_kernel = generate.paged_decode_step(params, token, pool, tables, pos, cfg)
    live = lengths > 0
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live], atol=2e-4, rtol=2e-4)
    if window == 24:  # and the window tells: the 70-token row without it reads otherwise
        full, _ = generate.paged_decode_step(params, token, pool, tables, pos, TransformerConfig(**MODEL, dtype=jnp.float32, param_dtype=jnp.float32))
        assert np.abs(np.asarray(full)[2] - np.asarray(got)[2]).max() > 1e-3
    for name in pool:  # the rows written, but the inactive slot's in the null block: it attends to zeros now
        np.testing.assert_allclose(np.asarray(pool_kernel[name])[:, 1:], np.asarray(pool_view[name])[:, 1:], atol=2e-5, rtol=2e-5)


def _serve(model, prompts, new_tokens, stagger=0):
    """Greedy tokens of ``prompts``; ``stagger``: the later prompts are sent
    once the first has drawn that many tokens, so their chunks find rows decoding."""
    from ray_tpu.serve.llm import LLMEngine

    params, cfg = model
    eng = LLMEngine(params, cfg, **ENGINE)
    try:
        reqs = [eng.submit(prompts[0], max_new_tokens=new_tokens[0])]
        stream = iter(reqs[0])
        head = [next(stream) for _ in range(stagger)]
        reqs += [eng.submit(p, max_new_tokens=n) for p, n in zip(prompts[1:], new_tokens[1:])]
        out = [head + list(stream)] + [r.result(timeout=300) for r in reqs[1:]]
        return out, eng.stats(), eng._view_rungs
    finally:
        eng.shutdown()


def test_a_kv_pool_has_one_decode_program_where_the_kernel_reads_it(model, monkeypatch):
    """On the CPU the predicate says no: the ladder, the view, ``kv_kernel_steps``
    0. Told that the backend is a TPU's (the kernel then runs interpreted), the
    engine hands every step the whole table, builds one decode program, counts
    every step, and serves the same tokens: through passes in which a chunk
    rides the step too, and one in which a prompt's last chunk lands its first
    token from inside the step."""
    generate = importlib.import_module("ray_tpu.models.generate")  # the package's ``generate`` is the function
    engine = importlib.import_module("ray_tpu.serve.llm.engine")

    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, MODEL["vocab_size"], n).tolist() for n in (37, 5, 150, 70)]
    new_tokens = (40, 20, 9, 12)
    want, stats, rungs = _serve(model, prompts, new_tokens, stagger=3)
    assert rungs == (16, 32, 48) and stats["kv_kernel_steps"] == 0 and stats["decode_steps"] > 0
    assert stats["latent_kernel_chunks"] == 0
    assert stats["decode_steps_with_chunk"] > 0

    _, cfg = model
    assert not generate.kv_kernel_reads(cfg, paged=True, q=1)
    monkeypatch.setattr(engine, "_JIT_CACHE", {})  # programs traced under the other answer
    monkeypatch.setattr(generate._attention_ops, "_on_tpu", lambda: True)
    assert generate.kv_kernel_reads(cfg, paged=True, q=1)
    assert not generate.kv_kernel_reads(cfg, paged=True, q=16)  # a prefill chunk keeps the view
    assert not generate.kv_kernel_reads(cfg, paged=False, q=1)  # and so does the dense cache
    assert not generate.latent_kernel_reads(cfg, paged=True, q=1)
    got, stats, rungs = _serve(model, prompts, new_tokens, stagger=3)
    assert got == want
    assert rungs == (48,) and set(stats["decode_width_steps"]) == {48}
    assert stats["kv_kernel_steps"] == stats["decode_steps"] == stats["decode_width_steps"][48] > 0
    assert stats["latent_kernel_steps"] == 0 and stats["latent_kernel_chunks"] == 0  # a K/V pool's chunk keeps the view
    # Chunks rode steps (three prompts arrive while the first decodes: 15 chunks), a last chunk among them.
    assert stats["decode_steps_with_chunk"] >= 10
    assert stats["kv_pool_not_donated"] == 0
