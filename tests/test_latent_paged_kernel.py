"""The decode kernel over a paged latent pool (PR 44, ``ops/latent_attention.py``)
against the view it replaces (``generate._paged_view`` + ``_latent_attention``'s
``bhqr`` product), interpreted on the CPU; and ``LLMEngine`` over a latent pool
where the kernel is chosen: one decode program, ``latent_kernel_steps``, the
XLA path's tokens."""

import numpy as np
import pytest

L, N, BS, W, H = 3, 48, 8, 128, 4
SCALE = 0.11


def _view_result(q, ckv, layer, tables, lengths):
    """What ``_latent_attention`` computes ahead of W_uv, over ``_paged_view``'s rows."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.generate import _paged_view

    view = _paged_view(tables)(ckv, layer)
    mask = jnp.arange(view.shape[1])[None, :] < lengths[:, None]
    s = jnp.einsum("bqhr,bkr->bhqk", q, view, preferred_element_type=jnp.float32)
    p = jax.nn.softmax(jnp.where(mask[:, None, None], s * SCALE, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkr->bhqr", p.astype(view.dtype), view, preferred_element_type=jnp.float32).astype(q.dtype)


def _tables(lengths, n_max, rng, shuffled=True):
    """Each slot's blocks, distinct and (``shuffled``) scattered over the pool;
    past a slot's last block the null block."""
    blocks = rng.permutation(np.arange(1, N)) if shuffled else np.arange(1, N)
    tables, k = np.zeros((len(lengths), n_max), np.int32), 0
    for b, n in enumerate(-(-np.asarray(lengths) // BS)):
        tables[b, :n] = blocks[k : k + n]
        k += n
    return tables


# name -> (lengths, n_max, blocks a compute step takes)
CASES = {
    "ragged lengths": ([5, 33, 70, 18], 10, 4),
    "a length of 1": ([1, 40], 8, 4),
    "a length on a block's edge and one past it": ([16, 17, 32, 33], 8, 2),
    "a slot of length 0": ([23, 0, 0, 9], 8, 4),
    "a slot of length 0 first and last": ([0, 50, 0], 8, 2),
    "a table whose tail is the null block": ([9, 12], 12, 4),
    "a table in the pool's order": ([64, 30], 8, 4),
    "n_max no multiple of a step's blocks": ([56, 49, 3], 7, 3),
    "a step as wide as the table": ([40, 7], 5, 32),
    "every slot full": ([64, 64, 64], 8, 4),
}


@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_kernel_reads_what_the_view_reads(case, dtype, monkeypatch):
    import jax.numpy as jnp

    from ray_tpu.ops import latent_attention

    lengths, n_max, pages = CASES[case]
    monkeypatch.setattr(latent_attention, "_PAGES", pages)
    rng = np.random.default_rng(len(case))
    ckv = jnp.asarray(rng.standard_normal((L, N, BS, W)), dtype)
    q = jnp.asarray(rng.standard_normal((len(lengths), 1, H, W)), dtype)
    tables = jnp.asarray(_tables(lengths, n_max, rng, shuffled="pool's order" not in case))
    lengths = jnp.asarray(lengths, jnp.int32)
    got = latent_attention.paged_latent_attention(q, ckv, jnp.int32(1), tables, lengths, sm_scale=SCALE, interpret=True)
    assert got.shape == (len(lengths), H, 1, W) and got.dtype == q.dtype
    live = np.asarray(lengths) > 0
    want = _view_result(q, ckv, 1, tables, jnp.maximum(lengths, 1))
    tol = 2e-2 if dtype == "bfloat16" else 2e-6
    np.testing.assert_allclose(
        np.asarray(got, np.float32)[live], np.asarray(want, np.float32)[live], atol=tol, rtol=tol
    )
    assert not np.asarray(got, np.float32)[~live].any()  # a slot of length 0 read nothing: zeros


def test_the_kernel_reads_the_layer_it_is_told_and_no_block_past_a_length():
    """The layer is an operand; what lies behind a slot's length (the rest of
    its last block, the blocks behind it in its table) moves nothing, NaNs included."""
    import jax.numpy as jnp

    from ray_tpu.ops.latent_attention import paged_latent_attention

    rng = np.random.default_rng(7)
    lengths = np.array([19, 42], np.int32)
    tables = _tables([64, 64], 8, rng)  # every entry a real block; the lengths stop short of them
    ckv = rng.standard_normal((L, N, BS, W)).astype(np.float32)
    q = jnp.asarray(rng.standard_normal((2, 1, H, W)), jnp.float32)
    clean = paged_latent_attention(q, jnp.asarray(ckv), jnp.int32(2), jnp.asarray(tables), jnp.asarray(lengths), sm_scale=SCALE, interpret=True)
    want = _view_result(q, jnp.asarray(ckv), 2, jnp.asarray(tables), jnp.asarray(lengths))
    np.testing.assert_allclose(np.asarray(clean), np.asarray(want), atol=2e-6, rtol=2e-6)
    other = paged_latent_attention(q, jnp.asarray(ckv), jnp.int32(0), jnp.asarray(tables), jnp.asarray(lengths), sm_scale=SCALE, interpret=True)
    assert np.abs(np.asarray(other) - np.asarray(clean)).max() > 1e-3
    dirty = ckv.copy()
    for b, n in enumerate(lengths):
        for j in range(-(-int(n) // BS), 8):  # whole blocks past the length
            dirty[2, tables[b, j]] = np.nan
    got = paged_latent_attention(q, jnp.asarray(dirty), jnp.int32(2), jnp.asarray(tables), jnp.asarray(lengths), sm_scale=SCALE, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(clean))


# --- the engine over a latent pool, the kernel chosen ---

MODEL = dict(
    vocab_size=128, d_model=64, n_layers=3, n_heads=4, n_kv_heads=4, d_ff=96, max_seq_len=384,
    num_experts=8, experts_per_token=2, d_expert=32, num_shared_experts=1, routed_scaling_factor=1.8,
    first_dense_layers=1, q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=12, qk_rope_head_dim=8, v_head_dim=20,
)
ENGINE = dict(num_slots=4, block_size=8, max_model_len=384, prefill_chunk=16)


@pytest.fixture(scope="module")
def model():
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.transformer import TransformerConfig, init_params

    cfg = TransformerConfig(**MODEL, dtype=jnp.float32, param_dtype=jnp.float32)
    return init_params(jax.random.PRNGKey(0), cfg), cfg


def _serve(model, prompts, new_tokens):
    from ray_tpu.serve.llm import LLMEngine

    params, cfg = model
    eng = LLMEngine(params, cfg, **ENGINE)
    try:
        reqs = [eng.submit(p, max_new_tokens=n) for p, n in zip(prompts, new_tokens)]
        return [r.result(timeout=300) for r in reqs], eng.stats(), eng._view_rungs
    finally:
        eng.shutdown()


def test_a_latent_pool_has_one_decode_program_where_the_kernel_reads_it(model, monkeypatch):
    """On the CPU the predicate says no: the ladder, the view, ``latent_kernel_steps``
    0. Told that the backend is a TPU's (the kernel then runs interpreted: the
    backend is still this one), the engine hands every step the whole table,
    builds one decode program, counts every step, and serves the same tokens."""
    import importlib

    generate = importlib.import_module("ray_tpu.models.generate")  # the package's ``generate`` is the function
    engine = importlib.import_module("ray_tpu.serve.llm.engine")

    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, MODEL["vocab_size"], n).tolist() for n in (37, 5, 150)]
    new_tokens = (12, 20, 9)
    want, stats, rungs = _serve(model, prompts, new_tokens)
    assert rungs == (16, 32, 48) and stats["latent_kernel_steps"] == 0 and stats["decode_steps"] > 0

    _, cfg = model
    assert not generate.latent_kernel_reads(cfg, paged=True, q=1)
    monkeypatch.setattr(engine, "_JIT_CACHE", {})  # programs traced under the other answer
    monkeypatch.setattr(generate._attention_ops, "_on_tpu", lambda: True)
    assert generate.latent_kernel_reads(cfg, paged=True, q=1)
    assert not generate.latent_kernel_reads(cfg, paged=True, q=16)  # a prefill chunk keeps the view
    assert not generate.latent_kernel_reads(cfg, paged=False, q=1)  # and so does the dense cache
    got, stats, rungs = _serve(model, prompts, new_tokens)
    assert got == want
    assert rungs == (48,) and set(stats["decode_width_steps"]) == {48}
    assert stats["latent_kernel_steps"] == stats["decode_steps"] == stats["decode_width_steps"][48] > 0
    assert stats["kv_pool_not_donated"] == 0


@pytest.mark.parametrize("kind", ["one_group", "layer_pattern"])
def test_only_a_latent_pool_loses_its_ladder(kind, monkeypatch):
    """Restated in PR 45: on a TPU a pool of ONE group of key and value leaves
    loses its ladder too (``kv_kernel_reads``, tests/test_paged_kv_kernel.py).
    A layer pattern's pool keeps it: the predicates are about the pool's kind,
    and its decode program, lowered for the TPU, is text for text the one it
    gets where no kernel reads anything (the parent's: tests/test_tpu_lowering.py
    holds the cells' to their hashes)."""
    import importlib

    import jax
    import jax.numpy as jnp

    from ray_tpu.models.generate import init_paged_cache, ring_blocks
    from ray_tpu.models.transformer import TransformerConfig, init_params

    generate = importlib.import_module("ray_tpu.models.generate")
    engine = importlib.import_module("ray_tpu.serve.llm.engine")
    over = dict(n_layers=4, sliding_window=24, layer_kinds=("window", "window", "window", "full")) if kind == "layer_pattern" else {}
    cfg = TransformerConfig(**{**dict(vocab_size=64, d_model=32, n_layers=1, n_heads=2, n_kv_heads=2, d_ff=64, max_seq_len=64, dtype=jnp.float32), **over})
    slots, bs, chunk, n_max = 3, 4, 8, 16
    ring = ring_blocks(cfg.sliding_window, chunk, bs) if cfg.layer_kinds else 0

    def lowered():
        monkeypatch.setattr(engine, "_JIT_CACHE", {})
        pool = jax.eval_shape(lambda: init_paged_cache(cfg, 49, bs, window_blocks=slots * ring + 1 if ring else 0))
        params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
        ints = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
        args = params, ints(slots, engine._ROW_TABLE + ring + n_max), pool, ints(slots)
        return engine._compiled_fns(cfg, ring)[0].trace(*args).lower(lowering_platforms=("tpu",)).as_text()

    view = lowered()
    monkeypatch.setattr(generate._attention_ops, "_on_tpu", lambda: True)
    assert not generate.latent_kernel_reads(cfg, paged=True, q=1)
    assert generate.kv_kernel_reads(cfg, paged=True, q=1) == (kind == "one_group")
    assert (lowered() == view) == (kind == "layer_pattern")
    params = init_params(jax.random.PRNGKey(0), cfg)
    eng = engine.LLMEngine(params, cfg, num_slots=slots, block_size=bs, max_model_len=n_max * bs * 2, prefill_chunk=chunk)
    try:
        assert eng._view_rungs == ((2 * n_max,) if kind == "one_group" else (16, 2 * n_max))
    finally:
        eng.shutdown()
