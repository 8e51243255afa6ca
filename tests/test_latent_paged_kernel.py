"""The decode kernel over a paged latent pool (PR 44, ``ops/latent_attention.py``)
and the prefill chunk's (PR 48) against the view they replace
(``generate._paged_view`` + ``_latent_attention``'s ``bhqr`` product, the
chunk's under ``_cache_mask``), interpreted on the CPU; and ``LLMEngine`` over a
latent pool where the kernels are chosen: one decode program,
``latent_kernel_steps``, ``latent_kernel_chunks``, the XLA path's tokens."""

import numpy as np
import pytest

L, N, BS, W, H = 3, 48, 8, 128, 4
SCALE = 0.11


def _weighted_rows(q, view, mask):
    """``_latent_attention``'s two products ahead of W_uv: q [B, T, H, W] over view [B, S, W] under mask [B, T, S]."""
    import jax
    import jax.numpy as jnp

    s = jnp.einsum("bqhr,bkr->bhqk", q, view, preferred_element_type=jnp.float32)
    p = jax.nn.softmax(jnp.where(mask[:, None], s * SCALE, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkr->bhqr", p.astype(view.dtype), view, preferred_element_type=jnp.float32).astype(q.dtype)


def _view_result(q, ckv, layer, tables, lengths):
    """What ``_latent_attention`` computes ahead of W_uv, over ``_paged_view``'s rows."""
    import jax.numpy as jnp

    from ray_tpu.models.generate import _paged_view

    view = _paged_view(tables)(ckv, layer)
    return _weighted_rows(q, view, (jnp.arange(view.shape[1])[None, :] < lengths[:, None])[:, None])


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


# --- the prefill chunk's kernel ---


def _chunk_view_result(q, ckv, layer, tables, starts, ends):
    """What ``_latent_attention`` computes ahead of W_uv for a chunk q [B, T, H, W]
    whose row b starts at ``starts[b]``, over ``_paged_view``'s rows under
    ``_cache_mask``'s causal mask (and ``ends``: no key past a row's real rows)."""
    import jax.numpy as jnp

    from ray_tpu.models.generate import _cache_mask, _paged_view

    view = _paged_view(tables)(ckv, layer)
    positions = starts[:, None] + jnp.arange(q.shape[1], dtype=jnp.int32)[None, :]
    return _weighted_rows(q, view, _cache_mask(positions, view.shape[1], 0, key_len=ends))


def _chunk_tables(starts, T, live, n_max, rng, blocks=N):
    """A table a row that holds its context and the chunk, scattered over the
    pool; an inactive row's is the null block all through."""
    free = rng.permutation(np.arange(1, blocks))
    tables, k = np.zeros((len(starts), n_max), np.int32), 0
    for b, start in enumerate(starts):
        n = -(-(start + T) // BS) if live[b] else 0
        tables[b, :n] = free[k : k + n]
        k += n
    assert k <= blocks - 1
    return tables


# name -> (each row's first position, chunk T, each row's real rows (0: an inactive row), n_max, heads,
#          blocks a compute step takes, rows a tile holds at most)
CHUNK_CASES = {
    "context 0": ([0], 32, [32], 6, 4, 2, 64),
    "context 0, one tile and one step": ([0], 16, [16], 2, 4, 2, 64),
    "a context that ends inside a block and inside a compute step": ([37], 32, [32], 12, 4, 4, 64),
    "a context near the table's end": ([150], 32, [32], 23, 4, 4, 64),
    "a chunk that fills the table": ([160], 32, [32], 24, 4, 8, 64),
    "rows at different positions": ([90, 3, 41], 32, [32, 32, 32], 16, 4, 2, 64),
    "a padded last chunk": ([21], 48, [19], 10, 4, 2, 64),
    "a padded last chunk whose real rows end on a tile's edge": ([8], 48, [32], 8, 4, 2, 64),
    "a padded chunk beside a whole one": ([5, 70], 32, [7, 32], 14, 4, 4, 64),
    "an inactive row first, between and last": ([0, 33, 0, 12, 0], 32, [0, 32, 0, 20, 0], 10, 4, 2, 64),
    "a chunk no tile divides": ([13], 20, [20], 6, 4, 2, 64),
    "a tile as long as the chunk": ([29], 32, [32], 8, 4, 4, 4096),
    "20 heads": ([45], 32, [32], 10, 20, 4, 320),
    "32 heads": ([45], 32, [25], 10, 32, 4, 512),
}


@pytest.mark.parametrize("case", sorted(CHUNK_CASES))
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_chunk_kernel_reads_what_the_view_reads(case, dtype, monkeypatch):
    """Every real query of a chunk against the view's answer; a padded query
    (at or past its row's ``ends``) and an inactive row give finite numbers,
    the inactive row zeros."""
    import jax.numpy as jnp

    from ray_tpu.ops import latent_attention

    starts, T, real, n_max, heads, pages, tile_rows = CHUNK_CASES[case]
    monkeypatch.setattr(latent_attention, "_CHUNK_PAGES", pages)
    monkeypatch.setattr(latent_attention, "_TILE_ROWS", tile_rows)
    rng = np.random.default_rng(len(case))
    blocks = 96
    ckv = jnp.asarray(rng.standard_normal((L, blocks, BS, W)), dtype)
    q = jnp.asarray(rng.standard_normal((len(starts), T, heads, W)), dtype)
    live = [n > 0 for n in real]
    tables = jnp.asarray(_chunk_tables(starts, T, live, n_max, rng, blocks))
    first = jnp.asarray(starts, jnp.int32)
    ends = jnp.asarray([s + n if n else 0 for s, n in zip(starts, real)], jnp.int32)
    got = latent_attention.paged_latent_chunk_attention(q, ckv, jnp.int32(1), tables, first, ends, sm_scale=SCALE, interpret=True)
    assert got.shape == (len(starts), heads, T, W) and got.dtype == q.dtype
    want = _chunk_view_result(q, ckv, 1, tables, first, jnp.maximum(ends, 1))
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.isfinite(got).all()
    tol = 2e-2 if dtype == "bfloat16" else 3e-6
    for b, n in enumerate(real):
        if n:
            np.testing.assert_allclose(got[b, :, :n], want[b, :, :n], atol=tol, rtol=tol)
        else:
            assert not got[b].any()  # an inactive row read nothing: zeros


def test_the_chunk_kernel_sums_over_the_columns_it_is_told(monkeypatch):
    """``value_width``: the weighted sum over the rows' first columns alone (the
    latent's; what the caller keeps), zeros in the others; a width that is no
    whole number of lane tiles is not taken."""
    import jax.numpy as jnp

    from ray_tpu.ops import latent_attention

    monkeypatch.setattr(latent_attention, "_CHUNK_PAGES", 2)
    monkeypatch.setattr(latent_attention, "_TILE_ROWS", 64)
    rng = np.random.default_rng(23)
    wide = 2 * W
    ckv = jnp.asarray(rng.standard_normal((L, N, BS, wide)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((2, 32, H, wide)), jnp.float32)
    starts, ends = jnp.asarray([27, 4], jnp.int32), jnp.asarray([59, 30], jnp.int32)
    tables = jnp.asarray(_chunk_tables([27, 4], 32, [True, True], 10, rng))
    run = lambda **kw: np.asarray(latent_attention.paged_latent_chunk_attention(q, ckv, jnp.int32(0), tables, starts, ends, sm_scale=SCALE, interpret=True, **kw))  # noqa: E731
    whole, cut = run(), run(value_width=W)
    np.testing.assert_allclose(cut[..., :W], whole[..., :W], atol=3e-6, rtol=3e-6)
    assert not cut[..., W:].any() and whole[..., W:].any()
    np.testing.assert_array_equal(run(value_width=W - 8), whole)


def test_the_chunk_kernel_reads_the_layer_it_is_told_and_no_block_past_a_query(monkeypatch):
    """The layer is an operand; what lies past a row's real rows (the rest of
    their last block, the blocks behind it in the table, the null block the
    padding was written to) and every other layer moves nothing, NaNs
    included; nor does a tile read a block past its own last query (a NaN
    there would reach it through 0 x NaN, masked or not)."""
    import jax.numpy as jnp

    from ray_tpu.ops import latent_attention as la

    rng = np.random.default_rng(11)
    starts, T, real, n_max = [40, 9], 32, [32, 21], 16
    tables = _chunk_tables([s + 40 for s in starts], T, [True, True], n_max, rng)  # real blocks well past the rows' ends
    first, ends = jnp.asarray(starts, jnp.int32), jnp.asarray([s + n for s, n in zip(starts, real)], jnp.int32)
    ckv = rng.standard_normal((L, N, BS, W)).astype(np.float32)
    q = jnp.asarray(rng.standard_normal((2, T, H, W)), jnp.float32)

    monkeypatch.setattr(la, "_CHUNK_PAGES", 2)  # a step of 16 rows
    monkeypatch.setattr(la, "_TILE_ROWS", 64)  # and a tile of 16 queries: a block past a TILE's last query is poisoned too

    def run(pool, layer):
        return np.asarray(la.paged_latent_chunk_attention(q, jnp.asarray(pool), jnp.int32(layer), jnp.asarray(tables), first, ends, sm_scale=SCALE, interpret=True))

    clean = run(ckv, 2)
    want = np.asarray(_chunk_view_result(q, jnp.asarray(ckv), 2, jnp.asarray(tables), first, ends))
    for b, n in enumerate(real):
        np.testing.assert_allclose(clean[b, :, :n], want[b, :, :n], atol=3e-6, rtol=3e-6)
    assert np.abs(run(ckv, 0) - clean).max() > 1e-3
    dirty = ckv.copy()
    dirty[:2] = np.nan  # the other layers
    dirty[2, 0] = np.nan  # the null block
    for b, end in enumerate(np.asarray(ends)):
        for j in range(-(-int(end) // BS), n_max):  # whole blocks past the row's real rows
            dirty[2, tables[b, j]] = np.nan
    np.testing.assert_array_equal(run(dirty, 2), clean)
    # Row 0's first tile (queries 40..55) walks blocks 0..6 and no further: a NaN in block 7, which its second tile reads.
    late = ckv.copy()
    late[2, tables[0, 7]] = np.nan
    got = run(late, 2)
    assert np.isfinite(got[0, :, :16]).all() and np.isnan(got[0, :, 16:]).all() and np.isfinite(got[1]).all()


@pytest.mark.parametrize("pool, q, backend, reads", [
    ("latent", 1, "tpu", True), ("latent", 512, "tpu", True), ("latent", 16, "tpu", True),
    ("kv", 1, "tpu", True), ("kv", 512, "tpu", False),
    ("latent", 1, "cpu", False), ("latent", 512, "cpu", False), ("kv", 1, "cpu", False),
    ("pattern", 1, "tpu", False), ("pattern", 512, "tpu", False),
])
def test_the_one_predicate_by_pool_width_and_backend(pool, q, backend, reads, monkeypatch):
    """``generate.kernel_reads``: a paged latent pool on a TPU reads in place at
    ANY q (PR 48: the chunk has its kernel), a pool of one group of keys and
    values at q = 1 only, a pattern's pool and the CPU never; the dense cache
    (``paged`` False) never."""
    import importlib

    import jax.numpy as jnp

    from ray_tpu.models.transformer import TransformerConfig

    generate = importlib.import_module("ray_tpu.models.generate")
    small = dict(vocab_size=64, d_model=32, n_layers=4, n_heads=2, n_kv_heads=2, d_ff=64, max_seq_len=64, dtype=jnp.float32)
    cfg = {
        "latent": lambda: TransformerConfig(**MODEL, dtype=jnp.float32),
        "kv": lambda: TransformerConfig(**small),
        "pattern": lambda: TransformerConfig(**small, sliding_window=24, layer_kinds=("window", "window", "window", "full")),
    }[pool]()
    monkeypatch.setattr(generate._attention_ops, "_on_tpu", lambda: backend == "tpu")
    assert generate.kernel_reads(cfg, True, q) == reads
    assert not generate.kernel_reads(cfg, False, q)
    assert generate.latent_kernel_reads(cfg, True, q) == (reads and pool == "latent")
    assert generate.kv_kernel_reads(cfg, True, q) == (reads and pool == "kv")


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
    assert stats["latent_kernel_chunks"] == 0

    _, cfg = model
    assert not generate.latent_kernel_reads(cfg, paged=True, q=1)
    monkeypatch.setattr(engine, "_JIT_CACHE", {})  # programs traced under the other answer
    monkeypatch.setattr(generate._attention_ops, "_on_tpu", lambda: True)
    assert generate.latent_kernel_reads(cfg, paged=True, q=1)
    assert generate.latent_kernel_reads(cfg, paged=True, q=16)  # and so does a prefill chunk (PR 48)
    assert not generate.latent_kernel_reads(cfg, paged=False, q=1)  # the dense cache keeps the view
    got, stats, rungs = _serve(model, prompts, new_tokens)
    assert got == want
    assert rungs == (48,) and set(stats["decode_width_steps"]) == {48}
    assert stats["latent_kernel_steps"] == stats["decode_steps"] == stats["decode_width_steps"][48] > 0
    assert stats["kv_pool_not_donated"] == 0


def test_a_latent_pools_prefill_passes_read_it_in_place_where_the_kernel_is_chosen(model, monkeypatch):
    """PR 48: told that the backend is a TPU's, every prefill pass of a latent
    pool's engine runs the program whose chunk reads the pool in place (two
    tiles a chunk here, compute steps of two blocks, prompts that end inside a
    chunk, a prompt of several chunks beside rows that decode):
    ``latent_kernel_chunks`` counts them all, and the tokens are the view's."""
    import importlib

    from ray_tpu.ops import latent_attention
    from ray_tpu.serve.llm import LLMEngine

    generate = importlib.import_module("ray_tpu.models.generate")
    engine = importlib.import_module("ray_tpu.serve.llm.engine")
    params, cfg = model
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, MODEL["vocab_size"], n).tolist() for n in (70, 9, 200, 32)]
    new_tokens = (6, 14, 5, 8)
    settings = {**ENGINE, "prefill_chunk": 32}

    def serve():
        eng = LLMEngine(params, cfg, **settings)
        try:
            reqs = [eng.submit(p, max_new_tokens=n) for p, n in zip(prompts, new_tokens)]
            return [r.result(timeout=300) for r in reqs], eng.stats()
        finally:
            eng.shutdown()

    monkeypatch.setattr(engine, "_JIT_CACHE", {})
    want, stats = serve()
    passes = (stats["chunk_tokens_valid"] + stats["chunk_tokens_padded"]) // settings["prefill_chunk"]
    assert passes >= 3 + 1 + 7 + 1 and stats["latent_kernel_chunks"] == 0 and stats["chunk_tokens_padded"] > 0
    monkeypatch.setattr(engine, "_JIT_CACHE", {})
    monkeypatch.setattr(generate._attention_ops, "_on_tpu", lambda: True)
    monkeypatch.setattr(latent_attention, "_TILE_ROWS", 64)
    monkeypatch.setattr(latent_attention, "_CHUNK_PAGES", 2)
    got, stats = serve()
    assert got == want
    assert stats["latent_kernel_chunks"] == (stats["chunk_tokens_valid"] + stats["chunk_tokens_padded"]) // settings["prefill_chunk"] >= passes
    assert stats["latent_kernel_steps"] == stats["decode_steps"] > 0 and stats["kv_kernel_steps"] == 0
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
