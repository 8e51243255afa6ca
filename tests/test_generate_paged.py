"""Paged KV-cache decode correctness (ISSUE 11 tentpole, model layer).

The serving oracle: paged attention over a block table must produce the SAME
tokens as the dense-cache path for any schedule the engine can produce —
fragmented/out-of-order physical blocks, inactive slots sharing the batch,
write-masked padded prefill chunks. Dense decode_step/decode_chunk are the
reference; tokens (argmax chains) must match exactly.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.generate import (
    decode_chunk,
    init_cache,
    init_paged_cache,
    paged_decode_chunk,
    paged_decode_step,
    prefill,
)
from ray_tpu.models.transformer import TransformerConfig, init_params


def _cfg(**kw):
    base = dict(
        vocab_size=128,
        d_model=48,
        n_layers=2,
        n_heads=4,
        n_kv_heads=4,
        d_ff=64,
        max_seq_len=64,
        dtype=jnp.float32,
        remat=False,
    )
    base.update(kw)
    return TransformerConfig(**base)


def _paged_prefill(params, toks, cache, table, cfg, chunk=4):
    """Chunked prefill of a single sequence through paged_decode_chunk
    (exactly what the serving engine does): fixed [1, chunk] shape, padded
    final chunk write-masked via valid_to."""
    T = len(toks)
    logits = None
    p = 0
    while p < T:
        piece = toks[p : p + chunk]
        fed = piece + [0] * (chunk - len(piece))
        logits, cache = paged_decode_chunk(
            params,
            jnp.asarray([fed], jnp.int32),
            cache,
            jnp.asarray([table], jnp.int32),
            jnp.asarray([p], jnp.int32),
            cfg,
            valid_to=jnp.asarray([T], jnp.int32),
        )
        p += len(piece)
    last_row = (T - 1) % chunk if T % chunk else chunk - 1
    return logits[:, last_row], cache


def test_paged_decode_matches_dense():
    """Greedy continuation over a paged cache with a FRAGMENTED, out-of-order
    block table matches dense prefill+decode token for token (GQA config —
    the KV==H attention branch is covered by the valid_to test below)."""
    cfg = _cfg(n_kv_heads=2)
    params = init_params(jax.random.PRNGKey(0), cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(1), (1, 7), 0, cfg.vocab_size)
    toks = np.asarray(prompt)[0].tolist()
    bs, n_new = 4, 6

    # Dense reference: prefill + greedy decode steps.
    dcache = init_cache(cfg, 1, 32)
    dlogits, dcache, dpos = prefill(params, prompt, dcache, cfg)
    want = []
    cur = int(np.asarray(dlogits).argmax())
    from ray_tpu.models.generate import decode_step

    for _ in range(n_new):
        want.append(cur)
        dlogits, dcache = decode_step(
            params, jnp.asarray([cur], jnp.int32), dcache, dpos, cfg
        )
        dpos = dpos + 1
        cur = int(np.asarray(dlogits).argmax())

    # Paged: deliberately fragmented physical blocks (never 0 — reserved).
    table = [5, 2, 7, 1]  # covers 16 positions at block_size 4
    pcache = init_paged_cache(cfg, num_blocks=9, block_size=bs)
    plogits, pcache = _paged_prefill(params, toks, pcache, table, cfg, chunk=4)
    got = []
    cur = int(np.asarray(plogits)[0].argmax())
    pos = len(toks)
    for _ in range(n_new):
        got.append(cur)
        step_logits, pcache = paged_decode_step(
            params,
            jnp.asarray([cur], jnp.int32),
            pcache,
            jnp.asarray([table], jnp.int32),
            jnp.asarray([pos], jnp.int32),
            cfg,
        )
        pos += 1
        cur = int(np.asarray(step_logits)[0].argmax())
    assert got == want


def test_paged_multi_slot_batch_matches_solo_and_inactive_slots_are_inert():
    """A multi-slot decode batch (different positions per slot, one slot
    INACTIVE) produces per-slot logits matching each sequence decoded alone
    — slots must not couple, and the inactive slot must stay finite."""
    cfg = _cfg(n_kv_heads=2)
    params = init_params(jax.random.PRNGKey(0), cfg)
    bs = 4
    seqs = [
        np.asarray(
            jax.random.randint(jax.random.PRNGKey(i + 1), (n,), 0, cfg.vocab_size)
        ).tolist()
        for i, n in enumerate((5, 9))
    ]
    # Shared pool: slot 0 owns blocks [1,2,3], slot 1 owns [4,5,6], slot 2
    # inactive (all-zero table).
    tables = [[1, 2, 3], [4, 5, 6], [0, 0, 0]]
    cache = init_paged_cache(cfg, num_blocks=8, block_size=bs)
    last = {}
    for slot, toks in enumerate(seqs):
        logits, cache = _paged_prefill(params, toks, cache, tables[slot], cfg)
        last[slot] = int(np.asarray(logits)[0].argmax())

    # One batched step across all three slots.
    step_tok = jnp.asarray([last[0], last[1], 0], jnp.int32)
    step_pos = jnp.asarray([len(seqs[0]), len(seqs[1]), 0], jnp.int32)
    logits_b, _ = paged_decode_step(
        params, step_tok, cache, jnp.asarray(tables, jnp.int32), step_pos, cfg
    )
    logits_b = np.asarray(logits_b)
    assert np.isfinite(logits_b).all(), "inactive slot leaked non-finite values"

    # Solo reference per sequence via the DENSE path.
    for slot, toks in enumerate(seqs):
        dcache = init_cache(cfg, 1, 32)
        dlogits, dcache, dpos = prefill(
            params, jnp.asarray([toks], jnp.int32), dcache, cfg
        )
        assert int(np.asarray(dlogits).argmax()) == last[slot]
        from ray_tpu.models.generate import decode_step

        ref, _ = decode_step(
            params, jnp.asarray([last[slot]], jnp.int32), dcache, dpos, cfg
        )
        assert int(logits_b[slot].argmax()) == int(np.asarray(ref)[0].argmax())


def test_paged_valid_to_masks_padded_writes():
    """A padded prefill chunk must not write beyond valid_to: the blocks
    covering the padding stay bit-identical to their pre-call state, and
    the null block absorbs the masked rows."""
    cfg = _cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    bs = 4
    cache = init_paged_cache(cfg, num_blocks=6, block_size=bs)
    table = [1, 2, 3]
    toks = [7, 3, 9, 1, 5]  # 5 real tokens, chunk 8 -> 3 padded rows
    before_b3 = np.asarray(cache["k"][:, 3])
    fed = toks + [0] * 3
    _, cache = paged_decode_chunk(
        params,
        jnp.asarray([fed], jnp.int32),
        cache,
        jnp.asarray([table], jnp.int32),
        jnp.asarray([0], jnp.int32),
        cfg,
        valid_to=jnp.asarray([5], jnp.int32),
    )
    # Positions 5..7 live in blocks 2 (rows 1..3): those rows must be
    # untouched zeros; block 3 (positions 8..11) entirely untouched.
    np.testing.assert_array_equal(np.asarray(cache["k"][:, 3]), before_b3)
    assert float(jnp.abs(cache["k"][:, 2, 1:]).sum()) == 0.0
    # Real rows WERE written (block 1 rows 0..3, block 2 row 0).
    assert float(jnp.abs(cache["k"][:, 1]).sum()) > 0.0
    assert float(jnp.abs(cache["k"][:, 2, 0]).sum()) > 0.0


def test_paged_chunk_matches_dense_chunk_with_window():
    """Sliding-window config: multi-token paged decode_chunk logits match
    the dense decode_chunk on the same continuation."""
    cfg = _cfg(sliding_window=6, n_kv_heads=2)
    params = init_params(jax.random.PRNGKey(0), cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(1), (1, 6), 0, cfg.vocab_size)
    extra = jax.random.randint(jax.random.PRNGKey(2), (1, 3), 0, cfg.vocab_size)
    toks = np.asarray(prompt)[0].tolist()

    dcache = init_cache(cfg, 1, 16)
    _, dcache, dpos = prefill(params, prompt, dcache, cfg)
    dense, _ = decode_chunk(params, extra, dcache, dpos, cfg)

    bs = 4
    table = [3, 1, 2, 4]
    pcache = init_paged_cache(cfg, num_blocks=5, block_size=bs)
    _, pcache = _paged_prefill(params, toks, pcache, table, cfg, chunk=3)
    paged, _ = paged_decode_chunk(
        params,
        extra,
        pcache,
        jnp.asarray([table], jnp.int32),
        jnp.asarray([6], jnp.int32),
        cfg,
    )
    assert (
        np.asarray(paged).argmax(-1) == np.asarray(dense).argmax(-1)
    ).all()
    np.testing.assert_allclose(
        np.asarray(paged), np.asarray(dense), rtol=2e-4, atol=2e-5
    )


# ---------------------------------------------------------------------------
# The pool updated in place: jitted with the cache DONATED (what the serving
# engine does), each paged entry point gives the same logits and the same
# pool as the un-donated call, in the buffer it was handed.
# ---------------------------------------------------------------------------


def _dense_after_prompt(params, cfg, toks, fed):
    """Dense-cache oracle: logits [len(fed), V] of feeding ``fed`` after the
    prompt ``toks`` (an empty prompt: ``fed`` starts at position 0)."""
    dcache, pos = init_cache(cfg, 1, 32), jnp.int32(0)
    if toks:
        _, dcache, pos = prefill(params, jnp.asarray([toks], jnp.int32), dcache, cfg)
    logits, _ = decode_chunk(params, jnp.asarray([fed], jnp.int32), dcache, pos, cfg)
    return np.asarray(logits)[0]


def _case_step_inactive_slot():
    """Decode step over three slots at different positions, one INACTIVE."""
    cfg = _cfg(n_kv_heads=2)
    params = init_params(jax.random.PRNGKey(0), cfg)
    seqs = [[7, 3, 9, 1, 5], [2, 8, 6, 4, 11, 13, 17, 19, 23]]
    tables = [[1, 2, 3], [6, 4, 5], [0, 0, 0]]
    cache = init_paged_cache(cfg, num_blocks=8, block_size=4)
    for toks, table in zip(seqs, tables):
        _, cache = _paged_prefill(params, toks, cache, table, cfg)
    step = [31, 37, 0]
    call = lambda c: paged_decode_step(  # noqa: E731
        params, jnp.asarray(step, jnp.int32), c, jnp.asarray(tables, jnp.int32),
        jnp.asarray([len(seqs[0]), len(seqs[1]), 0], jnp.int32), cfg,
    )
    want = {
        (slot,): _dense_after_prompt(params, cfg, seqs[slot], [step[slot]])[0]
        for slot in (0, 1)
    }
    return call, cache, want


def _case_chunk_valid_to():
    """A padded prefill chunk (5 real tokens of 8): masked rows go to the null block."""
    cfg = _cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    toks = [7, 3, 9, 1, 5]
    cache = init_paged_cache(cfg, num_blocks=6, block_size=4)
    call = lambda c: paged_decode_chunk(  # noqa: E731
        params, jnp.asarray([toks + [0] * 3], jnp.int32), c,
        jnp.asarray([[1, 2, 3]], jnp.int32), jnp.asarray([0], jnp.int32), cfg,
        valid_to=jnp.asarray([5], jnp.int32),
    )
    dense = _dense_after_prompt(params, cfg, [], toks)
    return call, cache, {(0, row): dense[row] for row in range(5)}


def _case_chunk_window():
    """Sliding window on: a three-token chunk after a six-token prompt."""
    cfg = _cfg(sliding_window=6, n_kv_heads=2)
    params = init_params(jax.random.PRNGKey(0), cfg)
    toks, extra, table = [9, 4, 7, 1, 3, 8], [5, 2, 6], [3, 1, 2, 4]
    cache = init_paged_cache(cfg, num_blocks=5, block_size=4)
    _, cache = _paged_prefill(params, toks, cache, table, cfg, chunk=3)
    call = lambda c: paged_decode_chunk(  # noqa: E731
        params, jnp.asarray([extra], jnp.int32), c, jnp.asarray([table], jnp.int32),
        jnp.asarray([6], jnp.int32), cfg,
    )
    dense = _dense_after_prompt(params, cfg, toks, extra)
    return call, cache, {(0, row): dense[row] for row in range(3)}


def _case_dense_chunk_ragged():
    """The DENSE cache, carried by the same scan: a two-token chunk at per-row
    positions after a ragged prefill, sliding window on. A donated
    ``decode_chunk`` updates the cache it was handed."""
    cfg = _cfg(sliding_window=6, n_kv_heads=2)
    params = init_params(jax.random.PRNGKey(0), cfg)
    seqs, fed = [[7, 3, 9, 1, 5, 2, 8], [4, 6, 11]], [[13, 17], [19, 23]]
    prompt = jnp.asarray([seqs[0], seqs[1] + [0] * 4], jnp.int32)
    _, cache, pos = prefill(
        params, prompt, init_cache(cfg, 2, 32), cfg, prompt_lens=jnp.asarray([7, 3], jnp.int32)
    )
    call = lambda c: decode_chunk(params, jnp.asarray(fed, jnp.int32), c, pos, cfg)  # noqa: E731
    want = {
        (b, row): _dense_after_prompt(params, cfg, seqs[b], fed[b])[row]
        for b in (0, 1) for row in (0, 1)
    }
    return call, cache, want


@pytest.mark.parametrize(
    "case",
    [_case_step_inactive_slot, _case_chunk_valid_to, _case_chunk_window, _case_dense_chunk_ragged],
)
def test_donated_jit_matches_undonated_call_and_dense_oracle(case):
    call, cache, want = case()
    ref_logits, ref_pool = call(cache)  # plain call: the caller's pool survives
    assert not any(a.is_deleted() for a in cache.values())
    given = jax.tree.map(jnp.copy, cache)
    ptrs = {name: a.unsafe_buffer_pointer() for name, a in given.items()}
    logits, pool = jax.jit(call, donate_argnums=0)(given)
    assert all(a.is_deleted() for a in given.values())
    for name in ("k", "v"):
        assert pool[name].unsafe_buffer_pointer() == ptrs[name]
        np.testing.assert_array_equal(np.asarray(pool[name]), np.asarray(ref_pool[name]))
    np.testing.assert_array_equal(np.asarray(logits), np.asarray(ref_logits))
    assert np.isfinite(np.asarray(logits)).all()
    for idx, dense in want.items():
        got = np.asarray(logits)[idx]
        assert got.argmax() == dense.argmax()
        np.testing.assert_allclose(got, dense, rtol=2e-4, atol=2e-5)
