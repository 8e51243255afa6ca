"""Sliding-window flash attention vs the XLA reference (interpret mode).

Covers the kernel's k-block pruning lower bound, the fully-masked-block
NaN guard, and the custom-VJP backward under a window.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops.attention import _xla_attention, flash_attention


def _flash_tokens_major(q, k, v, **kw):
    """``flash_attention`` over the reference's ``[B, T, H, D]``: the call itself takes and gives heads before tokens."""
    swap = lambda x: x.transpose(0, 2, 1, 3)
    return swap(flash_attention(swap(q), swap(k), swap(v), **kw))


def _qkv(T=256, B=2, H=2, D=32):
    mk = lambda s: jax.random.normal(jax.random.PRNGKey(s), (B, T, H, D))
    return mk(0), mk(1), mk(2)


@pytest.mark.parametrize("window", [64, 96, 1])  # 96: not block-aligned
def test_windowed_kernel_matches_reference(window):
    q, k, v = _qkv()
    D = q.shape[-1]
    ref = _xla_attention(q, k, v, True, D**-0.5, None, window=window)
    got = _flash_tokens_major(
        q, k, v, causal=True, window=window, force_pallas=True,
        interpret=True, block_q=64, block_k=64,
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_windowed_backward_matches_reference():
    q, k, v = _qkv(T=128)
    D = q.shape[-1]
    W = 32

    def f(q, k, v):
        return _flash_tokens_major(
            q, k, v, causal=True, window=W, force_pallas=True,
            interpret=True, block_q=32, block_k=32,
        ).sum()

    def fr(q, k, v):
        return _xla_attention(q, k, v, True, D**-0.5, None, window=W).sum()

    g = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(fr, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("window", [128, 10_000])  # the keys' length itself, and beyond it
def test_wide_window_equals_full_causal(window):
    """A window that reaches every key binds nothing: ``flash_attention`` hands the kernels ``window=0``, so the
    output and the three gradients are the full-causal schedule's, bit for bit (until PR 60 a different program:
    the windowed loop masked every tile)."""
    q, k, v = _qkv(T=128)
    run = lambda w: lambda q, k, v: _flash_tokens_major(q, k, v, causal=True, window=w, force_pallas=True, interpret=True, block_q=64, block_k=32)
    np.testing.assert_array_equal(np.asarray(run(window)(q, k, v)), np.asarray(run(0)(q, k, v)))
    grads = lambda w: jax.grad(lambda *a: (run(w)(*a) ** 2).sum(), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(grads(window), grads(0)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert str(jax.make_jaxpr(run(window))(q, k, v)) == str(jax.make_jaxpr(run(0))(q, k, v))
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, k, v, causal=False, window=8)


# (Tq, Tk, window, (block_q, block_k)): each kernel's loop bounds with the window smaller than a block (16), equal to
# one (64), not a multiple of one (96 over blocks of 64; 48), wide enough that whole tiles lie between the mask's
# two edges (96), reaching every key (1000: no window at all), none; more keys than queries (the
# bottom-right alignment; a window there leaves the oldest key blocks to no query); two block shapes with
# block_q != block_k. Sequences of at most 128 keep the blocks the call names (``_fit_block`` raises a longer
# sequence's to a whole lane tile).
_SPANS = [
    (Tq, 128, window, blocks)
    for Tq, windows in ((128, (0, 16, 48, 64, 96, 1000)), (64, (0, 16, 96)))
    for blocks in ((64, 32), (32, 64))
    for window in windows
]


@pytest.mark.parametrize("Tq, Tk, window, blocks", _SPANS)
def test_every_span_of_the_mask_matches_the_reference(Tq, Tk, window, blocks):
    """The output and dq, dk, dv of the three interpreted kernels, each over the blocks its span names
    (``_key_span`` / ``_query_span``), against ``_xla_attention``'s."""
    B, H, D = 1, 2, 32
    mk = lambda seed, T: jax.random.normal(jax.random.PRNGKey(seed), (B, T, H, D))
    q, k, v = mk(0, Tq), mk(1, Tk), mk(2, Tk)
    kernels = lambda q, k, v: _flash_tokens_major(q, k, v, causal=True, window=window, force_pallas=True, interpret=True, block_q=blocks[0], block_k=blocks[1])
    reference = lambda q, k, v: _xla_attention(q, k, v, True, D**-0.5, None, window=window)
    both = lambda f: (f(q, k, v), *jax.grad(lambda *a: (f(*a) ** 2).sum(), argnums=(0, 1, 2))(q, k, v))
    for name, a, b in zip(("out", "dq", "dk", "dv"), both(kernels), both(reference)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5, err_msg=name)


@pytest.mark.parametrize("window", [0, 96])
@pytest.mark.parametrize("kernel", ["forward", "backward"])
def test_an_unmasked_tile_is_the_masked_tile_bit_for_bit_where_the_mask_hides_nothing(kernel, window):
    """The kernels' two tile bodies, run operation by operation: with the tile wholly under the diagonal and inside
    the window, ``masked=False`` gives the bits ``masked=True`` gives; with an edge across it, it does not. (The
    kernels mask every tile they cover: ``tile_schedule``'s docstring says why. This is what makes that free of
    any cost in bits, and what a split of the loop would have to keep.)"""
    from ray_tpu.ops import attention

    bq, bk, D = 32, 64, 32
    mk = lambda seed, rows: jax.random.normal(jax.random.PRNGKey(seed), (rows, D))
    q, k, v, do = mk(0, bq), mk(1, bk), mk(2, bk), mk(3, bq)
    row = lambda seed: jax.random.normal(jax.random.PRNGKey(seed), (1, bq))
    carry = (jnp.full((bq, 1), -1.0), jnp.ones((bq, 1)), mk(4, bq))

    def tile(masked, q_pos0):
        if kernel == "forward":
            return attention._fwd_tile(q, k, v, carry, q_pos0, 0, masked, D**-0.5, window)
        return attention._bwd_tile(q, do, k, v, row(5), row(6), q_pos0, 0, masked, D**-0.5, window)

    with jax.disable_jit():
        inside = bk - 1  # the first row's position: every key is at or before it, and (96) the last row's window holds key 0
        assert window == 0 or inside + bq - 1 < window
        for a, b in zip(tile(True, inside), tile(False, inside)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        for crossed in (bk // 2, *((200,) if window else ())):  # the diagonal through the tile; the window's edge through it
            assert not all(np.array_equal(np.asarray(a), np.asarray(b)) for a, b in zip(tile(True, crossed), tile(False, crossed)))


def _count_by_position(Tq, Tk, window, bq, bk):
    """``tile_schedule``'s four numbers from the positions themselves: row r sees the keys lo[r]..hi[r]; a tile is
    covered where a row of it sees a key of it, masked where a row of it does not see every key of it."""
    pos = np.arange(Tq) + Tk - Tq
    hi = np.minimum(pos, Tk - 1)
    lo = np.maximum(pos - window + 1, 0) if window else np.zeros_like(pos)
    covered = masked = 0
    for qb in range(Tq // bq):
        rows = slice(qb * bq, (qb + 1) * bq)
        for kb in range(Tk // bk):
            first, last = kb * bk, (kb + 1) * bk - 1
            touched = (lo[rows] <= last) & (hi[rows] >= first)
            whole = (lo[rows] <= first) & (hi[rows] >= last)
            covered += bool(touched.any())
            masked += bool(touched.any() and not whole.all())
    return covered, masked, covered * bq * bk, int(np.maximum(hi - lo + 1, 0).sum())


# The kernels of the benchmark's training cells, a head (ISSUE 60's table): T 8192 under window 1024, T 8192 full,
# T 4096 under a window of 4096 (which binds nothing); then shapes that no block divides evenly into the window.
@pytest.mark.parametrize("Tq, Tk, window, bq, bk, want", [
    (8192, 8192, 1024, 1024, 1024, (15, 15, 15_728_640, 7_864_832)),
    (8192, 8192, 1024, 512, 512, (45, 30, 11_796_480, 7_864_832)),
    (8192, 8192, 1024, 256, 256, (150, 60, 9_830_400, 7_864_832)),
    (8192, 8192, 0, 1024, 1024, (36, 8, 37_748_736, 33_558_528)),
    (8192, 8192, 0, 512, 512, (136, 16, 35_651_584, 33_558_528)),
    (4096, 4096, 0, 1024, 1024, (10, 4, 10_485_760, 8_390_656)),
    (4096, 4096, 0, 512, 512, (36, 8, 9_437_184, 8_390_656)),
    (4096, 4096, 4096, 512, 512, None),
    (8192, 8192, 1024, 512, 256, None),
    (8192, 8192, 1024, 256, 512, None),
    (256, 384, 96, 64, 128, None),
    (512, 512, 192, 128, 64, None),
    (128, 256, 0, 32, 64, None),
    (256, 256, 1, 64, 32, None),
    (384, 256, 100, 128, 64, None),
])
def test_tile_schedule_counts_what_the_positions_say(Tq, Tk, window, bq, bk, want):
    from ray_tpu.ops import attention

    got = attention.tile_schedule(Tq, Tk, window, bq, bk)
    assert got == _count_by_position(Tq, Tk, window, bq, bk)
    assert want is None or got == want
    # the dK/dV kernel walks the same tiles by key block
    by_k = [attention._query_span(kb * bk - (Tk - Tq), bq, bk, Tq // bq, window, attention._int_clip) for kb in range(Tk // bk)]
    assert sum(end - start for start, end in by_k) == got[0]


# (query heads a KV head, window, Tk - Tq, head width): grouped queries read in place, k and v never repeated. The
# call and the kernels take [B, H or KV, T, D]. 128 is a lane tile, 64 the head of the usual ViT.
_GROUPED = [
    (rep, window, longer, D)
    for D, reps in ((128, (1, 4, 8)), (64, (1, 4)))
    for rep in reps
    for window in (0, 96)
    for longer in (0, 128)
]


@pytest.mark.parametrize("rep, window, longer, D", _GROUPED)
def test_grouped_queries_in_place_match_the_reference_over_repeated_keys(rep, window, longer, D):
    """The interpreted kernels, handed k and v at KV heads, against ``_xla_attention`` over k and v repeated by
    hand: the output, dq, and dk, dv AT KV HEADS (the repeat's cotangent: the sum over a group's query heads), with
    and without a window, and with more keys than queries (the bottom-right alignment)."""
    # the call's (128, 128) are the backward kernels' blocks too: two and three blocks an axis
    B, Tq, KV = 2, 256, 2 if rep < 8 else 1
    mk = lambda seed, T, heads: jax.random.normal(jax.random.PRNGKey(seed), (B, T, heads, D))
    q, k, v = mk(0, Tq, KV * rep), mk(1, Tq + longer, KV), mk(2, Tq + longer, KV)

    def kernels(q, k, v):
        return _flash_tokens_major(q, k, v, causal=True, window=window, force_pallas=True, interpret=True, block_q=128, block_k=128)

    def reference(q, k, v):
        return _xla_attention(q, jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2), True, D**-0.5, None, window=window)

    np.testing.assert_allclose(np.asarray(kernels(q, k, v)), np.asarray(reference(q, k, v)), rtol=2e-5, atol=2e-5)
    got = jax.grad(lambda *a: (kernels(*a) ** 2).sum(), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: (reference(*a) ** 2).sum(), argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", got, want):
        assert a.shape == b.shape, name
        rel = float(jnp.abs(a - b).max() / (jnp.abs(b).max() + 1e-9))
        assert rel < 1e-4, f"d{name}: {rel}"


@pytest.mark.parametrize("rep", [1, 4])
def test_the_blockwise_backward_in_xla_sums_a_groups_query_heads(rep):
    """The backward rule's fallback where the kernels cannot tile the sequence: the same dq and, at KV heads, dk
    and dv as autodiff through the reference over repeated k and v."""
    from ray_tpu.ops import attention

    B, T, KV, D, window = 2, 128, 2, 32, 48
    mk = lambda seed, heads: jax.random.normal(jax.random.PRNGKey(seed), (B, T, heads, D))
    q, k, v, dout = mk(0, KV * rep), mk(1, KV), mk(2, KV), mk(3, KV * rep)
    reference = lambda q, k, v: _xla_attention(q, jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2), True, D**-0.5, None, window=window)
    out, vjp = jax.vjp(reference, q, k, v)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, jnp.repeat(k, rep, axis=2)) * D**-0.5
    visible = (jnp.arange(T)[:, None] >= jnp.arange(T)[None]) & (jnp.arange(T)[:, None] - jnp.arange(T)[None] < window)
    lse = jax.nn.logsumexp(jnp.where(visible, logits, -jnp.inf), axis=-1)
    swap = attention._swap  # the backward rule's operands are the kernels': [B, H or KV, T, D]
    got = attention._xla_blockwise_bwd(True, D**-0.5, 32, 32, window, (swap(q), swap(k), swap(v), swap(out), lse), swap(dout))
    for name, a, b in zip("qkv", map(swap, got), vjp(dout)):
        assert a.shape == b.shape, name
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5, err_msg=f"d{name}")


# (T, window as the kernels see it): the benchmark's training cells' attention cores (a window of 4096 over 4096
# keys binds nothing and reaches the kernels as 0), then shapes no cell has.
@pytest.mark.parametrize("T, window", [(8192, 1024), (8192, 0), (4096, 0), (2048, 512), (1024, 0), (16384, 4096), (1536, 0), (384, 0)])
def test_the_blocks_the_mask_gives_divide_the_sequence_and_fit_the_memory_the_kernel_asks_for(T, window):
    """``_blocks``' answer for each kernel: whole lane tiles that divide T (after ``_edges``' fit, which is all a
    sequence no measured shape divides gets), and the kernel's two resident operands (two buffers each), its
    blocked operands (two buffers each), two float32 score tiles and its float32 accumulators inside the limit
    ``_vmem`` sets, or the compiler's own 16 MiB where it sets none. (The TPU's compiler has the last word:
    ``tests/test_tpu_lowering.py`` compiles the cells' shapes.)"""
    from ray_tpu.ops import attention

    D, itemsize = 128, 2
    x = jax.ShapeDtypeStruct((1, 1, T, D), jnp.bfloat16)
    params = attention._vmem(T, D, itemsize).get("compiler_params")
    limit = params.vmem_limit_bytes if params is not None else 16 << 20
    blocked_rows = {"fwd": lambda bq, bk: 2 * bq, "dkv": lambda bq, bk: 4 * bk, "dq": lambda bq, bk: 3 * bq}  # q, o | k, v, dk, dv | q, do, dq
    for kernel, rows in blocked_rows.items():
        bq, bk = attention._edges(kernel, x, x, window, None, None)
        assert bq % 128 == 0 and bk % 128 == 0 and T % bq == 0 and T % bk == 0, (kernel, bq, bk)
        if T % 512 == 0:
            assert (bq, bk) == attention._blocks(kernel, T, T, D, window) and (bq, bk) in attention._PS_A_COVERED_SCORE[kernel]
        accumulators = (bq if kernel != "dkv" else 2 * bk) * D * 4
        held = 4 * T * D * itemsize + 2 * rows(bq, bk) * D * itemsize + 2 * bq * bk * 4 + accumulators
        assert held <= limit, (kernel, bq, bk, held, limit)
    # what the mask decides: under a window no kernel takes a block as long as the window; wider heads keep (512, 512)
    if window:
        assert all(max(attention._blocks(kernel, T, T, D, window)) <= max(512, window // 2) for kernel in blocked_rows)
    assert attention._blocks("dkv", T, T, 256, window) == (512, 512)
