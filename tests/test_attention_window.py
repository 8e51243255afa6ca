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


def test_wide_window_equals_full_causal():
    q, k, v = _qkv(T=128)
    full = _flash_tokens_major(q, k, v, causal=True, force_pallas=True,
                               interpret=True, block_q=64, block_k=64)
    wide = _flash_tokens_major(q, k, v, causal=True, window=10_000, force_pallas=True,
                               interpret=True, block_q=64, block_k=64)
    # Value-level f32 equivalence, not bitwise: the full-causal path takes
    # the split-at-the-diagonal loop (no mask select below the diagonal)
    # while the windowed path keeps the uniform masked loop, so the two
    # compile to different programs with different fusion/rounding.
    np.testing.assert_allclose(np.asarray(wide), np.asarray(full), rtol=1e-5, atol=1e-6)
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, k, v, causal=False, window=8)


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
def test_grouped_queries_in_place_match_the_reference_over_repeated_keys(rep, window, longer, D, monkeypatch):
    """The interpreted kernels, handed k and v at KV heads, against ``_xla_attention`` over k and v repeated by
    hand: the output, dq, and dk, dv AT KV HEADS (the repeat's cotangent: the sum over a group's query heads), with
    and without a window, and with more keys than queries (the bottom-right alignment)."""
    from ray_tpu.ops import attention

    monkeypatch.setattr(attention, "_BWD_BLOCK", 128)  # two and three blocks an axis
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
