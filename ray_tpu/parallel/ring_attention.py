"""Ring attention — context parallelism over the ``sp`` mesh axis.

Absent from the reference (SURVEY.md §5.7 confirms no SP/CP/ring attention
in-tree); built natively here the TPU way: Q/K/V are sharded over the sequence
dimension across the ``sp`` axis; each device computes blockwise attention of
its local Q chunk against a K/V chunk that rotates around the ICI ring via
``lax.ppermute``, combining per-chunk flash outputs through their log-sum-exp
so the result is exact (Liu et al., Ring Attention; see PAPERS.md).

Two TPU-specific optimizations over the naive schedule:

- **Compute/ICI overlap (double buffering)**: the ppermute moving chunk i+1
  is issued BEFORE the attention on chunk i, so XLA's latency-hiding
  scheduler can run the neighbour transfer concurrently with the MXU work
  (round-1 issued the permute after the attention, serializing them).
- **Pallas local math**: each Q-chunk x K-chunk block runs the flash
  attention kernel (ops/attention.py) when the chunk shapes are Mosaic
  tileable, so the [Tq, Tc] logits tile never round-trips HBM; per-chunk
  (out, lse) pairs combine exactly via logaddexp weighting.

Training works through a ring-level custom VJP: the backward makes a second
ring pass (standard flash backward per chunk, XLA einsums), rotating dK/dV
accumulators along with K/V so each chunk's gradients land back on its home
device after the full cycle.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P


_NEG_INF = -1e30  # finite stand-in for -inf: keeps exp/where math NaN-free


def _xla_chunk(q, k, v, q_start, k_start, causal: bool, sm_scale: float):
    """One Q-chunk x K-chunk flash block in plain XLA: returns the chunk's
    normalized output [B,Tq,H,D] (f32) and log-sum-exp [B,H,Tq] (f32)."""
    qf = q.astype(jnp.float32)
    s = jnp.einsum("bqhd,bkhd->bhqk", qf, k.astype(jnp.float32)) * sm_scale
    if causal:
        Tq, Tc = q.shape[1], k.shape[1]
        q_pos = q_start + lax.broadcasted_iota(jnp.int32, (Tq, Tc), 0)
        k_pos = k_start + lax.broadcasted_iota(jnp.int32, (Tq, Tc), 1)
        s = jnp.where((q_pos >= k_pos)[None, None], s, _NEG_INF)
    m = jnp.max(s, axis=-1)                      # [B,H,Tq]
    p = jnp.exp(s - m[..., None])
    p = jnp.where(s <= _NEG_INF, 0.0, p)
    l = p.sum(axis=-1)                           # [B,H,Tq]
    masked = l == 0.0
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    out = out / jnp.where(masked, 1.0, l).transpose(0, 2, 1)[..., None]
    lse = jnp.where(masked, _NEG_INF, m + jnp.log(jnp.where(masked, 1.0, l)))
    return out, lse


def _pallas_chunk(q, k, v, q_start, k_start, sm_scale: float, interpret: bool):
    """Pallas flash kernel for one chunk pair. Ring chunks are size-aligned
    (Tq == Tc, offsets multiples of Tq), so the causal relation collapses to
    three cases decided per device at runtime: k-chunk strictly after the
    q-chunk (fully masked), the diagonal (causal within the chunk), or
    strictly before (no mask)."""
    from ray_tpu.ops.attention import _pallas_flash_with_lse, _swap

    B, Tq, H, D = q.shape

    def kernel(q, k, v, causal):  # the kernel reads and writes [B, H, T, D]
        out, lse = _pallas_flash_with_lse(_swap(q), _swap(k), _swap(v), causal, sm_scale, min(128, Tq), min(128, k.shape[1]), interpret)
        return _swap(out).astype(jnp.float32), lse

    def masked_case(_q, _k, _v):
        return (
            jnp.zeros((B, Tq, H, D), jnp.float32),
            jnp.full((B, H, Tq), _NEG_INF, jnp.float32),
        )

    diag_case, full_case = functools.partial(kernel, causal=True), functools.partial(kernel, causal=False)

    if q_start is None:  # non-causal: every chunk is a plain full block
        return full_case(q, k, v)
    return lax.cond(
        k_start > q_start,
        masked_case,
        lambda a, b, c: lax.cond(k_start == q_start, diag_case, full_case, a, b, c),
        q, k, v,
    )


def _combine(acc, lse_run, out_c, lse_c):
    """Merge one chunk's flash output into the running result via LSE
    weighting: out = sum_c out_c * exp(lse_c - lse_global), exactly."""
    new_lse = jnp.logaddexp(lse_run, lse_c)
    safe = jnp.where(new_lse <= _NEG_INF, 0.0, new_lse)
    w_old = jnp.where(lse_run <= _NEG_INF, 0.0, jnp.exp(lse_run - safe))
    w_new = jnp.where(lse_c <= _NEG_INF, 0.0, jnp.exp(lse_c - safe))
    acc = acc * w_old.transpose(0, 2, 1)[..., None] + out_c * w_new.transpose(0, 2, 1)[..., None]
    return acc, new_lse


def _ring_forward(q_loc, k_loc, v_loc, axis_name, n, causal, sm_scale, impl, interpret):
    B, Tq, H, D = q_loc.shape
    idx = lax.axis_index(axis_name)
    q_start = idx * Tq
    perm = [(i, (i + 1) % n) for i in range(n)]

    def chunk(kc, vc, i):
        k_start = ((idx - i) % n) * Tq
        if impl == "pallas":
            return _pallas_chunk(
                q_loc, kc, vc, q_start if causal else None, k_start, sm_scale, interpret
            )
        return _xla_chunk(q_loc, kc, vc, q_start, k_start, causal, sm_scale)

    acc0 = jnp.zeros((B, Tq, H, D), jnp.float32)
    lse0 = jnp.full((B, H, Tq), _NEG_INF, jnp.float32)

    def step(i, carry):
        kc, vc, acc, lse_run = carry
        # Double buffering: launch the neighbour transfer of the NEXT chunk
        # before attending the current one — the attention consumes kc/vc,
        # not kn/vn, so the ICI hop and the MXU block run concurrently.
        kn = lax.ppermute(kc, axis_name, perm)
        vn = lax.ppermute(vc, axis_name, perm)
        out_c, lse_c = chunk(kc, vc, i)
        acc, lse_run = _combine(acc, lse_run, out_c, lse_c)
        return kn, vn, acc, lse_run

    kc, vc, acc, lse_run = lax.fori_loop(0, n - 1, step, (k_loc, v_loc, acc0, lse0))
    # Final chunk: no further transfer needed.
    out_c, lse_c = chunk(kc, vc, n - 1)
    acc, lse_run = _combine(acc, lse_run, out_c, lse_c)
    return acc.astype(q_loc.dtype), lse_run


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _ring_attn_local(q_loc, k_loc, v_loc, axis_name, n, causal, sm_scale, impl, interpret):
    out, _ = _ring_forward(q_loc, k_loc, v_loc, axis_name, n, causal, sm_scale, impl, interpret)
    return out


def _ring_attn_fwd(q_loc, k_loc, v_loc, axis_name, n, causal, sm_scale, impl, interpret):
    out, lse = _ring_forward(q_loc, k_loc, v_loc, axis_name, n, causal, sm_scale, impl, interpret)
    return out, (q_loc, k_loc, v_loc, out, lse)


def _ring_attn_bwd(axis_name, n, causal, sm_scale, impl, interpret, res, dout):
    """Second ring pass (standard flash backward per chunk): dK/dV
    accumulators rotate WITH their K/V chunks, so after the full cycle each
    chunk's gradients are back on its home device."""
    q_loc, k_loc, v_loc, out, lse = res
    B, Tq, H, D = q_loc.shape
    idx = lax.axis_index(axis_name)
    q_start = idx * Tq
    perm = [(i, (i + 1) % n) for i in range(n)]

    qf = q_loc.astype(jnp.float32)
    doutf = dout.astype(jnp.float32)
    outf = out.astype(jnp.float32)
    delta = jnp.sum(doutf * outf, axis=-1).transpose(0, 2, 1)  # [B,H,Tq]
    lse_safe = jnp.where(lse <= _NEG_INF, 0.0, lse)
    row_live = (lse > _NEG_INF)[..., None]  # fully-masked rows contribute nothing

    def step(i, carry):
        kc, vc, dk, dv, dq = carry
        # Same double buffering as the forward: K/V for the next chunk leave
        # NOW so the ICI hop overlaps the einsums below; only dK/dV must wait
        # for this step's accumulation (alignment with their chunk is kept —
        # every buffer is permuted exactly once per step).
        kn = lax.ppermute(kc, axis_name, perm)
        vn = lax.ppermute(vc, axis_name, perm)
        k_start = ((idx - i) % n) * Tq
        kf = kc.astype(jnp.float32)
        vf = vc.astype(jnp.float32)
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, kf) * sm_scale
        if causal:
            q_pos = q_start + lax.broadcasted_iota(jnp.int32, (Tq, Tq), 0)
            k_pos = k_start + lax.broadcasted_iota(jnp.int32, (Tq, Tq), 1)
            s = jnp.where((q_pos >= k_pos)[None, None], s, _NEG_INF)
        p = jnp.where((s <= _NEG_INF) | ~row_live, 0.0, jnp.exp(s - lse_safe[..., None]))
        dp = jnp.einsum("bqhd,bkhd->bhqk", doutf, vf)
        ds = p * (dp - delta[..., None]) * sm_scale
        dq = dq + jnp.einsum("bhqk,bkhd->bqhd", ds, kf)
        dk = dk + jnp.einsum("bhqk,bqhd->bkhd", ds, qf)
        dv = dv + jnp.einsum("bhqk,bqhd->bkhd", p, doutf)
        # Rotate grads together with their chunks; after n steps they're home.
        return (
            kn,
            vn,
            lax.ppermute(dk, axis_name, perm),
            lax.ppermute(dv, axis_name, perm),
            dq,
        )

    dk0 = jnp.zeros((B, Tq, H, D), jnp.float32)
    dv0 = jnp.zeros_like(dk0)
    dq0 = jnp.zeros_like(dk0)
    _, _, dk, dv, dq = lax.fori_loop(0, n, step, (k_loc, v_loc, dk0, dv0, dq0))
    return dq.astype(q_loc.dtype), dk.astype(k_loc.dtype), dv.astype(v_loc.dtype)


_ring_attn_local.defvjp(_ring_attn_fwd, _ring_attn_bwd)


def ring_attention(
    q,
    k,
    v,
    mesh,
    *,
    axis_name: str = "sp",
    causal: bool = False,
    sm_scale: float | None = None,
    impl: str = "auto",
    interpret: bool = False,
):
    """Exact attention over sequence-sharded Q/K/V.

    Inputs are global arrays [B, T, H, D] sharded over axis_name on dim 1 (or
    plain arrays, which shard_map will split). Returns output with the same
    sharding. Differentiable (ring-level custom VJP; see module docstring).

    impl: "pallas" (flash kernel per chunk), "xla", or "auto" (pallas on TPU
    when the local chunk is Mosaic-tileable, else xla).
    """
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    n = mesh.shape[axis_name]
    chunk_len = q.shape[1] // n
    # Same tiling requirement as flash_attention (ops/attention.py): blocks
    # must divide the chunk exactly — a clamped tail slice would read
    # overlapping rows and the backward would double-count them. Head dim is
    # unconstrained (the kernel's block spans all of D).
    tileable = chunk_len % min(128, chunk_len) == 0
    if impl == "auto":
        on_tpu = jax.default_backend() == "tpu"
        impl = "pallas" if (on_tpu or interpret) and tileable else "xla"
    elif impl == "pallas" and not tileable:
        raise ValueError(
            f"impl='pallas' requires the per-device chunk length ({chunk_len}) "
            "to be a multiple of the 128 block size (or < 128 exactly); "
            "use impl='xla' or pad the sequence"
        )
    spec = P(None, axis_name, None, None)
    fn = jax.shard_map(
        # custom_vjp takes nondiff args positionally (kwargs unsupported).
        lambda a, b, c: _ring_attn_local(
            a, b, c, axis_name, n, causal, sm_scale, impl, interpret
        ),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v)
