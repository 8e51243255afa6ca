"""Attention kernels.

The hot op of every transformer in models/: a Pallas TPU flash-attention
kernel (blockwise online-softmax, VMEM-resident accumulators, MXU-shaped
tiles) with a pure-XLA fallback for CPU/debug.

The reference has no attention kernels at all (it delegates model math to
torch; SURVEY.md §5.7) — this module is where the TPU-native build spends the
FLOPs the reference hands to external frameworks.

Design notes (per /opt/skills/guides/pallas_guide.md):
- grid = (batch, heads, q_blocks); the k-loop runs inside the kernel as a
  fori_loop so the running max/denominator stay in VMEM scratch.
- the kernels read q ``[B, H, T, D]`` and k, v ``[B, KV, T, D]`` (heads before
  tokens: a head's ``[T, D]`` is contiguous and tiled as a kernel wants it) in
  place: a KV head is block ``h // rep`` of its axis, so nothing repeats k and v.
- block edges are multiples of 128 (the (8,128)/f32, (16,128)/bf16 tile
  constraints), chosen a kernel and a mask by ``_blocks``.
- causal and windowed masking prune the blocks the mask hides whole via the
  loop's bounds (``_key_span`` / ``_query_span``; ``tile_schedule`` counts).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name


def _xla_attention(q, k, v, causal: bool, sm_scale: float, bias=None, window: int = 0):
    """Reference implementation (XLA fuses this fine on CPU; used for
    correctness tests and non-TPU fallback). ``window`` > 0: sliding-window
    causal attention — row i sees keys (i-window, i]. k and v may have fewer
    heads than q (``H % KV == 0``): query head h reads KV head ``h // (H // KV)``."""
    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    k, v = repeat_kv(k, H, axis=2), repeat_kv(v, H, axis=2)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * sm_scale
    if bias is not None:
        logits = logits + bias
    if causal:
        mask = jnp.tril(jnp.ones((Tq, Tk), dtype=bool), k=Tk - Tq)
        if window > 0:
            q_pos = (Tk - Tq) + jnp.arange(Tq)[:, None]
            k_pos = jnp.arange(Tk)[None, :]
            mask = mask & (q_pos - k_pos < window)
        logits = jnp.where(mask[None, None], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


# A row's log-sum-exp is a [block_q, 1] column in the kernel, and Mosaic tiles the
# last two dims as (8, 128): the column is spread over this many lanes and
# transposed, and ONE row of that, [1, block_q] lane-major, is what leaves
# (``[B, H, 1, Tq]``, the layout the backward kernels slice). Until PR 53 all 128
# replicated lanes were written, ``[B*H, Tq, 128]`` float32 (268 MB a layer at
# Mellum's widths), and sliced after.
_LSE_LANES = 128

# What a kernel pays for a score of a tile it covers, by the tile's (block_q, block_k), in picoseconds: each kernel
# alone on the v5e over the covered tiles of a full causal T = 8192 (B 2, H 32, KV 4, D 128; PERF.md section 6, PR
# 60 holds the whole table, fourteen shapes a kernel a mask). A larger tile is cheaper a score (fewer loop steps,
# longer matmuls) and covers more of what the mask hides; ``_blocks`` weighs the two. Shapes left out lost under
# every mask measured: an edge of 256 or less in a backward kernel, of 2048 anywhere.
_PS_A_COVERED_SCORE = {
    "fwd": {(512, 512): 4.387, (256, 1024): 4.117, (512, 1024): 4.232, (1024, 1024): 4.278},
    "dkv": {(512, 512): 6.843, (1024, 512): 6.383, (512, 1024): 6.606, (1024, 1024): 6.256},
    "dq": {(512, 512): 5.206, (1024, 512): 4.650, (512, 1024): 4.474, (1024, 1024): 4.492},
}
# Each of the three kernels keeps two operands of a head whole in VMEM (the
# forward and dq its keys and values, dkv its queries and their cotangent), two
# buffers each. Up to this many bytes of them the compiler's own scoped limit
# (16 MiB on the v5e) holds them and the tiles (4096 tokens of 128 in bfloat16:
# every cell the benchmark had before its 8192-token one); beyond, the kernel
# asks for them and this much beside (at 8192 tokens the forward needs 17.7 MB
# and was refused: compiled for the v5e off the chip, PR 50).
_RESIDENT_BYTES = 4 << 20
_TILES_BYTES = 16 << 20


def _vmem(rows: int, d: int, itemsize: int) -> dict:
    """``pallas_call``'s compiler parameters for a kernel that keeps two ``[rows,
    d]`` operands whole: none while the default limit holds them."""
    resident = 4 * rows * d * itemsize
    if resident <= _RESIDENT_BYTES:
        return {}
    from jax.experimental.pallas import tpu as pltpu

    return {"compiler_params": pltpu.CompilerParams(vmem_limit_bytes=resident + _TILES_BYTES)}


def repeat_kv(x, H: int, axis: int):
    """k or v at ``H`` heads along ``axis``, each KV head ``H // KV`` times in a row: what a path pays that cannot
    address a KV head as ``h // rep``."""
    KV = x.shape[axis]
    return x if KV == H else jnp.repeat(x, H // KV, axis=axis)


def _sum_groups(dx, KV: int, dtype):
    """``repeat_kv``'s cotangent: ``[B, H, T, D]`` summed over each KV head's query heads, in float32."""
    B, H, T, D = dx.shape
    if KV == H:
        return dx.astype(dtype)
    return dx.reshape(B, KV, H // KV, T, D).astype(jnp.float32).sum(axis=2).astype(dtype)


def _swap(x):
    """``[B, T, H, D]`` <-> ``[B, H, T, D]``."""
    return x.transpose(0, 2, 1, 3)


def _int_clip(x, lo, hi):
    return min(max(x, lo), hi)


def _key_span(first_q_pos, block_q: int, block_k: int, num_kb, window: int, clip=jnp.clip):
    """The key blocks ``[from, to)`` a block of queries walks under the causal mask: from the block of the first
    row's oldest visible key (0 without a window) to the block of the last row's own. ``first_q_pos`` is the
    POSITION of the block's first row (its index plus ``Tk - Tq``: the bottom-right alignment). Blocks outside are
    never walked: under a window that is what makes the cost O(T * window), not O(T^2). The same arithmetic serves
    a kernel (traced scalars, ``jnp.clip``) and ``tile_schedule`` (ints, ``_int_clip``)."""
    end = clip((first_q_pos + block_q - 1) // block_k + 1, 0, num_kb)
    start = clip((first_q_pos - window + 1) // block_k, 0, end) if window > 0 else 0
    return start, end


def _query_span(first_key_row, block_q: int, block_k: int, num_qb, window: int, clip=jnp.clip):
    """``_key_span`` seen from a block of keys (the dK/dV kernel's loop): from the first query block whose LAST row
    reaches the first key to the last whose FIRST row still has the last key in its window (the last block without
    one). ``first_key_row`` is the first key's position less ``Tk - Tq``: the index of the row whose own key it is."""
    start = clip(first_key_row // block_q, 0, num_qb)
    end = clip((first_key_row + block_k + window - 2) // block_q + 1, start, num_qb) if window > 0 else num_qb
    return start, end


def tile_schedule(Tq: int, Tk: int, window: int, block_q: int, block_k: int) -> tuple:
    """What the three kernels' loops do for ONE head under the causal mask (``window`` 0: full causal), counted:
    (tiles covered, tiles an edge of the mask crosses, scores the covered tiles hold, scores the mask lets through).
    The three kernels cover the same tiles, those the band of visible scores touches (the dK/dV kernel walks them by
    key block, the other two by query block), and mask every one: only the crossed tiles need it, but on the v5e
    the mask costs nothing that can be measured and a second loop for the tiles between costs 0.3-0.8 us a grid
    step (PERF.md section 6, PR 60). What a kernel's time follows is the third number."""
    offset = Tk - Tq
    covered = crossed = 0
    for qb in range(Tq // block_q):
        first, last = qb * block_q + offset, (qb + 1) * block_q - 1 + offset
        start, end = _key_span(first, block_q, block_k, Tk // block_k, window, _int_clip)
        covered += end - start
        # whole tiles: every key at or before the first row's own, and (a window) the last row still sees the tile's first
        crossed += sum(not (first >= (kb + 1) * block_k - 1 and (window == 0 or last - kb * block_k < window)) for kb in range(start, end))
    visible = sum(max(0, min(p, Tk - 1) - (max(0, p - window + 1) if window > 0 else 0) + 1) for p in range(offset, Tk))
    return covered, crossed, covered * block_q * block_k, visible


def _fwd_tile(q, k_blk, v_blk, carry, q_pos0, k_pos0, masked: bool, sm_scale: float, window: int):
    """The forward kernel's inner body: one [block_q, block_k] score tile folded into the running (max, sum,
    accumulator). ``masked`` False: no causal mask, every score visible."""
    m_prev, l_prev, acc_prev = carry
    s = jax.lax.dot_general(
        q, k_blk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * sm_scale  # [block_q, block_k]
    if masked:
        q_pos = q_pos0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        k_pos = k_pos0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        visible = q_pos >= k_pos
        if window > 0:
            visible &= q_pos - k_pos < window
        s = jnp.where(visible, s, -jnp.inf)
    m_cur = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    # Fully-masked-so-far rows (possible under a sliding window: early
    # k-blocks can be entirely outside a late row's window) have
    # m_cur = -inf; exp(-inf - -inf) would be NaN. Substituting 0 for
    # the max keeps correction = p = exp(-inf) = 0 — the correct
    # "contributes nothing" behavior.
    safe_m = jnp.where(jnp.isneginf(m_cur), 0.0, m_cur)
    correction = jnp.exp(m_prev - safe_m)
    p = jnp.exp(s - safe_m)
    l_cur = l_prev * correction + p.sum(axis=-1, keepdims=True)
    pv = jax.lax.dot_general(
        p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    return m_cur, l_cur, acc_prev * correction + pv


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref=None, *, block_k: int, causal: bool, sm_scale: float, seq_k: int, block_q: int, window: int = 0):
    from jax.experimental import pallas as pl

    q = q_ref[...]  # [block_q, d]
    q_idx = pl.program_id(2)  # grid = (batch, heads, q blocks)
    d = q.shape[-1]

    m0 = jnp.full((q.shape[0], 1), -jnp.inf, dtype=jnp.float32)
    l0 = jnp.zeros((q.shape[0], 1), dtype=jnp.float32)
    acc0 = jnp.zeros((q.shape[0], d), dtype=jnp.float32)

    # Bottom-right-aligned causal mask (matches _xla_attention's
    # tril(k=Tk-Tq)): query row i sees keys 0..i+(Tk-Tq). Identical to the
    # usual mask when Tq == Tk; for Tq < Tk (decode with cache) the tail of
    # the keys is what's visible.
    causal_offset = seq_k - block_q * pl.num_programs(2)
    first_q_pos = q_idx * block_q + causal_offset

    def body(kb, carry):
        k_blk = k_ref[pl.ds(kb * block_k, block_k), :]
        v_blk = v_ref[pl.ds(kb * block_k, block_k), :]
        return _fwd_tile(q, k_blk, v_blk, carry, first_q_pos, kb * block_k, causal, sm_scale, window)

    # ONE loop over the key blocks the mask's band touches, every tile masked alike (``tile_schedule``).
    start, end = _key_span(first_q_pos, block_q, block_k, pl.cdiv(seq_k, block_k), window) if causal else (0, pl.cdiv(seq_k, block_k))
    m, l, acc = jax.lax.fori_loop(start, end, body, (m0, l0, acc0))
    o_ref[...] = (acc / l).astype(o_ref.dtype)
    if lse_ref is not None:
        # Log-sum-exp per row: the residual the backward pass needs to
        # reconstruct P = exp(S - lse) blockwise without re-running the
        # online softmax. Replicated across the lane dim (see _LSE_LANES).
        # Only materialized on the VJP forward — the primal path skips the
        # HBM write entirely.
        lse = jnp.broadcast_to(m + jnp.log(l), (q.shape[0], _LSE_LANES)).T  # [lanes, block_q]: a row is lane-major
        lse_ref[...] = lse[:1].astype(lse_ref.dtype)


def _pallas_flash_with_lse(q, k, v, causal: bool, sm_scale: float, block_q: int, block_k: int, interpret: bool, save_lse: bool = True, window: int = 0):
    """q ``[B, H, Tq, D]``, k and v ``[B, KV, Tk, D]`` (``H % KV == 0``) -> (out ``[B, H, Tq, D]``, log-sum-exp
    ``[B, H, Tq]`` float32 or None), read and written where they lie: the keys and values of query head ``h`` are
    block ``h // rep`` of the KV axis, which the pipeline fetches once for the ``rep`` consecutive heads that name it."""
    from jax.experimental import pallas as pl

    B, H, Tq, D = q.shape
    KV, Tk = k.shape[1], k.shape[2]
    rep = H // KV
    kernel = functools.partial(
        _flash_kernel,
        block_k=block_k,
        causal=causal,
        sm_scale=sm_scale,
        seq_k=Tk,
        block_q=block_q,
        window=window,
    )
    q_block = pl.BlockSpec((None, None, block_q, D), lambda b, h, qb: (b, h, qb, 0))
    kv_whole = pl.BlockSpec((None, None, Tk, D), lambda b, h, qb: (b, h // rep, 0, 0))
    out_specs = [q_block]
    out_shape = [jax.ShapeDtypeStruct(q.shape, q.dtype)]
    if save_lse:
        out_specs.append(pl.BlockSpec((None, None, 1, block_q), lambda b, h, qb: (b, h, 0, qb)))
        out_shape.append(jax.ShapeDtypeStruct((B, H, 1, Tq), jnp.float32))
    res = pl.pallas_call(
        kernel,
        grid=(B, H, pl.cdiv(Tq, block_q)),
        in_specs=[q_block, kv_whole, kv_whole],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
        **_vmem(Tk, D, k.dtype.itemsize),
    )(q, k, v)
    return res[0], res[1][:, :, 0] if save_lse else None


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _pallas_flash(q, k, v, causal: bool, sm_scale: float, block_q: int | None, block_k: int | None, interpret: bool, window: int = 0):
    """``block_q`` / ``block_k``: a caller's block edges for all three kernels, or None for ``_blocks``'s."""
    bq, bk = _edges("fwd", q, k, window, block_q, block_k)
    out, _ = _pallas_flash_with_lse(q, k, v, causal, sm_scale, bq, bk, interpret, save_lse=False, window=window)
    return out


# What a layer under ``jax.checkpoint`` may keep of this call (models/transformer.py's policy names them): the
# forward kernel's two results. Kept, the backward pass runs the two backward kernels on them and the forward kernel
# once a step, not twice. A name outside a checkpoint with a policy is the identity.
FLASH_OUT, FLASH_LSE = "flash_out", "flash_lse"


def _pallas_flash_fwd(q, k, v, causal, sm_scale, block_q, block_k, interpret, window=0):
    bq, bk = _edges("fwd", q, k, window, block_q, block_k)
    out, lse = _pallas_flash_with_lse(q, k, v, causal, sm_scale, bq, bk, interpret, window=window)
    out, lse = checkpoint_name(out, FLASH_OUT), checkpoint_name(lse, FLASH_LSE)
    return out, (q, k, v, out, lse)


def _xla_blockwise_bwd(causal, sm_scale, block_q, block_k, window, res, dout):
    """Memory-efficient flash backward, expressed in XLA (lax.fori_loop over
    K blocks — the compiler tiles the matmuls onto the MXU; peak memory is
    one [B,H,Tq,block_k] logits block instead of the full [Tq,Tk] matrix).
    CPU/debug fallback for the Pallas backward kernels below.

    Standard flash-attention backward (Dao et al. 2022):
        D  = rowsum(dO * O)
        P  = exp(S - lse)
        dV = P^T dO;  dP = dO V^T;  dS = P * (dP - D) * sm_scale
        dQ = dS K;    dK = dS^T Q
    """
    qT, kT, vT, oT, lse = res                          # [B,H,Tq,D], [B,KV,Tk,D] x2, [B,H,Tq,D], [B,H,Tq]
    doT = dout
    B, H, Tq, D = qT.shape
    KV, Tk = kT.shape[1], kT.shape[2]
    kT, vT = repeat_kv(kT, H, axis=1), repeat_kv(vT, H, axis=1)
    # Inputs stay in their storage dtype (bf16 on TPU): every matmul below
    # asks for f32 accumulation via preferred_element_type, which is the
    # MXU's native mode. An upfront .astype(f32) would instead force f32
    # matmuls (multi-pass on the MXU, ~4x slower) — measured 89.8k -> 97k+
    # tok/s on the v5e bench when the casts were dropped.
    delta = jnp.sum(doT.astype(jnp.float32) * oT.astype(jnp.float32), axis=-1)  # [B,H,Tq]

    def mm(a, b, pat):
        return jnp.einsum(pat, a, b, preferred_element_type=jnp.float32)

    bk = min(block_k, Tk)
    num_kb = (Tk + bk - 1) // bk
    # Sliding window: only q rows with k_pos <= q_pos < k_pos + window can
    # attend a given k block, so the q range touching block [start,
    # start+bk) spans at most bk + window - 1 rows. Slicing q to that
    # (static) width keeps the backward O(T·window) like the forward
    # kernel, instead of scoring all Tq rows per block.
    qw = min(Tq, bk + window - 1) if (causal and window > 0) else Tq
    # Same bottom-right causal alignment as forward kernel/_xla_attention.
    q_row = jax.lax.broadcasted_iota(jnp.int32, (qw, bk), 0)

    def body(kb, carry):
        dq_acc, dk_acc, dv_acc = carry
        start = kb * bk
        qs_start = (
            jnp.clip(start - (Tk - Tq), 0, Tq - qw) if qw < Tq else jnp.int32(0)
        )
        ks = jax.lax.dynamic_slice_in_dim(kT, start, bk, axis=2)   # [B,H,bk,D]
        vs = jax.lax.dynamic_slice_in_dim(vT, start, bk, axis=2)
        qs = jax.lax.dynamic_slice_in_dim(qT, qs_start, qw, axis=2)
        dos = jax.lax.dynamic_slice_in_dim(doT, qs_start, qw, axis=2)
        lses = jax.lax.dynamic_slice_in_dim(lse, qs_start, qw, axis=2)
        deltas = jax.lax.dynamic_slice_in_dim(delta, qs_start, qw, axis=2)
        s = mm(qs, ks, "bhqd,bhkd->bhqk") * sm_scale
        if causal:
            q_pos = (Tk - Tq) + qs_start + q_row
            k_pos = start + jax.lax.broadcasted_iota(jnp.int32, (qw, bk), 1)
            visible = q_pos >= k_pos
            if window > 0:
                visible &= q_pos - k_pos < window
            s = jnp.where(visible[None, None], s, -jnp.inf)
        p = jnp.exp(s - lses[..., None])                # f32; masked rows -> 0
        dp = mm(dos, vs, "bhqd,bhkd->bhqk")
        ds = (p * (dp - deltas[..., None]) * sm_scale).astype(qT.dtype)
        pb = p.astype(qT.dtype)
        dq_slice = jax.lax.dynamic_slice_in_dim(dq_acc, qs_start, qw, axis=2)
        dq_acc = jax.lax.dynamic_update_slice_in_dim(
            dq_acc, dq_slice + mm(ds, ks, "bhqk,bhkd->bhqd"), qs_start, axis=2
        )
        dk_b = mm(ds, qs, "bhqk,bhqd->bhkd")
        dv_b = mm(pb, dos, "bhqk,bhqd->bhkd")
        dk_acc = jax.lax.dynamic_update_slice_in_dim(dk_acc, dk_b, start, axis=2)
        dv_acc = jax.lax.dynamic_update_slice_in_dim(dv_acc, dv_b, start, axis=2)
        return dq_acc, dk_acc, dv_acc

    dq0 = jnp.zeros((B, H, Tq, D), jnp.float32)
    dk0 = jnp.zeros((B, H, Tk, D), jnp.float32)
    dv0 = jnp.zeros((B, H, Tk, D), jnp.float32)
    dq, dk, dv = jax.lax.fori_loop(0, num_kb, body, (dq0, dk0, dv0))
    return dq.astype(qT.dtype), _sum_groups(dk, KV, kT.dtype), _sum_groups(dv, KV, vT.dtype)


# --- Pallas backward kernels -------------------------------------------------
#
# Two kernels (Dao et al. 2022 split): dkv iterates the grid over K blocks
# accumulating [block_k, D] dK/dV in VMEM; dq iterates over Q blocks
# accumulating [block_q, D] dQ. Both compute scores in the TRANSPOSED
# orientation s[block_k, block_q] = (K·Qᵀ)·scale so the per-row softmax
# residuals (lse) and delta = rowsum(dO·O) broadcast in as [1, block_q]
# lane-major rows — no 128-lane replication blowup and no [block_q, 1]
# layouts Mosaic can't tile. Causal + sliding-window pruning bound the inner
# loop exactly like the forward kernel, so the backward does ~half the MXU
# work of a full-score XLA backward (and never materializes a [Tq, Tk]
# tensor in HBM: measured 90.1k -> 109k tok/s on the v5e single-chip bench).


def _bwd_tile(q_blk, do_blk, k_blk, v_blk, lse_row, delta_row, q_pos0, k_pos0, masked, sm_scale, window):
    """Shared inner body: one (k-block, q-block) score tile, transposed
    orientation. Returns (p, ds) as [block_k, block_q] f32. ``masked`` False:
    no causal mask, every score visible."""
    s = jax.lax.dot_general(
        k_blk, q_blk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * sm_scale  # [bk, bq]
    if masked:
        k_pos = k_pos0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        q_pos = q_pos0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        visible = q_pos >= k_pos
        if window > 0:
            visible &= q_pos - k_pos < window
        s = jnp.where(visible, s, -jnp.inf)
    p = jnp.exp(s - lse_row)  # [1, bq] broadcasts over k rows; masked -> 0
    dp = jax.lax.dot_general(
        v_blk, do_blk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    ds = p * (dp - delta_row) * sm_scale
    return p, ds


def _flash_bwd_dkv_kernel(q_ref, do_ref, k_ref, v_ref, lse_ref, delta_ref, dk_ref, dv_ref, *, block_q: int, block_k: int, causal: bool, sm_scale: float, seq_q: int, seq_k: int, window: int):
    from jax.experimental import pallas as pl

    kb = pl.program_id(2)
    k_blk = k_ref[...]
    v_blk = v_ref[...]
    offset = seq_k - seq_q  # bottom-right causal alignment
    num_qb = pl.cdiv(seq_q, block_q)

    def body(qb, carry):
        dk_acc, dv_acc = carry
        q_blk = q_ref[pl.ds(qb * block_q, block_q), :]
        do_blk = do_ref[pl.ds(qb * block_q, block_q), :]
        lse_row = lse_ref[:, pl.ds(qb * block_q, block_q)]
        delta_row = delta_ref[:, pl.ds(qb * block_q, block_q)]
        p, ds = _bwd_tile(
            q_blk, do_blk, k_blk, v_blk, lse_row, delta_row,
            qb * block_q + offset, kb * block_k, causal, sm_scale, window,
        )
        dv_acc += jax.lax.dot_general(
            p.astype(do_blk.dtype), do_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dk_acc += jax.lax.dot_general(
            ds.astype(q_blk.dtype), q_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return dk_acc, dv_acc

    qb_start, qb_end = _query_span(kb * block_k - offset, block_q, block_k, num_qb, window) if causal else (0, num_qb)
    z = jnp.zeros((k_blk.shape[0], k_blk.shape[1]), jnp.float32)
    dk, dv = jax.lax.fori_loop(qb_start, qb_end, body, (z, z))
    dk_ref[...] = dk.astype(dk_ref.dtype)
    dv_ref[...] = dv.astype(dv_ref.dtype)


def _flash_bwd_dq_kernel(q_ref, do_ref, k_ref, v_ref, lse_ref, delta_ref, dq_ref, *, block_q: int, block_k: int, causal: bool, sm_scale: float, seq_q: int, seq_k: int, window: int):
    from jax.experimental import pallas as pl

    qb = pl.program_id(2)
    q_blk = q_ref[...]
    do_blk = do_ref[...]
    lse_row = lse_ref[...]
    delta_row = delta_ref[...]
    offset = seq_k - seq_q
    num_kb = pl.cdiv(seq_k, block_k)

    def body(kb, dq_acc):
        k_blk = k_ref[pl.ds(kb * block_k, block_k), :]
        v_blk = v_ref[pl.ds(kb * block_k, block_k), :]
        _, ds = _bwd_tile(
            q_blk, do_blk, k_blk, v_blk, lse_row, delta_row,
            qb * block_q + offset, kb * block_k, causal, sm_scale, window,
        )
        # dQ += dSᵀ K : contract over the k rows of the transposed tile.
        return dq_acc + jax.lax.dot_general(
            ds.astype(k_blk.dtype), k_blk, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    kb_start, kb_end = _key_span(qb * block_q + offset, block_q, block_k, num_kb, window) if causal else (0, num_kb)
    z = jnp.zeros((q_blk.shape[0], q_blk.shape[1]), jnp.float32)
    dq = jax.lax.fori_loop(kb_start, kb_end, body, z)
    dq_ref[...] = dq.astype(dq_ref.dtype)


def _pallas_bwd_impl(q, k, v, out, lse, dout, causal, sm_scale, dkv_edges, dq_edges, interpret, window):
    """Operands as the forward call's -> (dq ``[B, H, Tq, D]``, dk and dv ``[B, KV, Tk, D]``). The dkv kernel writes
    a query head's dk and dv each, ``[B, H, Tk, D]``, and ONE reduction in float32 sums a KV head's over its
    ``rep`` query heads (``_sum_groups``), as the cotangent of a repeat would. Each kernel has its own
    ``(block_q, block_k)``: its grid's axis sets what it covers, its loop's axis only the tile."""
    from jax.experimental import pallas as pl

    B, H, Tq, D = q.shape
    KV, Tk = k.shape[1], k.shape[2]
    rep = H // KV
    # delta = rowsum(dO · O) and the log-sum-exp: small float32 [B, H, 1, Tq], lane-major so that the kernels
    # can slice [1, block_q] rows without layout tricks.
    delta = jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)[:, :, None, :]
    operands = (q, dout, k, v, lse[:, :, None, :], delta)

    kw = dict(causal=causal, sm_scale=sm_scale, seq_q=Tq, seq_k=Tk, window=window)
    block_q, block_k = dkv_edges
    q_whole = pl.BlockSpec((None, None, Tq, D), lambda b, h, kb: (b, h, 0, 0))
    kv_block = pl.BlockSpec((None, None, block_k, D), lambda b, h, kb: (b, h // rep, kb, 0))
    dkv_block = pl.BlockSpec((None, None, block_k, D), lambda b, h, kb: (b, h, kb, 0))
    row_whole = pl.BlockSpec((None, None, 1, Tq), lambda b, h, kb: (b, h, 0, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, block_q=block_q, block_k=block_k, **kw),
        grid=(B, H, pl.cdiv(Tk, block_k)),
        in_specs=[q_whole, q_whole, kv_block, kv_block, row_whole, row_whole],
        out_specs=[dkv_block, dkv_block],
        out_shape=[jax.ShapeDtypeStruct((B, H, Tk, D), k.dtype), jax.ShapeDtypeStruct((B, H, Tk, D), v.dtype)],
        interpret=interpret,
        **_vmem(Tq, D, q.dtype.itemsize),
    )(*operands)
    block_q, block_k = dq_edges
    q_block = pl.BlockSpec((None, None, block_q, D), lambda b, h, qb: (b, h, qb, 0))
    kv_whole = pl.BlockSpec((None, None, Tk, D), lambda b, h, qb: (b, h // rep, 0, 0))
    row_block = pl.BlockSpec((None, None, 1, block_q), lambda b, h, qb: (b, h, 0, qb))
    (dq,) = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, block_q=block_q, block_k=block_k, **kw),
        grid=(B, H, pl.cdiv(Tq, block_q)),
        in_specs=[q_block, q_block, kv_whole, kv_whole, row_block, row_block],
        out_specs=[q_block],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype)],
        interpret=interpret,
        **_vmem(Tk, D, k.dtype.itemsize),
    )(*operands)
    return dq, _sum_groups(dk, KV, k.dtype), _sum_groups(dv, KV, v.dtype)


def _pallas_flash_bwd(causal, sm_scale, block_q, block_k, interpret, window, res, dout):
    q, k, v, out, lse = res
    Tq, Tk = q.shape[2], k.shape[2]
    dkv_edges, dq_edges = (_edges(kernel, q, k, window, block_q, block_k) for kernel in ("dkv", "dq"))
    use_pallas = (_on_tpu() or interpret) and not any(Tq % bq or Tk % bk for bq, bk in (dkv_edges, dq_edges))
    if not use_pallas:
        return _xla_blockwise_bwd(causal, sm_scale, *dkv_edges, window, (q, k, v, out, lse), dout)
    return _pallas_bwd_impl(q, k, v, out, lse, dout, causal, sm_scale, dkv_edges, dq_edges, interpret, window)


_pallas_flash.defvjp(_pallas_flash_fwd, _pallas_flash_bwd)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def flash_attention(
    q,
    k,
    v,
    *,
    causal: bool = False,
    sm_scale: float | None = None,
    block_q: int | None = None,  # None: ``_blocks``'s, a kernel; given, all three kernels' (the tests' small blocks)
    block_k: int | None = None,
    bias=None,
    force_pallas: bool | None = None,
    interpret: bool = False,
    window: int = 0,
):
    """Multi-head attention, heads before tokens: q [B, H, Tq, D], k and v [B, KV, Tk, D] with ``H % KV == 0``
    (grouped queries: head h reads KV head ``h // (H // KV)``; no caller repeats k and v) -> [B, H, Tq, D]. It is
    the layout the kernels read in place (a head's ``[T, D]`` contiguous); a caller that holds ``[B, T, H, D]``
    transposes at its own call.

    Pallas on TPU; XLA reference elsewhere (or with a bias, which the kernel
    does not support yet). ``window`` > 0 (requires causal) is Mistral-style
    sliding-window attention: row i attends keys (i-window, i]; the kernel
    SKIPS k-blocks entirely outside the window, so long-context cost is
    O(T·window), not O(T²). A window that reaches every key (``window >= Tk``)
    is none. Each kernel's block edges follow the mask (``_blocks``).
    """
    if window and not causal:
        raise ValueError("sliding window requires causal=True")
    if q.shape[1] % k.shape[1] or k.shape != v.shape:
        raise ValueError(f"{q.shape[1]} query heads over k {k.shape} and v {v.shape}: not whole groups")
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    use_pallas = force_pallas if force_pallas is not None else (_on_tpu() or interpret)
    Tq, Tk = q.shape[2], k.shape[2]
    if window >= Tk:  # binds nothing: the kernels get the full-causal mask's blocks, and no second compare a score
        window = 0
    bq, bk = _edges("fwd", q, k, window, block_q, block_k)
    # Block sizes must tile the sequence exactly: a clamped tail slice would
    # read overlapping rows (and the backward would double-count them).
    if bias is not None or not use_pallas or Tq % bq or Tk % bk:
        return _swap(_xla_attention(_swap(q), _swap(k), _swap(v), causal, sm_scale, bias, window=window))
    return _pallas_flash(q, k, v, causal, sm_scale, block_q, block_k, interpret, window)


@functools.lru_cache(maxsize=None)
def _blocks(kernel: str, Tq: int, Tk: int, D: int, window: int) -> tuple:
    """``(block_q, block_k)`` of the forward (``"fwd"``), the dK/dV (``"dkv"``) or the dQ (``"dq"``) kernel: of the
    shapes measured, the one whose covered tiles (``tile_schedule``: what the mask's band touches at these edges)
    cost least at the shape's measured price a score. The mask decides: under a window of 1024 every kernel takes
    (512, 512) (a larger block spans more keys outside the window than its cheaper score pays for); a full causal
    sequence takes the larger tiles once it is long enough that the diagonal's half-hidden tiles are few (the dK/dV
    kernel (1024, 1024) at 8192 tokens and (512, 512) at 4096). Heads wider than 128 keep (512, 512), as does a
    sequence that no measured shape divides: nothing wider was measured, and a tile's operands grow with D."""
    shapes = {edges: ps for edges, ps in _PS_A_COVERED_SCORE[kernel].items() if D <= 128 and Tq % edges[0] == 0 and Tk % edges[1] == 0}
    if not shapes:  # a short or ragged sequence: ``_fit_block`` brings 512 down to it
        return 512, 512
    return min(shapes, key=lambda edges: tile_schedule(Tq, Tk, window, *edges)[2] * shapes[edges])  # ties: the first listed


def _edges(kernel: str, q, k, window: int, block_q: int | None, block_k: int | None) -> tuple:
    """A kernel's block edges for ``q [.., Tq, D]`` over ``k [.., Tk, D]``: the caller's where given, else
    ``_blocks``'s, fitted to the sequence."""
    Tq, Tk = q.shape[2], k.shape[2]
    want_q, want_k = _blocks(kernel, Tq, Tk, q.shape[3], window)
    return _fit_block(block_q or want_q, Tq), _fit_block(block_k or want_k, Tk)


def _fit_block(want: int, t: int) -> int:
    """Largest 128-multiple <= want that divides t (so a sequence divisible
    by 128 but not by the preferred block still rides the kernel at a
    smaller block). For t <= 128 the block is t itself (block == full dim is
    Mosaic-legal); for larger non-128-multiple t the result is 128, and the
    caller's divisibility guard then routes to the XLA fallback — a 136-wide
    block would violate the (8, 128) tile constraint."""
    if t <= 128:
        return min(want, t)
    b = min(want, t)
    b -= b % 128
    while b > 128 and t % b:
        b -= 128
    return max(b, 128)
