"""A grouped matmul whose row tile fits a group: Pallas ``megablox.gmm`` at
tiles chosen for routed experts' matrices (``parallel/moe.routed_experts``).

``jax.lax.ragged_dot``'s TPU kernel takes rows 512 at a time and pays a tile
whole for every group the tile crosses: a prefill chunk's 2048 assignments over
64 experts, 32 rows a group, cost what 64 x 512 rows cost, and the call is bound
by the matrix unit where a decode step's is bound by the experts' bytes. Here a
row tile is ``_ROW_TILE`` rows, and the contraction is ONE tile: a group's
``[in, tn]`` strip of its matrix then keeps its block index over the row tiles
the group spans, and the pipeline fetches it once. What a call reads is each
touched expert's matrix once and the rows once a column strip.

The matrices are the whole ``[L * E, in, out]`` stack as it lies: the layer is
in ``groups`` (sizes of all ``L * E`` groups, the other layers' zero), an empty
group is visited by no tile, and the kernel's block index reaches into the
stack, so nothing of it is sliced or copied.

A row of no group (behind the last) is in no tile's store mask, or in no tile
at all: it holds whatever was there, as after ``ragged_dot``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ray_tpu.ops.attention import _on_tpu

# Rows a tile. A group's rows lie in at most ``rows / _ROW_TILE + 1`` tiles, each paid whole by the matrix unit.
_ROW_TILE = 128
# Bytes of a matrix's column strip ``[in, tn]`` in VMEM, where the pipeline holds two.
_STRIP_BYTES = 4 << 20
_LANES = 128


def row_tile(rows: int) -> int | None:
    """The row tile of a call of ``rows`` rows, None where no tile divides them."""
    return _ROW_TILE if rows % _ROW_TILE == 0 else None


def _strip(k: int, n: int, itemsize: int) -> int:
    """Columns of a strip: whole lanes within ``_STRIP_BYTES``, the widest that
    divides ``n`` if any does (no ragged last strip), else the widest."""
    fit = [tn for tn in range(_LANES, n + 1, _LANES) if k * tn * itemsize <= _STRIP_BYTES] or [_LANES]
    return max([tn for tn in fit if n % tn == 0] or fit)


# Rows a tile of the matrices' gradient (``_tgmm``), where a tile's product contracts over the tile's rows alone.
_GRAD_ROW_TILE = 512
# Bytes of that gradient's float32 ``[tk, tn]`` tile in VMEM: the accumulator and the two the pipeline holds.
_GRAD_TILE_BYTES = 3 << 20


def _lane_divisors(n: int) -> list:
    """The widths of whole lanes that divide ``n``, or ``n`` itself where none does."""
    return [t for t in range(_LANES, n + 1, _LANES) if n % t == 0] or [n]


def _grad_tiles(k: int, n: int) -> tuple:
    """``(tk, tn)`` of the matrices' gradient ``[G, k, n]``: the largest tile of
    whole lanes both ways that divides both and fits ``_GRAD_TILE_BYTES``, the
    wider side kept whole first (the rows are read once a tile of the other)."""
    fits = [(tk * tn, tk, tn) for tk in _lane_divisors(k) for tn in _lane_divisors(n) if tk * tn * 4 <= _GRAD_TILE_BYTES]
    return max(fits)[1:] if fits else (_lane_divisors(k)[0], _lane_divisors(n)[0])


def grouped_matmul(a, w, groups, *, cast=None, interpret: bool | None = None):
    """a [rows, in] sorted by group, w [G, in, out], ``groups`` [G] int32 rows of
    each group in order: ``a[rows of g] @ w[g]``, [rows, out] in a's dtype,
    accumulated in float32; ``w`` is cast to a's dtype here, or ``cast`` is
    that already (a caller inside a differentiated loop casts once outside it:
    cast inside, the matrix would be stacked an iteration as a residual).
    ``interpret`` None: compiled on a TPU, interpreted elsewhere (tests).

    A matrix whose ``out`` fills no whole lanes (Nemotron-3-Nano's 1856) lies on
    the device with ``in`` minor-most, and handed over as it is declared would
    be copied whole into the kernel's layout in every call (1.3 GB a stack of
    2688 x 1856, v5e, PR 43 and 49): it goes in transposed, which is how it
    lies, and the kernel contracts its strips' minor axis.

    Differentiable in ``a`` and ``w`` (a ``custom_vjp`` of the repo's own over
    ``megablox``'s two kernels, each call at a tiling of its own: the one that
    ``megablox.ops.gmm`` hands its backward pass is the forward's, one
    contraction tile as wide as the forward's ``in``, which fits neither):
    the rows' gradient is the grouped matmul of the cotangent against the
    matrices transposed (contraction ``out`` in one tile, strips over ``in``),
    the matrices' gradient ``a[rows of g]^T @ cotangent[rows of g]`` a group
    (``tgmm``: ``_GRAD_ROW_TILE`` rows a tile, ``_grad_tiles``), accumulated in
    float32 and written in ``w``'s OWN dtype, a float32 leaf's gradient never
    rounded to a's. A row of no group gets whatever was there in both."""
    cast = None if cast is None else jax.lax.stop_gradient(cast)  # ``w`` is what is differentiated
    return _grouped_matmul(a, w, cast, groups, not _on_tpu() if interpret is None else interpret)


def _backend():
    """``megablox``'s module of the two kernels (the package's ``gmm`` is its function with its own VJP)."""
    import importlib

    return importlib.import_module("jax.experimental.pallas.ops.tpu.megablox.gmm")


def _gmm(a, w, groups, transposed: bool, interpret: bool):
    """``a @ w[g]`` (``transposed``: ``a @ w[g]^T``) a group, w in a's dtype."""
    k, n = w.shape[1:] if not transposed else w.shape[:0:-1]
    lies_transposed = w.shape[2] % _LANES != 0
    return _backend().gmm(
        a, w.swapaxes(1, 2) if lies_transposed else w, groups, preferred_element_type=a.dtype,
        tiling=(row_tile(a.shape[0]), k, _strip(k, n, w.dtype.itemsize)), transpose_rhs=lies_transposed != transposed,
        interpret=interpret,
    )


def _forward(a, w, cast, groups, interpret):
    given = cast is not None
    cast = cast if given else w.astype(a.dtype)
    return _gmm(a, cast, groups, False, interpret), (a, cast, groups, jnp.zeros((0,), w.dtype), given)


def _backward(interpret, residuals, cotangent):
    a, cast, groups, leaf, given = residuals
    cotangent = cotangent.astype(a.dtype)
    rows, (k, n) = a.shape[0], cast.shape[1:]
    tile = _GRAD_ROW_TILE if rows % _GRAD_ROW_TILE == 0 else row_tile(rows)
    matrices = _backend().tgmm(
        a.swapaxes(0, 1), cotangent, groups, preferred_element_type=leaf.dtype, tiling=(tile, *_grad_tiles(k, n)),
        interpret=interpret,
    )
    return _gmm(cotangent, cast, groups, True, interpret), matrices, jnp.zeros_like(cast) if given else None, None


_grouped_matmul = jax.custom_vjp(lambda a, w, cast, groups, interpret: _forward(a, w, cast, groups, interpret)[0], nondiff_argnums=(4,))
_grouped_matmul.defvjp(_forward, _backward)
