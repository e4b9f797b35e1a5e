"""Shared in-kernel building blocks for the Intelligent-Unroll Pallas kernels.

TPU adaptation of the paper's instruction groups:
  * ``permute_tiles`` — the paper's ``permutation + select`` pair (Fig. 6):
    an in-register lane permute of each window tile, merged by a select
    chain on the lane's window slot.
  * ``segmented_reduce_lanes`` — the paper's log-step shuffle-reduce (§5,
    Fig. 5): ``op_flag`` static steps of masked lane-rotate-combine; masks are
    derived on the fly from segment-id compares (cheaper than the paper's
    stored M mask vectors — a beyond-paper micro-optimization, VPU compares
    are free relative to the metadata loads they replace).

Both blocks are rank-polymorphic over trailing lane axes (DESIGN.md §8,
§13): windows/terms may carry ``(..., D)`` value rows (SpMM lanes), while
slot/offset/segment metadata stays 2-D and broadcasts — the same
``_expand_trailing`` rule the XLA emitter applies.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

from repro.core.seed import reduce_identity_for

SEG_PAD = -(2 ** 30)

REDUCE_FNS = {
    "add": (jnp.add, 0.0, jnp.sum),
    "mul": (jnp.multiply, 1.0, jnp.prod),
    "max": (jnp.maximum, -jnp.inf, jnp.max),
    "min": (jnp.minimum, jnp.inf, jnp.min),
}

FULL_REDUCE = -1


def expand_trailing(a: jnp.ndarray, ndim: int) -> jnp.ndarray:
    """Append trailing singleton axes until ``a.ndim == ndim`` — the §8
    rank rule, usable inside kernel bodies (pure reshape)."""
    if a.ndim >= ndim:
        return a
    return a.reshape(a.shape + (1,) * (ndim - a.ndim))


def resolve_interpret(interpret: bool | None) -> bool:
    """Platform-resolve the interpret toggle: Pallas kernels compile for
    real on TPU/GPU and fall back to interpret mode only where no Mosaic/
    Triton lowering exists (CPU CI) or when explicitly requested.
    Interpret mode is a correctness/debugging vehicle — it must be opt-in
    on accelerators so an interpreted launch can never masquerade as the
    production path."""
    if interpret is not None:
        return bool(interpret)
    return jax.default_backend() not in ("tpu", "gpu")


def permute_tiles(tiles, slot: jnp.ndarray, offset: jnp.ndarray
                  ) -> jnp.ndarray:
    """Gather-replacement permute (paper Fig. 6: permutation + select):
    ``M`` window tiles of shape ``(rows, N, ...)``, one block per row ->
    the ``(rows, N, ...)`` lanes whose lane ``j`` of row ``r`` is word
    ``offset[r, j]`` of row ``r`` of tile ``slot[r, j]``.

    ``slot``/``offset`` are ``(rows, N)`` int32.  Each tile is permuted
    inside the lane axis (a batched lane gather — an in-register lane
    shuffle on TPU, no memory gather) and the ``M`` permuted tiles are
    merged by a select chain on ``slot``.  Every lane returns the selected
    word bit for bit, for every dtype (no arithmetic touches the payload,
    so ``±inf`` identities and large int32 words survive); a lane whose
    slot names no tile reads 0.

    Rank rule: trailing axes ride along unchanged — every lane selects a
    whole ``(...,)`` value row (SpMM fetches rows of B), and the 2-D lane
    metadata broadcasts over them.  With trailing axes the tiles are
    turned on their side (:func:`to_side`) and permuted by the 2-D lane
    gather, the only one Mosaic lowers."""
    if tiles[0].ndim > 2:
        d = math.prod(tiles[0].shape[2:])
        out = permute_tiles([to_side(t) for t in tiles],
                            side_meta(slot, d), side_meta(offset, d))
        return from_side(out, tiles[0].shape)
    idx = jnp.broadcast_to(offset.astype(jnp.int32), tiles[0].shape)
    slot = slot.astype(jnp.int32)
    out = jnp.zeros(tiles[0].shape, tiles[0].dtype)
    for w, tile in enumerate(tiles):
        out = jnp.where(slot == w, _lane_gather(tile, idx), out)
    return out


def to_side(a: jnp.ndarray) -> jnp.ndarray:
    """``(rows, N, ...)`` lanes with trailing value axes of ``D`` words
    -> ``(rows * D, N)``: each value column of each block a lane row of
    its own, so lanes are the minor axis again and the 2-D lane
    permutes, rolls and reductions apply to every width, column by
    column."""
    rows, n = a.shape[:2]
    d = math.prod(a.shape[2:])
    return jnp.swapaxes(a.reshape(rows, n, d), 1, 2).reshape(rows * d, n)


def side_meta(m: jnp.ndarray, d: int) -> jnp.ndarray:
    """``(rows, N)`` lane metadata -> ``(rows * d, N)``, repeated for each
    of a block's ``d`` value columns (the layout of :func:`to_side`)."""
    rows, n = m.shape
    return jnp.broadcast_to(m[:, None, :], (rows, d, n)).reshape(rows * d, n)


def from_side(a: jnp.ndarray, shape: tuple) -> jnp.ndarray:
    """The inverse of :func:`to_side`, back to ``shape``."""
    rows, n = shape[:2]
    d = math.prod(shape[2:])
    return jnp.swapaxes(a.reshape(rows, d, n), 1, 2).reshape(shape)


def _lane_gather(x: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """``out[r, j] = x[r, idx[r, j]]`` with in-bounds indices, spelled as
    the batched lane gather Mosaic lowers to ``tpu.dynamic_gather``
    (``jnp.take_along_axis`` picks another form when the leading dim is
    1)."""
    dnums = jax.lax.GatherDimensionNumbers(
        offset_dims=(), collapsed_slice_dims=(1,), start_index_map=(1,),
        operand_batching_dims=(0,), start_indices_batching_dims=(0,))
    return jax.lax.gather(x, idx[..., None], dnums, slice_sizes=(1, 1),
                          mode=jax.lax.GatherScatterMode.PROMISE_IN_BOUNDS)


def permute_onehot(windows: jnp.ndarray, slot: jnp.ndarray,
                   offset: jnp.ndarray) -> jnp.ndarray:
    """Stacked-window form of :func:`permute_tiles`: windows ``(M, N, ...)``
    -> ``(N, ...)``, equivalent to ``concat(windows)[slot * N + offset]``."""
    tiles = [windows[w:w + 1] for w in range(windows.shape[0])]
    return permute_tiles(tiles, slot, offset)[0]


def round_term(term: jnp.ndarray, zero) -> jnp.ndarray:
    """The combine's lanes, rounded to their dtype before the ladder reads
    them — the in-kernel twin of ``engine.combine_rounded``, for interpret
    mode.  That mode runs the body as XLA-CPU code, where LLVM may
    contract a product with the ladder's sum into one fused multiply-add
    depending on the slab shape; passing float bits through an integer XOR
    with ``zero`` (a runtime scalar, always 0, that the compiler cannot
    fold) ends every product at a rounded word.  Integer terms pass
    through."""
    if not jnp.issubdtype(term.dtype, jnp.floating):
        return term
    bits = jnp.dtype(f"int{8 * term.dtype.itemsize}")
    return jax.lax.bitcast_convert_type(
        jax.lax.bitcast_convert_type(term, bits) ^ zero.astype(bits),
        term.dtype)


def shift_lanes(a: jnp.ndarray, d: int, fill) -> jnp.ndarray:
    """``out[:, j] = a[:, j + d]`` for ``j < N - d``, ``fill`` beyond —
    a lane rotation (``pltpu.roll``) plus a lane mask, the form Mosaic
    lowers without unaligned lane slices."""
    n = a.shape[1]
    rolled = pltpu.roll(a, n - d, 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, a.shape[:2], 1)
    keep = expand_trailing(lane < n - d, a.ndim)
    return jnp.where(keep, rolled, jnp.asarray(fill, a.dtype))


def segmented_reduce_lanes(term: jnp.ndarray, seg: jnp.ndarray,
                           op_flag: int, reduce: str,
                           butterfly: bool = False) -> jnp.ndarray:
    """(rows, N, ...) lanes, one block per row -> the same shape with each
    segment head holding the full segment reduction.  ``op_flag`` is
    static (one kernel specialization per pattern class — the paper's
    per-flag code generation).  ``seg`` is always (rows, N) and broadcasts
    over trailing lane axes.  Shift pads use the dtype-aware identity
    (DESIGN.md §3a).

    ``FULL_REDUCE`` is the architecture-native lane reduction, or with
    ``butterfly`` a lane butterfly (rotate + combine) whose lane 0 is the
    XLA form's pairwise halving tree, combine for combine: interpret mode
    takes it, because XLA-CPU picks a native reduce's order per slab
    shape (1 vs R rows a step); the TPU's native reduce gives the same
    bits at every slab height, and 5-6% less stage-A time on a fused
    mixed launch (v5e, PERF.md)."""
    op, _, full = REDUCE_FNS[reduce]
    identity = reduce_identity_for(reduce, term.dtype)
    if op_flag == FULL_REDUCE:
        if butterfly:
            total, d = term, 1
            while d < term.shape[1]:
                total = op(total, shift_lanes(total, d, identity))
                d *= 2
        else:
            total = full(term, axis=1, keepdims=True)
        lane = jax.lax.broadcasted_iota(jnp.int32, term.shape[:2], 1)
        return jnp.where(expand_trailing(lane == 0, term.ndim), total, term)
    for k in range(op_flag):
        d = 1 << k
        if d >= term.shape[1]:
            break               # every shifted lane is pad: a no-op step
        shifted = shift_lanes(term, d, identity)
        seg_shift = shift_lanes(seg, d, SEG_PAD)
        mask = expand_trailing(seg == seg_shift, term.ndim)
        term = jnp.where(mask, op(term, shifted), term)
    return term
