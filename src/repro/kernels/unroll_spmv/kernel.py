"""Pallas kernel ladder for Intelligent-Unroll stage A.

One ``pallas_call`` per launch — a pattern class in per-class mode, or the
whole vload section in fused mode (the grid spans every vload block).  Per
block the kernel

  1. reads the launch's ``ls`` windows of each gathered array as lane
     tiles — the window *index* is runtime data (``window_ids`` in SMEM):
     a dynamic row load out of the view held in VMEM (resident form) or a
     dynamic tile DMA from HBM pipelined across grid steps (per-tile
     form).  This is the paper's ``vload`` group replacing the
     per-element ``gather``.  In fused mode ``ls`` is the section-wide
     max: slots beyond a block's own window count repeat the last valid
     window id (a legal load, never selected by the lane permutation).
  2. applies the static per-lane permutation + select: an in-register
     lane gather per window tile merged by a select chain on the lane's
     slot (paper Fig. 6: permutation + select instructions),
  3. evaluates the seed's combine expression on the lane vectors,
  4. runs ``op_flag`` masked shift-reduce steps (paper Fig. 5) so each
     segment head lane holds the segment total.  In fused ``mixed`` mode a
     second scalar-prefetched per-block flag selects the architecture-
     native full reduction for single-segment blocks — bitwise-identical
     to the per-class launch of the same block (DESIGN.md §3).

Outputs the post-reduce lane vector of each block; the merged write-back
(Fig. 4) happens outside (stage B) on the compressed head stream.  On the
TPU every form gives the same bits at any slab height.  In interpret mode
a single-segment block's native reduction is a lane butterfly whose lane
0 is the XLA emitter's pairwise halving tree, and float terms are rounded
before the ladder (``common.round_term``), so there too every form is
bitwise equal to the others, and to the jax backend.

Rank polymorphism (DESIGN.md §13): gathered views may carry trailing lane
axes — ``(W, N, D)`` for SpMM rows of B — which ride through the window
DMAs, the lane permute and the shift ladder unchanged; lane metadata
(slot/offset/segment) stays 2-D and broadcasts, the same
``_expand_trailing`` rule the XLA emitter applies.

Four lowering forms share the ladder body:

  * ``resident_stage_a`` — TPU window form for views that fit VMEM
    (``resident_steps``): the whole view in VMEM, a window a dynamic row
    load, a fixed granule of blocks per grid step.  Also the portable
    ``interpret=True`` CI form.
  * ``class_stage_a`` — TPU window form for larger views
    (``PrefetchScalarGridSpec``, one block per grid step, each window a
    tile DMA; ``meta_prefetch`` widens the metadata DMA tiles).
  * ``coalesced_stage_a`` — the dense-slice form for
    ``ir.coalesce_gathers`` launches: per block one unaligned slice of
    ``lane_width`` elements, built from the two aligned lane tiles that
    hold it (rotated by ``start % N`` and merged by a lane mask), plus a
    static in-tile permute — no per-element gather at all (the paper's
    gather→vector-load rewrite, §6).  ``rows_per_step`` blocks share one
    grid step.
  * ``gpu_stage_a`` — Triton form: no scalar prefetch exists there, so
    window tiles are fetched with in-kernel dynamic ``pl.ds`` loads from
    the full view; ``rows_per_step`` rows per program.

A fifth kernel, ``fallback_stage_a``, runs the gather fallback's ladder
and full reduce alone, for lanes with trailing axes: the XLA gather and
combine give its term, and each block passes through VMEM once, lanes
on the sublanes (``fallback_rows``).

VMEM budget: the resident form holds the launch's gathered views whole
while their footprint under Mosaic's (8, 128) tiling is at most
``RESIDENT_VIEW_BYTES`` (32 MiB: x of 8e6 float32 rows at N = 128), plus
double-buffered ``(R, N)`` step blocks and ``(ls, 8, N, ...)`` window
scratch within ``RESIDENT_STEP_BYTES``; ``vmem_limit_bytes`` is raised to
cover both.  A step's window-id and flag blocks sit in SMEM, within twice
``PREFETCH_WORDS``.  The choice reads only the bytes of the views and of
a step.
The per-tile form, and the coalesced form, hold
(ls * n_gathered + n_elementwise + 4) lane tiles of N*prod(trailing)
words a step — a few KB at N = 128 — and nothing of the size of the
gathered array.  Their per-block operands are laid out with the block
axis in front of two whole dims (``(W, 1, N, ...)`` views,
``(Bc // p, p, N)`` metadata), so each block's last two dims equal the
array's, which the tiling rule accepts at any ``p``; their
scalar-prefetched operands are chunked to fit SMEM (``PREFETCH_WORDS``).
"""
from __future__ import annotations

import functools
import math
from typing import Callable

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.seed import reduce_identity_for
from repro.kernels import common


def _largest_divisor(b: int, r: int) -> int:
    """Largest step size <= r that divides b (>= 1) — kernel params are
    upper bounds; the realized value keeps the grid exact so no block is
    ever padded or dropped (bitwise-stable across any requested value)."""
    r = max(1, min(int(r), max(b, 1)))
    while b % r:
        r -= 1
    return r


# Scalar-prefetched operands live in SMEM (1 MiB on v5e, shared with the
# kernel's own scalars).  A launch whose per-block prefetch words exceed
# this budget runs as a sequence of equal chunks of whole grid steps (one
# ``pallas_call`` inside a ``lax.map``) plus one tail call — blocks are
# independent, so no block's reduction tree changes.
PREFETCH_WORDS = 1 << 15

# The resident window form holds every gathered view of a launch in VMEM
# (128 MiB on v5e) while its footprint under Mosaic's tiling stays within
# RESIDENT_VIEW_BYTES; the per-step buffers get up to RESIDENT_STEP_BYTES
# more, so a kernel asks for at most 72 MiB.
RESIDENT_VIEW_BYTES = 32 << 20
RESIDENT_STEP_BYTES = 32 << 20
# A group's window-row copies are unrolled up to this many (static sublane
# stores, 13% less kernel time than a loop over the rows on HPCG-104's ls
# of 9, on v5e); a wider fused section loops over its rows instead, which
# keeps the program small.
UNROLL_COPIES = 128


def _vmem_bytes(shape: tuple, dtype) -> int:
    """VMEM footprint of an array under Mosaic's tiling: the last two
    dims pad to (sublane tile, 128 lanes); leading dims multiply."""
    itemsize = jnp.dtype(dtype).itemsize
    shape = (1,) * max(0, 2 - len(shape)) + tuple(shape)
    sub = 8 * max(1, 4 // itemsize)
    rows = -(-shape[-2] // sub) * sub
    lanes = -(-shape[-1] // 128) * 128
    return math.prod(shape[:-2]) * rows * lanes * itemsize


def _chunk_blocks(bc: int, words_per_block: int, step: int) -> int:
    """Blocks per chunk: a multiple of ``step`` whose prefetch fits
    :data:`PREFETCH_WORDS` (>= ``step``), or ``bc`` when all of it does."""
    c = max(step, PREFETCH_WORDS // max(words_per_block, 1) // step * step)
    return bc if c >= bc else c


def _chunked(call, bc: int, chunk: int, per_block: list, widths: list):
    """Run ``call(grid_blocks, base, *prefetch) -> (grid_blocks, ...)``
    over ``[0, bc)`` in chunks of ``chunk`` blocks.  ``per_block`` are the
    flat scalar-prefetch arrays (``widths[i]`` words per block); each call
    sees only its chunk's slice of them, plus its first block ``base``."""
    def run(n_blocks, base):
        pre = [jax.lax.dynamic_slice(a, (base * w,), (n_blocks * w,))
               for a, w in zip(per_block, widths)]
        return call(n_blocks, jnp.reshape(base, (1,)).astype(jnp.int32),
                    *pre)
    n_full, tail = divmod(bc, chunk)
    if n_full == 1 and tail == 0:
        return run(bc, jnp.int32(0))
    parts = []
    if n_full:
        full = jax.lax.map(lambda i: run(chunk, i * chunk),
                           jnp.arange(n_full, dtype=jnp.int32))
        parts.append(full.reshape((n_full * chunk,) + full.shape[2:]))
    if tail:
        parts.append(run(tail, jnp.int32(n_full * chunk)))
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, 0)


def _combine_lanes(win_vals: dict, elem_vals: dict, combine: Callable,
                   seg: jnp.ndarray, op: int, mixed, reduce: str, zero,
                   side: int = 0):
    """Shared ladder tail on ``(rows, N, ...)`` lane slabs, one block per
    row: broadcast elementwise lanes up to the gathered rank (§8), combine,
    shift-reduce, and resolve the fused-mixed native-reduction select.
    ``mixed`` is the per-block flag (a traced scalar, or a ``(rows, N)``
    slab holding each row's flag) or None.  ``zero`` is None on the chip;
    in interpret mode it is a runtime 0 for :func:`common.round_term`
    (the min of a window id or slice start, never negative, and 0), and
    the full reduction runs as the halving-tree butterfly.  ``side`` is
    ``D`` where the gathered lanes come on their side (:func:`_permute`):
    the metadata is repeated to match and the result stays on its
    side."""
    if side:
        seg = common.side_meta(seg, side)
        elem_vals = {e: common.side_meta(v, side)
                     for e, v in elem_vals.items()}
        if mixed is not None and mixed.ndim:
            mixed = common.side_meta(mixed, side)
    vals = dict(win_vals)
    rank = max((v.ndim for v in vals.values()), default=2)
    for e, v in elem_vals.items():
        vals[e] = common.expand_trailing(v, rank)
    term = combine(vals)
    exact = zero is not None
    if exact:
        term = common.round_term(term, zero)
    red = common.segmented_reduce_lanes(term, seg, op, reduce,
                                        butterfly=exact)
    if mixed is not None:
        native = common.segmented_reduce_lanes(
            term, seg, common.FULL_REDUCE, reduce, butterfly=exact)
        pick = mixed != 0
        if pick.ndim:
            pick = common.expand_trailing(pick, red.ndim)
        red = jnp.where(pick, native, red)
    return red


def _side(shape: tuple) -> int:
    """The value columns ``D`` of lanes with trailing axes, 0 without."""
    return math.prod(shape[2:]) if len(shape) > 2 else 0


def _permute(tiles: list, slot, off, stream: bool):
    """One gathered array's lanes from its window tiles: the permute,
    or the first tile for ``stream``.  Tiles with trailing lane axes
    (SpMM's ``(N, D)`` lanes) are turned on their side
    (``common.to_side``) and stay so: Mosaic lowers the lane permute,
    rolls and masks in 2-D only, and on its side each value column takes
    the ops of a ``D = 1`` lane row, in the same order, which is what the
    XLA emitter computes per column."""
    d = _side(tiles[0].shape)
    if d:
        tiles = [common.to_side(t) for t in tiles]
        if not stream:
            slot, off = common.side_meta(slot, d), common.side_meta(off, d)
    return tiles[0] if stream else common.permute_tiles(tiles, slot, off)


def _rows(a: jnp.ndarray, p: int) -> jnp.ndarray:
    """``(Bc, N, ...) -> (Bc // p, p, N, ...)``: per-block lane rows laid
    out so a ``(None, p, N, ...)`` block's last two dims are whole array
    dims — legal for any ``p`` under Mosaic's (8, 128) tiling rule."""
    return a.reshape((a.shape[0] // p, p) + a.shape[1:])


def _tiles(view: jnp.ndarray) -> jnp.ndarray:
    """``(W, N, ...) -> (W, 1, N, ...)``: one lane tile per leading index,
    fetched as a ``(None, 1, N, ...)`` block (whole last two dims)."""
    return view.reshape((view.shape[0], 1) + view.shape[1:])


# ------------------------------------------------------- TPU window form
def _stage_a_body(base_ref, win_ref, flag_ref, *refs, pads: bool, **kw):
    """Kernel body. ``refs`` layout:
    [g0_win0..g0_win{ls-1}, g1_win0.., ...] + [elem...] +
    [slot, offset, seg] + [out].  With ``pads`` the steps from
    ``base_ref[1] - base_ref[0]`` on are pads and run nothing."""
    # the step index is read out here: interpret mode binds it only at
    # the body's top level, not inside ``pl.when``
    b = pl.program_id(0)
    step = functools.partial(_stage_a_step, b, win_ref, flag_ref, *refs,
                             **kw)
    if pads:
        pl.when(b < base_ref[1] - base_ref[0])(step)
    else:
        step()


def _stage_a_step(b, win_ref, flag_ref, *refs, combine: Callable,
                  gathered: tuple, elementwise: tuple, ls: int, op: int,
                  stream: bool, mixed: bool, reduce: str, out_dtype,
                  meta_prefetch: int, interpret: bool):
    """Step ``b``'s block of the per-tile form (refs as
    :func:`_stage_a_body`)."""
    n_g = len(gathered)
    n_e = len(elementwise)
    win_refs = refs[: n_g * ls]
    elem_refs = refs[n_g * ls: n_g * ls + n_e]
    slot_ref, off_ref, seg_ref = refs[n_g * ls + n_e: n_g * ls + n_e + 3]
    out_ref = refs[-1]

    if meta_prefetch == 1:
        slot, off, seg = slot_ref[...], off_ref[...], seg_ref[...]
    else:
        # metadata arrives in (meta_prefetch, N) tiles — fewer, larger
        # DMAs; this step's row is selected dynamically inside VMEM
        i = b % meta_prefetch
        slot = slot_ref[pl.ds(i, 1)]
        off = off_ref[pl.ds(i, 1)]
        seg = seg_ref[pl.ds(i, 1)]

    vals = {}
    for gi, g in enumerate(gathered):
        tiles = [win_refs[gi * ls + k][...] for k in range(ls)]
        vals[g] = _permute(tiles, slot, off, stream)
    elem_vals = {e: elem_refs[ei][...] for ei, e in enumerate(elementwise)}
    flag = flag_ref[b] if mixed else None
    side = _side(out_ref.shape)
    red = _combine_lanes(vals, elem_vals, combine, seg, op, flag, reduce,
                         jnp.minimum(win_ref[0], 0) if interpret else None,
                         side)
    if side:
        red = common.from_side(red, out_ref.shape)
    out_ref[...] = red.astype(out_dtype)


def _resident_body(win_ref, *refs, combine: Callable, gathered: tuple,
                   elementwise: tuple, ls: int, op: int, stream: bool,
                   mixed: bool, reduce: str, out_dtype, rows: int,
                   group: int, bc: int, interpret: bool):
    """Resident-form body: ``rows`` blocks per grid step, ``group`` (8, a
    vreg of blocks) at a time.  ``win_ref`` is this step's SMEM block of
    window ids (``ls`` per block).  ``refs`` layout: [flags] (SMEM,
    ``mixed`` only) + [view_g...] (whole views in VMEM) + [elem...] +
    [slot, off] (not for ``stream``) + [seg] + [out] + [window scratch per
    gathered array] + [flag scratch] (``mixed`` only).

    Per group, each block's ``ls`` window rows are copied out of the
    resident views into ``(ls, group, N, ...)`` scratch (unrolled up to
    :data:`UNROLL_COPIES` copies), then the permute, the combine and the
    ladder run on ``(group, N, ...)`` slabs — row by row the same ops as
    the per-tile form.  The last step of a launch may
    hold fewer than ``rows`` valid blocks: its groups stop at the last
    valid one, whose window ids fill any rows past it (never written
    back).  A one-step launch of Bc blocks, Bc not a multiple of 8, runs
    its last group over rows ``[Bc - 8, Bc)``, recomputing a few rows."""
    if mixed:
        flag_ref, *refs = refs
    n_g = len(gathered)
    n_e = len(elementwise)
    view_refs = refs[:n_g]
    elem_refs = refs[n_g:n_g + n_e]
    k = n_g + n_e
    if not stream:
        slot_ref, off_ref = refs[k:k + 2]
        k += 2
    seg_ref, out_ref = refs[k:k + 2]
    scr_refs = refs[k + 2:k + 2 + n_g]
    flag_scr = refs[k + 2 + n_g] if mixed else None
    valid = jnp.minimum(rows, bc - pl.program_id(0) * rows)
    zero = jnp.minimum(win_ref[0], 0) if interpret else None
    side = _side(out_ref.shape)

    def run_group(q, carry):
        if rows % group:
            base = jnp.minimum(q * group, rows - group)
        else:
            base = pl.multiple_of(q * group, group)

        def gather_row(r, carry=None):
            i = jnp.minimum(base + r, valid - 1)
            for gi in range(n_g):
                rest = (slice(None),) * (view_refs[gi].ndim - 1)
                for j in range(ls):
                    w = win_ref[i * ls + j]
                    scr_refs[gi][(j, pl.ds(r, 1)) + rest] = \
                        view_refs[gi][(pl.ds(w, 1),) + rest]
            if mixed:
                flag_scr[pl.ds(r, 1), :] = jnp.full(
                    (1, flag_scr.shape[1]), flag_ref[i], jnp.int32)
            return carry

        if group * ls <= UNROLL_COPIES:
            for r in range(group):
                gather_row(r)
        else:
            jax.lax.fori_loop(0, group, gather_row, 0)
        sl = pl.ds(base, group)
        vals = {}
        for gi, g in enumerate(gathered):
            tiles = [scr_refs[gi][j] for j in range(ls)]
            vals[g] = _permute(tiles, None if stream else slot_ref[sl],
                               None if stream else off_ref[sl], stream)
        elem_vals = {e: elem_refs[ei][sl]
                     for ei, e in enumerate(elementwise)}
        flag = flag_scr[...] if mixed else None
        red = _combine_lanes(vals, elem_vals, combine, seg_ref[sl], op,
                             flag, reduce, zero, side)
        if side:
            red = common.from_side(red, (group,) + out_ref.shape[1:])
        out_ref[sl] = red.astype(out_dtype)
        return carry

    jax.lax.fori_loop(0, pl.cdiv(valid, group), run_group, 0)


def _resident_rows(bc: int, ls: int, mixed: bool) -> tuple[int, int]:
    """``(rows, group)`` of the resident form: blocks per grid step and
    per vreg-high slab.  A launch that fits one step runs as one step.
    Otherwise ``rows`` is the step granule: the fewest blocks that make
    whole sublane tiles of the ``(Bc, N)`` operands (8 rows, so they need
    no relayout) and whole 1024-word tiles of the 1-D int32 window ids
    (``rows * ls`` words) and flags (``rows`` words) in HBM, which the
    SMEM blocks are cut from — 1024 for an odd ``ls`` or a mixed launch."""
    rows = math.lcm(8, 1024 // math.gcd(ls, 1024), 1024 if mixed else 1)
    if bc <= rows:
        return bc, min(bc, 8)
    return rows, 8


def resident_steps(gathered_views: dict, *, blocks: int, ls: int,
                   mixed: bool, stream: bool, elementwise: int, out_dtype,
                   out_trailing: tuple = (), interpret: bool | None = None,
                   platform: str | None = None) -> tuple | None:
    """``(rows, group, vmem_limit_bytes)`` of the resident form for one
    window launch, or None where the launch keeps the per-tile form: the
    Triton lowering, views whose VMEM footprint passes
    :data:`RESIDENT_VIEW_BYTES`, a step whose window-id and flag blocks
    pass twice :data:`PREFETCH_WORDS` of SMEM (``ls`` over 63 at the
    1024-block granule), or a step whose VMEM buffers pass
    :data:`RESIDENT_STEP_BYTES` (wide trailing lane axes).  Decided from
    the bytes of the input alone, so every app gets the same rule; the
    Pallas stage A (``ops.make_stage_a``) picks the form by it, once per
    launch, and sets the ``engine.nnz.window_resident`` gauge from it."""
    interpret = common.resolve_interpret(interpret)
    platform = platform or jax.default_backend()
    if platform == "gpu" and not interpret:
        return None
    views = list(gathered_views.values())
    view_bytes = sum(_vmem_bytes(v.shape, v.dtype) for v in views)
    if view_bytes > RESIDENT_VIEW_BYTES:
        return None
    rows, group = _resident_rows(blocks, ls, mixed)
    # SMEM of a step: its window-id and flag blocks, double-buffered,
    # within half of v5e's 1 MiB (four per-tile prefetch chunks); at the
    # 1024-block granule that admits ``ls`` up to 63, every fused section
    # at the default window cut of N // 4
    if rows * (ls + int(mixed)) > 2 * PREFETCH_WORDS:
        return None
    # VMEM of a step: double-buffered elementwise, metadata and output
    # rows per block, and one group's window rows and flag rows
    n = views[0].shape[1]
    lane_row = _vmem_bytes((8, n), jnp.int32) // 8
    per_block = 2 * lane_row * (elementwise + (1 if stream else 3))
    per_block += 2 * _vmem_bytes((8, n) + tuple(out_trailing),
                                 out_dtype) // 8
    scratch = sum(ls * _vmem_bytes((8,) + v.shape[1:], v.dtype)
                  for v in views) + (8 * lane_row if mixed else 0)
    step = rows * per_block + scratch
    if step > RESIDENT_STEP_BYTES:
        return None
    return rows, group, view_bytes + step + (8 << 20)


def resident_stage_a(win_ids: jnp.ndarray, gathered_views: dict,
                     elem_blocks: dict, slot: jnp.ndarray,
                     off: jnp.ndarray, seg: jnp.ndarray, *, steps: tuple,
                     combine: Callable, gathered: tuple, elementwise: tuple,
                     ls: int, op: int, stream: bool, reduce: str,
                     full_flags: jnp.ndarray | None = None,
                     out_dtype=jnp.float32, out_trailing: tuple = (),
                     interpret: bool | None = None) -> jnp.ndarray:
    """The window form with the gathered views resident in VMEM (same
    contract as :func:`class_stage_a`; ``win_ids`` may be flat and
    ``steps`` is what :func:`resident_steps` gave for the launch).

    Each view is one whole-array VMEM operand, copied in once per call, so
    a window costs a dynamic row load out of VMEM instead of a tile DMA
    and a grid step.  ``R`` blocks share a grid step: their elementwise,
    metadata and output rows move as ``(R, N)`` blocks of the ``(Bc, N)``
    arrays, their window ids and flags as SMEM blocks of the step, so one
    call covers the launch with no prefetch chunking.  ``R`` is the step
    granule of :func:`_resident_rows`, fixed by the tiles of the step's
    blocks; ``meta_prefetch`` and ``rows_per_step`` have no part in this
    form.  When ``R`` does not divide Bc the last step is partial: Pallas
    clips its reads and writes to the arrays, and the kernel gathers only
    its valid blocks, so no block is padded or dropped.  Rank-2 views stay ``(W, N)``, one window a row;
    views with trailing lane axes keep their ``(W, N, ...)`` shape."""
    interpret = common.resolve_interpret(interpret)
    bc, n = seg.shape
    mixed = full_flags is not None
    rows, group, vmem_limit = steps
    views = [gathered_views[g] for g in gathered]
    body = functools.partial(_resident_body, combine=combine,
                             gathered=gathered, elementwise=elementwise,
                             ls=ls, op=op, stream=stream, mixed=mixed,
                             reduce=reduce, out_dtype=out_dtype, rows=rows,
                             group=group, bc=bc, interpret=interpret)
    z = len(out_trailing)
    in_specs = [pl.BlockSpec((rows * ls,), lambda b: (b,),
                             memory_space=pltpu.SMEM)]
    operands = [jnp.asarray(win_ids, jnp.int32).reshape(-1)]
    if mixed:
        in_specs.append(pl.BlockSpec((rows,), lambda b: (b,),
                                     memory_space=pltpu.SMEM))
        operands.append(jnp.asarray(full_flags, jnp.int32))
    for v in views:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.VMEM))
        operands.append(v)
    metas = (seg,) if stream else (slot, off, seg)
    for a in [elem_blocks[e] for e in elementwise] + list(metas):
        in_specs.append(pl.BlockSpec((rows, n), lambda b: (b, 0)))
        operands.append(a)
    scratch = [pltpu.VMEM((ls, group) + v.shape[1:], v.dtype)
               for v in views]
    if mixed:
        scratch.append(pltpu.VMEM((group, n), jnp.int32))
    return pl.pallas_call(
        body, grid=(pl.cdiv(bc, rows),), in_specs=in_specs,
        out_specs=pl.BlockSpec((rows, n) + out_trailing,
                               lambda b: (b, 0) + (0,) * z),
        out_shape=jax.ShapeDtypeStruct((bc, n) + out_trailing, out_dtype),
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem_limit),
        interpret=interpret,
    )(*operands)


def class_stage_a(win_ids: jnp.ndarray, gathered_views: dict,
                  elem_blocks: dict, slot: jnp.ndarray, off: jnp.ndarray,
                  seg: jnp.ndarray, *, combine: Callable,
                  gathered: tuple, elementwise: tuple, ls: int, op: int,
                  stream: bool, reduce: str,
                  full_flags: jnp.ndarray | None = None,
                  out_dtype=jnp.float32, out_trailing: tuple = (),
                  interpret: bool | None = None,
                  meta_prefetch: int = 1,
                  platform: str | None = None,
                  live: jnp.ndarray | None = None) -> jnp.ndarray:
    """Launch stage A for one pattern class / fused section in the
    per-tile form: one block per grid step, its ``ls`` window tiles DMA'd
    from HBM.  The form for views over the resident budget
    (:func:`resident_steps` is None); the rest run
    :func:`resident_stage_a`.

    win_ids        (Bc, ls) int32 — scalar-prefetched window indices
    gathered_views g -> (W, N, ...) lane-tile view of the dense array
    elem_blocks    e -> (Bc, N) exec-order immutable data
    slot/off/seg   (Bc, N) int32
    full_flags     (Bc,) int32 or None — per-block native-reduction flags
                   (fused mixed sections only), scalar-prefetched
    out_trailing   trailing lane axes of the combine result (§8)
    meta_prefetch  metadata DMA tile height (upper bound; realized value
                   is the largest divisor of Bc — a tuned kernel param)
    platform       lowering form override; default ``jax.default_backend()``
                   (gpu -> Triton form, otherwise TPU/interpret form)
    live           traced count of the leading blocks that are real, or
                   None (all are): the rest are a row partition's pads,
                   which no head reads (``engine._run_partitions``).  A
                   pad step keeps the last real block's indices, so no
                   DMA runs, and skips the body; its lanes stay unwritten
    returns        (Bc, N, ...) post-reduce lane matrix
    """
    interpret = common.resolve_interpret(interpret)
    platform = platform or jax.default_backend()
    if platform == "gpu" and not interpret:
        return gpu_stage_a(
            win_ids, gathered_views, elem_blocks, slot, off, seg,
            combine=combine, gathered=gathered, elementwise=elementwise,
            ls=ls, op=op, stream=stream, reduce=reduce,
            full_flags=full_flags, out_dtype=out_dtype,
            out_trailing=out_trailing, interpret=interpret)
    bc, n = slot.shape
    mixed = full_flags is not None
    if full_flags is None:
        full_flags = jnp.zeros((bc,), jnp.int32)
    p = _largest_divisor(bc, meta_prefetch)
    body = functools.partial(_stage_a_body, combine=combine,
                             gathered=gathered, elementwise=elementwise,
                             ls=ls, op=op, stream=stream, mixed=mixed,
                             reduce=reduce, out_dtype=out_dtype,
                             meta_prefetch=p, interpret=interpret,
                             pads=live is not None)
    z = len(out_trailing)
    if live is None:
        def at(b, base):
            return b
    else:
        def at(b, base):
            # a pad step keeps the chunk's last real block (its first
            # where it has none)
            return jnp.minimum(b, jnp.maximum(base[1] - base[0] - 1, 0))

    in_specs = []
    operands = []
    for g in gathered:
        view = _tiles(gathered_views[g])
        tz = view.ndim - 3
        for k in range(ls):
            def im(b, base, w, f, k=k, tz=tz):
                return (w[at(b, base) * ls + k], 0, 0) + (0,) * tz
            in_specs.append(pl.BlockSpec((None,) + view.shape[1:], im))
            operands.append(view)
    for e in elementwise:
        in_specs.append(pl.BlockSpec(
            (None, 1, n),
            lambda b, base, w, f: (base[0] + at(b, base), 0, 0)))
        operands.append(_rows(elem_blocks[e], 1))
    for meta in (slot, off, seg):
        in_specs.append(pl.BlockSpec(
            (None, p, n),
            lambda b, base, w, f, p=p: ((base[0] + at(b, base)) // p, 0,
                                        0)))
        operands.append(_rows(meta, p))

    def call(n_blocks, base, win, flags):
        if live is not None:
            base = jnp.concatenate(
                [base, jnp.reshape(live, (1,)).astype(jnp.int32)])
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n_blocks,),
            in_specs=in_specs,
            out_specs=pl.BlockSpec(
                (None, 1, n) + out_trailing,
                lambda b, base, w, f: (at(b, base), 0, 0) + (0,) * z),
        )
        out = pl.pallas_call(
            body, grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((n_blocks, 1, n) + out_trailing,
                                           out_dtype),
            interpret=interpret,
        )(base, win, flags, *operands)
        return out.reshape((n_blocks, n) + out_trailing)

    chunk = _chunk_blocks(bc, ls + 1, p)
    return _chunked(call, bc, chunk,
                    [jnp.asarray(win_ids, jnp.int32).reshape(-1),
                     jnp.asarray(full_flags, jnp.int32)], [ls, 1])


# -------------------------------------------------- dense-slice (coalesced)
def _coalesced_body(base_ref, start_ref, flag_ref, *refs, combine: Callable,
                    gathered: tuple, elementwise: tuple, op: int,
                    mixed: bool, reduce: str, out_dtype, has_off: bool,
                    rows: int, n: int, interpret: bool):
    """``refs`` layout: [lo/hi tile pair per row, per gathered array] +
    [elem...] + [off?, seg] + [out].  Per row: the two aligned lane tiles
    that hold the slice ``[st, st + N)`` are rotated by ``st % N`` and
    merged by a lane mask — the paper's unaligned vector load, built from
    aligned DMAs — then a static in-tile permute when the run is strided
    (``local_offset``), then the shared ladder."""
    del base_ref                 # consumed by the index maps only
    n_g = len(gathered)
    n_e = len(elementwise)
    tile_refs = refs[:2 * rows * n_g]
    rest = refs[2 * rows * n_g:]
    elem_refs = rest[:n_e]
    off_ref = rest[n_e] if has_off else None
    seg_ref = rest[n_e + int(has_off)]
    out_ref = rest[-1]
    zero_slot = jnp.zeros((1, n), jnp.int32)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, n), 1)
    zero = jnp.minimum(start_ref[0], 0) if interpret else None
    for i in range(rows):
        b = pl.program_id(0) * rows + i
        rem = start_ref[b] % n
        shift = (n - rem) % n
        vals = {}
        for gi, g in enumerate(gathered):
            lo = tile_refs[(gi * rows + i) * 2][...]
            hi = tile_refs[(gi * rows + i) * 2 + 1][...]
            keep = common.expand_trailing(lane < n - rem, lo.ndim)
            tile = jnp.where(keep, pltpu.roll(lo, shift, 1),
                             pltpu.roll(hi, shift, 1))
            if has_off:
                # strided run: permute inside the loaded tile (static
                # metadata, no memory gather)
                tile = common.permute_tiles([tile], zero_slot,
                                            off_ref[i:i + 1])
            vals[g] = tile
        elem_vals = {e: elem_refs[ei][i:i + 1]
                     for ei, e in enumerate(elementwise)}
        seg = seg_ref[i:i + 1]
        flag = flag_ref[b] if mixed else None
        red = _combine_lanes(vals, elem_vals, combine, seg, op, flag,
                             reduce, zero)
        out_ref[i:i + 1] = red.astype(out_dtype)


def coalesced_stage_a(starts: jnp.ndarray, gathered_views: dict,
                      elem_blocks: dict, local_off: jnp.ndarray | None,
                      seg: jnp.ndarray, *, combine: Callable,
                      gathered: tuple, elementwise: tuple, op: int,
                      reduce: str, full_flags: jnp.ndarray | None = None,
                      out_dtype=jnp.float32, out_trailing: tuple = (),
                      interpret: bool | None = None,
                      rows_per_step: int = 1) -> jnp.ndarray:
    """Stage A for one COALESCED launch (``ir.coalesce_gathers``).

    starts          (Bc,) int32 clamped slice bases, scalar-prefetched
    gathered_views  g -> (W, N, ...) lane-tile view — the same padded view
                    the window form reads (``eng._pad_gathered``)
    local_off       (Bc, N) int32 in-tile permute, or None for identity runs
    rows_per_step   blocks per grid step (upper bound; realized value is
                    the largest divisor of Bc — a tuned kernel param)

    Nothing of the size of the view is held in VMEM: each row DMAs the two
    aligned tiles ``st // N`` and ``st // N + 1`` (clamped to the last
    tile, which only an aligned ``st`` can reach, and then it is masked
    out).  The legality/bitwise argument is the coalesce pass's own
    (DESIGN.md §8/§13): the rotated pair covers ``[base, base + N)`` of
    the same padded view the window path reads, and every lane selects
    the identical word the gather fetched.
    """
    interpret = common.resolve_interpret(interpret)
    bc, n = seg.shape
    mixed = full_flags is not None
    if full_flags is None:
        full_flags = jnp.zeros((bc,), jnp.int32)
    r = _largest_divisor(bc, rows_per_step)
    has_off = local_off is not None
    body = functools.partial(_coalesced_body, combine=combine,
                             gathered=gathered, elementwise=elementwise,
                             op=op, mixed=mixed, reduce=reduce,
                             out_dtype=out_dtype, has_off=has_off,
                             rows=r, n=n, interpret=interpret)
    z = len(out_trailing)
    in_specs = []
    operands = []
    for g in gathered:
        view = _tiles(gathered_views[g])
        last = view.shape[0] - 1
        tz = view.ndim - 3
        for i in range(r):
            for hi in (0, 1):
                def im(b, base, s, f, i=i, hi=hi, tz=tz, last=last):
                    t = s[b * r + i] // n + hi
                    return (jnp.minimum(t, last), 0, 0) + (0,) * tz
                in_specs.append(pl.BlockSpec((None,) + view.shape[1:], im))
                operands.append(view)
    row_map = lambda b, base, s, f: (base[0] // r + b, 0, 0)  # noqa: E731
    for e in elementwise:
        in_specs.append(pl.BlockSpec((None, r, n), row_map))
        operands.append(_rows(elem_blocks[e], r))
    if has_off:
        in_specs.append(pl.BlockSpec((None, r, n), row_map))
        operands.append(_rows(jnp.asarray(local_off, jnp.int32), r))
    in_specs.append(pl.BlockSpec((None, r, n), row_map))
    operands.append(_rows(seg, r))

    def call(n_blocks, base, st, flags):
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n_blocks // r,),
            in_specs=in_specs,
            out_specs=pl.BlockSpec(
                (None, r, n) + out_trailing,
                lambda b, base, s, f: (b, 0, 0) + (0,) * z),
        )
        out = pl.pallas_call(
            body, grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct(
                (n_blocks // r, r, n) + out_trailing, out_dtype),
            interpret=interpret,
        )(base, st, flags, *operands)
        return out.reshape((n_blocks, n) + out_trailing)

    chunk = _chunk_blocks(bc, 2, r)
    return _chunked(call, bc, chunk,
                    [jnp.asarray(starts, jnp.int32),
                     jnp.asarray(full_flags, jnp.int32)], [1, 1])


# ------------------------------------------- fallback lanes (trailing D)
# The fallback kernel moves each step's term and lanes in and out
# double-buffered: its blocks a step are the most, in whole groups of 8,
# whose four buffers fit FALLBACK_STEP_BYTES.
FALLBACK_STEP_BYTES = 32 << 20
FALLBACK_KERNEL = "fallback_lanes"


def fallback_rows(term_shape: tuple, dtype, interpret: bool | None = None,
                  platform: str | None = None) -> int | None:
    """Blocks a grid step of :func:`fallback_stage_a` for a fallback
    term of ``term_shape`` ``(Bc, N, ...)``, or None where the launch
    keeps the XLA ladder: lanes without trailing axes (``D = 1``, whose
    gathered array XLA may keep in VMEM beside the reduce), a lane width
    off the 8-row sublane tile, and the Triton lowering.  Decided from
    the term's shape alone."""
    interpret = common.resolve_interpret(interpret)
    platform = platform or jax.default_backend()
    bc, n = term_shape[:2]
    if (not _side(term_shape) or n % 8
            or (platform == "gpu" and not interpret)):
        return None
    block = _vmem_bytes((n, _side(term_shape)), dtype)
    per_block = 4 * block + 2 * _vmem_bytes((8, n), jnp.int32) // 8
    rows = max(8, FALLBACK_STEP_BYTES // per_block // 8 * 8)
    return bc if bc <= rows else rows


def _ladder_bits(seg: jnp.ndarray, op: int) -> jnp.ndarray:
    """``(rows, N)`` segment ids -> ``(rows, N)`` int32 whose bit ``k`` at
    lane ``j`` says that lane ``j + 2^k`` is in lane ``j``'s segment: the
    masks of the ``op`` ladder steps, packed (the shifted ids are the
    ladder's, :data:`common.SEG_PAD` past the end)."""
    bits = jnp.zeros(seg.shape, jnp.int32)
    for k in range(op):
        same = seg == common.shift_lanes(seg, 1 << k, common.SEG_PAD)
        bits = bits | (same.astype(jnp.int32) << k)
    return bits


def _shift_rows(t: jnp.ndarray, d: int) -> jnp.ndarray:
    """``out[j] = t[j + d]`` for ``j < N - d``; the last ``d`` rows wrap
    round (a sublane rotation) and no result reads them."""
    return pltpu.roll(t, t.shape[0] - d, 0)


def _rows_ladder(t: jnp.ndarray, bits: jnp.ndarray, op: int, fn):
    """The ladder of one ``(N, D)`` block, lanes on the rows: step ``k``
    combines row ``j`` with row ``j + 2^k`` where bit ``k`` of
    ``bits[j]`` (an ``(N, 1)`` column) is set — ``segmented_reduce``'s
    step, combine for combine.  A wrapped row is never selected: the
    ladder's mask is false for the last ``2^k`` rows."""
    for k in range(op):
        t = jnp.where(((bits >> k) & 1) != 0, fn(t, _shift_rows(t, 1 << k)),
                      t)
    return t


def _rows_total(t: jnp.ndarray, fn, identity) -> jnp.ndarray:
    """``(N, D)`` -> ``(1, D)``, ``N`` a multiple of 8: the full reduction
    of a block by ``engine._halving_tree``'s pairwise order, combine for
    combine.  A rotate-and-combine butterfly inside each 8-row tile (its
    first row then holds the tile's tree; no wrapped row reaches it),
    then halving over the tiles, whose pairs are the tree's next levels
    (an odd count takes the identity, as the tree's padding does)."""
    for d in (1, 2, 4):
        t = fn(t, _shift_rows(t, d))
    tiles = [t[r:r + 8] for r in range(0, t.shape[0], 8)]
    while len(tiles) > 1:
        if len(tiles) % 2:
            tiles.append(jnp.full(tiles[0].shape, identity, t.dtype))
        tiles = [fn(a, b) for a, b in zip(tiles[0::2], tiles[1::2])]
    return tiles[0][:1]


def _fallback_body(base_ref, flag_ref, seg_ref, term_ref, out_ref, *,
                   op: int, mixed: bool, reduce: str, rows: int,
                   n_blocks: int):
    """A step of ``rows`` blocks, 8 at a time: their ladder masks from one
    ``(8, N)`` tile of segment ids, turned to ``(N, 8)`` columns, then per
    block either the full reduce (``op`` is FULL_REDUCE, or the block's
    flag is set) or the ladder, on the ``(N, D)`` block in VMEM with the
    lanes on the sublanes.  Rows past the launch's last block run
    nothing."""
    del base_ref                 # consumed by the flags' chunking only
    fn, _, _ = common.REDUCE_FNS[reduce]
    identity = reduce_identity_for(reduce, term_ref.dtype)
    step = pl.program_id(0)
    valid = jnp.minimum(rows, n_blocks - step * rows)
    group = min(rows, 8)

    def run_group(q, carry):
        if rows % group:
            base = jnp.minimum(q * group, rows - group)
        else:
            base = pl.multiple_of(q * group, group)
        cols = None
        if op != common.FULL_REDUCE:
            cols = _ladder_bits(seg_ref[pl.ds(base, group)], op).T

        for g in range(group):
            i = base + g

            def full(i=i):
                t = term_ref[i]
                out_ref[i] = t
                out_ref[i, 0:1] = _rows_total(t, fn, identity)

            def ladder(i=i, g=g):
                out_ref[i] = _rows_ladder(term_ref[i], cols[:, g:g + 1],
                                          op, fn)

            def block(i=i):
                if op == common.FULL_REDUCE:
                    full()
                elif not mixed:
                    ladder()
                else:
                    flag = flag_ref[step * rows + i] != 0
                    pl.when(flag)(full)
                    pl.when(jnp.logical_not(flag))(ladder)

            pl.when(i < valid)(block)
        return carry

    jax.lax.fori_loop(0, pl.cdiv(valid, group), run_group, 0)


def fallback_stage_a(term: jnp.ndarray, seg: jnp.ndarray, *, op: int,
                     reduce: str, rows: int,
                     full_flags: jnp.ndarray | None = None,
                     interpret: bool | None = None) -> jnp.ndarray:
    """The gather fallback's reduce for lanes with trailing axes, in one
    pass: ``(Bc, N, ...)`` term (the XLA gather and combine's, rounded)
    -> the same lanes :func:`repro.core.engine.segmented_reduce` gives,
    with ``full_flags`` (``(Bc,)``, or None) selecting the full reduce
    per block, bit for bit.

    Each block moves through VMEM once, read and written, ``rows`` blocks
    a grid step (:func:`fallback_rows`).  The block keeps its HBM layout,
    lanes on the sublanes and the value columns on the lanes, so the
    ladder's shifts are sublane rotations and no lane is transposed; only
    the ``(8, N)`` segment ids of each 8 blocks turn to columns.  The full
    reduce is ``engine._halving_tree``'s order, on the chip as in
    interpret mode.  The flags are scalar-prefetched in chunks of whole
    steps that fit SMEM, as the per-tile window form's are.  A last step
    past the end of the launch runs its valid blocks; Pallas clips its
    reads and writes."""
    interpret = common.resolve_interpret(interpret)
    bc, n = seg.shape
    shape = term.shape
    lanes = term.reshape(bc, n, _side(shape))
    mixed = full_flags is not None
    if full_flags is None:
        full_flags = jnp.zeros((bc,), jnp.int32)
    d = lanes.shape[2]

    def call(n_blocks, base, flags):
        body = functools.partial(_fallback_body, op=op, mixed=mixed,
                                 reduce=reduce, rows=rows,
                                 n_blocks=n_blocks)
        at = lambda b, base, f: (base[0] // rows + b, 0)  # noqa: E731
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(pl.cdiv(n_blocks, rows),),
            in_specs=[pl.BlockSpec((rows, n), at),
                      pl.BlockSpec((rows, n, d), lambda *a: at(*a) + (0,))],
            out_specs=pl.BlockSpec((rows, n, d),
                                   lambda b, base, f: (b, 0, 0)),
        )
        step = 4 * rows * _vmem_bytes((n, d), term.dtype)
        return pl.pallas_call(
            body, grid_spec=grid_spec, name=FALLBACK_KERNEL,
            out_shape=jax.ShapeDtypeStruct((n_blocks, n, d), term.dtype),
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=step + (8 << 20)),
            interpret=interpret,
        )(base, flags, seg, lanes)

    chunk = _chunk_blocks(bc, 1, rows)
    out = _chunked(call, bc, chunk, [jnp.asarray(full_flags, jnp.int32)],
                   [1])
    return out.reshape(shape)


# --------------------------------------------------------- GPU (Triton)
def _gpu_body(*refs, combine: Callable, gathered: tuple,
              elementwise: tuple, ls: int, op: int, stream: bool,
              mixed: bool, reduce: str, out_dtype, rows: int,
              interpret: bool):
    """``refs`` layout: [win, flag] + [view_g...] + [elem...] +
    [slot, off, seg] + [out].  No scalar prefetch on Triton: window tiles
    are fetched with dynamic ``pl.ds`` row loads from the full view."""
    win_ref, flag_ref = refs[0], refs[1]
    n_g = len(gathered)
    n_e = len(elementwise)
    view_refs = refs[2: 2 + n_g]
    elem_refs = refs[2 + n_g: 2 + n_g + n_e]
    slot_ref, off_ref, seg_ref = refs[2 + n_g + n_e: 2 + n_g + n_e + 3]
    out_ref = refs[-1]
    zero = jnp.minimum(win_ref[0, 0], 0) if interpret else None
    for i in range(rows):
        vals = {}
        for gi, g in enumerate(gathered):
            view = view_refs[gi]
            rest = (slice(None),) * (view.ndim - 1)
            tiles = [view[(pl.ds(win_ref[i, k], 1),) + rest]
                     for k in range(ls)]
            vals[g] = tiles[0] if stream else common.permute_tiles(
                tiles, slot_ref[i:i + 1], off_ref[i:i + 1])
        elem_vals = {e: elem_refs[ei][i:i + 1]
                     for ei, e in enumerate(elementwise)}
        flag = flag_ref[i] if mixed else None
        red = _combine_lanes(vals, elem_vals, combine, seg_ref[i:i + 1],
                             op, flag, reduce, zero)
        out_ref[i:i + 1] = red.astype(out_dtype)


def gpu_stage_a(win_ids: jnp.ndarray, gathered_views: dict,
                elem_blocks: dict, slot: jnp.ndarray, off: jnp.ndarray,
                seg: jnp.ndarray, *, combine: Callable, gathered: tuple,
                elementwise: tuple, ls: int, op: int, stream: bool,
                reduce: str, full_flags: jnp.ndarray | None = None,
                out_dtype=jnp.float32, out_trailing: tuple = (),
                interpret: bool | None = None,
                rows_per_step: int = 1) -> jnp.ndarray:
    """Triton lowering of :func:`class_stage_a` (same contract).  Used
    when ``jax.default_backend() == "gpu"``; also runs under
    ``interpret=True`` so CPU CI covers the form."""
    interpret = common.resolve_interpret(interpret)
    bc, n = slot.shape
    mixed = full_flags is not None
    if full_flags is None:
        full_flags = jnp.zeros((bc,), jnp.int32)
    r = _largest_divisor(bc, rows_per_step)
    body = functools.partial(_gpu_body, combine=combine, gathered=gathered,
                             elementwise=elementwise, ls=ls, op=op,
                             stream=stream, mixed=mixed, reduce=reduce,
                             out_dtype=out_dtype, rows=r,
                             interpret=interpret)
    in_specs = [pl.BlockSpec((r, ls), lambda b: (b, 0)),
                pl.BlockSpec((r,), lambda b: (b,))]
    operands = [jnp.asarray(win_ids, jnp.int32), full_flags]
    for g in gathered:
        view = gathered_views[g]
        in_specs.append(pl.BlockSpec(
            view.shape, lambda b, z=view.ndim: (0,) * z))
        operands.append(view)
    for e in elementwise:
        in_specs.append(pl.BlockSpec((r, n), lambda b: (b, 0)))
        operands.append(elem_blocks[e])
    for meta in (slot, off, seg):
        in_specs.append(pl.BlockSpec((r, n), lambda b: (b, 0)))
        operands.append(meta)
    fn = pl.pallas_call(
        body,
        grid=(bc // r,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (r, n) + out_trailing,
            lambda b: (b, 0) + (0,) * len(out_trailing)),
        out_shape=jax.ShapeDtypeStruct((bc, n) + out_trailing, out_dtype),
        interpret=interpret,
    )
    return fn(*operands)
