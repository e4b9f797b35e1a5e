"""Plan execution engine — backend emitters over the lowered code tree.

Lowering decisions live in :mod:`repro.core.ir` (the information-code
tree: fuse_sections -> choose_stage_b -> coalesce_gathers, DESIGN.md §8);
this module only *emits* runnable programs by walking the lowered tree.

Backends:
  * ``jax``    — pure-XLA execution of the specialized plan (class-sorted
    blocks, tile-granular window loads, log-step segmented reduce).  This is
    the portable path and the one used inside the distributed stack.
  * ``pallas`` — the Pallas TPU kernels in ``repro.kernels``; validated with
    ``interpret=True`` on CPU, targeted at TPU VMEM/MXU.
  * ``segsum`` — CPU-optimal single segment-sum form.
  * ``reference`` — direct scatter oracle (un-optimized seed semantics).
  * ``baseline_gather`` — what a conservative compiler emits: native gather
    + full scatter-add, no pattern specialization (the paper's icc baseline
    analogue; used by the benchmarks).

Execution modes (``fused`` flag, default True):
  * **fused** — the default hot path.  All vload classes collapse into ONE
    launch (one ``pallas_call`` / one XLA segment) padded to the plan-wide
    max window count with a shift-reduce ladder covering the longest run,
    plus one batched XLA segment for all gather-fallback blocks: at most two
    launches per call regardless of ``num_classes``, and the write-back runs
    over a precomputed dense head-row buffer (no flat B*N re-gather).
    Legality argument in DESIGN.md §3.
  * **per-class** (``fused=False``) — the paper's one-launch-per-pattern-
    class form (kept for A/B benchmarking and as the bitwise oracle of the
    fused path).

``coalesce=True`` additionally runs the gather-coalescing pass
(:func:`repro.core.ir.coalesce_gathers`): launches whose blocks hold
contiguous/strided gather-index runs are re-lowered to dense unaligned
``lax.dynamic_slice`` vector loads — bitwise-identical by construction.

Stage A and stage B are **rank-polymorphic** over a trailing lane axis
(DESIGN.md §8): gathered arrays may carry extra trailing dims (SpMM's
``x`` is ``(data_len, D)``), per-nnz elementwise arrays are broadcast with
trailing singleton axes, and the ladder/write-back reduce along the lane
axis only — SpMM is literally the SpMV program with a 2-D lane.

The executor factory performs the Data Transfer step once (physical nnz
reorder into class-sorted, in-block-sorted order) and returns a jitted
callable over the *mutable* inputs only — mirroring the paper's split of
immutable access arrays (analyzed, reordered) vs mutable data (touched every
call).

Device-resident iteration (DESIGN.md §7): :func:`make_sweeper` returns the
same sweep *body* un-jitted, safe to embed inside ``lax.while_loop`` /
``fori_loop`` fixpoint drivers — every host constant is staged to the
device once at build time, so re-tracing the body inside a loop uploads
nothing.  :func:`make_executor` jits exactly that body (the jitted
``run`` exposes it as ``run.sweep_body``), so a resident loop iteration
is byte-for-byte the program a standalone call runs; ``donate=True``
additionally jit-donates ``out_init`` so back-to-back fixpoint sweeps
double-buffer in place instead of allocating a fresh output per call.
"""
from __future__ import annotations

import functools
import math
import time
from typing import Mapping

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import feature_table as ft
from repro.core import ir
from repro.core.mesh import dp_axes
from repro.core.plan import BlockPlan
from repro.core.seed import (CodeSeed, reduce_identity_for,
                             reference_execute)
from repro.obs import metrics as _metrics
from repro.obs import trace as _trace

# lowering helpers re-exported for callers that inspect launch lists
# (benchmarks, tune.cost, kernels.unroll_spmv) — implementations in ir.py
fused_sections = ir.fused_sections
fused_xla_classes = ir.fused_xla_classes
section_full_mask = ir.section_full_mask
_FUSE_MIN_CLASSES = ir.FUSE_MIN_CLASSES

_SEG_PAD = -(2 ** 30)

# the device scope (repro.obs.trace) each launch kind's ops run under
_LAUNCH_SCOPES = {ir.FALLBACK: _trace.SCOPE_FALLBACK,
                  ir.WINDOW: _trace.SCOPE_WINDOW,
                  ir.STREAM: _trace.SCOPE_WINDOW,
                  ir.COALESCED: _trace.SCOPE_COALESCED}


def launch_scope(launch: ir.Launch):
    """``jax.named_scope`` of a launch's kind: its ops' device time is
    read by that name (DESIGN.md §11)."""
    return jax.named_scope(_LAUNCH_SCOPES[launch.gather])


def _padded_view_len(data_len: int, n: int) -> int:
    return max(1, -(-data_len // n)) * n


def _expand_trailing(a: jnp.ndarray, ndim: int) -> jnp.ndarray:
    """Append trailing singleton axes until ``a.ndim == ndim`` — the §8
    rank rule: lane metadata (segment ids, offsets) and per-nnz
    elementwise arrays broadcast over any trailing lane axes."""
    if a.ndim >= ndim:
        return a
    return a.reshape(a.shape + (1,) * (ndim - a.ndim))


def reorder_elementwise(plan: BlockPlan, arr: np.ndarray | jnp.ndarray,
                        identity: float | None = None,
                        reduce: str = "add") -> jnp.ndarray:
    """Data Transfer: physically reorder an nnz-aligned immutable array into
    exec order (class-sorted blocks, in-block write-sorted), padding with the
    reduce identity *in the array's dtype* (DESIGN.md §3a — a float ``inf``
    pad on an int array is an invalid cast). Returns (B, N)."""
    arr = jnp.asarray(arr)
    if identity is None:
        identity = reduce_identity_for(reduce, arr.dtype)
    padded = jnp.concatenate(
        [arr, jnp.full((1,) + arr.shape[1:], identity, arr.dtype)])
    flat = padded[jnp.asarray(np.minimum(plan.flat_perm, plan.nnz))]
    return flat.reshape(plan.num_blocks, plan.lane_width)


def _pad_flat(plan: BlockPlan, g: jnp.ndarray) -> jnp.ndarray:
    """Pad a gathered dense array to a whole number of lane tiles (flat
    view) — the address space of both the window and the coalesced-slice
    loads."""
    total = _padded_view_len(plan.data_len, plan.lane_width)
    pad = total - g.shape[0]
    if pad:
        g = jnp.pad(g, ((0, pad),) + ((0, 0),) * (g.ndim - 1))
    return g


def _pad_gathered(plan: BlockPlan, g: jnp.ndarray) -> jnp.ndarray:
    """Pad a gathered dense array to a whole number of lane tiles and view
    it as (num_windows, N, ...) — the tile-granular unit of the vload
    path."""
    n = plan.lane_width
    gp = _pad_flat(plan, g)
    return gp.reshape((gp.shape[0] // n, n) + g.shape[1:])


def combine_rounded(seed: CodeSeed, vals: dict,
                    zero: jnp.ndarray) -> jnp.ndarray:
    """The seed's combine, rounded to its dtype before any reduction
    reads it.

    XLA-CPU always lets LLVM contract ``a * b + c`` into one fused
    multiply-add, and which products meet which sums inside one fusion
    depends on the surrounding program — so two programs that run the
    same combine and the same pinned reduce tree (fused vs per-class,
    sharded vs single-device) could round differently.  Passing a float
    term's bits through an integer XOR with ``zero`` — a runtime operand
    (always 0) the compiler cannot fold — ends every product at a
    rounded word, so every bitwise guarantee in this engine holds in
    every surrounding program.  Integer terms pass through."""
    term = seed.combine(vals)
    if not jnp.issubdtype(term.dtype, jnp.floating):
        return term
    bits = jnp.dtype(f"int{8 * term.dtype.itemsize}")
    return jax.lax.bitcast_convert_type(
        jax.lax.bitcast_convert_type(term, bits) ^ zero.astype(bits),
        term.dtype)


def segmented_reduce(term: jnp.ndarray, seg: jnp.ndarray, op_flag: int,
                     reduce: str, identity: float | None = None
                     ) -> jnp.ndarray:
    """§5: log-step masked shift-reduce.  ``op_flag`` static steps; runs are
    consecutive (the Data Transfer sort guarantees it); after the loop each
    segment's *head lane* holds the full segment reduction.  The shift pad
    identity is derived from ``term.dtype`` unless given (DESIGN.md §3a).

    Rank-polymorphic: ``term`` is ``(B, N)`` or ``(B, N, ...)`` with any
    trailing lane axes; ``seg`` is always ``(B, N)`` and broadcasts."""
    from repro.core.seed import REDUCE_OPS
    op, _ = REDUCE_OPS[reduce]
    if identity is None:
        identity = reduce_identity_for(reduce, term.dtype)
    if op_flag == ft.FULL_REDUCE:
        # paper: single-segment block -> architecture-native reduction.  On
        # XLA a native row reduce (jnp.sum) does not pin its accumulation
        # order across different surrounding programs, which would break
        # the fused-vs-per-class bitwise guarantee — so the XLA form is an
        # explicit pairwise halving tree: a fixed combine order in every
        # program (elementwise ops cannot be reassociated by XLA), 2N work
        # instead of the ladder's N log N, and for power-of-two widths its
        # root is bit-identical to the masked ladder's head lane.  The
        # Pallas kernel keeps the true native reduction on the chip; in
        # interpret mode it runs this tree as a lane butterfly
        # (``common.segmented_reduce_lanes``), combine for combine.
        total = _halving_tree(term, op, identity)
        return term.at[:, 0].set(total[:, 0])
    trailing = ((0, 0),) * (term.ndim - 2)
    for k in range(op_flag):
        d = 1 << k
        shifted = jnp.pad(term[:, d:], ((0, 0), (0, d)) + trailing,
                          constant_values=identity)
        seg_shift = jnp.pad(seg[:, d:], ((0, 0), (0, d)),
                            constant_values=_SEG_PAD)
        mask = _expand_trailing(seg == seg_shift, term.ndim)
        term = jnp.where(mask, op(term, shifted), term)
    return term


def _halving_tree(total: jnp.ndarray, op, identity) -> jnp.ndarray:
    """(B, N, ...) -> (B, 1, ...) full reduction by pairwise halving along
    axis 1 — a FIXED combine order in every surrounding program
    (elementwise ops cannot be reassociated by XLA), which is what every
    bitwise guarantee in this engine leans on; see the FULL_REDUCE note in
    :func:`segmented_reduce`."""
    trailing = ((0, 0),) * (total.ndim - 2)
    while total.shape[1] > 1:
        if total.shape[1] % 2:
            total = jnp.pad(total, ((0, 0), (0, 1)) + trailing,
                            constant_values=identity)
        total = op(total[:, 0::2], total[:, 1::2])
    return total


def tree_sum(x: jnp.ndarray) -> jnp.ndarray:
    """Deterministic full sum of a 1-D array by pairwise halving — the same
    fixed combine order in every surrounding program (a native ``jnp.sum``
    does not pin its accumulation order across programs, which would break
    host-vs-resident bitwise parity for PageRank's dangling-mass
    reduction)."""
    if x.size == 0:
        return jnp.zeros((), x.dtype)
    return _halving_tree(x.reshape(1, -1), jnp.add, 0)[0, 0]


def state_healthy(state: jnp.ndarray, reduce: str = "add") -> jnp.ndarray:
    """Device-side scalar bool: is a fixpoint state still numerically
    healthy for its semiring? (DESIGN.md §9)

    A NaN-poisoned state can never satisfy an exact-equality convergence
    check (NaN != NaN), so without this predicate a resident
    ``while_loop`` silently burns ``max_sweeps``.  "Healthy" is
    semiring-aware: the ``min`` reduce's identity is ``+inf`` (SSSP's
    legitimate "unreachable"), so only NaN and wrong-direction infinity
    count as divergence; symmetrically for ``max``; for ``add``/``mul``
    any non-finite value is divergence.  Integer states cannot diverge —
    the check folds to a constant True at trace time, costing the int
    apps (BFS, CC) nothing."""
    if not jnp.issubdtype(state.dtype, jnp.floating):
        return jnp.bool_(True)
    if reduce == "min":
        bad = jnp.isnan(state) | jnp.isneginf(state)
    elif reduce == "max":
        bad = jnp.isnan(state) | jnp.isposinf(state)
    else:
        bad = jnp.logical_not(jnp.isfinite(state))
    return jnp.logical_not(jnp.any(bad))


def _gather_launch_values(plan: BlockPlan, launch: ir.Launch, s: slice,
                          meta: Mapping[str, jnp.ndarray],
                          mutable: Mapping[str, jnp.ndarray],
                          co: dict | None) -> dict:
    """§6: produce per-lane gathered values for one launch, by its lowered
    gather idiom (fallback gather / window tiles / stream vload /
    coalesced dense slices)."""
    seed = plan.seed
    vals = {}
    if seed.gather_index is None:
        return vals
    n = plan.lane_width
    if launch.gather == ir.FALLBACK:
        gi = meta["gather_idx"][s]
        for g in seed.gathered:
            vals[g] = jnp.asarray(mutable[g])[gi]
        return vals
    if launch.gather == ir.COALESCED:
        for g in seed.gathered:
            arr = jnp.asarray(mutable[g])
            flat = _pad_flat(plan, arr)
            sizes = (n,) + arr.shape[1:]
            zeros = (jnp.int32(0),) * (arr.ndim - 1)
            tiles = jax.vmap(lambda st: jax.lax.dynamic_slice(
                flat, (st,) + zeros, sizes))(co["starts"])   # (Bc, N, ...)
            if co["off"] is None:
                vals[g] = tiles                 # contiguous run: pure slice
            else:
                vals[g] = jnp.take_along_axis(
                    tiles, _expand_trailing(co["off"], tiles.ndim), axis=1)
        return vals
    win = meta["window_ids"][s][:, :launch.ls_flag]           # (Bc, M)
    for g in seed.gathered:
        gv = _pad_gathered(plan, jnp.asarray(mutable[g]))[win]
        if launch.gather == ir.STREAM:
            vals[g] = gv[:, 0]                                # pure vload
        else:
            flat = gv.reshape((gv.shape[0], launch.ls_flag * n)
                              + gv.shape[3:])
            lane = (meta["lane_slot"][s].astype(jnp.int32) * n
                    + meta["lane_offset"][s].astype(jnp.int32))
            vals[g] = jnp.take_along_axis(
                flat, _expand_trailing(lane, flat.ndim), axis=1)
    return vals


def _stage_a_jax(plan: BlockPlan, meta, elem_exec, mutable,
                 launches: list[ir.Launch], co_meta: dict,
                 blocks: dict | None = None) -> jnp.ndarray:
    """Walk the lowered launch list; return the (B, N, ...) post-reduce
    lane matrix in exec-block order — or, with ``blocks`` (a row
    partition, :func:`_run_partitions`), the lanes of launch ``i``'s
    blocks at positions ``blocks[i]``, for the launches it names.  Mixed
    native/ladder sections never occur here — ``fuse_sections`` merges
    only equal-op classes on the XLA backend, so per-block full-reduce
    selection is a Pallas concern (``ops.make_stage_a``)."""
    seed = plan.seed
    parts = []
    for i, launch in enumerate(launches):
        co = co_meta.get(i)
        if blocks is None:
            s = slice(launch.start, launch.stop)
        elif i in blocks:
            s = launch.start + blocks[i]
            if co is not None:
                co = {k: None if v is None else v[blocks[i]]
                      for k, v in co.items()}
        else:
            continue
        with launch_scope(launch):
            vals = _gather_launch_values(plan, launch, s, meta, mutable, co)
            rank = max((v.ndim for v in vals.values()), default=2)
            for e in seed.elementwise:
                vals[e] = _expand_trailing(elem_exec[e][s], rank)
            term = combine_rounded(seed, vals, meta["zero"])
            red = segmented_reduce(term, meta["seg_ids"][s],
                                   launch.op_flag, seed.reduce)
        parts.append(red)
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)


@jax.named_scope(_trace.SCOPE_STAGE_B)
def _stage_b(plan: BlockPlan, meta, lanes: jnp.ndarray,
             out_init: jnp.ndarray, depth: int = 0) -> jnp.ndarray:
    """Merged write-back (Fig. 4): one RMW per distinct (block, row) head.
    Head values are re-gathered from the flat (B*N, ...) lane stream in
    row-sorted order, cross-block contributions to one row are combined by
    a log-step tree (deterministic float order), and the final scatter hits
    each output row at most once — XLA's unspecified accumulation order for
    duplicate scatter indices can therefore never perturb the result, which
    is what makes fused and per-class launches bitwise-comparable end to
    end (DESIGN.md §3).  ``depth`` is the static tree depth covering the
    longest run (:func:`head_write_meta`)."""
    hv = lanes.reshape((-1,) + lanes.shape[2:])[meta["head_pos_rowsorted"]]
    hv = _head_tree(hv, meta["head_row_seg"], depth, plan.seed.reduce)
    return _write_rows(out_init, meta["head_unique_rows"],
                       hv[meta["head_run_starts"]], plan.seed.reduce)


def _head_tree(hv: jnp.ndarray, seg: jnp.ndarray, depth: int,
               reduce: str) -> jnp.ndarray:
    """The write-back's log-step tree over row-sorted heads: after
    ``depth`` steps each row's first head holds the row's combined
    value.  A head combines only with heads of its own ``seg``, so any
    slice that holds whole runs gives their rows the same bits."""
    from repro.core.seed import REDUCE_OPS
    op, _ = REDUCE_OPS[reduce]
    identity = reduce_identity_for(reduce, hv.dtype)
    trailing = ((0, 0),) * (hv.ndim - 1)
    for k in range(depth):
        d = 1 << k
        shifted = jnp.pad(hv[d:], ((0, d),) + trailing,
                          constant_values=identity)
        seg_shift = jnp.pad(seg[d:], (0, d), constant_values=_SEG_PAD)
        hv = jnp.where(_expand_trailing(seg == seg_shift, hv.ndim),
                       op(hv, shifted), hv)
    return hv


def _write_rows(out: jnp.ndarray, rows: jnp.ndarray, vals: jnp.ndarray,
                reduce: str, mode=None) -> jnp.ndarray:
    """Fold each row's value into ``out`` with the reduce, one write a
    row."""
    if reduce == "add":
        return out.at[rows].add(vals, mode=mode)
    if reduce == "mul":
        return out.at[rows].multiply(vals, mode=mode)
    if reduce == "max":
        return out.at[rows].max(vals, mode=mode)
    return out.at[rows].min(vals, mode=mode)


def head_write_meta(plan: BlockPlan) -> dict:
    """Static metadata for the collision-free write-back: heads sorted by
    output row (stable in exec order), per-row run structure, and the tree
    depth covering the longest run."""
    order = np.argsort(plan.head_rows, kind="stable")
    rows_sorted = plan.head_rows[order]
    change = np.ones(rows_sorted.shape[0], dtype=bool)
    change[1:] = rows_sorted[1:] != rows_sorted[:-1]
    seg = np.cumsum(change) - 1
    counts = np.diff(np.append(np.nonzero(change)[0],
                               rows_sorted.shape[0]))
    depth = int(np.ceil(np.log2(counts.max()))) if counts.size \
        and counts.max() > 1 else 0
    return {
        "head_pos_rowsorted": jnp.asarray(plan.head_pos[order]),
        "head_row_seg": jnp.asarray(seg.astype(np.int32)),
        "head_run_starts": jnp.asarray(
            np.nonzero(change)[0].astype(np.int64)),
        "head_unique_rows": jnp.asarray(rows_sorted[change]),
        "head_tree_depth": depth,
    }


def dense_head_rows(plan: BlockPlan) -> np.ndarray:
    """(B*N,) int32: output row per exec lane for head lanes, ``out_len``
    (a discard bucket) for every other lane — the precomputed dense head
    buffer of the fused write-back."""
    rows = np.full(plan.num_blocks * plan.lane_width, plan.out_len, np.int64)
    rows[plan.head_pos] = plan.head_rows
    return rows.astype(np.int32)


@jax.named_scope(_trace.SCOPE_STAGE_B)
def _stage_b_dense(plan: BlockPlan, meta, lanes: jnp.ndarray,
                   out_init: jnp.ndarray, depth: int = 0) -> jnp.ndarray:
    """Fused write-back: scatter the whole post-reduce lane stream through
    the dense head-row buffer (non-head lanes land in the discard bucket at
    ``out_len``), avoiding the flat B*N re-gather of :func:`_stage_b`."""
    rows = meta["lane_rows"]
    flat = lanes.reshape((-1,) + lanes.shape[2:])
    seed = plan.seed
    n_out = plan.out_len
    shape = (n_out + 1,) + flat.shape[1:]
    identity = reduce_identity_for(seed.reduce, flat.dtype)
    if seed.reduce == "add":
        acc = jnp.zeros(shape, flat.dtype).at[rows].add(flat)
        return out_init + acc[:n_out]
    if seed.reduce == "mul":
        acc = jnp.ones(shape, flat.dtype).at[rows].multiply(flat)
        return out_init * acc[:n_out]
    if seed.reduce == "max":
        acc = jnp.full(shape, identity, flat.dtype).at[rows].max(flat)
        return jnp.maximum(out_init, acc[:n_out])
    acc = jnp.full(shape, identity, flat.dtype).at[rows].min(flat)
    return jnp.minimum(out_init, acc[:n_out])


def term_struct(seed, mutable, elem_dtypes) -> tuple:
    """``(dtype, trailing axes)`` of the seed's combine for these inputs:
    the lanes' structure — int32 for the graph semirings, ``(D,)``
    trailing for SpMM (DESIGN.md §8)."""
    specs = {}
    for g in seed.gathered:
        a = jnp.asarray(mutable[g])
        specs[g] = jax.ShapeDtypeStruct((1,) + a.shape[1:], a.dtype)
    rank = max((s.ndim for s in specs.values()), default=1)
    for e in seed.elementwise:
        specs[e] = jax.ShapeDtypeStruct((1,) * rank, elem_dtypes[e])
    out = jax.eval_shape(seed.combine, specs)
    return out.dtype, out.shape[1:]


def identity_out(seed, out_len: int, mutable, elem_dtypes) -> jnp.ndarray:
    """The reduce identity in the shape and dtype of a product's output:
    ``out_len`` rows with the combine's trailing axes."""
    dtype, trailing = term_struct(seed, mutable, elem_dtypes)
    return jnp.full((out_len,) + trailing,
                    reduce_identity_for(seed.reduce, dtype), dtype)


def _fills_identity(run, seed, out_len: int, elem_dtypes):
    """``run`` that takes ``out_init=None`` as the reduce identity, made
    inside the program, so a wide product holds one output on the
    device, not two.  It keeps ``run``'s name, and so the jitted
    program's."""
    @functools.wraps(run)
    def apply(c, mutable, out_init):
        if out_init is None:
            out_init = identity_out(seed, out_len, mutable, elem_dtypes)
        return run(c, mutable, out_init)
    return apply


def _lane_partitions(plan: BlockPlan, rows: ir.RowOrder | None, mutable,
                     elem_dtypes) -> ir.LanePartitions | None:
    """The row partitions this product runs in (None: one piece), from
    the bytes of its lanes; sets the ``engine.lane_partitions`` and
    ``engine.lane_bytes`` gauges (the largest partition's stream) when
    the program is traced."""
    dtype, trailing = term_struct(plan.seed, mutable, elem_dtypes)
    lane = (plan.lane_width * math.prod(trailing)
            * jnp.dtype(dtype).itemsize)
    parts = rows.partitions(lane) if rows is not None else None
    _metrics.set_gauge("engine.lane_partitions",
                       parts.count if parts else 1)
    _metrics.set_gauge("engine.lane_bytes", parts.lane_bytes if parts
                       else plan.num_blocks * lane)
    return parts


def _run_partitions(plan: BlockPlan, rows: ir.RowOrder,
                    parts: ir.LanePartitions, meta, stage_a, mutable,
                    out_init: jnp.ndarray, depth: int) -> jnp.ndarray:
    """Stage A and stage B of each row partition in turn, in one
    ``fori_loop`` (DESIGN.md §8).  ``stage_a(mutable, blocks, counts)``
    runs, of each launch ``l`` in ``blocks``, the blocks at launch
    positions ``blocks[l]``, the first ``counts[l]`` of them real, and
    returns their lanes in launch order.  Partitions are padded to equal
    sizes: no head reads a pad block, and the per-tile window kernel
    skips them (hub rows put most of a launch's blocks in a few
    partitions); pad heads and pad rows are masked, their writes
    dropped.  Each row's heads sit in one partition and run the same
    tree and the same single write as in one piece, so the result is the
    one-piece result bit for bit."""
    n = plan.lane_width
    live = [l for l, m in enumerate(parts.block_max) if m]
    offset = np.zeros(len(parts.block_max), np.int64)
    offset[live] = np.cumsum([0] + [parts.block_max[l] for l in live])[:-1]
    orders = {l: jnp.pad(meta["row_order"][rows.starts[l]:rows.starts[l]
                                            + len(rows.first[l])],
                         (0, parts.block_max[l])) for l in live}
    block_lo = jnp.asarray(parts.block_lo, jnp.int32)
    count = jnp.asarray(np.maximum(parts.block_hi - parts.block_lo, 0),
                        jnp.int32)
    # row-order stream position -> position in the partition's lanes
    base = jnp.asarray(np.asarray(rows.starts)[None, :] + parts.block_lo
                       - offset[None, :], jnp.int32)
    head_lo = jnp.asarray(parts.head_lo, jnp.int32)
    run_lo = jnp.asarray(parts.run_lo, jnp.int32)
    hmax, umax = parts.head_max, parts.run_max
    heads = {"pos": jnp.pad(meta["head_rowpos"], (0, hmax)),
             "seg": jnp.pad(meta["head_row_seg"], (0, hmax)),
             "start": jnp.pad(meta["head_run_starts"], (0, umax)),
             "row": jnp.pad(meta["head_unique_rows"], (0, umax))}
    reduce = plan.seed.reduce

    def body(k, out):
        blocks = {l: jax.lax.dynamic_slice(orders[l], (block_lo[k, l],),
                                           (parts.block_max[l],))
                  for l in live}
        lanes = stage_a(mutable, blocks, {l: count[k, l] for l in live})
        with jax.named_scope(_trace.SCOPE_STAGE_B):
            h0 = head_lo[k]
            ok = jnp.arange(hmax) < head_lo[k + 1] - h0
            q = jax.lax.dynamic_slice(heads["pos"], (h0,), (hmax,))
            r = q // n
            launch = sum((r >= s).astype(jnp.int32) for s in rows.starts[1:])
            pos = jnp.where(ok, (r - base[k][launch]) * n + q % n, 0)
            hv = lanes.reshape((-1,) + lanes.shape[2:])[pos]
            seg = jnp.where(
                ok, jax.lax.dynamic_slice(heads["seg"], (h0,), (hmax,)), -1)
            hv = _head_tree(hv, seg, depth, reduce)
            u0 = run_lo[k]
            uok = jnp.arange(umax) < run_lo[k + 1] - u0
            start = jax.lax.dynamic_slice(heads["start"], (u0,), (umax,))
            row = jax.lax.dynamic_slice(heads["row"], (u0,), (umax,))
            return _write_rows(out, jnp.where(uok, row, plan.out_len),
                               hv[jnp.where(uok, start - h0, 0)], reduce,
                               mode="drop")

    return jax.lax.fori_loop(0, parts.count, body, out_init)


def reorder_static(plan: BlockPlan, static_data: Mapping[str, np.ndarray]
                   ) -> dict:
    """Data Transfer for the seed's elementwise arrays: reorder each into
    exec order once.  The result can be shared across every executor built
    on the same plan (``make_executor(..., elem_exec=...)``) — the tuner
    measures several candidate configurations per plan and must not pay
    the physical reorder per candidate."""
    seed = plan.seed
    return {e: reorder_elementwise(plan, static_data[e], reduce=seed.reduce)
            for e in seed.elementwise}


class Sweep:
    """One sweep program split into its plan operands and a pure body.

    ``consts`` is a pytree of device arrays staged once at build time
    (lane metadata, reordered elementwise data, write-back structure,
    coalesced slice bases); ``apply(consts, mutable, out_init) -> out``
    is the traceable stage-A/stage-B program over them.  Every jitted
    caller passes ``consts`` as an ARGUMENT: a device array closed over by
    a traced function becomes an HLO literal, which at 10^7-10^8 nnz
    bloats the program toward the serialization limit, makes the compiler
    constant-fold the plan, and keys the compile cache on the matrix.
    Calling the object directly (``sweep(mutable, out_init)``) is for
    eager use."""

    def __init__(self, apply, consts, tree: ir.CodeTree | None = None):
        self.apply = apply
        self.consts = consts
        self.tree = tree

    def __call__(self, mutable, out_init):
        return self.apply(self.consts, mutable, out_init)


def sweep_parts(run):
    """``(consts, apply)`` of an executor's sweep program — the pieces a
    jitted driver (resident loop, vmapped batch, timed tuner loop) embeds,
    passing ``consts`` as an argument.  A callable without a
    :class:`Sweep` body is wrapped as-is (no operands)."""
    body = getattr(run, "sweep_body", None)
    if isinstance(body, Sweep):
        return body.consts, body.apply
    return (), lambda _consts, mutable, out_init: run(mutable, out_init)


_META_KEYS = ("window_ids", "lane_slot", "lane_offset", "seg_ids",
              "gather_idx")


def _stage_meta(plan: BlockPlan, launches: list[ir.Launch]) -> dict:
    """Device copies of the per-lane plan arrays the XLA launches read —
    only those some launch needs, so unused metadata never occupies
    device memory."""
    kinds = {launch.gather for launch in launches}
    keys = {"seg_ids"} if launches else set()
    if ir.FALLBACK in kinds:
        keys.add("gather_idx")
    if kinds & {ir.WINDOW, ir.STREAM}:
        keys.update(("window_ids", "lane_slot", "lane_offset"))
    return {k: jnp.asarray(getattr(plan, k)) for k in _META_KEYS
            if k in keys}


def _record_nnz(trees) -> None:
    """Set the ``engine.nnz.{window,coalesced,fallback}`` gauges: the
    valid (unpadded) nonzeros of the trees' launches of each kind.  The
    segsum form runs every block through its one per-element gather.
    ``engine.nnz.window_resident`` and ``engine.nnz.fallback_vmem``
    restart at 0: the Pallas stage A sets them when its program is
    traced, once the views' bytes and the lanes' shape are known."""
    nnz = {"window": 0, "coalesced": 0, "fallback": 0}
    for tree in trees:
        valid = tree.plan.valid
        for launch in tree.launches:
            kind = ("fallback" if tree.backend == "segsum" else
                    _LAUNCH_SCOPES[launch.gather].rpartition(".")[2])
            nnz[kind] += int(valid[launch.start:launch.stop].sum())
    for kind, n in nnz.items():
        _metrics.set_gauge(f"engine.nnz.{kind}", n)
    _metrics.set_gauge("engine.nnz.window_resident", 0)
    _metrics.set_gauge("engine.nnz.fallback_vmem", 0)


@_trace.traced("engine.build_sweeper")
def make_sweeper(plan: BlockPlan, static_data: Mapping[str, np.ndarray],
                 backend: str = "jax", interpret: bool | None = None,
                 fused: bool = True, stage_b: str = "auto",
                 elem_exec: Mapping[str, jnp.ndarray] | None = None,
                 coalesce: bool = False, tree: ir.CodeTree | None = None,
                 kernel_params: Mapping[str, int] | None = None) -> Sweep:
    """The raw sweep program as a :class:`Sweep` — the same stage-A/
    stage-B program :func:`make_executor` jits, without the jit boundary,
    for embedding inside ``lax.while_loop`` / ``fori_loop`` fixpoint
    drivers (DESIGN.md §7).

    The plan is first lowered through the information-code-tree pipeline
    (:func:`repro.core.ir.lower` — fuse/stage-B/coalesce passes per the
    ``fused`` / ``stage_b`` / ``coalesce`` toggles); the emitter below
    walks the lowered launch list and makes no lowering decisions itself.
    Each build is observed in the ``engine.build_seconds`` histogram and
    sets the ``engine.nnz.*`` gauges.

    All host-side constants (reordered elementwise arrays, lane metadata,
    write-back structure, coalesced slice bases) are staged to the device
    HERE, once, as ``Sweep.consts``: a resident loop passes them into its
    jitted program and re-uploads nothing.  Because the standalone
    executor is literally ``jax.jit`` of ``Sweep.apply``, a resident loop
    iteration is bitwise identical to a standalone executor call.

    ``tree`` optionally supplies an ALREADY-LOWERED code tree (its plan
    must be ``plan``) and skips the internal :func:`repro.core.ir.lower`
    — the emission path of the partitioned per-shard subtrees
    (:func:`repro.core.ir.partition_plan`), whose launch lists were
    sliced, not re-lowered."""
    t0 = time.perf_counter()
    sweep = _emit_sweeper(plan, static_data, backend, interpret, fused,
                          stage_b, elem_exec, coalesce, tree, kernel_params)
    _metrics.observe("engine.build_seconds", time.perf_counter() - t0)
    _record_nnz([sweep.tree])
    return sweep


def _emit_sweeper(plan, static_data, backend, interpret, fused, stage_b,
                  elem_exec, coalesce, tree, kernel_params) -> Sweep:
    seed = plan.seed
    if tree is None:
        tree = ir.lower(plan, backend=backend, fused=fused,
                        stage_b=stage_b, coalesce=coalesce)
    elif tree.plan is not plan:
        raise ValueError("make_sweeper: tree.plan must be the given plan")
    elif tree.backend != backend:
        raise ValueError(
            f"make_sweeper: tree was lowered for backend "
            f"{tree.backend!r}, emitter asked for {backend!r}")
    if elem_exec is None:
        elem_exec = reorder_static(plan, static_data)
    elem_exec = dict(elem_exec)
    wb_meta, depth, rows = {}, 0, None
    if tree.stage_b == "dense":
        wb_meta["lane_rows"] = jnp.asarray(dense_head_rows(plan))
        write_back = _stage_b_dense
    elif tree.stage_b == "gather":
        wb_meta = head_write_meta(plan)
        depth = wb_meta.pop("head_tree_depth")
        write_back = _stage_b
        if tree.launches:
            # what a lane-wide product's row partitions are cut from
            rows = ir.RowOrder(tree)
            wb_meta["row_order"] = jnp.asarray(rows.order)
            wb_meta["head_rowpos"] = jnp.asarray(rows.head_rowpos)
    else:
        write_back = None            # "fold": segsum stage A+B are one op
    elem_dtypes = {e: elem_exec[e].dtype for e in seed.elementwise}
    fills = functools.partial(_fills_identity, seed=seed,
                              out_len=plan.out_len, elem_dtypes=elem_dtypes)

    if backend == "jax":
        launches = tree.launches
        co_meta = {
            i: {"starts": jnp.asarray(launch.slice_starts, jnp.int32),
                "off": (None if launch.local_offset is None
                        else jnp.asarray(launch.local_offset, jnp.int32))}
            for i, launch in enumerate(launches)
            if launch.gather == ir.COALESCED}
        consts = {"meta": {**_stage_meta(plan, launches), **wb_meta,
                           "zero": jnp.zeros((), jnp.int32)},
                  "elem": elem_exec, "co": co_meta}

        def run(c, mutable, out_init):
            parts = _lane_partitions(plan, rows, mutable, elem_dtypes)
            if parts is not None:
                return _run_partitions(
                    plan, rows, parts, c["meta"],
                    lambda mut, blocks, _counts: _stage_a_jax(
                        plan, c["meta"], c["elem"], mut, launches, c["co"],
                        blocks), mutable, out_init, depth)
            lanes = _stage_a_jax(plan, c["meta"], c["elem"], mutable,
                                 launches, c["co"])
            return write_back(plan, c["meta"], lanes, out_init, depth)
        return Sweep(fills(run), consts, tree)

    if backend == "segsum":
        # CPU-optimal configuration of the same plan: the Data Transfer
        # sort already made (block, row) runs consecutive, so stage A+B
        # collapse into ONE sorted segment reduce straight into y.  On
        # register-rich targets (TPU VMEM / AVX-512) the log-shift path
        # wins; on XLA-CPU each shift step round-trips memory and this
        # form is strictly better (see EXPERIMENTS §Perf iteration log).
        # All four semiring reduces map onto jax.ops.segment_{sum,prod,
        # max,min}; empty segments (rows with no nnz, plus the discard
        # bucket at out_len) come back as the dtype-aware identity, so
        # folding into out_init with the reduce op leaves them untouched.
        # global output row per exec lane (pads -> bucket out_len):
        # scatter each head's row onto its (block, segment), then read it
        # back per lane — runs are consecutive post-sort.
        seg = plan.seg_ids
        per_seg = np.full((plan.num_blocks, plan.lane_width), plan.out_len,
                          np.int64)
        hb = plan.head_pos // plan.lane_width
        hl = plan.head_pos % plan.lane_width
        per_seg[hb, seg[hb, hl]] = plan.head_rows
        lane_rows = per_seg[np.arange(plan.num_blocks)[:, None], seg]
        lane_rows = np.where(plan.valid, lane_rows, plan.out_len)
        consts = {
            "rows": jnp.asarray(lane_rows.reshape(-1), jnp.int32),
            "gidx": jnp.asarray(plan.gather_idx.reshape(-1), jnp.int32),
            "elem": elem_exec}

        seg_reduce = {"add": jax.ops.segment_sum,
                      "mul": jax.ops.segment_prod,
                      "max": jax.ops.segment_max,
                      "min": jax.ops.segment_min}[seed.reduce]
        from repro.core.seed import REDUCE_OPS
        fold = REDUCE_OPS[seed.reduce][0]

        def run_ss(c, mutable, out_init):
            # one per-element gather + segment reduce: the fallback idiom
            # for every block, its write-back the fold into out_init
            with jax.named_scope(_trace.SCOPE_FALLBACK):
                vals = {}
                for g in seed.gathered:
                    vals[g] = jnp.asarray(mutable[g])[c["gidx"]]
                rank = max((v.ndim for v in vals.values()), default=1)
                for e in seed.elementwise:
                    vals[e] = _expand_trailing(c["elem"][e].reshape(-1),
                                               rank)
                term = seed.combine(vals)
                red = seg_reduce(term, c["rows"],
                                 num_segments=plan.out_len + 1)
            with jax.named_scope(_trace.SCOPE_STAGE_B):
                return fold(out_init, red[:plan.out_len])
        return Sweep(fills(run_ss), consts, tree)

    if backend == "pallas":
        from repro.kernels import common as kcommon
        from repro.kernels.unroll_spmv import ops as kops
        # interpret=None platform-resolves: real compile on TPU/GPU,
        # interpret mode only on CPU or by explicit request (DESIGN.md §13)
        interpret = kcommon.resolve_interpret(interpret)
        stage_consts, stage_a = kops.make_stage_a(
            plan, elem_exec, interpret=interpret, launches=tree.launches,
            kernel_params=kernel_params)
        consts = {"meta": wb_meta, "stage_a": stage_consts}

        def run_pl(c, mutable, out_init):
            parts = _lane_partitions(plan, rows, mutable, elem_dtypes)
            if parts is not None:
                return _run_partitions(
                    plan, rows, parts, c["meta"],
                    lambda mut, blocks, counts: stage_a(
                        c["stage_a"], mut, blocks, counts),
                    mutable, out_init, depth)
            lanes = stage_a(c["stage_a"], mutable)
            return write_back(plan, c["meta"], lanes, out_init, depth)
        return Sweep(fills(run_pl), consts, tree)

    raise ValueError(f"unknown backend {backend!r}")


def make_executor(plan: BlockPlan, static_data: Mapping[str, np.ndarray],
                  backend: str = "jax", interpret: bool | None = None,
                  fused: bool = True, stage_b: str = "auto",
                  fuse_classes: bool | None = None,
                  elem_exec: Mapping[str, jnp.ndarray] | None = None,
                  donate: bool = False, coalesce: bool = False,
                  tree: ir.CodeTree | None = None,
                  kernel_params: Mapping[str, int] | None = None):
    """Build a jitted executor ``fn(mutable: dict, out_init) -> out``;
    ``out_init=None`` folds into the reduce identity, made inside the
    program.

    ``static_data`` holds the seed's *elementwise* (immutable, nnz-aligned)
    arrays in original order; they are reordered once here (Data Transfer)
    and staged as device operands of the program.  ``elem_exec``
    optionally supplies the already-reordered arrays
    (:func:`reorder_static`) so multiple executors on one plan share the
    reorder work.

    ``fused`` (default) collapses the per-class launch list into at most
    two launches (DESIGN.md §3); ``fused=False`` keeps the paper's
    one-launch-per-pattern-class form.  ``stage_b`` selects the write-back:
    ``"gather"`` (head re-gather from the flat lane stream), ``"dense"``
    (scatter the full lane stream through the precomputed dense head-row
    buffer), or ``"auto"`` (the collision-free gather form).  ``coalesce``
    enables the gather-coalescing lowering pass (DESIGN.md §8) on both the
    jax and pallas backends (the latter lowers COALESCED launches to the
    dense-slice kernel, DESIGN.md §13).  ``kernel_params`` carries the
    tuned Pallas kernel knobs (``rows_per_step``, ``meta_prefetch``);
    ignored by the XLA backends.

    ``donate=True`` jit-donates ``out_init``: a fixpoint driver that
    ping-pongs two buffers then reuses storage in place instead of
    allocating ``out_len`` per call.  Donation safety (DESIGN.md §7): the
    donated ``out_init`` must be a DIFFERENT buffer from every gathered
    mutable input — XLA rejects the self-alias ``run(state, donate(state))``
    with an explicit error rather than corrupting — and the caller's
    ``out_init`` array is consumed, so retaining and reusing the reference
    raises instead of silently reading clobbered memory.  For the aliased
    self-fold sweep (``out_init`` IS the state), use the resident loop
    drivers instead: the ``while_loop`` carry double-buffers internally
    with no donation hazard.

    The returned callable exposes the :class:`Sweep` as
    ``run.sweep_body``, the jitted ``(consts, mutable, out_init)`` program
    as ``run.jitted``, ``run.lower(mutable, out_init)`` (the profiler
    lowers it to HLO), and the lowered code tree as ``run.tree``
    (per-launch cost attribution, DESIGN.md §11).  With tracing enabled
    each call emits an ``engine.execute`` span — ``first_call=True``
    marks the call that paid JIT compilation.
    """
    if fuse_classes is not None:      # legacy alias of the pre-fused API
        fused = fuse_classes
    body = make_sweeper(plan, static_data, backend=backend,
                        interpret=interpret, fused=fused, stage_b=stage_b,
                        elem_exec=elem_exec, coalesce=coalesce, tree=tree,
                        kernel_params=kernel_params)
    return _executor(body, donate, backend=backend)


def _executor(body: Sweep, donate: bool, **span_attrs):
    """Jit a :class:`Sweep` with its operands passed as arguments and wrap
    it in the traced ``run(mutable, out_init)`` call contract."""
    jitted = jax.jit(body.apply, donate_argnums=(2,) if donate else ())

    def run(mutable, out_init):
        if not _trace.active():
            return jitted(body.consts, mutable, out_init)
        first = not run._called
        run._called = True
        with _trace.span("engine.execute", first_call=first, **span_attrs):
            return jitted(body.consts, mutable, out_init)
    run._called = False
    run.sweep_body = body
    run.jitted = jitted
    run.lower = lambda mutable, out_init: jitted.lower(body.consts, mutable,
                                                       out_init)
    run.tree = body.tree
    return run


# ------------------------------------------------------ sharded emitters
# One mesh, one plan per shard (DESIGN.md §10): the emitters below run
# the per-shard subtrees of ir.partition_plan under shard_map over a
# named mesh.  Public interfaces stay FULL-ARRAY (pad/shard on entry,
# unpad on exit, all inside one jit), so a sharded executor is a drop-in
# replacement for a single-device one — same oracle checks, same tuner
# measurement harness, bitwise-equal outputs.
from jax.sharding import NamedSharding as _NS
from jax.sharding import PartitionSpec as _PS


def _shard_axis(mesh) -> str:
    """The mesh axis shard rows ride on — the data-parallel axis."""
    dp = dp_axes(mesh)
    if len(dp) != 1:
        raise ValueError(
            f"sharded execution needs exactly one data axis in the mesh "
            f"(axes {mesh.axis_names}, data axes {dp}); build one with "
            "repro.core.mesh.make_shard_mesh(shards)")
    if int(np.prod([mesh.shape[a] for a in mesh.axis_names
                    if a not in dp])) != 1:
        raise ValueError(
            f"sharded execution replicates over non-data axes; mesh "
            f"{dict(mesh.shape)} has a non-trivial model axis")
    return dp[0]


def _check_parts(parts, mesh) -> str:
    axis = _shard_axis(mesh)
    k = int(mesh.shape[axis])
    if len(parts) != k:
        raise ValueError(
            f"{len(parts)} plan shards over a {k}-device '{axis}' axis; "
            "partition_plan(tree, shards) must match the mesh")
    if parts and parts[0].tree.backend == "pallas":
        raise ValueError("sharded execution supports the jax/segsum "
                         "backends (Pallas kernels are single-device)")
    return axis


def shard_widths(parts) -> tuple[list[int], int]:
    """Per-shard row counts and the common padded width S (>= 1)."""
    widths = [p.num_rows for p in parts]
    return widths, max(max(widths), 1)


def pad_rows(state: jnp.ndarray, widths: list[int], s: int) -> jnp.ndarray:
    """(n, ...) full array -> (k, S, ...) stacked per-shard rows, each
    shard's slice zero-padded to S.  Pads are CONSTANT zeros in every
    sweep (the emitters re-pad with zeros), so padded-state equality is
    exactly full-state equality — the sharded convergence check leans on
    this."""
    pieces, lo = [], 0
    for w in widths:
        piece = state[lo:lo + w]
        pad = ((0, s - w),) + ((0, 0),) * (state.ndim - 1)
        pieces.append(jnp.pad(piece, pad))
        lo += w
    return jnp.stack(pieces)


def unpad_rows(padded: jnp.ndarray, widths: list[int]) -> jnp.ndarray:
    """(k, S, ...) -> (n, ...): drop each shard's pad rows and concat."""
    return jnp.concatenate([padded[i, :w] for i, w in enumerate(widths)],
                           axis=0)


def shard_sweep_bodies(parts, static_data) -> list[Sweep | None]:
    """One :class:`Sweep` per shard (``None`` for an empty shard, which
    runs as the identity).  Elementwise arrays stay FULL-LENGTH: each
    shard's sliced ``flat_perm`` holds global nnz positions, so the
    per-shard Data Transfer reorders the same full arrays the parent
    would (the parent's own ``elem_exec`` cannot be shared — it is
    already block-reordered)."""
    bodies = []
    for p in parts:
        if p.num_blocks == 0 or p.tree.plan.head_pos.size == 0:
            bodies.append(None)
            continue
        bodies.append(make_sweeper(p.tree.plan, static_data,
                                   backend=p.tree.backend, tree=p.tree))
    _record_nnz([p.tree for p in parts])
    return bodies


def _replicated(tree, mesh):
    """Place a pytree of plan operands on every mesh device once, so a
    sharded call never re-broadcasts them."""
    return jax.device_put(tree, _NS(mesh, _PS()))


def _pad_to(y: jnp.ndarray, s: int) -> jnp.ndarray:
    return jnp.pad(y, ((0, s - y.shape[0]),) + ((0, 0),) * (y.ndim - 1))


def make_sharded_executor(parts, static_data, mesh, *,
                          donate: bool = False):
    """Placement-parameterized executor over a partitioned plan:
    ``run(mutable, out_init)`` with FULL arrays, executing shard ``i``'s
    subtree on mesh device ``i`` under ``shard_map``.

    The mutable gathered inputs are replicated (every shard gathers
    through GLOBAL indices); ``out_init`` is row-sharded.  Device ``i``
    selects its shard's program with ``lax.switch(axis_index)`` — every
    branch pads its rows to the common width S so the switch is
    shape-legal.  Every shard's plan operands are replicated onto every
    device and passed into the program as arguments.  Bitwise: each
    output row runs the parent's identical block program and per-row
    combine tree (ir.partition_plan), so the result equals single-device
    execution bit for bit."""
    axis = _check_parts(parts, mesh)
    widths, s = shard_widths(parts)
    k = len(parts)
    bodies = shard_sweep_bodies(parts, static_data)
    consts = _replicated([b.consts if b else () for b in bodies], mesh)

    def device_fn(c, mutable, block):       # block: (1, S, ...) local
        def branch(j):
            def f(c, mut, blk):
                if bodies[j] is None:
                    return blk
                y = bodies[j].apply(c[j], mut, blk[0, :widths[j]])
                return _pad_to(y, s)[None]
            return f
        i = jax.lax.axis_index(axis)
        return jax.lax.switch(i, [branch(j) for j in range(k)],
                              c, mutable, block)

    def run_full(c, mutable, out_init):
        padded = pad_rows(out_init, widths, s)
        y = jax.shard_map(device_fn, mesh=mesh,
                          in_specs=(_PS(), _PS(), _PS(axis)),
                          out_specs=_PS(axis))(c, mutable, padded)
        return unpad_rows(y, widths)

    seed = parts[0].tree.plan.seed
    elem_dtypes = {e: jax.dtypes.canonicalize_dtype(
        np.asarray(static_data[e]).dtype) for e in seed.elementwise}
    run = _executor(Sweep(_fills_identity(run_full, seed, sum(widths),
                                          elem_dtypes), consts), donate,
                    backend=parts[0].tree.backend, shards=k)
    run.parts = parts
    run.mesh = mesh
    return run


def make_sharded_fixpoint_step(parts, static_data, mesh, state_key: str,
                               *, local_step=None, extra=(),
                               with_convergence: bool = True):
    """The sharded resident sweep ``step(consts, padded_state) -> ...``
    for fixpoint drivers (DESIGN.md §7/§10), with its replicated plan
    operands on ``step.consts``: state lives row-sharded as the padded
    ``(k, S, ...)`` stack, each sweep ``all_gather``s the shard pieces
    into the full dense input vector, runs the local subtree on the
    shard's own rows (fold semantics: ``out_init`` is the shard's
    previous rows), and re-pads.  With ``with_convergence`` the step
    also returns replicated device-side ``(changed, healthy)`` scalars —
    ``psum`` of the per-shard ``array_equal`` / ``state_healthy``
    verdicts, so convergence needs no host round-trip and no full-state
    rebuild outside the loop.

    ``local_step`` optionally overrides the per-shard body:
    ``f(apply_j, consts_j, extra, full_state, local_rows) ->
    new_local_rows`` (PageRank's damping fold wraps the contribution
    sweep this way); ``extra`` is a pytree of further device operands
    it reads, replicated and passed in like the plan's."""
    axis = _check_parts(parts, mesh)
    widths, s = shard_widths(parts)
    k = len(parts)
    reduce = parts[0].tree.plan.seed.reduce
    bodies = shard_sweep_bodies(parts, static_data)
    if local_step is None:
        def local_step(apply, c, _extra, full, local):
            return apply(c, {state_key: full}, local)
    consts = _replicated(([b.consts if b else () for b in bodies], extra),
                         mesh)

    def device_fn(c, block):                 # (1, S, ...) local rows
        shard_consts, ext = c
        pieces = jax.lax.all_gather(block[0], axis)       # (k, S, ...)
        full = unpad_rows(pieces, widths)                 # (n, ...)

        def branch(j):
            def f(c, blk):
                if bodies[j] is None:
                    return blk
                new = local_step(bodies[j].apply, c[j], ext, full,
                                 blk[0, :widths[j]])
                return _pad_to(new, s)[None]
            return f
        i = jax.lax.axis_index(axis)
        new = jax.lax.switch(i, [branch(j) for j in range(k)],
                             shard_consts, block)
        if not with_convergence:
            return new
        # device-side convergence via psum of the per-shard verdicts —
        # both scalars replicate across the axis
        with jax.named_scope(_trace.SCOPE_FIXPOINT_CHECK):
            changed_here = jnp.logical_not(jnp.array_equal(new, block))
            changed = jax.lax.psum(changed_here.astype(jnp.int32),
                                   axis) > 0
            sick_here = jnp.logical_not(state_healthy(new, reduce))
            healthy = jax.lax.psum(sick_here.astype(jnp.int32), axis) == 0
        return new, changed, healthy

    out_specs = (_PS(axis), _PS(), _PS()) if with_convergence \
        else _PS(axis)

    def step(c, padded_state):
        return jax.shard_map(device_fn, mesh=mesh,
                             in_specs=(_PS(), _PS(axis)),
                             out_specs=out_specs)(c, padded_state)
    step.consts = consts
    step.widths = widths
    step.padded_width = s
    step.axis = axis
    return step


def make_baseline_gather(seed: CodeSeed, access: Mapping[str, np.ndarray],
                         static_data: Mapping[str, np.ndarray]):
    """The conservative-compiler baseline: native gather + scatter-add,
    no pattern analysis (used as the icc/-O3 stand-in by the benchmarks)."""
    acc = {k: jnp.asarray(v) for k, v in access.items()}
    elem = {e: jnp.asarray(static_data[e]) for e in seed.elementwise}

    @jax.jit
    def run(mutable, out_init):
        data = dict(mutable)
        data.update(elem)
        return reference_execute(seed, acc, data, out_init)
    return run
