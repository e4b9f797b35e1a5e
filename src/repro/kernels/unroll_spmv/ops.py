"""Jitted wrapper assembling Pallas launches into stage A.

``make_stage_a(plan, ..., launches=...)`` returns a function
``fn(mutable) -> (B, N, ...)`` lanes matrix in exec-block order.  The
launch list comes from the lowered information-code tree
(:mod:`repro.core.ir`): the fused form is at most ONE ``pallas_call``
covering every vload block (the grid spans the whole vload section,
window BlockSpecs are padded to the section-wide max ``ls`` —
scalar-prefetched ``window_ids`` repeat the last valid window, so the
extra DMAs are legal and lanes never select them — and the shift-reduce
ladder is deep enough for every member class; extra steps are exact
no-ops, DESIGN.md §3) plus ONE batched XLA segment for all
gather-fallback blocks, with per-block native-reduce flags carried on
``Launch.full_mask``.  The un-fused form is the paper's
one-``pallas_call``-per-pattern-class list (§6.3 applies the rewrite
only when the flags indicate a benefit).

COALESCED launches (``ir.coalesce_gathers``, DESIGN.md §8) lower to the
dense-slice kernel: one unaligned vector load per block (two aligned
tile DMAs + a lane rotate) plus a static in-tile permute — no
per-element gather.  Trailing lane axes (§8
rank rules) flow through every form, so SpMM and the graph apps run on
this emitter unchanged.

``interpret`` is platform-resolved (``None`` -> real compile on TPU/GPU,
interpret mode only on CPU or when explicitly requested).
``kernel_params`` carries the tuned per-launch kernel knobs
(:class:`repro.tune.space.Candidate`): ``rows_per_step`` for the
dense-slice form, ``meta_prefetch`` for the per-tile TPU window form.
The resident window form takes neither: its step is fixed by the tiles of
its blocks.

Each window launch takes the resident form where ``kernel.resident_steps``
says its gathered views and a step fit VMEM and SMEM, else the per-tile
form; tracing ``stage_a`` makes that choice once per launch and sets the
``engine.nnz.window_resident`` gauge from it: the valid nonzeros of the
window launches that run the resident form.

A fallback launch whose lanes have trailing axes (SpMM, ``D > 1``) keeps
the XLA gather and combine and runs its ladder, full reduce and select in
``kernel.fallback_stage_a``, one pass through VMEM
(``kernel.fallback_rows``); ``D = 1`` lanes keep the XLA ladder, whose
gathered array XLA may place in VMEM.  Tracing ``stage_a`` sets the
``engine.nnz.fallback_vmem`` gauge: the valid nonzeros of the fallback
launches that run the kernel.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.core import engine as eng
from repro.core import ir
from repro.core.plan import BlockPlan
from repro.kernels import common
from repro.kernels.unroll_spmv.kernel import (class_stage_a,
                                              coalesced_stage_a,
                                              fallback_rows,
                                              fallback_stage_a,
                                              resident_stage_a,
                                              resident_steps)
from repro.obs import metrics as _metrics


def _select_blocks(cm: dict, ids: jnp.ndarray) -> dict:
    """A launch's staged operands for its blocks at positions ``ids`` (a
    row partition's, ``engine._run_partitions``)."""
    out = dict(cm)
    for k, v in cm.items():
        if k == "elem":
            out[k] = {e: a[ids] for e, a in v.items()}
        elif k == "win":
            out[k] = v.reshape(cm["seg"].shape[0], -1)[ids].reshape(-1)
        elif v is not None and k != "zero":
            out[k] = v[ids]
    return out


def make_stage_a(plan: BlockPlan, elem_exec,
                 interpret: bool | None = None,
                 launches: list[ir.Launch] | None = None,
                 kernel_params: dict | None = None):
    """Stage each launch's kernel operands on the device and return
    ``(consts, stage_a)``: ``stage_a(consts, mutable) -> (B, N, ...)``
    lanes in exec-block order.  ``consts`` is passed in by the jitted
    caller, never closed over (see :class:`repro.core.engine.Sweep`).
    ``stage_a(consts, mutable, blocks, counts)`` runs, of each launch
    ``i`` in ``blocks``, only its blocks at positions ``blocks[i]``, of
    which the first ``counts[i]`` are real and the rest pads (a row
    partition, ``engine._run_partitions``): the per-tile window form
    skips the pads."""
    seed = plan.seed
    interpret = common.resolve_interpret(interpret)
    kp = kernel_params or {}
    rows_per_step = int(kp.get("rows_per_step") or 1)
    meta_prefetch = int(kp.get("meta_prefetch") or 1)
    if launches is None:
        launches = ir.lower(plan, backend="pallas").launches
    elem_dtypes = {e: elem_exec[e].dtype for e in seed.elementwise}
    # per-launch static metadata, upcast to kernel-friendly int32 once;
    # each launch stages only the operands its form reads
    consts = []
    for launch in launches:
        s = slice(launch.start, launch.stop)
        mask = launch.full_mask
        cm = dict(seg=jnp.asarray(plan.seg_ids[s], jnp.int32),
                  elem={e: elem_exec[e][s] for e in seed.elementwise},
                  full=None if mask is None else jnp.asarray(mask, jnp.int32))
        if launch.gather == ir.FALLBACK:
            cm["gidx"] = jnp.asarray(plan.gather_idx[s], jnp.int32)
            cm["zero"] = jnp.zeros((), jnp.int32)
        elif launch.gather == ir.COALESCED:
            cm["starts"] = jnp.asarray(launch.slice_starts, jnp.int32)
            cm["local"] = (None if launch.local_offset is None
                           else jnp.asarray(launch.local_offset, jnp.int32))
        else:
            # flat: the kernels read window ids as 1-D scalar arrays, and a
            # 1-D device layout keeps that reshape a no-op
            cm["win"] = jnp.asarray(
                plan.window_ids[s][:, :max(launch.ls_flag, 1)].reshape(-1),
                jnp.int32)
            cm["slot"] = jnp.asarray(plan.lane_slot[s], jnp.int32)
            cm["off"] = jnp.asarray(plan.lane_offset[s], jnp.int32)
        consts.append(cm)
    valid = [int(plan.valid[launch.start:launch.stop].sum())
             for launch in launches]

    def launch_lanes(launch, cm, views, mutable, out_dtype, out_trailing,
                     steps, live):
        elem_blocks = cm["elem"]
        if launch.gather == ir.FALLBACK and seed.gather_index is not None:
            # native gather path (XLA); the segmented reduce in XLA for
            # D = 1 lanes, else in one pass of the fallback kernel
            vals = {g: jnp.asarray(mutable[g])[cm["gidx"]]
                    for g in seed.gathered}
            rank = max((v.ndim for v in vals.values()), default=2)
            for e in seed.elementwise:
                vals[e] = eng._expand_trailing(elem_blocks[e], rank)
            term = eng.combine_rounded(seed, vals, cm["zero"])
            if steps is not None:
                # lanes with trailing axes: the ladder and the full
                # reduce in one pass through VMEM
                return fallback_stage_a(
                    term, cm["seg"], op=launch.op_flag, reduce=seed.reduce,
                    rows=steps, full_flags=cm["full"], interpret=interpret)
            red = eng.segmented_reduce(term, cm["seg"], launch.op_flag,
                                       seed.reduce)
            if cm["full"] is not None:
                native = eng.segmented_reduce(
                    term, cm["seg"], eng.ft.FULL_REDUCE, seed.reduce)
                red = jnp.where(
                    eng._expand_trailing((cm["full"] != 0)[:, None],
                                         term.ndim), native, red)
            return red
        if launch.gather == ir.COALESCED:
            return coalesced_stage_a(
                cm["starts"], views, elem_blocks, cm["local"],
                cm["seg"], combine=seed.combine, gathered=seed.gathered,
                elementwise=seed.elementwise, op=launch.op_flag,
                reduce=seed.reduce, full_flags=cm["full"],
                out_dtype=out_dtype, out_trailing=out_trailing,
                interpret=interpret, rows_per_step=rows_per_step)
        kw = dict(combine=seed.combine, gathered=seed.gathered,
                  elementwise=seed.elementwise, ls=max(launch.ls_flag, 1),
                  op=launch.op_flag, stream=launch.stream,
                  reduce=seed.reduce, full_flags=cm["full"],
                  out_dtype=out_dtype, out_trailing=out_trailing,
                  interpret=interpret)
        if steps is not None:
            return resident_stage_a(cm["win"], views, elem_blocks,
                                    cm["slot"], cm["off"], cm["seg"],
                                    steps=steps, **kw)
        win = cm["win"].reshape(cm["seg"].shape[0], -1)
        return class_stage_a(win, views, elem_blocks, cm["slot"], cm["off"],
                             cm["seg"], meta_prefetch=meta_prefetch,
                             live=live, **kw)

    def stage_a(consts, mutable, blocks=None, counts=None):
        views = {g: eng._pad_gathered(plan, jnp.asarray(mutable[g]))
                 for g in seed.gathered}
        out_dtype, out_trailing = eng.term_struct(seed, mutable,
                                                  elem_dtypes)
        parts = []
        resident = vmem_fallback = 0
        for i, (launch, cm, n_valid) in enumerate(zip(launches, consts,
                                                       valid)):
            if blocks is not None and i not in blocks:
                continue
            bc = launch.stop - launch.start if blocks is None \
                else blocks[i].shape[0]
            # a window launch's form follows the bytes of its views and
            # step, known once the views are; a fallback launch's, the
            # shape of its lanes
            steps = None
            if launch.gather in (ir.WINDOW, ir.STREAM):
                steps = resident_steps(
                    views, blocks=bc,
                    ls=max(launch.ls_flag, 1), mixed=cm["full"] is not None,
                    stream=launch.stream, elementwise=len(seed.elementwise),
                    out_dtype=out_dtype, out_trailing=out_trailing,
                    interpret=interpret)
                resident += n_valid if steps is not None else 0
            elif (launch.gather == ir.FALLBACK
                  and seed.gather_index is not None):
                steps = fallback_rows(
                    (bc, plan.lane_width) + out_trailing, out_dtype,
                    interpret=interpret)
                vmem_fallback += n_valid if steps is not None else 0
            # each launch's ops run under its kind's device scope
            with eng.launch_scope(launch):
                if blocks is not None:
                    cm = _select_blocks(cm, blocks[i])
                parts.append(launch_lanes(
                    launch, cm, views, mutable, out_dtype, out_trailing,
                    steps, None if counts is None else counts[i]))
        _metrics.set_gauge("engine.nnz.window_resident", resident)
        _metrics.set_gauge("engine.nnz.fallback_vmem", vmem_fallback)
        if not parts:      # empty plan (nnz == 0): no launches, no lanes
            return jnp.zeros((0, plan.lane_width) + out_trailing, out_dtype)
        return parts[0] if len(parts) == 1 else jnp.concatenate(parts, 0)

    return consts, stage_a
