"""Applications built on the Intelligent-Unroll engine (paper §7).

* :class:`SpMV` — COO sparse matrix-vector product (paper Alg. 5).  The plan
  is built once per matrix (access arrays immutable); ``matvec`` is a jitted
  call over the mutable ``x`` with a cached per-dtype zero ``y_init`` (no
  per-call allocation litter).
* :class:`PageRank` — edge-push power iteration (paper Alg. 4); one plan for
  the whole run, reused every sweep, exactly the amortization the paper's
  runtime JIT relies on.  ``run()`` is device-resident by default
  (DESIGN.md §7): the contribution sweep, the dangling-mass reduction, and
  the damping fold all live inside ONE jitted ``lax.fori_loop`` with a
  donated rank buffer — one dispatch per run instead of 3+ dispatches per
  iteration; ``driver="host"`` keeps the stepwise A/B baseline (bitwise
  identical ranks).
* :class:`BFS` / :class:`SSSP` / :class:`ConnectedComponents` — the graph
  applications (non-add semirings), re-exported from
  :mod:`repro.core.graphs`.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import engine as eng
from repro.core import validate as validation
from repro.core.graphs import check_auto_kwargs
from repro.core.mesh import (make_shard_mesh, resolve_shard_mesh,
                             row_sharding)
from repro.core.plan import BlockPlan, CostModel, build_plan
from repro.core.seed import pagerank_seed, spmv_seed
from repro.obs import trace as _trace


def _plan(seed, access, out_len, data_len, cost, plan_cache_dir):
    """build_plan, through the content-addressed cache when a dir is given
    (repeat matrices skip the analysis entirely — DESIGN.md §4)."""
    if plan_cache_dir is None:
        return build_plan(seed, access, out_len, data_len, cost=cost)
    from repro.core import planio
    return planio.cached_build_plan(seed, access, out_len, data_len,
                                    cost=cost, cache_dir=plan_cache_dir)


@dataclasses.dataclass
class SpMV:
    plan: BlockPlan
    shape: tuple[int, int]
    _run: object
    dtype: np.dtype
    tuning: object | None = None   # TuningResult when built via backend="auto"
    validation: object | None = None    # ValidationReport from from_coo
    degradations: tuple = ()            # DegradationEvents from the build
    # sharded execution (DESIGN.md §10): the mesh the executor runs over
    # (None = single device) and the per-shard plan subtrees
    mesh: object | None = None
    _shard_parts: tuple = dataclasses.field(default=(), repr=False)
    # cached zero y_init per dtype: repeated matvecs share one device
    # constant instead of allocating a fresh jnp.zeros per call
    _y0: dict = dataclasses.field(default_factory=dict, repr=False)
    # cached vmapped batched-matvec program + the distinct batch shapes
    # it has specialized on (compile-count accounting, mirrored into the
    # ``spmv.batched_shapes`` counter)
    _vrun: object = dataclasses.field(default=None, repr=False)
    _batched_shapes: set = dataclasses.field(default_factory=set,
                                             repr=False)

    @classmethod
    def from_coo(cls, rows: np.ndarray, cols: np.ndarray, vals: np.ndarray,
                 shape: tuple[int, int], lane_width: int = 128,
                 backend: str = "jax",
                 cost: CostModel | None = None,
                 fused: bool = True,
                 stage_b: str = "auto",
                 coalesce: bool = False,
                 plan_cache_dir: str | None = None,
                 tune: bool = False,
                 tune_cache_dir: str | None = None,
                 validate: str = "strict",
                 allow_interpret: bool = False,
                 mesh=None, shards: int | None = None) -> "SpMV":
        """``backend="auto"`` (or ``tune=True``) selects the execution
        variant per matrix via :mod:`repro.tune` — measured on this
        device, cached in ``tune_cache_dir`` so warm processes skip the
        measurements; the decision is recorded in ``.tuning``.
        ``coalesce=True`` opts in to the gather-coalescing lowering pass
        (DESIGN.md §8); under ``backend="auto"`` it is a tuned axis.
        ``validate`` is the ingestion policy (DESIGN.md §9): ``"strict"``
        (default) raises :class:`~repro.core.validate.InputError` on
        out-of-range indices or non-finite values, ``"repair"`` drops or
        combines them into a canonical matrix (report on
        ``.validation``), ``"off"`` skips the checks.

        ``mesh=`` / ``shards=`` select sharded multi-device execution
        (DESIGN.md §10): the plan is partitioned along row ranges and
        each shard's subtree runs on its own mesh device, bitwise-equal
        to single-device execution.  Under ``backend="auto"`` the shard
        count becomes a *tuned axis* (the space gains ``{1, shards}``
        candidates and the measured winner decides); an explicit
        ``mesh`` cannot be combined with the tuner.

        ``allow_interpret=True`` admits interpret-mode Pallas candidates
        into the tuned space off-accelerator (excluded by default —
        interpret timings are not wall-clock comparable; the tuning
        cache key folds the platform, so an interpret winner can never
        replay as an accelerator choice)."""
        with _trace.span("app.spmv.build", backend=backend,
                         nnz=int(np.asarray(vals).size)):
            return cls._from_coo(
                rows, cols, vals, shape, lane_width=lane_width,
                backend=backend, cost=cost, fused=fused, stage_b=stage_b,
                coalesce=coalesce, plan_cache_dir=plan_cache_dir,
                tune=tune, tune_cache_dir=tune_cache_dir,
                validate=validate, allow_interpret=allow_interpret,
                mesh=mesh, shards=shards)

    @classmethod
    def _from_coo(cls, rows, cols, vals, shape, *, lane_width, backend,
                  cost, fused, stage_b, coalesce, plan_cache_dir, tune,
                  tune_cache_dir, validate, allow_interpret, mesh,
                  shards) -> "SpMV":
        seed = spmv_seed()
        rows, cols, vals, vreport = validation.validate_coo(
            rows, cols, np.asarray(vals), shape, policy=validate)
        access = {"row": rows, "col": cols}
        with validation.collect_degradations() as events:
            if backend == "auto" or tune:
                # shards= is a legal tuned axis here (unlike the graph
                # apps); an explicit mesh still conflicts with the tuner
                check_auto_kwargs("SpMV.from_coo", backend=backend,
                                  fused=fused, stage_b=stage_b, cost=cost,
                                  coalesce=coalesce, mesh=mesh)
                from repro.tune import autotune
                shard_counts = None
                if shards is not None:
                    make_shard_mesh(int(shards))   # validate, with recipe
                    shard_counts = tuple(sorted({1, int(shards)}))
                dt = vals.dtype if np.issubdtype(vals.dtype, np.inexact) \
                    else np.float32
                x_ex = jnp.asarray(np.random.default_rng(0).standard_normal(
                    shape[1]).astype(dt))
                plan, run, result = autotune(
                    seed, access, shape[0], shape[1], {"value": vals},
                    {"x": x_ex}, jnp.zeros(shape[0], dt),
                    lane_widths=(lane_width,),
                    shard_counts=shard_counts,
                    tune_cache_dir=tune_cache_dir,
                    plan_cache_dir=plan_cache_dir,
                    allow_interpret=allow_interpret)
                app = cls(plan=plan, shape=shape, _run=run,
                          dtype=vals.dtype, tuning=result,
                          mesh=getattr(run, "mesh", None),
                          _shard_parts=tuple(getattr(run, "parts", ())))
            else:
                mesh, num_shards = resolve_shard_mesh(mesh, shards)
                cost = cost or CostModel(lane_width=lane_width)
                plan = _plan(seed, access, shape[0], shape[1], cost,
                             plan_cache_dir)
                parts = ()
                if mesh is None:
                    run = eng.make_executor(plan, {"value": vals},
                                            backend=backend, fused=fused,
                                            stage_b=stage_b,
                                            coalesce=coalesce)
                else:
                    from repro.core import ir
                    tree = ir.lower(plan, backend=backend, fused=fused,
                                    stage_b=stage_b, coalesce=coalesce)
                    parts = tuple(ir.partition_plan(tree, num_shards))
                    run = eng.make_sharded_executor(
                        parts, {"value": vals}, mesh)
                app = cls(plan=plan, shape=shape, _run=run,
                          dtype=vals.dtype, mesh=mesh, _shard_parts=parts)
        app.validation = vreport
        app.degradations = tuple(events)
        return app

    @classmethod
    def from_csr(cls, indptr: np.ndarray, indices: np.ndarray,
                 vals: np.ndarray, shape: tuple[int, int],
                 validate: str = "strict", **kw) -> "SpMV":
        """CSR ingestion.  The row partition is validated BEFORE the
        ``np.repeat`` expansion: a non-monotone or wrong-length
        ``indptr`` used to produce garbage ``rows`` silently and fail
        far downstream (or not at all) — it now raises a structured
        :class:`~repro.core.validate.InputError` under any policy but
        ``"off"``.  Entry-level defects follow ``validate`` exactly as
        :meth:`from_coo` does."""
        indptr, indices, vals, vreport = validation.validate_csr(
            indptr, indices, vals, shape, policy=validate)
        rows = np.repeat(np.arange(shape[0]), np.diff(indptr))
        # entries were already validated/repaired above — do not repeat
        # (or re-repair) the work in from_coo
        app = cls.from_coo(rows, indices, vals, shape, validate="off", **kw)
        app.validation = vreport
        return app

    def matvec(self, x: jnp.ndarray, y_init: jnp.ndarray | None = None
               ) -> jnp.ndarray:
        with _trace.span("spmv.matvec"):
            if y_init is None:
                key = np.dtype(x.dtype).str
                y_init = self._y0.get(key)
                if y_init is None:
                    y_init = self._y0[key] = jnp.zeros(self.shape[0],
                                                       dtype=x.dtype)
            return self._run({"x": x}, y_init)

    def matvec_many(self, xs, bucket: bool = True) -> jnp.ndarray:
        """Batched matvec: ONE vmapped dispatch over ``S`` stacked input
        vectors ``(S, n) -> (S, m)`` — the serving layer's batch entry
        (S requests' worth of work from one plan and one compiled
        program).  ``bucket=True`` (default) pads ``S`` up the
        :data:`~repro.core.graphs.BATCH_BUCKETS` ladder by replicating
        the last row (sliced off the result), so distinct arrival counts
        share compiled programs instead of retracing per ``S``.  Row
        ``i`` is bitwise-equal to ``matvec(xs[i])``: vmap batches the
        same per-row program, gather order and reduce tree unchanged."""
        from repro.core.graphs import pad_to_bucket
        if self._shard_parts:
            raise NotImplementedError(
                "matvec_many on a sharded SpMV (vmap over shard_map); "
                "build without mesh=/shards= for batched serving")
        xs = np.asarray(xs)
        if xs.ndim != 2 or xs.shape[1] != self.shape[1]:
            raise ValueError(
                f"matvec_many expects (S, {self.shape[1]}) inputs, "
                f"got {xs.shape}")
        n = xs.shape[0]
        if bucket:
            xs, n = pad_to_bucket(xs)
        if self._vrun is None:
            consts, apply = eng.sweep_parts(self._run)
            jitted = jax.jit(jax.vmap(
                lambda c, x, y0: apply(c, {"x": x}, y0),
                in_axes=(None, 0, None)))
            self._vrun = lambda xs, y0: jitted(consts, xs, y0)
        key = (xs.shape[0], np.dtype(xs.dtype).str)
        if key not in self._batched_shapes:
            self._batched_shapes.add(key)
            from repro.obs import metrics as _metrics
            _metrics.inc("spmv.batched_shapes")
        y0 = self._y0.get(np.dtype(xs.dtype).str)
        if y0 is None:
            y0 = self._y0[np.dtype(xs.dtype).str] = jnp.zeros(
                self.shape[0], dtype=xs.dtype)
        return self._vrun(jnp.asarray(xs), y0)[:n]

    def report(self):
        """Structured :class:`~repro.obs.profile.RunReport`: plan stats,
        IR pass deltas, per-launch cost attribution (and the compiled
        program's HLO-derived flops/bytes when XLA exposes them), tuning
        choice, validation summary, and recorded degradations."""
        from repro.obs.profile import build_report
        dt = self.dtype if np.issubdtype(self.dtype, np.inexact) \
            else np.float32
        example = ({"x": jnp.zeros(self.shape[1], dt)},
                   jnp.zeros(self.shape[0], dt))
        return build_report(self, "SpMV", example=example)


@dataclasses.dataclass
class PageRank:
    plan: BlockPlan
    num_nodes: int
    inv_deg: jnp.ndarray
    dangling: jnp.ndarray
    damping: float
    _run: object
    tuning: object | None = None   # TuningResult when built via backend="auto"
    driver: str = "resident"
    validation: object | None = None    # ValidationReport from from_edges
    degradations: tuple = ()            # DegradationEvents from the build
    # sharded execution (DESIGN.md §10)
    mesh: object | None = None
    _shard_parts: tuple = dataclasses.field(default=(), repr=False)
    # cached per-dtype zero out_init + compiled driver programs
    _zero: dict = dataclasses.field(default_factory=dict, repr=False)
    _progs: dict = dataclasses.field(default_factory=dict, repr=False)

    @classmethod
    def from_edges(cls, src: np.ndarray, dst: np.ndarray, num_nodes: int,
                   damping: float = 0.85, lane_width: int = 128,
                   backend: str = "jax",
                   cost: CostModel | None = None,
                   fused: bool = True,
                   plan_cache_dir: str | None = None,
                   tune: bool = False,
                   tune_cache_dir: str | None = None,
                   driver: str = "resident",
                   validate: str = "strict",
                   mesh=None, shards: int | None = None) -> "PageRank":
        with _trace.span("app.pagerank.build", backend=backend,
                         num_nodes=num_nodes):
            return cls._from_edges(
                src, dst, num_nodes, damping=damping,
                lane_width=lane_width, backend=backend, cost=cost,
                fused=fused, plan_cache_dir=plan_cache_dir, tune=tune,
                tune_cache_dir=tune_cache_dir, driver=driver,
                validate=validate, mesh=mesh, shards=shards)

    @classmethod
    def _from_edges(cls, src, dst, num_nodes, *, damping, lane_width,
                    backend, cost, fused, plan_cache_dir, tune,
                    tune_cache_dir, driver, validate, mesh,
                    shards) -> "PageRank":
        src, dst, _, vreport = validation.validate_edges(
            src, dst, num_nodes, policy=validate)
        seed = pagerank_seed()
        access = {"n2": dst, "n1": src}
        deg = np.bincount(src, minlength=num_nodes).astype(np.float64)
        inv = np.where(deg > 0, 1.0 / np.maximum(deg, 1), 0.0)
        inv_j = jnp.asarray(inv, jnp.float32)
        tuning = None
        shard_parts = ()
        with validation.collect_degradations() as events:
            if backend == "auto" or tune:
                check_auto_kwargs("PageRank.from_edges", backend=backend,
                                  fused=fused, cost=cost, mesh=mesh,
                                  shards=shards)
                from repro.tune import autotune
                rank_ex = jnp.full((num_nodes,), 1.0 / max(num_nodes, 1),
                                   jnp.float32)
                plan, run, tuning = autotune(
                    seed, access, num_nodes, num_nodes, {},
                    {"rank": rank_ex, "inv_nneighbor": inv_j},
                    jnp.zeros(num_nodes, jnp.float32),
                    lane_widths=(lane_width,),
                    tune_cache_dir=tune_cache_dir,
                    plan_cache_dir=plan_cache_dir)
            else:
                mesh, num_shards = resolve_shard_mesh(mesh, shards)
                cost = cost or CostModel(lane_width=lane_width)
                plan = _plan(seed, access, num_nodes, num_nodes, cost,
                             plan_cache_dir)
                if mesh is None:
                    run = eng.make_executor(plan, {}, backend=backend,
                                            fused=fused)
                else:
                    from repro.core import ir
                    tree = ir.lower(plan, backend=backend, fused=fused)
                    shard_parts = tuple(ir.partition_plan(tree, num_shards))
                    run = eng.make_sharded_executor(shard_parts, {}, mesh)
        app = cls(plan=plan, num_nodes=num_nodes,
                  inv_deg=inv_j,
                  dangling=jnp.asarray(deg == 0),
                  damping=damping, _run=run, tuning=tuning, driver=driver,
                  validation=vreport, degradations=tuple(events))
        # mesh is still None on the tuner path (check_auto_kwargs rejects
        # an explicit one there)
        app.mesh = mesh
        app._shard_parts = shard_parts
        return app

    def _zero_init(self, dtype) -> jnp.ndarray:
        key = np.dtype(dtype).str
        z = self._zero.get(key)
        if z is None:
            z = self._zero[key] = jnp.zeros(self.num_nodes, dtype)
        return z

    def sweep(self, rank: jnp.ndarray,
              out_init: jnp.ndarray | None = None) -> jnp.ndarray:
        """One contribution pass: sum[n2] += rank[n1] * inv_deg[n1],
        folded into ``out_init`` (default: the cached zero vector)."""
        if out_init is None:
            out_init = self._zero_init(rank.dtype)
        return self._run({"rank": rank, "inv_nneighbor": self.inv_deg},
                         out_init)

    def _step(self):
        """``(consts, step)``: one full power iteration
        ``step(consts, rank) -> rank`` as a traceable body — contribution
        sweep + dangling-mass reduction + damping fold — and the device
        operands it reads.  Both drivers run exactly this function (the
        host driver jits it standalone, the resident driver embeds it in a
        ``fori_loop``), and the dangling mass uses the pinned-order
        :func:`engine.tree_sum`, so host and resident ranks are bitwise
        identical."""
        sweep_consts, apply = eng.sweep_parts(self._run)
        n = self.num_nodes
        damping = self.damping

        def step(c, rank):
            sc, inv, dangling = c
            contrib = apply(sc, {"rank": rank, "inv_nneighbor": inv},
                            jnp.zeros_like(rank))
            dangling_mass = eng.tree_sum(jnp.where(dangling, rank, 0.0))
            return ((1.0 - damping) / n
                    + damping * (contrib + dangling_mass / n))
        return (sweep_consts, self.inv_deg, self.dangling), step

    def _make_resident_shard(self):
        """The sharded resident driver (DESIGN.md §10): rank lives
        row-sharded as the padded ``(k, S)`` stack inside one jitted
        ``fori_loop``; each iteration all-gathers the shard pieces into
        the full rank vector and every device applies the damping fold to
        its own rows.  Bitwise vs single-device: the dangling mass is
        :func:`engine.tree_sum` over the SAME reassembled full vector on
        every device (identical combine order to :meth:`_step`), never a
        psum of per-shard partial sums."""
        parts = self._shard_parts
        widths, s = eng.shard_widths(parts)
        n = self.num_nodes
        damping = self.damping

        def local_step(apply, c, extra, full_rank, local_prev):
            inv, dangling = extra
            contrib = apply(c, {"rank": full_rank, "inv_nneighbor": inv},
                            jnp.zeros_like(local_prev))
            mass = eng.tree_sum(jnp.where(dangling, full_rank, 0.0))
            return ((1.0 - damping) / n
                    + damping * (contrib + mass / n))

        step = eng.make_sharded_fixpoint_step(
            parts, {}, self.mesh, "rank",
            local_step=local_step,
            extra=(self.inv_deg, self.dangling), with_convergence=False)
        placement = row_sharding(self.mesh)

        def whole_run(c, padded0, num_iters):
            return jax.lax.fori_loop(0, num_iters,
                                     lambda _i, p: step(c, p), padded0)
        jprog = jax.jit(whole_run, donate_argnums=(1,))

        def prog(rank0, num_iters):
            padded = jax.device_put(eng.pad_rows(rank0, widths, s),
                                    placement)
            return eng.unpad_rows(jprog(step.consts, padded, num_iters),
                                  widths)
        self._progs["resident_shard"] = prog
        return prog

    def run(self, iters: int = 20, driver: str | None = None) -> jnp.ndarray:
        """``iters`` power iterations from the uniform distribution.

        ``driver="resident"`` (default) is ONE jitted ``lax.fori_loop``
        dispatch for the whole run — the freshly created rank buffer is
        donated into the loop, which double-buffers the carry in place.
        ``driver="host"`` dispatches one jitted iteration per step (the
        A/B baseline); both return bitwise-identical ranks."""
        with _trace.span("pagerank.run", iters=iters,
                         driver=driver or self.driver):
            return self._run_impl(iters, driver)

    def report(self):
        """Structured :class:`~repro.obs.profile.RunReport`: plan stats,
        IR pass deltas, per-launch cost attribution, tuning choice,
        validation summary, and recorded degradations."""
        from repro.obs.profile import build_report
        return build_report(self, "PageRank")

    def _run_impl(self, iters: int, driver: str | None) -> jnp.ndarray:
        driver = driver or self.driver
        n = self.num_nodes
        rank = jnp.full((n,), 1.0 / n, dtype=jnp.float32)
        if driver == "resident" and self._shard_parts:
            prog = (self._progs.get("resident_shard")
                    or self._make_resident_shard())
            return prog(rank, jnp.asarray(iters, jnp.int32))
        if driver == "resident":
            prog = self._progs.get("resident")
            if prog is None:
                consts, step = self._step()

                def whole_run(c, rank0, num_iters):
                    return jax.lax.fori_loop(0, num_iters,
                                             lambda _i, r: step(c, r), rank0)
                jprog = jax.jit(whole_run, donate_argnums=(1,))

                def prog(rank0, num_iters):
                    return jprog(consts, rank0, num_iters)
                prog._cache_size = jprog._cache_size
                self._progs["resident"] = prog
            # `rank` was created just above and never escapes: donating it
            # is safe, the loop carry reuses its buffer
            return prog(rank, jnp.asarray(iters, jnp.int32))
        if driver != "host":
            raise ValueError(f"unknown driver {driver!r}; "
                             "expected 'resident' or 'host'")
        step = self._progs.get("host")
        if step is None:
            consts, body = self._step()
            jstep = jax.jit(body)

            def step(rank):
                return jstep(consts, rank)
            self._progs["host"] = step
        for _ in range(iters):
            rank = step(rank)
        return rank


def pagerank_reference(src: np.ndarray, dst: np.ndarray, num_nodes: int,
                       damping: float = 0.85, iters: int = 20) -> np.ndarray:
    """Dense numpy oracle for PageRank (tests/benchmarks)."""
    deg = np.bincount(src, minlength=num_nodes).astype(np.float64)
    inv = np.where(deg > 0, 1.0 / np.maximum(deg, 1), 0.0)
    rank = np.full(num_nodes, 1.0 / num_nodes)
    for _ in range(iters):
        contrib = np.zeros(num_nodes)
        np.add.at(contrib, dst, rank[src] * inv[src])
        dangling_mass = rank[deg == 0].sum()
        rank = (1 - damping) / num_nodes + damping * (
            contrib + dangling_mass / num_nodes)
    return rank


# graph applications live in their own module; re-exported here so callers
# have one `repro.core.apps` entry point for every paper §7 workload.
from repro.core.graphs import (BFS, SSSP,  # noqa: E402,F401
                               ConnectedComponents)
