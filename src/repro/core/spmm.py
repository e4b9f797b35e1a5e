"""SpMM: sparse x dense matrix product on the Intelligent-Unroll plan.

``Y = A_sparse @ B`` generalizes the paper's SpMV seed to row-vector
values, and since the engine's stage A / stage B are **rank-polymorphic**
over trailing lane axes (DESIGN.md §8), SpMM is literally the SpMV
program executed with a 2-D lane: the gather through ``col`` fetches
whole rows of B (``(Bc, N, D)`` instead of ``(Bc, N)``), the per-nnz
``value`` array broadcasts with a trailing singleton axis, and the §5
ladder plus the merged write-back reduce along the lane axis only.

There is no separate SpMM executor any more: ``from_coo`` builds the same
``engine.make_executor`` the SpMV path uses, which means SpMM gets the
full semiring reduce set (``reduce="min"/"max"/"mul"``), the fused /
per-class launch lists, the segsum backend, the gather-coalescing pass,
``backend="pallas"`` (the kernel ladder is rank-polymorphic over
trailing lane axes too — BlockSpecs carry the trailing shape and the
lane metadata broadcasts, DESIGN.md §13), and ``backend="auto"``
input-adaptive tuning — all from one pipeline.

Reuses the 1-D BlockPlan verbatim: the plan is a property of the access
arrays only (the paper's point) — the value rank is an execution detail.
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np

from repro.core import engine as eng
from repro.core import validate as validation
from repro.core.mesh import make_shard_mesh, resolve_shard_mesh
from repro.core.plan import BlockPlan, CostModel
from repro.core.seed import spmv_seed
from repro.obs import trace as _trace

_BACKENDS = ("jax", "segsum", "pallas", "auto")


@dataclasses.dataclass
class SpMM:
    plan: BlockPlan
    shape: tuple[int, int]
    _run: object
    reduce: str = "add"
    tuning: object | None = None   # TuningResult when built via backend="auto"
    validation: object | None = None    # ValidationReport from from_coo
    degradations: tuple = ()            # DegradationEvents from the build
    # sharded execution (DESIGN.md §10)
    mesh: object | None = None
    _shard_parts: tuple = dataclasses.field(default=(), repr=False)

    @classmethod
    def from_coo(cls, rows: np.ndarray, cols: np.ndarray, vals: np.ndarray,
                 shape: tuple[int, int], lane_width: int = 128,
                 backend: str = "jax",
                 cost: CostModel | None = None,
                 fused: bool = True,
                 stage_b: str = "auto",
                 coalesce: bool = False,
                 reduce: str = "add",
                 plan_cache_dir: str | None = None,
                 tune: bool = False,
                 tune_cache_dir: str | None = None,
                 validate: str = "strict",
                 allow_interpret: bool = False,
                 mesh=None, shards: int | None = None) -> "SpMM":
        """``allow_interpret=True`` admits interpret-mode Pallas
        candidates into the ``backend="auto"`` / ``tune=True`` space
        off-accelerator (their timings are not wall-clock comparable, so
        they are excluded by default; the tuning cache key folds the
        platform, so an interpret winner can never replay as an
        accelerator choice)."""
        with _trace.span("app.spmm.build", backend=backend,
                         nnz=int(np.asarray(vals).size)):
            return cls._from_coo(
                rows, cols, vals, shape, lane_width=lane_width,
                backend=backend, cost=cost, fused=fused, stage_b=stage_b,
                coalesce=coalesce, reduce=reduce,
                plan_cache_dir=plan_cache_dir, tune=tune,
                tune_cache_dir=tune_cache_dir, validate=validate,
                allow_interpret=allow_interpret, mesh=mesh, shards=shards)

    @classmethod
    def _from_coo(cls, rows, cols, vals, shape, *, lane_width, backend,
                  cost, fused, stage_b, coalesce, reduce, plan_cache_dir,
                  tune, tune_cache_dir, validate, allow_interpret, mesh,
                  shards) -> "SpMM":
        from repro.core import planio
        if backend not in _BACKENDS:
            raise ValueError(
                f"SpMM supports backend in {_BACKENDS} (got {backend!r})")
        seed = spmv_seed(reduce=reduce)
        # repair combines duplicates with THIS product's semiring reduce —
        # min/max/mul dedup differently from add (DESIGN.md §9)
        rows, cols, vals, vreport = validation.validate_coo(
            rows, cols, np.asarray(vals), shape, policy=validate,
            reduce=reduce)
        access = {"row": rows, "col": cols}
        with validation.collect_degradations() as events:
            if backend == "auto" or tune:
                from repro.core.graphs import check_auto_kwargs
                # shards= is a tuned axis (as in SpMV); mesh= conflicts
                check_auto_kwargs("SpMM.from_coo", backend=backend,
                                  fused=fused, stage_b=stage_b, cost=cost,
                                  coalesce=coalesce, mesh=mesh)
                from repro.tune import autotune, candidate_space
                shard_counts = (1,)
                if shards is not None:
                    make_shard_mesh(int(shards))   # validate, with recipe
                    shard_counts = tuple(sorted({1, int(shards)}))
                space = candidate_space(
                    seed, lane_widths=(lane_width,),
                    shard_counts=shard_counts,
                    allow_interpret=allow_interpret)
                rng = np.random.default_rng(0)
                b_ex = jnp.asarray(rng.standard_normal(
                    (shape[1], 8)).astype(np.float32))
                y0 = jnp.full((shape[0], 8), seed.reduce_identity,
                              jnp.float32)
                plan, run, result = autotune(
                    seed, access, shape[0], shape[1], {"value": vals},
                    {"x": b_ex}, y0, space=space,
                    tune_cache_dir=tune_cache_dir,
                    plan_cache_dir=plan_cache_dir,
                    cache_extra="spmm:d8")
                app = cls(plan=plan, shape=shape, _run=run, reduce=reduce,
                          tuning=result, mesh=getattr(run, "mesh", None),
                          _shard_parts=tuple(getattr(run, "parts", ())))
            else:
                mesh, num_shards = resolve_shard_mesh(mesh, shards)
                cost = cost or CostModel(lane_width=lane_width)
                plan = planio.cached_build_plan(seed, access,
                                                out_len=shape[0],
                                                data_len=shape[1], cost=cost,
                                                cache_dir=plan_cache_dir)
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
                app = cls(plan=plan, shape=shape, _run=run, reduce=reduce,
                          mesh=mesh, _shard_parts=parts)
        app.validation = vreport
        app.degradations = tuple(events)
        return app

    def matmat(self, bmat: jnp.ndarray,
               y_init: jnp.ndarray | None = None) -> jnp.ndarray:
        """``Y = A @ bmat`` folded into ``y_init``.  Without ``y_init``
        the reduce identity is made inside the product's program, so a
        wide product holds one ``Y`` on the device, not two."""
        return self._run({"x": bmat}, y_init)

    def report(self):
        """Structured :class:`~repro.obs.profile.RunReport`: plan stats,
        IR pass deltas, per-launch cost attribution, tuning choice,
        validation summary, and recorded degradations."""
        from repro.core.seed import reduce_identity_for
        from repro.obs.profile import build_report
        example = ({"x": jnp.zeros((self.shape[1], 8), jnp.float32)},
                   jnp.full((self.shape[0], 8),
                            reduce_identity_for(self.reduce, np.float32),
                            jnp.float32))
        return build_report(self, "SpMM", example=example)
