"""Candidate variant space for the input-adaptive autotuner.

The paper's core observation is that the right code variant is "unknown
until runtime due to input dependence": the same engine exposes several
genuinely different execution strategies (XLA fused vs per-class launch
lists, the CPU-optimal single segment-reduce form, the Pallas TPU
kernels, both write-backs, and the CostModel knobs that reshape the plan
itself), and the measured winner flips across matrices.  This module
declares that space once — a :class:`Candidate` is one fully-specified
configuration — and applies the *validity rules* that keep the tuner from
ever measuring a configuration that cannot run (or cannot run honestly)
on the current platform/seed:

* ``pallas`` is skipped off-accelerator unless interpret-mode candidates
  are explicitly requested (interpret timings are not wall-clock
  comparable);
* ``segsum`` requires the reduce to have a ``jax.ops.segment_*`` form;
* ``segsum`` ignores ``fused``/``stage_b`` (stage A+B collapse into one
  segment reduce), so those axes are canonicalized away to keep the
  space free of duplicate configurations;
* ``stage_b="dense"`` only exists for the jax/pallas backends;
* the per-launch kernel-param axes (``kernel_rows`` — stage-A grid rows
  per step, ``kernel_prefetch`` — metadata DMA tile depth) exist only
  for ``pallas`` candidates, and ``kernel_prefetch`` only where the
  lowering has scalar prefetch (TPU / interpret; the Triton form reads
  metadata through full-view refs, so the knob would be a silent no-op
  on GPU and is rejected rather than measured twice).  Both steer only
  the per-tile window form and the coalesced form: a window launch whose
  views fit VMEM runs the resident form, which takes neither.

``coalesce`` is a real axis for both lane-granular emitters now that the
Pallas lowering consumes ``coalesce_gathers``-rewritten launches
(dense-slice loads, DESIGN.md §13); only segsum canonicalizes it away.
"""
from __future__ import annotations

import dataclasses

from repro.core.plan import CostModel
from repro.core.seed import CodeSeed

# reduces with a jax.ops.segment_* lowering (engine's segsum backend)
SEGMENT_REDUCES = frozenset({"add", "mul", "max", "min"})

_BACKENDS = ("jax", "segsum", "pallas")
_STAGE_BS = ("gather", "dense")


@dataclasses.dataclass(frozen=True)
class Candidate:
    """One point of the variant space — everything needed to build an
    executor: the plan shape (``lane_width``, ``max_windows_replace`` are
    CostModel inputs, so they select which *plan* is built) and the
    execution strategy on top of it."""

    backend: str = "jax"               # "jax" | "segsum" | "pallas"
    fused: bool = True
    stage_b: str = "gather"            # "gather" | "dense"
    lane_width: int = 128
    max_windows_replace: int | None = None
    coalesce: bool = False             # ir.coalesce_gathers lowering pass
    shards: int = 1                    # row shards over a device mesh (§10)
    # per-launch Pallas kernel params (None = emitter default of 1).
    # Upper bounds, not exact values: the kernels realize the largest
    # divisor of the block count, so results are bitwise-stable across
    # every setting and the axes are pure performance knobs.
    kernel_rows: int | None = None     # stage-A grid rows per step
    kernel_prefetch: int | None = None  # metadata DMA tile depth (TPU)

    @property
    def plan_key(self) -> tuple:
        """Candidates with equal plan keys share one BlockPlan (and the
        reorder work that goes with it).  ``shards`` is deliberately NOT
        part of the key: every shard count partitions the same parent
        plan (``ir.partition_plan`` slices, it never re-analyzes)."""
        return (self.lane_width, self.max_windows_replace)

    def cost_model(self) -> CostModel:
        return CostModel(lane_width=self.lane_width,
                         max_windows_replace=self.max_windows_replace)

    @property
    def label(self) -> str:
        mode = "fused" if self.fused else "per_class"
        cut = ("" if self.max_windows_replace is None
               else f"/w{self.max_windows_replace}")
        co = "/co" if self.coalesce else ""
        sh = f"/s{self.shards}" if self.shards > 1 else ""
        kr = "" if self.kernel_rows is None else f"/kr{self.kernel_rows}"
        kp = ("" if self.kernel_prefetch is None
              else f"/kp{self.kernel_prefetch}")
        return (f"{self.backend}/{mode}/{self.stage_b}"
                f"/n{self.lane_width}{cut}{co}{sh}{kr}{kp}")

    @property
    def kernel_params(self) -> dict | None:
        """The ``kernel_params`` mapping :func:`engine.make_executor`
        consumes, or None when every knob is at its emitter default."""
        kp: dict = {}
        if self.kernel_rows is not None:
            kp["rows_per_step"] = self.kernel_rows
        if self.kernel_prefetch is not None:
            kp["meta_prefetch"] = self.kernel_prefetch
        return kp or None

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "Candidate":
        fields = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in fields})


def default_platform() -> str:
    import jax
    return jax.devices()[0].platform


def canonicalize(c: Candidate) -> Candidate:
    """Collapse don't-care axes so the space holds no duplicate configs:
    the segsum backend has a single form (stage A+B are one segment
    reduce), so ``fused``/``stage_b`` are fixed to their defaults; both
    lane-granular emitters consume ``coalesce_gathers``-rewritten
    launches now (DESIGN.md §13), so ``coalesce`` only canonicalizes
    away for segsum; the kernel-param axes steer the Pallas emitters
    alone, so they are fixed to None everywhere else."""
    if c.backend == "segsum":
        c = dataclasses.replace(c, fused=True, stage_b="gather")
    if c.backend not in ("jax", "pallas") and c.coalesce:
        c = dataclasses.replace(c, coalesce=False)
    if c.backend != "pallas" and (c.kernel_rows is not None
                                  or c.kernel_prefetch is not None):
        c = dataclasses.replace(c, kernel_rows=None, kernel_prefetch=None)
    return c


def is_valid(c: Candidate, seed: CodeSeed, platform: str,
             allow_interpret: bool = False,
             devices: int | None = None) -> bool:
    """The platform/seed validity rules (module docstring).  ``devices``
    (when given) caps the shard axis at the visible device count so the
    tuner never measures a mesh it cannot build."""
    if c.backend not in _BACKENDS or c.stage_b not in _STAGE_BS:
        return False
    if c.lane_width < 2:
        return False
    if (c.backend == "pallas" and platform not in ("tpu", "gpu")
            and not allow_interpret):
        return False
    if c.backend == "segsum" and seed.reduce not in SEGMENT_REDUCES:
        return False
    if c.shards < 1:
        return False
    if c.shards > 1 and c.backend == "pallas":
        # partition_plan refuses pallas subtrees (shard_map over the
        # kernel emitters is not wired)
        return False
    if devices is not None and c.shards > devices:
        return False
    for knob in (c.kernel_rows, c.kernel_prefetch):
        if knob is not None and not (1 <= knob <= 64):
            return False
    if c.kernel_prefetch is not None and platform == "gpu":
        # the Triton form has no scalar prefetch — metadata rides in
        # full-view refs, so the knob would time the same kernel twice
        return False
    return True


# default per-launch kernel-param axes swept for pallas candidates on
# accelerator platforms (None = emitter default).  Kept to one non-default
# point per knob so the accelerator space stays measurable; widen via the
# ``kernel_rows_axis`` / ``kernel_prefetch_axis`` arguments.
_KERNEL_ROWS_AXIS = (None, 8)
_KERNEL_PREFETCH_AXIS = (None, 4)


def candidate_space(seed: CodeSeed, *, platform: str | None = None,
                    backends: tuple = _BACKENDS,
                    lane_widths: tuple = (128,),
                    window_cutoffs: tuple = (None,),
                    shard_counts: tuple = (1,),
                    allow_interpret: bool = False,
                    kernel_rows_axis: tuple = _KERNEL_ROWS_AXIS,
                    kernel_prefetch_axis: tuple = _KERNEL_PREFETCH_AXIS,
                    ) -> list["Candidate"]:
    """Enumerate the valid, canonical candidate list for ``seed`` on
    ``platform`` — the declarative product space filtered by
    :func:`is_valid` and deduplicated through :func:`canonicalize`.

    The default axes give 9 candidates on CPU (8 jax forms: fused x
    stage_b x coalesce, + segsum); accelerator platforms add the Pallas
    forms (fused x stage_b x coalesce, crossed with the kernel-param
    axes — rows-per-step everywhere, metadata prefetch where the
    lowering has scalar prefetch).  Widening ``lane_widths`` /
    ``window_cutoffs`` multiplies the *plan* axis, which the search
    harness shares per :attr:`Candidate.plan_key`.
    """
    platform = platform or default_platform()
    devices = None
    if any(k > 1 for k in shard_counts):
        import jax
        devices = len(jax.devices())
    out: list[Candidate] = []
    seen: set[Candidate] = set()
    for n in lane_widths:
        for cut in window_cutoffs:
            for k in shard_counts:
                for backend in backends:
                    kr_axis = (kernel_rows_axis if backend == "pallas"
                               else (None,))
                    kp_axis = (kernel_prefetch_axis if backend == "pallas"
                               else (None,))
                    for fused in (True, False):
                        for stage_b in _STAGE_BS:
                            for coalesce in (False, True):
                                for kr in kr_axis:
                                    for kp in kp_axis:
                                        c = Candidate(
                                            backend=backend, fused=fused,
                                            stage_b=stage_b, lane_width=n,
                                            max_windows_replace=cut,
                                            coalesce=coalesce, shards=k,
                                            kernel_rows=kr,
                                            kernel_prefetch=kp)
                                        if not is_valid(c, seed, platform,
                                                        allow_interpret,
                                                        devices):
                                            continue
                                        c = canonicalize(c)
                                        if c in seen:
                                            continue
                                        seen.add(c)
                                        out.append(c)
    return out


def space_signature(candidates: list[Candidate]) -> str:
    """Stable textual identity of a candidate list — part of the tuning
    cache key, so a changed space (new backend, new knob) re-tunes instead
    of replaying a choice made over a different menu."""
    return ";".join(sorted(c.label for c in candidates))
