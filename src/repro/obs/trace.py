"""Nestable, thread-local spans over the lowering pipeline, on the
profiler's clock.

A *span* is one timed region with a name, attributes, and a parent — the
pipeline opens them around plan builds, validation, cache lookups, IR
passes, tuner rounds, executor construction, and sweep execution, so one
``backend="auto"`` run produces a tree covering
build → validate → lower(per-pass) → tune → execute.

Design constraints (DESIGN.md §11):

* **Disabled is free.**  With tracing off and no profiler session
  collecting, ``span()`` returns a shared singleton no-op context
  manager — no object is allocated, no clock is read, no lock is taken
  (one C++ flag read asks the profiler).  The pinned perf test holds the
  instrumented 1M-nnz plan build under 1% overhead.
* **One clock.**  While a JAX profiler session collects
  (``jax.profiler.trace(dir)``), every span also enters a
  ``jax.profiler.TraceAnnotation`` of its name, so the profile holds the
  program's host spans beside the device operations, on the device
  trace's clock.  That profile is the export: open it in TensorBoard's
  profile plugin or Perfetto.
* **Thread-local nesting, process-global record.**  With tracing on,
  each thread keeps its own open-span stack (the tuner and the serving
  layer run builds concurrently); finished spans land in one
  process-wide list (:func:`finished_spans`), which degradation events
  and tests read, and :func:`tree_dump` renders as an indented text tree.

Enable the records with ``trace.enable()`` or ``REPRO_TRACE=1`` in the
environment.

**Device scopes.**  The engine wraps each launch kind of a sweep program
in ``jax.named_scope`` with one of the names below
(:data:`DEVICE_SCOPES`).  A scope only adds metadata: the name reaches
each HLO op's ``op_name`` and, on the chip, the ``tf_op`` stat of the
op's events in the device trace, where device time is read by scope.
XLA gives an op that it fuses from ops of two scopes the ``op_name`` of
the fusion's root, so such an op counts under the root's scope.
"""
from __future__ import annotations

import functools
import os
import threading
import time

from jax.profiler import TraceAnnotation

__all__ = ["enable", "disable", "enabled", "active", "reset", "span",
           "traced", "current_span_id", "open_spans", "finished_spans",
           "tree_dump", "SpanRecord", "DEVICE_SCOPES", "SCOPE_WINDOW",
           "SCOPE_COALESCED", "SCOPE_FALLBACK", "SCOPE_STAGE_B",
           "SCOPE_FIXPOINT_CHECK"]

# device scopes, one per launch kind of a sweep program (module docstring)
SCOPE_WINDOW = "stage_a.window"        # window and stream tile loads
SCOPE_COALESCED = "stage_a.coalesced"  # dense-slice loads
SCOPE_FALLBACK = "stage_a.fallback"    # per-element gather + its reduce
SCOPE_STAGE_B = "stage_b"              # the write-back, every form
SCOPE_FIXPOINT_CHECK = "fixpoint.check"  # resident loop's equality/health
DEVICE_SCOPES = (SCOPE_WINDOW, SCOPE_COALESCED, SCOPE_FALLBACK,
                 SCOPE_STAGE_B, SCOPE_FIXPOINT_CHECK)

# C++ flag read: is a profiler session collecting host annotations?
_profiling = TraceAnnotation.is_enabled

_enabled = os.environ.get("REPRO_TRACE", "").lower() not in (
    "", "0", "false", "off")
_lock = threading.Lock()
_next_id = 0
_finished: list["SpanRecord"] = []
_tls = threading.local()


class SpanRecord:
    """One finished span (immutable-by-convention export record)."""

    __slots__ = ("span_id", "parent_id", "name", "start_ns", "end_ns",
                 "attrs", "thread_id")

    def __init__(self, span_id, parent_id, name, start_ns, end_ns, attrs,
                 thread_id):
        self.span_id = span_id
        self.parent_id = parent_id
        self.name = name
        self.start_ns = start_ns
        self.end_ns = end_ns
        self.attrs = attrs
        self.thread_id = thread_id

    @property
    def duration_ns(self) -> int:
        return self.end_ns - self.start_ns

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return (f"SpanRecord({self.name!r}, id={self.span_id}, "
                f"dur={self.duration_ns / 1e6:.3f}ms, attrs={self.attrs})")


def _stack() -> list:
    st = getattr(_tls, "stack", None)
    if st is None:
        st = _tls.stack = []
    return st


class _Span:
    """A live (open) span; becomes a :class:`SpanRecord` on exit."""

    __slots__ = ("name", "attrs", "span_id", "parent_id", "start_ns",
                 "_annotation")

    def __init__(self, name: str, attrs: dict):
        self.name = name
        self.attrs = attrs
        self._annotation = None

    def set(self, **attrs) -> "_Span":
        self.attrs.update(attrs)
        return self

    def __enter__(self) -> "_Span":
        global _next_id
        with _lock:
            _next_id += 1
            self.span_id = _next_id
        stack = _stack()
        self.parent_id = stack[-1].span_id if stack else None
        stack.append(self)
        if _profiling():
            self._annotation = TraceAnnotation(self.name)
            self._annotation.__enter__()
        self.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        end_ns = time.perf_counter_ns()
        if self._annotation is not None:
            self._annotation.__exit__(exc_type, exc, tb)
        stack = _stack()
        # tolerate imbalance (a leaked child) rather than corrupting the
        # stack: pop self specifically
        if stack and stack[-1] is self:
            stack.pop()
        else:  # pragma: no cover - defensive
            try:
                stack.remove(self)
            except ValueError:
                pass
        if exc_type is not None:
            self.attrs.setdefault("error", exc_type.__name__)
        rec = SpanRecord(self.span_id, self.parent_id, self.name,
                         self.start_ns, end_ns, self.attrs,
                         threading.get_ident())
        with _lock:
            _finished.append(rec)
        return False


class _NopSpan:
    """Shared do-nothing span: the entire disabled-tracing fast path."""

    __slots__ = ()

    def set(self, **attrs) -> "_NopSpan":
        return self

    def __enter__(self) -> "_NopSpan":
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        return False


_NOP = _NopSpan()


class _AnnotationSpan:
    """A span with tracing off while a profiler session collects: the
    profiler's annotation alone, with no record."""

    __slots__ = ("_annotation",)

    def __init__(self, name: str):
        self._annotation = TraceAnnotation(name)

    def set(self, **attrs) -> "_AnnotationSpan":
        return self

    def __enter__(self) -> "_AnnotationSpan":
        self._annotation.__enter__()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self._annotation.__exit__(exc_type, exc, tb)
        return False


def span(name: str, **attrs):
    """Open a span.  Use as ``with trace.span("plan.build", nnz=n) as sp:``
    and add result attributes via ``sp.set(...)`` before the block exits.
    With tracing disabled this returns a shared no-op singleton, or,
    while a profiler session collects, the profiler's annotation alone."""
    if not _enabled:
        return _AnnotationSpan(name) if _profiling() else _NOP
    return _Span(name, attrs)


def traced(name: str, **static_attrs):
    """Decorator form of :func:`span` for functions whose whole body is
    one region (validators, app constructors).  The disabled path is a
    module-global check and a profiler flag read before delegating."""
    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            if not (_enabled or _profiling()):
                return fn(*args, **kwargs)
            with span(name, **static_attrs):
                return fn(*args, **kwargs)
        return wrapper
    return deco


# ------------------------------------------------------------- control
def enable() -> None:
    global _enabled
    _enabled = True


def disable() -> None:
    global _enabled
    _enabled = False


def enabled() -> bool:
    return _enabled


def active() -> bool:
    """Would :func:`span` record or annotate?  Hot paths test this before
    they build a span's attributes."""
    return _enabled or _profiling()


def reset() -> None:
    """Drop all finished spans and this thread's open stack (tests)."""
    global _next_id
    with _lock:
        _finished.clear()
        _next_id = 0
    _stack().clear()


# ------------------------------------------------------------ inspection
def current_span_id() -> int | None:
    """Id of the innermost open span on THIS thread (None when tracing
    is disabled or no span is open) — degradation events record it."""
    if not _enabled:
        return None
    stack = _stack()
    return stack[-1].span_id if stack else None


def open_spans() -> list[str]:
    """Names of this thread's currently-open spans, outermost first —
    must be empty between pipeline operations (the leak test)."""
    return [s.name for s in _stack()]


def finished_spans() -> list[SpanRecord]:
    with _lock:
        return list(_finished)


# --------------------------------------------------------------- export
def tree_dump() -> str:
    """Plain-text span tree (per thread, chronological)."""
    recs = finished_spans()
    children: dict = {}
    roots = []
    for rec in recs:
        if rec.parent_id is None:
            roots.append(rec)
        else:
            children.setdefault(rec.parent_id, []).append(rec)
    lines: list[str] = []

    def walk(rec: SpanRecord, depth: int) -> None:
        attrs = " ".join(f"{k}={v}" for k, v in rec.attrs.items())
        lines.append(f"{'  ' * depth}{rec.name}  "
                     f"{rec.duration_ns / 1e6:.3f}ms"
                     f"{('  [' + attrs + ']') if attrs else ''}")
        for child in sorted(children.get(rec.span_id, []),
                            key=lambda r: r.start_ns):
            walk(child, depth + 1)

    for root in sorted(roots, key=lambda r: r.start_ns):
        walk(root, 0)
    return "\n".join(lines)
