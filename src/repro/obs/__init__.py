"""Observability layer: structured tracing, a unified metrics registry,
and static per-launch cost attribution (DESIGN.md §11).

The rest of the pipeline imports these modules unconditionally — with
tracing disabled every span is one shared no-op object (pinned by
``tests/test_obs.py``), so there is no "instrumented build" vs "fast
build" split to keep in sync.

``repro.obs`` is a leaf package: it imports only the standard library
and JAX's profiler, so every layer of the pipeline — validate, plan,
planio, ir, engine, tune, graphs, apps — can depend on it without
cycles.
"""
from repro.obs import metrics, trace
from repro.obs.log import get_logger
from repro.obs.profile import RunReport, build_report

__all__ = ["metrics", "trace", "get_logger", "RunReport", "build_report"]
