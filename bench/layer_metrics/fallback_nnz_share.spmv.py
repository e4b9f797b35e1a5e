"""Percent of the matrix's nonzeros that the built executor runs through
the per-element gather fallback: the program's ``engine.nnz.fallback``
gauge over the sum of ``engine.nnz.{window,coalesced,fallback}``."""
from repro.obs import metrics


def read(ctx):
    nnz = {k: metrics.gauge_value(f"engine.nnz.{k}")
           for k in ("window", "coalesced", "fallback")}
    total = sum(nnz.values())
    return 100.0 * nnz["fallback"] / total if total else None
