"""Percent of the nonzeros whose fallback ladder runs in one pass through
VMEM (the fallback kernel for lanes with trailing axes): the program's
``engine.nnz.fallback_vmem`` gauge over the sum of
``engine.nnz.{window,coalesced,fallback}``.  ``None`` where the program
sets no such gauge or runs no launch."""
from repro.obs import metrics


def read(ctx):
    vmem = metrics.gauge_value("engine.nnz.fallback_vmem", None)
    total = sum(metrics.gauge_value(f"engine.nnz.{kind}")
                for kind in ("window", "coalesced", "fallback"))
    if vmem is None or not total:
        return None
    return 100.0 * vmem / total
