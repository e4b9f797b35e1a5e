"""Percent of the window kernel's nonzeros that run with the gathered
views resident in VMEM: the program's ``engine.nnz.window_resident``
gauge over ``engine.nnz.window``.  ``None`` where the program sets no
such gauge or runs no window launch."""
from repro.obs import metrics


def read(ctx):
    resident = metrics.gauge_value("engine.nnz.window_resident", None)
    window = metrics.gauge_value("engine.nnz.window")
    if resident is None or not window:
        return None
    return 100.0 * resident / window
