"""Host seconds of executor builds (IR lowering, nonzero reorder, device
staging of the plan operands), from the program's
``engine.build_seconds`` histogram: its sum over the builds in this
process, which builds one app."""
from repro.obs import metrics


def read(ctx):
    h = metrics.histogram_value("engine.build_seconds")
    return h["sum"] if h else None
