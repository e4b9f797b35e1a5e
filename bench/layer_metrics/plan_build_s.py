"""Host seconds of feature analysis (``build_plan``), from the program's
``plan.build_seconds`` histogram: its sum over the builds in this
process, which builds one app."""
from repro.obs import metrics


def read(ctx):
    h = metrics.histogram_value("plan.build_seconds")
    return h["sum"] if h else None
