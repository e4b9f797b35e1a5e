"""Row partitions one product runs in, from the program's
``engine.lane_partitions`` gauge (set when the product's program is
traced); ``None`` where the program sets no such gauge."""
from repro.obs import metrics


def read(ctx):
    return metrics.gauge_value("engine.lane_partitions", None)
