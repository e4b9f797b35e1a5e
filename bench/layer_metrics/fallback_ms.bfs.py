"""Device milliseconds a sweep spends in the per-element gather fallback:
own time of the ops under the program's ``stage_a.fallback`` scope in
the traced window (``bench/scope_reduce.py``), over the sweeps of the
searches completed."""
from bench import scope_reduce


def read(ctx):
    sweeps = ctx.completed * ctx.counters.get("bfs_sweeps", 0)
    return scope_reduce.per_call_ms(ctx, "stage_a.fallback", sweeps)
