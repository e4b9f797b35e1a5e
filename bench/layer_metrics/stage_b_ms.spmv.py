"""Device milliseconds a matvec spends in the write-back: own time of the
ops under the program's ``stage_b`` scope in the traced window
(``bench/scope_reduce.py``), over the matvecs completed."""
from bench import scope_reduce


def read(ctx):
    return scope_reduce.per_call_ms(ctx, "stage_b", ctx.completed)
