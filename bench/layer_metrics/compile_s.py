"""Seconds of set-up inside JAX's compile events (trace, lower, compile
or load from the persistent cache), as a union of their spans."""


def read(ctx):
    return ctx.counters["compile_s"]
