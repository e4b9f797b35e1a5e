"""Host seconds of the app's constructor (plan build, IR passes,
executor build), taken by the benchmark's clock around it."""


def read(ctx):
    return ctx.counters["app_build_s"]
