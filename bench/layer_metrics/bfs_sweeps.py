"""Mean sweeps per search, from the app's ``convergence.sweeps``."""


def read(ctx):
    return ctx.counters.get("bfs_sweeps")
