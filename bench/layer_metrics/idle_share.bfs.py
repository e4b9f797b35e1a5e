"""Percent of the traced window in which no operation ran on the device,
in a search cell."""


def read(ctx):
    return 100.0 * ctx.trace.idle_share
