"""Share of the traced window in which no operation ran on the device."""
from bench import trace


def read(ctx):
    return trace.idle_share(ctx.trace)
