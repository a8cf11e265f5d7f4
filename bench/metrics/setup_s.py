"""Set-up: process start to the first timed request, compilation included."""


def read(ctx):
    return ctx.setup_s
