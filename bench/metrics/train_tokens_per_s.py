"""Useful tokens of the window's training jobs (replayed steps not counted)
over the window's whole wall time, saves, kill and resume included."""


def read(ctx):
    tokens = ctx.samples.get("useful_tokens")
    return tokens / ctx.window_s if tokens else None
