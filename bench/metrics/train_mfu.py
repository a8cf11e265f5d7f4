"""Model FLOP utilisation of the training job: forward and backward FLOPs per
token (bench/work.py) x useful tokens per second over the bf16 peak."""


def read(ctx):
    tokens = ctx.samples.get("useful_tokens")
    if not tokens or ctx.trace is None:
        return None
    rate = tokens / ctx.window_s
    return 100.0 * ctx.work["train_flops_per_token"] * rate \
        / ctx.peaks["bf16_flops_per_s"]
