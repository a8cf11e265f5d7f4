"""Share of the bf16 peak that the expert layer's grouped matmuls reach: the
FLOPs of the held rows the window computed (``moe_rows`` summed over its
steps, ``bench/work_moe.py``) over the device time of the ops named
``moe_gmm*`` and ``moe_tgmm*`` (a trace names an op by its HLO text,
``%moe_gmm.79 = bf16[...] custom-call(...)``).  None where the program has
no such ops."""

NAMES = ("moe_gmm", "moe_tgmm")


def read(ctx):
    flops = (ctx.work or {}).get("expert_gmm_flops")
    if not flops or ctx.trace is None:
        return None
    seconds = sum(s for op, s in ctx.trace["op_s"].items()
                  if op.lstrip("%").startswith(NAMES))
    if seconds <= 0:
        return None
    return 100.0 * flops / (seconds * ctx.peaks["bf16_flops_per_s"])
