"""Share (%) of the fused information-gain select kernel's roofline over
its traced calls (one full pass over the shard per round-1 standard
step)."""


def read(ctx):
  return ctx.roofline("info_select")
