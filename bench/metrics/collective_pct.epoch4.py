"""Share (%) of device time in all-to-all, all-gather, all-reduce,
reduce-scatter and collective-permute operations, from the trace."""


def read(ctx):
  total = ctx.trace.device_total_s()
  if total <= 0.0:
    return None
  return 100.0 * sum(ctx.trace.collective_s.values()) / total
