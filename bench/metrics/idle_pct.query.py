"""Share (%) of the traced window in which no operation ran on the device
(one minus the union of op intervals, averaged over the chips used)."""


def read(ctx):
  return ctx.trace.idle_pct
