"""Share (%) of the ``facility_gain`` kernel's roofline over its traced
calls (round-1 lazy rescans and round 2 of the epoch)."""


def read(ctx):
  return ctx.roofline("facility_gain")
