"""Share (%) of the ``pairwise`` kernel's roofline over its traced calls."""


def read(ctx):
  return ctx.roofline("pairwise")
