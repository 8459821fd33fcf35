"""Requests per micro-batch drain over the window
(``BatcherStats.mean_occupancy`` of the window's drains)."""


def read(ctx):
  return ctx.counters.get("occupancy")
