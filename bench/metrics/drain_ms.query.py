"""Mean wall time of one micro-batch drain (the ``batcher.drain`` span, as
the service's drain-wall histogram records it), in ms."""


def read(ctx):
  return ctx.counters.get("drain_ms")
