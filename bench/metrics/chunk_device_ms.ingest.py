"""Device busy time per 1024-row append chunk, in ms, from the trace."""


def read(ctx):
  chunks = ctx.counters.get("chunks")
  if not chunks:
    return None
  return 1e3 * ctx.trace.busy_s / chunks
