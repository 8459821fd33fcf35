"""Round-1 lazy tiles rescanned per epoch, summed over shards (the
device-fed ``GreediResult.r1_rescans``)."""


def read(ctx):
  return ctx.counters.get("r1_rescans_per_epoch")
