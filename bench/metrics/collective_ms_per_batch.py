"""Device time of the all-reduce ops per batch, per chip, in ms (the
on-device per-cell reduction of a sharded sweep)."""

from fleetbench.layers import COLLECTIVE


def read(ctx):
    if ctx.trace is None:
        return None
    s = ctx.trace.seconds_where(lambda o: bool(COLLECTIVE.match(o.opcode)))
    if s <= 0.0:
        return None
    batches = ctx.calls * -(-ctx.replicas_per_call // ctx.traffic["batch_size"])
    return 1e3 * s / batches
