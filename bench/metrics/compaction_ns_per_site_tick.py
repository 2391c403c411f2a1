"""Device time of the ops under the compaction ``lax.cond`` per real
site-tick, per chip."""

from fleetbench.layers import COMPACTION


def read(ctx):
    if ctx.trace is None:
        return None
    s = ctx.trace.scope_seconds(COMPACTION)
    if s <= 0.0:
        return None
    return s * 1e9 * ctx.chips / ctx.real_site_ticks
