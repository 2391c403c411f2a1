"""Device time of the ops under ``fused_place*`` (the kernel with its
boundary transposes, or the jnp oracle) per real site-tick, per chip."""

from fleetbench.layers import PLACEMENT


def read(ctx):
    if ctx.trace is None:
        return None
    s = ctx.trace.scope_seconds(PLACEMENT)
    if s <= 0.0:
        return None
    return s * 1e9 * ctx.chips / ctx.real_site_ticks
