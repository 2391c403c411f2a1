"""Device time charged to ``tick/device`` and the phases inside it, per
real site-tick, per device of the site, per chip: one device's frame
release (its HP commit, re-placement and LP launches)."""

from fleetbench.device_loop import tick_ns


def read(ctx):
    return tick_ns(ctx)
