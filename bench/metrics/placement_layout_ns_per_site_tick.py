"""Device time charged to the ``placement/layout`` scope (the kernel
boundary: replica-last transposes in and out, dtype casts, lane padding,
and the unnamed copies charged to them) per real site-tick, per chip."""

from fleetbench.scopes import PLACEMENT_LAYOUT, ns_per_site_tick


def read(ctx):
    return ns_per_site_tick(ctx, PLACEMENT_LAYOUT)
