"""Device time charged to the ``tick/mask`` scope (the padded-tick
select over the whole carry) per real site-tick, per chip."""

from fleetbench.scopes import MASK, ns_per_site_tick


def read(ctx):
    return ns_per_site_tick(ctx, MASK)
