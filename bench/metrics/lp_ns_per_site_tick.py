"""Device time charged to the ``tick/lp`` scope (the ``MAX_LP`` task
placements of every device: 16 of the 21 placement launches of a tick
at 4 devices) per real site-tick, per chip."""

from fleetbench.scopes import LP, ns_per_site_tick


def read(ctx):
    return ns_per_site_tick(ctx, LP)
