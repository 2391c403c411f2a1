"""Device time charged to the ``tick/hp`` scope (HP containment query,
fan-out commit, victim check) per real site-tick, per chip."""

from fleetbench.scopes import HP, ns_per_site_tick


def read(ctx):
    return ns_per_site_tick(ctx, HP)
