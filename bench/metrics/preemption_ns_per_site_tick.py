"""Device time charged to the ``tick/requeue`` and ``tick/realloc``
scopes (victim expiry and re-queue attempt, the immediate reallocation
of each device's victim and the re-queue push) per real site-tick, per
chip."""

from fleetbench.scopes import PREEMPTION, ns_per_site_tick


def read(ctx):
    return ns_per_site_tick(ctx, PREEMPTION)
