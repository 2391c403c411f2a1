"""Device time of the engine's device loop.

The segment scan runs a tick's per-device phases (``tick/hp``,
``tick/realloc``, ``tick/lp``) in one rolled loop over the site's
devices, under the named scope ``tick/device`` (``fleet/engine.py``).
What the loop adds of its own (its index, its condition, the carry it
passes from one trip to the next) is charged to ``tick/device`` and to
no phase inside it.

Both readings use the charging rule of ``fleetbench.scopes`` and read
nothing (``None``) on a trace of a program without ``tick/device``.
"""

from __future__ import annotations

import re

from fleetbench.scopes import charged_seconds, ns_per_site_tick

#: everything the device loop runs, its phases included.
DEVICE = re.compile(r"(^|/)tick/device(/|$)")
#: the loop's own work: under ``tick/device`` and under no scope
#: ``tick/<phase>`` inside it.
DEVICE_OWN = re.compile(r"(^|/)tick/device(/|$)(?!.*/tick/)")


def overhead_share(ctx) -> float | None:
    """Device self time charged to the loop's own work, in percent of the
    device's busy time, averaged over the cell's chips."""
    if ctx.trace is None or charged_seconds(ctx.trace, DEVICE) <= 0.0:
        return None
    own = charged_seconds(ctx.trace, DEVICE_OWN)
    return 100.0 * own / ctx.trace.mean_busy_s()


def tick_ns(ctx) -> float | None:
    """Device time charged to the device loop per real site-tick, per
    device of the site, per chip: what one device's release costs."""
    ns = ns_per_site_tick(ctx, DEVICE)
    if ns is None:
        return None
    return ns / ctx.config["site"]["n_devices"]
