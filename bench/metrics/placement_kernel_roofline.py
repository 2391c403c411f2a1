"""Least time over measured time of the placement kernel alone, in
percent.

The least time is ``placement_roofline``'s: the bytes the real steps of
the window must move through placement (``fleetbench.roofline``), over
the chip's peak HBM bandwidth; bandwidth bounds it.  The measured time
is the device time charged to the ``placement/kernel`` scope (the Pallas
launches), per chip, without the boundary layout work around them."""

from fleetbench.scopes import (PLACEMENT_KERNEL, charged_seconds,
                               placement_least_seconds)


def read(ctx):
    if ctx.trace is None:
        return None
    measured = charged_seconds(ctx.trace, PLACEMENT_KERNEL)
    if measured <= 0.0:
        return None
    return 100.0 * placement_least_seconds(ctx) / measured
