"""Device self time charged to the device loop's own work (``tick/device``
and no ``tick/<phase>`` scope inside it: the loop index, its condition,
the carry between trips), in percent of busy time, per chip."""

from fleetbench.device_loop import overhead_share


def read(ctx):
    return overhead_share(ctx)
