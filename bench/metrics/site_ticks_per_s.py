"""Real simulated site-ticks completed per second of the window, per chip.

One site-tick is one replica advanced one frame period.  Padded steps
do not count; every second of the window does (host generation,
transfer, the scan, the reduction)."""


def read(ctx):
    return ctx.real_site_ticks / ctx.elapsed_s / ctx.chips
