"""1 - (union of device op intervals) / traced window, averaged over the
cell's chips, in percent."""


def read(ctx):
    if ctx.trace is None:
        return None
    return 100.0 * ctx.trace.idle_share()
