"""Process start to the first measured call: JAX and TPU start-up, the
compile or cache read, the warm-up call."""


def read(ctx):
    return ctx.setup_s
