"""Share of the window spent inside ``make_workload`` as ``run_sweep``
calls it (the benchmark's wrapper, a host clock), in percent."""


def read(ctx):
    return 100.0 * ctx.host_seconds.get("host_gen", 0.0) / ctx.elapsed_s
