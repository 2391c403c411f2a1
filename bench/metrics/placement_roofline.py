"""Least time over measured time of the placement layer, in percent.

The least time is the bytes that the real steps of the window must
move through placement (``fleetbench.roofline``: the window state read
and written once per step, every attempt's operands and results),
over the chip's peak HBM bandwidth.  Bandwidth bounds it.  The measured
time is the device time of the ops under ``fused_place*``, per chip."""

from fleetbench.layers import PLACEMENT
from fleetbench.roofline import least_seconds, placement_bytes_per_step


def read(ctx):
    if ctx.trace is None:
        return None
    measured = ctx.trace.scope_seconds(PLACEMENT)
    if measured <= 0.0:
        return None
    site, eng = ctx.config["site"], ctx.config["engine"]
    tasks = ctx.config["tasks"]
    batch = ctx.traffic["batch_size"] // ctx.chips
    tracks = max(site["device_cores"] // tasks[k]["cores"]
                 for k in ("hp", "lp2", "lp4"))
    per_step = placement_bytes_per_step(
        batch, site["n_devices"], 3, tracks, eng["max_windows"],
        tasks["max_lp_per_frame"])
    batches = ctx.calls * -(-ctx.replicas_per_call // ctx.traffic["batch_size"])
    steps = batches * site["n_frames"]
    least = least_seconds(per_step * steps, ctx.peaks()["hbm_bytes_per_s"])
    return 100.0 * least / measured
