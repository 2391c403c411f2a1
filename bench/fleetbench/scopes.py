"""Device time by the program's own named scopes.

The segment scan runs each phase of a tick under a ``jax.named_scope``
(``tick/housekeeping``, ``tick/requeue``, ``tick/hp``, ``tick/realloc``,
``tick/lp``, ``tick/mask``; ``fleet/engine.py``), the placement wrapper
its boundary work under ``placement/layout`` and its launch under
``placement/kernel``, and the sharded sweep's reduction runs under
``sweep/cell_moments``.  An op's ``scope`` (its HLO ``op_name``, read by
``trace.load``) carries them.

Ops the compiler makes (layout copies, some fusions) have no name stack.
Each is *charged* to the nearest op on its chip, in the order the chip
ran them, that has one: the next such op (the copy's consumer, as a rule),
else the previous one.  An op with a name stack is charged to its own.

Everything here reads a ``trace.Reduced`` and nothing else, so a trace
of a program without these scopes reads as nothing (``None``), not 0.
"""

from __future__ import annotations

import re
import weakref

from fleetbench.roofline import least_seconds, placement_bytes_per_step

#: one pattern per layer, matched against an op's charged scope.
PHASES = ("housekeeping", "requeue", "hp", "realloc", "lp", "mask")
TICK = re.compile(r"(^|/)tick/(" + "|".join(PHASES) + r")(/|$)")
HP = re.compile(r"(^|/)tick/hp(/|$)")
LP = re.compile(r"(^|/)tick/lp(/|$)")
PREEMPTION = re.compile(r"(^|/)tick/(requeue|realloc)(/|$)")
MASK = re.compile(r"(^|/)tick/mask(/|$)")
PLACEMENT_LAYOUT = re.compile(r"(^|/)placement/layout(/|$)")
PLACEMENT_KERNEL = re.compile(r"(^|/)placement/kernel(/|$)")
SWEEP = re.compile(r"(^|/)sweep/[^/]+(/|$)")
#: any scope of the program's own: time under none of them is
#: unattributed.
PROGRAM = re.compile(r"(^|/)(tick|placement|sweep)/[^/]+(/|$)")

_CHARGED: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def charge(ops: list) -> list:
    """The charged scope of each of one chip's ``ops`` (``trace.Op``s):
    its own scope, else the scope of the next op in start order that has
    one, else of the previous one, else ""."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i].start, -ops[i].end))
    out = [ops[i].scope for i in range(len(ops))]
    nxt = ""
    for i in reversed(order):
        if ops[i].scope:
            nxt = ops[i].scope
        elif nxt:
            out[i] = nxt
    prev = ""
    for i in order:
        if ops[i].scope:
            prev = ops[i].scope
        elif not out[i]:
            out[i] = prev
    return out


def charged(trace) -> dict:
    """``{chip: [charged scope of each op of trace.ops[chip]]}``, computed
    once per trace."""
    if trace not in _CHARGED:
        _CHARGED[trace] = {c: charge(trace.ops[c]) for c in trace.chips}
    return _CHARGED[trace]


def charged_seconds(trace, pattern) -> float:
    """Device self seconds of the ops whose charged scope matches
    ``pattern``, summed over chips and averaged per chip."""
    ch = charged(trace)
    total = sum(t for c in trace.chips
                for s, t in zip(ch[c], trace.self_ns[c])
                if pattern.search(s))
    return total * 1e-9 / len(trace.chips)


def self_seconds(trace) -> float:
    """Device self seconds of every op, per chip."""
    return sum(sum(trace.self_ns[c]) for c in trace.chips) * 1e-9 / len(
        trace.chips)


def ns_per_site_tick(ctx, pattern) -> float | None:
    """Device time charged to ``pattern`` per real site-tick, per chip;
    None where nothing is charged to it."""
    if ctx.trace is None:
        return None
    s = charged_seconds(ctx.trace, pattern)
    if s <= 0.0:
        return None
    return s * 1e9 * ctx.chips / ctx.real_site_ticks


def placement_least_seconds(ctx) -> float:
    """Least time of the window's placement work, per chip: the bytes its
    real steps must move through placement
    (``roofline.placement_bytes_per_step``) over the chip's peak HBM
    bandwidth."""
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
    return least_seconds(per_step * steps, ctx.peaks()["hbm_bytes_per_s"])
