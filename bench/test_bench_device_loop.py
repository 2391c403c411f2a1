"""The readers of the engine's device loop (``fleetbench.device_loop`` and
the metrics ``device_loop_overhead_share`` and ``device_tick_ns``) on a
small synthetic trace, and their silence on traces of a program without
the ``tick/device`` scope."""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from fleetbench import device_loop  # noqa: E402
from fleetbench import trace as T  # noqa: E402
from fleetbench.harness import Context  # noqa: E402
from fleetbench.spec import Benchmark  # noqa: E402

MS = 1_000_000
CELL = "site50.weighted1_cong0.3"
BODY = "jit(_run_segment)/while/body/closed_call/"
LOOP = BODY + "tick/device/"
TRIP = LOOP + "while/body/closed_call/"
NEW = ("device_loop_overhead_share", "device_tick_ns")


def _synthetic(chips=1):
    """One tick of a site, in ms: housekeeping, the device loop (a
    ``while`` op around its condition, phases, carry copy and index),
    then the mask."""
    ops = [
        ("fusion.1", "fusion", BODY + "tick/housekeeping/x", 0, 10),
        # 9 ms of its 30 are its own: the ops below lie inside it
        ("while.2", "while", LOOP + "while", 10, 30),
        ("lt.3", "compare", LOOP + "while/cond/lt", 10, 1),
        ("copy.4", "copy", "", 11, 2),            # charged to tick/hp
        ("fusion.5", "fusion", TRIP + "tick/hp/eq", 13, 8),
        ("fusion.6", "fusion", TRIP + "tick/realloc/select_n", 21, 2),
        ("fused_place.7", "custom-call",
         TRIP + "tick/lp/jit(fused_place)/placement/kernel/pallas_call",
         23, 4),
        ("copy.8", "copy", TRIP.rstrip("/"), 27, 3),   # the carry
        ("add.9", "add", LOOP + "while/body/add", 30, 1),
        ("fusion.10", "fusion", BODY + "tick/mask/select_n", 40, 2),
    ]
    device = [[c, n, code, sc, s * MS, d * MS]
              for c in range(chips) for n, code, sc, s, d in ops]
    return {"host": [["bench/window", 0, 100 * MS]], "device": device}


def _ctx(trace, chips=1, real_site_ticks=1000):
    b = Benchmark()
    return Context(cell=b.cell(CELL), chips=chips, device_kind="TPU v5 lite",
                   setup_s=1.0, elapsed_s=1.0, calls=1,
                   replicas_per_call=128, real_site_ticks=real_site_ticks,
                   host_seconds={}, trace=trace), b


@pytest.mark.parametrize("chips", [1, 2])
def test_bench_device_loop_overhead_counts_only_the_loops_own_time(chips):
    ctx, b = _ctx(T.Reduced(_synthetic(chips)), chips=chips)
    # the while op's own 9 ms, its condition 1, the carry copy 3 and the
    # index 1, over the 42 ms busy; the phases and the unnamed copy
    # charged to tick/hp are not the loop's
    want = 100.0 * 14 / 42
    got = b.metrics["device_loop_overhead_share"].reader.read(ctx)
    assert got == pytest.approx(want, rel=1e-9)
    # the accepted phase readers still find their phases inside the loop
    hp = b.metrics["hp_ns_per_site_tick"].reader.read(ctx)
    assert hp == pytest.approx(10 * MS * chips / 1000, rel=1e-9)


@pytest.mark.parametrize("chips, ticks", [(1, 1000), (2, 2000), (1, 4000)])
def test_bench_device_tick_ns_divides_by_ticks_devices_and_chips(chips,
                                                                 ticks):
    ctx, b = _ctx(T.Reduced(_synthetic(chips)), chips=chips,
                  real_site_ticks=ticks)
    n_dev = ctx.config["site"]["n_devices"]
    assert n_dev == 50
    # 30 ms charged to the loop on each chip: the while op's own 9, the
    # condition 1, the copy before tick/hp 2, hp 8, realloc 2, lp 4, the
    # carry 3 and the index 1
    want = 30 * MS * chips / ticks / n_dev
    got = b.metrics["device_tick_ns"].reader.read(ctx)
    assert got == pytest.approx(want, rel=1e-9)
    assert device_loop.tick_ns(ctx) == got


def _no_loop_trace():
    ops = [("fusion.1", "fusion", BODY + "tick/hp/eq", 0, 5),
           ("add.2", "add", "jit(_run_segment)/while/body/add", 5, 1)]
    device = [[0, n, code, sc, s * MS, d * MS] for n, code, sc, s, d in ops]
    return {"host": [["bench/window", 0, 10 * MS]], "device": device}


@pytest.mark.parametrize("name", NEW)
@pytest.mark.parametrize("source", ["unrolled", "chip_slice", "untraced"])
def test_bench_device_loop_readers_read_nothing_without_the_scope(name,
                                                                  source):
    trace = {"unrolled": lambda: T.Reduced(_no_loop_trace()),
             "chip_slice": lambda: T.Reduced(T.load(os.path.join(
                 HERE, "testdata"))),
             "untraced": lambda: None}[source]()
    ctx, b = _ctx(trace)
    assert b.metrics[name].reader.read(ctx) is None


def test_bench_device_loop_patterns():
    own, loop = device_loop.DEVICE_OWN, device_loop.DEVICE
    for name in (LOOP + "while", LOOP + "while/cond/lt",
                 LOOP + "while/body/add", TRIP.rstrip("/")):
        assert own.search(name) and loop.search(name), name
    for name in (TRIP + "tick/hp/eq",
                 TRIP + "tick/lp/jit(fused_place)/placement/layout/copy"):
        assert loop.search(name) and not own.search(name), name
    for name in (BODY + "tick/hp/eq", BODY + "tick/devices/x",
                 "jit(_run_segment)/while/body/add"):
        assert not loop.search(name) and not own.search(name), name
