"""The readers of the program's own scopes (``fleetbench.scopes`` and the
metrics on it), and the readings of the accepted readers on the
committed chip slice, pinned so that what this adds leaves them as they
were."""

import os
import re
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP_TRACE = os.path.join(HERE, "testdata")
sys.path.insert(0, HERE)

from fleetbench import scopes  # noqa: E402
from fleetbench import trace as T  # noqa: E402
from fleetbench.harness import Context  # noqa: E402
from fleetbench.layers import COLLECTIVE, COMPACTION, PLACEMENT  # noqa: E402
from fleetbench.roofline import least_seconds, placement_bytes_per_step  # noqa: E402
from fleetbench.spec import Benchmark  # noqa: E402

MS = 1_000_000
CELL = "paper_site.weighted4_cong0.3"
BODY = "jit(_run_segment)/while/body/closed_call/"
#: metric name -> its reading on ``_synthetic()`` with 1,000 real
#: site-ticks, by hand (see the op list there).
NEW = {
    # hp 8 ms plus the 2 ms copy before it
    "hp_ns_per_site_tick": 10_000.0,
    # layout 6 + 1 (the copy before it) + 3, kernel 4, select 5
    "lp_ns_per_site_tick": 19_000.0,
    # re-queue 3 ms, reallocation 2 ms
    "preemption_ns_per_site_tick": 5_000.0,
    "mask_ns_per_site_tick": 2_000.0,
    "placement_layout_ns_per_site_tick": 10_000.0,
    # the loop's own add, 1 ms of 53 ms of self time
    "unattributed_share": 100.0 / 53.0,
    "placement_kernel_roofline": None,       # computed in the test
}


def _synthetic():
    ops = [
        ("fusion.1", "fusion", "tick/housekeeping/cond/branch_1_fun/x", 0, 10),
        ("copy.2", "copy", "", 10, 2),                 # charged to tick/hp
        ("fusion.3", "fusion", "tick/hp/eq", 12, 8),
        ("fusion.4", "fusion", "tick/requeue/lt", 20, 3),
        ("fusion.5", "fusion", "tick/realloc/select_n", 23, 2),
        ("transpose.6", "transpose",
         "tick/lp/jit(fused_place)/placement/layout/transpose", 25, 6),
        ("fused_place.7", "custom-call",
         "tick/lp/jit(fused_place)/placement/kernel/pallas_call", 31, 4),
        ("copy.8", "copy", "", 35, 1),                 # to placement/layout
        ("transpose.9", "transpose",
         "tick/lp/jit(fused_place)/placement/layout/transpose", 36, 3),
        ("fusion.10", "fusion", "tick/lp/select_n", 39, 5),
        ("fusion.11", "fusion", "tick/mask/select_n", 44, 2),
    ]
    device = [[0, n, code, BODY + sc if sc else "", s * MS, d * MS]
              for n, code, sc, s, d in ops]
    device += [
        # the scan's induction variable: a name, but none of the program's
        [0, "add.12", "add", "jit(_run_segment)/while/body/add", 46 * MS, MS],
        [0, "dot.13", "dot", "jit(_cell_reducer)/shard_map/sweep/"
         "cell_moments/dot", 60 * MS, 2 * MS],
        # nothing named after it: charged to the op before it
        [0, "copy.14", "copy", "", 90 * MS, 4 * MS],
    ]
    return {"host": [["bench/window", 0, 100 * MS]], "device": device}


def _ctx(trace, real_site_ticks=1000):
    b = Benchmark()
    return Context(cell=b.cell(CELL), chips=1, device_kind="TPU v5 lite",
                   setup_s=1.0, elapsed_s=1.0, calls=1,
                   replicas_per_call=4096, real_site_ticks=real_site_ticks,
                   host_seconds={}, trace=trace), b


def test_bench_charging_rule_on_a_synthetic_trace():
    r = T.Reduced(_synthetic())
    ch = dict(zip((o.name for o in r.ops[0]), scopes.charged(r)[0]))
    assert ch["copy.2"] == BODY + "tick/hp/eq"
    assert ch["copy.8"].endswith("placement/layout/transpose")
    assert ch["copy.14"].endswith("sweep/cell_moments/dot")
    assert ch["add.12"] == "jit(_run_segment)/while/body/add"
    # every op once: the phases, the sweep and the rest make the whole
    tick = scopes.charged_seconds(r, scopes.TICK)
    sweep = scopes.charged_seconds(r, scopes.SWEEP)
    assert (tick, sweep) == pytest.approx((0.046, 0.006))
    assert tick + sweep + 0.001 == pytest.approx(scopes.self_seconds(r))
    assert scopes.self_seconds(r) == pytest.approx(r.mean_busy_s())
    assert scopes.charge([]) == []


def test_bench_charging_leaves_an_unnamed_trace_unnamed():
    ops = [T.Op("copy.1", "copy", "", 0, 5), T.Op("copy.2", "copy", "", 5, 9)]
    assert scopes.charge(ops) == ["", ""]


@pytest.mark.parametrize("name", sorted(NEW))
def test_bench_new_reader_on_a_synthetic_trace(name):
    ctx, b = _ctx(T.Reduced(_synthetic()))
    value = b.metrics[name].reader.read(ctx)
    want = NEW[name]
    if name == "placement_kernel_roofline":
        site = ctx.config["site"]
        least = least_seconds(
            placement_bytes_per_step(4096, 4, 3, 2, 16) * site["n_frames"],
            819e9)
        want = 100.0 * least / 0.004
    assert value == pytest.approx(want, rel=1e-9)


@pytest.mark.parametrize("name", sorted(NEW))
def test_bench_new_reader_reads_nothing_without_the_scopes(name):
    # the committed slice is of a program with no scopes of its own
    ctx, b = _ctx(T.Reduced(T.load(CHIP_TRACE)))
    assert b.metrics[name].reader.read(ctx) is None
    ctx.trace = None
    assert b.metrics[name].reader.read(ctx) is None


def test_bench_accepted_readings_of_the_chip_slice_are_pinned():
    r = T.Reduced(T.load(CHIP_TRACE))
    assert r.scope_seconds(COMPACTION) == pytest.approx(0.07570377, rel=1e-9)
    assert r.scope_seconds(PLACEMENT) == pytest.approx(0.002522496,
                                                       rel=1e-9)
    assert r.seconds_where(lambda o: bool(COLLECTIVE.match(o.opcode))) == 0
    assert r.mean_busy_s() == pytest.approx(0.088569668, rel=1e-9)
    assert r.idle_share() == pytest.approx(0.00192988741339295, rel=1e-9)
    top = r.top_ops(3)
    assert [t[1] for t in top] == pytest.approx(
        [0.025813203, 0.025813202, 0.023003346], rel=1e-9)
    ctx, b = _ctx(r, real_site_ticks=2 * 4096)
    reads = {m: b.metrics[m].reader.read(ctx) for m in (
        "compaction_ns_per_site_tick", "placement_ns_per_site_tick",
        "device_idle_share", "collective_ms_per_batch")}
    assert reads["compaction_ns_per_site_tick"] == pytest.approx(
        0.07570377e9 / 8192, rel=1e-9)
    assert reads["placement_ns_per_site_tick"] == pytest.approx(
        0.002522496e9 / 8192, rel=1e-9)
    assert reads["device_idle_share"] == pytest.approx(0.192988741339295,
                                                       rel=1e-9)
    assert reads["collective_ms_per_batch"] is None


def test_bench_scope_patterns_key_on_no_accepted_name():
    # the accepted patterns anchor on names no scope of the program uses
    for p in (scopes.TICK, scopes.PLACEMENT_LAYOUT, scopes.PLACEMENT_KERNEL,
              scopes.SWEEP):
        for probe in ("jit(_run_segment)/while/cond/lt",
                      "x/cond/branch_1_fun/gather", "x/jit(fused_place)/add"):
            assert not p.search(probe)
    assert re.search(PLACEMENT, BODY + "tick/lp/jit(fused_place)/"
                     "placement/kernel/pallas_call")
    assert re.search(COMPACTION, BODY + "tick/housekeeping/cond/"
                     "branch_1_fun/gather")
