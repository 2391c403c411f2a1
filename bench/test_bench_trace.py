"""The trace reduction, the placement byte count and the peak table."""

import os
import re
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
#: two steps of cell 1's segment scan cut from a TPU v5e trace (the step
#: before a compaction, then the compaction step), with the HLO of the
#: segment program trimmed to the instructions that ran in them.
CHIP_TRACE = os.path.join(HERE, "testdata")
sys.path.insert(0, HERE)

from fleetbench import roofline  # noqa: E402
from fleetbench import trace as T  # noqa: E402
from fleetbench.layers import COLLECTIVE, COMPACTION, PLACEMENT  # noqa: E402
from fleetbench.peaks import UnknownDevice, peaks  # noqa: E402


def test_bench_placement_bytes_by_hand():
    # (4096, 4, 3, 2, 16): state 4096*4*3*2*16 slots of 9 B, read and
    # written; 21 attempts of (2*4*4 + 4 + 1 + 3*4) + (1 + 4 + 4 + 4 + 1
    # + 4) = 67 B per replica
    assert roofline.attempts_per_step(4, 4) == 21
    assert roofline.attempt_operand_bytes(4, 3) == 67
    assert roofline.placement_bytes_per_step(4096, 4, 3, 2, 16) == \
        2 * 4096 * 384 * 9 + 4096 * 21 * 67 == 34_074_624
    # (2048, 8, 3, 2, 16): the same state bytes; 41 attempts of
    # (2*4*8 + 4 + 1 + 12) + 18 = 99 B
    assert roofline.attempts_per_step(8, 4) == 41
    assert roofline.attempt_operand_bytes(8, 3) == 99
    assert roofline.placement_bytes_per_step(2048, 8, 3, 2, 16) == \
        2 * 2048 * 768 * 9 + 2048 * 41 * 99 == 36_624_384


def test_bench_peaks_by_device_kind():
    assert peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    assert peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(UnknownDevice):
        peaks("TPU v9 imaginary")
    with pytest.raises(UnknownDevice):
        peaks("cpu")


def _synthetic():
    ms = 1_000_000
    return {
        "host": [["bench/window", 0, 100 * ms], ["bench/sweep", 0, 100 * ms],
                 ["bench/host_gen", 10 * ms, 20 * ms]],
        "device": [
            [0, "fusion.1", "fusion",
             "jit(_run_segment)/while/body/closed_call/cond/branch_1_fun/x",
             0, 5 * ms],
            [0, "fusion.2", "fusion",
             "jit(_run_segment)/while/body/closed_call/cond/branch_1_fun/y",
             3 * ms, 4 * ms],                          # overlaps the first
            [0, "while.8", "while",
             "jit(_run_segment)/jit(fused_place)/while",
             40 * ms, 10 * ms],                        # holds fused_place.3
            [0, "fused_place.3", "custom-call",
             "jit(_run_segment)/jit(fused_place)/pallas_call", 41 * ms, 8 * ms],
            [0, "lt.6", "compare", "jit(_run_segment)/while/cond/lt",
             60 * ms, ms],
            [0, "psum.23", "all-reduce", "jit(cell_moments)/shard_map/psum",
             62 * ms, ms // 2],
            [0, "copy.4", "copy", "jit(_run_segment)/while/body",
             90 * ms, 20 * ms],
            # ends before the window
            [0, "fusion.5", "fusion", "", -10 * ms, 5 * ms],
        ],
    }


def test_bench_reduction_on_a_synthetic_trace():
    r = T.Reduced(_synthetic())
    assert r.window_s == pytest.approx(0.1)
    # union: [0, 7) + [40, 50) + [60, 61) + [62, 62.5) + [90, 100) ms,
    # clipped to the window
    assert r.busy_s(0) == pytest.approx(0.0285)
    assert r.idle_share() == pytest.approx(0.715)
    # by self time: the overlap of the two branch ops once, the while
    # around the kernel 2 ms of its own; the scan's loop condition is not
    # compaction
    assert r.scope_seconds(COMPACTION) == pytest.approx(0.007)
    assert r.scope_seconds(PLACEMENT) == pytest.approx(0.010)
    assert r.seconds_where(lambda o: bool(COLLECTIVE.match(o.opcode))) == \
        pytest.approx(0.0005)
    top = r.top_ops(2)
    assert top[0][0].startswith("copy.4") and top[0][1] == \
        pytest.approx(0.010)
    assert top[1][0].startswith("fused_place.3") and top[1][1] == \
        pytest.approx(0.008)
    gaps = r.idle_gaps(3)
    # [7, 40) under host_gen (10-30) at its midpoint 23.5; [62.5, 90) and
    # [50, 60) under the sweep span only
    assert gaps[0] == ["bench/host_gen", pytest.approx(0.033)]
    assert gaps[1] == ["bench/sweep", pytest.approx(0.0275)]
    assert gaps[2] == ["bench/sweep", pytest.approx(0.010)]


def test_bench_reduction_needs_the_window_and_a_device_op():
    ev = _synthetic()
    with pytest.raises(RuntimeError, match="bench/window"):
        T.Reduced({"host": ev["host"][1:], "device": ev["device"]})
    with pytest.raises(RuntimeError, match="no device op"):
        T.Reduced({"host": ev["host"], "device": ev["device"][-1:]})



def test_bench_reduction_on_a_chip_trace():
    ev = T.load(CHIP_TRACE)
    r = T.Reduced(ev)
    scope = {op: sc for _, op, _, sc, _, _ in ev["device"]}
    opcode = {op: code for _, op, code, _, _, _ in ev["device"]}
    # names and scopes come from the segment program's HLO in the trace
    assert scope["fusion.55"] == ("jit(_run_segment)/while/body/closed_call/"
                                  "cond/branch_1_fun/jit(take_along_axis)/"
                                  "gather")
    assert not re.search(COMPACTION, scope["cond.50"])
    assert opcode["cond.50"] == "conditional"
    kernels = [op for op, sc in scope.items()
               if re.search(PLACEMENT, sc) and sc.endswith("/pallas_call")]
    # the Pallas kernel's custom call takes the kernel's name
    assert kernels and all(op.startswith("fused_place") for op in kernels)
    assert {opcode[op] for op in kernels} == {"custom-call"}
    # compaction by name stack against the span of the one taken
    # conditional, found by time alone: they agree to within its own
    # branching
    (cond,) = [e for e in ev["device"]
               if e[1].startswith("cond.") and e[5] > 1_000_000]
    assert r.scope_seconds(COMPACTION) == pytest.approx(cond[5] * 1e-9,
                                                        rel=1e-2)
    assert 0 < r.scope_seconds(PLACEMENT) < r.window_s - cond[5] * 1e-9
    assert r.window_s == pytest.approx(0.08874092875)
    assert 0.95 < r.mean_busy_s() / r.window_s <= 1.0
    assert [k.split(" ")[0] for k, _ in r.top_ops(3)] == \
        ["fusion.55", "fusion.56", "fusion.4"]
    assert {g[0] for g in r.idle_gaps(3)} == {"bench/transfer"}


def test_bench_hlo_op_names_of_a_chip_trace():
    (path,) = [os.path.join(CHIP_TRACE, f) for f in os.listdir(CHIP_TRACE)]
    with open(path, "rb") as f:
        names = T.hlo_op_names(f.read())
    (program,) = names
    assert program.startswith("jit__run_segment(")
    assert len(names[program]) == 1865
