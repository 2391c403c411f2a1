"""Fused placement kernel tests: Pallas-vs-oracle equivalence (including
the batch-padding and B=1 paths), selection semantics against a plain
numpy reference, and commit masking (do=False rows bit-identical).

The kernel body traces the same jnp graph as the oracle, so equivalence
asserts are exact (`assert_array_equal`, no tolerance).
"""

import jax
import numpy as np
import pytest

from repro.core.jax_state import BIG
from repro.kernels.placement.ops import fused_place_op, resolve_backend
from repro.kernels.placement.placement import block_size, fused_place
from repro.kernels.placement.ref import SRC_PREF, fused_place_ref

DEV, CFG, T, W = 4, 3, 2, 16
LP2_IDX, LP4_IDX = 1, 2


def _random_case(b, seed=0, do_rate=0.8):
    rng = np.random.default_rng(seed)
    t1 = rng.uniform(0, 50, (b, DEV, CFG, T, W)).astype(np.float32)
    t2 = (t1 + rng.uniform(0.1, 30, t1.shape)).astype(np.float32)
    valid = rng.random(t1.shape) < 0.6
    order = np.argsort(np.where(valid, t1, 1e9), axis=-1)
    t1 = np.take_along_axis(t1, order, -1)
    t2 = np.take_along_axis(t2, order, -1)
    valid = np.take_along_axis(valid, order, -1)
    md = rng.uniform(1, 8, (b, CFG)).astype(np.float32)
    q1 = rng.uniform(0, 40, (b, DEV)).astype(np.float32)
    dl = (q1 + rng.uniform(5, 40, q1.shape)).astype(np.float32)
    src = rng.integers(0, DEV, b).astype(np.int32)
    do = rng.random(b) < do_rate
    return t1, t2, valid, md, q1, dl, src, do


@pytest.mark.parametrize("b,block_b",
                         [(8, 8), (130, 128), (1, 128), (300, 128)],
                         ids=["exact", "pad", "b1", "pad3"])
def test_kernel_matches_oracle(b, block_b):
    """Interpret-mode kernel output must equal the jnp oracle exactly —
    including when B is not divisible by block_b (padding path) and at
    B=1 (one tile of the whole batch)."""
    for seed in range(3):
        args = _random_case(b, seed=seed)
        ref = fused_place_ref(*args)
        ker = fused_place(*args, block_b=block_b, interpret=True)
        for i, (r, k) in enumerate(zip(ref, ker)):
            np.testing.assert_array_equal(
                np.asarray(r), np.asarray(k), err_msg=f"output {i}"
            )


def test_op_backends_agree():
    args = _random_case(6, seed=11)
    ref = fused_place_op(*args, backend="ref")
    ker = fused_place_op(*args, backend="kernel")
    for r, k in zip(ref, ker):
        np.testing.assert_array_equal(np.asarray(r), np.asarray(k))


def test_kernel_backend_says_it_interprets_off_tpu():
    """backend="kernel" off a TPU runs the interpreter, and says so."""
    if jax.default_backend() == "tpu":
        pytest.skip("the kernel compiles on a TPU")
    with pytest.warns(RuntimeWarning, match="interpret mode"):
        fused_place_op(*_random_case(2, seed=4), backend="kernel")
    assert resolve_backend("auto") == (False, False)
    assert resolve_backend("ref") == (False, False)


def test_block_size_is_lane_aligned():
    """A tile smaller than the batch must be a multiple of the 128 lanes
    (the TPU's rule for a block's minor dim); a batch that fits in one
    tile is the whole block."""
    assert block_size(1, 128) == 1
    assert block_size(100, 128) == 100
    assert block_size(4096, 256) == 256
    with pytest.raises(ValueError, match="multiple of 128"):
        block_size(256, 8)


def test_op_rejects_unknown_backend():
    args = _random_case(2, seed=1)
    with pytest.raises(ValueError, match="backend"):
        fused_place_op(*args, backend="tpu")


def test_selection_semantics_vs_numpy():
    """2-core preferred / 4-core fallback, source preference within
    SRC_PREF, earliest start, first device wins exact ties — checked
    against a straight numpy re-derivation of §IV.B.2."""
    args = _random_case(32, seed=5, do_rate=1.0)
    t1, t2, valid, md, q1, dl, src, do = args
    _, _, _, ok, sel, start, dur, use4, _ = fused_place_ref(*args)
    ok, sel = np.asarray(ok), np.asarray(sel)
    start, use4 = np.asarray(start), np.asarray(use4)

    for b in range(t1.shape[0]):
        per_cfg = {}
        for ci in (LP2_IDX, LP4_IDX):
            best = np.full(DEV, np.inf)
            for d in range(DEV):
                for tt in range(T):
                    for w in range(W):
                        if not valid[b, d, ci, tt, w]:
                            continue
                        s0 = max(t1[b, d, ci, tt, w], q1[b, d])
                        if s0 + md[b, ci] <= min(t2[b, d, ci, tt, w],
                                                 dl[b, d]):
                            best[d] = min(best[d], s0)
            key = np.where(np.isfinite(best), best, BIG)
            key = key - np.where(np.arange(DEV) == src[b], SRC_PREF, 0.0)
            d0 = int(np.argmin(key))
            per_cfg[ci] = (np.isfinite(best[d0]), d0, best[d0])
        ok2, d2, s2 = per_cfg[LP2_IDX]
        ok4, d4, s4 = per_cfg[LP4_IDX]
        assert bool(ok[b]) == (ok2 or ok4)
        if ok[b]:
            assert bool(use4[b]) == (not ok2)
            want_d, want_s = (d2, s2) if ok2 else (d4, s4)
            assert sel[b] == want_d
            np.testing.assert_allclose(start[b], want_s, rtol=1e-6)
        assert float(dur[b]) == md[b, LP4_IDX if use4[b] else LP2_IDX]


def test_do_false_rows_bit_identical():
    """Masked-off replicas (and failed placements) must pass through with
    window arrays untouched — compaction or trimming of inactive rows
    would break scan no-op masking in the fleet engine."""
    args = list(_random_case(8, seed=3))
    args[7] = np.zeros(8, bool)   # do = False everywhere
    t1, t2, valid = args[0], args[1], args[2]
    for backend in ("ref", "kernel"):
        nt1, nt2, nv, ok, *_ = fused_place_op(*args, backend=backend)
        assert not np.asarray(ok).any()
        np.testing.assert_array_equal(np.asarray(nt1), t1)
        np.testing.assert_array_equal(np.asarray(nt2), t2)
        np.testing.assert_array_equal(np.asarray(nv), valid)


def test_commit_consumes_placed_interval():
    """After a successful placement, on the selected device each config
    list may retain at most ``T - OCC_TABLE[cfg, list]`` tracks still
    fully containing the committed span — the §IV.A.1 fan-out must have
    trimmed the occupancy-width most-overlapping tracks."""
    from repro.core.jax_state import OCC_TABLE

    args = _random_case(16, seed=9, do_rate=1.0)
    nt1, nt2, nv, ok, sel, start, dur, use4, _ = fused_place_ref(*args)
    nt1, nt2, nv = np.asarray(nt1), np.asarray(nt2), np.asarray(nv)
    ok, sel = np.asarray(ok), np.asarray(sel)
    start, dur, use4 = np.asarray(start), np.asarray(dur), np.asarray(use4)
    assert ok.any()
    for b in np.nonzero(ok)[0]:
        d = sel[b]
        s, e = start[b], start[b] + dur[b]
        cfg = LP4_IDX if use4[b] else LP2_IDX
        for ci in range(CFG):
            # a valid window containing the whole span ⇒ that track still
            # advertises the reserved cores as free
            contains = (nv[b, d, ci]
                        & (nt1[b, d, ci] <= s + 1e-5)
                        & (nt2[b, d, ci] >= e - 1e-5))
            n_containing = int(contains.any(axis=-1).sum())
            assert n_containing <= T - int(OCC_TABLE[cfg, ci]), (b, ci)
