"""Fused placement kernel tests: Pallas-vs-oracle equivalence (including
the batch-padding and B=1 paths, device counts up to 50 and per-replica
durations), selection semantics against a plain numpy reference, the
oracle's query against the serial ``AvailabilityList.find_slot``, and
commit masking (do=False rows bit-identical).

The kernel body traces the same jnp graph as the oracle, so equivalence
asserts are exact (`assert_array_equal`, no tolerance).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st
from _sched import loaded_scheduler

from repro.core.jax_state import BIG, export_state
from repro.core.scheduler import RASScheduler
from repro.core.tasks import ALL_CONFIGS, LP2_CONFIG, LP4_CONFIG
from repro.fleet.state import stack_states
from repro.kernels.placement.ops import fused_place_op, resolve_backend
from repro.kernels.placement.placement import block_size, fused_place
from repro.kernels.placement.ref import SRC_PREF, fused_place_ref

DEV, CFG, T, W = 4, 3, 2, 16
LP2_IDX, LP4_IDX = 1, 2


def _random_case(b, seed=0, do_rate=0.8, dev=DEV, w=W):
    rng = np.random.default_rng(seed)
    t1 = rng.uniform(0, 50, (b, dev, CFG, T, w)).astype(np.float32)
    t2 = (t1 + rng.uniform(0.1, 30, t1.shape)).astype(np.float32)
    valid = rng.random(t1.shape) < 0.6
    order = np.argsort(np.where(valid, t1, 1e9), axis=-1)
    t1 = np.take_along_axis(t1, order, -1)
    t2 = np.take_along_axis(t2, order, -1)
    valid = np.take_along_axis(valid, order, -1)
    md = rng.uniform(1, 8, (b, CFG)).astype(np.float32)
    q1 = rng.uniform(0, 40, (b, dev)).astype(np.float32)
    dl = (q1 + rng.uniform(5, 40, q1.shape)).astype(np.float32)
    src = rng.integers(0, dev, b).astype(np.int32)
    do = rng.random(b) < do_rate
    return t1, t2, valid, md, q1, dl, src, do


def _assert_kernel_matches_oracle(args, block_b):
    ref = fused_place_ref(*args)
    ker = fused_place(*args, block_b=block_b, interpret=True)
    for i, (r, k) in enumerate(zip(ref, ker)):
        np.testing.assert_array_equal(
            np.asarray(r), np.asarray(k), err_msg=f"output {i}"
        )


@pytest.mark.parametrize(
    "b,block_b",
    [(8, 8), (130, 128), (1, 128), (300, 128),
     (256, 128), (254, 128), (253, 128), (100, 128)],
    ids=["exact", "pad", "b1", "pad3",
         "exact2", "pad_by2", "pad_by3", "under_block"])
def test_kernel_matches_oracle(b, block_b):
    """Interpret-mode kernel output must equal the jnp oracle exactly —
    including when B is a multiple of block_b, when it is not (padding
    path, by 2 and by 3 replicas among others), and when the batch is
    under one block (one tile of the whole batch, B=1 too)."""
    for seed in range(3):
        _assert_kernel_matches_oracle(_random_case(b, seed=seed), block_b)


@pytest.mark.parametrize("b,dev,w", [(8, 4, 8), (64, 50, 16), (130, 6, 32)],
                         ids=["dev4_w8", "dev50_w16", "dev6_w32"])
def test_kernel_matches_oracle_across_geometries(b, dev, w):
    """Kernel == oracle at other device counts and window widths: the
    50-device site, a 32-slot track, and a batch that pads a tile."""
    args = _random_case(b, seed=dev + w, dev=dev, w=w)
    _assert_kernel_matches_oracle(args, 128)


def test_kernel_matches_oracle_per_replica_min_dur():
    """Per-replica durations: replicas whose durations fit nowhere, fit
    only the 2-core or only the 4-core config, and fit everywhere, each
    beside the others in one tile, with per-device release times."""
    args = list(_random_case(16, seed=21, do_rate=1.0))
    rng = np.random.default_rng(21)
    md = rng.uniform(0.1, 8.0, (16, CFG)).astype(np.float32)
    md[0::4] = 500.0                           # nothing fits
    md[1::4, LP2_IDX] = 500.0                  # 4-core only
    md[2::4, LP4_IDX] = 500.0                  # 2-core only
    args[3] = md
    _assert_kernel_matches_oracle(args, 128)
    _, _, _, ok, _, _, dur, use4, _ = fused_place_ref(*args)
    ok, dur, use4 = np.asarray(ok), np.asarray(dur), np.asarray(use4)
    assert not ok[0::4].any()
    assert (use4[1::4] | ~ok[1::4]).all() and not use4[2::4].any()
    assert ok[3::4].any()
    np.testing.assert_array_equal(
        dur[ok], np.where(use4, md[:, LP4_IDX], md[:, LP2_IDX])[ok])


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


# ---------------------------------------------------------------------------
# the oracle's query against the serial availability lists
# ---------------------------------------------------------------------------

def _per_device_rows(state, q1, dl, **cfgs):
    """One oracle attempt per (replica, device) of a stacked state: row
    ``b * Dev + d`` holds replica b's windows and can only place on
    device d (every other device's deadline falls before its release)."""
    n_b, n_dev = state.win_t1.shape[:2]
    rep = lambda a: jnp.repeat(a, n_dev, axis=0)
    own = np.tile(np.eye(n_dev, dtype=bool), (n_b, 1))
    _, _, _, ok, sel, start, _, use4, _ = fused_place_ref(
        rep(state.win_t1), rep(state.win_t2), rep(state.win_valid),
        rep(state.min_dur),
        jnp.full(own.shape, q1, jnp.float32),
        jnp.asarray(np.where(own, dl, q1 - 1.0), jnp.float32),
        jnp.asarray(np.tile(np.arange(n_dev), n_b), jnp.int32),
        jnp.ones((own.shape[0],), bool), **cfgs,
    )
    shape = (n_b, n_dev)
    return tuple(np.asarray(a).reshape(shape) for a in (ok, sel, start, use4))


@pytest.mark.parametrize("seeds", [(0, 3), (5, 9)], ids=["seeds0", "seeds1"])
def test_oracle_query_matches_python_availability(seeds):
    """A stacked batch of live schedulers, queried by the oracle on every
    (replica, device): the 2-core slot ``find_slot`` reports where there
    is one, else the 4-core slot, else no placement."""
    scheds = [loaded_scheduler(s) for s in seeds]
    batch = stack_states([export_state(s) for s in scheds])
    q1, dl = 35.0, 95.0
    ok, sel, start, use4 = _per_device_rows(
        batch, q1, dl, cfg_pref=LP2_IDX, cfg_fallback=LP4_IDX)
    for b, s in enumerate(scheds):
        for d, dev in enumerate(s.devices):
            py2 = dev.list_for(LP2_CONFIG).find_slot(q1, dl)
            py4 = dev.list_for(LP4_CONFIG).find_slot(q1, dl)
            want = py2 if py2 is not None else py4
            assert bool(ok[b, d]) == (want is not None), (b, d)
            if want is not None:
                assert sel[b, d] == d
                assert bool(use4[b, d]) == (py2 is None)
                assert abs(float(start[b, d]) - want[2]) < 1e-3


@given(seed=st.integers(0, 2**16), q1_8ths=st.integers(0, 720),
       span_8ths=st.integers(8, 960))
@settings(max_examples=25, deadline=None)
def test_oracle_query_property_matches_python(seed, q1_8ths, span_8ths):
    """Randomly fragmented serial lists (every config list of every
    device trimmed by ``subtract``), exported and queried by the oracle
    list by list: the same slot as ``find_slot``.  Times are in eighths
    of a second, exact in float32."""
    rng = np.random.default_rng(seed)
    s = RASScheduler(DEV, 20e6, seed=seed)
    for dev in s.devices:
        for al in dev.lists.values():
            for _ in range(int(rng.integers(0, 8))):
                a = float(rng.uniform(0, 100))
                al.subtract(a, a + float(rng.uniform(0.5, 30)),
                            int(rng.integers(1, al.track_count + 1)))
    q1 = q1_8ths / 8
    dl = q1 + span_8ths / 8
    state = stack_states([export_state(s)])
    for ci, cfg in enumerate(ALL_CONFIGS):
        ok, _, start, _ = _per_device_rows(
            state, q1, dl, cfg_pref=ci, cfg_fallback=ci)
        for d, dev in enumerate(s.devices):
            py = dev.list_for(cfg).find_slot(q1, dl)
            assert bool(ok[0, d]) == (py is not None), (cfg.name, d)
            if py is not None:
                assert abs(float(start[0, d]) - py[2]) < 1e-3
