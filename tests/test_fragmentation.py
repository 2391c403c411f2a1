"""Fragmentation telemetry and availability-conservation properties.

The §IV.A.1 fan-out commit keeps BOTH min-duration remainders of every
trimmed window and *counts* any piece it cannot fit into the fixed-W
arrays (``remainders_dropped``) — the seed engine silently dropped the
right remainder whenever a track had no free slot.  These tests pin the
accounting identity:

    availability(before) = availability(after) + consumed overlap
                           + dropped time + sub-min-duration discards

for arbitrary bisect sequences, and the measure/disjointness invariants
of the in-scan window compaction pass, which must equal its sort-and-
gather statement bit for bit and lower with no gather or sort.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from _hyp import given, settings, st

from repro.core.jax_state import (
    BIG,
    OCC_TABLE,
    SchedState,
    compact_state,
    compact_tracks,
    fanout_commit,
)

DEV, CFG, T, W = 2, 3, 2, 8


def _measure(t1, t2, valid):
    return float(np.where(np.asarray(valid),
                          np.asarray(t2) - np.asarray(t1), 0.0).sum())


def _disjoint_tracks(rng, b=1, w_used=4, gap=1.0):
    """Sorted, pairwise-disjoint windows per track (the engine invariant)."""
    t1 = np.full((b, DEV, CFG, T, W), BIG, np.float32)
    t2 = np.full((b, DEV, CFG, T, W), BIG, np.float32)
    valid = np.zeros((b, DEV, CFG, T, W), bool)
    for idx in np.ndindex(b, DEV, CFG, T):
        t = 0.0
        for w in range(w_used):
            t += rng.uniform(gap, 3.0)
            d = rng.uniform(1.0, 6.0)
            t1[idx + (w,)] = t
            t2[idx + (w,)] = t + d
            t += d
            valid[idx + (w,)] = True
    return t1, t2, valid


def _commit(t1, t2, valid, md_val, dev, cfg, s, e):
    b = t1.shape[0]
    md = np.full((b, CFG), md_val, np.float32)
    return fanout_commit(
        jnp.asarray(t1), jnp.asarray(t2), jnp.asarray(valid),
        jnp.asarray(md),
        jnp.full((b,), dev, jnp.int32), jnp.full((b,), cfg, jnp.int32),
        jnp.full((b,), s, jnp.float32), jnp.full((b,), e, jnp.float32),
        jnp.ones((b,), bool),
    )


def _expected_consumed(t1, t2, valid, dev, cfg, s, e, md):
    """Numpy re-derivation of the reference subtract accounting: per config
    list, overlap consumed from the OCC most-overlapping tracks plus the
    sub-min-duration pieces those trims discard."""
    consumed = sub_md = 0.0
    for ci in range(CFG):
        ol = np.where(
            valid[dev, ci] & (t1[dev, ci] < e) & (s < t2[dev, ci]),
            np.minimum(t2[dev, ci], e) - np.maximum(t1[dev, ci], s), 0.0
        ).sum(axis=-1)                                        # [T]
        order = sorted(range(T), key=lambda t: (-ol[t], t))
        for t in order[:OCC_TABLE[cfg, ci]]:
            if ol[t] <= 0.0:
                continue
            consumed += ol[t]
            for w in range(W):
                if not valid[dev, ci, t, w]:
                    continue
                w1, w2 = t1[dev, ci, t, w], t2[dev, ci, t, w]
                if not (w1 < e and s < w2):
                    continue
                left = min(w2, s) - w1
                right = w2 - max(w1, e)
                for piece in (left, right):
                    if 0.0 < piece < md:
                        sub_md += piece
    return consumed, sub_md


@pytest.mark.parametrize("md", [0.0, 2.5], ids=["md0", "md2.5"])
def test_bisect_sequence_conserves_availability(md):
    """Random commit sequences: total availability is exactly accounted
    for by surviving windows + consumed overlap + counted drops +
    sub-min-duration discards (no silent loss)."""
    rng = np.random.default_rng(42)
    t1, t2, valid = _disjoint_tracks(rng)
    dropped_time = 0.0
    for step in range(12):
        dev = int(rng.integers(DEV))
        cfg = int(rng.integers(CFG))
        s = float(rng.uniform(0, 40))
        e = s + float(rng.uniform(0.5, 8))
        before = _measure(t1, t2, valid)
        consumed, sub_md = _expected_consumed(
            t1[0], t2[0], valid[0], dev, cfg, s, e, md
        )
        nt1, nt2, nv, n_drop, t_drop = _commit(
            t1, t2, valid, md, dev, cfg, s, e
        )
        nt1, nt2, nv = (np.asarray(nt1), np.asarray(nt2), np.asarray(nv))
        after = _measure(nt1, nt2, nv)
        np.testing.assert_allclose(
            before, after + consumed + sub_md + float(t_drop[0]),
            rtol=1e-5, err_msg=f"step {step}", atol=1e-4,
        )
        if md == 0.0:
            # with no minimum duration nothing is legitimately discarded:
            # every missing second must be consumed or counted as dropped
            assert sub_md == 0.0
        t1, t2, valid = nt1, nt2, nv
    assert int(n_drop[0]) >= 0   # counter exists and is non-negative


def test_full_track_drop_is_counted():
    """Regression for the seed's silent right-remainder drop: a bisect of
    a full track (all W slots valid) that produces two remainders must
    count exactly one dropped piece, not lose it silently."""
    t1 = np.full((1, DEV, CFG, T, W), BIG, np.float32)
    t2 = np.full((1, DEV, CFG, T, W), BIG, np.float32)
    valid = np.zeros((1, DEV, CFG, T, W), bool)
    # config 0, track 0 of device 0: W disjoint [10i, 10i+8) windows
    for w in range(W):
        t1[0, 0, :, :, w] = 10.0 * w
        t2[0, 0, :, :, w] = 10.0 * w + 8.0
    valid[0, 0] = True
    before = _measure(t1, t2, valid)
    # commit [2, 5) ⊂ window 0 of an hp task: both remainders [0,2), [5,8)
    # satisfy md=1; the track already holds W windows so one piece drops
    nt1, nt2, nv, n_drop, t_drop = _commit(
        t1, t2, valid, 1.0, dev=0, cfg=0, s=2.0, e=5.0
    )
    # one track per list is trimmed (hp occ row is all-ones): each trimmed
    # track overflows by exactly one piece
    assert int(n_drop[0]) == CFG
    np.testing.assert_allclose(float(t_drop[0]), 3.0 * CFG, rtol=1e-6)
    after = _measure(nt1, nt2, nv)
    consumed = 3.0 * CFG   # [2,5) once per trimmed track
    np.testing.assert_allclose(
        before, after + consumed + float(t_drop[0]), rtol=1e-6
    )


def test_untouched_lists_unchanged():
    """A commit with no overlap anywhere must leave every window array
    bit-identical (inactive tracks pass through the trim unchanged)."""
    rng = np.random.default_rng(7)
    t1, t2, valid = _disjoint_tracks(rng)
    nt1, nt2, nv, n_drop, t_drop = _commit(
        t1, t2, valid, 1.0, dev=0, cfg=1, s=1e6, e=1e6 + 5.0
    )
    np.testing.assert_array_equal(np.asarray(nv), valid)
    np.testing.assert_array_equal(np.asarray(nt1)[np.asarray(nv)],
                                  t1[valid])
    np.testing.assert_array_equal(np.asarray(nt2)[np.asarray(nv)],
                                  t2[valid])
    assert int(n_drop[0]) == 0 and float(t_drop[0]) == 0.0


# ---------------------------------------------------------------------------
# compaction
# ---------------------------------------------------------------------------

@given(st.lists(st.tuples(st.floats(0, 100), st.floats(0.1, 20)),
                min_size=0, max_size=W))
@settings(max_examples=60, deadline=None)
def test_compaction_conserves_measure_of_disjoint_windows(spans):
    """For disjoint windows, compaction preserves total availability and
    yields sorted, pairwise-disjoint windows packed into the low slots."""
    # build disjoint windows by laying spans end to end with gaps > eps
    t1 = np.full((T, W), BIG, np.float32)
    t2 = np.full((T, W), BIG, np.float32)
    valid = np.zeros((T, W), bool)
    t = 0.0
    for w, (gap, d) in enumerate(spans):
        t += gap + 1e-3
        t1[0, w] = t
        t2[0, w] = t + d
        valid[0, w] = True
        t += d
    # shuffle slot order: compaction must not depend on it
    rng = np.random.default_rng(len(spans))
    perm = rng.permutation(W)
    t1[0], t2[0], valid[0] = t1[0, perm], t2[0, perm], valid[0, perm]
    before = _measure(t1, t2, valid)
    nt1, nt2, nv = compact_tracks(
        jnp.asarray(t1), jnp.asarray(t2), jnp.asarray(valid)
    )
    nt1, nt2, nv = np.asarray(nt1), np.asarray(nt2), np.asarray(nv)
    np.testing.assert_allclose(_measure(nt1, nt2, nv), before, rtol=1e-5)
    for tr in range(T):
        k = int(nv[tr].sum())
        assert nv[tr, :k].all() and not nv[tr, k:].any()  # packed low
        assert (np.diff(nt1[tr, :k]) > 0).all()           # sorted
        if k > 1:                                         # disjoint
            assert (nt1[tr, 1:k] >= nt2[tr, :k - 1]).all()


def test_compaction_merges_abutting_windows():
    t1 = np.full((1, W), BIG, np.float32)
    t2 = np.full((1, W), BIG, np.float32)
    valid = np.zeros((1, W), bool)
    # [0,4) + [4,7) abut; [9,11) stands alone
    t1[0, :3] = [4.0, 0.0, 9.0]
    t2[0, :3] = [7.0, 4.0, 11.0]
    valid[0, :3] = True
    nt1, nt2, nv = compact_tracks(
        jnp.asarray(t1), jnp.asarray(t2), jnp.asarray(valid)
    )
    nt1, nt2, nv = np.asarray(nt1), np.asarray(nt2), np.asarray(nv)
    assert nv[0].sum() == 2
    np.testing.assert_allclose(nt1[0, :2], [0.0, 9.0])
    np.testing.assert_allclose(nt2[0, :2], [7.0, 11.0])


def _compact_sorted(t1, t2, valid, *, eps=1e-6):
    """The sort-and-gather statement of compaction, the oracle the
    gather-free ``compact_tracks`` must match bit for bit: stable argsort
    by ``where(valid, t1, BIG)``, the windows permuted with
    ``take_along_axis``, a running max of ``t2`` and a segment cumsum in
    that order."""
    W = t1.shape[-1]
    order = jnp.argsort(jnp.where(valid, t1, BIG), axis=-1, stable=True)
    t1s = jnp.take_along_axis(t1, order, axis=-1)
    t2s = jnp.take_along_axis(t2, order, axis=-1)
    vs = jnp.take_along_axis(valid, order, axis=-1)
    cmax = jax.lax.cummax(jnp.where(vs, t2s, -BIG), axis=t1.ndim - 1)
    prev_end = jnp.concatenate(
        [jnp.full_like(cmax[..., :1], -BIG), cmax[..., :-1]], axis=-1
    )
    starts_seg = vs & (t1s > prev_end + eps)
    seg = jnp.cumsum(starts_seg.astype(jnp.int32), axis=-1,
                     dtype=jnp.int32) - 1
    slots = jnp.arange(W, dtype=jnp.int32)
    member = vs[..., None] & (seg[..., None] == slots)          # [..., W, W]
    head = starts_seg[..., None] & (seg[..., None] == slots)
    new_valid = jnp.any(member, axis=-2)
    new_t1 = jnp.where(
        new_valid, jnp.sum(jnp.where(head, t1s[..., None], 0.0), axis=-2), BIG
    )
    new_t2 = jnp.where(
        new_valid, jnp.max(jnp.where(member, t2s[..., None], -BIG), axis=-2),
        BIG,
    )
    return new_t1, new_t2, new_valid


_EPS = 1e-6
#: gaps between consecutive windows of a chain: overlapping, abutting,
#: within ``eps`` of abutting on either side, and apart
_GAPS = np.array([-1.0, -0.25, 0.0, _EPS / 2, _EPS, 2 * _EPS, 0.25, 1.0],
                 np.float32)


def _fragmented_tracks(seed, b, w, p_valid):
    """``[b, DEV, CFG, T, w]`` windows in every kind of track: random
    starts on a coarse grid (ties), chains whose neighbours overlap, abut
    or abut within eps, all-valid and all-invalid tracks; invalid slots
    hold arbitrary values (ties with valid starts, BIG among them).
    Slots are shuffled, so compaction cannot lean on their order."""
    rng = np.random.default_rng(seed)
    shape = (b, DEV, CFG, T, w)
    t1 = np.empty(shape, np.float32)
    t2 = np.empty(shape, np.float32)
    valid = np.empty(shape, bool)
    for idx in np.ndindex(shape[:-1]):
        kind = rng.integers(4)
        if kind == 0:                              # grid starts: ties
            a = rng.integers(0, 6, w).astype(np.float32) * 0.5
            # an inverted window (t2 < t1) makes the order among tied
            # starts visible in the output
            d = rng.choice(np.float32([-0.5, 0.0, 0.5, 1.0, 2.5]), w)
        else:                                      # a chain, in order
            gaps = rng.choice(_GAPS, w)
            d = rng.choice(np.float32([0.25, 0.5, 1.0, 3.0]), w)
            a = np.empty(w, np.float32)
            end = np.float32(rng.integers(0, 4))
            for k in range(w):
                a[k] = end + gaps[k]
                end = np.float32(a[k] + d[k])
        t1[idx], t2[idx] = a, (a + d).astype(np.float32)
        v = rng.random(w) < p_valid
        valid[idx] = (True if kind == 2 else False if kind == 3 else v)
        perm = rng.permutation(w)
        t1[idx], t2[idx], valid[idx] = t1[idx][perm], t2[idx][perm], \
            valid[idx][perm]
    # invalid slots keep arbitrary leftovers, BIG among them
    junk = rng.random(shape) < 0.3
    t1 = np.where(~valid & junk, BIG, t1).astype(np.float32)
    t2 = np.where(~valid & ~junk & (rng.random(shape) < 0.5),
                  -t2, t2).astype(np.float32)
    return t1, t2, valid


@given(st.integers(0, 2**32 - 1), st.integers(1, 3), st.sampled_from([8, 16]),
       st.sampled_from([0.0, 0.3, 0.7, 1.0]))
@settings(max_examples=40, deadline=None)
def test_compaction_matches_sorted_oracle_bit_for_bit(seed, b, w, p_valid):
    """The gather-free compaction equals the argsort + ``take_along_axis``
    statement exactly (t1, t2 and valid, every slot) on batched states
    with tied starts, junk in invalid slots, all-valid and all-invalid
    tracks, and windows that overlap, abut or abut within eps."""
    t1, t2, valid = _fragmented_tracks(seed, b, w, p_valid)
    args = (jnp.asarray(t1), jnp.asarray(t2), jnp.asarray(valid))
    got = jax.jit(compact_tracks)(*args)
    want = jax.jit(_compact_sorted)(*args)
    for name, g, x in zip(("t1", "t2", "valid"), got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(x),
                                      err_msg=name)


def test_compaction_lowers_without_gather_sort_or_scatter():
    """``compact_state`` on a batched ``[8, 4, 3, 2, 16]`` state is
    compares, selects and reduces only: no ``gather``, ``sort`` or
    ``scatter`` primitive anywhere in its jaxpr."""
    shape = (8, 4, 3, 2, 16)
    win = jax.ShapeDtypeStruct(shape, jnp.float32)
    state = SchedState(
        win_t1=win, win_t2=win,
        win_valid=jax.ShapeDtypeStruct(shape, jnp.bool_),
        min_dur=jax.ShapeDtypeStruct((8, 3), jnp.float32),
    )

    def prims(jaxpr):
        for eqn in jaxpr.eqns:
            yield eqn.primitive.name
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from prims(sub)

    names = set(prims(jax.make_jaxpr(compact_state)(state).jaxpr))
    assert "reduce_max" in names                  # the traversal sees ops
    assert not {n for n in names
                if n.startswith(("gather", "sort", "scatter"))}, names
