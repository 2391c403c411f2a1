"""The scheduler state of §IV as JAX arrays, and the jitted commit and
compaction steps the fleet engine runs on it.

``export_state`` converts a live serial ``RASScheduler`` (the reference,
core/windows.py) into one pytree of arrays, a valid jit carry:

    win_t1, win_t2      f32[DEV, CFG, T, W]   availability windows
    win_valid           bool[DEV, CFG, T, W]
    min_dur             f32[CFG]              per-config minimum duration

The §IV.A.1 fan-out commit is one trace, ``_commit_row``, in two
layouts: ``commit_device_rows`` (one device's rows, the fleet engine's
HP commit) and ``fanout_commit_lanes`` (replica axis last, the placement
kernel and its oracle).  ``fanout_commit`` is the per-row reference the
tests pin both against.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.tasks import ALL_CONFIGS, DEVICE_CORES

BIG = 1e30


class SchedState(NamedTuple):
    win_t1: jnp.ndarray     # [DEV, CFG, T, W]
    win_t2: jnp.ndarray
    win_valid: jnp.ndarray
    min_dur: jnp.ndarray    # [CFG]


CFG_INDEX = {c.name: i for i, c in enumerate(ALL_CONFIGS)}


def export_state(sched, max_windows: int = 16) -> SchedState:
    """Snapshot a live RASScheduler into array form."""
    n_dev = sched.n_devices
    n_cfg = len(ALL_CONFIGS)
    max_tracks = max(
        sched.devices[0].lists[c.name].track_count for c in ALL_CONFIGS
    )
    t1 = np.full((n_dev, n_cfg, max_tracks, max_windows), BIG, np.float32)
    t2 = np.full_like(t1, BIG)
    valid = np.zeros(t1.shape, bool)
    for d, dev in enumerate(sched.devices):
        for ci, cfg in enumerate(ALL_CONFIGS):
            al = dev.lists[cfg.name]
            for ti, track in enumerate(al.tracks):
                for wi, w in enumerate(track[:max_windows]):
                    t1[d, ci, ti, wi] = w.t1
                    t2[d, ci, ti, wi] = min(w.t2, BIG)
                    valid[d, ci, ti, wi] = True
    return SchedState(
        win_t1=jnp.asarray(t1),
        win_t2=jnp.asarray(t2),
        win_valid=jnp.asarray(valid),
        min_dur=jnp.asarray([c.padded_time for c in ALL_CONFIGS], jnp.float32),
    )


# ---------------------------------------------------------------------------
# config geometry (static tables used by the fan-out commit)
# ---------------------------------------------------------------------------

#: cores per track of each config list == the config's own core count.
CFG_CORES = np.array([c.cores for c in ALL_CONFIGS], np.int32)

#: tracks per config list.
CFG_TRACKS = (DEVICE_CORES // CFG_CORES).astype(np.int32)

#: OCC_TABLE[task_cfg, list_cfg] — how many tracks of ``list_cfg`` a
#: committed ``task_cfg`` task occupies: ceil(task_cores / track_cores),
#: capped at the list's track count (the §IV.A.1 fan-out width; matches
#: AvailabilityList.subtract's ``occupy_tracks``).
OCC_TABLE = np.minimum(
    -(-CFG_CORES[:, None] // CFG_CORES[None, :]), CFG_TRACKS[None, :]
).astype(np.int32)


def _iota(shape, axis):
    """int32 iota along ``axis`` of ``shape`` (never the default int, which
    is int64 under JAX_ENABLE_X64)."""
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _tree_sum(x, axis):
    """Sum over ``axis`` (kept as size 1) by pairwise halving, in a fixed
    order.  A reduce leaves the float summation order to the backend;
    spelling the adds out fixes it, so XLA and Mosaic round the same sums
    identically."""
    tails = []
    while x.shape[axis] > 1:
        n = x.shape[axis]
        h = n // 2
        if n % 2:
            tails.append(jax.lax.slice_in_dim(x, n - 1, n, axis=axis))
        x = (jax.lax.slice_in_dim(x, 0, h, axis=axis)
             + jax.lax.slice_in_dim(x, h, 2 * h, axis=axis))
    for t in tails:
        x = x + t
    return x


def _trim_tracks(t1, t2, valid, s, e, md, active):
    """Multi-remainder trim of ``[s, e)`` from every window of the active
    tracks (``[..., W, N]`` arrays, replica axis last; ``s``/``e``/``md``/
    ``active`` broadcast).

    Every overlapping window keeps its left piece ``[t1, s)`` and right
    piece ``[e, t2)`` when they satisfy the minimum duration — the exact
    semantics of ``AvailabilityList.subtract``.  Pieces stay *in place*:
    a window keeps its slot for its surviving piece (left preferred), so
    only the straddle window — one whose left AND right pieces both
    survive — needs a second slot.  Tracks hold pairwise-disjoint
    windows, so at most one straddle exists per track; its right piece
    spills into the first free slot.  O(W) broadcast/compare/reduce ops
    throughout (this is the per-commit hot path of the fleet scan, and
    it must also lower inside the Pallas placement kernel).

    Pieces that satisfy the minimum duration but find no free slot (or
    extra straddles of non-disjoint test inputs) are *counted*, never
    silently lost: returns ``(t1', t2', valid', n_dropped, time_dropped)``
    with the drop tallies reduced over the window axis (kept as size 1).
    """
    W = t1.shape[-2]
    slots = _iota((W, 1), 0)
    ov = valid & (t1 < e) & (s < t2) & active
    left_t2 = jnp.minimum(t2, s)
    right_t1 = jnp.maximum(t1, e)
    left_ok = ov & (left_t2 - t1 >= md)
    right_ok = ov & (t2 - right_t1 >= md)
    both = left_ok & right_ok
    # in-place: the slot keeps the left piece when it survives, else the
    # right piece, else goes free
    new_valid = (ov & (left_ok | right_ok)) | (~ov & valid)
    new_t1 = jnp.where(ov & ~left_ok & right_ok, right_t1, t1)
    new_t2 = jnp.where(ov & left_ok, left_t2, t2)
    new_t1 = jnp.where(new_valid, new_t1, BIG)
    new_t2 = jnp.where(new_valid, new_t2, BIG)
    # spill the (single) straddle's right piece into the first free slot
    # — first-index min-reduces, no argmin/gather
    first_free = jnp.min(
        jnp.where(~new_valid, slots, W), axis=-2, keepdims=True
    )
    first_both = jnp.min(jnp.where(both, slots, W), axis=-2, keepdims=True)
    placed = (first_both < W) & (first_free < W)
    oh_b = both & (slots == first_both)
    sp_t1 = jnp.sum(jnp.where(oh_b, right_t1, 0.0), axis=-2, keepdims=True)
    sp_t2 = jnp.sum(jnp.where(oh_b, t2, 0.0), axis=-2, keepdims=True)
    place = placed & (slots == first_free)
    new_t1 = jnp.where(place, sp_t1, new_t1)
    new_t2 = jnp.where(place, sp_t2, new_t2)
    new_valid = new_valid | place
    # every straddle right piece except a successfully-placed first one
    # is dropped (counted, not lost)
    dropped = both & ~(placed & (slots == first_both))
    n_drop = jnp.sum(dropped.astype(jnp.int32), axis=-2, keepdims=True,
                     dtype=jnp.int32)
    t_drop = jnp.sum(jnp.where(dropped, t2 - right_t1, 0.0), axis=-2,
                     keepdims=True)
    return new_t1, new_t2, new_valid, n_drop, t_drop


def _commit_row(t1d, t2d, vd, md, cfg, s, e, do):
    """§IV.A.1 fan-out trim of one device's windows, replica axis last:
    consume ``[s, e)`` from the ``OCC_TABLE[cfg, ci]`` most-overlapping
    tracks of every config list ``ci`` (multi-remainder).

    ``t1d``/``t2d``/``vd`` are ``[CFG, T, W, N]``; ``md`` ``[CFG, 1, N]``;
    ``cfg`` i32, ``s``/``e`` f32 and ``do`` bool are ``[1, N]``.  Returns
    ``(t1', t2', valid', n_dropped [1, N], time_dropped [1, N])``; the
    rows are meaningful only where ``do`` holds (the callers write back
    under that mask).  Both commit forms below share this one trace.
    """
    n_cfg, T = t1d.shape[:2]
    ov = vd & (t1d < e) & (s < t2d)
    ol = _tree_sum(
        jnp.where(ov, jnp.minimum(t2d, e) - jnp.maximum(t1d, s), 0.0), axis=2
    )                                                          # [CFG, T, 1, N]
    # stable descending rank of tracks by overlap (first index wins ties):
    # beats[c, t, u] = track u outranks track t
    ol_t, ol_u = ol[:, :, None], ol[:, None]
    u_first = _iota((T, T, 1, 1), 1) < _iota((T, T, 1, 1), 0)
    beats = (ol_u > ol_t) | ((ol_u == ol_t) & u_first)
    rank = jnp.sum(beats.astype(jnp.int32), axis=2, dtype=jnp.int32)
    # occupancy width: ceil(task_cores / track_cores), selected from
    # OCC_TABLE by the (data-dependent) committed config.  Unrolled over
    # the tiny static table with scalar constants only, so no array
    # constant is captured when this traces inside the Pallas kernel.
    list_ids = _iota((n_cfg, 1, 1), 0)
    occ = jnp.zeros((n_cfg,) + cfg.shape, jnp.int32)
    for ti in range(n_cfg):
        for li in range(n_cfg):
            occ = jnp.where(
                (cfg == ti) & (list_ids == li),
                jnp.int32(OCC_TABLE[ti, li]), occ,
            )                                                  # [CFG, 1, N]
    active = do & (rank < occ[:, None]) & (ol > 0.0)           # [CFG, T, 1, N]
    nt1, nt2, nv, n_drop, t_drop = _trim_tracks(
        t1d, t2d, vd, s, e, md[:, None], active
    )
    n_drop = jnp.where(do, jnp.sum(n_drop, axis=(0, 1), dtype=jnp.int32), 0)
    t_drop = jnp.where(do, jnp.sum(t_drop, axis=(0, 1)), 0.0)
    return nt1, nt2, nv, n_drop, t_drop


def commit_device_rows(t1d, t2d, vd, min_dur, cfg, s, e, do):
    """§IV.A.1 fan-out trim of one device's rows, one row per replica:
    consume ``[s, e)`` from the ``OCC_TABLE[cfg, ci]`` most-overlapping
    tracks of every config list ``ci`` (multi-remainder).

    Shapes: rows ``[N, CFG, T, W]``; ``min_dur [N, CFG]``; ``cfg`` i32,
    ``s``/``e`` f32 and ``do`` bool ``[N]``.  Returns ``(t1', t2',
    valid', n_dropped [N], time_dropped [N])``: rows where ``do`` is
    False come back bit-identical to the input.  The trim is
    ``_commit_row`` on the rows turned replica-last, the same trace the
    Pallas placement kernel commits with (``fanout_commit_lanes``).
    """
    lanes = lambda a: jnp.moveaxis(a, 0, -1)
    back = lambda a: jnp.moveaxis(a, -1, 0)
    nt1, nt2, nv, n_drop, t_drop = _commit_row(
        lanes(t1d), lanes(t2d), lanes(vd), min_dur.T[:, None], cfg[None],
        s[None], e[None], do[None],
    )
    dom = do[:, None, None, None]
    return (jnp.where(dom, back(nt1), t1d), jnp.where(dom, back(nt2), t2d),
            jnp.where(dom, back(nv), vd), n_drop[0], t_drop[0])


def fanout_commit(t1, t2, valid, min_dur, dev, cfg, s, e, do):
    """Batched §IV.A.1 fan-out commit: consume ``[s, e)`` on device
    ``dev`` across every config list, trimming the ``OCC_TABLE[cfg, ci]``
    most-overlapping tracks of each list ``ci`` (multi-remainder).

    Shapes: windows ``[N, Dev, CFG, T, W]``; ``min_dur [N, CFG]``;
    ``dev``/``cfg`` i32 ``[N]``; ``s``/``e`` f32 ``[N]``; ``do`` bool
    ``[N]`` masks the commit per row.  Returns
    ``(t1', t2', valid', n_dropped [N], time_dropped [N])``.

    Each row may commit on its own device, so the row is gathered with
    ``take_along_axis``, trimmed by ``commit_device_rows`` and written
    back with an ``.at[].set`` scatter.  On a TPU that write is no
    in-place row update: XLA lowers it as a scatter over a flattened
    ``[N x Dev, CFG, T, W]`` view and relays out the whole state to
    reach it.  The fleet engine commits on one device index shared by
    the batch and uses ``commit_device_rows`` on a dynamic slice
    instead; this form is the per-row reference the tests compare the
    engine's commit with.
    """
    N = t1.shape[0]
    idx = dev[:, None, None, None, None]
    take = lambda a: jnp.take_along_axis(a, idx, axis=1)[:, 0]
    nt1, nt2, nv, n_drop, t_drop = commit_device_rows(
        take(t1), take(t2), take(valid), min_dur, cfg, s, e, do
    )
    rows = jnp.arange(N, dtype=jnp.int32)
    return (t1.at[rows, dev].set(nt1), t2.at[rows, dev].set(nt2),
            valid.at[rows, dev].set(nv), n_drop, t_drop)


def fanout_commit_lanes(t1, t2, valid, min_dur, dev, cfg, s, e, do):
    """``fanout_commit`` in the Pallas placement kernel's layout, replica
    axis last: windows ``[Dev, CFG, T, W, N]``; ``min_dur [CFG, 1, N]``;
    ``dev``/``cfg`` i32, ``s``/``e`` f32, ``do`` bool ``[1, N]``.

    Broadcast/compare/reduce ops only (one-hot ``where`` + sum over the
    device axis instead of a gather/scatter), the subset that lowers in a
    kernel body.  Returns ``(t1', t2', valid', n_dropped [1, N])``.
    """
    n_dev = t1.shape[0]
    dev_oh = _iota((n_dev, 1, 1, 1, 1), 0) == dev              # [Dev,1,1,1,N]
    t1d = jnp.sum(jnp.where(dev_oh, t1, 0.0), axis=0)          # [CFG, T, W, N]
    t2d = jnp.sum(jnp.where(dev_oh, t2, 0.0), axis=0)
    vd = jnp.max((valid & dev_oh).astype(jnp.int32), axis=0) > 0
    nt1, nt2, nv, n_drop, _ = _commit_row(
        t1d, t2d, vd, min_dur, cfg, s, e, do
    )
    sel = dev_oh & do
    return (jnp.where(sel, nt1, t1), jnp.where(sel, nt2, t2),
            (sel & nv) | (~sel & valid), n_drop)


def compact_tracks(t1, t2, valid, *, eps: float = 1e-6):
    """Per-track window compaction: order windows by start and merge
    adjacent/abutting ones (``next.t1 <= prev.t2 + eps``) so remainders
    produced by repeated bisects cannot clog the fixed-W slots.  Disjoint
    windows conserve total availability exactly.  ``[..., W]`` arrays ->
    ``(t1', t2', valid')``, bit-identical to a stable argsort by
    ``where(valid, t1, BIG)``, a running max of ``t2`` and a segment
    cumsum over the sorted windows.

    No sort and no gather (a TPU gathers a W-slot permutation one tiny
    slice per index): ``before[..., j, i]``, window j precedes window i
    in that stable order, comes from W x W compares, and each
    sorted-order quantity is a masked reduce over j; output slot k takes
    segment k by one-hot select and reduce.  Window i lies on the lanes
    of every ``[..., W, W]`` intermediate."""
    W = t1.shape[-1]
    key = jnp.where(valid, t1, BIG)
    jj, ii = _iota((W, W), 0), _iota((W, W), 1)
    k_j, k_i = key[..., :, None], key[..., None, :]
    before = (k_j < k_i) | ((k_j == k_i) & (jj < ii))          # [..., j, i]
    # end of the windows before i (the sorted cummax, shifted by one);
    # the same f32 ``+ eps`` as the sorted form, so it rounds alike
    prev_end = jnp.max(
        jnp.where(before & valid[..., :, None], t2[..., :, None], -BIG),
        axis=-2,
    )
    start = valid & (t1 > prev_end + eps)
    seg = jnp.sum(
        ((before | (jj == ii)) & start[..., :, None]).astype(jnp.int32),
        axis=-2, dtype=jnp.int32,
    ) - 1
    slot = seg[..., None, :] == jj                             # [..., k, i]
    member = valid[..., None, :] & slot
    new_valid = jnp.any(member, axis=-1)
    # a segment has one start, so the sum has one non-zero term: exact
    new_t1 = jnp.where(
        new_valid,
        jnp.sum(jnp.where(start[..., None, :] & slot, t1[..., None, :], 0.0),
                axis=-1),
        BIG,
    )
    new_t2 = jnp.where(
        new_valid,
        jnp.max(jnp.where(member, t2[..., None, :], -BIG), axis=-1),
        BIG,
    )
    return new_t1, new_t2, new_valid


def compact_state(state: SchedState) -> SchedState:
    """Apply window compaction to every (device, config, track) of a
    (possibly batched) SchedState."""
    t1, t2, valid = compact_tracks(
        state.win_t1, state.win_t2, state.win_valid
    )
    return state._replace(win_t1=t1, win_t2=t2, win_valid=valid)
