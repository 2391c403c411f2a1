"""Fully-jitted scheduler state: the paper's §IV data structures as JAX
arrays, with allocation steps that run as single XLA programs.

This substantiates DESIGN.md §3: on a TPU-hosted controller the whole
scheduling decision — multi-containment query across every worker, slot
selection, window bisection and link reservation — is one fused device
program (`hp_place` / `lp_place` below), with *no host round-trips*.
The Python structures in `windows.py` / `netlink.py` remain the reference;
`export_state` converts a live RASScheduler and the equivalence tests in
tests/test_jax_state.py pin the two implementations together.

State layout (one pytree of arrays, a valid jit carry):

    win_t1, win_t2      f32[DEV, CFG, T, W]   availability windows
    win_valid           bool[DEV, CFG, T, W]
    min_dur             f32[CFG]              per-config minimum duration
    link_t1, link_t2    f32[B]                discretised link buckets
    link_cap, link_used i32[B]
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import checkify

from repro.analysis import sanitize as _sanitize
from repro.core.tasks import ALL_CONFIGS, DEVICE_CORES

BIG = 1e30


class SchedState(NamedTuple):
    win_t1: jnp.ndarray     # [DEV, CFG, T, W]
    win_t2: jnp.ndarray
    win_valid: jnp.ndarray
    min_dur: jnp.ndarray    # [CFG]
    link_t1: jnp.ndarray    # [B]
    link_t2: jnp.ndarray
    link_cap: jnp.ndarray
    link_used: jnp.ndarray


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
    link = sched.link
    return SchedState(
        win_t1=jnp.asarray(t1),
        win_t2=jnp.asarray(t2),
        win_valid=jnp.asarray(valid),
        min_dur=jnp.asarray([c.padded_time for c in ALL_CONFIGS], jnp.float32),
        link_t1=jnp.asarray([b.t1 for b in link.buckets], jnp.float32),
        link_t2=jnp.asarray([b.t2 for b in link.buckets], jnp.float32),
        link_cap=jnp.asarray([b.capacity for b in link.buckets], jnp.int32),
        link_used=jnp.asarray([len(b.items) for b in link.buckets], jnp.int32),
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


def _csum(x):
    """Inclusive cumsum over the last axis via a triangular mask — only
    broadcast/compare/reduce ops, so the same code lowers inside a Pallas
    kernel body (jnp.cumsum does not)."""
    n = x.shape[-1]
    tril = (jnp.arange(n, dtype=jnp.int32)[:, None]
            <= jnp.arange(n, dtype=jnp.int32)[None, :])   # k <= w
    return jnp.sum(jnp.where(tril, x[..., :, None], 0), axis=-2)


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


def fanout_commit(t1, t2, valid, min_dur, dev, cfg, s, e, do, *,
                  sanitize: bool = False):
    """Batched §IV.A.1 fan-out commit: consume ``[s, e)`` on device
    ``dev`` across every config list, trimming the ``OCC_TABLE[cfg, ci]``
    most-overlapping tracks of each list ``ci`` (multi-remainder).

    Shapes: windows ``[N, Dev, CFG, T, W]``; ``min_dur [N, CFG]``;
    ``dev``/``cfg`` i32 ``[N]``; ``s``/``e`` f32 ``[N]``; ``do`` bool
    ``[N]`` masks the commit per row.  Returns
    ``(t1', t2', valid', n_dropped [N], time_dropped [N])``.

    The committed device row is gathered with ``take_along_axis`` and
    written back with an ``.at[].set`` scatter, so XLA updates that row
    in place inside the fleet scan instead of rewriting the whole
    ``[N, Dev, CFG, T, W]`` state per commit.  The trim itself is
    ``_commit_row`` on the row turned replica-last, the same trace the
    Pallas placement kernel commits with (``fanout_commit_lanes``).
    """
    N = t1.shape[0]
    idx = dev[:, None, None, None, None]
    take = lambda a: jnp.take_along_axis(a, idx, axis=1)[:, 0]
    t1d, t2d, vd = take(t1), take(t2), take(valid)             # [N, CFG, T, W]
    lanes = lambda a: jnp.moveaxis(a, 0, -1)
    back = lambda a: jnp.moveaxis(a, -1, 0)
    nt1, nt2, nv, n_drop, t_drop = _commit_row(
        lanes(t1d), lanes(t2d), lanes(vd), min_dur.T[:, None], cfg[None],
        s[None], e[None], do[None],
    )
    # write back only committed rows (do=False rows stay bit-identical)
    rows = jnp.arange(N, dtype=jnp.int32)
    dom = do[:, None, None, None]
    out_t1 = t1.at[rows, dev].set(jnp.where(dom, back(nt1), t1d))
    out_t2 = t2.at[rows, dev].set(jnp.where(dom, back(nt2), t2d))
    out_valid = valid.at[rows, dev].set(jnp.where(dom, back(nv), vd))
    n_drop, t_drop = n_drop[0], t_drop[0]
    if sanitize:
        # checkify invariants (only valid under a checkify.checkify
        # transform; checks cannot lower inside a Pallas kernel body)
        _sanitize.check_windows(out_t1, out_t2, out_valid, "fanout_commit")
        _sanitize.check_no_avail_increase(
            _sanitize.total_availability(t1, t2, valid, batch_axes=1),
            _sanitize.total_availability(
                out_t1, out_t2, out_valid, batch_axes=1
            ),
            "fanout_commit",
        )
    return out_t1, out_t2, out_valid, n_drop, t_drop


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
    """Per-track window compaction: sort windows by start and merge
    adjacent/abutting ones (``next.t1 <= prev.t2 + eps``) so remainders
    produced by repeated bisects cannot clog the fixed-W slots.  Disjoint
    windows conserve total availability exactly.  ``[..., W]`` arrays ->
    ``(t1', t2', valid')``."""
    W = t1.shape[-1]
    order = jnp.argsort(jnp.where(valid, t1, BIG), axis=-1)
    t1s = jnp.take_along_axis(t1, order, axis=-1)
    t2s = jnp.take_along_axis(t2, order, axis=-1)
    vs = jnp.take_along_axis(valid, order, axis=-1)
    cmax = jax.lax.cummax(jnp.where(vs, t2s, -BIG), axis=t1.ndim - 1)
    prev_end = jnp.concatenate(
        [jnp.full_like(cmax[..., :1], -BIG), cmax[..., :-1]], axis=-1
    )
    starts_seg = vs & (t1s > prev_end + eps)
    seg = _csum(starts_seg.astype(jnp.int32)) - 1
    lanes = jnp.arange(W, dtype=jnp.int32)
    member = vs[..., None] & (seg[..., None] == lanes)         # [..., W, W]
    head = starts_seg[..., None] & (seg[..., None] == lanes)
    new_valid = jnp.any(member, axis=-2)
    new_t1 = jnp.where(
        new_valid, jnp.sum(jnp.where(head, t1s[..., None], 0.0), axis=-2), BIG
    )
    new_t2 = jnp.where(
        new_valid, jnp.max(jnp.where(member, t2s[..., None], -BIG), axis=-2),
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


# ---------------------------------------------------------------------------
# queries (pure functions of SchedState)
# ---------------------------------------------------------------------------

def _device_slot(state: SchedState, dev, cfg_idx, q1, deadline, dur):
    """Earliest feasible (track, window, start) on one device+config."""
    t1 = state.win_t1[dev, cfg_idx]          # [T, W]
    t2 = state.win_t2[dev, cfg_idx]
    valid = state.win_valid[dev, cfg_idx]
    start = jnp.maximum(t1, q1)
    feasible = valid & (start + dur <= jnp.minimum(t2, deadline))
    key = jnp.where(feasible, start, BIG)
    flat = jnp.argmin(key.reshape(-1))
    best = key.reshape(-1)[flat]
    T, W = t1.shape
    return best < BIG, flat // W, flat % W, best


def _bisect(state: SchedState, dev, cfg_idx, track, slot, s, e,
            do=True, *, sanitize: bool = False
            ) -> tuple[SchedState, jnp.ndarray]:
    """Consume [s, e) from device ``dev`` across EVERY config list (the
    §IV.A.1 fan-out write) for a committed task of config ``cfg_idx``,
    keeping ALL min-duration remainders (multi-remainder form — the exact
    semantics of ``AvailabilityList.subtract``, including the
    ``OCC_TABLE`` track fan-out for wide tasks).  ``track``/``slot`` are
    retained for API compatibility; the fan-out recomputes the
    most-overlapping tracks per config.  ``do`` masks the commit.

    Returns ``(new_state, n_dropped)`` where ``n_dropped`` counts
    min-duration-satisfying remainders that found no free window slot
    (fragmentation telemetry — previously a silent drop)."""
    del track, slot
    t1, t2, valid, n_drop, _ = fanout_commit(
        state.win_t1[None], state.win_t2[None], state.win_valid[None],
        state.min_dur[None],
        jnp.asarray(dev, jnp.int32)[None],
        jnp.asarray(cfg_idx, jnp.int32)[None],
        jnp.asarray(s, jnp.float32)[None],
        jnp.asarray(e, jnp.float32)[None],
        jnp.asarray(do, bool)[None],
        sanitize=sanitize,
    )
    return state._replace(
        win_t1=t1[0], win_t2=t2[0], win_valid=valid[0]
    ), n_drop[0]


@functools.partial(jax.jit, static_argnames=("cfg_idx", "sanitize"))
def hp_place_jit(state: SchedState, dev, now, *, cfg_idx: int = 0,
                 sanitize: bool = False):
    """High-priority placement (§IV.B.1): strict containment of
    [now, now+dur) on the source device, committed in one XLA program.
    ``sanitize=True`` traces the checkify invariants into the program
    (only valid under a ``checkify.checkify`` transform); the default
    trace carries no checks and stays byte-identical to the old build."""
    if sanitize:
        _sanitize.check_sched_state(state, "hp_place input")
        before = _sanitize.total_availability(
            state.win_t1, state.win_t2, state.win_valid
        )
    dur = state.min_dur[cfg_idx]
    found, track, slot, start = _device_slot(
        state, dev, cfg_idx, now, now + dur + 1e-6, dur
    )
    new_state, _ = _bisect(
        state, dev, cfg_idx, track, slot, start, start + dur, do=found,
        sanitize=sanitize,
    )
    if sanitize:
        _sanitize.check_sched_state(new_state, "hp_place output")
        _sanitize.check_no_avail_increase(
            before,
            _sanitize.total_availability(
                new_state.win_t1, new_state.win_t2, new_state.win_valid
            ),
            "hp_place",
        )
    return found, start, new_state


@functools.lru_cache(maxsize=None)
def _hp_place_checked(cfg_idx: int):
    fn = functools.partial(hp_place_jit, cfg_idx=cfg_idx, sanitize=True)
    return checkify.checkify(fn, errors=checkify.user_checks)


def hp_place(state: SchedState, dev, now, *, cfg_idx: int = 0):
    """Public HP placement: dispatches to the checkify-sanitized variant
    when ``REPRO_SANITIZE=1`` (repro.analysis.sanitize), raising
    ``checkify.JaxRuntimeError`` on an invariant trip; otherwise runs the
    check-free jitted program (``hp_place_jit``)."""
    if _sanitize.enabled():
        err, out = _hp_place_checked(cfg_idx)(state, dev, now)
        err.throw()
        return out
    return hp_place_jit(state, dev, now, cfg_idx=cfg_idx)


# Donation is deliberately withheld: callers (calib harness, fleet replay)
# reuse the input SchedState after the call, so donating the carry would
# invalidate buffers they still hold.
@functools.partial(jax.jit, static_argnames=("cfg_idx", "n_tasks", "sanitize"))
def lp_place_jit(state: SchedState, src_dev, now, deadline, *,  # repro: lint-ok(scan-donate)
                 cfg_idx: int = 1, n_tasks: int = 1,
                 sanitize: bool = False):
    """Low-priority request (§IV.B.2): reserve a link slot per task, run the
    multi-containment query across all devices, prefer the source device,
    commit each placement — all inside one jitted scan.  ``sanitize=True``
    traces the checkify invariants (only valid under a
    ``checkify.checkify`` transform)."""
    if sanitize:
        _sanitize.check_sched_state(state, "lp_place input")
        before = _sanitize.total_availability(
            state.win_t1, state.win_t2, state.win_valid
        )
    dur = state.min_dur[cfg_idx]
    n_dev = state.win_t1.shape[0]

    def link_reserve(st: SchedState, t_p):
        ok = (st.link_used < st.link_cap) & (st.link_t2 > t_p)
        idx = jnp.argmax(ok)
        found = ok.any()
        used = st.link_used.at[idx].add(jnp.where(found, 1, 0))
        return st._replace(link_used=used), found, st.link_t2[idx]

    def place_one(carry, _):
        st, n_ok = carry
        st, comm_ok, comm_end = link_reserve(st, now)
        # multi-containment across every device
        founds, tracks, slots, starts = jax.vmap(
            lambda d: _device_slot(st, d, cfg_idx, now, deadline, dur)
        )(jnp.arange(n_dev, dtype=jnp.int32))
        # remote devices cannot start before their transfer lands
        starts_adj = jnp.where(
            jnp.arange(n_dev, dtype=jnp.int32) == src_dev,
            starts, jnp.maximum(starts, comm_end)
        )
        feasible = founds & (starts_adj + dur <= deadline)
        feasible &= (jnp.arange(n_dev, dtype=jnp.int32) == src_dev) | comm_ok
        # prefer source device, then earliest start
        key = jnp.where(feasible, starts_adj, BIG)
        key = key - jnp.where(
            jnp.arange(n_dev, dtype=jnp.int32) == src_dev, 1e-3, 0.0
        )
        d = jnp.argmin(key)
        ok = feasible[d]
        start = starts_adj[d]
        st, _ = _bisect(st, d, cfg_idx, tracks[d], slots[d], start,
                        start + dur, do=ok, sanitize=sanitize)
        return (st, n_ok + ok.astype(jnp.int32)), (ok, d, start)

    (state, n_ok), (oks, devs, starts) = jax.lax.scan(
        place_one, (state, jnp.asarray(0, jnp.int32)), None, length=n_tasks
    )
    all_ok = n_ok == n_tasks
    if sanitize:
        _sanitize.check_sched_state(state, "lp_place output")
        _sanitize.check_no_avail_increase(
            before,
            _sanitize.total_availability(
                state.win_t1, state.win_t2, state.win_valid
            ),
            "lp_place",
        )
    return all_ok, oks, devs, starts, state


@functools.lru_cache(maxsize=None)
def _lp_place_checked(cfg_idx: int, n_tasks: int):
    fn = functools.partial(
        lp_place_jit, cfg_idx=cfg_idx, n_tasks=n_tasks, sanitize=True
    )
    return checkify.checkify(fn, errors=checkify.user_checks)


def lp_place(state: SchedState, src_dev, now, deadline, *,
             cfg_idx: int = 1, n_tasks: int = 1):
    """Public LP placement: dispatches to the checkify-sanitized variant
    when ``REPRO_SANITIZE=1`` (repro.analysis.sanitize), raising
    ``checkify.JaxRuntimeError`` on an invariant trip; otherwise runs the
    check-free jitted program (``lp_place_jit``)."""
    if _sanitize.enabled():
        err, out = _lp_place_checked(cfg_idx, n_tasks)(
            state, src_dev, now, deadline
        )
        err.throw()
        return out
    return lp_place_jit(
        state, src_dev, now, deadline, cfg_idx=cfg_idx, n_tasks=n_tasks
    )
