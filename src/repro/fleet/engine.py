"""Batched fixed-step fleet simulator: B replicas per XLA program.

The serial simulator (sim/engine.py) is an event-driven replay of one
testbed — rich (controller serialisation, execution jitter, preemption)
but one replica per Python process.  This engine trades event granularity
for throughput: a `jax.lax.scan` over frame periods advances **every
replica of a Monte-Carlo fleet at once**, with the per-tick pipeline

    housekeeping → victim re-queue → frame release → HP placement
                 → LP placement → accounting

entirely inside one jitted program.  Placement reuses the §IV data
structures of core/jax_state.py — every LP placement attempt (the
§IV.B.2 multi-containment query over both configs, device selection and
the §IV.A.1 multi-remainder fan-out commit) runs through the *fused
placement kernel* (kernels/placement/): one launch per attempt for the
whole fleet.  Every ``compact_every`` ticks an in-scan compaction pass
merges abutting windows per track so bisect remainders cannot clog the
fixed-W slots.

Long scans are *segmented*: `fleet_run` is a Python driver over a jitted
``segment_frames``-tick scan with donated carry buffers, so the XLA
program (and its compile time) is keyed on the segment length rather
than the full trace length, and carry buffers are updated in place.
Ticks past the true trace length are masked to exact no-ops, so results
are bit-identical to an unsegmented run.

Preemption fidelity (§IV.B.3): each device carries a one-deep *victim
cache* of its most recently committed LP placement.  The serial engine
evicts the overlapping LP task with the farthest deadline; deadlines grow
with release time, so the newest commit is that victim whenever its
reserved slot overlaps the requested HP window (older overlapping tasks
are invisible to the one-deep cache).  When the HP containment query
misses:

- the cached victim overlaps [now, now+dur) → *committed preemption*: the
  victim loses its completion credit, gets one immediate reallocation
  attempt at HP-commit time (the serial §VI.A path), and on failure
  enters the bounded re-queue buffer; HP runs either way.
- no overlapping victim → HP **fails admission** (the serial engine's
  ``no-preemptable`` path) and the frame dies — occasionally spuriously,
  when only an older-than-cached task overlapped.

The per-tick re-queue pass re-places buffered victims through the same
two-config window semantics (source preference, transfer gating) before
new frames are released; a victim whose deadline can no longer fit even
the 4-core config is dropped and counted as ``missed_by_preemption``
(as is a victim arriving to a full buffer).

Fidelity contract (what the abstraction keeps / drops):

- keeps: RAS window semantics (placements are guaranteed, so a committed
  task completes by its deadline — violations surface as placement
  failures), 2-core-preferred / 4-core-fallback LP configs, source-device
  preference, serial-link transfer queueing, per-replica bandwidth churn,
  HP preemption with single-victim eviction + re-queue + deadline-expiry
  drops, HP admission failure when nothing is preemptable, the
  multi-remainder §IV.A.1 fan-out (both min-duration remainders survive a
  bisect, wide tasks consume ``ceil(cores/track_cores)`` tracks), and
  explicit fragmentation accounting (``remainders_dropped`` counts any
  remainder lost to a full window array — previously a silent drop).
- drops: controller queueing latency, run-time jitter, per-victim
  reallocation latency (the immediate attempt is instantaneous; buffered
  retries happen at tick granularity), depth of the victim pool (one
  cached commit per device — older overlapping tasks cannot be evicted,
  so some preemptions become spurious admission failures), and
  retroactive frame accounting (a frame whose LP task is later preempted
  keeps its placement-time completion credit; the victim itself is
  re-accounted exactly).  calib/ quantifies the net drift per scenario.

Use the serial engine for paper-figure replication; use the fleet for
scenario sweeps at scale (sweep.py fans seed × scenario × congestion
grids into batches); use calib/ to quantify the divergence between the
two on matched traces.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.experimental import checkify
from jax.sharding import PartitionSpec

from repro.analysis import sanitize as _sanitize
from repro.fleet import mesh as _mesh
from repro.core.jax_state import (
    BIG, SchedState, commit_device_rows, compact_state,
)
from repro.core.tasks import FRAME_PERIOD, MAX_IMAGE_BYTES
from repro.fleet.metrics import FleetStats, init_stats
from repro.fleet.state import FleetState
from repro.kernels.placement.ops import fused_place_op
from repro.obs import profile as _profile
from repro.obs import telemetry as _telemetry

HP_IDX, LP2_IDX, LP4_IDX = 0, 1, 2
MAX_LP = 4   # trace alphabet spawns at most 4 DNN tasks per frame


@dataclasses.dataclass(frozen=True)
class FleetParams:
    """Static (compile-time) knobs of the batched engine."""

    n_devices: int = 4
    nominal_bw_bps: float = 20e6
    transfer_bytes: int = MAX_IMAGE_BYTES
    hp_deadline: float = 3.0
    lp_deadline_factor: float = 1.2
    stagger: float = 1.0
    #: fused_place_op backend: "auto" | "kernel" | "ref".
    placement_backend: str = "auto"
    #: replicas per fused-placement kernel tile (per shard when the mesh
    #: is on): a multiple of the 128 TPU lanes, or at least the local
    #: batch, which is then one tile.
    placement_block_b: int = 128
    #: shard the batch axis over this many devices of a 1-D `shard_map`
    #: mesh (fleet/mesh.py).  0 disables sharding entirely; 1 runs the
    #: sharded code path on a single-device mesh (useful for testing the
    #: machinery without multiple devices).  B is padded up to a multiple
    #: of the mesh size with masked no-op replicas and trimmed from every
    #: output, so results are bit-identical to the unsharded engine.
    mesh_shards: int = 0
    #: width of the per-replica victim re-queue buffer (0 disables the
    #: reallocation pass and reverts to capacity-eviction-only preemption).
    requeue_slots: int = 4
    #: merge abutting windows per track every this many ticks (0 disables).
    compact_every: int = 8
    #: scan segment length: the jitted program covers this many ticks and
    #: is re-invoked with donated carry buffers until the trace is
    #: consumed, so compile time is keyed on the segment, not the trace
    #: (0 → one segment spanning the whole trace).
    segment_frames: int = 40
    #: opt-in in-scan telemetry (obs/telemetry.py): the scan additionally
    #: emits per-tick series (device occupancy, re-queue depth, bandwidth,
    #: counter deltas) and ``fleet_run`` returns a third TelemetryRecord
    #: value.  The capture is read-only: state/stats stay bit-identical
    #: to a telemetry-off run (same discipline as REPRO_SANITIZE).
    telemetry: bool = False
    #: keep every k-th tick of the telemetry series (downsampling happens
    #: inside the jitted segment, so host transfer is O(S/k)).
    telemetry_every: int = 1


def _col(x, d):
    """``x[:, d]`` for a traced device index ``d``."""
    return jax.lax.dynamic_index_in_dim(x, d, axis=1, keepdims=False,
                                        allow_negative_indices=False)


def _set_col(x, d, col):
    """``x.at[:, d].set(col)`` for a traced device index ``d``."""
    return jax.lax.dynamic_update_index_in_dim(x, col, d, axis=1,
                                               allow_negative_indices=False)


def _hp_query(st: SchedState, dev, now, dur, hp_deadline: float):
    """HP containment query on device ``dev`` (traced): a `dur` slot
    starting in [now, now + hp_deadline - dur] (§IV.B.1)."""
    t1 = _col(st.win_t1, dev)[:, HP_IDX]              # [B, T, W]
    t2 = _col(st.win_t2, dev)[:, HP_IDX]
    valid = _col(st.win_valid, dev)[:, HP_IDX]
    nowb = now[:, None, None]
    durb = dur[:, None, None]
    deadline = nowb + jnp.maximum(hp_deadline, durb + 1e-6)
    start = jnp.maximum(t1, nowb)
    feasible = valid & (start + durb <= jnp.minimum(t2, deadline))
    key = jnp.where(feasible, start, BIG).reshape(t1.shape[0], -1)
    best = jnp.min(key, axis=1)
    return best < BIG, best


def _hp_commit(st: SchedState, dev, s, e, do):
    """§IV.A.1 fan-out commit of an HP slot on device ``dev`` (traced),
    per replica.  Every replica commits on the same device, so its row
    is one dynamic slice of the state, trimmed and written back in
    place.  Returns (state', n_dropped[B])."""
    t1, t2, valid, n_drop, _ = commit_device_rows(
        _col(st.win_t1, dev), _col(st.win_t2, dev), _col(st.win_valid, dev),
        st.min_dur, jnp.full(s.shape, HP_IDX, jnp.int32), s, e, do,
    )
    return st._replace(
        win_t1=_set_col(st.win_t1, dev, t1),
        win_t2=_set_col(st.win_t2, dev, t2),
        win_valid=_set_col(st.win_valid, dev, valid),
    ), n_drop


def _place_lp(st: SchedState, q1, dl, src, do, p: FleetParams):
    """One batched §IV.B.2 placement attempt through the fused kernel:
    2-core preferred, 4-core fallback, source-device preference, earliest
    start, committed in the same launch.

    q1/dl are [B, Dev] (transfer-adjusted release / deadline), ``src`` is
    the [B] source device, ``do`` masks the attempt per replica.  Returns
    (state', ok, sel, start, dur, use4, n_dropped), per-replica [B];
    windows of replicas with ``ok=False`` are untouched.
    """
    t1, t2, valid, ok, sel, start, dur, use4, n_drop = fused_place_op(
        st.win_t1, st.win_t2, st.win_valid, st.min_dur, q1, dl, src, do,
        backend=p.placement_backend, cfg_pref=LP2_IDX, cfg_fallback=LP4_IDX,
        block_b=p.placement_block_b,
    )
    st = st._replace(win_t1=t1, win_t2=t2, win_valid=valid)
    return st, ok, sel, start, dur, use4, n_drop


def _vc_commit(vc, ok, sel, start, end, deadline, src):
    """Record a committed LP placement in the per-device victim cache."""
    vc_s, vc_end, vc_dl, vc_src, vc_ok = vc
    n_dev = vc_end.shape[1]
    hit = ok[:, None] & (
        jnp.arange(n_dev, dtype=jnp.int32)[None, :] == sel[:, None]
    )
    return (
        jnp.where(hit, start[:, None], vc_s),
        jnp.where(hit, end[:, None], vc_end),
        jnp.where(hit, deadline[:, None], vc_dl),
        jnp.where(hit, src[:, None], vc_src),
        vc_ok | hit,
    )


def _avail_ahead(st: SchedState, base, p: FleetParams):
    """Per-replica window time inside the span a tick's commits fall in:
    from the tick's start to its last device's release plus the LP
    deadline and the HP window.  The sanitized tick checks that it never
    grows.  Clipped, because windows end at BIG, where an f32 total
    cannot see a second."""
    end = base + (1.0 + p.lp_deadline_factor) * FRAME_PERIOD + p.hp_deadline
    clip = lambda x: jnp.clip(x, base, end)
    return _sanitize.total_availability(
        clip(st.win_t1), clip(st.win_t2), st.win_valid, batch_axes=1
    )


def _segment_impl(carry, values, bw_scale, f0, n_frames, *,
                  params: FleetParams, sanitize: bool = False):
    """One scan over a ``[S, B, Dev]`` trace segment.  ``f0`` is
    the segment's global frame offset and ``n_frames`` the true trace
    length — ticks with ``f0 + i >= n_frames`` are masked to exact no-ops
    (padding), so segmented and unsegmented runs are bit-identical.
    ``sanitize=True`` traces per-tick checkify invariants (only valid
    under a ``checkify.checkify`` transform)."""
    p = params
    B = carry[0].win_t1.shape[0]
    n_dev = p.n_devices
    R = p.requeue_slots
    dev_ids = jnp.arange(n_dev, dtype=jnp.int32)
    # each device's release offset within the frame (the stagger), and
    # its LP deadline's
    t_offset = jnp.asarray(
        [d * (FRAME_PERIOD / n_dev) * p.stagger for d in range(n_dev)],
        jnp.float32)
    dl_offset = t_offset + p.lp_deadline_factor * FRAME_PERIOD
    rows = jnp.arange(B, dtype=jnp.int32)
    if sanitize:
        _sanitize.check_sched_state(carry[0], "fleet segment input")

    def frame_step(carry, xs):
        # The tick's phases run under named scopes (tick/housekeeping,
        # tick/requeue, tick/hp, tick/realloc, tick/lp, tick/mask) that
        # partition the body, so a profiler trace charges every op to one
        # phase; each stats update sits in the phase that produced it.
        # hp, realloc and lp run once per device inside the device loop,
        # under tick/device, which alone holds the loop's own work.
        st0, link_free0, rq0, vc0, stats0 = carry
        st, link_free, stats = st0, link_free0, stats0
        rq_dl, rq_src, rq_ok = rq0
        vc_s, vc_end, vc_dl, vc_src, vc_ok = vc0
        f, v, bws = xs                       # f i32, v [B,Dev] i32, bws [B]
        with jax.named_scope("tick/housekeeping"):
            base = f.astype(jnp.float32) * FRAME_PERIOD
            # recycle slots of fully-elapsed windows so the fixed-W arrays
            # never clog (the batched analog of the serial engine's
            # per-frame stale-window prune)
            st = st._replace(win_valid=st.win_valid & (st.win_t2 > base))
            if p.compact_every > 0:
                # periodic in-scan compaction: merge abutting per-track
                # windows so accumulated bisect remainders free up W slots
                st = jax.lax.cond(
                    f % p.compact_every == p.compact_every - 1,
                    compact_state, lambda s: s, st,
                )
            ttime = (p.transfer_bytes * 8.0) / (
                p.nominal_bw_bps * jnp.maximum(bws, 1e-3)
            )
            if sanitize:
                # availability the tick's commits start from, per replica
                avail0 = _avail_ahead(st, base, p)

        # -- victim re-queue pass (§IV.B.3 reallocation) -------------------
        # Runs before this tick's frame releases so victims get first pick
        # of the capacity they lost.  A victim whose deadline cannot fit
        # even the 4-core config any more is dropped as missed.
        if R > 0:
            with jax.named_scope("tick/requeue"):
                now0 = jnp.full((B,), 0.0, jnp.float32) + base
                min_lp_dur = jnp.minimum(st.min_dur[:, LP2_IDX],
                                         st.min_dur[:, LP4_IDX])
                # drop every victim whose deadline cannot fit even the 4-core
                # config any more (vectorised over all slots; no query needed)
                expired = rq_ok & (now0[:, None] + min_lp_dur[:, None] > rq_dl)
                rq_ok = rq_ok & ~expired
                stats = stats._replace(
                    missed_by_preemption=stats.missed_by_preemption
                    + expired.sum(axis=1, dtype=jnp.int32)
                )
                # one placement attempt per tick for the earliest-deadline
                # survivor (buffered victims rarely outlive a frame period, so
                # one attempt per tick drains the buffer in practice while
                # costing a single fused-kernel launch)
                slot = jnp.argmin(jnp.where(rq_ok, rq_dl, BIG), axis=1)
                valid_r = rq_ok[rows, slot]
                dl = rq_dl[rows, slot]
                src = rq_src[rows, slot]
                comm_end = jnp.maximum(link_free, now0) + ttime
                q1 = jnp.where(
                    dev_ids[None, :] == src[:, None], now0[:, None],
                    jnp.maximum(now0, comm_end)[:, None],
                )
                dlb = jnp.broadcast_to(dl[:, None], (B, n_dev))
                st, ok, sel, start, dur, use4, nd = _place_lp(
                    st, q1, dlb, src, valid_r, p
                )
                offl = ok & (sel != src)
                link_free = jnp.where(offl, comm_end, link_free)
                # the re-placed victim is now the newest commit on its device
                vc_s, vc_end, vc_dl, vc_src, vc_ok = _vc_commit(
                    (vc_s, vc_end, vc_dl, vc_src, vc_ok), ok, sel, start,
                    start + dur, dl, src
                )
                stats = stats._replace(
                    lp_completed=stats.lp_completed + ok,
                    lp_requeued=stats.lp_requeued + ok,
                    lp_offloaded=stats.lp_offloaded + offl,
                    lp_four_core=stats.lp_four_core + (ok & use4),
                    comm_busy=stats.comm_busy + jnp.where(offl, ttime, 0.0),
                    remainders_dropped=stats.remainders_dropped + nd,
                )
                rq_ok = rq_ok.at[rows, slot].set(valid_r & ~ok)

        def device_step(d, dc):
            # one device's frame release; ``d`` is traced, so every device
            # runs this one body and the program does not grow with Dev
            st, link_free, stats, (rq_dl, rq_src, rq_ok), vc, pd = dc
            vc_s, vc_end, vc_dl, vc_src, vc_ok = vc
            with jax.named_scope("tick/hp"):
                # release and LP deadline each as one multiply-add of the
                # frame index and the device's offset.  The compiler may
                # fuse it and round once, so the multiply has to stay in
                # the loop, next to the add: ``d >= 0`` always holds, but
                # ties it to the loop so it is not hoisted out.
                fbase = jnp.where(d >= 0, f, 0).astype(jnp.float32) * (
                    FRAME_PERIOD)
                now = jnp.full((B,), 0.0, jnp.float32) + (fbase + t_offset[d])
                vd = _col(v, d)
                has_frame = vd >= 0

                # -- HP: immediate slot on the source device ---------------
                # The detector always runs at frame release (§IV.B.1): if
                # the strict-containment query finds no reserved gap, HP
                # requests a preemption.  A live cached victim ⇒ committed
                # preemption (the victim loses its credit and is re-queued,
                # [now, now+dur) is evicted from every availability list);
                # no victim ⇒ the serial engine's "no-preemptable"
                # admission failure — the frame dies.
                hp_dur = st.min_dur[:, HP_IDX]
                hp_found, hp_start = _hp_query(st, d, now, hp_dur,
                                               p.hp_deadline)
                if R > 0:
                    # the serial engine evicts only a task whose reserved
                    # slot overlaps the requested HP window (§IV.B.3)
                    victim_live = (_col(vc_ok, d) & (_col(vc_end, d) > now)
                                   & (_col(vc_s, d) < now + hp_dur))
                else:
                    # reallocation disabled: legacy capacity-eviction
                    # semantics (HP always runs, victims implicitly keep
                    # their credit)
                    victim_live = jnp.ones((B,), bool)
                hp_ok = has_frame & (hp_found | victim_live)
                preempt = has_frame & ~hp_found & victim_live
                hp_fail = has_frame & ~hp_found & ~victim_live
                hp_start = jnp.where(hp_found, hp_start, now)
                st, nd = _hp_commit(st, d, hp_start, hp_start + hp_dur,
                                    hp_ok)
                stats = stats._replace(
                    remainders_dropped=stats.remainders_dropped + nd,
                    frames=stats.frames + has_frame,
                    hp_completed=stats.hp_completed + hp_ok,
                    hp_failed=stats.hp_failed + hp_fail,
                    # committed preemptions only: an admission failure that
                    # found nothing to evict is hp_failed, not a preemption
                    hp_preempted=stats.hp_preempted + preempt,
                )
                if R > 0:
                    vc_ok = _set_col(vc_ok, d, _col(vc_ok, d) & ~preempt)
                    # the victim's placement-time completion credit is
                    # revoked; re-earned on re-placement or it becomes a
                    # miss
                    stats = stats._replace(lp_completed=stats.lp_completed
                                           - preempt)

            if R > 0:
                with jax.named_scope("tick/realloc"):
                    # immediate reallocation attempt (§VI.A: the serial
                    # engine re-enters the victim at HP-commit time, and
                    # that path succeeds in the common case — deferring a
                    # whole frame period would eat most of the victim's
                    # deadline budget)
                    dl_v = _col(vc_dl, d)
                    src_v = _col(vc_src, d)
                    comm_end = jnp.maximum(link_free, now) + ttime
                    q1 = jnp.where(
                        dev_ids[None, :] == src_v[:, None], now[:, None],
                        jnp.maximum(now, comm_end)[:, None],
                    )
                    st, ok_v, sel_v, start_v, dur_v, use4_v, nd = _place_lp(
                        st, q1, jnp.broadcast_to(dl_v[:, None], (B, n_dev)),
                        src_v, preempt, p,
                    )
                    offl_v = ok_v & (sel_v != src_v)
                    link_free = jnp.where(offl_v, comm_end, link_free)
                    vc_s, vc_end, vc_dl, vc_src, vc_ok = _vc_commit(
                        (vc_s, vc_end, vc_dl, vc_src, vc_ok), ok_v, sel_v,
                        start_v, start_v + dur_v, dl_v, src_v,
                    )
                    stats = stats._replace(
                        lp_completed=stats.lp_completed + ok_v,
                        lp_requeued=stats.lp_requeued + ok_v,
                        lp_offloaded=stats.lp_offloaded + offl_v,
                        lp_four_core=stats.lp_four_core + (ok_v & use4_v),
                        comm_busy=stats.comm_busy
                        + jnp.where(offl_v, ttime, 0.0),
                        remainders_dropped=stats.remainders_dropped + nd,
                    )

                    # unplaced victims enter the bounded re-queue buffer
                    # for next-tick retries; a full buffer drops the victim
                    # (counted missed, not silent)
                    free = jnp.argmin(rq_ok, axis=1)
                    has_free = ~rq_ok.all(axis=1)
                    unplaced = preempt & ~ok_v
                    push = unplaced & has_free
                    rq_dl = rq_dl.at[rows, free].set(
                        jnp.where(push, dl_v, rq_dl[rows, free])
                    )
                    rq_src = rq_src.at[rows, free].set(
                        jnp.where(push, src_v, rq_src[rows, free])
                    )
                    rq_ok = rq_ok.at[rows, free].set(
                        rq_ok[rows, free] | push)
                    stats = stats._replace(
                        missed_by_preemption=stats.missed_by_preemption
                        + (unplaced & ~has_free),
                    )

            with jax.named_scope("tick/lp"):
                # -- LP: up to 4 DNN tasks once HP completes ---------------
                n_lp = jnp.where(hp_ok, jnp.clip(vd, 0, MAX_LP), 0)
                release = hp_start + hp_dur
                deadline = jnp.full((B,), 0.0, jnp.float32) + (
                    fbase + dl_offset[d])
                frame_ok = hp_ok
                src_d = jnp.full((B,), d, jnp.int32)
                if p.telemetry:
                    lp_placed_d = jnp.zeros((B,), jnp.int32)
                for k in range(MAX_LP):
                    mask = hp_ok & (k < n_lp)
                    comm_end = jnp.maximum(link_free, release) + ttime
                    # remote devices can only start once their transfer
                    # lands
                    q1 = jnp.where(
                        dev_ids[None, :] == d, release[:, None],
                        jnp.maximum(release, comm_end)[:, None],
                    )
                    dl = jnp.broadcast_to(deadline[:, None], (B, n_dev))
                    st, ok, sel, start, dur, use4, nd = _place_lp(
                        st, q1, dl, src_d, mask, p
                    )
                    offl = ok & (sel != d)
                    link_free = jnp.where(offl, comm_end, link_free)
                    vc_s, vc_end, vc_dl, vc_src, vc_ok = _vc_commit(
                        (vc_s, vc_end, vc_dl, vc_src, vc_ok), ok, sel, start,
                        start + dur, deadline, src_d,
                    )
                    stats = stats._replace(
                        lp_spawned=stats.lp_spawned + mask,
                        lp_completed=stats.lp_completed + ok,
                        lp_failed=stats.lp_failed + (mask & ~ok),
                        lp_offloaded=stats.lp_offloaded + offl,
                        lp_four_core=stats.lp_four_core + (ok & use4),
                        start_delay_sum=stats.start_delay_sum
                        + jnp.where(ok, start - release, 0.0),
                        comm_busy=stats.comm_busy
                        + jnp.where(offl, ttime, 0.0),
                        remainders_dropped=stats.remainders_dropped + nd,
                    )
                    frame_ok = frame_ok & (ok | (k >= n_lp))
                    if p.telemetry:
                        lp_placed_d = lp_placed_d + ok.astype(jnp.int32)
                stats = stats._replace(
                    frames_completed=stats.frames_completed
                    + (has_frame & frame_ok)
                )
            if p.telemetry:
                pd = tuple(
                    _set_col(a, d, x.astype(jnp.int32)) for a, x in
                    zip(pd, (hp_ok, hp_fail, preempt, lp_placed_d)))
            return (st, link_free, stats, (rq_dl, rq_src, rq_ok),
                    (vc_s, vc_end, vc_dl, vc_src, vc_ok), pd)

        # per-device decision counts for obs/, [B, Dev] each
        pd0 = ((jnp.zeros((B, n_dev), jnp.int32),) * 4 if p.telemetry
               else ())
        with jax.named_scope("tick/device"):
            st, link_free, stats, (rq_dl, rq_src, rq_ok), vc, pd = (
                jax.lax.fori_loop(0, n_dev, device_step, (
                    st, link_free, stats, (rq_dl, rq_src, rq_ok),
                    (vc_s, vc_end, vc_dl, vc_src, vc_ok), pd0)))
        vc_s, vc_end, vc_dl, vc_src, vc_ok = vc
        with jax.named_scope("tick/mask"):
            if sanitize:
                _sanitize.check_windows(
                    st.win_t1, st.win_t2, st.win_valid, "fleet tick"
                )
                _sanitize.check(
                    jnp.all(~vc_ok | (vc_s <= vc_end)),
                    "victim cache corrupt (fleet tick): a live entry has "
                    "start > end",
                )
                _sanitize.check(
                    jnp.all(link_free >= 0.0),
                    "negative link_free (fleet tick): {lf}",
                    lf=jnp.min(link_free),
                )
                _sanitize.check_no_avail_increase(
                    avail0, _avail_ahead(st, base, p), "fleet tick")
            new = (st, link_free, (rq_dl, rq_src, rq_ok),
                   (vc_s, vc_end, vc_dl, vc_src, vc_ok), stats)
            # mask padded ticks (beyond the true trace) to exact no-ops so
            # a padded segment is bit-identical to an unsegmented run
            active = f < n_frames
            out = jax.tree_util.tree_map(
                lambda n, o: jnp.where(active, n, o), new, carry
            )
            if not p.telemetry:
                return out, None

            # read-only capture from the post-mask carry: the per-device
            # decision counts are already zero on padded ticks (padded
            # trace values are -1, so has_frame is False everywhere)
            ys = _telemetry.capture_tick(
                out[0], out[1], out[2][2], stats0, out[4], base, bws,
                p.nominal_bw_bps, *pd,
            )
            return out, ys

    S = values.shape[0]
    xs = (f0 + jnp.arange(S, dtype=jnp.int32),
          values.astype(jnp.int32), bw_scale.astype(jnp.float32))
    carry, ys = jax.lax.scan(frame_step, carry, xs)
    if not p.telemetry:
        return carry
    if p.telemetry_every > 1:
        # fleet_run sizes segments to a multiple of the stride, so row i
        # of segment j sits at global tick j*S + i*telemetry_every
        ys = jax.tree_util.tree_map(lambda a: a[::p.telemetry_every], ys)
    return carry, ys


@functools.partial(
    jax.jit, static_argnames=("params",), donate_argnums=(0,)
)
def _run_segment(carry, values, bw_scale, f0, n_frames, *,
                 params: FleetParams):
    """Fast path: the jitted segment scan with a donated carry (buffers
    update in place across segments)."""
    return _segment_impl(
        carry, values, bw_scale, f0, n_frames, params=params
    )


@functools.lru_cache(maxsize=None)
def _run_segment_checked(params: FleetParams):
    """Checkify-sanitized segment scan (``REPRO_SANITIZE=1``).  The carry
    is deliberately NOT donated: the discharged error value aliases the
    inputs, and sanitized runs trade speed for checks anyway."""
    fn = functools.partial(_segment_impl, params=params, sanitize=True)
    # repro: lint-ok(host-transfer)  — checked carry intentionally kept
    return jax.jit(checkify.checkify(fn, errors=checkify.user_checks))


def _shard_segment(params: FleetParams, *, sanitize: bool):
    """`_segment_impl` wrapped in `shard_map` over the fleet mesh: every
    carry leaf and the workload batch axis split into B/shards rows per
    device; replicas are independent, so the scan body needs no
    collectives and each shard runs the exact unsharded per-replica math
    (bit-identical results — the per-replica pipeline never reduces over
    B)."""
    mesh = _mesh.fleet_mesh(params.mesh_shards)
    fn = functools.partial(_segment_impl, params=params, sanitize=sanitize)
    P = PartitionSpec
    # prefix specs: carry leaves shard on their leading [B] axis, the
    # [S, B, ...] workload slices on axis 1, f0/n_frames replicate
    in_specs = (P(_mesh.FLEET_AXIS), P(None, _mesh.FLEET_AXIS),
                P(None, _mesh.FLEET_AXIS), P(), P())
    out_specs = ((P(_mesh.FLEET_AXIS), P(None, _mesh.FLEET_AXIS))
                 if params.telemetry else P(_mesh.FLEET_AXIS))
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


@functools.lru_cache(maxsize=None)
def _run_segment_sharded(params: FleetParams):
    """Fast sharded path: jitted shard_map scan with a donated carry —
    state buffers stay resident per shard across segments, so the only
    host interaction per segment is dispatch."""
    return jax.jit(_shard_segment(params, sanitize=False),
                   donate_argnums=(0,))


@functools.lru_cache(maxsize=None)
def _run_segment_sharded_checked(params: FleetParams):
    """Sanitized sharded path: checkify discharges *outside* shard_map
    (per-shard error states merge through the transform), not donated for
    the same aliasing reason as the unsharded checked runner."""
    # repro: lint-ok(host-transfer)  — checked carry intentionally kept
    return jax.jit(checkify.checkify(
        _shard_segment(params, sanitize=True), errors=checkify.user_checks
    ))


def initial_carry(fleet: FleetState, pad_b: int = 0):
    """The segment scan's carry for ``fleet``: its buffers and zero stats,
    with ``pad_b`` padding replicas appended to the batch."""
    state_tree = (
        fleet.sched, fleet.link_free,
        (fleet.rq_deadline, fleet.rq_src, fleet.rq_valid),
        (fleet.vc_start, fleet.vc_end, fleet.vc_deadline, fleet.vc_src,
         fleet.vc_valid),
    )
    return _initial_carry(state_tree, pad_b=pad_b)


@functools.partial(jax.jit, static_argnames="pad_b")
def _initial_carry(state_tree, *, pad_b):
    # one device program, so a call costs the host one dispatch.  Copy
    # the carry: the segment runners donate their input buffers, and the
    # caller's fleet must stay valid (benchmarks re-run the same fleet);
    # every output of the program is a buffer of its own, the zero stats
    # leaves that share a value too, as donation requires.  Batch padding
    # tiles existing replica rows instead — any valid state works, the
    # padded columns release no frames.
    B = state_tree[1].shape[0]
    Bp = B + pad_b
    if pad_b:
        rows = jnp.arange(Bp, dtype=jnp.int32) % B
        state_tree = jax.tree_util.tree_map(
            lambda x: jnp.take(x, rows, axis=0), state_tree
        )
    else:
        state_tree = jax.tree_util.tree_map(jnp.copy, state_tree)
    stats0 = jax.tree_util.tree_map(jnp.copy, init_stats(Bp))
    return (*state_tree, stats0)


def fleet_run(fleet: FleetState, values: jnp.ndarray, bw_scale: jnp.ndarray,
              *, params: FleetParams):
    """Advance a whole fleet over `values` ([F, B, Dev] workload) in
    jitted ``segment_frames``-tick scans.  `bw_scale` is [F, B].  Returns
    ``(state, stats)`` — or ``(state, stats, telemetry_record)`` when
    ``params.telemetry`` is on (the extra return is in-scan time series,
    see obs/telemetry.py; state and stats are bit-identical either way).
    The input `fleet` is left untouched (segments run on donated copies).

    With ``params.mesh_shards >= 1`` the segment scan runs under
    `shard_map` over the fleet mesh: B is padded to a multiple of the
    mesh size with masked no-op replicas (trimmed from every output),
    state buffers live sharded across devices for the whole run, and
    results are bit-identical to the unsharded engine.
    """
    p = params
    B = fleet.sched.win_t1.shape[0]
    n_dev = p.n_devices
    R = p.requeue_slots
    F = values.shape[0]
    shards = p.mesh_shards
    sharded = shards >= 1
    pad_b = _mesh.shard_pad(B, shards) if sharded else 0
    assert values.shape[2] == n_dev and fleet.sched.win_t1.shape[1] == n_dev
    assert fleet.rq_valid.shape == (B, R), (
        f"fleet re-queue buffer {fleet.rq_valid.shape} != (B={B}, "
        f"requeue_slots={R}); build the fleet with matching requeue_slots"
    )
    assert p.telemetry_every >= 1, "telemetry_every must be >= 1"
    S = F if p.segment_frames <= 0 else min(p.segment_frames, F)
    if p.telemetry and p.telemetry_every > 1:
        # the segment length must be a multiple of the stride so strided
        # telemetry rows align on one global tick grid across segments
        S = max(p.telemetry_every, S - S % p.telemetry_every)
    n_seg = -(-F // S)
    pad = n_seg * S - F
    sanitized = _sanitize.enabled()
    telem_segs = []
    with _profile.maybe_jax_trace():
        with _profile.span("fleet/put"):
            values = jnp.asarray(values, jnp.int32)
            bw_scale = jnp.broadcast_to(
                jnp.asarray(bw_scale, jnp.float32), (F, B)
            )
            if pad:
                # padded frames carry no workload and are masked off inside
                # the scan anyway; -1 == "no frame released"
                values = jnp.concatenate(
                    [values, jnp.full((pad, B, n_dev), -1, jnp.int32)]
                )
                bw_scale = jnp.concatenate(
                    [bw_scale, jnp.ones((pad, B), jnp.float32)]
                )
            if pad_b:
                # pad the batch so it splits evenly across mesh shards:
                # padded replicas get no workload (-1 frames), so they
                # advance as pure no-ops and their (zero) stats rows are
                # trimmed below
                values = jnp.concatenate(
                    [values, jnp.full(values.shape[:1] + (pad_b, n_dev), -1,
                                      jnp.int32)], axis=1,
                )
                bw_scale = jnp.concatenate(
                    [bw_scale, jnp.ones(bw_scale.shape[:1] + (pad_b,),
                                        jnp.float32)], axis=1,
                )
            carry = initial_carry(fleet, pad_b)
            if sharded:
                # commit the carry and the workload to the mesh once: the
                # donated buffers then round-trip through every segment
                # without a resharding copy, and each segment's workload
                # slice is already split across the devices
                mesh = _mesh.fleet_mesh(shards)
                carry = _mesh.put_sharded(carry, mesh)
                values, bw_scale = _mesh.put_sharded((values, bw_scale),
                                                     mesh, batch_axis=1)
            nf = jnp.asarray(F, jnp.int32)
        for i in range(n_seg):
            seg_args = (
                carry, values[i * S:(i + 1) * S],
                bw_scale[i * S:(i + 1) * S],
                jnp.asarray(i * S, jnp.int32), nf,
            )
            # times the dispatch: the segment runs asynchronously, so the
            # device work shows in a profiler trace, not in this span
            with _profile.span("fleet/segment"):
                if sanitized:
                    checked = (_run_segment_sharded_checked(p) if sharded
                               else _run_segment_checked(p))
                    err, res = checked(*seg_args)
                    err.throw()
                elif sharded:
                    res = _run_segment_sharded(p)(*seg_args)
                else:
                    res = _run_segment(*seg_args, params=p)
            if p.telemetry:
                carry, ys = res
                telem_segs.append(ys)
            else:
                carry = res
    if pad_b:
        # drop the shard-padding replicas from every output (device-side
        # slice; nothing is gathered to the host here)
        carry = jax.tree_util.tree_map(lambda x: x[:B], carry)
    sched, link_free, rq, vc, stats = carry
    out = FleetState(
        sched=sched, link_free=link_free,
        rq_deadline=rq[0], rq_src=rq[1], rq_valid=rq[2],
        vc_start=vc[0], vc_end=vc[1], vc_deadline=vc[2], vc_src=vc[3],
        vc_valid=vc[4],
    )
    if not p.telemetry:
        return out, stats
    record = _telemetry.assemble(
        telem_segs, n_frames=F, every=p.telemetry_every,
        nominal_bw_bps=p.nominal_bw_bps, n_replicas=B,
    )
    return out, stats, record
