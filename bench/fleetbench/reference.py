"""Plain reference of the fleet semantics, in numpy, written from the
deployment's description and imports nothing of the program.

It makes its own inputs from the seed (the paper's trace tables, §V, and
the §VI.C congestion bursts), advances every sampled site frame by frame
through the same decisions the fleet engine documents, and reduces
per-replica counters to per-cell rates.  One tick, in order:

1. housekeeping: windows that ended by the frame start are freed;
2. compaction on every ``compact_every``-th tick: each track's windows are
   sorted by start and abutting ones merged;
3. the re-queue pass: expired victims are dropped as missed, then one
   placement attempt for the earliest-deadline survivor;
4. per device: the HP containment query, preemption of the device's
   newest LP placement when it overlaps (with one immediate re-placement
   attempt, else the bounded re-queue), the HP commit, then up to four LP
   placements (2-core preferred, 4-core fallback, the source device
   preferred, earliest start, fan-out commit over every config list);
5. accounting.

Every array carries a leading replica axis ``K``, so a sample of sites
advances together; nothing is shared between replicas.  ``dtype`` is the
precision of every time value: float32 as the configuration states, or
a lower one for the control.
"""

from __future__ import annotations

import zlib

import numpy as np

#: trace alphabet (§V): -1 no object, 0 HP only, n = HP then n LP tasks.
VALUES = (-1, 0, 1, 2, 3, 4)

#: the 15 per-replica counters, in the order the program reports them.
INT_COUNTERS = (
    "frames", "frames_completed", "hp_completed", "hp_preempted",
    "hp_failed", "lp_spawned", "lp_completed", "lp_failed", "lp_requeued",
    "missed_by_preemption", "lp_offloaded", "lp_four_core",
    "remainders_dropped",
)
FLOAT_COUNTERS = ("start_delay_sum", "comm_busy")


# ---------------------------------------------------------------------------
# inputs
# ---------------------------------------------------------------------------

def trace_probs(scenario: str) -> dict[int, float]:
    """§V value distributions: ``uniform`` draws 1..4 alike; ``weightedX``
    draws X with probability 0.55 and spreads 0.30 over the other three."""
    if scenario == "uniform":
        probs = {v: 0.0 for v in VALUES}
        for v in (1, 2, 3, 4):
            probs[v] = 0.225
        probs[0] = 0.05
        probs[-1] = 0.05
        return probs
    if scenario.startswith("weighted"):
        x = int(scenario[len("weighted"):])
        probs = {v: 0.0 for v in VALUES}
        probs[x] = 0.55
        others = [v for v in (1, 2, 3, 4) if v != x]
        for v in others:
            probs[v] = 0.30 / len(others)
        probs[0] = 0.075
        probs[-1] = 0.075
        return probs
    raise ValueError(f"the reference has no inputs for scenario {scenario!r}")


def make_inputs(scenario: str, n_sites: int, n_frames: int, n_devices: int,
                seed: int, congestion: float, burst_residual: float):
    """One grid cell's inputs: ``values [F, n, Dev]`` and ``bw [F, n]``.

    The stream is keyed by (crc32 of the scenario name's low 16 bits,
    seed): one draw of the whole block from numpy's PCG64, the values by
    inverse CDF, then one uniform per (frame, site) for the congestion
    bursts, which leave ``burst_residual`` of the nominal bandwidth."""
    rng = np.random.default_rng(np.random.SeedSequence(
        [zlib.crc32(scenario.encode()) & 0xFFFF, seed]
    ))
    probs = trace_probs(scenario)
    p = np.array([probs[v] for v in VALUES], np.float64)
    values = rng.choice(np.array(VALUES, np.int8),
                        size=(n_frames, n_sites, n_devices), p=p / p.sum())
    bw = np.ones((n_frames, n_sites), np.float32)
    if congestion > 0.0:
        burst = rng.random((n_frames, n_sites)) < congestion
        bw = bw * np.where(burst, burst_residual, 1.0).astype(np.float32)
    return values.astype(np.int8), bw.astype(np.float32)


# ---------------------------------------------------------------------------
# the site model
# ---------------------------------------------------------------------------

def _first(mask, axis=-1):
    """Index of the first True along ``axis``, or the axis length."""
    n = mask.shape[axis]
    return np.where(mask.any(axis=axis), np.argmax(mask, axis=axis), n)


def _pairwise_sum(x):
    """Sum over the last axis by halving, in a fixed order, so that the
    rounding of the overlap sums is defined."""
    tails = []
    while x.shape[-1] > 1:
        n = x.shape[-1]
        h = n // 2
        if n % 2:
            tails.append(x[..., n - 1:n])
        x = x[..., :h] + x[..., h:2 * h]
    for t in tails:
        x = x + t
    return x[..., 0]


class SiteModel:
    """The deployment's constants, in ``dtype``, and the tick."""

    HP, LP2, LP4 = 0, 1, 2

    def __init__(self, config: dict, dtype=np.float32):
        self.dt = dt = dtype
        c = lambda x: dt(x)                                   # noqa: E731
        site, tasks, eng = config["site"], config["tasks"], config["engine"]
        self.n_dev = int(site["n_devices"])
        cores = site["device_cores"]
        order = ("hp", "lp2", "lp4")
        self.cfg_cores = np.array([tasks[k]["cores"] for k in order])
        self.tracks = cores // self.cfg_cores
        self.T = int(self.tracks.max())
        self.W = int(eng["max_windows"])
        pad = tasks["lp_pad_fraction"]
        durs = [tasks["hp"]["seconds"]] + [
            tasks[k]["seconds"] * (1.0 + pad) for k in ("lp2", "lp4")
        ]
        self.md = np.array([c(x) for x in durs], dt)
        # tracks of list l that a committed task of config t occupies
        self.occ = np.minimum(
            -(-self.cfg_cores[:, None] // self.cfg_cores[None, :]),
            self.tracks[None, :],
        )
        self.period = site["frame_period_s"]
        self.n_frames = int(site["n_frames"])
        self.R = int(site["requeue_slots"])
        self.max_lp = int(tasks["max_lp_per_frame"])
        self.compact_every = int(eng["compact_every"])
        self.big = c(eng["big"])
        self.src_pref = c(eng["source_preference_s"])
        self.eps = c(eng["merge_eps_s"])
        self.hp_window = c(max(site["hp_deadline_s"],
                               float(c(self.md[0]) + c(1e-6))))
        self.lp_budget = c(site["lp_deadline_factor"] * self.period)
        self.offsets = [c(d * (self.period / self.n_dev) * site["stagger"])
                        for d in range(self.n_dev)]
        self.bits = c(site["transfer_bytes"] * 8.0)
        self.bw_bps = c(site["nominal_bw_bps"])
        self.bw_floor = c(1e-3)
        self.burst_residual = site["congestion_residual"]
        self.c = c

    # -- state ---------------------------------------------------------------
    def initial(self, K: int) -> dict:
        dt, D = self.dt, self.n_dev
        shape = (K, D, 3, self.T, self.W)
        t1 = np.full(shape, self.big, dt)
        t2 = np.full(shape, self.big, dt)
        valid = np.zeros(shape, bool)
        for ci, n in enumerate(self.tracks):
            t1[:, :, ci, :n, 0] = self.c(0.0)
            valid[:, :, ci, :n, 0] = True
        z = lambda *s: np.zeros(s, dt)                         # noqa: E731
        zi = lambda *s: np.zeros(s, np.int32)                  # noqa: E731
        st = {
            "t1": t1, "t2": t2, "valid": valid,
            "link_free": z(K),
            "rq_dl": z(K, self.R), "rq_src": zi(K, self.R),
            "rq_ok": np.zeros((K, self.R), bool),
            "vc_s": z(K, D), "vc_end": z(K, D), "vc_dl": z(K, D),
            "vc_src": zi(K, D), "vc_ok": np.zeros((K, D), bool),
        }
        for k in INT_COUNTERS:
            st[k] = zi(K)
        for k in FLOAT_COUNTERS:
            st[k] = z(K)
        return st

    # -- window lists --------------------------------------------------------
    def _trim(self, t1, t2, valid, s, e, active):
        """Cut ``[s, e)`` out of the active tracks (``[..., C, T, W]``):
        each overlapping window keeps its left and right pieces that are
        at least the list's minimum duration, in place (left preferred);
        the first window whose both pieces survive spills its right piece
        into the first free slot.  Returns the windows and the count of
        surviving pieces that found no slot."""
        big, W = self.big, self.W
        md = self.md[None, :, None, None]
        ov = valid & (t1 < e) & (s < t2) & active[..., None]
        left_t2 = np.minimum(t2, s)
        right_t1 = np.maximum(t1, e)
        left_ok = ov & (left_t2 - t1 >= md)
        right_ok = ov & (t2 - right_t1 >= md)
        both = left_ok & right_ok
        nv = (ov & (left_ok | right_ok)) | (~ov & valid)
        n1 = np.where(ov & ~left_ok & right_ok, right_t1, t1)
        n2 = np.where(ov & left_ok, left_t2, t2)
        n1 = np.where(nv, n1, big)
        n2 = np.where(nv, n2, big)
        free = _first(~nv)
        fb = _first(both)
        placed = (fb < W) & (free < W)
        fbc = np.minimum(fb, W - 1)[..., None]
        sp1 = np.take_along_axis(right_t1, fbc, -1)
        sp2 = np.take_along_axis(t2, fbc, -1)
        slots = np.arange(W)
        put = placed[..., None] & (slots == free[..., None])
        n1 = np.where(put, sp1, n1)
        n2 = np.where(put, sp2, n2)
        nv = nv | put
        dropped = both & ~(placed[..., None] & (slots == fb[..., None]))
        return n1, n2, nv, dropped.sum(axis=(-3, -2, -1))

    def commit(self, st, dev, cfg, s, e, do):
        """Consume ``[s, e)`` on device ``dev[k]`` for a task of config
        ``cfg[k]``, where ``do[k]``: in every config list, the
        ``occ[cfg, list]`` tracks that overlap it most (first track on
        ties) are trimmed.  Returns the dropped-piece count per replica."""
        K = dev.shape[0]
        rows = np.arange(K)
        r1, r2, rv = st["t1"][rows, dev], st["t2"][rows, dev], \
            st["valid"][rows, dev]                              # [K, C, T, W]
        s4, e4 = s[:, None, None, None], e[:, None, None, None]
        ov = rv & (r1 < e4) & (s4 < r2)
        piece = np.where(ov, np.minimum(r2, e4) - np.maximum(r1, s4),
                         self.c(0.0))
        ol = _pairwise_sum(piece)                               # [K, C, T]
        T = self.T
        u_first = np.arange(T)[None, :] < np.arange(T)[:, None]  # [t, u]
        olt, olu = ol[..., :, None], ol[..., None, :]
        beats = (olu > olt) | ((olu == olt) & u_first)
        rank = beats.sum(axis=-1)
        width = self.occ[cfg]                                   # [K, C]
        active = do[:, None, None] & (rank < width[..., None]) & (ol > 0)
        n1, n2, nv, nd = self._trim(r1, r2, rv, s4, e4, active)
        m = do
        st["t1"][rows[m], dev[m]] = n1[m]
        st["t2"][rows[m], dev[m]] = n2[m]
        st["valid"][rows[m], dev[m]] = nv[m]
        return np.where(do, nd, 0).astype(np.int32)

    def compact(self, st):
        """Sort each track's windows by start and merge those that abut
        (start at most ``eps`` past the running end)."""
        big, W = self.big, self.W
        t1, t2, valid = st["t1"], st["t2"], st["valid"]
        order = np.argsort(np.where(valid, t1, big), axis=-1, kind="stable")
        t1s = np.take_along_axis(t1, order, -1)
        t2s = np.take_along_axis(t2, order, -1)
        vs = np.take_along_axis(valid, order, -1)
        run_end = np.maximum.accumulate(np.where(vs, t2s, -big), axis=-1)
        prev = np.concatenate(
            [np.full(run_end.shape[:-1] + (1,), -big, self.dt),
             run_end[..., :-1]], axis=-1)
        head = vs & (t1s > prev + self.eps)
        seg = np.cumsum(head, axis=-1) - 1
        lanes = np.arange(W)
        member = vs[..., None] & (seg[..., None] == lanes)      # [..., w, lane]
        is_head = head[..., None] & (seg[..., None] == lanes)
        nv = member.any(axis=-2)
        n1 = np.where(is_head, t1s[..., None], self.c(0.0)).sum(
            axis=-2, dtype=self.dt)
        n2 = np.where(member, t2s[..., None], -big).max(axis=-2)
        st["t1"] = np.where(nv, n1, big).astype(self.dt)
        st["t2"] = np.where(nv, n2, big).astype(self.dt)
        st["valid"] = nv

    # -- placement -----------------------------------------------------------
    def place(self, st, q1, dl, src, do):
        """One LP placement attempt: per config (2-core, then 4-core) the
        earliest feasible start on each device, ``q1[k, d]`` onwards and
        ending by ``dl[k]``; the source device wins ties within
        ``source_preference_s``, then the lowest index; 4 cores only where
        2 cannot.  Commits where ``do``."""
        K, D = q1.shape
        rows = np.arange(K)
        devs = np.arange(D)
        big = self.big
        per = []
        for ci in (self.LP2, self.LP4):
            dur = self.md[ci]
            t1, t2, v = st["t1"][:, :, ci], st["t2"][:, :, ci], \
                st["valid"][:, :, ci]
            start = np.maximum(t1, q1[:, :, None, None])
            feas = v & (start + dur <= np.minimum(t2, dl[:, None, None, None]))
            best = np.where(feas, start, big).min(axis=(-2, -1))  # [K, D]
            found = best < big
            key = np.where(found, best, big) - np.where(
                devs[None] == src[:, None], self.src_pref, self.c(0.0))
            sel = np.argmax(key == key.min(axis=1, keepdims=True), axis=1)
            per.append((found[rows, sel], sel, best[rows, sel], dur))
        (ok2, sel2, s2, d2), (ok4, sel4, s4, d4) = per
        use4 = ~ok2 & ok4
        ok = (ok2 | ok4) & do
        sel = np.where(use4, sel4, sel2)
        start = np.where(use4, s4, s2)
        dur = np.where(use4, d4, d2).astype(self.dt)
        cfg = np.where(use4, self.LP4, self.LP2)
        nd = self.commit(st, sel, cfg, start, (start + dur).astype(self.dt), ok)
        return ok, sel, start, dur, use4, nd

    def _remember(self, st, ok, sel, start, end, deadline, src):
        """The device's newest committed LP placement (the victim cache)."""
        hit = ok[:, None] & (np.arange(self.n_dev)[None, :] == sel[:, None])
        st["vc_s"] = np.where(hit, start[:, None], st["vc_s"])
        st["vc_end"] = np.where(hit, end[:, None], st["vc_end"])
        st["vc_dl"] = np.where(hit, deadline[:, None], st["vc_dl"])
        st["vc_src"] = np.where(hit, src[:, None], st["vc_src"])
        st["vc_ok"] = st["vc_ok"] | hit

    def _offload(self, st, ok, sel, src, comm_end, ttime):
        offl = ok & (sel != src)
        st["link_free"] = np.where(offl, comm_end, st["link_free"])
        st["lp_offloaded"] += offl
        st["comm_busy"] = st["comm_busy"] + np.where(offl, ttime,
                                                     self.c(0.0))

    def _q1(self, src, ready, comm_end):
        """Earliest start per device: ``ready`` on the source, the end of
        the transfer elsewhere."""
        devs = np.arange(self.n_dev)
        return np.where(devs[None] == src[:, None], ready[:, None],
                        np.maximum(ready, comm_end)[:, None])

    # -- one tick ------------------------------------------------------------
    def tick(self, st, f: int, v, bw):
        """Advance every replica one frame: ``v [K, Dev]`` values,
        ``bw [K]`` bandwidth scale."""
        dt, c, big = self.dt, self.c, self.big
        K = v.shape[0]
        rows = np.arange(K)
        base = c(c(f) * c(self.period))
        st["valid"] = st["valid"] & (st["t2"] > base)
        if self.compact_every > 0 and f % self.compact_every == \
                self.compact_every - 1:
            self.compact(st)
        ttime = (self.bits / (self.bw_bps * np.maximum(bw.astype(dt),
                                                       self.bw_floor)))
        ttime = ttime.astype(dt)
        now0 = np.full(K, base, dt)

        if self.R > 0:
            min_lp = min(self.md[self.LP2], self.md[self.LP4])
            expired = st["rq_ok"] & (now0[:, None] + min_lp > st["rq_dl"])
            st["rq_ok"] = st["rq_ok"] & ~expired
            st["missed_by_preemption"] += expired.sum(axis=1, dtype=np.int32)
            slot = np.argmin(np.where(st["rq_ok"], st["rq_dl"], big), axis=1)
            live = st["rq_ok"][rows, slot]
            dl = st["rq_dl"][rows, slot]
            src = st["rq_src"][rows, slot]
            comm_end = np.maximum(st["link_free"], now0) + ttime
            ok, sel, start, dur, use4, nd = self.place(
                st, self._q1(src, now0, comm_end), dl, src, live)
            self._offload(st, ok, sel, src, comm_end, ttime)
            self._remember(st, ok, sel, start, start + dur, dl, src)
            st["lp_completed"] += ok
            st["lp_requeued"] += ok
            st["lp_four_core"] += ok & use4
            st["remainders_dropped"] += nd
            st["rq_ok"][rows, slot] = live & ~ok

        hp_dur = self.md[self.HP]
        for d in range(self.n_dev):
            now = np.full(K, base + self.offsets[d], dt)
            vd = v[:, d].astype(np.int32)
            has_frame = vd >= 0
            # HP: earliest slot on the source device within its deadline
            t1, t2, val = st["t1"][:, d, self.HP], st["t2"][:, d, self.HP], \
                st["valid"][:, d, self.HP]
            start = np.maximum(t1, now[:, None, None])
            feas = val & (start + hp_dur <= np.minimum(
                t2, (now + self.hp_window)[:, None, None]))
            best = np.where(feas, start, big).min(axis=(-2, -1))
            found = best < big
            if self.R > 0:
                victim = (st["vc_ok"][:, d] & (st["vc_end"][:, d] > now)
                          & (st["vc_s"][:, d] < now + hp_dur))
            else:
                victim = np.ones(K, bool)
            hp_ok = has_frame & (found | victim)
            preempt = has_frame & ~found & victim
            hp_fail = has_frame & ~found & ~victim
            hp_start = np.where(found, best, now)
            st["remainders_dropped"] += self.commit(
                st, np.full(K, d), np.full(K, self.HP), hp_start,
                hp_start + hp_dur, hp_ok)

            if self.R > 0:
                st["vc_ok"][:, d] = st["vc_ok"][:, d] & ~preempt
                st["lp_completed"] -= preempt
                dl_v = st["vc_dl"][:, d].copy()
                src_v = st["vc_src"][:, d].copy()
                comm_end = np.maximum(st["link_free"], now) + ttime
                ok, sel, s_v, dur, use4, nd = self.place(
                    st, self._q1(src_v, now, comm_end), dl_v, src_v, preempt)
                self._offload(st, ok, sel, src_v, comm_end, ttime)
                self._remember(st, ok, sel, s_v, s_v + dur, dl_v, src_v)
                st["lp_completed"] += ok
                st["lp_requeued"] += ok
                st["lp_four_core"] += ok & use4
                st["remainders_dropped"] += nd
                # unplaced victims wait in the first free re-queue slot
                free = np.argmin(st["rq_ok"], axis=1)
                has_free = ~st["rq_ok"].all(axis=1)
                unplaced = preempt & ~ok
                push = unplaced & has_free
                st["rq_dl"][rows, free] = np.where(
                    push, dl_v, st["rq_dl"][rows, free])
                st["rq_src"][rows, free] = np.where(
                    push, src_v, st["rq_src"][rows, free])
                st["rq_ok"][rows, free] = st["rq_ok"][rows, free] | push
                st["missed_by_preemption"] += unplaced & ~has_free

            st["frames"] += has_frame
            st["hp_completed"] += hp_ok
            st["hp_failed"] += hp_fail
            st["hp_preempted"] += preempt

            n_lp = np.where(hp_ok, np.clip(vd, 0, self.max_lp), 0)
            release = (hp_start + hp_dur).astype(dt)
            deadline = (now + self.lp_budget).astype(dt)
            src_d = np.full(K, d)
            frame_ok = hp_ok.copy()
            for k in range(self.max_lp):
                mask = hp_ok & (k < n_lp)
                comm_end = np.maximum(st["link_free"], release) + ttime
                ok, sel, s_k, dur, use4, nd = self.place(
                    st, self._q1(src_d, release, comm_end), deadline, src_d,
                    mask)
                self._offload(st, ok, sel, src_d, comm_end, ttime)
                self._remember(st, ok, sel, s_k, s_k + dur, deadline, src_d)
                st["lp_spawned"] += mask
                st["lp_completed"] += ok
                st["lp_failed"] += mask & ~ok
                st["lp_four_core"] += ok & use4
                st["start_delay_sum"] = st["start_delay_sum"] + np.where(
                    ok, s_k - release, c(0.0))
                st["remainders_dropped"] += nd
                frame_ok = frame_ok & (ok | (k >= n_lp))
            st["frames_completed"] += has_frame & frame_ok

    def run(self, values, bw) -> dict:
        """Whole runs of ``values [F, K, Dev]`` / ``bw [F, K]``."""
        st = self.initial(values.shape[1])
        for f in range(values.shape[0]):
            self.tick(st, f, values[f], bw[f])
        st["rq_pending"] = st["rq_ok"].sum(axis=1)
        return st


# ---------------------------------------------------------------------------
# per-cell reduction
# ---------------------------------------------------------------------------

def rates(c: dict, n_frames: int, period: float) -> dict:
    """Per-replica rates from counters (float64 arrays keyed by counter
    name, plus ``rq_pending``)."""
    frames = np.maximum(c["frames"], 1)
    lp = np.maximum(c["lp_spawned"], 1)
    placed = np.maximum(c["lp_completed"] + c["hp_preempted"], 1)
    victims = np.maximum(c["hp_preempted"], 1)
    initial = np.maximum(
        c["lp_completed"] + c["hp_preempted"] - c["lp_requeued"], 1)
    sim_time = n_frames * period
    return {
        "frame_completion_rate": c["frames_completed"] / frames,
        "hp_completion_rate": c["hp_completed"] / frames,
        "hp_preemption_rate": c["hp_preempted"] / frames,
        "hp_failure_rate": c["hp_failed"] / frames,
        "lp_completion_rate": c["lp_completed"] / lp,
        "lp_violation_rate": c["lp_failed"] / lp,
        "requeue_success_rate": c["lp_requeued"] / victims,
        "missed_by_preemption_rate": c["missed_by_preemption"] / lp,
        "lp_offload_fraction": c["lp_offloaded"] / placed,
        "four_core_fraction": c["lp_four_core"] / placed,
        "mean_start_delay_s": c["start_delay_sum"] / initial,
        "remainder_drop_rate": c["remainders_dropped"] / frames,
        "rq_pending_depth": c["rq_pending"],
        "link_utilisation": c["comm_busy"] / sim_time,
        "lp_throughput_per_s": c["lp_completed"] / sim_time,
        "conservation_residual": residual(c),
    }


def residual(c: dict):
    """LP conservation: every spawned task is completed, failed, missed
    or still waiting in the re-queue."""
    return c["lp_spawned"] - (c["lp_completed"] + c["lp_failed"]
                              + c["missed_by_preemption"] + c["rq_pending"])


def summarize(c: dict, n_frames: int, period: float, dtype=np.float64
              ) -> dict:
    """One cell's summary: replica count, and each rate's mean and 95%
    confidence half-width over replicas (``max_abs`` for the residual).
    ``dtype`` is the precision of the reduction."""
    cast = {k: np.asarray(v, np.float64) for k, v in c.items()}
    n = int(cast["frames"].size)
    out: dict = {"replicas": n}
    for k, x in rates(cast, n_frames, period).items():
        x = np.asarray(x).astype(dtype)
        mean = x.sum(dtype=dtype) / dtype(max(n, 1))
        if n > 1:
            dev = (x - mean).astype(dtype)
            var = (dev * dev).sum(dtype=dtype) / dtype(n - 1)
            ci = 1.96 * np.sqrt(float(var) / n)
        else:
            ci = 0.0
        out[k] = {"mean": float(mean), "ci95": float(ci)}
        if k == "conservation_residual":
            out[k]["max_abs"] = int(np.abs(np.asarray(x, np.float64)).max()
                                    ) if n else 0
    return out
