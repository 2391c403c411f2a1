"""What decides ``correct``: the numbers compared, and their limits.

- ``replica_mismatch``: of the replicas sampled from the window, the
  share whose run disagrees with the plain reference.  A replica agrees
  when every integer counter, every flag and index of its final state
  (windows, re-queue, victim cache) is equal, and every time value
  (counter sums, window bounds, link and deadline times) is within
  ``TIME_RTOL`` of the reference's, relative to the larger of its size
  and one second.
- ``summary_gap``: the widest absolute gap between any number the
  program's per-cell summaries report (replicas, each rate's mean and
  95% half-width, the residual's ``max_abs``) and the reference's
  reduction of the same per-replica counters, over every call of the
  window.
- ``residual_failures``: replicas of the window whose LP conservation
  residual is not 0, or whose call raised.  The configuration states the
  guarantee, so its limit is 0.

PERF.md gives the readings each limit was set from.
"""

from __future__ import annotations

import numpy as np

from fleetbench import reference as R

#: agreement of one time value, relative to max(|reference|, 1 s).  The
#: widest sound gap is ``start_delay_sum``'s: a sum of small differences
#: of absolute times near the 1,792 s horizon, each off by an ulp.
TIME_RTOL = 2e-4

LIMITS = {
    "replica_mismatch": 0.25,
    "summary_gap": 1.5e-2,
    "residual_failures": 0,
}

#: the program's final-state fields and the reference's names for them.
STATE_FIELDS = {
    "t1": ("sched", "win_t1"), "t2": ("sched", "win_t2"),
    "valid": ("sched", "win_valid"), "link_free": ("link_free",),
    "rq_dl": ("rq_deadline",), "rq_src": ("rq_src",),
    "rq_ok": ("rq_valid",), "vc_s": ("vc_start",), "vc_end": ("vc_end",),
    "vc_dl": ("vc_deadline",), "vc_src": ("vc_src",),
    "vc_ok": ("vc_valid",),
}
TIME_FIELDS = ("t1", "t2", "link_free", "rq_dl", "vc_s", "vc_end",
               "vc_dl") + R.FLOAT_COUNTERS
EXACT_FIELDS = ("valid", "rq_src", "rq_ok", "vc_src", "vc_ok") \
    + R.INT_COUNTERS


def replica_gaps(prog: dict, ref: dict) -> tuple[np.ndarray, np.ndarray]:
    """Per sampled replica: whether an exact field differs, and the
    widest relative gap of a time value.  ``prog``/``ref`` map field
    names to arrays with a leading replica axis."""
    K = len(ref["frames"])
    exact_bad = np.zeros(K, bool)
    for k in EXACT_FIELDS:
        a = np.asarray(prog[k]).reshape(K, -1)
        b = np.asarray(ref[k]).reshape(K, -1)
        exact_bad |= (a != b).any(axis=1)
    rel = np.zeros(K)
    for k in TIME_FIELDS:
        a = np.asarray(prog[k], np.float64).reshape(K, -1)
        b = np.asarray(ref[k], np.float64).reshape(K, -1)
        gap = np.abs(a - b) / np.maximum(np.abs(b), 1.0)
        rel = np.maximum(rel, np.nan_to_num(gap, nan=np.inf).max(axis=1))
    return exact_bad, rel


def replica_mismatch(prog: dict, ref: dict) -> tuple[float, float]:
    """(share of replicas that disagree, widest time gap among replicas
    whose exact fields agree — for the record)."""
    exact_bad, rel = replica_gaps(prog, ref)
    bad = exact_bad | (rel > TIME_RTOL)
    agree = ~exact_bad
    return float(bad.mean()), float(rel[agree].max()) if agree.any() else 0.0


def summary_gap(prog: dict, ref: dict) -> float:
    """Widest gap between two per-cell summaries (dicts as
    ``reference.summarize`` returns); a key on one side only is an
    infinite gap."""
    gap = abs(float(prog["replicas"]) - float(ref["replicas"]))
    for k, r in ref.items():
        if not isinstance(r, dict):
            continue
        p = prog.get(k)
        if not isinstance(p, dict):
            return float("inf")
        for stat, rv in r.items():
            if stat not in p:
                return float("inf")
            gap = max(gap, abs(float(p[stat]) - float(rv)))
    return gap


def judge(numbers: dict) -> tuple[bool, dict]:
    checks = {k: {"value": numbers[k], "limit": lim}
              for k, lim in LIMITS.items()}
    ok = all(np.isfinite(c["value"]) and c["value"] <= c["limit"]
             for c in checks.values())
    return ok, checks
