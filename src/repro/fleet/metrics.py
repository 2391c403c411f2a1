"""Fleet statistics: per-replica counters and cross-replica reduction.

`FleetStats` is a pytree of `[B]` arrays carried through the engine's
scan; `summarize` reduces a (sub-)batch to Fig.-4-style rates with 95%
confidence intervals over replicas.

Preemption counters follow the serial engine's accounting so the two are
directly comparable (calib/):

- ``hp_preempted`` counts **committed** preemptions only — an HP
  containment miss that found no evictable LP victim is an admission
  failure (``hp_failed``), not a preemption.  One committed preemption
  evicts exactly one victim (the paper's single-victim §IV.B.3 path), so
  ``hp_preempted`` is also the victim count (the serial engine's
  ``lp_preempted``).
- ``lp_requeued`` counts victims successfully re-placed by the per-tick
  reallocation pass (the serial engine's ``lp_realloc_success``).
- ``missed_by_preemption`` counts victims dropped because their deadline
  expired before re-placement or the bounded re-queue buffer was full.

Conservation: every spawned LP task ends in exactly one of completed /
failed / missed_by_preemption / still-pending-in-buffer, i.e.

    lp_spawned == lp_completed + lp_failed + missed_by_preemption
                  + rq_valid.sum(axis=1)
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.tasks import FRAME_PERIOD


class FleetStats(NamedTuple):
    frames: jnp.ndarray             # i32[B] frames released
    frames_completed: jnp.ndarray   # i32[B] HP + every LP task placed in time
    hp_completed: jnp.ndarray       # i32[B]
    hp_preempted: jnp.ndarray       # i32[B] committed preemptions (= victims)
    hp_failed: jnp.ndarray          # i32[B] admission failed: nothing to evict
    lp_spawned: jnp.ndarray         # i32[B]
    lp_completed: jnp.ndarray       # i32[B] placed with end <= deadline,
    #                                        net of revoked victim credit
    lp_failed: jnp.ndarray          # i32[B] deadline-infeasible everywhere
    lp_requeued: jnp.ndarray        # i32[B] victims re-placed after eviction
    missed_by_preemption: jnp.ndarray  # i32[B] victims expired / buffer-full
    lp_offloaded: jnp.ndarray       # i32[B]
    lp_four_core: jnp.ndarray       # i32[B] widened to the 4-core config
    start_delay_sum: jnp.ndarray    # f32[B] Σ (start - release) of placed LP
    comm_busy: jnp.ndarray          # f32[B] link seconds spent transferring
    remainders_dropped: jnp.ndarray  # i32[B] min-duration remainders lost to
    #                                  full window arrays (fragmentation
    #                                  telemetry; previously a silent drop)


def init_stats(batch: int) -> FleetStats:
    zi = jnp.zeros((batch,), jnp.int32)
    zf = jnp.zeros((batch,), jnp.float32)
    return FleetStats(
        zi, zi, zi, zi, zi, zi, zi, zi, zi, zi, zi, zi, zf, zf, zi
    )


def _mean_ci(x: np.ndarray) -> dict:
    x = np.asarray(x, np.float64)
    n = x.size
    mean = float(x.mean()) if n else 0.0
    ci = float(1.96 * x.std(ddof=1) / np.sqrt(n)) if n > 1 else 0.0
    return {"mean": round(mean, 4), "ci95": round(ci, 4)}


def conservation_residual(stats: FleetStats, rq_pending) -> np.ndarray:
    """Per-replica residual of the LP-task conservation identity above:
    ``lp_spawned - (lp_completed + lp_failed + missed_by_preemption +
    rq_pending)``.  Exactly zero on every trace unless the engine has
    lost or double-counted a task; ``rq_pending`` is the end-of-run
    re-queue buffer occupancy ``FleetState.rq_valid.sum(axis=1)``."""
    s = {k: np.asarray(v, np.int64) for k, v in stats._asdict().items()
         if k in ("lp_spawned", "lp_completed", "lp_failed",
                  "missed_by_preemption")}
    pending = np.asarray(rq_pending, np.int64)
    return s["lp_spawned"] - (s["lp_completed"] + s["lp_failed"]
                              + s["missed_by_preemption"] + pending)


def _rates_impl(s: dict, rq_pending, xp) -> dict:
    """The counter→rate algebra over an array namespace (``xp`` is numpy
    for the host path, jax.numpy inside the sharded on-device reduction —
    one body, so the two can never drift apart).  ``s`` maps counter
    names to float arrays of the namespace's dtype."""
    frames = xp.maximum(s["frames"], 1)
    lp = xp.maximum(s["lp_spawned"], 1)
    # placements ever committed = net completions + revoked victim credits
    # (offload/4-core counters accrue at placement time and are not
    # unwound by preemption, so they normalise by this total)
    placed = xp.maximum(s["lp_completed"] + s["hp_preempted"], 1)
    victims = xp.maximum(s["hp_preempted"], 1)
    # only *initial* placements carry a start-delay sample (the requeue
    # paths measure nothing), so the mean excludes realloc placements
    initial = xp.maximum(
        s["lp_completed"] + s["hp_preempted"] - s["lp_requeued"], 1
    )
    out = {
        "frame_completion_rate": s["frames_completed"] / frames,
        "hp_completion_rate": s["hp_completed"] / frames,
        "hp_preemption_rate": s["hp_preempted"] / frames,
        "hp_failure_rate": s["hp_failed"] / frames,
        "lp_completion_rate": s["lp_completed"] / lp,
        "lp_violation_rate": s["lp_failed"] / lp,
        "requeue_success_rate": s["lp_requeued"] / victims,
        "missed_by_preemption_rate": s["missed_by_preemption"] / lp,
        "lp_offload_fraction": s["lp_offloaded"] / placed,
        "four_core_fraction": s["lp_four_core"] / placed,
        "mean_start_delay_s": s["start_delay_sum"] / initial,
        "remainder_drop_rate": s["remainders_dropped"] / frames,
    }
    if rq_pending is not None:
        # end-of-run re-queue buffer depth: the only term of the
        # conservation identity the counters alone do not report
        out["rq_pending_depth"] = rq_pending
    return out


def per_replica_rates(stats: FleetStats, rq_pending=None) -> dict:
    """Per-replica `[B]` rate arrays — the single place the counter
    algebra lives (summarize and the calibration harness both consume
    it, so the two can never drift apart).  Pass the end-of-run re-queue
    occupancy (``FleetState.rq_valid.sum(axis=1)``) as ``rq_pending`` to
    additionally report the one conservation term the counters alone
    cannot see."""
    s = {k: np.asarray(v, np.float64) for k, v in stats._asdict().items()}
    pending = (None if rq_pending is None
               else np.asarray(rq_pending, np.float64))
    return _rates_impl(s, pending, np)


# ---------------------------------------------------------------------------
# on-device cell reduction (sharded sweeps)
# ---------------------------------------------------------------------------
#
# A sharded sweep never pulls per-replica arrays to the host: the rates
# above are evaluated *inside* the sharded region (same `_rates_impl`
# body, jnp namespace), grouped by an `owner` cell id per replica, and
# reduced to per-cell first/second moments with `lax.psum` across the
# mesh (`lax.pmax` for the conservation-residual worst case).  The host
# receives `[C, K]` moment arrays — O(cells × metrics), independent of
# B and of the O(B·Dev·CFG·T·W) window state.

class CellMoments(NamedTuple):
    """Per-cell sufficient statistics of the per-replica rate vectors.

    ``count[C]`` replicas per cell, ``mean[C, K]``/``m2[C, K]`` the mean
    and centred second moment of each rate (K = ``len(cell_rate_keys())``,
    last column is the conservation residual), ``resid_max_abs[C]`` the
    per-cell worst |residual|.  Padding replicas carry ``owner == -1``
    and contribute to nothing.
    """

    count: np.ndarray          # f32[C]
    mean: np.ndarray           # f32[C, K]
    m2: np.ndarray             # f32[C, K]
    resid_max_abs: np.ndarray  # i32[C]


def _device_rates(stats: FleetStats, rq_pending, n_frames: int) -> dict:
    """`_rates_impl` under jnp, plus the two absolute-time rates that
    `summarize` derives outside the algebra — the on-device reduction
    must cover everything the host summary reports."""
    s = {k: v.astype(jnp.float32) for k, v in stats._asdict().items()}
    rates = _rates_impl(s, rq_pending.astype(jnp.float32), jnp)
    sim_time = n_frames * FRAME_PERIOD
    rates["link_utilisation"] = s["comm_busy"] / sim_time
    rates["lp_throughput_per_s"] = s["lp_completed"] / sim_time
    return rates


def cell_rate_keys() -> tuple[str, ...]:
    """Ordered rate names of the ``mean``/``m2`` columns (the residual
    column is appended by ``cell_moments``)."""
    dummy = FleetStats(*(np.zeros((1,), np.int32) for _ in
                         FleetStats._fields))
    keys = list(per_replica_rates(dummy, rq_pending=np.zeros((1,))))
    keys += ["link_utilisation", "lp_throughput_per_s",
             "conservation_residual"]
    return tuple(keys)


def cell_moments(stats: FleetStats, rq_valid, owner, *, n_cells: int,
                 n_frames: int, axis_name: str | None = None
                 ) -> CellMoments:
    """Reduce a (shard-local) batch to per-cell rate moments on device.

    ``owner`` is ``i32[B]`` mapping each replica to its grid cell
    (``-1`` = padding, excluded from every reduction).  Inside a
    ``shard_map`` pass the mesh ``axis_name`` so counts/sums/maxima
    combine across shards (`psum`/`pmax`) and every shard returns the
    identical replicated result; the two-pass centred second moment
    (mean first, then Σ(x−mean)²) keeps f32 variance stable at 10⁶
    replicas.
    """
    with jax.named_scope("sweep/cell_moments"):
        pending = rq_valid.sum(axis=1, dtype=jnp.int32)
        rates = _device_rates(stats, pending, n_frames)
        resid = (stats.lp_spawned - stats.lp_completed - stats.lp_failed
                 - stats.missed_by_preemption - pending).astype(jnp.int32)
        rates["conservation_residual"] = resid.astype(jnp.float32)
        mat = jnp.stack(list(rates.values()), axis=1)          # [B, K]
        # owner == -1 matches no cell column, so padding drops out of every
        # count/sum/max below without an explicit mask
        oh = (owner[:, None] == jnp.arange(n_cells, dtype=jnp.int32)[None, :]
              ).astype(jnp.float32)                            # [B, C]
        count = oh.sum(axis=0)
        # full f32 matmuls: the TPU's default precision rounds the operands
        # to bf16, which moves a cell mean by ~1e-4 relative
        dot = functools.partial(jnp.matmul,
                                precision=jax.lax.Precision.HIGHEST)
        sums = dot(oh.T, mat)                                  # [C, K]
        if axis_name is not None:
            count, sums = jax.lax.psum((count, sums), axis_name)
        mean = sums / jnp.maximum(count, 1.0)[:, None]
        centred = mat - mean[jnp.clip(owner, 0)]
        m2 = dot(oh.T, centred * centred)
        resid_max = jnp.max(
            jnp.where(oh > 0, jnp.abs(resid)[:, None], 0), axis=0
        ).astype(jnp.int32)
        if axis_name is not None:
            m2 = jax.lax.psum(m2, axis_name)
            resid_max = jax.lax.pmax(resid_max, axis_name)
        return CellMoments(count, mean, m2, resid_max)


def merge_cell_moments(a: Optional[CellMoments],
                       b: CellMoments) -> CellMoments:
    """Combine per-cell moments of two disjoint replica populations
    (Chan et al. parallel-variance merge, float64 host-side) — the sweep
    folds one batch at a time into a running total."""
    b = CellMoments(*(np.asarray(x, np.float64) for x in b[:3]),
                    np.asarray(b.resid_max_abs, np.int64))
    if a is None:
        return b
    n = a.count + b.count
    safe = np.maximum(n, 1.0)
    delta = b.mean - a.mean
    mean = a.mean + delta * (b.count / safe)[:, None]
    m2 = a.m2 + b.m2 + (delta * delta) * (
        a.count * b.count / safe
    )[:, None]
    return CellMoments(
        n, mean, m2, np.maximum(a.resid_max_abs, b.resid_max_abs)
    )


def summarize_cells(m: CellMoments, keys: tuple[str, ...] | None = None
                    ) -> list[dict]:
    """Per-cell summaries (same shape as ``summarize``'s dict) from
    reduced moments — the O(metrics) twin of the per-replica path."""
    keys = keys or cell_rate_keys()
    out = []
    for c in range(m.count.shape[0]):
        n = float(m.count[c])
        cell: dict = {"replicas": int(n)}
        for k, ki in zip(keys, range(len(keys))):
            mean = float(m.mean[c, ki])
            var = float(m.m2[c, ki]) / (n - 1.0) if n > 1 else 0.0
            ci = 1.96 * np.sqrt(max(var, 0.0) / n) if n > 1 else 0.0
            entry = {"mean": round(mean, 4), "ci95": round(float(ci), 4)}
            if k == "conservation_residual":
                entry["max_abs"] = int(m.resid_max_abs[c])
            cell[k] = entry
        out.append(cell)
    return out


def summarize(stats: FleetStats, n_frames: int, *, rq_pending=None) -> dict:
    """Reduce per-replica counters to mean ± 95% CI across the batch.

    With ``rq_pending`` (end-of-run ``FleetState.rq_valid.sum(axis=1)``)
    the summary additionally reports ``rq_pending_depth`` and the checked
    ``conservation_residual`` of the LP-task identity — any non-zero
    ``max_abs`` means the engine lost or double-counted a task."""
    s = {k: np.asarray(v) for k, v in stats._asdict().items()}
    sim_time = n_frames * FRAME_PERIOD
    out = {"replicas": int(s["frames"].size)}
    out.update(
        (k, _mean_ci(v))
        for k, v in per_replica_rates(stats, rq_pending=rq_pending).items()
    )
    out["link_utilisation"] = _mean_ci(s["comm_busy"] / sim_time)
    out["lp_throughput_per_s"] = _mean_ci(s["lp_completed"] / sim_time)
    if rq_pending is not None:
        residual = conservation_residual(stats, rq_pending)
        out["conservation_residual"] = {
            **_mean_ci(residual),
            "max_abs": int(np.abs(residual).max()) if residual.size else 0,
        }
    return out
