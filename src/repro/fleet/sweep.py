"""Monte-Carlo sweep driver: seed × scenario × congestion grids as batches.

`run_sweep` flattens the grid into replicas (cell = scenario × congestion,
`n_seeds` replicas per cell), packs replicas into fixed-size batches so
every `fleet_run` call shares one compiled program, and reduces each
cell's slice to mean ± 95% CI statistics.  There is **no Python loop over
replicas** — only over batches, each of which advances up to
`batch_size` replicas inside a single jitted scan.

Sharded sweeps (``mesh_shards >= 1``): each batch runs under `shard_map`
over the fleet mesh (B/shards replicas per device) and is reduced to
per-cell rate moments *on device* (metrics.cell_moments — `psum`/`pmax`
inside the sharded region), so the host receives O(cells × metrics)
floats per batch instead of `[B]` counter arrays and never sees the
O(B·state) window buffers.  Batch moments fold into a running total via
the parallel-variance merge; the per-cell summaries carry the same keys
as the host path (including the checked conservation residual, whose
``max_abs`` must be 0 on every trace).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Sequence

import numpy as np

import jax
from jax.sharding import PartitionSpec

from repro.fleet import mesh as _mesh
from repro.fleet.engine import FleetParams, fleet_run
from repro.fleet.metrics import (
    FleetStats, cell_moments, cell_rate_keys,
    merge_cell_moments, summarize, summarize_cells,
)
from repro.fleet.scenarios import make_workload
from repro.fleet.state import make_fleet
from repro.obs.profile import maybe_jax_trace, span


@dataclasses.dataclass(frozen=True)
class SweepConfig:
    scenarios: Sequence[str] = ("uniform", "weighted2")
    congestion_levels: Sequence[float] = (0.0, 0.3)
    n_seeds: int = 64                 # replicas per (scenario, congestion)
    n_frames: int = 95
    n_devices: int = 4
    batch_size: int = 256             # replicas advanced per XLA program
    base_seed: int = 0
    #: shard every batch over this many mesh devices and reduce metrics
    #: on-device (0 = unsharded host-side reduction, the legacy path).
    mesh_shards: int = 0
    params: Optional[FleetParams] = None

    def fleet_params(self) -> FleetParams:
        p = self.params if self.params is not None else FleetParams(
            n_devices=self.n_devices
        )
        if self.mesh_shards and p.mesh_shards != self.mesh_shards:
            p = dataclasses.replace(p, mesh_shards=self.mesh_shards)
        return p


def _cells(cfg: SweepConfig):
    for scen in cfg.scenarios:
        for cong in cfg.congestion_levels:
            yield scen, float(cong)


def _build_population(cfg: SweepConfig):
    """Host-side workload for the whole grid: each cell contributes
    n_seeds replica columns keyed by (base_seed, scenario, congestion)."""
    cells = list(_cells(cfg))
    vals, bws, owners = [], [], []
    for ci, (scen, cong) in enumerate(cells):
        wl = make_workload(
            scen, cfg.n_seeds, cfg.n_frames, cfg.n_devices,
            seed=cfg.base_seed + ci, congestion=cong,
        )
        vals.append(wl.values)
        bws.append(wl.bw_scale)
        owners.extend([ci] * cfg.n_seeds)
    values = np.concatenate(vals, axis=1)          # [F, Btot, Dev]
    bw_scale = np.concatenate(bws, axis=1)         # [F, Btot]
    return cells, values, bw_scale, np.asarray(owners, np.int32)


def run_sweep(cfg: SweepConfig) -> dict:
    """Returns {"scenario@congestion": summary} plus a "_sweep" header.

    With ``$REPRO_PROFILE_DIR`` set, the whole sweep is one profiler
    trace (``obs.profile.maybe_jax_trace``): its host spans and the
    device ops of every batch."""
    with maybe_jax_trace():
        if cfg.mesh_shards:
            return _run_sweep_sharded(cfg)
        return _run_sweep_host(cfg)


def _run_sweep_host(cfg: SweepConfig) -> dict:
    p = cfg.fleet_params()
    with span("sweep/workload"):
        cells, values, bw_scale, owners = _build_population(cfg)
    total = values.shape[1]

    # Fan into fixed-size batches (pad the tail so every launch reuses the
    # same compiled program; padded replicas are dropped on reduction).
    bs = min(cfg.batch_size, total) if total else cfg.batch_size
    pad = (-total) % bs
    if pad:
        values = np.concatenate([values, values[:, :pad]], axis=1)
        bw_scale = np.concatenate([bw_scale, bw_scale[:, :pad]], axis=1)
    per_replica: list[FleetStats] = []
    per_replica_pending: list[np.ndarray] = []
    for b0 in range(0, values.shape[1], bs):
        with span("sweep/batch"):
            with span("sweep/make_fleet"):
                fleet = make_fleet(bs, cfg.n_devices,
                                   requeue_slots=p.requeue_slots)
            state, stats = fleet_run(
                fleet,
                values[:, b0:b0 + bs],
                bw_scale[:, b0:b0 + bs],
                params=p,
            )
            # the host waits here for the batch's device work
            with span("sweep/collect"):
                per_replica.append(jax_to_np(stats))
                # end-of-run re-queue occupancy: closes the LP
                # conservation identity that summarize() checks per cell
                per_replica_pending.append(
                    np.asarray(state.rq_valid).sum(axis=1).astype(np.int64)
                )
    with span("sweep/summarize"):
        merged = FleetStats(*(
            np.concatenate([getattr(s, f) for s in per_replica])[:total]
            for f in FleetStats._fields
        ))
        pending = np.concatenate(per_replica_pending)[:total]
        out = {
            "_sweep": {
                "cells": [f"{s}@{c:g}" for s, c in cells],
                "n_seeds": cfg.n_seeds,
                "n_frames": cfg.n_frames,
                "total_replicas": int(total),
                "batch_size": bs,
            }
        }
        for ci, (scen, cong) in enumerate(cells):
            sel = owners == ci
            cell_stats = FleetStats(
                *(getattr(merged, f)[sel] for f in FleetStats._fields)
            )
            out[f"{scen}@{cong:g}"] = summarize(
                cell_stats, cfg.n_frames, rq_pending=pending[sel]
            )
    return out


# ---------------------------------------------------------------------------
# sharded path: on-device per-cell reduction, O(metrics) host transfer
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _cell_reducer(shards: int, n_cells: int, n_frames: int):
    """Jitted sharded reducer: (stats, rq_valid, owner) — all sharded on
    the batch axis — to replicated per-cell CellMoments.  The psum/pmax
    collectives live inside the shard_map region, so each shard transfers
    nothing and the host reads one tiny replicated result."""
    fn = functools.partial(
        cell_moments, n_cells=n_cells, n_frames=n_frames,
        axis_name=_mesh.FLEET_AXIS,
    )
    P = PartitionSpec
    sharded = jax.shard_map(
        fn, mesh=_mesh.fleet_mesh(shards),
        in_specs=(P(_mesh.FLEET_AXIS), P(_mesh.FLEET_AXIS),
                  P(_mesh.FLEET_AXIS)),
        out_specs=P(),
        check_vma=False,
    )
    # one-shot reduction over stats the caller still owns; the replicated
    # [C, K] output IS the intended O(metrics) transfer
    # repro: lint-ok(host-transfer)
    return jax.jit(sharded)


def _run_sweep_sharded(cfg: SweepConfig) -> dict:
    p = cfg.fleet_params()
    shards = p.mesh_shards
    with span("sweep/workload"):
        cells, values, bw_scale, owners = _build_population(cfg)
    total = values.shape[1]

    # batch size must split across the mesh; pad the tail with owner=-1
    # replicas, which the on-device reduction excludes from every cell
    bs = min(cfg.batch_size, total) if total else cfg.batch_size
    bs += _mesh.shard_pad(bs, shards)
    pad = (-total) % bs
    if pad:
        values = np.concatenate([values, values[:, :pad]], axis=1)
        bw_scale = np.concatenate([bw_scale, bw_scale[:, :pad]], axis=1)
        owners = np.concatenate([owners, np.full((pad,), -1, np.int32)])

    reducer = _cell_reducer(shards, len(cells), cfg.n_frames)
    moments = None
    for b0 in range(0, values.shape[1], bs):
        with span("sweep/batch"):
            with span("sweep/make_fleet"):
                fleet = make_fleet(bs, cfg.n_devices,
                                   requeue_slots=p.requeue_slots)
            state, stats = fleet_run(
                fleet,
                values[:, b0:b0 + bs],
                bw_scale[:, b0:b0 + bs],
                params=p,
            )
            owner = _mesh.put_sharded(
                np.ascontiguousarray(owners[b0:b0 + bs]),
                _mesh.fleet_mesh(shards),
            )
            # the host waits here for the batch's device work
            with span("sweep/reduce"):
                batch_moments = reducer(stats, state.rq_valid, owner)
                # the one host transfer per batch: [C] + 2×[C, K] moment
                # arrays
                moments = merge_cell_moments(
                    moments,
                    jax.tree_util.tree_map(np.asarray, batch_moments)
                )

    keys = cell_rate_keys()
    summaries = summarize_cells(moments, keys)
    out = {
        "_sweep": {
            "cells": [f"{s}@{c:g}" for s, c in cells],
            "n_seeds": cfg.n_seeds,
            "n_frames": cfg.n_frames,
            "total_replicas": int(total),
            "batch_size": bs,
            "mesh": {
                "shards": shards,
                "replicas_per_shard": bs // shards,
                "reduction": "on-device (psum/pmax, O(cells x metrics) "
                             "host transfer)",
            },
        }
    }
    for ci, (scen, cong) in enumerate(cells):
        out[f"{scen}@{cong:g}"] = summaries[ci]
    return out


def jax_to_np(stats: FleetStats) -> FleetStats:
    return FleetStats(*(np.asarray(x) for x in stats))
