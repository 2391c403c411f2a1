"""Fleet state: B independent scheduler replicas as one pytree.

`FleetState` stacks `SchedState` (core/jax_state.py) along a leading batch
axis — every window/link array gains a `[B, ...]` dimension, so the whole
Monte-Carlo fleet is a valid `jax.lax.scan` carry and a single XLA
program advances all replicas per tick.

Fleet-only fields ride along:

    link_free  f32[B]   serial-link FIFO head — the earliest instant a new
                        offload transfer may start on each replica's WLAN.
                        The fixed-step engine models the shared 802.11 link
                        as a serial queue (transfers don't overlap), which
                        is the paper's §IV.A.2 discretisation collapsed to
                        its capacity-1 limit; per-replica bandwidth churn
                        (scenarios.py) scales each transfer's duration.
    now        f32[B]   per-replica simulation clock (replicas share the
                        frame grid but keep their own clock so partially
                        filled batches stay independent).

Preemption fidelity (§IV.B.3) needs two more groups of arrays:

    rq_deadline  f32[B, R]   bounded victim re-queue: LP tasks evicted by an
    rq_src       i32[B, R]   HP preemption wait here for re-placement on a
    rq_valid     bool[B, R]  later tick (R = FleetParams.requeue_slots).

    vc_start     f32[B, Dev] one-deep victim cache: the most recently
    vc_end       f32[B, Dev] committed LP placement per device.  The serial
    vc_deadline  f32[B, Dev] engine evicts the overlapping LP task with the
    vc_src       i32[B, Dev] *farthest* deadline; deadlines grow with
    vc_valid     bool[B, Dev] release time, so the newest commit is that
                             victim whenever it overlaps the HP slot — a
                             one-slot cache per device is the
                             bounded-memory abstraction of the workload
                             scan (older overlapping tasks are invisible,
                             so preemption can fail admission where the
                             serial engine would still find a victim).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.jax_state import BIG as STATE_BIG  # noqa: F401  (re-export)
from repro.core.jax_state import SchedState, export_state
from repro.core.scheduler import RASScheduler
from repro.core.tasks import ALL_CONFIGS, DEVICE_CORES


class FleetState(NamedTuple):
    sched: SchedState        # every leaf carries a leading [B] axis
    link_free: jnp.ndarray   # [B]
    now: jnp.ndarray         # [B]
    # victim re-queue buffer (preempted LP tasks awaiting re-placement)
    rq_deadline: jnp.ndarray  # f32[B, R]
    rq_src: jnp.ndarray       # i32[B, R]
    rq_valid: jnp.ndarray     # bool[B, R]
    # per-device cache of the most recent committed LP placement
    vc_start: jnp.ndarray     # f32[B, Dev]
    vc_end: jnp.ndarray       # f32[B, Dev]
    vc_deadline: jnp.ndarray  # f32[B, Dev]
    vc_src: jnp.ndarray       # i32[B, Dev]
    vc_valid: jnp.ndarray     # bool[B, Dev]


def broadcast_state(st: SchedState, batch: int) -> SchedState:
    """Tile one replica's SchedState along a new leading batch axis."""
    return jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x, (batch,) + x.shape), st
    )


def stack_states(states: list[SchedState]) -> SchedState:
    """Stack per-replica SchedStates (e.g. mid-run snapshots) into a batch."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *states)


@functools.lru_cache(maxsize=None)
def _pristine_replica(n_devices: int, bandwidth_bps: float,
                      max_windows: int) -> SchedState:
    """One replica's pristine SchedState as host arrays, exported from a
    fresh `RASScheduler` once per layout (eagerly, also when first
    asked for inside a trace)."""
    with jax.ensure_compile_time_eval():
        base = export_state(
            RASScheduler(n_devices, bandwidth_bps), max_windows=max_windows
        )
        return jax.tree_util.tree_map(np.asarray, base)


def make_fleet(batch: int, n_devices: int = 4, bandwidth_bps: float = 20e6,
               *, max_windows: int = 16, requeue_slots: int = 4) -> FleetState:
    """A pristine B-replica fleet: every device fully available from t=0.

    Built by exporting a fresh `RASScheduler` (so window/track/link layout
    is byte-identical to the reference path) and broadcasting it, in one
    device program: a sweep builds a fleet per batch, and each eager op
    would cost the host a dispatch while the device waits.

    ``max_windows=16`` (the export default) is calibrated for the fleet
    scan: the per-tick housekeeping pass recycles elapsed windows, so
    occupancy never approaches the cap — W=8 yields byte-identical sweep
    statistics, and doubling W roughly halves replicas/sec on CPU.

    ``requeue_slots`` must match ``FleetParams.requeue_slots`` of the
    engine that will consume this fleet (the re-queue buffer is part of
    the scan carry, so its width is a compile-time shape).
    """
    return _make_fleet(batch, n_devices, float(bandwidth_bps), max_windows,
                       requeue_slots)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4))
def _make_fleet(batch, n_devices, bandwidth_bps, max_windows, requeue_slots):
    base = _pristine_replica(n_devices, bandwidth_bps, max_windows)
    return FleetState(
        sched=broadcast_state(base, batch),
        link_free=jnp.zeros((batch,), jnp.float32),
        now=jnp.zeros((batch,), jnp.float32),
        rq_deadline=jnp.zeros((batch, requeue_slots), jnp.float32),
        rq_src=jnp.zeros((batch, requeue_slots), jnp.int32),
        rq_valid=jnp.zeros((batch, requeue_slots), bool),
        vc_start=jnp.zeros((batch, n_devices), jnp.float32),
        vc_end=jnp.zeros((batch, n_devices), jnp.float32),
        vc_deadline=jnp.zeros((batch, n_devices), jnp.float32),
        vc_src=jnp.zeros((batch, n_devices), jnp.int32),
        vc_valid=jnp.zeros((batch, n_devices), bool),
    )


def fleet_shape(fs: FleetState) -> tuple[int, int, int, int, int]:
    """(B, Dev, CFG, T, W) of a fleet."""
    return fs.sched.win_t1.shape


def track_counts() -> dict[str, int]:
    return {c.name: DEVICE_CORES // c.cores for c in ALL_CONFIGS}
