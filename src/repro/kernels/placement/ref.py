"""Pure-jnp oracle for the fused placement kernel.

One *placement attempt* of the batched fleet engine — the §IV.B.2
multi-containment query over every device for the preferred (2-core) and
fallback (4-core) LP configs, device selection (source preference, then
earliest start), and the §IV.A.1 multi-remainder fan-out commit on the
winning device — as a single pure function of the window arrays.

``_fused_place_math`` is one trace shared by the oracle and the Pallas
kernel body (placement.py).  It works in the kernel's layout, replica
axis last (``to_lanes``): the replicas fill the TPU's 128-lane axis, and
every per-replica or per-device value broadcasts along leading dims.
Every op is broadcast/compare/reduce (no gather/scatter/sort), the subset
that lowers in a kernel, and every float sum that can hold more than one
non-zero term is a fixed-order fold, so the kernel and the oracle round
identically.  The oracle is ``to_lanes`` → the math → ``from_lanes``; the
equivalence tests assert exact equality.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.jax_state import BIG, _iota, fanout_commit_lanes

#: source-device preference margin (seconds) — matches the fleet engine's
#: historical tie-break.
SRC_PREF = 1e-3


def to_lanes(t1, t2, valid, min_dur, q1, dl, src, do):
    """The engine's batch-first operands (windows ``[B, Dev, CFG, T, W]``,
    ``min_dur [B, CFG]``, ``q1``/``dl`` ``[B, Dev]`` or broadcastable,
    ``src``/``do`` ``[B]``) in the replica-last layout of
    ``_fused_place_math``."""
    B, n_dev = t1.shape[:2]
    last = lambda a: jnp.moveaxis(a, 0, -1)
    per_dev = lambda a: last(
        jnp.broadcast_to(jnp.asarray(a, jnp.float32), (B, n_dev))
    )[:, None]
    return (last(t1), last(t2), last(valid.astype(bool)),
            last(min_dur)[:, None], per_dev(q1), per_dev(dl),
            jnp.asarray(src, jnp.int32)[None], jnp.asarray(do, bool)[None])


def from_lanes(nt1, nt2, nv, ok, sel, start, dur, use4, n_drop):
    """``_fused_place_math`` outputs back in the batch-first layout."""
    first = lambda a: jnp.moveaxis(a, -1, 0)
    return (first(nt1), first(nt2), first(nv), ok[0], sel[0], start[0],
            dur[0], use4[0], n_drop[0])


def _fused_place_math(t1, t2, valid, min_dur, q1, dl, src, do, *,
                      cfg_pref: int, cfg_fallback: int):
    """Query + select + commit, replica axis last.

    Windows ``[Dev, CFG, T, W, N]``; ``min_dur [CFG, 1, N]``; ``q1``/``dl``
    ``[Dev, 1, N]`` (comm-adjusted per device); ``src`` i32 and ``do`` bool
    ``[1, N]``; ``do`` masks the attempt.

    Returns ``(t1', t2', valid', ok, sel, start, dur, use4, n_dropped)``
    with per-replica outputs ``[1, N]``; ``ok`` is already ANDed with
    ``do`` and the windows of replicas with ``ok=False`` are bit-identical
    to the input.
    """
    n_dev = q1.shape[0]
    dev_ids = _iota((n_dev, 1, 1), 0)
    per_cfg = []
    for ci in (cfg_pref, cfg_fallback):
        dur_c = min_dur[ci]                                    # [1, N]
        startw = jnp.maximum(t1[:, ci], q1[:, None])           # [Dev, T, W, N]
        feas = valid[:, ci] & (
            startw + dur_c <= jnp.minimum(t2[:, ci], dl[:, None])
        )
        best = jnp.min(jnp.where(feas, startw, BIG), axis=2, keepdims=True)
        best = jnp.min(best, axis=1)                           # [Dev, 1, N]
        found = best < BIG
        # prefer the source device, then earliest start; first index wins
        # ties (== jnp.argmin), expressed as a min-reduce so the identical
        # code lowers inside the kernel
        key = jnp.where(found, best, BIG)
        key = key - jnp.where(dev_ids == src, SRC_PREF, 0.0)
        kmin = jnp.min(key, axis=0)                            # [1, N]
        sel_c = jnp.min(jnp.where(key == kmin, dev_ids, n_dev), axis=0)
        sel_oh = dev_ids == sel_c                              # [Dev, 1, N]
        ok_c = jnp.max((found & sel_oh).astype(jnp.int32), axis=0) > 0
        start_c = jnp.sum(jnp.where(sel_oh, best, 0.0), axis=0)
        per_cfg.append((ok_c, sel_c, start_c, dur_c))
    (ok2, sel2, start2, dur2), (ok4, sel4, start4, dur4) = per_cfg
    # §IV.B.2: 2-core preferred; widen to 4 cores only when the deadline
    # would otherwise be violated
    use4 = ~ok2 & ok4
    ok = (ok2 | ok4) & do
    sel = jnp.where(use4, sel4, sel2)
    start = jnp.where(use4, start4, start2)
    dur = jnp.where(use4, dur4, dur2)
    cfg_commit = jnp.where(
        use4, jnp.int32(cfg_fallback), jnp.int32(cfg_pref)
    )
    nt1, nt2, nv, n_drop = fanout_commit_lanes(
        t1, t2, valid, min_dur, sel, cfg_commit, start, start + dur, ok,
    )
    return nt1, nt2, nv, ok, sel, start, dur, use4, n_drop


@functools.partial(jax.jit, static_argnames=("cfg_pref", "cfg_fallback"))
def fused_place_ref(t1, t2, valid, min_dur, q1, dl, src, do, *,
                    cfg_pref: int = 1, cfg_fallback: int = 2):
    """jnp oracle entry point on the engine's batch-first operands:
    windows ``[B, Dev, CFG, T, W]``, ``min_dur [B, CFG]``, ``q1``/``dl``
    ``[B, Dev]``, ``src``/``do`` ``[B]``.  Returns ``(t1', t2', valid',
    ok, sel, start, dur, use4, n_dropped)`` with per-replica outputs
    ``[B]``."""
    return from_lanes(*_fused_place_math(
        *to_lanes(t1, t2, valid, min_dur, q1, dl, src, do),
        cfg_pref=cfg_pref, cfg_fallback=cfg_fallback,
    ))
