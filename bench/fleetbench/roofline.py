"""Least bytes the placement layer must move per engine step.

One step of the fleet engine makes ``1 + Dev * (1 + MAX_LP)`` LP
placement attempts: one re-queue attempt, then per device one
preemption re-placement and up to ``MAX_LP`` task placements.  However
the attempts are fused, a step must read each replica's window state
once and write it once, and every attempt must read its per-replica
operands and write its results.  Counted at logical dtypes (f32 times,
1-byte flags, i32 indices), per step and not per launch, so the count
holds whatever the kernel's layout or launch count.  The bound is
bandwidth: the attempts do a few compares per byte.
"""

from __future__ import annotations

F32, I32, FLAG = 4, 4, 1

#: window state per (device, config, track, slot): t1, t2 f32 and valid.
WINDOW_BYTES = F32 + F32 + FLAG


def attempts_per_step(n_devices: int, max_lp: int) -> int:
    return 1 + n_devices * (1 + max_lp)


def attempt_operand_bytes(n_devices: int, n_cfg: int) -> int:
    """Per replica and attempt: in q1 and dl per device, src, do and the
    per-config minimum durations; out ok, sel, start, dur, use4 and the
    dropped-piece count."""
    inputs = 2 * F32 * n_devices + I32 + FLAG + F32 * n_cfg
    outputs = FLAG + I32 + F32 + F32 + FLAG + I32
    return inputs + outputs


def placement_bytes_per_step(batch: int, n_devices: int, n_cfg: int,
                             tracks: int, windows: int,
                             max_lp: int = 4) -> int:
    """Bytes for one step of a ``(batch, n_devices, n_cfg, tracks,
    windows)`` window state: one read and one write of the state plus
    every attempt's operands and results."""
    state = batch * n_devices * n_cfg * tracks * windows * WINDOW_BYTES
    attempts = attempts_per_step(n_devices, max_lp)
    return 2 * state + batch * attempts * attempt_operand_bytes(
        n_devices, n_cfg)


def least_seconds(bytes_: float, hbm_bytes_per_s: float) -> float:
    return bytes_ / hbm_bytes_per_s
