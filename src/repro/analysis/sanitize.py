"""Checkify sanitizer mode: runtime invariants on the §IV.A/§IV.B state
machine, switched on with ``REPRO_SANITIZE=1``.

When enabled, the fleet scan (``fleet_run`` in fleet/engine.py) runs
``jax.experimental.checkify``-transformed segment programs that assert,
inside every tick:

- **window monotonicity** — every valid availability window has
  ``t1 <= t2`` (a corrupted window order is exactly the silent
  scheduler-state corruption a racy kernel write would produce);
- **availability conservation** — the re-queue, HP and LP commits of a
  tick only ever *consume* availability: per replica, the valid window
  time inside the span the tick's commits fall in does not grow from
  after housekeeping and compaction to the end of the device loop;
- **capacity sanity** — ``link_free`` non-negative, victim-cache windows
  ordered.

The invariant checks are *traced into the program* only on the sanitized
path (a static ``sanitize`` flag selects the variant), so the default
path stays byte-identical to the unsanitized build; a trip raises
``checkify.JaxRuntimeError`` with the failing invariant named.

The CI test matrix runs the whole suite once with ``REPRO_SANITIZE=1``
(see .github/workflows/ci.yml), so every existing equivalence/regression
test doubles as an invariant probe.
"""

from __future__ import annotations

import os

import jax.numpy as jnp
from jax.experimental import checkify

ENV_VAR = "REPRO_SANITIZE"

#: relative + absolute slack for f32 availability totals (window ends sit
#: at BIG=1e30, where one ulp is ~1e23 — conservation can only be judged
#: relative to the total's magnitude).
REL_TOL = 1e-5
ABS_TOL = 1e-3


def enabled() -> bool:
    """True when ``REPRO_SANITIZE`` is set to anything but ''/'0'."""
    return os.environ.get(ENV_VAR, "0") not in ("", "0")


def check(pred, msg: str, **fmt) -> None:
    """``checkify.check`` with keyword payloads; call sites gate on a
    static ``sanitize`` flag so the unsanitized trace carries no checks."""
    checkify.check(pred, msg, **fmt)


# ---------------------------------------------------------------------------
# invariants over SchedState-shaped window arrays
# ---------------------------------------------------------------------------

def total_availability(t1, t2, valid, *, batch_axes: int = 0):
    """Total valid window length, reduced over everything but the leading
    ``batch_axes`` axes."""
    axes = tuple(range(batch_axes, t1.ndim))
    return jnp.sum(jnp.where(valid, t2 - t1, 0.0), axis=axes)


def check_windows(t1, t2, valid, where: str) -> None:
    """Window monotonicity: valid ⇒ t1 <= t2."""
    ordered = jnp.all(~valid | (t1 <= t2))
    check(
        ordered,
        "window order violated (" + where + "): a valid availability "
        "window has t1 > t2 — scheduler window state is corrupt; "
        "min t2-t1 = {gap}",
        gap=jnp.min(jnp.where(valid, t2 - t1, jnp.inf)),
    )


def check_sched_state(state, where: str) -> None:
    """Full §IV invariant set on one (possibly batched) SchedState."""
    check_windows(state.win_t1, state.win_t2, state.win_valid, where)
    check(
        jnp.all(state.min_dur > 0),
        "non-positive min_dur (" + where + "): {md}", md=state.min_dur,
    )


def check_no_avail_increase(before, after, where: str) -> None:
    """Availability conservation: totals may only shrink (placements
    consume windows; housekeeping expires them; nothing creates them)."""
    bound = before * (1.0 + REL_TOL) + ABS_TOL
    check(
        jnp.all(after <= bound),
        "availability increased (" + where + "): a commit/compaction "
        "manufactured window time; max excess = {x}",
        x=jnp.max(after - before),
    )
