"""Host-side phase profiling: one span primitive on the profiler's clock,
a host span timer and counters, plus an optional ``jax.profiler.trace``
hook.

Two cooperating layers:

- **Spans and counters** — instrumented code (``run_sweep``,
  ``fleet_run``, benchmark scripts) wraps its phases in
  ``with span("name"):``.  Every span is a
  ``jax.profiler.TraceAnnotation``, so while a profiler trace is being
  captured it lands on the trace's host planes, on the same clock as
  the device ops.  ``with PhaseTimer() as t:`` additionally collects the
  spans' wall time and the ``count(name, n)`` counters; with no timer
  active a span costs one annotation and a counter nothing, so the
  program stays instrumented unconditionally.  ``t.summary()`` reduces
  to per-phase count / total / mean / max plus each counter's total, and
  ``t.save(path)`` persists the summary as a ``results/obs/`` artifact.
- **Device profiler hook** — ``maybe_jax_trace()`` wraps a block in
  ``jax.profiler.trace(REPRO_PROFILE_DIR)`` when that environment
  variable is set (the emitted trace opens in TensorBoard's profiler or
  ui.perfetto.dev), and is a no-op otherwise.  Nested invocations are
  guarded: only the outermost block traces.

Timers nest: every active timer records every span and counter, so a
benchmark-level timer sees the engine's internal phases too.
"""

from __future__ import annotations

import contextlib
import json
import os
import time
from typing import Iterator

import jax

ENV_VAR = "REPRO_PROFILE_DIR"

#: currently-active timers (appended by ``PhaseTimer.__enter__``).
_ACTIVE: list["PhaseTimer"] = []

_TRACING = False


class PhaseTimer:
    """Collects wall-clock span durations and counter totals while active
    (context manager)."""

    def __init__(self) -> None:
        self.spans: dict[str, list[float]] = {}
        self.counts: dict[str, int] = {}

    def add(self, name: str, seconds: float) -> None:
        self.spans.setdefault(name, []).append(seconds)

    def __enter__(self) -> "PhaseTimer":
        _ACTIVE.append(self)
        return self

    def __exit__(self, *exc) -> None:
        _ACTIVE.remove(self)

    def summary(self) -> dict:
        out = {}
        for name, xs in sorted(self.spans.items()):
            total = sum(xs)
            out[name] = {
                "count": len(xs),
                "total_s": round(total, 6),
                "mean_ms": round(total / len(xs) * 1e3, 3),
                "max_ms": round(max(xs) * 1e3, 3),
            }
        for name, n in sorted(self.counts.items()):
            out[name] = {"total": n}
        return out

    def save(self, path: str, extra: dict | None = None) -> dict:
        """Write ``{phases: summary, **extra}`` as JSON; returns the dict."""
        payload = {"phases": self.summary()}
        if extra:
            payload.update(extra)
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        with open(path, "w") as f:
            json.dump(payload, f, indent=1)
        return payload


@contextlib.contextmanager
def span(name: str) -> Iterator[None]:
    """A ``jax.profiler.TraceAnnotation`` named ``name``, whose wall time
    is also recorded into every active PhaseTimer."""
    with jax.profiler.TraceAnnotation(name):
        if not _ACTIVE:
            yield
            return
        t0 = time.perf_counter()
        try:
            yield
        finally:
            dt = time.perf_counter() - t0
            for timer in _ACTIVE:
                timer.add(name, dt)


def count(name: str, n: int) -> None:
    """Add ``n`` to counter ``name`` of every active PhaseTimer (no-op when
    none is active)."""
    for timer in _ACTIVE:
        timer.counts[name] = timer.counts.get(name, 0) + int(n)


@contextlib.contextmanager
def maybe_jax_trace() -> Iterator[None]:
    """Wrap a block in ``jax.profiler.trace($REPRO_PROFILE_DIR)`` when the
    variable is set; plain passthrough (and re-entrant safe) otherwise."""
    global _TRACING
    trace_dir = os.environ.get(ENV_VAR, "")
    if not trace_dir or _TRACING:
        yield
        return
    import jax

    _TRACING = True
    try:
        with jax.profiler.trace(trace_dir):
            yield
    finally:
        _TRACING = False
