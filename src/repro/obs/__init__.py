"""Observability subsystem: in-scan fleet telemetry, serial-DES event
logs, Chrome-trace/Perfetto exporters, and host-side phase profiling.

Deliberately dependency-light at import time: this package is imported
by ``fleet/engine.py`` and ``sim/engine.py``, so nothing here may import
them back at module scope (the CLI imports the engines lazily).
"""

from repro.obs.events import KINDS, Event, EventLog
from repro.obs.export import (
    fleet_trace_events,
    load_trace,
    sim_trace_events,
    validate_trace,
    write_chrome_trace,
)
from repro.obs.profile import PhaseTimer, count, maybe_jax_trace, span
from repro.obs.telemetry import (
    TelemetryFrame,
    TelemetryRecord,
    assemble,
    capture_tick,
    load_record,
)

__all__ = [
    "Event",
    "EventLog",
    "KINDS",
    "PhaseTimer",
    "TelemetryFrame",
    "TelemetryRecord",
    "assemble",
    "capture_tick",
    "count",
    "fleet_trace_events",
    "load_record",
    "load_trace",
    "maybe_jax_trace",
    "sim_trace_events",
    "span",
    "validate_trace",
    "write_chrome_trace",
]
