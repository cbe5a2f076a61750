"""Fleet telemetry: in-scan device metrics, host traces, event streams.

Three layers, one per kind of blindness the fleet pipeline had:

  metrics.py  `MetricsSpec`-gated FleetMetrics computed *inside* the
              jit'd episode scan (shortlist hit-rate, chosen-vs-oracle
              rank, EWMA labels, budget counters) — per-step [E, F]
              device outputs, zero cost when off
  trace.py    the step's stage names (`STAGES`, `stage` named scopes,
              read from a device trace; `SUBSTAGES` inside `backbone`)
              and host spans (build / compile / steady-state /
              bench-leg phases) that always reach the
              profiler's trace, optionally recorded as Chrome trace JSON
  events.py   FleetResult -> chunked JSONL event stream with per-camera
              health summaries (`serve --fleet N --telemetry PATH|-`)

This package never imports repro.fleet at module scope (the runner
imports metrics into the scan body), so it stays import-cycle-free and
usable from any layer.
"""
from repro.obs.metrics import (
    METRIC_KEYS,
    MetricsSpec,
    median_valid_rank,
    step_metrics,
    summarize_metrics,
)
from repro.obs.trace import (
    STAGES,
    SUBSTAGES,
    Tracer,
    activate,
    active_tracer,
    deactivate,
    span,
    stage,
    staged,
    substage,
    tracing,
)
from repro.obs.events import (
    SCHEMA_VERSION,
    episode_events,
    read_events,
    validate_event,
    write_events,
)

__all__ = [
    "METRIC_KEYS",
    "MetricsSpec",
    "median_valid_rank",
    "step_metrics",
    "summarize_metrics",
    "STAGES",
    "SUBSTAGES",
    "Tracer",
    "activate",
    "active_tracer",
    "deactivate",
    "span",
    "stage",
    "staged",
    "substage",
    "tracing",
    "SCHEMA_VERSION",
    "episode_events",
    "read_events",
    "validate_event",
    "write_events",
]
