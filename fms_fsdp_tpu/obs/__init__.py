"""Unified observability: metrics registry, step-phase timing,
goodput/MFU accounting, pluggable sinks, and a pollable heartbeat.

The training loop historically reported a fixed print-set plus an
optional wandb/aim tracker; the resilience layer (PR 1) added
skipped-step and watchdog signals with nowhere structured to land. This
package gives every run a machine-readable record (docs/observability.md):

- :class:`~fms_fsdp_tpu.obs.registry.MetricRegistry` — counters, gauges,
  EWMAs, and windowed histograms that are cheap on the hot path (a float
  add / deque append; no host sync, no IO) and only materialize at
  report cadence;
- :class:`~fms_fsdp_tpu.obs.timing.PhaseTimer` — splits host wall time
  into data-wait / compute / checkpoint / other;
- :class:`~fms_fsdp_tpu.obs.timing.GoodputTracker` — goodput =
  productive-step time / wall time, folding in resilience skipped steps;
- sinks (:mod:`~fms_fsdp_tpu.obs.sinks`) — schema-versioned JSONL, CSV
  summary, and an adapter wrapping the legacy wandb/aim tracker so
  ``get_tracker`` becomes one sink among several;
- :func:`~fms_fsdp_tpu.obs.spans.span` — the serving engine's host
  spans (``serve/*``), written into a ``jax.profiler`` session when one
  runs and costing one flag check when none does;
- :class:`~fms_fsdp_tpu.obs.observer.Observer` — the facade the train
  loops drive; built from config by
  :func:`~fms_fsdp_tpu.obs.observer.build_observer`.

Everything is CPU-testable (tests/test_obs.py) and adds no device work:
the only inputs are host timestamps and the metric scalars the loop
already fetched once per report interval.
"""

from fms_fsdp_tpu.obs.observer import Observer, build_observer
from fms_fsdp_tpu.obs.registry import MetricRegistry
from fms_fsdp_tpu.obs.schema import (
    SCHEMA_VERSION,
    schema_digest,
    validate_record,
)
from fms_fsdp_tpu.obs.sinks import (
    CSVSink,
    Heartbeat,
    JSONLSink,
    TrackerSink,
)
from fms_fsdp_tpu.obs.spans import span
from fms_fsdp_tpu.obs.timing import GoodputTracker, PhaseTimer

__all__ = [
    "Observer",
    "build_observer",
    "MetricRegistry",
    "SCHEMA_VERSION",
    "schema_digest",
    "validate_record",
    "JSONLSink",
    "CSVSink",
    "TrackerSink",
    "Heartbeat",
    "PhaseTimer",
    "GoodputTracker",
    "span",
]
