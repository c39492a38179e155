"""Telemetry — the unified observability subsystem (docs/OBSERVABILITY.md).

One event stream for everything the runtime observes: the trainer's step
metrics, the data loader's io_retry events, the resilience runtime's
skip/rollback/preempt events and the policy engine's decisions all flow
through one schema-versioned :class:`EventBus` with a monotonic sequence
number, fan out to pluggable exporters (JSONL file, Prometheus textfile,
in-memory ring buffer), and are reconstructed offline by the report CLI
(``python -m gaussiank_sgd_tpu.telemetry report run.jsonl``).

The on-device half (compressed bytes sent, achieved density, EF-residual
norm, per-bucket selection counts) is fused into the jitted step in
parallel/trainstep.py and lands here as fields of the ``train`` event.

Import layout: this package is pure stdlib (no jax) EXCEPT
:mod:`.profiler`, which wraps ``jax.profiler`` and is imported lazily by
its users — so the report/validate CLI runs without initializing a
backend, like the linter.
"""

from .bus import EventBus
from .events import SCHEMA_VERSION, validate_record, validate_stream
from .exporters import (Exporter, JSONLExporter, MemoryExporter,
                        PrometheusTextfileExporter)
from .health import (HealthMonitor, HealthPolicy, HealthServer,
                     replay_health)
from .throughput import ThroughputSignals, ThroughputTracker
from .tracing import TraceContext, build_chrome_trace, recorded

__all__ = [
    "EventBus",
    "Exporter",
    "HealthMonitor",
    "HealthPolicy",
    "HealthServer",
    "JSONLExporter",
    "MemoryExporter",
    "PrometheusTextfileExporter",
    "SCHEMA_VERSION",
    "ThroughputSignals",
    "ThroughputTracker",
    "TraceContext",
    "build_chrome_trace",
    "recorded",
    "replay_health",
    "validate_record",
    "validate_stream",
]
