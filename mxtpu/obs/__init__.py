"""mxtpu.obs — fleet-wide observability (ISSUE 14).

Three planes, one package:

* **Metrics** (:mod:`mxtpu.obs.metrics`): the process-wide
  :data:`REGISTRY` of Counter/Gauge/Histogram instruments with bounded
  label cardinality and lock-cheap hot-path increments. Every
  pre-existing ``stats()`` dict either reads its values back from
  registry instruments or registers as a polled view, so
  ``Registry.snapshot()`` is the one JSON any policy process can poll
  — the sensor contract the ROADMAP-3 autoscaling controller builds
  on. The metric catalog is ``docs/observability.md``; the mxlint
  ``metrics-drift`` pass keeps code and catalog identical.
* **Traces** (:mod:`mxtpu.obs.trace`): sampled cross-process spans
  (``MXTPU_TRACE_SAMPLE``) — a trace id rides the pickle-5 frames of
  the kvstore and serving wires, each hop records chrome-trace spans
  into :mod:`mxtpu.profiler`, and :func:`merge_traces` stitches the
  per-process dumps (``MXTPU_TRACE_DIR``) into ONE chrome://tracing
  timeline spanning worker + PS + backup + serving replica.
* **Telemetry** (:mod:`mxtpu.obs.telemetry`): the ``metrics`` wire op
  (ParameterServer, ModelServer, and the worker-side
  :class:`TelemetryExporter`), the aggregator that polls the fleet
  into ``fleet.json`` + ring-buffer history (``tools/launch.py
  --telemetry``), and ``tools/mxtop.py`` rendering it live.

Observability is strictly passive: metrics polls and trace metadata
never influence training or serving results — pinned by the
fault-matrix rows in ``tests/test_observability.py`` and the overhead
contract in ``ci/check_observability.py`` (zero retraces, zero
training-thread host syncs, <= 3% steps/s with telemetry + sampled
tracing on).
"""
from __future__ import annotations

from .metrics import (REGISTRY, Counter, Gauge, Histogram,  # noqa: F401
                      Registry, counter, gauge, histogram, view,
                      max_series)
from .trace import (Sampler, active_ctx, adopt, device_run,  # noqa: F401
                    dump_process_trace, merge_traces, sample_rate, span,
                    start_trace, end_trace, trace_dir, wire_ctx)
from .telemetry import (TelemetryAggregator, TelemetryExporter,  # noqa: F401
                        ensure_exporter, telemetry_enabled,
                        telemetry_dir)

__all__ = [
    "REGISTRY", "Registry", "Counter", "Gauge", "Histogram",
    "counter", "gauge", "histogram", "view", "max_series",
    "Sampler", "span", "device_run", "adopt", "active_ctx", "wire_ctx",
    "start_trace", "end_trace", "sample_rate", "trace_dir",
    "dump_process_trace", "merge_traces",
    "TelemetryExporter", "TelemetryAggregator", "ensure_exporter",
    "telemetry_enabled", "telemetry_dir",
]
