"""Unified observability subsystem (ISSUE 9): ONE metrics surface,
request-scoped tracing, and a crash flight recorder across the
serving, decode, and distributed stacks.

Reference contrast: the reference framework ships a first-class
profiler layer (platform/profiler.h RecordEvent + CUPTI DeviceTracer
+ tools/timeline.py chrome-trace merge) but no metrics registry or
post-mortem recorder; production operation of a "millions of users"
stack needs all three (docs/OBSERVABILITY.md).

  metrics.py          process-wide registry of typed labeled
                      instruments (Counter/Gauge/Histogram, bounded
                      label cardinality, prometheus text + one-JSON-
                      line snapshot)
  tracing.py          structured spans with trace-id propagation
                      (serving request -> admission -> batch ->
                      replica -> delivery; RPC envelope carries the id
                      to pserver handler spans), chrome-trace export
                      merged by tools/timeline.py; default-off typed
                      flag ``tracing`` with a one-conditional disabled
                      cost
  flight_recorder.py  bounded lock-free ring of recent structured
                      events dumped to a file on crash /
                      BarrierTimeoutError / replica death / request
  export.py           /metrics + /varz (+ /fleetz) HTTP endpoint
                      mountable on listen_and_serv, InferenceServer,
                      DecodeServer; in-tree prometheus grammar checker
                      (incl. OpenMetrics exemplar syntax)
  collector.py        fleet collector (ISSUE 12): cross-process
                      aggregation of snapshots/spans/dump refs with
                      chaos-tested exactly-once push loss handling,
                      one-store trace assembly, staleness marking,
                      and the fleet SLO roll-up

  step_record.py      the always-on step record: a bounded ring of
                      per-call stamps from CompiledProgram._run and
                      DeviceFeeder (where exe.run's host time goes),
                      the same phases as profiler annotations
  step_stats.py       the always-on stat ring: what a compiled step
                      says of itself (layers.step_stat), one row a
                      step in the program's own state, read by order
                      beside the step record

``paddle_tpu/profiler.py`` (the Fluid-shaped start_profiler/
stop_profiler/RecordEvent surface) is a thin shim over tracing.py.
"""

from paddle_tpu.observability import collector
from paddle_tpu.observability import device_trace
from paddle_tpu.observability import flight_recorder
from paddle_tpu.observability import metrics
from paddle_tpu.observability import slo
from paddle_tpu.observability import step_record
from paddle_tpu.observability import step_stats
from paddle_tpu.observability import tracing
from paddle_tpu.observability.collector import (CollectorPusher,
                                                CollectorServer)
from paddle_tpu.observability.device_trace import DeviceTraceSession
from paddle_tpu.observability.export import (MetricsHTTPServer,
                                             metrics_port_from_env,
                                             parse_prometheus_text)
from paddle_tpu.observability.flight_recorder import FlightRecorder
from paddle_tpu.observability.metrics import (Counter, Gauge,
                                              Histogram,
                                              MetricsRegistry,
                                              registry)
from paddle_tpu.observability.slo import SLO, SLOMonitor
from paddle_tpu.observability.tracing import (Span, Tracer,
                                              maybe_tracer,
                                              start_tracing,
                                              stop_tracing)

__all__ = [
    "CollectorPusher", "CollectorServer", "Counter",
    "DeviceTraceSession", "FlightRecorder", "Gauge",
    "Histogram", "MetricsHTTPServer", "MetricsRegistry", "SLO",
    "SLOMonitor", "Span", "Tracer", "collector", "device_trace",
    "flight_recorder", "maybe_tracer", "metrics",
    "metrics_port_from_env", "parse_prometheus_text", "registry",
    "slo", "start_tracing", "step_record", "stop_tracing", "tracing",
]
