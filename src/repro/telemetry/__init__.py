"""repro.telemetry — fleet-wide observability for the SLED serving stack.

Three pieces:

* a process-local :class:`~repro.telemetry.metrics.MetricsRegistry`
  (counters / gauges / fixed-bucket histograms) with Prometheus-style text
  exposition and a JSON snapshot, fed by cheap host-side monotonic spans;
  each :func:`~repro.telemetry.metrics.span` is also a ``sled.<phase>``
  ``jax.profiler.TraceAnnotation``, so a profiler trace shows the serving
  loop's phases on the device's clock;
* per-round :class:`~repro.telemetry.trace.TraceEvent` records propagated
  across process boundaries (Verdict frames carry the server-timing
  breakdown; codec v3 ``ReplicaStats`` carries a telemetry payload), plus a
  bounded :class:`~repro.telemetry.trace.FlightRecorder` ring dumped on
  replica crash/eviction/drain;
* surfacing: ``repro top`` (live fleet table over the control plane),
  ``repro trace`` (per-round JSONL), and the ``sled.*`` spans in any
  ``jax.profiler.trace`` of a serve.

Telemetry is OFF by default — :func:`enable` is flipped by ``System.build``
when the ServeSpec says so, and instrumented call sites cost a flag check
or two while disabled.  The profiler half of a span does not depend on
that flag: it records whenever a profiler does.  Spans wrap host-side
boundaries only; nothing here runs inside jitted code.
"""

from repro.telemetry.logs import setup_logging
from repro.telemetry.metrics import (
    C_TH_BUCKETS,
    K_BUCKETS,
    LATENCY_BUCKETS_S,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    count,
    enable,
    enabled,
    observe,
    registry,
    span,
)
from repro.telemetry.trace import FlightRecorder, TraceEvent

__all__ = [
    "C_TH_BUCKETS",
    "Counter",
    "FlightRecorder",
    "Gauge",
    "Histogram",
    "K_BUCKETS",
    "LATENCY_BUCKETS_S",
    "MetricsRegistry",
    "TraceEvent",
    "count",
    "enable",
    "enabled",
    "observe",
    "registry",
    "setup_logging",
    "span",
]
