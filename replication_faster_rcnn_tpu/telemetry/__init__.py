"""Zero-dependency observability layer.

Four pillars, each usable on its own:

- :mod:`.spans` — host-side span tracer emitting Chrome-trace JSON
  (``chrome://tracing`` / Perfetto loadable) so feed-vs-compute time is
  directly visible per pipeline phase.
- :mod:`.health` — on-device train-health metrics (grad/param/update
  norms, update ratio, non-finite counts) folded into the jitted step so
  they ride the existing metrics sync instead of adding one.
- :mod:`.mfu` — model FLOPs utilisation from a step's analytical
  FLOPs, with a measured-matmul CPU peak so MFU is non-null even
  off-TPU; the device record every reported number carries.
- :mod:`.watchdog` — heartbeat daemon that detects a stalled run and
  dumps a diagnostic snapshot (last span, queue depth,
  elapsed-since-progress) instead of leaving a hung process to guess at.
- :mod:`.tracecontext` — W3C-traceparent-style request tracing: trace
  and span ids that propagate across the serving fleet's process hops
  (router → replica HTTP → batcher → engine) so one request's timeline
  is greppable by one id in the merged Chrome trace.
- :mod:`.metrics` — a unified :class:`~.metrics.MetricsRegistry`
  (counters, gauges, fixed-bucket histograms with derived
  p50/p95/p99) that the serving tiers register into; rendered both as
  JSON (``/stats``, ``fleet.jsonl``) and Prometheus text (``/metrics``).
- :mod:`.slo_burn` — multi-window error-budget burn-rate accounting
  feeding the replica ``degraded`` flag and the router's canary
  auto-demote hook.

:mod:`.report` turns a run directory (trace.json + metrics.jsonl +
watchdog.jsonl) into a phase-time and health report; surfaced as the
``telemetry`` CLI subcommand.
"""

from replication_faster_rcnn_tpu.telemetry.metrics import (  # noqa: F401
    MetricsRegistry,
)
from replication_faster_rcnn_tpu.telemetry.slo_burn import (  # noqa: F401
    BurnRateTracker,
)
from replication_faster_rcnn_tpu.telemetry.spans import (  # noqa: F401
    NULL_TRACER,
    SpanTracer,
    current_tracer,
    set_tracer,
)
from replication_faster_rcnn_tpu.telemetry.tracecontext import (  # noqa: F401
    TraceContext,
    bind,
    current_trace,
    new_trace_context,
    parse_traceparent,
)
from replication_faster_rcnn_tpu.telemetry.watchdog import StallWatchdog  # noqa: F401
