"""Cross-host telemetry: metrics registry, step-event log, HLO collective
audit, and trace spans — the first layer that sees every rank every step.

The reference stack's visibility came from Chainer's ``Reporter`` +
``LogReport`` extensions plus external nvprof (SURVEY §5.1).  Here the
telemetry is library-native and SPMD-aware:

* :class:`Reporter` — scalars/counters/histograms per process,
  :meth:`Reporter.aggregate` merging across hosts through the
  communicator's object plane (mean/sum/max on rank 0, off-TPU safe).
* :class:`StepRecorder` — structured JSONL step-event log with atomic
  append, rotation, crash-safe partial-line recovery, compile events
  (drained from the start-up ledger) and device-memory stats.
* :mod:`startup` — the start-up ledger, the program's one
  ``jax.monitoring`` bridge: import, boundary marks, the first calls,
  :func:`startup.phase` for an entry point's own stretches, every
  compile stage by program name with the cache's answer
  (:func:`startup.summary`).
* :mod:`hlo_audit` — per-collective counts and per-mesh-axis operand
  bytes of any traced step fn (the generalized bench census).
* :mod:`spans` — the scope vocabulary: :func:`named_scope` for traced
  code (device time by scope), :func:`annotate` / :func:`span` for host
  regions on the profiler's clock (``span`` also publishes the host-side
  duration to the Reporter and the JSONL log).
* :mod:`device_trace` — the reader of a profiler capture: device time
  by step phase and kernel region, idle gaps by host span
  (:func:`device_trace.capture` around a few steps).
* :mod:`tracing` — cross-replica request tracing for the serving tier:
  :class:`SpanCtx` contexts over the cluster wire, a crash-surviving
  :class:`FlightRecorder`, Chrome-trace export, per-stage percentiles,
  SLO burn-rate gauges and a straggler detector.

Summarize/export a log with ``python -m chainermn_tpu.tools.obs``
(incl. Prometheus textfile output).  See ``docs/observability.md``.
"""

from chainermn_tpu.observability.reporter import (  # noqa: F401
    Reporter,
    get_reporter,
    merge_summaries,
    report,
    scope,
)
from chainermn_tpu.observability.step_log import (  # noqa: F401
    StepRecorder,
    current_recorder,
    device_memory_stats,
    read_records,
    recover,
    recording,
)
from chainermn_tpu.observability.hlo_audit import (  # noqa: F401
    CollectiveAudit,
    TracedStep,
    audit_allreduce,
    audit_allreduce_tree,
    audit_compiled,
    audit_fn,
    audit_hlo_text,
    audit_jaxpr,
    fold_async_counts,
    trace_step,
)
from chainermn_tpu.observability.exporter import (  # noqa: F401
    MetricsExporter,
)
from chainermn_tpu.observability.anomaly import (  # noqa: F401
    AnomalyDetector,
)
from chainermn_tpu.observability import device_trace  # noqa: F401
from chainermn_tpu.observability import startup  # noqa: F401
from chainermn_tpu.observability.spans import (  # noqa: F401
    annotate,
    named_scope,
    span,
    telemetry_active,
)
from chainermn_tpu.observability.tracing import (  # noqa: F401
    FlightRecorder,
    SLOConfig,
    SpanCtx,
    Tracer,
    detect_stragglers,
    get_tracer,
    read_flight,
    read_flight_dir,
    stage_percentiles,
    stitch,
    to_chrome_trace,
    trace_scope,
    tracing_active,
    validate_trace,
)
