"""The unified run-trace + metrics plane (ISSUE 9 tentpole).

Until this package, the evidence for *why* the system did anything lived
in disconnected fragments: ``PhaseTimer`` blocks inside solvers, per-fit
``PrefetchStats``, per-server ``stats()`` dicts, and bench-row ``detail``
blobs — none of them correlated after the fact. This package is the one
causally-linked record:

  - :mod:`~keystone_tpu.obs.tracer` — a process-wide :class:`Tracer`
    with nested, thread-safe spans carrying one ``run_id`` and parent
    links, instrumented at the load-bearing seams (``Pipeline.fit``
    phases, optimizer rules, verifier pre-passes, cost-model decisions,
    fold chunk steps, data-plane runtime lane tasks, prefetch waits,
    checkpoint write-behind, serving requests). The whole plane is a
    **no-op guarded by one branch** when tracing is off — hooks cost one
    global read — and cheap when on (the ``observability_overhead``
    bench row holds the enabled cost to <=2% of the disk-streamed fold).
  - :mod:`~keystone_tpu.obs.metrics` — :class:`MetricsRegistry`
    (counters / gauges / histograms with a flat ``snapshot()``), the
    single store behind ``DataPlaneRuntime.stats()``, the serving
    breaker counters, and ``PrefetchStats`` site accounting. Every
    metric name comes from the parsed ``METRIC_*`` catalogue
    (``tools/lint.py``'s ``metric-name`` rule — dashboards cannot
    silently fork names).
  - :mod:`~keystone_tpu.obs.export` — Chrome-trace/Perfetto JSON
    exporter (one track per thread, counter tracks) plus a compact
    JSONL event log; ``tools/trace.py`` / ``bin/trace`` summarize it.
  - :mod:`~keystone_tpu.obs.flight` — the flight recorder: a bounded
    ring of recent events that chaos/fault paths (worker death, breaker
    opens, shard corruption, watchdog evictions) dump alongside the
    exception, so a postmortem names the spans in flight at death.
  - :mod:`~keystone_tpu.obs.calibrate` — the cost-model calibration
    plane (ISSUE 13): joins every ``cost.decision`` with the measured
    seconds of the work it priced, reports prediction error per engine
    and weight family, flags mis-routes with their regret, refits the
    weight families from production traces
    (``KEYSTONE_COST_WEIGHTS=calibrated:<artifact>``), and gates on
    drift (``bin/calibrate``).

Activation (docs/observability.md): ``KEYSTONE_TRACE=dir`` env knob,
``run.py --trace=dir``, ``with obs.tracing(dir):`` in code, or a jax
profile taken of a running fit (``utils.profiling.follow_profiler``:
the spans then ride the profile, and ``obs.last_session()`` keeps
them). This package imports no jax at module level — the data-plane runtime (which must stay
jax-free) reports into it from its IO workers.
"""

from keystone_tpu.obs.calibrate import (
    calibration_report,
    drift_gate,
    join_decisions,
    load_calibration_artifact,
    refit,
    write_calibration_artifact,
)
from keystone_tpu.obs.export import (
    load_events,
    to_chrome_trace,
    validate_chrome_trace,
    write_trace_dir,
)
from keystone_tpu.obs.flight import (
    FlightRecorder,
    flight_note,
    flight_snapshot,
    render_flight_record,
)
from keystone_tpu.obs.live import LiveExporter, render_prometheus
from keystone_tpu.obs.metrics import (  # noqa: F401 — METRIC_* re-exported
    BucketedHistogram,
    MetricsRegistry,
)
from keystone_tpu.obs.metrics import __all__ as _metrics_all
from keystone_tpu.obs.metrics import *  # noqa: F401,F403 — the catalogue
from keystone_tpu.obs.slo import (
    STATE_BREACH,
    STATE_OK,
    STATE_WARN,
    SLOObjective,
    SLOTracker,
)
from keystone_tpu.obs.tracer import (
    CostDecision,
    CostOutcomeRef,
    Span,
    TailSampler,
    Tracer,
    active_tracer,
    counter_track,
    enabled,
    end_session,
    event,
    last_session,
    record_cost_decision,
    set_on_open,
    span,
    start_session,
    tracing,
    tracing_from_env,
)

__all__ = [
    "CostDecision",
    "CostOutcomeRef",
    "FlightRecorder",
    "LiveExporter",
    "MetricsRegistry",
    "STATE_BREACH",
    "STATE_OK",
    "STATE_WARN",
    "SLOObjective",
    "SLOTracker",
    "Span",
    "TailSampler",
    "Tracer",
    "active_tracer",
    "calibration_report",
    "counter_track",
    "drift_gate",
    "enabled",
    "end_session",
    "event",
    "flight_note",
    "flight_snapshot",
    "join_decisions",
    "last_session",
    "load_calibration_artifact",
    "load_events",
    "record_cost_decision",
    "refit",
    "write_calibration_artifact",
    "render_flight_record",
    "render_prometheus",
    "set_on_open",
    "span",
    "start_session",
    "to_chrome_trace",
    "tracing",
    "tracing_from_env",
    "validate_chrome_trace",
    "write_trace_dir",
] + list(_metrics_all)
