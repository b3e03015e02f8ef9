"""Tracing / profiling utilities (SURVEY.md §5).

The reference has two profiling mechanisms: the AutoCacheRule sampling
profiler (wall-clock + memory per node, AutoCacheRule.scala:153-465) and
ad-hoc per-phase nanosecond logs inside solvers (KernelRidgeRegression.scala:
213-221). The TPU equivalents here:

  - ``PhaseTimer`` — named phase accumulation with a log summary, used by the
    iterative solvers for per-phase breakdowns.
  - ``trace`` — context manager around ``jax.profiler`` emitting a TensorBoard
    trace directory (XLA device timelines), the deep-dive tool.
  - ``follow_profiler`` — the bridge between a jax profile and the
    program's own tracer (:mod:`keystone_tpu.obs`): while a profile is
    being taken the program's spans are recorded and written into it.
  - ``CompileClock`` / ``compile_ledger`` — what JAX traced, lowered and
    compiled (or fetched), from ``jax.monitoring``: counts for a caller's
    block, ``jax.compile`` spans for the active tracer.
  - ``compiled_cost`` — static cost extraction from a jitted function's
    compiled XLA executable (FLOPs / bytes accessed), the analog of the
    reference's analytic ``CostModel`` inputs but read from the compiler
    instead of hand-derived.
  - ``prefetch_overlap_fraction`` — the achieved ingestion-overlap share
    of a prefetched streamed fit, from its
    :class:`~keystone_tpu.data.prefetch.PrefetchStats`.
  - ``RequestSpan`` / ``SpanLog`` — per-request serving spans (queue wait /
    pad fraction / execution time) recorded by the online micro-batcher
    (:mod:`keystone_tpu.serving.batcher`), bounded so a long-lived server
    never grows its profiling state without limit.
"""

from __future__ import annotations

import contextlib
import logging
import threading
import time
from collections import OrderedDict, deque
from dataclasses import dataclass
from typing import Any, Dict, Iterator, List, Optional, Sequence

import jax

from keystone_tpu.obs import tracer as _tracer

logger = logging.getLogger("keystone_tpu.profiling")

# The annotation factory a :class:`~keystone_tpu.obs.Tracer` takes so that
# its spans are written into a jax profile (the tracer itself imports no jax).
TraceAnnotation = jax.profiler.TraceAnnotation


class PhaseTimer:
    """Accumulate wall-clock per named phase.

    >>> t = PhaseTimer("krr")
    >>> with t.phase("kernel_gen"):
    ...     do_work()
    >>> t.log_summary()
    """

    def __init__(self, name: str = ""):
        self.name = name
        self.totals: "OrderedDict[str, float]" = OrderedDict()
        self.counts: Dict[str, int] = {}

    @contextlib.contextmanager
    def phase(self, phase_name: str):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            dt = time.perf_counter() - t0
            self.totals[phase_name] = self.totals.get(phase_name, 0.0) + dt
            self.counts[phase_name] = self.counts.get(phase_name, 0) + 1

    def total(self, phase_name: str) -> float:
        return self.totals.get(phase_name, 0.0)

    def summary(self) -> str:
        parts = [
            f"{k}={v:.3f}s/{self.counts[k]}x" for k, v in self.totals.items()
        ]
        prefix = f"{self.name}: " if self.name else ""
        return prefix + ", ".join(parts) if parts else prefix + "(no phases)"

    def log_summary(self, level: int = logging.INFO) -> None:
        logger.log(level, "%s", self.summary())


def prefetch_overlap_fraction(stats) -> Optional[float]:
    """Achieved ingestion-overlap fraction of one prefetched streamed fit.

    ``stats`` is the :class:`~keystone_tpu.data.prefetch.PrefetchStats` the
    fit's Prefetcher filled: ``load_s`` is total time inside
    ``source.load`` (reader thread — disk + staging copies), ``wait_s`` is
    total time the CONSUMER blocked on the queue (latency the prefetch
    failed to hide). The hidden share is

        (load_s − wait_s) / load_s        clamped to [0, 1]

    — 1.0 means every second of disk→host ingestion ran behind device
    compute; 0.0 means fully serial (every load was waited on). Unlike the
    bench's two-leg A/B (``(wall_off − wall_on) / load_s``), this needs
    ONE run, so any streamed fit can report it (pass ``prefetch_stats`` to
    ``streaming_bcd_fit_segments`` / ``run_lbfgs_gram_streamed``). Returns
    None when no load time was recorded; a serial ``prefetch_depth=0``
    pass (``stats.prefetched`` False — loads ran inline on the consumer,
    nothing overlapped) reports 0.0.
    """
    load_s = float(getattr(stats, "load_s", 0.0) or 0.0)
    if load_s <= 0.0:
        return None
    if not getattr(stats, "prefetched", False):
        return 0.0
    wait_s = float(getattr(stats, "wait_s", 0.0) or 0.0)
    return min(max((load_s - wait_s) / load_s, 0.0), 1.0)


def overlap_report(stats) -> Dict[str, Dict[str, Optional[float]]]:
    """Per-SITE overlap report of one streamed fit (ISSUE 8 satellite):
    the per-phase form of :func:`prefetch_overlap_fraction`, built from
    the ``site_busy_s`` / ``site_wait_s`` accounting the data-plane
    runtime's consumers fill in one
    :class:`~keystone_tpu.data.prefetch.PrefetchStats`:

      - ``read`` — segment loads on the runtime's ``read`` worker
        (busy) vs consumer queue waits (wait);
      - ``verify`` — the shard layer's CRC pass (rides inside read's
        wall, attributed via ``faults.observe_busy``);
      - ``checkpoint`` — write-behind snapshot writes (busy, worker
        side) vs the fold-blocking sync+submit share (wait);
      - ``decode`` / ``augment`` — the image tier's per-segment decode
        and seeded augmentation (ride inside the read lane's wall,
        attributed via ``faults.observe_busy`` from
        ``EncodedImageSource.load`` — ISSUE 18);
      - ``compute`` — the consumer's transfer + fold dispatch + device
        throttle, the denominator phase everything else hides behind.

    Per site: ``busy_s`` (wall the phase worked), ``wait_s`` (wall the
    CONSUMER blocked on it), ``hidden_s = max(busy − wait, 0)`` and
    ``overlap = hidden/busy`` (None when the site did no work) — 1.0
    means the phase ran entirely behind compute, 0.0 fully serial. A
    serial ``prefetch_depth=0`` leg records busy == wait for ``read``,
    so the oracle path reads 0 overlap by construction. This is what
    makes a fold-floor claim (the Amazon 131.4 s) auditable per phase:
    wall − compute.busy must be accounted for by the visible waits.

    Reads the ``MetricsRegistry`` a real :class:`~keystone_tpu.data.
    prefetch.PrefetchStats` carries (ISSUE 9 — the registry is the
    single store); plain objects exposing ``site_busy_s``/``site_wait_s``
    dicts still work through a deprecated attribute shim."""
    busy, wait = _site_dicts(stats)
    report: Dict[str, Dict[str, Optional[float]]] = {}
    for site in sorted(set(busy) | set(wait)):
        b = float(busy.get(site, 0.0))
        w = float(wait.get(site, 0.0))
        hidden = max(b - w, 0.0)
        report[site] = {
            "busy_s": b,
            "wait_s": w,
            "hidden_s": hidden,
            "overlap": (min(hidden / b, 1.0) if b > 0.0 else None),
        }
    return report


def _site_dicts(stats):
    """(busy, wait) per-site dicts: from the stats object's
    ``MetricsRegistry`` when it carries one (the PrefetchStats form —
    the single store), else the deprecated bare-attribute shim for
    plain objects (kept so pre-registry callers and tests keep
    working)."""
    reg = getattr(stats, "registry", None)
    if reg is not None and hasattr(reg, "values_by_label"):
        from keystone_tpu.obs.metrics import (
            METRIC_SITE_BUSY_S,
            METRIC_SITE_WAIT_S,
        )

        return (
            reg.values_by_label(METRIC_SITE_BUSY_S, "site"),
            reg.values_by_label(METRIC_SITE_WAIT_S, "site"),
        )
    _warn_legacy_stats("overlap_report")
    return (
        dict(getattr(stats, "site_busy_s", {}) or {}),
        dict(getattr(stats, "site_wait_s", {}) or {}),
    )


def _warn_legacy_stats(fn_name: str) -> None:
    import warnings

    warnings.warn(
        f"{fn_name}: reading bare stats attributes is deprecated — pass "
        "a PrefetchStats (whose MetricsRegistry is the single metrics "
        "store, keystone_tpu/obs) instead of a plain object",
        DeprecationWarning, stacklevel=3,
    )


def prefetch_retry_counters(stats) -> Dict[str, float]:
    """Reliability accounting of one streamed fit's ingestion
    (docs/reliability.md): how many transient read failures the retry
    layer absorbed (``retries``) and the backoff wall it paid for them
    (``backoff_s``), from the fit's
    :class:`~keystone_tpu.data.prefetch.PrefetchStats`. Zero/zero on a
    healthy run — the steady-state cost of the retry layer is nothing
    but the counters themselves. Nonzero values mean the fit SUCCEEDED
    over flaky IO; alert on them before they become exhaustions.

    Reads the stats object's ``MetricsRegistry`` when it carries one
    (ISSUE 9); bare attributes remain as a deprecated shim."""
    reg = getattr(stats, "registry", None)
    if reg is not None and hasattr(reg, "snapshot"):
        from keystone_tpu.obs.metrics import (
            METRIC_PREFETCH_BACKOFF_S,
            METRIC_PREFETCH_RETRIES,
        )

        snap = reg.snapshot()
        return {
            "retries": int(snap.get(METRIC_PREFETCH_RETRIES, 0) or 0),
            "backoff_s": float(
                snap.get(METRIC_PREFETCH_BACKOFF_S, 0.0) or 0.0
            ),
        }
    _warn_legacy_stats("prefetch_retry_counters")
    return {
        "retries": int(getattr(stats, "retries", 0) or 0),
        "backoff_s": float(getattr(stats, "backoff_s", 0.0) or 0.0),
    }


@dataclass(frozen=True)
class RequestSpan:
    """Where one served request's latency went (the serving analog of a
    PhaseTimer breakdown): ``queue_wait_s`` is time spent queued before
    its batch dispatched, ``exec_s`` the batch's execution wall (shared
    by every request coalesced into it), ``batch_size`` the real
    requests in the batch, ``bucket`` the padded shape it ran at, and
    ``pad_fraction`` the share of bucket rows that were padding — the
    amortization price the micro-batcher paid for a warm compile-cache
    hit."""

    queue_wait_s: float
    exec_s: float
    batch_size: int
    bucket: int
    pad_fraction: float
    # Which replica of a replicated serving plane executed the batch
    # (None on a standalone MicroBatchServer) — per-replica span
    # attribution for serving/replicas.py's aggregate stats.
    replica: Optional[int] = None


class SpanLog:
    """Bounded, thread-safe log of :class:`RequestSpan` records.

    The micro-batcher records one span per request from its worker
    thread while ``stats()`` readers snapshot from submitter threads;
    the lock keeps the snapshot consistent and ``maxlen`` bounds a
    long-lived server's profiling memory."""

    def __init__(self, maxlen: int = 4096):
        self._spans: "deque[RequestSpan]" = deque(maxlen=maxlen)
        self._lock = threading.Lock()

    def record(self, span: RequestSpan) -> None:
        with self._lock:
            self._spans.append(span)

    def snapshot(self) -> List[RequestSpan]:
        with self._lock:
            return list(self._spans)

    def __len__(self) -> int:
        with self._lock:
            return len(self._spans)

    def summary(self) -> Dict[str, float]:
        """Mean queue wait / exec / pad fraction over the retained window
        (empty dict when nothing has been served)."""
        return summarize_spans(self.snapshot())


def summarize_spans(spans: Sequence["RequestSpan"]) -> Dict[str, float]:
    """The one summary shape for a span collection (SpanLog.summary, the
    per-replica blocks, and callers holding an already-snapshotted list
    — no second ring copy). Empty dict for no spans — EXPLICITLY: the
    empty case is a contract, not a numpy mean-of-empty-slice warning
    (ISSUE 9 satellite). Non-finite span fields raise ValueError naming
    the field: a NaN queue wait silently poisons every mean downstream,
    and numpy would only warn."""
    spans = list(spans)
    if not spans:
        return {}
    n = float(len(spans))
    sums = {"mean_queue_wait_s": 0.0, "mean_exec_s": 0.0,
            "mean_batch_size": 0.0, "mean_pad_fraction": 0.0}
    for i, s in enumerate(spans):
        for key, v in (
            ("mean_queue_wait_s", s.queue_wait_s),
            ("mean_exec_s", s.exec_s),
            ("mean_batch_size", s.batch_size),
            ("mean_pad_fraction", s.pad_fraction),
        ):
            v = float(v)
            if v != v or v in (float("inf"), float("-inf")):
                raise ValueError(
                    f"summarize_spans: span {i} has non-finite "
                    f"{key.replace('mean_', '')} ({v}) — refusing to "
                    "fold it into the means"
                )
            sums[key] += v
    return {"num_spans": len(spans),
            **{k: v / n for k, v in sums.items()}}


def latency_percentiles(
    latencies_s: Sequence[float], qs: Sequence[float] = (50.0, 99.0)
) -> Optional[Dict[str, float]]:
    """p-th percentile latencies in SECONDS keyed ``p50``/``p99``/...;
    None for an empty sample (a server that has completed nothing has no
    percentiles — callers must not report zeros as measurements).

    Edge cases are explicit contracts, not numpy warnings (ISSUE 9
    satellite): a single sample IS every percentile (p50 == p99 ==
    the sample — documented, tested); an out-of-range ``q`` raises
    ValueError naming it (numpy's own message names neither the value
    nor the caller); a NaN/inf sample raises ValueError instead of
    propagating NaN percentiles under a RuntimeWarning; an empty ``qs``
    raises rather than returning a vacuous ``{}`` that reads as "no
    latency problem". Accepts any iterable (a generator no longer
    TypeErrors on ``len``)."""
    import math

    import numpy as np

    samples = [float(v) for v in latencies_s]
    if not samples:
        return None
    qs = list(qs)
    if not qs:
        raise ValueError(
            "latency_percentiles: qs is empty — an empty percentile "
            "request is a caller bug, not a measurement"
        )
    for q in qs:
        if not 0.0 <= float(q) <= 100.0:
            raise ValueError(
                f"latency_percentiles: q={q!r} outside [0, 100]"
            )
    bad = [v for v in samples if not math.isfinite(v)]
    if bad:
        raise ValueError(
            f"latency_percentiles: {len(bad)} non-finite sample(s) "
            f"(first: {bad[0]!r}) — percentiles over NaN/inf are not "
            "measurements"
        )
    arr = np.asarray(samples, dtype=np.float64)
    return {f"p{int(q) if float(q).is_integer() else q}": float(v)
            for q, v in zip(qs, np.percentile(arr, list(qs)))}


@contextlib.contextmanager
def trace(log_dir: str):
    """Emit a jax.profiler trace (TensorBoard 'profile' plugin format) for
    everything run inside the context. No-op if the profiler cannot start
    (e.g. a second concurrent trace).

    This is the XLA device-timeline leg of the obs plane (ISSUE 9
    satellite — previously orphaned): ``obs.tracing(dir,
    xla_profile=True)`` wraps the traced block in it, writing under
    ``dir/xla`` beside the Perfetto span trace, and hands its tracer
    :data:`TraceAnnotation`, so the program's spans are ``ks.*`` events
    in this profile too: ONE activation, one clock."""
    started = False
    try:
        jax.profiler.start_trace(log_dir)
        started = True
    except Exception as e:  # pragma: no cover - depends on runtime state
        logger.warning("profiler trace unavailable: %s", e)
    try:
        yield
    finally:
        if started:
            jax.profiler.stop_trace()


def follow_profiler() -> None:
    """Make the program's tracing follow a jax profile; called at the
    public fit entries (``Pipeline.fit``, ``pipelines/timit.py``) and at
    the eager scoring entry (``FittedPipeline.apply``).

    A profile is being taken and no tracer is active: activate an
    in-memory one whose spans are also ``ks.<name>`` annotations in the
    profile (under whatever annotation the caller holds open, on the
    clock of the device lines), and note where the profile is written.
    No profile and the active tracer is one this function started:
    deactivate it and take the device's account of the profile it
    followed (``Tracer.device_account``); ``obs.last_session()`` keeps
    it readable. A tracer of ``obs.tracing`` / ``KEYSTONE_TRACE`` is
    never touched. With no profile and no tracer this is two reads."""
    if TraceAnnotation.is_enabled():
        if not _tracer.enabled():
            session = _tracer.start_session(TraceAnnotation)
            if session is not None:
                session.profile_dir = _profile_dir()
    elif _tracer.enabled():
        session = _tracer.end_session()
        if session is not None:
            _take_device_account(session)


def _profile_dir() -> Optional[str]:
    """Where the running jax profile is written. JAX keeps it in a
    private place; where that has moved there is no account, never an
    error."""
    try:
        from jax._src import profiler as _jax_profiler

        return _jax_profiler._profile_state.log_dir
    except Exception as e:  # pragma: no cover - depends on the jax version
        logger.info("the running profile's directory cannot be read (%s): "
                    "no device account will be taken", e)
        return None


def _take_device_account(session) -> None:
    """The account of the profile ``session`` followed, once, when the
    session ends (``obs.device`` is imported here and nowhere earlier).

    Taken here and not on the first read of ``Tracer.device_account``: the
    profile is the caller's to remove once it has stopped (the benchmark's
    harness removes it before any reader runs), and the newest
    ``.xplane.pb`` under the directory is this session's only now. The fit
    or the apply that finds the profile over pays the read — about 10 us
    an operation event of the profile, seconds for a long one."""
    if session.profile_dir is None:
        logger.info("the ended session knows no profile directory: no device account")
        return
    try:
        from keystone_tpu.obs import device

        session.device_account = device.device_account(
            session.profile_dir, session.spans())
    except Exception:  # a reader's fault must not reach the fit or the apply
        logger.warning("no device account of the profile under %s",
                       session.profile_dir, exc_info=True)


class CompileClock:
    """What JAX compiled, from ``jax.monitoring``: programs handed to the
    backend compiler (persistent-cache look-ups included) and the seconds
    that took, cumulative, with :meth:`measure` for one block's share — so
    a phase driven through a public entry point still reports compilation
    apart from the rest.

    ``record_spans`` (the process's ledger, :func:`compile_ledger`): each
    trace, lowering and compile-or-fetch that JAX reports is also a
    ``jax.compile`` span (``stage=trace|lower|backend``, ``fun`` the
    program's name) of the active tracer, from ``now - duration`` to
    ``now``, under the innermost span open on the calling thread: the
    node or solver phase that caused it."""

    _STAGES = {
        "/jax/core/compile/jaxpr_trace_duration": "trace",
        "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
        "/jax/core/compile/backend_compile_duration": "backend",
    }
    _HIT = "/jax/compilation_cache/cache_hits"
    _MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self, record_spans: bool = False) -> None:
        self.record_spans = record_spans
        self.compile_s = 0.0
        self.programs = 0
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **kwargs: Any) -> None:
        stage = self._STAGES.get(event)
        if stage is None:
            return
        if stage == "backend":
            self.compile_s += duration
            self.programs += 1
        tracer = _tracer.active_tracer() if self.record_spans else None
        if tracer is not None:
            now = time.perf_counter()
            tracer.add_span("jax.compile", now - duration, now, stage=stage,
                            fun=kwargs.get("fun_name"))

    def _event(self, event: str, **_: Any) -> None:
        if event == self._HIT:
            self.cache_hits += 1
        elif event == self._MISS:
            self.cache_misses += 1

    @contextlib.contextmanager
    def measure(self) -> Iterator[Dict[str, Any]]:
        """Yield a dict that is filled in on exit with this block's wall
        and its compile share."""
        out: Dict[str, Any] = {}
        before = (self.compile_s, self.programs, self.cache_hits,
                  self.cache_misses)
        t0 = time.perf_counter()
        try:
            yield out
        finally:
            wall = time.perf_counter() - t0
            compile_s = self.compile_s - before[0]
            out.update({
                "wall_s_smoke": round(wall, 3),
                "compile_s_smoke": round(compile_s, 3),
                "non_compile_s_smoke": round(max(wall - compile_s, 0.0), 3),
                "programs_compiled": self.programs - before[1],
                "persistent_cache_hits": self.cache_hits - before[2],
                "persistent_cache_misses": self.cache_misses - before[3],
            })


_LEDGER: Optional[CompileClock] = None
_LEDGER_LOCK = threading.Lock()


def compile_ledger() -> CompileClock:
    """The process's compile ledger, built — and its listeners registered —
    at the first call: the first activation of a tracer, or a caller that
    wants the counts (``chip_smoke.py``). Never before."""
    global _LEDGER
    with _LEDGER_LOCK:
        if _LEDGER is None:
            _LEDGER = CompileClock(record_spans=True)
        return _LEDGER


def compiled_cost(fn, *args, **kwargs) -> Optional[Dict[str, Any]]:
    """FLOPs / memory-traffic estimates for ``jax.jit(fn)(*args)`` from XLA's
    cost analysis of the compiled executable.

    Returns {"flops": float, "bytes accessed": float, ...} (keys as XLA
    reports them) or None when the backend doesn't support cost analysis.
    """
    try:
        lowered = jax.jit(fn).lower(*args, **kwargs)
        analysis = lowered.compile().cost_analysis()
    except Exception as e:  # pragma: no cover - backend-specific
        logger.warning("cost analysis unavailable: %s", e)
        return None
    if isinstance(analysis, (list, tuple)):
        analysis = analysis[0] if analysis else None
    return dict(analysis) if analysis else None
