"""Benchmark suite: reference workload geometries, each with a stated
FLOP model, measured device time, achieved TFLOP/s and MFU.

Headline (the printed JSON line): the REAL TIMIT baseline row — n=2,200,000
rows, d=16384 cosine features, BlockLeastSquares — run at full n through
the streaming (out-of-core) fit path and compared against the reference's
literal committed wall-clock (BASELINE.md, scripts/solver-comparisons-
final.csv:26 — TIMIT d=16384 Block on 16x r3.4xlarge Spark: 580,555 ms at
n=2.2e6) with NO n-scaling term. The 72 GB bf16 feature matrix never
exists: features are generated per row tile inside one compiled scan, each
tile folds into the (d, d) Gramian + correlation (parallel/streaming.py),
and the BCD epochs run on the accumulated normal equations.

Additional metrics ride in detail.additional_metrics:

  - timit_resident_262k: the round-1..3 resident-feature headline geometry
    (kept for continuity; exercises the strided in-loop BCD kernels).
  - amazon_sparse_lbfgs_d16384: the csv:13 sparse geometry at n=500k
    resident, through BOTH sparse engines (gather data passes vs the
    fold-G-once gram engine).
  - amazon_fulln_streamed_gram: the REAL n=65e6 Amazon row, streamed
    (chunks never all resident), vs the literal 52.29 s — no n-scaling;
    min-of-N warm (compile reported separately) like the headline.
  - amazon_fulln_resident_compressed: the SAME n=65e6 row through the
    compressed-resident tier (data/resident.py — int16+bf16 at 4 B/nnz,
    ISSUE 8): the first ~28e6 rows fold from chip-RESIDENT compressed
    chunks (no regen/IO at all), the tail streams host->device through
    the data-plane runtime's prefetcher; the one-time encode pass is
    reported separately from the warm fold, and the row carries the
    per-site overlap report (read/verify/compute) that makes the
    131.4 s fold-floor claim auditable per phase. Retires the ad-hoc
    r05 resident-capacity probe.
  - outofcore_prefetch: fit at the TIMIT geometry FROM DISK SHARDS
    through the double-buffered prefetcher (data/prefetch.py), prefetch-on
    vs serial read-then-fold, with the achieved overlap fraction.
  - recovery_overhead: the reliability layer's steady-state price —
    checkpoint-on vs -off wall fraction of the same disk-streamed fit at
    the default snapshot interval (resume bit-identity is pinned by the
    chaos tests; this row prices the insurance).
  - krr_cifar_kernel_geometry: RandomPatchCifarKernel's KRR solver shape
    through the bf16x3 AND f32 kernel engines (no reference timing
    exists; absolute + MFU + cross-engine quality delta).
  - mnist_random_fft_end_to_end: the README example geometry end-to-end,
    with a featurize/solve/executor phase split.
  - autocache_on_chip: measured warm-sweep wall-clocks (no-cache /
    greedy post-fusion / greedy pre-fusion / aggressive, 3 GB budget)
    for a reused fully-fusable featurize chain — greedy must TIE no-cache.
  - autocache_host_boundary: same sweep convention with a fusion-breaking
    host decode stage in the chain — greedy must BEAT no-cache.
  - serving_mnist_open_loop_p99: the exported mnist_random_fft pipeline
    served ONLINE through the deadline-aware micro-batcher
    (keystone_tpu/serving/) under open-loop Poisson load — p50/p99
    latency, achieved QPS and pad overhead at 3 offered rates, A/B
    against naive batch-size-1 serving.
  - serving_replicated_chaos: the replicated serving plane
    (serving/replicas.py) under open-loop Poisson load across three
    legs — steady state, a replica KILL mid-storm (watchdog restart),
    and an atomic hot-swap under sustained load — recording the
    degraded-window p99 against the steady-state p99, with zero-drop
    accounting (offered == completed + rejected + failed) and
    per-fingerprint response attribution on the swap leg.
  - serving_fleet_chaos: the multi-process serving fleet
    (serving/fleet.py) — >= 4 crash-contained plane processes behind
    the FleetRouter's admission front door, >= 8 Poisson tenants at an
    aggregate rate >= 4x one plane's sustainable throughput — steady
    state, a whole-plane SIGKILL mid-storm (watchdog declares it dead,
    fails in-flight loudly, respawns from the shipped plan), and a
    mid-storm canary roll across the surviving fleet; value = the
    degraded-window worst-tenant p99, with EXACT fleet-wide books
    (offered == completed + rejected + failed across the process kill).
  - continuous_learning_staleness: the continuous-learning control plane
    (learning/continuous.py + serving/lifecycle.py) under open-loop
    Poisson serving — a trainer republishing every K arriving segments
    through the validation gate → canary → promote path; value = median
    model staleness (newest covered shard arrival -> first response
    under the covering fingerprint), with serving p99 held under a
    calibrated bound across >= 3 publications, one injected NaN
    candidate gate-rejected (zero requests under its fingerprint) and
    one injected canary latency regression rolled back — every leg with
    zero-drop accounting.
  - stupidbackoff_batch_scoring: vectorized LM serving vs the dict loop.

Timing method: each metric reports BOTH the single-dispatch wall-clock
(value / wallclock_s — includes host dispatch and the result transfer;
conservative, used for vs_baseline) and the marginal device time from
in-program repetition ((t_reps3 - t_reps1) / 2 — what the hardware actually
spends, with every per-dispatch host cost differenced out; used for
achieved TFLOP/s + MFU). Every row declares its
convention machine-readably in ``detail.timing`` (one of VALID_TIMING,
enforced by make_row and tests/test_bench_conventions.py).

Env knobs: BENCH_N (headline rows, default the REAL 2.2e6),
BENCH_AMAZON_N (default the REAL 65e6), BENCH_SCALE (resident-row
multiplier), BENCH_PRECISION=bf16|f32, BENCH_EPOCHS (BCD epochs, default
3), BENCH_ONLY=timit (headline only).

Prints ONE JSON line:
  {"metric": ..., "value": <seconds>, "unit": "s", "vs_baseline": <speedup x>}
vs_baseline > 1 means faster than the (n-scaled) 16-node Spark cluster.
"""

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

# TIMIT shapes (BASELINE.md; reference: TimitFeaturesDataLoader.scala:16-70)
TIMIT_INPUT_DIMS = 440
TIMIT_NUM_CLASSES = 147
BASELINE_N = 2_200_000
BASELINE_MS = 580_555.0  # scripts/solver-comparisons-final.csv:26 (d=16384, Block)
# Epochs assumed for the baseline CSV row (see comment at the scaling site).
BASELINE_ASSUMED_EPOCHS = 3
NUM_FEATURES = 16384
BLOCK_SIZE = 4096  # reference TimitPipeline blockSize (TimitPipeline.scala:37-109)
# Default 3 BCD sweeps — the baseline CSV row's inferred count (see the
# scaling-site comment), so the default comparison needs no epoch-ratio
# adjustment at all. Epochs 2+ reuse the stashed per-block Gramians and
# factors; they cost ~15% of the first sweep.
NUM_EPOCHS = int(os.environ.get("BENCH_EPOCHS", "3"))

# v5e per-chip peaks for MFU accounting (bf16 MXU; f32 runs the MXU's
# 3-pass emulation). MFU is computed against the precision the metric's
# dominant GEMMs use.
PEAK_TFLOPS_BF16 = 197.0
PEAK_TFLOPS_F32 = 49.0
# v5e per-chip HBM bandwidth, for roofline attribution of memory-bound
# phases (the FFT featurize stage).
PEAK_HBM_GBPS = 819.0

# Timing conventions a row may declare. EVERY emitted row carries
# ``detail.timing`` as one of these (enforced by make_row + the fast test
# tests/test_bench_conventions.py), so conventions can't silently diverge
# across rows again (VERDICT r5 Weak #1):
#   min_of_N_warm   — compile/warm pass first, min over N timed runs
#   single_run_cold — one measured run INCLUDING compile (capacity rows
#                     whose second run would double the bench's cost)
#   single_run_warm — compile/warm pass first, ONE timed run
#   host_only       — no device dispatch in the timed region
#   open_loop_latency — serving rows: requests arrive on an open-loop
#                     Poisson schedule (offered rate independent of
#                     completions — no coordinated omission) and the
#                     value is a latency percentile over completions
#   recovery_overhead — reliability rows: the value is the checkpoint-on
#                     vs -off wall FRACTION of the same warmed fit (each
#                     leg min-of-N); the row must carry the checkpoint
#                     interval and the baseline seconds it divides by
#   overhead_fraction — instrumentation rows (ISSUE 9): the value is the
#                     feature-on vs -off wall FRACTION of the same
#                     warmed run (each leg min-of-N); the row must carry
#                     the baseline seconds it divides by
VALID_TIMING = frozenset(
    {"min_of_N_warm", "single_run_cold", "single_run_warm", "host_only",
     "open_loop_latency", "recovery_overhead", "overhead_fraction"}
)


def _recovery_violations(detail, timing):
    """Auditability rule (ISSUE 5 satellite): a ``recovery_overhead``
    row's fraction is meaningless without the checkpoint interval it was
    measured at and the baseline wall it divides by — both must be
    numeric fields in the row's top-level detail."""
    if timing != "recovery_overhead":
        return []
    bad = []

    def has_numeric(pred):
        return any(
            pred(k) and isinstance(v, (int, float))
            and not isinstance(v, bool)
            for k, v in detail.items()
        )

    if not has_numeric(lambda k: k.startswith("checkpoint_every")):
        bad.append(
            "detail: recovery_overhead without a numeric "
            "checkpoint_every* interval field"
        )
    if not has_numeric(
        lambda k: k.startswith("baseline") and k.endswith("_s")
    ):
        bad.append(
            "detail: recovery_overhead without a numeric baseline*_s "
            "wall field"
        )
    return bad


def _overhead_violations(detail, timing):
    """Auditability rule (ISSUE 9): an ``overhead_fraction`` row — the
    feature-on vs -off wall fraction of one warmed run — is meaningless
    without the baseline wall it divides by."""
    if timing != "overhead_fraction":
        return []
    if not any(
        k.startswith("baseline") and k.endswith("_s")
        and isinstance(v, (int, float)) and not isinstance(v, bool)
        for k, v in detail.items()
    ):
        return [
            "detail: overhead_fraction without a numeric baseline*_s "
            "wall field"
        ]
    return []


def _latency_violations(obj, path):
    """Auditability rule (ISSUE 4 satellite): any dict claiming a latency
    percentile (a ``p50*`` / ``p99*`` key) must carry its sample count
    (``num_samples``) and the offered load (an ``offered*`` key) in the
    SAME dict — a percentile with no n and no arrival rate is not a
    measurement."""
    bad = []
    if isinstance(obj, dict):
        keys = list(obj)
        claims = [k for k in keys if k.startswith("p50") or k.startswith("p99")]
        if claims:
            if not any(
                k == "num_samples" or k.startswith("num_samples") for k in keys
            ):
                bad.append(f"{path}: {claims} without a num_samples field")
            # The offered rate must be a NUMBER — a prose offered_note
            # would satisfy a key-only check while carrying no arrival
            # rate, defeating the rule.
            if not any(
                k.startswith("offered")
                and isinstance(obj[k], (int, float))
                and not isinstance(obj[k], bool)
                for k in keys
            ):
                bad.append(
                    f"{path}: {claims} without a numeric offered* rate field"
                )
        for k, v in obj.items():
            bad.extend(_latency_violations(v, f"{path}.{k}"))
    elif isinstance(obj, (list, tuple)):
        for i, v in enumerate(obj):
            bad.extend(_latency_violations(v, f"{path}[{i}]"))
    return bad


def _autoscale_violations(obj, path):
    """Auditability rule (ISSUE 12 satellite): any dict claiming
    elasticity actions (a ``scale_ups`` / ``scale_downs`` key) must
    carry the decision-event count (``num_decisions``) and the replica
    bounds the controller ran under (``min_replicas`` + ``max_replicas``)
    in the SAME dict — a scale count with no audit trail and no bounds
    is not a measured control-loop claim. ``Autoscaler.stats()`` emits
    exactly this shape, so dropping it into a row passes as-is."""
    bad = []
    if isinstance(obj, dict):
        keys = list(obj)
        claims = [k for k in keys if k in ("scale_ups", "scale_downs")]
        if claims:

            def has_numeric(name):
                v = obj.get(name)
                return isinstance(v, (int, float)) and not isinstance(
                    v, bool
                )

            if not has_numeric("num_decisions"):
                bad.append(
                    f"{path}: {claims} without a numeric num_decisions "
                    "(decision-event count) field"
                )
            if not (has_numeric("min_replicas")
                    and has_numeric("max_replicas")):
                bad.append(
                    f"{path}: {claims} without numeric min_replicas + "
                    "max_replicas bounds"
                )
        for k, v in obj.items():
            bad.extend(_autoscale_violations(v, f"{path}.{k}"))
    elif isinstance(obj, (list, tuple)):
        for i, v in enumerate(obj):
            bad.extend(_autoscale_violations(v, f"{path}[{i}]"))
    return bad


def _scaling_violations(obj, path):
    """Auditability rule (ISSUE 16 satellite): any dict claiming a
    multi-device speedup (a ``speedup*`` key) or scaling efficiency
    (a ``scaling_efficiency*`` key) must carry the device count
    (``num_devices``) and the single-device wall it divides by
    (``single_device_baseline_s``) in the SAME dict — a speedup with no
    denominator and no device count is not a measured scaling claim."""
    bad = []
    if isinstance(obj, dict):
        keys = list(obj)
        claims = [
            k for k in keys
            if k.startswith("speedup") or k.startswith("scaling_efficiency")
        ]
        if claims:

            def has_numeric(name):
                v = obj.get(name)
                return isinstance(v, (int, float)) and not isinstance(
                    v, bool
                )

            if not has_numeric("num_devices"):
                bad.append(
                    f"{path}: {claims} without a numeric num_devices "
                    "field"
                )
            if not has_numeric("single_device_baseline_s"):
                bad.append(
                    f"{path}: {claims} without a numeric "
                    "single_device_baseline_s wall field"
                )
        for k, v in obj.items():
            bad.extend(_scaling_violations(v, f"{path}.{k}"))
    elif isinstance(obj, (list, tuple)):
        for i, v in enumerate(obj):
            bad.extend(_scaling_violations(v, f"{path}[{i}]"))
    return bad


def _sketch_violations(obj, path):
    """Auditability rule (ISSUE 17 satellite): any dict claiming a
    sketched-solver result (an ``accuracy_frontier*`` key, or any
    ``sketch_*`` key other than the ``sketch_size`` input itself) must
    carry the sketch size (``sketch_size``), the exact-solver wall it
    beats (``exact_baseline_s``) and a held-out quality metric (a
    numeric ``heldout_*`` field) in the SAME dict — a sketch wall with
    no exact denominator and no matched held-out quality is not a
    measured approximation claim (mirrors the scaling-claim audit
    above)."""
    bad = []
    if isinstance(obj, dict):
        claims = [
            k for k in obj
            if k.startswith("accuracy_frontier")
            or (k.startswith("sketch_") and k != "sketch_size")
        ]
        if claims:

            def has_numeric(name):
                v = obj.get(name)
                return isinstance(v, (int, float)) and not isinstance(
                    v, bool
                )

            if not has_numeric("sketch_size"):
                bad.append(
                    f"{path}: {claims} without a numeric sketch_size "
                    "field"
                )
            if not has_numeric("exact_baseline_s"):
                bad.append(
                    f"{path}: {claims} without a numeric "
                    "exact_baseline_s wall field"
                )
            if not any(
                k.startswith("heldout_")
                and isinstance(obj.get(k), (int, float))
                and not isinstance(obj.get(k), bool)
                for k in obj
            ):
                bad.append(
                    f"{path}: {claims} without a numeric heldout_* "
                    "quality field"
                )
        for k, v in obj.items():
            bad.extend(_sketch_violations(v, f"{path}.{k}"))
    elif isinstance(obj, (list, tuple)):
        for i, v in enumerate(obj):
            bad.extend(_sketch_violations(v, f"{path}[{i}]"))
    return bad


def _tenant_violations(obj, path):
    """Auditability rule (ISSUE 14 satellite): any dict carrying a
    ``tenants`` mapping whose per-tenant blocks claim latency
    percentiles (``p99*``) or SLO verdicts (``slo``) must carry a
    numeric ``num_tenants`` in the SAME dict, and EVERY per-tenant
    block must carry a numeric ``offered*`` field — a per-tenant
    isolation claim with no tenant count and no per-tenant offered load
    is not a measurement. ``MultiTenantLoadReport.to_row_dict`` and
    ``ModelZoo.stats()`` emit exactly this shape, so dropping either
    into a row passes as-is."""
    bad = []
    if isinstance(obj, dict):
        tenants = obj.get("tenants")
        if isinstance(tenants, dict) and any(
            isinstance(b, dict) and any(
                k.startswith("p99") or k == "slo" for k in b
            )
            for b in tenants.values()
        ):
            nt = obj.get("num_tenants")
            if not (isinstance(nt, (int, float))
                    and not isinstance(nt, bool)):
                bad.append(
                    f"{path}: per-tenant p99/slo claims without a "
                    "numeric num_tenants field beside the tenants block"
                )
            for name, b in tenants.items():
                if not isinstance(b, dict):
                    continue
                if not any(
                    k.startswith("offered")
                    and isinstance(b[k], (int, float))
                    and not isinstance(b[k], bool)
                    for k in b
                ):
                    bad.append(
                        f"{path}.tenants.{name}: per-tenant block "
                        "without a numeric offered* field"
                    )
        for k, v in obj.items():
            bad.extend(_tenant_violations(v, f"{path}.{k}"))
    elif isinstance(obj, (list, tuple)):
        for i, v in enumerate(obj):
            bad.extend(_tenant_violations(v, f"{path}[{i}]"))
    return bad


def _calibration_violations(obj, path):
    """Auditability rule (ISSUE 13 satellite): any dict claiming a
    cost-model prediction error (a ``prediction_error*`` key) must carry
    the decision-event count (``num_decisions``) and the weight-family
    name (``weights_family``) in the SAME dict — an error statistic with
    no n and no family is not a calibration claim.
    ``obs.calibrate.calibration_report`` emits exactly this shape, so
    dropping a report's summary into a row passes as-is."""
    bad = []
    if isinstance(obj, dict):
        keys = list(obj)
        claims = [k for k in keys if k.startswith("prediction_error")]
        if claims:
            nd = obj.get("num_decisions")
            if not (isinstance(nd, (int, float))
                    and not isinstance(nd, bool)):
                bad.append(
                    f"{path}: {claims} without a numeric num_decisions "
                    "(decision-event count) field"
                )
            if not isinstance(obj.get("weights_family"), str):
                bad.append(
                    f"{path}: {claims} without a weights_family name "
                    "field"
                )
        for k, v in obj.items():
            bad.extend(_calibration_violations(v, f"{path}.{k}"))
    elif isinstance(obj, (list, tuple)):
        for i, v in enumerate(obj):
            bad.extend(_calibration_violations(v, f"{path}[{i}]"))
    return bad


def _lifecycle_violations(obj, path):
    """Auditability rule (ISSUE 15 satellite): any dict claiming model
    staleness (a ``staleness*`` key) or publication rollbacks (a
    ``rollbacks`` key) must carry a numeric ``num_published`` and a
    numeric ``offered*`` rate in the SAME dict — a staleness or
    rollback claim with no publication count and no offered load behind
    it is not a measured continuous-learning claim.
    ``LifecycleController.stats()`` carries ``num_published`` itself;
    embedders merge it with the offered rate of the load the claims
    were measured under (the ``run.py learn`` summary shape)."""
    bad = []
    if isinstance(obj, dict):
        keys = list(obj)
        claims = [
            k for k in keys
            if k.startswith("staleness") or k == "rollbacks"
        ]
        if claims:

            def has_numeric(pred):
                return any(
                    pred(k) and isinstance(v, (int, float))
                    and not isinstance(v, bool)
                    for k, v in obj.items()
                )

            if not has_numeric(lambda k: k == "num_published"):
                bad.append(
                    f"{path}: {claims} without a numeric num_published "
                    "field"
                )
            if not has_numeric(lambda k: k.startswith("offered")):
                bad.append(
                    f"{path}: {claims} without a numeric offered* rate "
                    "field"
                )
        for k, v in obj.items():
            bad.extend(_lifecycle_violations(v, f"{path}.{k}"))
    elif isinstance(obj, (list, tuple)):
        for i, v in enumerate(obj):
            bad.extend(_lifecycle_violations(v, f"{path}[{i}]"))
    return bad


def _ingest_violations(obj, path):
    """Auditability rule (ISSUE 18 satellite): any dict claiming ingest
    bandwidth (an ``*ingest_gbps*`` key) or decode throughput (a
    ``decode_*`` key that reads as a rate — gbps / ``*_per_s`` /
    ``*rate*``) must carry the measured traffic (a numeric
    ``bytes_read``), a seconds field, and a numeric ``peak_*`` reference
    in the SAME dict — an ingest number with no byte count, no wall, and
    no peak to compare against is not a data-plane-bound claim.
    Evidence fields (``decode_busy_s`` and friends) are not claims and
    carry no burden."""
    bad = []
    if isinstance(obj, dict):
        keys = list(obj)
        claims = [
            k for k in keys
            if "ingest_gbps" in k
            or (
                k.startswith("decode_")
                and ("gbps" in k or k.endswith("_per_s") or "rate" in k)
            )
        ]
        if claims:

            def has_numeric(name):
                v = obj.get(name)
                return isinstance(v, (int, float)) and not isinstance(
                    v, bool
                )

            if not has_numeric("bytes_read"):
                bad.append(
                    f"{path}: {claims} without a numeric bytes_read "
                    "traffic field"
                )
            if not any(
                (k == "seconds" or k.endswith("_s"))
                and isinstance(obj.get(k), (int, float))
                and not isinstance(obj.get(k), bool)
                for k in keys
            ):
                bad.append(
                    f"{path}: {claims} without a numeric seconds field"
                )
            if not any(
                k.startswith("peak_")
                and isinstance(obj.get(k), (int, float))
                and not isinstance(obj.get(k), bool)
                for k in keys
            ):
                bad.append(
                    f"{path}: {claims} without a numeric peak_* "
                    "reference field"
                )
        for k, v in obj.items():
            bad.extend(_ingest_violations(v, f"{path}.{k}"))
    elif isinstance(obj, (list, tuple)):
        for i, v in enumerate(obj):
            bad.extend(_ingest_violations(v, f"{path}[{i}]"))
    return bad


def _whatif_violations(obj, path):
    """Auditability rule (ISSUE 19 satellite): any dict claiming a
    capacity-planner prediction (a ``predicted_p99*`` or ``whatif_*``
    key) must carry the decision count (``num_decisions``), the
    weight-family name (``weights_family``), and a numeric measured
    baseline (a ``measured*`` key) in the SAME dict — a what-if with no
    trace behind it, no pricing provenance, and no measured reality to
    compare against is not a capacity claim.
    ``CapacityPlanner.whatif_*`` rows emit exactly this shape, so
    dropping a planner row into a bench detail passes as-is."""
    bad = []
    if isinstance(obj, dict):
        keys = list(obj)
        claims = [
            k for k in keys
            if k.startswith("predicted_p99") or k.startswith("whatif_")
        ]
        if claims:
            nd = obj.get("num_decisions")
            if not (isinstance(nd, (int, float))
                    and not isinstance(nd, bool)):
                bad.append(
                    f"{path}: {claims} without a numeric num_decisions "
                    "(replayed decision count) field"
                )
            if not isinstance(obj.get("weights_family"), str):
                bad.append(
                    f"{path}: {claims} without a weights_family name "
                    "field"
                )
            if not any(
                k.startswith("measured")
                and isinstance(obj.get(k), (int, float))
                and not isinstance(obj.get(k), bool)
                for k in keys
            ):
                bad.append(
                    f"{path}: {claims} without a numeric measured* "
                    "baseline field"
                )
        for k, v in obj.items():
            bad.extend(_whatif_violations(v, f"{path}.{k}"))
    elif isinstance(obj, (list, tuple)):
        for i, v in enumerate(obj):
            bad.extend(_whatif_violations(v, f"{path}[{i}]"))
    return bad


def _fleet_violations(obj, path):
    """Auditability rule (ISSUE 20 satellite): any dict claiming a
    fleet-wide latency merge (a ``fleet_p99*`` key) or fleet-wide load
    (an ``aggregate_offered*`` key) must carry a numeric ``num_planes``
    AND a ``planes`` mapping whose per-plane blocks each carry numeric
    ``completed`` / ``rejected`` / ``failed`` accounting in the SAME
    dict — a cross-process p99 with no plane count and no per-plane
    books behind it is not a fleet measurement (there is no way to
    check the zero-drop invariant it rides on).
    ``FleetRouter.stats()`` emits exactly this shape, so dropping a
    fleet stats dict into a row passes as-is."""
    bad = []
    if isinstance(obj, dict):
        keys = list(obj)
        claims = [
            k for k in keys
            if k.startswith("fleet_p99")
            or k.startswith("aggregate_offered")
        ]
        if claims:
            np_ = obj.get("num_planes")
            if not (isinstance(np_, (int, float))
                    and not isinstance(np_, bool)):
                bad.append(
                    f"{path}: {claims} without a numeric num_planes "
                    "field"
                )
            planes = obj.get("planes")
            if not isinstance(planes, dict) or not planes:
                bad.append(
                    f"{path}: {claims} without a planes mapping "
                    "(per-plane accounting blocks)"
                )
            else:
                for name, b in planes.items():
                    if not isinstance(b, dict) or not all(
                        isinstance(b.get(f), (int, float))
                        and not isinstance(b.get(f), bool)
                        for f in ("completed", "rejected", "failed")
                    ):
                        bad.append(
                            f"{path}.planes.{name}: per-plane block "
                            "without numeric completed/rejected/"
                            "failed accounting"
                        )
        for k, v in obj.items():
            bad.extend(_fleet_violations(v, f"{path}.{k}"))
    elif isinstance(obj, (list, tuple)):
        for i, v in enumerate(obj):
            bad.extend(_fleet_violations(v, f"{path}[{i}]"))
    return bad


def _roofline_violations(obj, path, row_unit, top=False):
    """Auditability rule (ISSUE 3 satellite): any dict claiming an ``mfu``
    must carry its arithmetic inputs in the SAME dict — a flop model
    (``flop_model*``), the peak (``peak*``), and a seconds field (a
    ``*_s``/``*_s_*`` key; the top-level detail may instead lean on the
    row's own value when ``unit == "s"``). Any achieved-bandwidth claim
    (a ``*gbps*`` key that is not the peak) must carry a ``peak*gbps``
    sibling, a traffic input (``*_gb`` / ``*bytes*``), and seconds. So a
    roofline can always be re-derived from the row alone."""
    bad = []
    if isinstance(obj, dict):
        keys = list(obj)

        def has_seconds():
            if any(k.endswith("_s") or "_s_" in k for k in keys):
                return True
            return top and row_unit == "s"

        if "mfu" in keys:
            if not any(k.startswith("flop_model") for k in keys):
                bad.append(f"{path}: mfu without a flop_model* input")
            # The peak must be a COMPUTE peak — a bandwidth peak
            # (peak_hbm_gbps) in the same dict must not satisfy an mfu
            # claim, or the roofline re-derives against the wrong axis.
            if not any(
                k.startswith("peak") and "gbps" not in k for k in keys
            ):
                bad.append(f"{path}: mfu without a compute peak* field")
            if not has_seconds():
                bad.append(f"{path}: mfu without a seconds field")
        gbps = [
            k for k in keys
            if "gbps" in k and not ("peak" in k and "gbps" in k)
        ]
        if gbps:
            if not any("peak" in k and "gbps" in k for k in keys):
                bad.append(f"{path}: {gbps} without a peak*gbps sibling")
            if not any(
                k.endswith("_gb") or "bytes" in k or "traffic" in k
                for k in keys
            ):
                bad.append(f"{path}: {gbps} without a traffic/bytes input")
            if not has_seconds():
                bad.append(f"{path}: {gbps} without a seconds field")
        for k, v in obj.items():
            bad.extend(_roofline_violations(v, f"{path}.{k}", row_unit))
    elif isinstance(obj, (list, tuple)):
        for i, v in enumerate(obj):
            bad.extend(_roofline_violations(v, f"{path}[{i}]", row_unit))
    return bad


def make_row(metric, value, unit, vs_baseline, timing, detail):
    """The ONLY way a bench row is built: the timing convention is a
    required, validated field riding in detail, and every mfu /
    achieved-GB/s claim must carry its arithmetic inputs (enforced by
    ``_roofline_violations`` so rooflines stay auditable)."""
    if timing not in VALID_TIMING:
        raise ValueError(
            f"row {metric!r}: timing {timing!r} not in {sorted(VALID_TIMING)}"
        )
    detail = dict(detail)
    detail["timing"] = timing
    violations = _roofline_violations(detail, "detail", unit, top=True)
    violations += _latency_violations(detail, "detail")
    violations += _recovery_violations(detail, timing)
    violations += _overhead_violations(detail, timing)
    violations += _autoscale_violations(detail, "detail")
    violations += _scaling_violations(detail, "detail")
    violations += _sketch_violations(detail, "detail")
    violations += _calibration_violations(detail, "detail")
    violations += _tenant_violations(detail, "detail")
    violations += _lifecycle_violations(detail, "detail")
    violations += _ingest_violations(detail, "detail")
    violations += _whatif_violations(detail, "detail")
    violations += _fleet_violations(detail, "detail")
    if violations:
        raise ValueError(
            f"row {metric!r}: unauditable roofline claims: {violations}"
        )
    return {
        "metric": metric,
        "value": value,
        "unit": unit,
        "vs_baseline": vs_baseline,
        "detail": detail,
    }


def min_wall(fn, reps: int = 3):
    """Min-of-N warm wall-clock: ``fn`` once untimed (compile + warm),
    then the min over ``reps`` timed runs. Returns (min_wall_s, last
    result, cold_wall_s) — cold includes the compile."""
    t0 = time.perf_counter()
    result = fn()
    cold = time.perf_counter() - t0
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        result = fn()
        best = min(best, time.perf_counter() - t0)
    return best, result, cold


def _sync_scalar(x) -> float:
    """Execution barrier that also delivers the value: the host transfer
    of a scalar result cannot complete before the program that produces
    it has run."""
    return float(x)


def marginal_device_time(make_repeated, reps: int = 3):
    """(t_repsN - t_reps1)/(N-1): in-program repetition isolates device
    execution time from everything a dispatch costs on the host (launch,
    argument handling, the result transfer). Returns
    (device_s, wall_single_s, dispatch_overhead_s)."""
    r1 = make_repeated(1)
    rN = make_repeated(reps)
    _sync_scalar(r1())  # compile + warm
    _sync_scalar(rN())
    t0 = time.perf_counter()
    _sync_scalar(r1())
    t1 = time.perf_counter() - t0
    t0 = time.perf_counter()
    _sync_scalar(rN())
    tN = time.perf_counter() - t0
    device = max((tN - t1) / (reps - 1), 1e-9)
    return device, t1, max(t1 - device, 0.0)


def timit_streaming_metric():
    """The REAL baseline row, full n, no scaling: n=2,200,000 × d=16384
    cosine features → 3-epoch block coordinate descent, via the streaming
    tier (features generated per 65536-row tile inside one compiled scan;
    the 72 GB feature matrix never exists — parallel/streaming.py).

    Inputs are generated device-side (untimed), mirroring every other
    row's device-resident-input convention; the raw TIMIT input at this
    geometry is 3.9 GB (2.2e6×440 f32) and stays resident, exactly like a
    production host would hold it. The timed region is ONE dispatch:
    tile sweep (fused featurize + accumulating syrk) + BCD epochs on the
    accumulated normal equations + algebraic train loss.
    """
    precision = os.environ.get("BENCH_PRECISION", "bf16")
    bf16 = precision == "bf16"
    n = int(os.environ.get("BENCH_N", str(BASELINE_N)))
    epochs = NUM_EPOCHS

    from keystone_tpu.ops import pallas_ops as po
    from keystone_tpu.ops.stats import CosineRandomFeatures
    from keystone_tpu.parallel import streaming

    use_pallas = po.pallas_enabled()
    feat_dtype = jnp.bfloat16 if bf16 else jnp.float32
    tile_rows = streaming.pick_tile_rows(
        NUM_FEATURES, 2 if bf16 else 4
    )  # 65536 bf16 / 32768 f32 — one ~2 GB slab

    num_blocks = NUM_FEATURES // BLOCK_SIZE
    rfs = [
        CosineRandomFeatures(TIMIT_INPUT_DIMS, BLOCK_SIZE, gamma=0.05, seed=i)
        for i in range(num_blocks)
    ]
    Wrf_flat = jnp.stack([rf.W for rf in rfs]).reshape(
        NUM_FEATURES, TIMIT_INPUT_DIMS
    )
    brf_flat = jnp.stack([rf.b for rf in rfs]).reshape(NUM_FEATURES)

    def make_featurize(bias):
        def featurize(X_t):
            if use_pallas:
                return po.cosine_features(
                    X_t, Wrf_flat, bias,
                    compute_dtype=feat_dtype, out_dtype=feat_dtype,
                )
            return jnp.cos(
                X_t.astype(jnp.float32) @ Wrf_flat.T + bias
            ).astype(feat_dtype)
        return featurize

    featurize = make_featurize(brf_flat)

    # Device-side input generation (untimed): PRE-TILED X (an in-program
    # reshape would make XLA hold a second lane-padded ~4.5 GB copy of X —
    # the difference between fitting 16 GB HBM and not) + int labels (the
    # one-hot target is built per tile by `labelize`, so the 1.3 GB target
    # matrix never exists at full n). In bf16 mode X is STORED bf16: the
    # bf16 MXU pass quantizes the operands to bf16 regardless, so the f32
    # copy holds no extra information — only 2.3 GB of extra HBM.
    num_tiles = -(-n // tile_rows)
    n_pad = num_tiles * tile_rows

    @jax.jit
    def gen(key):
        kx, ky = jax.random.split(key)
        X = jax.random.normal(
            kx, (num_tiles, tile_rows, TIMIT_INPUT_DIMS), jnp.float32
        ).astype(feat_dtype)
        y = jax.random.randint(
            ky, (num_tiles, tile_rows), 0, TIMIT_NUM_CLASSES
        )
        return X, y

    X, y = gen(jax.random.PRNGKey(0))
    _sync_scalar(jnp.sum(X[0, 0]) + jnp.sum(y[0, 0]))  # drain generation

    def labelize(y_t):
        return 2.0 * jax.nn.one_hot(
            y_t, TIMIT_NUM_CLASSES, dtype=jnp.float32
        ) - 1.0

    fit_kw = dict(
        featurize=featurize, d_feat=NUM_FEATURES, tile_rows=tile_rows,
        block_size=BLOCK_SIZE, lam=1e-4, num_iter=epochs,
        use_pallas=use_pallas, labelize=labelize,
        valid=n if n != n_pad else None,
    )

    def run_once():
        W, loss, _ = streaming.streaming_bcd_fit(X, y, **fit_kw)
        loss = float(loss)  # host transfer: the reliable execution barrier
        assert np.isfinite(loss), f"bad streamed solve: loss={loss}"
        return W, loss

    run_once()  # warmup (compile)
    elapsed = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        W, loss = run_once()
        elapsed = min(elapsed, time.perf_counter() - t0)

    # Untimed quality pass: train error from tile-wise predictions
    # (padding rows masked out of the mean).
    @jax.jit
    def err_of(X, y, W):
        preds = streaming.streaming_predict(X, W, featurize, tile_rows)
        hits = jnp.argmax(preds, axis=1) == y.reshape(-1)
        ok = jnp.arange(preds.shape[0]) < n
        return 1.0 - jnp.sum(hits * ok) / n

    train_err = float(err_of(X, y, W))

    # Marginal device time: repeat the full streamed fit in-program and
    # difference reps=3 vs 1 (strips per-dispatch host overhead). The
    # hoisting-defeat perturbation rides on the 16384-float featurizer
    # bias, NOT on X — `X + 0.0*acc` would materialize a second full-size
    # X and push the program back over HBM.
    def make_repeated(reps):
        valid = n if n != n_pad else None

        @jax.jit
        def run(X, y):
            def body(i, acc):
                f = make_featurize(brf_flat + 0.0 * acc)
                G, FY, yty = streaming.gram_stats(
                    X, y, f, NUM_FEATURES, tile_rows,
                    use_pallas=use_pallas, valid=valid, labelize=labelize,
                )
                W = streaming.bcd_from_gram(
                    G, FY, BLOCK_SIZE, 1e-4, epochs
                )
                return acc + jnp.sum(jnp.abs(W))
            return jax.lax.fori_loop(0, reps, body, 0.0)
        return lambda: run(X, y)

    device_s, _, dispatch_s = marginal_device_time(make_repeated)

    # FLOP accounting — two stated models:
    #   executed: the MACs the program actually issues. The Gramian is a
    #     symmetric rank-n update (syrk): n·d² FLOPs, not the dense 2·n·d².
    #   dense_equiv: what a dense implementation of the same algorithm
    #     (full FᵀF) must do — the convention rounds 1-3 used for the
    #     resident row's MFU. For a syrk-dominated program that convention
    #     can exceed peak, so MFU here is computed against EXECUTED work
    #     (i.e. it reads as true hardware utilization).
    d, k = NUM_FEATURES, TIMIT_NUM_CLASSES
    feat_fl = 2.0 * n * TIMIT_INPUT_DIMS * d
    syrk_fl = 1.0 * n * d * d
    fy_fl = 2.0 * n * d * k
    nb = d // BLOCK_SIZE
    epoch_fl = epochs * nb * 2 * 2.0 * d * BLOCK_SIZE * k
    chol_fl = nb * BLOCK_SIZE**3 / 3.0
    executed = feat_fl + syrk_fl + fy_fl + epoch_fl + chol_fl
    dense_equiv = executed + syrk_fl  # full Gramian doubles the syrk term
    achieved = executed / device_s / 1e12
    peak = PEAK_TFLOPS_BF16 if bf16 else PEAK_TFLOPS_F32

    baseline_s = BASELINE_MS / 1000.0
    return make_row(
        "timit_full_n_streaming_d16384_wallclock",
        round(elapsed, 3),
        "s",
        round(baseline_s / elapsed, 2),
        "min_of_N_warm",
        {
            "n": n,
            "d": d,
            "k": k,
            "block_size": BLOCK_SIZE,
            "epochs": epochs,
            "tile_rows": tile_rows,
            "precision": "bf16" if bf16 else "f32",
            "streaming": (
                "out-of-core tier: features generated per tile inside one "
                "compiled scan; the feature matrix (72 GB bf16 at this "
                "geometry) is never materialized (parallel/streaming.py)"
            ),
            "timing_note": "wallclock = min of 3 timed single-dispatch runs",
            "device_time_s": round(device_s, 3),
            "dispatch_overhead_s": round(dispatch_s, 3),
            "flop_model_executed_tflops": round(executed / 1e12, 2),
            "flop_model_dense_equiv_tflops": round(dense_equiv / 1e12, 2),
            "achieved_tflops": round(achieved, 1),
            "peak_tflops": peak,
            "mfu": round(achieved / peak, 3),
            "mfu_note": (
                "MFU against EXECUTED MACs (syrk counts n*d^2, so this is "
                "true hardware utilization; the rounds-1..3 dense-equiv "
                "convention would read "
                f"{round(dense_equiv / device_s / 1e12 / peak, 3)})"
            ),
            "vs_baseline_device_time": round(baseline_s / device_s, 2),
            "train_loss": round(loss, 4),
            "train_err": round(train_err, 4),
            "quality_note": (
                "synthetic labels; error/loss parity vs an exact solver on "
                "real data lives in parity.py / PARITY_RESULTS.json"
            ),
            "pallas": use_pallas,
            "single_dispatch": True,
            "baseline": (
                "16x r3.4xlarge Spark, 580.555s at the SAME n=2.2e6 and "
                "d=16384 (csv:26) — literal comparison, NO n-scaling. "
                "Epoch count: the CSV row's inferred 3 sweeps "
                "(constantEstimator.R:12); this run uses the same 3. "
                "Streamed epochs 2+ cost no data pass, so a 5-epoch run "
                "(TimitPipeline.scala:34 default) adds <2% — the epoch "
                "assumption no longer moves the comparison"
            ),
            "baseline_s": round(baseline_s, 3),
            "device": str(jax.devices()[0]),
        },
    )


def timit_metric():
    scale = float(os.environ.get("BENCH_SCALE", "1.0"))
    precision = os.environ.get("BENCH_PRECISION", "bf16")
    if precision not in ("bf16", "f32"):
        raise SystemExit(f"BENCH_PRECISION must be bf16 or f32, got {precision!r}")
    bf16 = precision == "bf16"

    from keystone_tpu.ops import pallas_ops as po

    use_pallas = po.pallas_enabled()
    # 262144 rows ≈ 12 GB peak HBM with fused bf16 features (fits a 16 GB
    # v5e with headroom). The XLA fallback materializes a full-width f32
    # pre-activation (~17 GB at that n) and f32 features double the buffer,
    # so both fall back to half the rows.
    n = int(262144 * scale) if (bf16 and use_pallas) else int(131072 * scale)

    rng = np.random.default_rng(0)
    X_np = rng.normal(size=(n, TIMIT_INPUT_DIMS)).astype(np.float32)
    y_np = rng.integers(0, TIMIT_NUM_CLASSES, size=n)

    from keystone_tpu.ops.stats import CosineRandomFeatures
    from keystone_tpu.parallel import linalg

    X = jnp.asarray(X_np)
    Y = 2.0 * jax.nn.one_hot(y_np, TIMIT_NUM_CLASSES, dtype=jnp.float32) - 1.0

    # One CosineRandomFeatures branch per feature block, mirroring the
    # reference TimitPipeline's gather of numCosines branches
    # (TimitPipeline.scala:37-109).
    num_blocks = NUM_FEATURES // BLOCK_SIZE
    rfs = [
        CosineRandomFeatures(TIMIT_INPUT_DIMS, BLOCK_SIZE, gamma=0.05, seed=i)
        for i in range(num_blocks)
    ]
    Wrf = jnp.stack([rf.W for rf in rfs])
    brf = jnp.stack([rf.b for rf in rfs])

    feat_dtype = jnp.bfloat16 if bf16 else jnp.float32

    # Flat (n, 16384) feature layout: one fused featurize producing a single
    # buffer — a stacked per-block layout would need 2x the features' HBM
    # during the stack and OOMs at BENCH_SCALE >= 2.
    Wrf_flat = Wrf.reshape(NUM_FEATURES, TIMIT_INPUT_DIMS)
    brf_flat = brf.reshape(NUM_FEATURES)

    def featurize(X):
        if use_pallas:
            return po.cosine_features(
                X, Wrf_flat, brf_flat,
                compute_dtype=feat_dtype, out_dtype=feat_dtype,
            )
        return jnp.cos(X @ Wrf_flat.T + brf_flat).astype(feat_dtype)

    @jax.jit
    def train_step(X, Wrf_flat, brf_flat, Y):
        F = featurize(X)
        W = linalg.bcd_least_squares_fused_flat(
            F, Y, BLOCK_SIZE, lam=1e-4, num_iter=NUM_EPOCHS,
            use_pallas=use_pallas,
        )
        # Checksum computed in-program: the barrier below is then a bare
        # scalar transfer, not a second dispatch round trip.
        return W, jnp.sum(jnp.abs(W))

    @jax.jit
    def quality_step(X, Wrf_flat, brf_flat, Y, W):
        # Untimed pass: ridge loss ||Y − F W||²/n and train error of the
        # fitted model (the CSV rows report err+loss, so the bench does
        # too). Kept out of train_step so the timed program is exactly the
        # solve — returning the residual there perturbs buffer lifetimes.
        F = featurize(X)
        nb = NUM_FEATURES // BLOCK_SIZE
        preds = sum(
            jax.lax.dynamic_slice_in_dim(F, i * BLOCK_SIZE, BLOCK_SIZE, 1)
            .astype(jnp.float32) @ W[i]
            for i in range(nb)
        )
        R = Y - preds
        loss = jnp.sum(R * R) / R.shape[0]
        train_acc = jnp.mean(
            jnp.argmax(preds, axis=1) == jnp.argmax(Y, axis=1)
        )
        return loss, 1.0 - train_acc

    def run_once():
        W, checksum = train_step(X, Wrf_flat, brf_flat, Y)
        # Force execution end-to-end: the checksum's host transfer is the
        # barrier, and its value is checked.
        checksum = float(checksum)
        assert np.isfinite(checksum) and checksum > 0, f"bad solve: {checksum}"
        return W

    run_once()  # warmup (compile)
    # Steady-state wall-clock: best of 3 timed runs; each run is one
    # full dispatch round trip.
    elapsed = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        W = run_once()  # timed: featurization + solve (the pipeline body)
        elapsed = min(elapsed, time.perf_counter() - t0)

    loss, train_err = (
        float(x) for x in quality_step(X, Wrf_flat, brf_flat, Y, W)
    )

    # Marginal device time (per-dispatch host overhead excluded): fori_loop
    # the whole train step inside one program and difference reps=3 vs 1.
    def make_repeated(reps):
        @jax.jit
        def run(X, Wrf_flat, brf_flat, Y):
            def body(i, acc):
                # The 0.0*acc carries defeat XLA's loop-invariant hoisting:
                # both featurize and the solve must execute on EVERY
                # repetition or the reps-difference under-counts the work.
                F = featurize(X + 0.0 * acc)
                Wr = linalg.bcd_least_squares_fused_flat(
                    F, Y + 0.0 * acc, BLOCK_SIZE, lam=1e-4,
                    num_iter=NUM_EPOCHS, use_pallas=use_pallas,
                )
                return acc + jnp.sum(jnp.abs(Wr))
            return jax.lax.fori_loop(0, reps, body, 0.0)
        return lambda: run(X, Wrf_flat, brf_flat, Y)

    device_s, _, dispatch_s = marginal_device_time(make_repeated)

    # Stated FLOP model (algorithmic, dense-equivalent; the syrk kernels do
    # ~half the Gramian MACs but MFU accounts the algorithm's work):
    #   featurize 2·n·440·16384; epoch-1 Gramians nb·2·n·bs²; every epoch's
    #   correlation+residual nb·2·2·n·bs·k; Cholesky nb·bs³/3 (factors
    #   cached across epochs); triangular solves epochs·nb·4·bs²·k.
    nb = NUM_FEATURES // BLOCK_SIZE
    k = TIMIT_NUM_CLASSES
    flops = (
        2.0 * n * TIMIT_INPUT_DIMS * NUM_FEATURES
        + nb * 2.0 * n * BLOCK_SIZE**2
        + NUM_EPOCHS * nb * 2 * 2.0 * n * BLOCK_SIZE * k
        + nb * BLOCK_SIZE**3 / 3.0
        + NUM_EPOCHS * nb * 4.0 * BLOCK_SIZE**2 * k
    )
    achieved_tflops = flops / device_s / 1e12
    peak = PEAK_TFLOPS_BF16 if bf16 else PEAK_TFLOPS_F32

    # The baseline CSV row is one full solver run whose epoch count is not
    # recorded. The reference's own cost-model fit multiplies the Block
    # solver's FLOPs/mem/network by 3 (scripts/constantEstimator.R:12,20,27)
    # — in-repo evidence the CSV Block rows ran 3 BCD sweeps — so model the
    # baseline as 3 epochs and scale per-epoch, linear in rows. This is
    # conservative only relative to round 1's single-sweep assumption (3x
    # lower); under the TimitPipeline *default* of numEpochs=5
    # (TimitPipeline.scala:34) the speedup would read another 3/5 lower —
    # reported alongside as vs_baseline_if_5_epochs.
    baseline_scaled_s = (
        (BASELINE_MS / 1000.0)
        * (n / BASELINE_N)
        * (NUM_EPOCHS / BASELINE_ASSUMED_EPOCHS)
    )
    speedup = baseline_scaled_s / elapsed

    return make_row(
        "timit_resident_262k",
        round(elapsed, 3),
        "s",
        round(speedup, 2),
        "min_of_N_warm",
        {
            "n": n,
            "d": NUM_FEATURES,
            "k": TIMIT_NUM_CLASSES,
            "block_size": BLOCK_SIZE,
            "epochs": NUM_EPOCHS,
            "precision": "bf16" if bf16 else "f32",
            "timing_note": (
                "wallclock = min of 3 timed runs (steady state; "
                "rounds 1-2 recorded a single run)"
            ),
            "device_time_s": round(device_s, 3),
            "dispatch_overhead_s": round(dispatch_s, 3),
            "flop_model_tflops": round(flops / 1e12, 2),
            "achieved_tflops": round(achieved_tflops, 1),
            "peak_tflops": peak,
            "mfu": round(achieved_tflops / peak, 3),
            "vs_baseline_device_time": round(baseline_scaled_s / device_s, 2),
            "train_loss": round(loss, 4),
            "train_err": round(train_err, 4),
            "quality_note": (
                "synthetic labels; error/loss parity vs an exact "
                "solver on real data lives in parity.py / "
                "PARITY_RESULTS.json"
            ),
            "pallas": use_pallas,
            "single_dispatch": True,
            "baseline": (
                "16x r3.4xlarge Spark, 580.6s @ n=2.2e6 (csv:26), "
                "n-scaled, assumed 3 epochs (constantEstimator.R:12)"
            ),
            "baseline_scaled_s": round(baseline_scaled_s, 3),
            "baseline_assumed_epochs": BASELINE_ASSUMED_EPOCHS,
            "vs_baseline_if_5_epochs": round(speedup * 3.0 / 5.0, 2),
            "vs_baseline_if_1_epoch": round(speedup * 3.0, 2),
            "device": str(jax.devices()[0]),
        },
    )


def amazon_sparse_metric():
    """csv:13 geometry (Amazon LS-LBFGS d=16384, sparsity 0.005 -> 82
    nnz/row, k=2) at n=500k resident through BOTH sparse engines:

      - "gather": the reference-shaped path (each iteration a gather +
        segment-sum data pass) — random-access-bound, ~2e8 idx/s.
      - "gram": fold G = AᵀA once over densified chunks (MXU syrk), then
        the SAME L-BFGS iterates on G at one small GEMM per iteration.

    Capacity arithmetic (stated, not assumed): n=65e6 × 83 nnz at int32+f32
    is ~43 GB — it does NOT fit 16 GB HBM (round 3 claimed it did; that was
    false). The compressed int16+bf16 COO (4 B/nnz) is ~21.6 GB at n=65e6 —
    still over; the measured resident point is n=30e6 (9.8 GB, probed with
    fit-path folds in amazon_fulln_metric; n=36e6 is past the
    fold-workspace ceiling). The full-n row therefore STREAMS — see
    amazon_fulln_streamed_gram, which runs the literal n=65e6.
    """
    from keystone_tpu.data import Dataset
    from keystone_tpu.ops.learning.lbfgs import SparseLBFGSwithL2

    n, d, nnz, k = 500_000, NUM_FEATURES, 82, 2
    iters = 20  # AmazonReviewsPipeline default numIters (scala :52)
    rng = np.random.default_rng(1)
    idx = rng.integers(0, d, size=(n, nnz)).astype(np.int32)
    idx.sort(axis=1)
    vals = rng.normal(size=(n, nnz)).astype(np.float32)
    labels = rng.integers(0, k, size=n)
    from keystone_tpu.data import one_hot_pm1

    Y = one_hot_pm1(labels, k)
    ds = Dataset({"indices": jnp.asarray(idx), "values": jnp.asarray(vals)}, n=n)
    Yd = Dataset.of(jnp.asarray(Y))

    def timed_fit(est):
        def run():
            model = est.fit(ds, Yd)
            _sync_scalar(jnp.sum(jnp.abs(model.x)))
            return model

        elapsed, model, _ = min_wall(run, reps=2)
        return model, elapsed

    model, elapsed = timed_fit(
        SparseLBFGSwithL2(lam=1e-3, num_iterations=iters, num_features=d)
    )
    model_g, elapsed_gram = timed_fit(
        SparseLBFGSwithL2(
            lam=1e-3, num_iterations=iters, num_features=d, solver="gram",
            gram_dtype="bf16",
        )
    )
    engine_err = float(jnp.max(jnp.abs(model.x - model_g.x)))

    # FLOP model (gather path): per L-BFGS iteration one Hessian-apply =
    # forward + transpose sparse matmul (2·nnz_total·k each).
    nnz_total = n * (nnz + 1)  # +1: append-ones intercept column
    flops = iters * 2 * 2.0 * nnz_total * k
    gathers_per_s = iters * 2 * nnz_total / elapsed
    baseline_scaled_s = 52.290 * (n / 65e6)  # csv:13, n-scaled, same iters
    best = min(elapsed, elapsed_gram)
    return make_row(
        "amazon_sparse_lbfgs_d16384",
        round(best, 3),
        "s",
        round(baseline_scaled_s / best, 4),
        "min_of_N_warm",
        {
            "n": n, "d": d, "nnz_per_row": nnz, "k": k, "iters": iters,
            "timing_note": "each engine: warm fit, then min of 2 timed fits",
            "gather_engine_s": round(elapsed, 3),
            "gram_engine_s": round(elapsed_gram, 3),
            "engines_max_abs_model_delta": round(engine_err, 6),
            "flop_model_tflops": round(flops / 1e12, 4),
            "gather_rate_per_s": round(gathers_per_s / 1e6, 1),
            "gather_rate_note": (
                "M random indices/s achieved on the gather engine — that "
                "path is random-access-bound, not MXU-bound; the gram "
                "engine moves the same iterates onto the MXU (one syrk "
                "fold + tiny per-iteration GEMMs)"
            ),
            "baseline": (
                "16x r3.4xlarge Spark LBFGS 52.29s @ n=65e6 (csv:13), "
                "n-scaled, 20 iters (AmazonReviewsPipeline default); the "
                "UN-scaled full-n comparison is amazon_fulln_streamed_gram"
            ),
            "baseline_scaled_s": round(baseline_scaled_s, 3),
            "device": str(jax.devices()[0]),
        },
    )


def amazon_sketched_frontier_metric():
    """Sketched-solver frontier on the Amazon sparse geometry (ISSUE 17
    tentpole claim): the randomized engines — CountSketch Iterative
    Hessian Sketch and SRHT sketch-and-precondition — against the
    20-iteration gather-engine L-BFGS wall (the reference-shaped path
    ``amazon_sparse_metric`` times), at MATCHED held-out quality on a
    row split the solvers never see.

    Each timed (engine, sketch_size) point is recorded as a stamped
    ``calibration_sweep`` decision (the same discipline as
    scripts/fit_cost_weights.py): the engine's own priced cost under
    the active weights goes in as the prediction, the measured wall is
    back-annotated via ``ref.stamp``, and the trace is replayed through
    ``obs.calibrate.calibration_report`` so the row carries
    predicted-vs-measured |log error| per engine — the acceptance
    evidence that the sketched tier is PRICED, not just fast.

    The row's ``accuracy_frontier`` / ``sketch_*`` keys are audited by
    ``_sketch_violations``: numeric ``sketch_size``,
    ``exact_baseline_s`` and a ``heldout_*`` quality metric are
    mandatory alongside any frontier claim.

    Env knobs: BENCH_SKETCH_N (train rows, default 500000) and
    BENCH_SKETCH_D (features, default 16384) — the csv:13 geometry;
    smaller values smoke the machinery on hosts that cannot QR a
    (2d, d) sketch at full width.
    """
    from keystone_tpu import obs
    from keystone_tpu.data import Dataset, one_hot_pm1
    from keystone_tpu.obs import calibrate as cal
    from keystone_tpu.ops.learning import cost as cost_mod
    from keystone_tpu.ops.learning.lbfgs import SparseLBFGSwithL2
    from keystone_tpu.ops.learning.sketch import (
        IterativeHessianSketch,
        SketchedLeastSquares,
    )
    from keystone_tpu.ops.sparse import sparse_matmul

    n = int(os.environ.get("BENCH_SKETCH_N", str(500_000)))
    d = int(os.environ.get("BENCH_SKETCH_D", str(NUM_FEATURES)))
    nnz, k = min(82, d // 4), 2
    iters = 20  # AmazonReviewsPipeline default numIters (scala :52)
    n_held = max(n // 10, 1_000)
    rng = np.random.default_rng(1)
    idx = rng.integers(0, d, size=(n + n_held, nnz)).astype(np.int32)
    idx.sort(axis=1)
    vals = rng.normal(size=(n + n_held, nnz)).astype(np.float32)
    labels = rng.integers(0, k, size=n + n_held)
    Y = one_hot_pm1(labels, k)
    ds = Dataset(
        {"indices": jnp.asarray(idx[:n]), "values": jnp.asarray(vals[:n])},
        n=n,
    )
    Yd = Dataset.of(jnp.asarray(Y[:n]))
    held_idx = jnp.asarray(idx[n:])
    held_val = jnp.asarray(vals[n:])
    held_labels = labels[n:]

    def heldout_accuracy(model):
        scores = sparse_matmul(held_idx, held_val, model.x)
        if getattr(model, "b_opt", None) is not None:
            scores = scores + model.b_opt
        pred = np.asarray(jnp.argmax(scores, axis=1))
        return float(np.mean(pred == held_labels))

    def timed_fit(est):
        def run():
            model = est.fit(ds, Yd)
            _sync_scalar(jnp.sum(jnp.abs(model.x)))
            return model

        elapsed, model, _ = min_wall(run, reps=2)
        return model, elapsed

    cpu_w, mem_w, net_w = cost_mod.active_weights()
    geometry = {"n": n, "d": d, "k": k, "sparsity": nnz / d, "machines": 1}

    def record_point(label, est, measured_s):
        """scripts/fit_cost_weights.py record_point discipline: a
        single-candidate calibration_sweep decision priced by the
        ACTUAL swept engine instance, measured wall stamped."""
        predicted = est.cost(
            n=n, d=d, k=k, sparsity=nnz / d, num_machines=1,
            cpu_weight=cpu_w, mem_weight=mem_w, network_weight=net_w,
        )
        ref = obs.record_cost_decision(obs.CostDecision(
            decision="calibration_sweep",
            winner=label,
            candidates=[{"label": label, "cost_s": predicted,
                         "feasible": True}],
            reason="sweep",
            context={**geometry, "weights": {
                "cpu": cpu_w, "mem": mem_w, "network": net_w,
                "family": cost_mod.weights_family_name(),
            }},
        ))
        ref.stamp(measured_s, timing="min_of_N_warm")

    m_base = 2 * (d + 1)
    sweep = [
        ("IterativeHessianSketch",
         IterativeHessianSketch(
             lam=1e-3, sketch_size=m_base, outer_iters=3, seed=7,
             num_features=d)),
        ("IterativeHessianSketch",
         IterativeHessianSketch(
             lam=1e-3, sketch_size=2 * m_base, outer_iters=3, seed=7,
             num_features=d)),
        ("SketchedLeastSquares",
         SketchedLeastSquares(
             lam=1e-3, sketch_size=m_base, pcg_iters=12, seed=7,
             num_features=d)),
    ]

    with obs.tracing() as t:
        baseline = SparseLBFGSwithL2(
            lam=1e-3, num_iterations=iters, num_features=d)
        model_exact, exact_s = timed_fit(baseline)
        exact_acc = heldout_accuracy(model_exact)
        frontier = []
        for label, est in sweep:
            model, wall = timed_fit(est)
            record_point(label, est, wall)
            frontier.append({
                "engine": label,
                "sketch_size": int(est._resolve_m(d + 1)),
                "wall_s": round(wall, 3),
                "heldout_accuracy": round(heldout_accuracy(model), 4),
                "model_max_abs_delta_vs_lbfgs": round(
                    float(jnp.max(jnp.abs(model.x - model_exact.x))), 5),
            })
    report = cal.calibration_report(cal.join_decisions(t.events))

    # Measured before/after for the fused CountSketch kernel (ISSUE 18
    # satellite): the sparse-chunk scatter pass ALONE, fused Pallas
    # sparse x dense-random product vs the flattened XLA scatter-add the
    # fold otherwise lowers to, at a small fixed geometry so the note
    # rides every run. kernel_active reports whether the kernel path
    # actually engages on this backend (pallas_direct_ok) — in interpret
    # mode the timing is the XLA emulation, stated, not a TPU claim.
    from keystone_tpu.ops import pallas_ops as _po

    cs_c, cs_s, cs_m, cs_d1 = 2048, 16, 512, 256
    rng_cs = np.random.default_rng(5)
    cs_idx = jnp.asarray(
        rng_cs.integers(0, cs_d1, (cs_c, cs_s)), jnp.int32)
    cs_val = jnp.asarray(rng_cs.normal(size=(cs_c, cs_s)), jnp.float32)
    cs_bucket = jnp.asarray(rng_cs.integers(0, cs_m, (cs_c,)), jnp.int32)
    cs_sign = jnp.asarray(
        rng_cs.choice(np.asarray([-1.0, 1.0], np.float32), cs_c))

    @jax.jit
    def _cs_xla(idxs, vs, bucket, sign):
        flat = jnp.zeros((cs_m * cs_d1 + 1,), jnp.float32)
        rows = bucket[:, None] * cs_d1 + idxs
        flat = flat.at[rows.reshape(-1)].add(
            (sign[:, None] * vs).reshape(-1))
        return flat[: cs_m * cs_d1].reshape(cs_m, cs_d1)

    @jax.jit
    def _cs_kernel(idxs, vs, bucket, sign):
        return _po.countsketch_scatter(
            idxs, vs, bucket, sign, cs_m, cs_d1)

    xla_wall, xla_out, _ = min_wall(
        lambda: jax.block_until_ready(
            _cs_xla(cs_idx, cs_val, cs_bucket, cs_sign)), reps=3)
    ker_wall, ker_out, _ = min_wall(
        lambda: jax.block_until_ready(
            _cs_kernel(cs_idx, cs_val, cs_bucket, cs_sign)), reps=3)
    cs_note = {
        "c": cs_c, "s": cs_s, "m": cs_m, "d1": cs_d1,
        "xla_scatter_wall_s": round(xla_wall, 5),
        "kernel_wall_s": round(ker_wall, 5),
        "wall_ratio": round(xla_wall / max(ker_wall, 1e-9), 3),
        "kernel_active": bool(_po.pallas_direct_ok(cs_idx, cs_val)),
        "backend": jax.default_backend(),
        "max_abs_delta": float(jnp.max(jnp.abs(xla_out - ker_out))),
    }

    # The claim is "faster at MATCHED held-out quality": the headline
    # point is the fastest sweep entry within tolerance of the exact
    # baseline's held-out accuracy (all points shown in the frontier).
    matched = [
        p for p in frontier
        if p["heldout_accuracy"] >= exact_acc - 0.005
    ]
    best = min(matched or frontier, key=lambda p: p["wall_s"])
    return make_row(
        "amazon_sketched_frontier_d16384",
        best["wall_s"],
        "s",
        round(exact_s / best["wall_s"], 4),
        "min_of_N_warm",
        {
            "n": n, "d": d, "nnz_per_row": nnz, "k": k,
            "timing_note": "each engine: warm fit, then min of 2 timed fits",
            "exact_baseline_s": round(exact_s, 3),
            "exact_baseline": (
                f"SparseLBFGSwithL2[gather] {iters} iters — the "
                "reference-shaped wall amazon_sparse_metric times"
            ),
            "heldout_rows": n_held,
            "heldout_accuracy": best["heldout_accuracy"],
            "heldout_accuracy_exact": round(exact_acc, 4),
            "sketch_size": best["sketch_size"],
            "sketch_engine_best": best["engine"],
            "accuracy_frontier": frontier,
            "countsketch_kernel": cs_note,
            "calibration": {
                "weights_family": report["weights_family"],
                "num_decisions": report["num_decisions"],
                "median_abs_log_error": report["median_abs_log_error"],
                "per_engine": report["per_engine"],
            },
            "device": str(jax.devices()[0]),
        },
    )


def amazon_hash_bits(cid, shape, salt):
    """Counter-based u32 generator (SplitMix-style multiply-xor): the
    regen stand-in for host I/O must not dominate the fold, and the
    threefry PRNG measures ~1.1 s per 5.4M-element chunk on this chip
    — 10x the chunk's actual densify+syrk work. Synthetic CONTENT does
    not affect GEMM/scatter throughput, so statistical polish buys
    nothing here (tests use jax.random; this generator is bench-local).

    The counter is built from 2-D iotas — a FLAT arange over the
    element count would create a single dimension past 2^31 at the
    n=36e6 capacity probe, which overflows TPU s32 indexing and
    crashes the worker process (observed, round 4).

    Module-level (not nested in the metric) so
    scripts/probe_amazon_headroom.py measures the EXACT generator the
    bench runs.
    """
    rows = jax.lax.broadcasted_iota(jnp.uint32, shape, 0)
    cols = (
        jax.lax.broadcasted_iota(jnp.uint32, shape, 1)
        if len(shape) > 1 else jnp.zeros(shape, jnp.uint32)
    )
    x = rows * jnp.uint32(shape[-1] if len(shape) > 1 else 1) + cols
    x = x + jnp.uint32(2654435761) * jnp.uint32(cid * 2 + salt + 1)
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x846CA68B)
    return x ^ (x >> 16)


def amazon_chunk_fn_factory(c, nnz, d, k, n_full):
    """The Amazon streamed fold's chunk generator (shared with the
    headroom probe): int16 indices + bf16 values regenerated per chunk,
    intercept lane, ragged-tail validity mask."""

    def chunk_fn(cid):
        bits = amazon_hash_bits(cid, (c, nnz), 0)
        idx = (bits % jnp.uint32(d)).astype(jnp.int16)
        u = amazon_hash_bits(cid, (c, nnz), 1)
        vals = (
            (u >> 8).astype(jnp.float32) * (3.464 / (1 << 24)) - 1.732
        ).astype(jnp.bfloat16)
        row = cid * c + jnp.arange(c)
        valid = row < n_full
        idx1 = jnp.concatenate(
            [idx.astype(jnp.int32), jnp.where(valid, d, -1)[:, None]],
            axis=1,
        )
        val1 = jnp.concatenate(
            [
                jnp.where(valid[:, None], vals, 0),
                valid.astype(jnp.bfloat16)[:, None],
            ],
            axis=1,
        )
        y = (amazon_hash_bits(cid, (c,), 2) % jnp.uint32(k)).astype(jnp.int32)
        Y = jnp.where(
            valid[:, None],
            2.0 * jax.nn.one_hot(y, k, dtype=jnp.float32) - 1.0,
            0.0,
        )
        return idx1, val1, Y

    return chunk_fn


def amazon_fulln_metric():
    """The REAL Amazon row, no n-scaling: n=65,000,000 × d=16384 sparse
    ridge, 20 L-BFGS iterations, on one chip.

    The dataset does not fit HBM at any COO precision (43 GB at int32+f32,
    21.6 GB at the compressed int16+bf16 4 B/nnz format), so the fit
    STREAMS: chunks are produced per scan step, folded into G = AᵀA
    (densify + accumulating MXU syrk), and the 20 iterations run on G —
    the same iterate sequence as per-pass LBFGS (tests/test_sparse_gram).
    Chunk production here regenerates synthetic rows device-side from the
    PRNG — the stand-in for host I/O, which every bench row excludes; a
    production host streams ~21.6 GB once over PCIe (~1-2 s at 16-32 GB/s,
    overlappable with the ~2-min fold).

    The r05 rounds carried an ad-hoc resident-capacity probe here; that
    became a real tier (data/resident.py) measured by its own row —
    amazon_resident_compressed_metric.
    """
    from keystone_tpu.ops.learning.lbfgs import run_lbfgs_gram_streamed
    from keystone_tpu.ops import pallas_ops

    d, nnz, k = NUM_FEATURES, 82, 2
    iters = 20
    n_full = int(os.environ.get("BENCH_AMAZON_N", str(65_000_000)))
    c = 65_536
    w = nnz + 1  # +1 intercept lane (index d, value 1)
    num_chunks = -(-n_full // c)
    use_pallas = pallas_ops.pallas_enabled()

    chunk_fn = amazon_chunk_fn_factory(c, nnz, d, k, n_full)

    def run_once():
        W, loss = run_lbfgs_gram_streamed(
            chunk_fn, num_chunks, d + 1, k, lam=1e-3,
            num_iterations=iters, n=n_full, use_pallas=use_pallas,
            val_dtype=jnp.bfloat16,
            # ~1000 chunks is minutes of device time; one dispatch that
            # long trips the worker watchdog (observed crash) — segment.
            max_chunks_per_dispatch=128,
        )
        return float(loss)

    # Min-of-N warm, the TIMIT headline convention (VERDICT r5 Weak #1 —
    # the old single cold run folded ~1 min of compile into a ~9 min wall,
    # leaving the two headline rows on different conventions). The cold
    # run is timed too: compile cost is REPORTED as its own field instead
    # of vanishing or polluting the wall. BENCH_AMAZON_REPS trims the warm
    # count for smoke runs (each warm rep is the full fold).
    reps = max(int(os.environ.get("BENCH_AMAZON_REPS", "2")), 1)
    elapsed, loss, cold_wall_s = min_wall(run_once, reps=reps)
    assert np.isfinite(loss), f"bad streamed sparse solve: {loss}"
    compile_s_est = max(cold_wall_s - elapsed, 0.0)

    flop_syrk = 1.0 * n_full * (d + 1024) ** 2  # executed MACs x2, padded d
    baseline_s = 52.290
    return make_row(
        "amazon_fulln_streamed_gram",
        round(elapsed, 3),
        "s",
        round(baseline_s / elapsed, 4),
        "min_of_N_warm",
        {
            "n": n_full, "d": d, "nnz_per_row": nnz, "k": k, "iters": iters,
            "streamed": (
                "chunks regenerated device-side per scan step (the I/O "
                "stand-in; all bench rows exclude input I/O); working set "
                "~2.3 GB regardless of n; 128-chunk dispatch segments"
            ),
            "timing_note": (
                f"cold run timed (compile included, reported separately), "
                f"then min of {reps} warm full folds — the TIMIT headline "
                f"convention; BENCH_AMAZON_REPS trims warm reps for smoke "
                f"runs"
            ),
            "cold_wall_s": round(cold_wall_s, 3),
            "compile_s_est": round(compile_s_est, 3),
            "warm_reps": reps,
            "engine": (
                "densify-chunk + accumulating MXU syrk -> G, then 20 "
                "L-BFGS iterations on G (same iterates as per-pass LBFGS; "
                "tests/test_sparse_gram.py)"
            ),
            "flop_model_executed_tflops": round(flop_syrk / 1e12, 1),
            "achieved_tflops": round(flop_syrk / 1e12 / elapsed, 1),
            "final_loss": round(loss, 4),
            "capacity": {
                "coo_int32_f32_gb": round(n_full * nnz * 8 / 1e9, 1),
                "coo_int16_bf16_gb": round(n_full * nnz * 4 / 1e9, 1),
                "hbm_gb": 16,
                "resident_tier_note": (
                    "the r05 ad-hoc resident probe was promoted to a "
                    "real tier (data/resident.py); its measured row is "
                    "amazon_fulln_resident_compressed"
                ),
            },
            "baseline": (
                "16x r3.4xlarge Spark LBFGS 52.29s at the SAME n=65e6 "
                "(csv:13) — literal comparison, NO n-scaling"
            ),
            "honesty": (
                "one chip loses this full-n wall-clock to the 16-node "
                "cluster; the claim is capacity + exactness (same LBFGS "
                "iterates, bounded working set, any n streams), not speed"
            ),
            "headroom_r6": {
                "note": (
                    "round-6 chunk loop (the measured 33% non-syrk "
                    "overhead of r5 claimed at the kernel/overlap level): "
                    "(1) the correlation A^T Y is FUSED into the "
                    "accumulating syrk's grid (pallas_ops."
                    "gram_corr_sym_acc — one kernel per chunk; the "
                    "separate GEMM re-read the whole 2.3 GB slab from "
                    "HBM), and (2) chunk k+1's regen+densify is "
                    "double-buffered through the scan carry against "
                    "chunk k's kernel (sparse_gram_fold pipeline=True — "
                    "the device-compute analog of data/prefetch.py's "
                    "host double buffer), costing one extra resident "
                    "slab. Stage decomposition: scripts/"
                    "probe_amazon_headroom.py measures regen, syrk-only, "
                    "fused syrk+corr, and serial-vs-pipelined whole-fold "
                    "per-chunk on-chip. The r5 measured floors stand "
                    "BELOW the target: syrk-only 0.132 s/chunk "
                    "(148.7 TF/s slab ceiling => 131.4 s full-n floor); "
                    "r5 whole-fold was 0.198 s/chunk."
                ),
                "target_s_per_chunk": 0.15,
                "target_fulln_warm_s": 170.0,
                "measured_s_per_chunk_warm": round(elapsed / num_chunks, 4),
                "r5_fold_s_per_chunk_warm": 0.198,
                "r5_syrk_floor_s_per_chunk": 0.132,
                "syrk_ceiling_tflops": 148.7,
                "fold_floor_fulln_s": 131.4,
            },
            "device": str(jax.devices()[0]),
        },
    )


def _multichip_subprocess(extra_args, trace_dir=None, timeout_s=1800):
    """Run ``bin/multichip``'s forced-8-host-device leg in a SUBPROCESS:
    this bench process's XLA backend is already initialized (one CPU
    device), and ``--xla_force_host_platform_device_count`` only takes
    effect at backend init — so the parity leg gets its own interpreter
    with 8 forced host devices."""
    import subprocess
    import sys as _sys

    cmd = [_sys.executable, "-m", "keystone_tpu.tools.multichip",
           "--force-host-devices", "8"] + list(extra_args)
    if trace_dir:
        cmd += ["--trace", trace_dir]
    return subprocess.run(
        cmd, capture_output=True, text=True, timeout=timeout_s,
        cwd=os.path.dirname(os.path.abspath(__file__)),
    )


def _multichip_encode_sample(d, w, k, sample_rows=65_536, parts=8):
    """MEASURED host-side encode+partition leg (the 'encode timed
    separately' half of the multichip row's accounting): a sampled slice
    of Amazon-like rows through ``CompressedCOOChunks.encode`` and
    ``partition(8)`` (each partition re-checks the int16 boundary
    against ITS indices — data/resident.py). The full-n number is an
    explicitly-labeled PROJECTION from the measured rows/s, never folded
    into any wall."""
    from keystone_tpu.data import resident

    rng = np.random.default_rng(0)
    idx = rng.integers(0, d, size=(sample_rows, w)).astype(np.int32)
    idx[rng.random((sample_rows, w)) < 0.2] = -1
    val = rng.normal(size=(sample_rows, w)).astype(np.float32)
    Y = rng.normal(size=(sample_rows, k)).astype(np.float32)
    t0 = time.perf_counter()
    chunks = resident.CompressedCOOChunks.encode(
        idx, val, Y, chunk_rows=4096, d=d,
    )
    chunks.partition(parts)
    encode_s = time.perf_counter() - t0
    rows_per_s = sample_rows / max(encode_s, 1e-9)
    return {
        "sampled_rows": sample_rows,
        "measured_encode_partition_s": round(encode_s, 4),
        "measured_rows_per_s": round(rows_per_s, 1),
        "num_partitions": parts,
        "per_partition_boundary_check": (
            "each partition re-validates int16 against its own rebased "
            "index range at encode (data/resident.py; "
            "tests/test_resident.py)"
        ),
        "note": (
            "host-side encode measured on a sample and reported "
            "SEPARATELY from fit walls; full-n figures below are "
            "projections from the measured rate, labeled as such"
        ),
    }


def multichip_amazon_fulln_metric():
    """The 8-chip mesh row for the Amazon full-n fit (ISSUE 16 tentpole):
    data-parallel streamed gram folds — each device folds its contiguous
    chunk shard locally, ONE psum tree-reduction of (G, AtY, yty) per
    fit crosses the ICI — targeting the 16-node Spark cluster's 52.29 s
    at the SAME n=65e6 (single chip measured 223.8 s).

    Honest split by backend:

    - **chips** (multi-device non-CPU backend): the measurement leg —
      full-n mesh fit, min-of-N warm, layout from
      ``cost.choose_mesh_layout`` with the decision stamped for
      bin/calibrate, per-device span evidence from a traced warm rep.
    - **this container** (CPU): the forced-8-host-device PARITY leg runs
      in a subprocess (``bin/multichip``): the mesh program — sharding,
      liveness masks, the one psum — is exercised end-to-end and checked
      bit-close against the 1-device fold. The row records
      ``skipped_on_host: true`` and the parity evidence; it never
      fabricates a device wall or a speedup.

    Either way the host-side encode+partition cost is measured
    separately on a sample (``_multichip_encode_sample``) — fit walls
    exclude ingestion by convention, so its cost is REPORTED, not
    hidden.
    """
    import re as _re

    from keystone_tpu.ops.learning import cost as cost_mod

    d, nnz, k = NUM_FEATURES, 82, 2
    iters = 20
    n_full = int(os.environ.get("BENCH_AMAZON_N", str(65_000_000)))
    c = 65_536
    w = nnz + 1
    num_chunks = -(-n_full // c)
    cluster_baseline_s = 52.290
    single_chip_measured_s = 223.8  # amazon_fulln_streamed_gram, r09
    devices = jax.devices()
    on_chips = jax.default_backend() != "cpu" and len(devices) >= 2

    # Layout priced for the 8-chip TARGET either way (the plan is real
    # even when the chips are not); on chips the runner's traced
    # decision is additionally stamped with the measured wall.
    (p, q), _ = cost_mod.choose_mesh_layout(
        n_full, d + 1, k, nnz_per_row=w,
        num_devices=len(devices) if on_chips else 8,
    )
    layout = {
        "winner": cost_mod.mesh_layout_label(p, q),
        "predicted_fold_s": round(
            cost_mod.price_mesh_layout(n_full, d + 1, k, p, q,
                                       nnz_per_row=w), 6,
        ),
        "per_device_resident_gb": round(
            cost_mod.mesh_layout_resident_bytes(
                n_full, d + 1, k, p, nnz_per_row=w) / 1e9, 2,
        ),
        "note": (
            "cost.choose_mesh_layout over (1x1, 4x1, 4x2, 8x1); the "
            "decision event flows to bin/calibrate when traced "
            "(tests/test_cost_replay.py pins this winner)"
        ),
    }
    encode = _multichip_encode_sample(d, w, k)
    encode["projected_fulln_encode_s"] = round(
        n_full / encode["measured_rows_per_s"], 1,
    )

    target = {
        "cluster_baseline_s": cluster_baseline_s,
        "single_chip_measured_s": single_chip_measured_s,
        "goal": "beat 52.29 s at the SAME n=65e6 on 8 chips",
        "required_speedup_vs_single_chip": round(
            single_chip_measured_s / cluster_baseline_s, 2,
        ),
        "ideal_8chip_from_single_chip_s": round(
            single_chip_measured_s / 8, 1,
        ),
        "fold_floor_8chip_s": round(131.4 / 8, 1),
    }

    if not on_chips:
        # Forced-host parity leg (subprocess; tier-1-safe geometry).
        mc_n = int(os.environ.get("BENCH_MULTICHIP_N", "20000"))
        trace_dir = os.path.join("/tmp", f"bench_mc_trace_{os.getpid()}")
        proc = _multichip_subprocess(
            ["--n", str(mc_n), "--d", "256", "--nnz", "16",
             "--chunk", "512", "--seg", "4", "--iters", str(iters)],
            trace_dir=trace_dir,
        )
        if proc.returncode != 0:
            raise RuntimeError(
                f"multichip parity leg failed (rc {proc.returncode}): "
                f"{proc.stderr[-2000:]}"
            )
        out = proc.stdout
        parity = float(_re.search(
            r"parity max\|dW\|: ([0-9.e+-]+)", out).group(1))
        mesh_wall = float(_re.search(
            r"mesh wall:\s+([0-9.]+)s", out).group(1))
        single_wall = float(_re.search(
            r"single-device wall:\s+([0-9.]+)s", out).group(1))

        # Per-device span evidence from the subprocess's trace: the
        # fold dispatches carry device tags; counts are real, walls are
        # host walls.
        import shutil

        from keystone_tpu.obs.export import device_of_span_args, load_events
        spans = [e for e in load_events(trace_dir)
                 if e.get("type") == "span"]
        shutil.rmtree(trace_dir, ignore_errors=True)
        dev_spans = {}
        for s in spans:
            dev = device_of_span_args(s.get("args") or {})
            if dev is not None:
                row = dev_spans.setdefault(dev, {"spans": 0, "busy_s": 0.0})
                row["spans"] += 1
                row["busy_s"] = round(
                    row["busy_s"] + s.get("dur_us", 0) / 1e6, 4,
                )

        return make_row(
            "multichip_amazon_fulln",
            round(mesh_wall, 3),
            "s",
            None,
            "host_only",
            {
                "skipped_on_host": True,
                "why": (
                    "no multi-chip accelerator backend in this "
                    "container; the forced-8-host-device parity leg ran "
                    "instead (8 XLA host devices share ONE CPU, so its "
                    "walls are program evidence, not device time — no "
                    "device wall or speedup is fabricated)"
                ),
                "value_note": (
                    "value = the parity leg's mesh wall at the reduced "
                    "geometry below, timing host_only; the full-n "
                    "device measurement needs chips (bin/multichip)"
                ),
                "parity": {
                    "max_dw": parity,
                    "tol": 5e-5,
                    "passed": True,
                    "legs": (
                        "1-device fold vs 8-forced-device mesh fold "
                        "(per-device local folds + one psum), same "
                        "arithmetic reassociated"
                    ),
                },
                "parity_leg_geometry": {
                    "n": mc_n, "d": 256, "nnz_per_row": 16, "k": k,
                    "chunk": 512, "seg": 4, "iters": iters,
                    "single_device_wall_s": single_wall,
                    "mesh_wall_s": mesh_wall,
                },
                "span_evidence": {
                    "per_device_spans": dev_spans,
                    "note": (
                        "fold.segment dispatches carry device= tags "
                        "(bin/trace renders the per-device occupancy "
                        "table; Perfetto puts each device on its own "
                        "track); per-lane read.d<k> evidence: "
                        "tests/test_multichip.py"
                    ),
                },
                "target": target,
                "mesh_layout": layout,
                "encode": encode,
                "full_geometry": {
                    "n": n_full, "d": d, "nnz_per_row": nnz, "k": k,
                    "iters": iters, "num_chunks": num_chunks,
                },
                "device": str(devices[0]),
            },
        )

    # ---- chips: the measurement leg --------------------------------------
    from keystone_tpu import obs
    from keystone_tpu.obs import tracer as tracer_mod
    from keystone_tpu.ops import pallas_ops
    from keystone_tpu.ops.learning.lbfgs import run_lbfgs_gram_streamed
    from keystone_tpu.parallel import mesh as mesh_lib

    use_pallas = pallas_ops.pallas_enabled()
    base_fn = amazon_chunk_fn_factory(c, nnz, d, k, n_full)
    m = p * q
    if q > 1:
        mesh = mesh_lib.make_mesh(
            (p, q), (mesh_lib.DATA_AXIS, mesh_lib.MODEL_AXIS),
            devices=devices[:m],
        )
    else:
        mesh = mesh_lib.make_mesh(
            (p,), (mesh_lib.DATA_AXIS,), devices=devices[:p],
        )
    cpd = -(-num_chunks // p)

    def mesh_chunk_fn(cid):
        # Runs INSIDE the shard_map fold: the device-local chunk id is
        # rebased to the global id this device owns, so regen stays
        # device-side (no host ingest in the timed wall — same
        # convention as amazon_fulln_streamed_gram, reported above).
        return base_fn(
            jax.lax.axis_index(mesh_lib.DATA_AXIS) * cpd + cid
        )

    def run_once():
        W, loss = run_lbfgs_gram_streamed(
            mesh_chunk_fn, num_chunks, d + 1, k, lam=1e-3,
            num_iterations=iters, n=n_full, use_pallas=use_pallas,
            val_dtype=jnp.bfloat16, max_chunks_per_dispatch=128,
            mesh=mesh, operands=(),
        )
        return float(loss)

    reps = max(int(os.environ.get("BENCH_AMAZON_REPS", "2")), 1)
    elapsed, loss, cold_wall_s = min_wall(run_once, reps=reps)
    assert np.isfinite(loss), f"bad mesh streamed solve: {loss}"

    # One traced warm rep for the per-device span + overlap evidence
    # (outside the timed min — tracing overhead must not ride the wall).
    span_evidence = {}
    overlap = {}
    if not obs.enabled():
        try:
            with obs.tracing() as t:
                run_once()
        finally:
            tracer_mod._ACTIVE = None
        folds = [e for e in t.events if e.get("type") == "span"
                 and e.get("name") == "fold.segment"]
        fold_busy = sum(e.get("dur_us", 0) for e in folds) / 1e6
        span_evidence = {
            "fold_dispatches": len(folds),
            "device_tags": sorted({
                (e.get("args") or {}).get("device") for e in folds
            }),
            "num_devices": m,
        }
        overlap = {
            "fold_busy_s": round(fold_busy, 3),
            "solve_and_psum_s": round(max(elapsed - fold_busy, 0.0), 3),
            "note": (
                "per-site split from the traced rep: fold dispatches "
                "(device-parallel) vs the remainder (one psum + "
                "replicated L-BFGS-on-G)"
            ),
        }

    single_wall = None
    if os.environ.get("BENCH_MULTICHIP_SINGLE", "1") == "1":
        def single_once():
            W, loss = run_lbfgs_gram_streamed(
                base_fn, num_chunks, d + 1, k, lam=1e-3,
                num_iterations=iters, n=n_full, use_pallas=use_pallas,
                val_dtype=jnp.bfloat16, max_chunks_per_dispatch=128,
            )
            return float(loss)

        single_wall, _, _ = min_wall(single_once, reps=1)

    detail = {
        "n": n_full, "d": d, "nnz_per_row": nnz, "k": k, "iters": iters,
        "num_chunks": num_chunks,
        "skipped_on_host": False,
        "mesh": f"{p}x{q} ({m} devices)",
        "engine": (
            "per-device local gram folds over contiguous chunk shards "
            "+ ONE psum tree-reduction of (G, AtY, yty) per fit, then "
            "the replicated L-BFGS-on-G solve"
        ),
        "cold_wall_s": round(cold_wall_s, 3),
        "warm_reps": reps,
        "target": target,
        "mesh_layout": layout,
        "encode": encode,
        "span_evidence": span_evidence,
        "per_site_overlap": overlap,
        "streamed": (
            "chunks regenerated device-side per scan step inside each "
            "device's shard (the I/O stand-in; all bench rows exclude "
            "input I/O); encode cost reported separately above"
        ),
        "device": str(devices[0]),
    }
    if single_wall is not None:
        detail["speedup"] = {
            "speedup_vs_single_device": round(single_wall / elapsed, 2),
            "num_devices": m,
            "single_device_baseline_s": round(single_wall, 3),
        }
    return make_row(
        "multichip_amazon_fulln",
        round(elapsed, 3),
        "s",
        round(cluster_baseline_s / elapsed, 4),
        "min_of_N_warm",
        detail,
    )


def multichip_timit_scaling_metric():
    """Scaling-efficiency row (ISSUE 16): the streamed gram fit at
    1/2/4/8 devices through ``bin/multichip --scaling``, every
    speedup/scaling_efficiency claim carrying its numeric num_devices
    and single_device_baseline_s in the SAME dict (the make_row audit
    rule this PR adds), and the bend in the curve ATTRIBUTED to a named
    phase from the per-leg fold/solve span split — not guessed.

    On this container the legs run on 8 FORCED HOST devices sharing one
    CPU: the walls are real host walls and the phase decomposition is
    real program structure, but they are NOT device evidence — the row
    says so (``device_evidence: false``, ``skipped_on_host: true``)
    instead of presenting host anti-scaling (or fabricated scaling) as
    chip behavior. On chips the same runner reports the measured curve;
    the single-chip TIMIT reference (4.17 s / 0.78 MFU) is the wall the
    1-device leg is held against there."""
    mc_n = int(os.environ.get("BENCH_MULTICHIP_SCALING_N", "20000"))
    proc = _multichip_subprocess(
        ["--scaling", "--n", str(mc_n), "--d", "256", "--nnz", "16",
         "--chunk", "512", "--seg", "4", "--iters", "20", "--reps", "2"],
    )
    if proc.returncode != 0:
        raise RuntimeError(
            f"multichip scaling legs failed (rc {proc.returncode}): "
            f"{proc.stderr[-2000:]}"
        )
    payload = None
    for line in proc.stdout.splitlines():
        if line.startswith("scaling: "):
            payload = json.loads(line[len("scaling: "):])
    assert payload is not None, proc.stdout[-2000:]
    legs = payload["legs"]
    assert [leg["num_devices"] for leg in legs] == [1, 2, 4, 8], legs
    eff8 = legs[-1]["scaling_efficiency"]
    device_evidence = bool(payload["device_evidence"])

    return make_row(
        "multichip_timit_scaling",
        eff8,
        "fraction",
        None,
        "single_run_warm" if device_evidence else "host_only",
        {
            "skipped_on_host": not device_evidence,
            "device_evidence": device_evidence,
            "why": (
                "8 forced host devices share ONE CPU: adding 'devices' "
                "adds sharding work without adding silicon, so the host "
                "curve anti-scales — reported as program evidence (the "
                "phase split is real), never as chip scaling"
            ) if not device_evidence else (
                "measured on a multi-device accelerator backend"
            ),
            "legs": legs,
            "bend": payload["bend"],
            "bend_phase": payload["bend"]["phase"],
            "parity": {
                "worst_max_dw": payload["parity_worst_max_dw"],
                "tol": payload["parity_tol"],
                "passed": True,
            },
            "geometry": payload["geometry"],
            "value_note": (
                "value = scaling efficiency at 8 devices (speedup/8); "
                "legs carry per-device walls, fold/solve phase split, "
                "and the audit-required num_devices + "
                "single_device_baseline_s fields"
            ),
            "chip_reference": {
                "timit_single_chip_s": 4.17,
                "timit_single_chip_mfu": 0.78,
                "note": (
                    "on chips the 1-device leg is held against the "
                    "TIMIT headline wall; near-linear fold scaling is "
                    "the target, the replicated solve+psum is the "
                    "expected bend (Amdahl term, named in bend.phase)"
                ),
            },
            "runner": "bin/multichip --scaling (subprocess, 8 forced "
                      "host devices)",
            "device": str(jax.devices()[0]),
        },
    )


def _amazon_host_bits(cid, shape, salt):
    """Numpy mirror of :func:`amazon_hash_bits` (same SplitMix constants,
    uint32 wraparound) — the HOST-side generator the resident-compressed
    row's streamed tail reads through the data-plane runtime, standing in
    for real disk/network ingestion so the per-site overlap fractions
    measure genuine host->device staging."""
    rows = np.arange(shape[0], dtype=np.uint32)[:, None]
    if len(shape) > 1:
        cols = np.arange(shape[1], dtype=np.uint32)[None, :]
        x = rows * np.uint32(shape[-1]) + cols
    else:
        x = rows[:, 0]
    with np.errstate(over="ignore"):
        x = x + np.uint32(2654435761) * np.uint32(cid * 2 + salt + 1)
        x = x ^ (x >> np.uint32(16))
        x = x * np.uint32(0x7FEB352D)
        x = x ^ (x >> np.uint32(15))
        x = x * np.uint32(0x846CA68B)
        return x ^ (x >> np.uint32(16))


def amazon_resident_compressed_metric():
    """The compressed-resident successor of the r05 probe (ISSUE 8): the
    REAL n=65e6 Amazon row with the working set routed through the
    int16+bf16 tier (data/resident.py, 4 B/nnz):

      - rows [0, n_res) live CHIP-RESIDENT as compressed chunks — the
        fold slices them in place (pipeline=False; decode is the
        densify's casts) with no regen and no IO at all;
      - the tail that truly cannot fit streams HOST->device: a numpy
        generator (the IO stand-in) feeding compressed segments through
        the data-plane runtime's prefetcher, so the row's per-site
        overlap report (read/verify/compute,
        utils.profiling.overlap_report) measures real staging overlap.

    The one-time encode pass is timed separately from the warm fold —
    the "pay an encoding pass once so the hot loop touches only packed
    bytes" trade the PAPERS.md sparse-fixed-matrix line formalizes.
    Targets (ISSUE 8 acceptance): warm fold <= 150 s vs the 131.4 s
    measured single-chip fold floor; checkpoint-on overhead stays <5%
    (the recovery_overhead row's gate).
    """
    from keystone_tpu.data.prefetch import PrefetchStats, ShardSource
    from keystone_tpu.ops import pallas_ops
    from keystone_tpu.ops.learning.lbfgs import (
        _resident_chunk_fn,
        run_lbfgs_gram_hybrid,
    )
    from keystone_tpu.utils import profiling

    d, nnz, k = NUM_FEATURES, 82, 2
    iters = 20
    n_full = int(os.environ.get("BENCH_AMAZON_N", str(65_000_000)))
    c = 65_536
    w = nnz + 1  # +1 intercept lane (index d, value 1)
    num_chunks = -(-n_full // c)
    seg = 16  # chunks per host segment & dispatch (~350 MB staged x2)
    use_pallas = pallas_ops.pallas_enabled()
    # Resident share: 28e6 rows of compressed chunks (idx+val+labels
    # ~9.7 GB — under the measured 9.8 GB r05 point, leaving fold
    # workspace headroom below the 11.8 GB cliff). Scaled-down smoke
    # runs keep the same ~43% share.
    n_res_default = min(28_000_000, int(n_full * 28 / 65))
    n_res = (int(os.environ.get("BENCH_AMAZON_RESIDENT_N",
                                str(n_res_default))) // c) * c
    num_res_chunks = min(n_res // c, num_chunks)
    chunk_fn = amazon_chunk_fn_factory(c, nnz, d, k, n_full)

    # --- encode pass: build the resident compressed chunks (device-side
    # generation stands in for the host encode; the LAYOUT is exactly
    # data/resident.py's — int16 indices incl. the intercept lane at
    # d < 2^15, bf16 values, f32 labels). Timed separately.
    def compressed_chunk(cid):
        idx1, val1, Y = chunk_fn(cid)
        return idx1.astype(jnp.int16), val1, Y

    @jax.jit
    def encode_resident():
        return jax.lax.map(compressed_chunk, jnp.arange(num_res_chunks))

    t0 = time.perf_counter()
    if num_res_chunks:
        idx_r, val_r, y_r = encode_resident()
        _sync_scalar(jnp.sum(val_r[0, 0].astype(jnp.float32)))
    else:
        # Scaled-down smoke runs (BENCH_AMAZON_N below one chunk's
        # resident share, or BENCH_AMAZON_RESIDENT_N=0) carry no
        # resident leg: the whole row streams through the tail.
        import ml_dtypes

        idx_r = jnp.zeros((0, c, w), jnp.int16)
        val_r = jnp.zeros((0, c, w), jnp.dtype(ml_dtypes.bfloat16))
        y_r = jnp.zeros((0, c, k), jnp.float32)
    encode_pass_s = time.perf_counter() - t0  # includes its compile

    class TailSource(ShardSource):
        """Host-generated compressed segments for chunks
        [num_res_chunks, num_chunks) — segment-relative layout, the
        run_lbfgs_gram_hybrid tail contract."""

        n_true = n_full

        @property
        def num_segments(self):
            return -(-(num_chunks - num_res_chunks) // seg)

        def load(self, s):
            import ml_dtypes

            idx = np.full((seg, c, w), -1, np.int16)
            val = np.zeros((seg, c, w), np.dtype(ml_dtypes.bfloat16))
            ys = np.zeros((seg, c, k), np.float32)
            for j in range(seg):
                cid = num_res_chunks + s * seg + j
                if cid >= num_chunks:
                    break  # phantom tail chunks stay inactive
                bits = _amazon_host_bits(cid, (c, nnz), 0)
                u = _amazon_host_bits(cid, (c, nnz), 1)
                row = cid * c + np.arange(c)
                valid = row < n_full
                idx[j, :, :nnz] = (bits % np.uint32(d)).astype(np.int16)
                idx[j, :, nnz] = np.where(valid, d, -1)
                vals = (
                    (u >> np.uint32(8)).astype(np.float32)
                    * (3.464 / (1 << 24)) - 1.732
                )
                val[j, :, :nnz] = np.where(valid[:, None], vals, 0.0)
                val[j, :, nnz] = valid
                yid = _amazon_host_bits(cid, (c,), 2) % np.uint32(k)
                onehot = 2.0 * np.eye(k, dtype=np.float32)[yid] - 1.0
                ys[j] = np.where(valid[:, None], onehot, 0.0)
            return idx, val, ys

    stats_box = {}

    def run_once():
        stats = PrefetchStats()
        W, loss = run_lbfgs_gram_hybrid(
            _resident_chunk_fn, num_res_chunks, (idx_r, val_r, y_r),
            num_chunks, d + 1, k, lam=1e-3, num_iterations=iters,
            n=n_full, use_pallas=use_pallas, val_dtype=jnp.bfloat16,
            max_chunks_per_dispatch=seg, segment_source=TailSource(),
            prefetch_depth=2, prefetch_stats=stats,
            # One extra staged slab beside ~10 GB resident busts the
            # workspace ceiling the r05 probe measured.
            pipeline=False,
        )
        stats_box["stats"] = stats
        return float(loss)

    reps = max(int(os.environ.get("BENCH_AMAZON_REPS", "2")), 1)
    elapsed, loss, cold_wall_s = min_wall(run_once, reps=reps)
    assert np.isfinite(loss), f"bad hybrid compressed solve: {loss}"
    stats = stats_box["stats"]
    overlap_sites = {
        site: {kk: (round(vv, 4) if isinstance(vv, float) else vv)
               for kk, vv in entry.items()}
        for site, entry in profiling.overlap_report(stats).items()
    }

    flop_syrk = 1.0 * n_full * (d + 1024) ** 2  # executed MACs x2
    baseline_s = 52.290
    resident_gb = (n_res * w * 4 + n_res * k * 4) / 1e9
    return make_row(
        "amazon_fulln_resident_compressed",
        round(elapsed, 3),
        "s",
        round(baseline_s / elapsed, 4),
        "min_of_N_warm",
        {
            "n": n_full, "d": d, "nnz_per_row": nnz, "k": k,
            "iters": iters,
            "tier": (
                f"rows [0, {n_res}) chip-resident as int16+bf16 "
                f"compressed chunks (data/resident.py, 4 B/nnz; decode "
                f"fused into the fold's densify casts); rows "
                f"[{n_res}, {n_full}) streamed host->device through the "
                f"data-plane runtime's read lane in {seg}-chunk "
                f"segments, prefetch depth 2"
            ),
            "timing_note": (
                f"encode pass timed once separately (compile included); "
                f"fold: cold run timed (compile reported separately), "
                f"then min of {reps} warm full folds"
            ),
            "encode_pass_s": round(encode_pass_s, 3),
            "cold_wall_s": round(cold_wall_s, 3),
            "compile_s_est": round(max(cold_wall_s - elapsed, 0.0), 3),
            "warm_reps": reps,
            "final_loss": round(loss, 4),
            "flop_model_executed_tflops": round(flop_syrk / 1e12, 1),
            "achieved_tflops": round(flop_syrk / 1e12 / elapsed, 1),
            "overlap_sites": overlap_sites,
            "overlap_note": (
                "per-site busy/wait/hidden seconds + overlap fraction "
                "(utils.profiling.overlap_report) from the LAST warm "
                "fold: `read` is host segment generation+staging on the "
                "runtime worker, `compute` the fold dispatch wall — the "
                "fold-floor audit: wall - compute.busy must be visible "
                "as read waits"
            ),
            "capacity": {
                "resident_compressed_gb": round(resident_gb, 1),
                "resident_rows": n_res,
                "coo_int16_bf16_fulln_gb": round(n_full * w * 4 / 1e9, 1),
                "coo_int32_f32_fulln_gb": round(n_full * w * 8 / 1e9, 1),
                "hbm_gb": 16,
            },
            "targets": {
                "fold_floor_fulln_s": 131.4,
                "target_fulln_warm_s": 150.0,
                "r05_streamed_measured_s": 223.8,
            },
            "baseline": (
                "16x r3.4xlarge Spark LBFGS 52.29s at the SAME n=65e6 "
                "(csv:13) — literal comparison, NO n-scaling"
            ),
            "device": str(jax.devices()[0]),
        },
    )


def krr_metric():
    """RandomPatchCifarKernel's KRR solver geometry
    (RandomPatchCifarKernel.scala:33-76: Gaussian-kernel ridge, CIFAR-scale
    n, block Gauss-Seidel). No reference wall-clock exists for this
    pipeline, so the row reports absolute device time + MFU only.

    Two kernel-generation engines are timed: exact f32 (6-pass MXU) and
    bf16x3 (3-pass bf16 decomposition — half the dominant GEMM's cost at
    ~2e-16-operand error; raw single-pass bf16 is REJECTED for this λ
    regime with measured divergence — tests/test_kernel_bf16.py). The
    headline value is the bf16x3 engine; quality is pinned by the
    max-abs prediction delta between the two fits.
    """
    from keystone_tpu.data import Dataset
    from keystone_tpu.ops.learning.kernel import (
        GaussianKernelGenerator,
        KernelRidgeRegression,
    )

    n, d, k, bs, epochs = 32_768, 2_048, 10, 4_096, 2
    gamma, lam = 5e-4, 1e-3
    rng = np.random.default_rng(2)
    X = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    Y = jnp.asarray(rng.normal(size=(n, k)).astype(np.float32))
    ds, ys = Dataset.of(X), Dataset.of(Y)

    def timed_fit(kdtype):
        krr = KernelRidgeRegression(
            GaussianKernelGenerator(gamma=gamma, kernel_dtype=kdtype),
            lam=lam, block_size=bs, num_epochs=epochs,
        )

        def run():
            m = krr.fit(ds, ys)
            _sync_scalar(jnp.sum(jnp.abs(m.w_locals[0])))
            return m

        elapsed, m, _ = min_wall(run, reps=2)
        return m, elapsed

    m32, elapsed_f32 = timed_fit("f32")
    m3, elapsed = timed_fit("bf16x3")
    # Quality pin: prediction delta between engines on a held-out batch.
    Xt = Dataset.of(jnp.asarray(rng.normal(size=(4096, d)).astype(np.float32)))
    p32 = jnp.asarray(m32.batch_apply(Xt).array)
    p3 = jnp.asarray(m3.batch_apply(Xt).array)
    quality_rel = float(
        jnp.max(jnp.abs(p3 - p32)) / (jnp.max(jnp.abs(p32)) + 1e-30)
    )

    # Marginal device time of the same fused sweep program fit() dispatches,
    # repeated in-program to strip per-dispatch host overhead
    # (identical method to the TIMIT row).
    from keystone_tpu.ops import pallas_ops
    from keystone_tpu.ops.learning.kernel import _krr_fit_fused

    nb = -(-n // bs)
    order = jnp.asarray(
        np.tile(np.arange(nb, dtype=np.int32), epochs)
    )
    use_pallas = pallas_ops.pallas_direct_ok(X)

    def make_repeated_for(kdtype):
        def make_repeated(reps):
            @jax.jit
            def run(X, Y):
                def body(i, acc):
                    _, w_stack = _krr_fit_fused(
                        X + 0.0 * acc, Y, order, gamma, lam, bs, n, nb,
                        use_pallas, kdtype=kdtype,
                    )
                    return acc + jnp.sum(jnp.abs(w_stack))
                return jax.lax.fori_loop(0, reps, body, 0.0)
            return lambda: run(X, Y)
        return make_repeated

    device_s, _, dispatch_s = marginal_device_time(make_repeated_for("bf16x3"))
    device_s_f32, _, _ = marginal_device_time(make_repeated_for("f32"))

    # Phase decomposition (VERDICT r5 Weak #2, restructured for the
    # round-6 program): attribute the fused sweep's device time to its
    # phases so the MFU gap against the BCD headline is EXPLAINED.
    # Round-6 sweep structure (ops/learning/kernel.py::_krr_fit_fused):
    #   kernel_resid — per step, the column-block generation + K_blockᵀW
    #     residual. On the Pallas engines these are ONE fused kernel
    #     (gaussian_resid_block: the column block never reaches HBM); the
    #     bf16x3 headline engine keeps the XLA 3-pass dot + GEMM (Mosaic
    #     has no 3-pass lowering), so its probe times exactly that pair.
    #   prepass_factor — the ONE-time batched diag-gram + Cholesky
    #     pre-pass (replaces round ≤5's re-factorization on every block
    #     step — the 'batch the per-block solves' lever).
    #   solve — per step, the two triangular solves against the STASHED
    #     factor (+ acceptance check).
    #   update_rest — the remainder (rhs assembly, model scatter).
    from keystone_tpu.ops.learning.kernel import (
        _column_block,
        _diag_factor_prepass,
    )
    from keystone_tpu.parallel.linalg import _psd_factor, _solve_psd

    x_norms_ph = jnp.sum(X * X, axis=1)

    def make_kernel_resid(reps):
        W_ph = jnp.zeros((n, k), jnp.float32)

        @jax.jit
        def run(X, x_norms):
            def body(i, acc):
                def step(carry, block):
                    K = _column_block(
                        X + 0.0 * acc, x_norms, block * bs, bs, gamma,
                        use_pallas, "bf16x3",
                    )
                    r = K.T @ (W_ph + carry)
                    return carry + jnp.sum(r[0]), None
                out, _ = jax.lax.scan(step, 0.0, order)
                return acc + out
            return jax.lax.fori_loop(0, reps, body, 0.0)
        return lambda: run(X, x_norms_ph)

    def make_prepass(reps):
        @jax.jit
        def run(X, x_norms):
            def body(i, acc):
                grams, chols = _diag_factor_prepass(
                    X + 0.0 * acc, x_norms, gamma,
                    jnp.asarray(lam, jnp.float32), bs, n, nb, use_pallas,
                    "bf16x3", jnp.float32,
                )
                return acc + jnp.sum(chols[0, 0])
            return jax.lax.fori_loop(0, reps, body, 0.0)
        return lambda: run(X, x_norms_ph)

    rng_ph = np.random.default_rng(9)
    A_ph = jnp.asarray(rng_ph.normal(size=(bs, bs)).astype(np.float32))
    gram_ph = A_ph @ A_ph.T + bs * jnp.eye(bs)
    chol_ph = _psd_factor(gram_ph, jnp.asarray(lam, jnp.float32))
    rhs_ph = jnp.asarray(rng_ph.normal(size=(bs, k)).astype(np.float32))

    def make_solve_only(reps):
        steps = epochs * nb

        @jax.jit
        def run(gram, chol, rhs):
            def body(i, acc):
                w = _solve_psd(
                    gram, rhs + 0.0 * acc, jnp.asarray(lam, jnp.float32),
                    chol=chol,
                )
                return acc + jnp.sum(w)
            return jax.lax.fori_loop(0, reps * steps, body, 0.0)
        return lambda: run(gram_ph, chol_ph, rhs_ph)

    kernel_resid_s, _, _ = marginal_device_time(make_kernel_resid)
    prepass_factor_s, _, _ = marginal_device_time(make_prepass)
    chol_solve_s, _, _ = marginal_device_time(make_solve_only)
    residual_update_s = max(
        device_s - kernel_resid_s - prepass_factor_s - chol_solve_s, 0.0
    )

    # FLOP model per block step: kernel column block 2·n·bs·d, residual
    # K_blockᵀW 2·n·bs·k + gramᵀw_old 2·bs²·k, triangular+check solves
    # ~6·bs²·k; plus the ONE-TIME pre-pass — diag blocks nb·2·bs²·d and
    # Cholesky nb·bs³/3 (round ≤5 re-factored every step: epochs·nb·bs³/3).
    flops = (
        epochs * nb * (2.0 * n * bs * d + 2.0 * n * bs * k + 8.0 * bs**2 * k)
        + nb * (2.0 * bs**2 * d + bs**3 / 3.0)
    )
    achieved = flops / 1e12 / device_s
    # bf16x3 runs the dominant GEMM as 3 bf16 passes: the algorithmic-f32
    # ceiling is peak_bf16/3.
    peak_x3 = PEAK_TFLOPS_BF16 / 3.0
    mfu = achieved / peak_x3
    # Per-phase measured floor (ISSUE 3): the MFU this program would reach
    # if everything OUTSIDE the kernel+residual GEMMs were free — the
    # structural ceiling the non-GEMM phases leave on the table.
    mfu_floor_kernel_resid = (
        flops / 1e12 / kernel_resid_s / peak_x3 if kernel_resid_s > 0 else None
    )
    return make_row(
        "krr_cifar_kernel_geometry",
        round(elapsed, 3),
        "s",
        None,
        "min_of_N_warm",
        {
            "n": n, "d": d, "k": k, "block_size": bs, "epochs": epochs,
            "timing_note": "each engine: warm fit, then min of 2 timed fits",
            "device_time_s": round(device_s, 3),
            "phases": {
                "kernel_resid_s": round(kernel_resid_s, 3),
                "prepass_factor_s": round(prepass_factor_s, 3),
                "chol_solve_s": round(chol_solve_s, 3),
                "update_rest_s": round(residual_update_s, 3),
                "note": (
                    "round-6 sweep attribution: kernel_resid is the "
                    "per-step column-block generation + K_block^T W "
                    "residual (ONE fused Pallas kernel on the f32/bf16 "
                    "engines — the column block never reaches HBM; the "
                    "bf16x3 headline engine keeps the XLA 3-pass dot + "
                    "GEMM, which Mosaic cannot lower, so this probe "
                    "times that pair); prepass_factor is the one-time "
                    "batched diag + Cholesky stash (replaces per-step "
                    "re-factorization); chol_solve is the per-step "
                    "stashed-factor triangular solves; update_rest is "
                    "the remainder (rhs assembly + model scatter)"
                ),
            },
            "headroom": {
                "target_mfu": 0.70,
                "mfu_floor_kernel_resid_only": (
                    round(mfu_floor_kernel_resid, 3)
                    if mfu_floor_kernel_resid is not None else None
                ),
                "phase_seconds_note": (
                    "floor = flop_model / kernel_resid_s / peak: the MFU "
                    "if the pre-pass, solves and updates were free. If "
                    "the floor itself sits below target_mfu, the gap is "
                    "structural to the bf16x3 kernel-generation GEMM "
                    "(VPU exp + 3-pass dot) at this geometry and the "
                    "phase numbers above are the committed floor note; "
                    "if the floor clears the target but mfu does not, "
                    "the residual phases still owe the difference"
                ),
            },
            "device_time_s_f32_engine": round(device_s_f32, 3),
            "wallclock_f32_engine_s": round(elapsed_f32, 3),
            "dispatch_overhead_s": round(dispatch_s, 3),
            "flop_model_tflops": round(flops / 1e12, 2),
            "achieved_tflops": round(achieved, 1),
            "achieved_tflops_f32_engine": round(
                flops / 1e12 / device_s_f32, 1
            ),
            "mfu": round(mfu, 3),
            "precision": (
                "bf16x3 kernel blocks (3-pass bf16 decomposition) + f32 "
                "Cholesky solves; raw bf16 measured DIVERGENT at this λ "
                "(tests/test_kernel_bf16.py) and rejected"
            ),
            "engines_pred_delta_rel": round(quality_rel, 6),
            "peak_tflops": round(peak_x3, 1),
            "single_dispatch": True,
            "baseline_note": (
                "no reference wall-clock exists for "
                "RandomPatchCifarKernel; absolute + MFU only"
            ),
            "device": str(jax.devices()[0]),
        },
    )


def mnist_fft_metric():
    """MnistRandomFFT end-to-end (README example geometry: 4 FFT branches,
    blockSize 2048) at MNIST-train scale on synthetic 784-dim rows. No
    reference wall-clock exists (the README quotes no time), so the row
    reports absolute end-to-end time + MFU of the solve-dominated work."""
    from keystone_tpu.data import Dataset
    from keystone_tpu.ops.learning.block import BlockLeastSquaresEstimator
    from keystone_tpu.ops.util import ClassLabelIndicatorsFromIntLabels
    from keystone_tpu.pipelines.mnist_random_fft import (
        MnistRandomFFTConfig,
        build_featurizer,
    )

    n, d_in, num_ffts, bs = 65_536, 784, 4, 2_048
    cfg = MnistRandomFFTConfig(num_ffts=num_ffts, block_size=bs, image_size=d_in)
    rng = np.random.default_rng(3)
    # Device-resident inputs: the timed region is the pipeline's compute
    # (like the baseline CSV's solver-only times), not the one-time host
    # upload.
    X = jnp.asarray(rng.normal(size=(n, d_in)).astype(np.float32))
    y = rng.integers(0, 10, size=n)
    labels = Dataset.of(
        jnp.asarray(
            np.asarray(ClassLabelIndicatorsFromIntLabels(10)(Dataset.of(y)).array)
        )
    )
    jax.block_until_ready(X)
    featurizer = build_featurizer(cfg)
    data = Dataset.of(X)

    def fit_once():
        pipe = featurizer.and_then(
            BlockLeastSquaresEstimator(bs, 1, 1e-4), data, labels
        )
        out = pipe.apply(data).get()
        return _sync_scalar(jnp.sum(jnp.abs(jnp.asarray(out.array))))

    elapsed, _, _ = min_wall(fit_once, reps=2)

    # Phase attribution (VERDICT r3 Weak #3): time the featurize program
    # and the solver separately on the same shapes, so the end-to-end MFU
    # decomposes instead of being one unexplained number. Phases re-run
    # the same compiled programs the pipeline dispatches (the featurizer
    # fuses to ONE program via Gather fusion; the fit fuses featurize+BCD
    # via EstimatorFusionRule).
    def timed(fn):
        fn()  # warm
        t0 = time.perf_counter()
        r = fn()
        return time.perf_counter() - t0

    feat_handle = featurizer.apply(data)
    F_ds = feat_handle.get()
    t_featurize = timed(
        lambda: _sync_scalar(
            jnp.sum(jnp.abs(jnp.asarray(featurizer.apply(data).get().array)))
        )
    )
    est = BlockLeastSquaresEstimator(bs, 1, 1e-4)

    def solve_only():
        m = est.fit(F_ds, labels)
        return _sync_scalar(jnp.sum(jnp.abs(m.xs[0])))

    t_solve = timed(solve_only)
    executor_overhead = max(elapsed - t_featurize - t_solve, 0.0)

    # FLOP model (executed): FFT featurize runs the packed-pair program —
    # ⌈num_ffts/2⌉ COMPLEX transforms of width p (5·n·p·log2 p each;
    # round 5 executed num_ffts real ones) + BCD epoch on d=4096:
    # gramians nb·2·n·bs², corr+resid nb·2·2·n·bs·k, cholesky nb·bs³/3.
    p = 1024
    d_feat = num_ffts * p
    nb = d_feat // bs
    k = 10
    flops = (
        (-(-num_ffts // 2)) * 5.0 * n * p * np.log2(p)
        + nb * 2.0 * n * bs**2
        + nb * 2 * 2.0 * n * bs * k
        + nb * bs**3 / 3.0
    )
    achieved = flops / 1e12 / elapsed

    # Roofline arithmetic for the featurize phase (VERDICT r5 Weak #3):
    # "FFT is HBM-bound" stated as BOUNDED numbers, not an assertion.
    # Traffic floor: X read once + the concat output written once — no
    # program can move less. Traffic model for the ROUND-6 packed program
    # (stats.packed_fft_gather_fn): X read ONCE for all branches (the
    # stacked sign multiply), branch PAIRS packed as real/imag of
    # ⌈nb/2⌉ complex FFTs — the c64 intermediate round-trips twice
    # (packed input write+read, FFT output write+read for the
    # conjugate-symmetry unpack) at HALF the per-branch-FFT width of the
    # round-5 layout — then the rectified concat written once. FLOP
    # model: ⌈nb/2⌉ complex transforms (5·p·log2 p each) instead of nb
    # real ones.
    npairs_b = -(-num_ffts // 2)
    fft_flops = npairs_b * 5.0 * n * p * np.log2(p)
    bytes_floor = n * d_in * 4.0 + n * d_feat * 4.0
    bytes_model = (
        n * d_in * 4.0                     # ONE stacked input read
        + 2.0 * npairs_b * n * p * 8.0     # packed c64 input write + read
        + 2.0 * npairs_b * n * p * 8.0     # c64 FFT output write + read
        + n * d_feat * 4.0                 # rectified concat output write
    )
    feat_gbps_floor = bytes_floor / t_featurize / 1e9
    feat_gbps_model = bytes_model / t_featurize / 1e9
    feat_tflops = fft_flops / t_featurize / 1e12

    return make_row(
        "mnist_random_fft_end_to_end",
        round(elapsed, 3),
        "s",
        None,
        "min_of_N_warm",
        {
            "n": n, "num_ffts": num_ffts, "block_size": bs,
            "timing_note": "warm fit, then min of 2 timed end-to-end fits",
            "flop_model_tflops": round(flops / 1e12, 3),
            "achieved_tflops": round(achieved, 1),
            "mfu": round(achieved / PEAK_TFLOPS_F32, 3),
            # The row-level achieved-HBM claim (ISSUE 3): the featurize
            # phase's bandwidth beside chip peak, auditable from the
            # inputs riding alongside.
            "achieved_gbps": round(feat_gbps_model, 1),
            "peak_hbm_gbps": PEAK_HBM_GBPS,
            "featurize_s": round(t_featurize, 3),
            "traffic_model_gb": round(bytes_model / 1e9, 2),
            "phases": {
                "featurize_s": round(t_featurize, 3),
                "solve_s": round(t_solve, 3),
                "executor_and_apply_s": round(executor_overhead, 3),
                "note": (
                    "featurize = the ONE packed gather program (round 6: "
                    "stacked sign multiply reads X once, branch pairs "
                    "packed into complex FFTs, conjugate-symmetry unpack "
                    "+ rectify; stats.packed_fft_gather_fn — see "
                    "featurize_roofline for the HBM accounting); solve = "
                    "the fused BCD on materialized features; remainder = "
                    "executor dispatch + the fused apply pass"
                ),
                "featurize_roofline": {
                    "featurize_s": round(t_featurize, 3),
                    "traffic_floor_gb": round(bytes_floor / 1e9, 2),
                    "traffic_model_gb": round(bytes_model / 1e9, 2),
                    "achieved_gbps_floor": round(feat_gbps_floor, 1),
                    "achieved_gbps_model": round(feat_gbps_model, 1),
                    "peak_hbm_gbps": PEAK_HBM_GBPS,
                    "hbm_fraction_model": round(
                        feat_gbps_model / PEAK_HBM_GBPS, 3
                    ),
                    "fft_achieved_tflops": round(feat_tflops, 2),
                    "fft_compute_fraction_f32_peak": round(
                        feat_tflops / PEAK_TFLOPS_F32, 3
                    ),
                    "note": (
                        "floor = X read once + output written once; "
                        "model adds the packed c64 intermediates' two "
                        "round trips (round 6 packed-pair layout: one X "
                        "read total and ceil(nb/2) complex FFTs — the "
                        "round-5 model had per-branch reads and nb "
                        "full-width c64 round trips). HBM-bound holds "
                        "iff achieved GB/s sits near peak while the "
                        "FFT's achieved TFLOP/s sits far below the f32 "
                        "compute peak — both fractions reported"
                    ),
                },
            },
            "precision": "f32 end-to-end (pipeline default)",
            "peak_tflops": PEAK_TFLOPS_F32,
            "includes": "full pipeline fit + apply (graph executor overhead included)",
            "baseline_note": (
                "no reference wall-clock exists for the MnistRandomFFT "
                "README example; absolute + MFU only"
            ),
            "device": str(jax.devices()[0]),
        },
    )


def _run_cache_sweeps(make_optimizer, make_chain, fit_sweep, num_warm=3):
    """Shared harness for the autocache rows: one COLD 3-fit λ-sweep
    (compiles + greedy's profiling passes), then ``num_warm`` further
    3-fit sweeps with FRESH λ values each (so every fit genuinely solves
    — an identical λ would load the published fit from the state table),
    taking the MIN warm sweep wall (the TIMIT headline's min-of-N warm
    convention). The env is NOT reset between sweeps of one config:
    steady-state cross-fit prefix reuse is exactly what the cache plan is
    being priced on."""
    from keystone_tpu.workflow import autocache
    from keystone_tpu.workflow.env import PipelineEnv

    env = PipelineEnv.get_or_create()
    env.reset()
    autocache.clear_observed_profiles()  # fair A/B across configs
    optimizer = make_optimizer()
    env.set_optimizer(optimizer)
    lams = np.logspace(-5, -2, 3 * (num_warm + 1))
    sweeps = []
    for s in range(num_warm + 1):
        t0 = time.perf_counter()
        fit_sweep(make_chain(), lams[3 * s: 3 * s + 3])
        sweeps.append(round(time.perf_counter() - t0, 3))
    # The PLAN: how many cache placements the strategy chose on a fresh
    # fit graph (read off the rule itself — in steady state the inserted
    # Cachers are immediately replaced by state-table splices, so counting
    # Cacher nodes in the final plan would report 0). Untimed.
    fit_sweep(make_chain(), None)
    num_cachers = 0
    for batch in getattr(optimizer, "batches", []):
        for rule in batch.rules:
            sel = getattr(rule, "last_selection", None)
            if sel is not None:
                num_cachers = len(sel)
    env.reset()
    return {
        "cold_sweep_s": sweeps[0],
        "warm_sweeps_s": sweeps[1:],
        "wall_s": min(sweeps[1:]),
        "cache_insertions": num_cachers,
    }


def _cache_configs(budget):
    from keystone_tpu.workflow.autocache import AggressiveCache, GreedyCache
    from keystone_tpu.workflow.optimizer import (
        AutoCachingOptimizer,
        DefaultOptimizer,
    )

    return (
        ("no_cache", DefaultOptimizer),
        ("greedy_postfusion", lambda: AutoCachingOptimizer(
            GreedyCache(max_mem_bytes=budget)
        )),
        ("greedy_prefusion", lambda: AutoCachingOptimizer(
            GreedyCache(max_mem_bytes=budget), cache_before_fusion=True
        )),
        ("aggressive_unbounded", lambda: AutoCachingOptimizer(
            AggressiveCache()
        )),
    )


def _make_fit_sweep(data, labels, X_probe):
    """The sweep body shared by both autocache rows (identical timing
    semantics by construction): fit BlockLS(512, 1, λ) per λ and sync a
    256-row probe; with lams=None, just trigger one fresh optimization
    (the plan probe _run_cache_sweeps reads off the rule)."""
    from keystone_tpu.ops.learning.block import BlockLeastSquaresEstimator
    from keystone_tpu.data import Dataset

    def fit_sweep(chain, lams):
        if lams is None:
            plan_pipe = chain.and_then(
                BlockLeastSquaresEstimator(512, 1, 3e-3), data, labels
            )
            plan_pipe.executor.optimized_graph
            return
        for lam in lams:
            fitted = chain.and_then(
                BlockLeastSquaresEstimator(512, 1, float(lam)), data, labels
            ).fit()
            probe = fitted.apply(Dataset.of(X_probe))
            _sync_scalar(jnp.sum(jnp.abs(jnp.asarray(probe.to_numpy()))))

    return fit_sweep


def autocache_metric():
    """Autocache vs whole-chain fusion ON CHIP under a stated HBM budget,
    min-of-N warm sweeps (the TIMIT headline convention).

    Workload: a 3-stage featurize chain (512→8192 cosine features →
    rectify → 8192→2048 cosine features) reused by 3-fit ridge λ-sweeps
    (the reference's canonical re-use pattern). Intermediates: stage-1/2
    outputs 4.3 GB each, stage-3 output 1.1 GB (n=131072, f32).

    ROUND-6 READING. Cache placement now runs on the POST-fusion plan:
    on this fully device-fusable chain the fused program absorbs every
    stage, so greedy_postfusion finds no profitable interior cut, inserts
    nothing that splits the program, and must tie no-cache (round 5's
    greedy lost 101.6 s vs 99.0 s because pre-fusion placement broke the
    fused chain into per-stage dispatches — kept measurable here as
    greedy_prefusion). The host-boundary row (autocache_host_boundary)
    carries the case caching must WIN.
    """
    from keystone_tpu.data import Dataset
    from keystone_tpu.ops.stats import CosineRandomFeatures, LinearRectifier
    from keystone_tpu.ops.util import ClassLabelIndicatorsFromIntLabels

    n, d_in, d_mid, d_out = 131_072, 512, 8192, 2048
    budget = 3 << 30
    rng = np.random.default_rng(5)
    X = jnp.asarray(rng.normal(size=(n, d_in)).astype(np.float32))
    y = rng.integers(0, 10, size=n)
    labels = Dataset.of(
        jnp.asarray(
            np.asarray(
                ClassLabelIndicatorsFromIntLabels(10)(Dataset.of(y)).array
            )
        )
    )
    data = Dataset.of(X)
    jax.block_until_ready(X)

    crf1 = CosineRandomFeatures(d_in, d_mid, 1e-2, seed=0)
    rect = LinearRectifier(0.0)
    crf2 = CosineRandomFeatures(d_mid, d_out, 1e-2, seed=1)

    def make_chain():
        return crf1.to_pipeline().and_then(rect).and_then(crf2)

    fit_sweep = _make_fit_sweep(data, labels, X[:256])

    results = {}
    for name, mk in _cache_configs(budget):
        try:
            results[name] = _run_cache_sweeps(mk, make_chain, fit_sweep)
        except Exception as e:
            results[name] = {"wall_s": None, "error": str(e)[:160]}

    greedy = results.get("greedy_postfusion", {}).get("wall_s")
    base = results.get("no_cache", {}).get("wall_s")
    return make_row(
        "autocache_on_chip",
        greedy if greedy is not None else -1.0,
        "s",
        round(base / greedy, 2) if greedy and base else None,
        "min_of_N_warm",
        {
            "n": n, "dims": [d_in, d_mid, d_out],
            "reuse": "3-fit lambda sweeps over one featurize chain",
            "timing_note": (
                "min of 3 warm 3-fit sweeps after one cold sweep; fresh "
                "lambdas per sweep so every fit genuinely solves"
            ),
            "budget_bytes": budget,
            "intermediate_gb": [
                round(n * d_mid * 4 / 1e9, 1),
                round(n * d_mid * 4 / 1e9, 1),
                round(n * d_out * 4 / 1e9, 1),
            ],
            "configs": results,
            "reading": (
                "round 6: AutoCacheRule runs on the POST-fusion plan and "
                "declines any cut inside a fusable region, so on this "
                "fully device-fusable chain greedy_postfusion must TIE "
                "no_cache (acceptance: wall <= no_cache wall); "
                "greedy_prefusion keeps the round-5 phase order for A/B "
                "(its placement granularity predates fusion, though the "
                "rule-level boundary guard now applies there too). The "
                "win case for caching lives in autocache_host_boundary"
            ),
            "vs_baseline_note": (
                "vs_baseline = no-cache warm wall / greedy_postfusion "
                "warm wall; >= 1.0 means the cache plan no longer "
                "degrades the fused program"
            ),
            "device": str(jax.devices()[0]),
        },
    )


def autocache_host_boundary_metric():
    """The case cache placement must WIN: a host decode stage feeds a
    device-fusable featurize+fit chain reused by λ-sweeps. Fusion cannot
    collapse the host stage; greedy caches its output at the fused-stage
    boundary and later fits load it from the prefix state table instead
    of re-paying transfer+decode. Same min-of-N warm sweep convention as
    autocache_on_chip. Acceptance: greedy_postfusion warm wall strictly
    below no-cache."""
    from keystone_tpu.data import Dataset
    from keystone_tpu.ops.stats import CosineRandomFeatures
    from keystone_tpu.ops.util import ClassLabelIndicatorsFromIntLabels
    from keystone_tpu.workflow import Transformer

    n, d_in, d_mid = 65_536, 512, 4096
    budget = 3 << 30
    rng = np.random.default_rng(6)
    X = jnp.asarray(rng.normal(size=(n, d_in)).astype(np.float32))
    y = rng.integers(0, 10, size=n)
    labels = Dataset.of(
        jnp.asarray(
            np.asarray(
                ClassLabelIndicatorsFromIntLabels(10)(Dataset.of(y)).array
            )
        )
    )
    data = Dataset.of(X)
    jax.block_until_ready(X)

    class HostDecode(Transformer):
        """Not device-fusable: device->host, host decode math, host->device
        — the loader/decode stage class fusion cannot collapse."""

        def apply(self, x):
            v = np.asarray(x)
            return np.sign(v) * np.sqrt(np.abs(v)).astype(np.float32)

        def batch_apply(self, ds):
            V = np.asarray(ds.array)  # device -> host
            out = np.sign(V) * np.sqrt(np.abs(V)).astype(np.float32)
            return Dataset(jnp.asarray(out), n=ds.n)  # host -> device

    host = HostDecode()
    crf = CosineRandomFeatures(d_in, d_mid, 1e-2, seed=2)

    def make_chain():
        return host.to_pipeline().and_then(crf)

    fit_sweep = _make_fit_sweep(data, labels, X[:256])

    results = {}
    for name, mk in _cache_configs(budget):
        if name == "aggressive_unbounded":
            continue  # the greedy-vs-none contrast is the claim here
        try:
            results[name] = _run_cache_sweeps(mk, make_chain, fit_sweep)
        except Exception as e:
            results[name] = {"wall_s": None, "error": str(e)[:160]}

    greedy = results.get("greedy_postfusion", {}).get("wall_s")
    base = results.get("no_cache", {}).get("wall_s")
    return make_row(
        "autocache_host_boundary",
        greedy if greedy is not None else -1.0,
        "s",
        round(base / greedy, 2) if greedy and base else None,
        "min_of_N_warm",
        {
            "n": n, "dims": [d_in, d_mid],
            "host_stage_gb_per_pass": round(n * d_in * 4 * 2 / 1e9, 2),
            "reuse": "3-fit lambda sweeps over host decode + fused chain",
            "timing_note": (
                "min of 3 warm 3-fit sweeps after one cold sweep; fresh "
                "lambdas per sweep"
            ),
            "budget_bytes": budget,
            "configs": results,
            "reading": (
                "the host decode stage is the fusion-breaking boundary "
                "autocache exists for post round-6: greedy caches its "
                "output and warm sweeps load it from the prefix state "
                "table, skipping the device->host->device roundtrip "
                "no_cache re-pays every fit; vs_baseline > 1.0 is the "
                "cache feature earning its keep on the plan fusion "
                "actually runs"
            ),
            "device": str(jax.devices()[0]),
        },
    )


def stupidbackoff_metric():
    """Vectorized StupidBackoff batch scoring vs the dict-loop oracle
    (host CPU; the reference scored data-parallel over the cluster,
    StupidBackoff.scala:128-182). Reports n-grams/s for the batched path;
    vs_baseline is the speedup over the per-query dict recursion."""
    from keystone_tpu.ops.nlp import (
        NGram,
        NGramIndexerImpl,
        NaiveBitPackIndexer,
        StupidBackoffModel,
    )

    rng = np.random.default_rng(7)
    vocab, n_tri, n_bi = 50_000, 400_000, 150_000
    unigrams = {int(w): int(c) for w, c in enumerate(
        rng.integers(1, 500, size=vocab)
    )}
    # Count-CONSISTENT tables (the corpus invariant the fit relies on):
    # every observed trigram's bigram context is itself observed, so the
    # dict oracle's context division never hits zero. Trigrams extend
    # observed bigrams; unigrams cover the whole vocab.
    counts = {}
    bigrams = rng.integers(0, vocab, (n_bi, 2))
    for row in bigrams:
        counts[NGram(int(w) for w in row)] = int(rng.integers(1, 40))
    ext = np.concatenate(
        [bigrams[rng.integers(0, n_bi, n_tri)],
         rng.integers(0, vocab, (n_tri, 1))], axis=1
    )
    for row in ext:
        counts[NGram(int(w) for w in row)] = int(rng.integers(1, 40))
    model = StupidBackoffModel(
        {}, counts, NGramIndexerImpl(), unigrams,
        num_tokens=sum(unigrams.values()), alpha=0.4,
    )

    packer = NaiveBitPackIndexer()
    observed = list(counts.keys())[: 10 ** 6]
    queries = observed + [
        NGram(int(w) for w in row)
        for row in rng.integers(0, vocab, (200_000, 3))
    ]
    packed = np.array([packer.pack(g.words) for g in queries], dtype=np.int64)

    model.batch_score_packed(packed[:1000])  # build sorted tables untimed
    t0 = time.perf_counter()
    scores = model.batch_score_packed(packed)
    t_vec = time.perf_counter() - t0
    vec_rate = len(packed) / t_vec

    n_dict = 20_000
    t0 = time.perf_counter()
    for g in queries[:n_dict]:
        model.score(g)
    t_dict = time.perf_counter() - t0
    dict_rate = n_dict / t_dict

    assert np.isfinite(scores).all()
    return make_row(
        "stupidbackoff_batch_scoring",
        round(vec_rate, 0),
        "ngrams/s",
        round(vec_rate / dict_rate, 1),
        "host_only",
        {
            "num_queries": len(packed),
            "table_ngrams": len(counts),
            "dict_loop_ngrams_per_s": round(dict_rate, 0),
            "baseline": (
                "per-query dict recursion (_score_locally) on the same "
                "host — the oracle the batch path is equality-tested "
                "against (tests/test_nlp_batch_scoring.py)"
            ),
            "note": (
                "host-side serving path (searchsorted over packed int64 "
                "tables, one lookup batch per backoff level); no reference "
                "wall-clock exists for scoring throughput"
            ),
        },
    )


def outofcore_prefetch_metric():
    """Out-of-core ingestion at the TIMIT geometry (ISSUE 2 tentpole):
    fit from DISK SHARDS — raw 440-dim rows in memory-mapped tile files,
    never resident as one array — through the double-buffered prefetcher
    (data/prefetch.py), A/B against the serial read-then-fold path.

    prefetch-on: a background reader stages segment k+1's host buffers
    (disk read + mmap copy) while segment k's H2D transfer and tile fold
    run; prefetch-off loads each segment on the consumer thread before
    dispatching its fold. Identical fold programs and order — the walls
    differ only by the ingestion overlap, and results are bit-identical
    (tests/test_prefetch.py).

    The achieved overlap fraction = (wall_off − wall_on) / measured load
    time: the share of disk→host latency the prefetcher hid behind
    device compute. Page-cache-warm reads make the load side a host
    memcpy + decode cost — the conservative case for this row, since
    cold reads would only widen the hidden latency.

    Env knobs: BENCH_OOC_N (rows, default 262144 ≈ 0.5 GB of shards;
    the full 2.2e6 is ~3.9 GB of disk), BENCH_OOC_DIR (shard directory,
    default a temp dir; pre-existing shards of the right geometry are
    reused so repeat runs skip the spill).
    """
    import tempfile

    from keystone_tpu.data import one_hot_pm1
    from keystone_tpu.data.prefetch import PrefetchStats
    from keystone_tpu.data.shards import DiskDenseShards
    from keystone_tpu.ops.stats import CosineRandomFeatures
    from keystone_tpu.ops.learning.streaming_ls import CosineBankFeaturize
    from keystone_tpu.parallel import streaming

    n = int(os.environ.get("BENCH_OOC_N", str(262_144)))
    d_in, d_feat, k = TIMIT_INPUT_DIMS, NUM_FEATURES, TIMIT_NUM_CLASSES
    tile_rows, tiles_per_segment = 8_192, 2
    epochs = NUM_EPOCHS

    num_blocks = d_feat // BLOCK_SIZE
    rfs = [
        CosineRandomFeatures(d_in, BLOCK_SIZE, gamma=0.05, seed=i)
        for i in range(num_blocks)
    ]
    bank = CosineBankFeaturize(
        jnp.stack([rf.W for rf in rfs]).reshape(d_feat, d_in),
        jnp.stack([rf.b for rf in rfs]).reshape(d_feat),
    )

    # Spill (untimed): synthetic TIMIT-shaped rows written tile-by-tile —
    # host residency during the spill is one tile block, matching the
    # loaders' to_disk_shards path.
    shard_dir = os.environ.get("BENCH_OOC_DIR") or os.path.join(
        tempfile.gettempdir(), f"keystone_ooc_{n}"
    )
    meta = os.path.join(shard_dir, "dense_shards.json")
    shards = None
    if os.path.exists(meta):
        existing = DiskDenseShards(shard_dir)
        # Reuse ONLY on full geometry match — a stale tiles_per_segment
        # or width would silently measure a different configuration than
        # the row reports (or crash mid-fit on a shape mismatch).
        if (
            existing.n_true == n
            and existing.tile_rows == tile_rows
            and existing.tiles_per_segment == tiles_per_segment
            and existing._x.shape[-1] == d_in
            and existing._y.shape[-1] == k
        ):
            shards = existing
    if shards is None:
        from keystone_tpu.data.shards import DiskDenseShardWriter

        writer = DiskDenseShardWriter(
            shard_dir, n, d_in, k, tile_rows=tile_rows,
            tiles_per_segment=tiles_per_segment,
        )
        rng = np.random.default_rng(0)
        for lo in range(0, n, tile_rows):
            m = min(tile_rows, n - lo)
            Xb = rng.normal(size=(m, d_in)).astype(np.float32)
            yb = rng.integers(0, k, size=m)
            writer.append(
                Xb, one_hot_pm1(yb, k)
            )
        shards = writer.close()
    source = shards.as_source()
    disk_gb = (
        shards._x.dtype.itemsize * shards._x.size
        + shards._y.dtype.itemsize * shards._y.size
    ) / 1e9

    # Fresh PrefetchStats per run; the dict keeps the LAST (warm) run's
    # stats so the reported load/wait figures are per-run, not sums over
    # min_wall's warm + timed passes.
    last_stats = {}

    def fit(depth):
        stats = PrefetchStats()
        W, fmean, ymean, loss = streaming.streaming_bcd_fit_segments(
            source, bank=bank, d_feat=d_feat, block_size=BLOCK_SIZE,
            lam=1e-4, num_iter=epochs, center=False,
            prefetch_depth=depth, prefetch_stats=stats,
        )
        loss = float(loss)
        assert np.isfinite(loss), f"bad out-of-core solve: loss={loss}"
        last_stats[depth] = stats
        return loss

    wall_off, _, _ = min_wall(lambda: fit(0), reps=3)
    wall_on, loss, _ = min_wall(lambda: fit(2), reps=3)
    load_s = last_stats[0].load_s  # serial load time of one warm run
    wait_s = last_stats[2].wait_s  # consumer queue-wait of one warm run
    hidden_s = max(wall_off - wall_on, 0.0)
    overlap_fraction = min(hidden_s / load_s, 1.0) if load_s > 0 else 0.0
    # ONE-run overlap accounting (ISSUE 3 satellite): the same fraction
    # any streamed fit can now report without an A/B leg, via the stats
    # the prefetcher fills (utils/profiling.py).
    from keystone_tpu.utils import profiling as _prof

    overlap_fraction_one_run = _prof.prefetch_overlap_fraction(last_stats[2])

    return make_row(
        "outofcore_prefetch",
        round(wall_on, 3),
        "s",
        round(wall_off / wall_on, 2),
        "min_of_N_warm",
        {
            "n": n, "d_in": d_in, "d_feat": d_feat, "k": k,
            "tile_rows": tile_rows,
            "tiles_per_segment": tiles_per_segment,
            "num_segments": source.num_segments,
            "epochs": epochs,
            "disk_shards_gb": round(disk_gb, 2),
            "prefetch_on_wall_s": round(wall_on, 3),
            "prefetch_off_wall_s": round(wall_off, 3),
            "segment_load_s_per_run": round(load_s, 3),
            "consumer_wait_s_per_run": round(wait_s, 3),
            "overlap_fraction": round(overlap_fraction, 3),
            "overlap_fraction_one_run": (
                round(overlap_fraction_one_run, 3)
                if overlap_fraction_one_run is not None else None
            ),
            "overlap_note": (
                "overlap_fraction = (off_wall - on_wall) / serial "
                "segment-load time (two-leg A/B); overlap_fraction_one_"
                "run = (load_s - wait_s)/load_s from ONE prefetched run "
                "(utils.profiling.prefetch_overlap_fraction — what any "
                "streamed fit can report). Page-cache-warm reads are "
                "the conservative case (cold reads widen both)"
            ),
            "timing_note": (
                "each leg: warm fit (compile), then min of 3 timed fits; "
                "identical fold programs, bit-identical results "
                "(tests/test_prefetch.py)"
            ),
            "vs_baseline_note": (
                "vs_baseline = prefetch-off wall / prefetch-on wall "
                "(serial read-then-fold is the baseline); > 1.0 means "
                "the prefetcher hides ingestion latency"
            ),
            "final_loss": round(loss, 4),
            "device": str(jax.devices()[0]),
        },
    )


def image_conv_featurize_solve_metric():
    """Images at ingest bandwidth (ISSUE 18 tentpole): the first
    DATA-PLANE-BOUND bench row. Encoded PPM images stream through
    ``EncodedImageSource`` — decode + seeded crop/flip run on the
    prefetcher's read lane — into a jitted conv-featurize + mean-pool +
    gram/AtY fold, closed by a ridge solve. The claim is inverted from
    every FLOPs row above: at this geometry the INGEST side (synthesize
    + decode + augment, the stand-in for tar reads) is the busier lane,
    and ``profiling.overlap_report`` proves the fold hides behind it —
    ingest busy >= compute busy and the one-run overlap fraction >= 0.5,
    both asserted before the row is built, with the serial depth-0
    oracle leg (overlap 0 by construction) reported beside.

    The filter-bank width auto-calibrates: one segment's measured decode
    wall and one fold pass size the bank so device compute lands at
    ~0.7x the read lane (real CIFAR pipelines split thousands of filters
    into sequential banks the same way; the row reports the chosen
    width). That keeps the row honestly data-plane-bound across hosts
    instead of tuning magic constants to one machine.

    Env knobs: BENCH_IMG_N (images, default 1024), BENCH_IMG_XY (source
    side, default 64), BENCH_IMG_CROP (augmented side, default 24),
    BENCH_IMG_SEG (images per segment, default 128).
    """
    from keystone_tpu.data.images import (
        EncodedImageSource,
        SyntheticEncodedImages,
    )
    from keystone_tpu.data.prefetch import PrefetchStats, iter_segments
    from keystone_tpu.ops.images.conv import im2col, normalize_patch_rows
    from keystone_tpu.ops.pallas_images import conv_featurize_flops
    from keystone_tpu.utils import profiling as _prof

    n = int(os.environ.get("BENCH_IMG_N", "1024"))
    xy = int(os.environ.get("BENCH_IMG_XY", "64"))
    crop = int(os.environ.get("BENCH_IMG_CROP", "24"))
    ips = int(os.environ.get("BENCH_IMG_SEG", "128"))
    patch, k_f0, k, lam = 5, 16, 10, 1e-3
    provider = SyntheticEncodedImages(
        n, x=xy, y=xy, channels=3, num_classes=k, seed=0)

    def make_source():
        return EncodedImageSource(
            provider, images_per_segment=ips, crop=(crop, crop),
            augment_seed=0)

    src = make_source()
    cx, cy, cc = src.out_shape
    xo, yo = cx - patch + 1, cy - patch + 1
    d_patch = patch * patch * cc

    rng_f = np.random.default_rng(3)

    def make_fold(K):
        filters = jnp.asarray(
            rng_f.normal(size=(K, d_patch)) / np.sqrt(d_patch),
            jnp.float32)

        @jax.jit
        def seg_fold(Xf, Yf, gram, aty):
            imgs = Xf.reshape((-1, cx, cy, cc))
            patches = normalize_patch_rows(im2col(imgs, patch), 10.0)
            feats = jnp.einsum(
                "nxyd,kd->nxyk", patches, filters,
                preferred_element_type=jnp.float32)
            pooled = jnp.mean(feats, axis=(1, 2))
            F = jnp.concatenate(
                [pooled, jnp.ones((pooled.shape[0], 1), jnp.float32)],
                axis=1)
            # Zero-padded tail rows must not count: their bias-column 1s
            # would pollute the gram. Valid rows carry +-1 labels.
            mask = (jnp.sum(jnp.abs(Yf), axis=1) > 0).astype(jnp.float32)
            F = F * mask[:, None]
            return gram + F.T @ F, aty + F.T @ Yf, F

        return filters, seg_fold

    # Calibrate the bank width: decode wall of one segment vs one fold
    # pass at the base width, then scale compute to ~0.7x the read lane.
    t0 = time.perf_counter()
    X0, Y0, _ = src.load(0)
    load_one = time.perf_counter() - t0
    _, fold0 = make_fold(k_f0)
    g0 = jnp.zeros((k_f0 + 1, k_f0 + 1), jnp.float32)
    a0 = jnp.zeros((k_f0 + 1, k), jnp.float32)
    _sync_scalar(jnp.sum(fold0(X0, Y0, g0, a0)[1]))  # compile, untimed
    t0 = time.perf_counter()
    _sync_scalar(jnp.sum(fold0(X0, Y0, g0, a0)[1]))
    compute_one = time.perf_counter() - t0
    scale = max(1, int(round(0.7 * load_one / max(compute_one, 1e-9))))
    K = int(min(k_f0 * scale, 512))
    _, seg_fold = make_fold(K)

    bytes_encoded = sum(
        src.segment_encoded_bytes(s) for s in range(src.num_segments))
    decoded_bytes = int(n * cx * cy * cc * 4)

    last_stats = {}

    def run(depth):
        stats = PrefetchStats()
        gram = jnp.zeros((K + 1, K + 1), jnp.float32)
        aty = jnp.zeros((K + 1, k), jnp.float32)
        for _s, (Xf, Yf, _valid) in iter_segments(
                make_source(), prefetch_depth=depth, stats=stats):
            t0 = time.perf_counter()
            gram, aty, _ = seg_fold(Xf, Yf, gram, aty)
            _sync_scalar(aty[0, 0])
            stats.add_busy("compute", time.perf_counter() - t0)
        last_stats[depth] = stats
        return gram, aty

    wall_off, _, _ = min_wall(lambda: run(0), reps=2)
    wall_on, (gram, aty), _ = min_wall(lambda: run(2), reps=2)

    # Close the pipeline: ridge solve over the streamed gram/AtY, scored
    # on segment 0's rows (re-decoded, untimed).
    W = jnp.linalg.solve(
        gram + lam * jnp.eye(K + 1, dtype=jnp.float32), aty)
    _, _, F0 = seg_fold(
        jnp.asarray(X0[: len(Y0)]), jnp.asarray(Y0),
        jnp.zeros((K + 1, K + 1), jnp.float32),
        jnp.zeros((K + 1, k), jnp.float32))
    pred = np.asarray(jnp.argmax(F0 @ W, axis=1))
    truth = np.asarray(np.argmax(Y0, axis=1))
    valid0 = np.abs(Y0).sum(axis=1) > 0
    train_acc = float(np.mean(pred[valid0] == truth[valid0]))

    stats_on, stats_off = last_stats[2], last_stats[0]
    report = _prof.overlap_report(stats_on)
    serial_report = _prof.overlap_report(stats_off)
    ingest_busy = report["read"]["busy_s"]
    compute_busy = report["compute"]["busy_s"]
    frac = _prof.prefetch_overlap_fraction(stats_on)
    serial_frac = _prof.prefetch_overlap_fraction(stats_off)

    # The row's claims, enforced BEFORE the row exists: data-plane-bound
    # (the read lane outworked the fold) and genuinely overlapped.
    assert ingest_busy >= compute_busy, (
        f"not data-plane-bound: ingest busy {ingest_busy:.4f}s < "
        f"compute busy {compute_busy:.4f}s (K={K})")
    assert frac is not None and frac >= 0.5, (
        f"decode/augment not hidden: one-run overlap {frac} < 0.5")
    assert serial_frac == 0.0, (
        f"serial oracle leg read {serial_frac}, expected 0.0")

    # Peak reference for the ingest bandwidth claim: a measured host
    # memcpy on this machine (one-way bytes), the ceiling a decode-free
    # read lane could hit.
    buf = np.empty(32 * 1024 * 1024, np.uint8)
    memcpy_s, _, _ = min_wall(lambda: buf.copy(), reps=3)
    peak_memcpy_gbps = buf.nbytes / 1e9 / max(memcpy_s, 1e-9)

    load_s = stats_on.load_s
    flops = conv_featurize_flops(n, xo, yo, d_patch, K)
    overlap_sites = {
        site: {
            kk: (round(vv, 4) if vv is not None else None)
            for kk, vv in entry.items()
        }
        for site, entry in report.items()
    }

    return make_row(
        "image_conv_featurize_solve",
        round(wall_on, 3),
        "s",
        round(wall_off / wall_on, 3),
        "min_of_N_warm",
        {
            "n_images": n, "source_xy": xy, "crop": crop,
            "images_per_segment": ips,
            "num_segments": src.num_segments,
            "patch_size": patch, "filters": K, "num_classes": k,
            "filters_note": (
                f"bank width auto-calibrated from base {k_f0}: one "
                "segment's decode wall vs one fold pass sizes device "
                "compute to ~0.7x the read lane (sequential filter "
                "banks, the CIFAR-pipeline memory idiom)"
            ),
            "data_plane_bound": True,
            "data_plane_bound_note": (
                "asserted before the row was built: read-lane busy "
                "(synthesize+decode+augment) >= compute busy, and the "
                "one-run overlap fraction >= 0.5 — ingest bandwidth, "
                "not FLOPs, is the measured bottleneck at this geometry"
            ),
            "prefetch_on_wall_s": round(wall_on, 3),
            "ingest_busy_s": round(ingest_busy, 4),
            "compute_busy_s": round(compute_busy, 4),
            "overlap_fraction_one_run": round(frac, 3),
            "overlap_sites": overlap_sites,
            "overlap_sites_note": (
                "per-site busy/wait/hidden from profiling."
                "overlap_report of the prefetched leg's PrefetchStats: "
                "decode and augment busy ride inside the read lane "
                "(attributed via faults.observe_busy from "
                "EncodedImageSource.load) and hide behind the fold"
            ),
            "serial_oracle_leg": {
                "prefetch_off_wall_s": round(wall_off, 3),
                "overlap_fraction_one_run": 0.0,
                "read_overlap": serial_report["read"]["overlap"],
                "note": (
                    "depth=0: loads run inline on the consumer, busy == "
                    "wait by construction, overlap reads 0 — the floor "
                    "the prefetched leg is measured against"
                ),
            },
            "ingest": {
                "ingest_gbps": round(bytes_encoded / 1e9 / load_s, 4),
                "bytes_read": bytes_encoded,
                "decoded_bytes": decoded_bytes,
                "seconds": round(load_s, 4),
                "load_wall_s": round(load_s, 4),
                "peak_host_memcpy_gbps": round(peak_memcpy_gbps, 2),
                "note": (
                    "bytes_read = encoded PPM bytes per epoch (the "
                    "synthesize step stands in for the tar read); peak "
                    "= measured one-way host memcpy on this machine"
                ),
            },
            "roofline": {
                "mfu": round(
                    flops / (PEAK_TFLOPS_F32 * 1e12 * compute_busy), 6),
                "flop_model_conv_featurize": flops,
                "peak_tflops_f32": PEAK_TFLOPS_F32,
                "compute_busy_s": round(compute_busy, 4),
                "note": (
                    "conv-featurize MFU against the f32 MXU peak over "
                    "the fold's busy seconds — LOW BY DESIGN: this row "
                    "holds compute under the read lane; the kernel-"
                    "level headroom story lives in docs/performance.md"
                ),
            },
            "train_accuracy_seg0": round(train_acc, 4),
            "timing_note": (
                "each leg: warm run (compile), then min of 2 timed "
                "full-epoch streams; identical fold programs and "
                "segment order, stats from the last warm run"
            ),
            "vs_baseline_note": (
                "vs_baseline = serial depth-0 wall / prefetched wall"
            ),
            "device": str(jax.devices()[0]),
        },
    )


def recovery_overhead_metric():
    """Reliability-layer steady-state cost (ISSUE 5): the SAME warmed
    disk-streamed dense fit with fold checkpointing ON (default interval)
    vs OFF. Value = (checkpointed_wall - baseline_wall) / baseline_wall —
    what fraction of fit wall the periodic carry snapshot (device→host
    sync + atomic write, data/durable.py) costs. Acceptance target:
    <= 5% at the default interval; resume correctness (bit-identical W
    under injected mid-fit kills) is pinned by tests/test_chaos.py, so
    this row only has to price the insurance, not prove it works.

    Env knobs: BENCH_RECOVERY_N (rows, default 65536),
    BENCH_RECOVERY_EVERY (checkpoint interval in segments, default the
    CheckpointSpec default of 8).
    """
    import shutil
    import tempfile

    from keystone_tpu.data import one_hot_pm1
    from keystone_tpu.data.durable import CheckpointSpec
    from keystone_tpu.data.shards import DiskDenseShards
    from keystone_tpu.ops.stats import CosineRandomFeatures
    from keystone_tpu.ops.learning.streaming_ls import CosineBankFeaturize
    from keystone_tpu.parallel import streaming

    n = int(os.environ.get("BENCH_RECOVERY_N", str(65_536)))
    every = int(os.environ.get("BENCH_RECOVERY_EVERY", "8"))
    d_in, k = TIMIT_INPUT_DIMS, TIMIT_NUM_CLASSES
    d_feat, block = 4096, 2048
    # One tile per segment: the default n gives 64 segments -> 7
    # snapshots per fit at the default interval, enough signal for the
    # overhead fraction to be a measurement rather than noise.
    tile_rows, tiles_per_segment = 1024, 1

    rfs = [
        CosineRandomFeatures(d_in, block, gamma=0.05, seed=i)
        for i in range(d_feat // block)
    ]
    bank = CosineBankFeaturize(
        jnp.stack([rf.W for rf in rfs]).reshape(d_feat, d_in),
        jnp.stack([rf.b for rf in rfs]).reshape(d_feat),
    )
    work = tempfile.mkdtemp(prefix="keystone_recovery_")
    # A global --checkpoint-dir drill (KEYSTONE_CHECKPOINT_DIR) would
    # silently checkpoint the BASELINE leg too (checkpoint=None resolves
    # the env), making the overhead fraction a fabricated ~0 — run both
    # legs with the ambient knob stripped.
    ambient_ckpt = os.environ.pop("KEYSTONE_CHECKPOINT_DIR", None)
    try:
        rng = np.random.default_rng(0)
        X = rng.normal(size=(n, d_in)).astype(np.float32)
        Y = np.asarray(one_hot_pm1(rng.integers(0, k, size=n), k))
        shards = DiskDenseShards.write(
            os.path.join(work, "shards"), X, Y, tile_rows=tile_rows,
            tiles_per_segment=tiles_per_segment,
        )
        del X, Y
        source = shards.as_source()
        ckpt = CheckpointSpec(
            os.path.join(work, "ckpt"), every_segments=every
        )

        def fit(checkpoint):
            W, _, _, loss = streaming.streaming_bcd_fit_segments(
                source, bank=bank, d_feat=d_feat, block_size=block,
                lam=1e-4, num_iter=NUM_EPOCHS, center=False,
                prefetch_depth=2, checkpoint=checkpoint,
            )
            loss = float(loss)
            assert np.isfinite(loss), f"bad recovery-bench solve: {loss}"
            return loss

        # Each leg min-of-N warm; a COMPLETED checkpointed fit clears its
        # snapshot, so every checkpointed rep starts fresh (no resume).
        wall_off, _, _ = min_wall(lambda: fit(None), reps=2)
        wall_on, loss, _ = min_wall(lambda: fit(ckpt), reps=2)
    finally:
        if ambient_ckpt is not None:
            os.environ["KEYSTONE_CHECKPOINT_DIR"] = ambient_ckpt
        shutil.rmtree(work, ignore_errors=True)

    overhead = (wall_on - wall_off) / wall_off
    num_segments = source.num_segments
    snapshots = max((num_segments - 1) // every, 0)
    # Carry = G + FY + yty + fsum + ysum, all f32.
    carry_bytes = 4 * (d_feat * d_feat + d_feat * k + 1 + d_feat + k)
    return make_row(
        "recovery_overhead",
        round(overhead, 4),
        "fraction",
        None,
        "recovery_overhead",
        {
            "n": n, "d_in": d_in, "d_feat": d_feat, "k": k,
            "tile_rows": tile_rows,
            "num_segments": num_segments,
            "epochs": NUM_EPOCHS,
            "checkpoint_every_segments": every,
            "snapshots_per_fit": snapshots,
            "carry_snapshot_bytes": carry_bytes,
            "baseline_wall_s": round(wall_off, 3),
            "checkpointed_wall_s": round(wall_on, 3),
            "target_max_fraction": 0.05,
            "final_loss": round(loss, 4),
            "timing_note": (
                "each leg: warm fit (compile), then min of 2 timed "
                "fits; identical fold programs and segment order — the "
                "only delta is the every-K carry sync + atomic snapshot "
                "write (resume bit-identity pinned in tests/test_chaos)"
            ),
            "device": str(jax.devices()[0]),
        },
    )


def _observability_serving_overhead():
    """The LIVE plane's price on served p99 (ISSUE 10): the SAME tiny
    exported plan driven open-loop twice at the same offered rate —
    bare, then with the full live plane on (SLO tracker fed per
    request, live exporter publishing Prometheus + atomic JSON
    snapshots every 250ms, and a traced serve with tail-sampled
    request spans at a 1% head rate). Returns the sub-dict the
    observability_overhead row carries; target <= 5% on p99.
    """
    import shutil
    import tempfile

    from keystone_tpu import obs
    from keystone_tpu.data import Dataset
    from keystone_tpu.ops.learning.block import BlockLeastSquaresEstimator
    from keystone_tpu.ops.util import ClassLabelIndicatorsFromIntLabels
    from keystone_tpu.pipelines.mnist_random_fft import (
        MnistRandomFFTConfig,
        build_featurizer,
    )
    from keystone_tpu.serving import MicroBatchServer, export_plan, run_open_loop

    n, d_in, num_ffts, bs = 2_048, 256, 2, 256
    duration_s = float(os.environ.get("BENCH_OBS_SERVE_S", "3"))
    rng = np.random.default_rng(7)
    X = jnp.asarray(rng.normal(size=(n, d_in)).astype(np.float32))
    y = rng.integers(0, 10, size=n)
    labels = Dataset.of(jnp.asarray(np.asarray(
        ClassLabelIndicatorsFromIntLabels(10)(Dataset.of(y)).array
    )))
    cfg = MnistRandomFFTConfig(
        num_ffts=num_ffts, block_size=bs, image_size=d_in
    )
    fitted = build_featurizer(cfg).and_then(
        BlockLeastSquaresEstimator(bs, 1, 1e-4), Dataset.of(X), labels
    ).fit()
    plan = export_plan(fitted, np.zeros(d_in, np.float32), max_batch=64)
    single_s = plan.measure_single_request_s(reps=5)
    # SUSTAINABLE offered rate: on a host where batching does not
    # amortize (CPU — batch exec scales with batch size), anything
    # past 1/single_s drowns the queue and the p99 becomes queue
    # depth, not serving cost — the A/B would measure saturation
    # noise, not the live plane's price.
    rate_hz = 0.6 / single_s
    max_wait_ms = min(25.0, max(2.0, 1.5e3 * single_s))
    pool = rng.normal(size=(256, d_in)).astype(np.float32)

    def req(i):
        return pool[i % len(pool)]

    def storm(server, slo=None, seed=31):
        return run_open_loop(
            server.submit, req, rate_hz=rate_hz, duration_s=duration_s,
            seed=seed, slo=slo,
        )

    # Baseline leg: nothing observing.
    with MicroBatchServer(plan, max_wait_ms=max_wait_ms) as server:
        base = storm(server)

    work = tempfile.mkdtemp(prefix="keystone_obs_serve_")
    try:
        # Registry attached: the measured configuration must be the one
        # run.py serve ships (slo gauges published on the exporter
        # tick), not a lighter tracker-only variant.
        slo_registry = obs.MetricsRegistry()
        slo_tracker = obs.SLOTracker([
            obs.SLOObjective("latency", kind="latency",
                             threshold_s=max(40.0 * single_s, 0.05),
                             target=0.9),
            obs.SLOObjective("availability", kind="availability",
                             target=0.99),
        ], metrics=slo_registry)
        sampler = obs.TailSampler(
            head_rate=0.01, slow_s=max(10.0 * single_s, 0.02)
        )
        with obs.tracing(os.path.join(work, "trace"),
                         serving_sampler=sampler):
            server = MicroBatchServer(
                plan, max_wait_ms=max_wait_ms, slo=slo_tracker
            )
            exporter = None
            try:
                # Inside the try: the server's worker must join even
                # when exporter construction (port bind / snapshot dir)
                # raises — same guard shape as run.py serve.
                exporter = obs.LiveExporter(
                    sources={"metrics": server.metrics,
                             "serving": server.stats,
                             "slo_metrics": slo_registry},
                    slo=slo_tracker, snapshot_dir=work, port=0,
                    interval_s=0.25,
                )
                live = storm(server, slo=slo_tracker)
            finally:
                if exporter is not None:
                    exporter.close()
                server.close()
        sampler_stats = sampler.stats()
        publishes = int(
            exporter.metrics.snapshot()["exporter.publishes"]
        )
    finally:
        shutil.rmtree(work, ignore_errors=True)

    overhead = (
        (live.p99_latency_s - base.p99_latency_s) / base.p99_latency_s
    )
    return {
        # NOT p99-prefixed: this key is a fraction, not a latency claim
        # (the latency-audit rule polices p50*/p99* keys).
        "served_p99_overhead_fraction": round(overhead, 4),
        "target_max_fraction": 0.05,
        "baseline_p99_s": round(base.p99_latency_s, 6),
        "duration_s_per_leg": duration_s,
        "baseline_leg": {
            "offered_rate_hz": round(rate_hz, 2),
            "num_samples": base.completed,
            "p99_latency_ms": round(base.p99_latency_s * 1e3, 3),
        },
        "live_leg": {
            "offered_rate_hz": round(rate_hz, 2),
            "num_samples": live.completed,
            "p99_latency_ms": round(live.p99_latency_s * 1e3, 3),
            "slo_state": (live.slo or {}).get("state"),
            "trace_spans_kept": sampler_stats["kept_total"],
            "trace_spans_sampled_out": sampler_stats["sampled_out"],
            "exporter_publishes": publishes,
        },
    }


def _cost_calibration_block():
    """Calibration audit of the shipped cost-model constants on THIS
    host (ISSUE 13 satellite): a selector-driven fit runs TRACED — the
    decision recorded, the winner's measured wall back-annotated by the
    executor — and the trace is replayed through the calibrator
    (``obs/calibrate.py``). The block RAISES if the median |log error|
    under the active weights exceeds the stated bound, so constants
    that stopped matching this host fail the bench loudly instead of
    silently mis-routing every fit.

    Measurement discipline matches ``scripts/fit_cost_weights.py``: the
    scored leg is a WARM fit (a first traced fit eats the compile) and
    a calibrated null-dispatch round trip is subtracted — the model
    prices device time, and host dispatch overhead must not read as
    model error. On a non-TPU host the bound derates (the
    constants are TPU-fit; a CPU run proves the machinery, not the
    constants) and the block says so (``host_derated_bound``).

    Env knobs: BENCH_CAL_N (rows, default 65536),
    BENCH_CAL_MAX_ABS_LOG_ERR (the bound).
    """
    from keystone_tpu import obs
    from keystone_tpu.data import Dataset
    from keystone_tpu.obs import calibrate as cal
    from keystone_tpu.ops.learning.cost import LeastSquaresEstimator

    n = int(os.environ.get("BENCH_CAL_N", str(65_536)))
    d, k = 2048, 32
    rng = np.random.default_rng(23)
    Xh = rng.normal(size=(n, d)).astype(np.float32)
    Yh = rng.normal(size=(n, k)).astype(np.float32)
    data, labels = Dataset.of(jnp.asarray(Xh)), Dataset.of(jnp.asarray(Yh))
    sample = Dataset.of(jnp.asarray(Xh[:24]))
    sample.total_n = n
    ls = Dataset.of(jnp.asarray(Yh[:24]))
    est = LeastSquaresEstimator(lam=1e-3, num_machines=1)

    @jax.jit
    def _null(x):
        return x + 1.0

    _sync_scalar(_null(jnp.zeros(())))  # compile
    dispatch = min(
        min_wall(lambda: _sync_scalar(_null(jnp.zeros(()))), reps=3)[0],
        0.5,
    )
    def fit_once(chosen, timing):
        # The bench's own barrier discipline: the measured wall must
        # cover the device work — apply the fitted model to one datum
        # and transfer the result before the clock stops.
        ref = chosen._pending_cost_outcome
        chosen._pending_cost_outcome = None
        t0 = time.perf_counter()
        m = chosen.fit_datasets([data, labels])
        float(np.abs(np.asarray(m.single_transform([Xh[0]]))).sum())
        if ref is not None:
            ref.stamp(time.perf_counter() - t0, timing=timing)

    with obs.tracing() as t:
        # Cold leg: compile + warm (its decision/outcome is recorded
        # but NOT scored — compile time is not a model claim).
        fit_once(est.optimize(sample, ls), "single_run_cold")
        # Scored leg: a fresh decision whose stamped outcome is warm.
        fit_once(est.optimize(sample, ls), "single_run_warm")
    outcomes = cal.join_decisions(t.events)
    warm = outcomes[-1]
    warm.measured_s = max(warm.measured_s - dispatch, 1e-6)
    active = cal.family_weights("active")
    report = cal.calibration_report([warm], weights=active)
    on_tpu = jax.devices()[0].platform == "tpu"
    bound = float(os.environ.get(
        "BENCH_CAL_MAX_ABS_LOG_ERR", "2.5" if on_tpu else "12.0"
    ))
    verdict = cal.drift_gate(report, threshold=bound)
    med = report["median_abs_log_error"]
    if med is None or verdict["drifted"]:
        raise AssertionError(
            f"cost-model calibration audit failed on this host: median "
            f"|log error| {med} vs bound {bound} under the "
            f"{report['weights_family']!r} weights (winner {warm.winner}"
            f", predicted {warm.predicted_s}, measured-minus-dispatch "
            f"{warm.measured_s:.4f}s) — refit with bin/calibrate --refit"
        )
    return {
        "prediction_error_median_abs_log": round(med, 4),
        "num_decisions": report["num_decisions"],
        "weights_family": report["weights_family"],
        "bound_abs_log_error": bound,
        "host_derated_bound": not on_tpu,
        "winner": warm.winner,
        "predicted_winner_s": (
            round(warm.predicted_s, 6)
            if warm.predicted_s is not None else None
        ),
        "measured_minus_dispatch_s": round(warm.measured_s, 4),
        "dispatch_overhead_s": round(dispatch, 4),
        "misroutes": len(report["misroutes"]),
        "n": n, "d": d, "k": k,
    }


def observability_overhead_metric():
    """The obs plane's price (ISSUE 9 acceptance): the SAME warmed
    disk-streamed dense fit with tracing ON (obs.tracing into a temp
    dir — fold chunk spans, prefetch read/wait spans, runtime lane
    tasks, counter tracks, and the trace-file write at tracing() exit,
    deliberately INSIDE the timed region: a traced run pays for its
    trace, and the row must say what it costs) vs OFF (the production
    default: every hook is one disabled-branch check). Value =
    (traced_wall - baseline_wall) / baseline_wall. Acceptance target: <= 2% traced; the DISABLED cost
    is pinned separately by tests/test_obs.py's per-hook regression
    (no measurable overhead on the streamed-fold test).

    The ``serving_live_plane`` sub-block (ISSUE 10) extends the row to
    the LIVE plane: the same exported plan served open-loop bare vs
    with SLO tracking + the live exporter + tail-sampled tracing —
    the served-p99 overhead fraction, target <= 5%.

    The ``cost_calibration`` sub-block (ISSUE 13) audits the shipped
    cost-model constants against this host: a traced selector-driven
    fit replayed through the calibrator, raising past the stated
    median-|log error| bound (``_cost_calibration_block``).

    Env knobs: BENCH_OBS_N (rows, default 65536), BENCH_OBS_SERVE_S
    (per-leg serve window, default 3), BENCH_CAL_N /
    BENCH_CAL_MAX_ABS_LOG_ERR (the calibration audit).
    """
    import shutil
    import tempfile

    from keystone_tpu import obs
    from keystone_tpu.data import one_hot_pm1
    from keystone_tpu.data.shards import DiskDenseShards
    from keystone_tpu.ops.stats import CosineRandomFeatures
    from keystone_tpu.ops.learning.streaming_ls import CosineBankFeaturize
    from keystone_tpu.parallel import streaming

    n = int(os.environ.get("BENCH_OBS_N", str(65_536)))
    d_in, k = TIMIT_INPUT_DIMS, TIMIT_NUM_CLASSES
    d_feat, block = 4096, 2048
    tile_rows, tiles_per_segment = 1024, 1

    rfs = [
        CosineRandomFeatures(d_in, block, gamma=0.05, seed=i)
        for i in range(d_feat // block)
    ]
    bank = CosineBankFeaturize(
        jnp.stack([rf.W for rf in rfs]).reshape(d_feat, d_in),
        jnp.stack([rf.b for rf in rfs]).reshape(d_feat),
    )
    work = tempfile.mkdtemp(prefix="keystone_obs_")
    # An ambient KEYSTONE_TRACE would trace the BASELINE leg too,
    # fabricating a ~0 fraction — strip it for both legs.
    ambient_trace = os.environ.pop("KEYSTONE_TRACE", None)
    try:
        rng = np.random.default_rng(0)
        X = rng.normal(size=(n, d_in)).astype(np.float32)
        Y = np.asarray(one_hot_pm1(rng.integers(0, k, size=n), k))
        shards = DiskDenseShards.write(
            os.path.join(work, "shards"), X, Y, tile_rows=tile_rows,
            tiles_per_segment=tiles_per_segment,
        )
        del X, Y
        source = shards.as_source()

        def fit():
            W, _, _, loss = streaming.streaming_bcd_fit_segments(
                source, bank=bank, d_feat=d_feat, block_size=block,
                lam=1e-4, num_iter=NUM_EPOCHS, center=False,
                prefetch_depth=2,
            )
            loss = float(loss)
            assert np.isfinite(loss), f"bad obs-bench solve: {loss}"
            return loss

        last_trace_dir = [""]

        def traced_fit(i=[0]):
            # Fresh dir per rep; the file write happens at tracing()
            # exit INSIDE the timed region deliberately — a traced run
            # pays for its trace, and the row must say what it costs.
            i[0] += 1
            last_trace_dir[0] = os.path.join(work, f"trace{i[0]}")
            with obs.tracing(last_trace_dir[0]):
                return fit()

        wall_off, _, _ = min_wall(fit, reps=3)
        wall_on, loss, _ = min_wall(traced_fit, reps=3)
        span_count = len(obs.load_events(last_trace_dir[0]))
        serving_live = _observability_serving_overhead()
        cost_calibration = _cost_calibration_block()
    finally:
        if ambient_trace is not None:
            os.environ["KEYSTONE_TRACE"] = ambient_trace
        shutil.rmtree(work, ignore_errors=True)

    overhead = (wall_on - wall_off) / wall_off
    return make_row(
        "observability_overhead",
        round(overhead, 4),
        "fraction",
        None,
        "overhead_fraction",
        {
            "n": n, "d_in": d_in, "d_feat": d_feat, "k": k,
            "tile_rows": tile_rows,
            "num_segments": source.num_segments,
            "epochs": NUM_EPOCHS,
            "baseline_wall_s": round(wall_off, 3),
            "traced_wall_s": round(wall_on, 3),
            "trace_records_per_fit": span_count,
            "target_max_fraction": 0.02,
            # ISSUE 10: the live plane's price on SERVED p99 (SLO
            # tracker + exporter + tail-sampled tracing), target <= 5%.
            "serving_live_plane": serving_live,
            # ISSUE 13: the calibration audit — a traced selector-driven
            # fit replayed through obs/calibrate.py; raises past the
            # stated |log error| bound (the shipped constants must still
            # hold on this host).
            "cost_calibration": cost_calibration,
            "timing_note": (
                "each leg: warm fit (compile), then min of 3 timed "
                "fits; identical fold programs and segment order — the "
                "only delta is the obs plane (span records on fold/"
                "read/wait/lane seams + trace-file write at exit). "
                "Disabled-path cost is pinned by tests/test_obs.py"
            ),
            "device": str(jax.devices()[0]),
        },
    )


def serving_mnist_metric():
    """Online serving of the exported mnist_random_fft pipeline (ISSUE 4
    tentpole): the fitted pipeline is exported through serving/export.py
    (apply subgraph re-fused to ONE program, weights pinned, power-of-two
    padding buckets pre-compiled) and driven by the deadline-aware
    micro-batcher under OPEN-LOOP Poisson load at three offered rates.

    The A/B is batch-size-1 serving — one dispatch per request, no
    coalescing (what the apply path does without serving/). The claim:
    at an offered rate where p99 latency stays within 5x the measured
    single-request time, the micro-batcher achieves >= 3x the naive
    throughput (acceptance block in detail). Open loop means arrivals
    follow the schedule regardless of completions — no coordinated
    omission; every percentile rides with its sample count and offered
    rate (make_row's latency audit rule).

    Env knobs: BENCH_SERVE_DURATION_S (per-rate window, default 5),
    BENCH_SERVE_MAX_BATCH (default 256).
    """
    from keystone_tpu.data import Dataset
    from keystone_tpu.ops.learning.block import BlockLeastSquaresEstimator
    from keystone_tpu.ops.util import ClassLabelIndicatorsFromIntLabels
    from keystone_tpu.pipelines.mnist_random_fft import (
        MnistRandomFFTConfig,
        build_featurizer,
    )
    from keystone_tpu.serving import (
        MicroBatchServer,
        closed_loop_qps,
        export_plan,
        run_open_loop,
    )

    n, d_in, num_ffts, bs = 16_384, 784, 4, 2_048
    max_batch = int(os.environ.get("BENCH_SERVE_MAX_BATCH", "256"))
    duration_s = float(os.environ.get("BENCH_SERVE_DURATION_S", "5"))
    rng = np.random.default_rng(11)
    X = jnp.asarray(rng.normal(size=(n, d_in)).astype(np.float32))
    y = rng.integers(0, 10, size=n)
    labels = Dataset.of(
        jnp.asarray(
            np.asarray(ClassLabelIndicatorsFromIntLabels(10)(Dataset.of(y)).array)
        )
    )
    jax.block_until_ready(X)
    cfg = MnistRandomFFTConfig(num_ffts=num_ffts, block_size=bs, image_size=d_in)
    fitted = build_featurizer(cfg).and_then(
        BlockLeastSquaresEstimator(bs, 1, 1e-4), Dataset.of(X), labels
    ).fit()

    plan = export_plan(fitted, np.zeros(d_in, np.float32), max_batch=max_batch)
    single_s = plan.measure_single_request_s(reps=10)

    pool = rng.normal(size=(1024, d_in)).astype(np.float32)

    def req(i):
        return pool[i % len(pool)]

    # Naive batch-size-1 serving: the baseline every rate A/Bs against.
    naive = closed_loop_qps(lambda x: plan.apply_batch([x]), req,
                            num_requests=48)
    naive_qps = naive["qps"]

    # Let the oldest request wait about one dispatch for co-riders —
    # enough to coalesce under load without dominating p99 when idle.
    max_wait_ms = min(25.0, max(2.0, 1.5e3 * single_s))

    runs = []
    for mult in (2.0, 8.0, 32.0):
        rate = mult * naive_qps
        server = MicroBatchServer(
            plan, max_batch=max_batch, max_wait_ms=max_wait_ms,
            max_queue_depth=4096,
        )
        try:
            report = run_open_loop(
                server.submit, req, rate_hz=rate, duration_s=duration_s,
                seed=13,
            )
            sstats = server.stats()
        finally:
            server.close()
        d = report.to_row_dict()
        d["offered_x_naive_qps"] = round(mult, 1)
        d["mean_pad_fraction"] = (
            round(sstats["mean_pad_fraction"], 4)
            if sstats["mean_pad_fraction"] is not None else None
        )
        d["mean_batch_size"] = (
            round(sstats["mean_batch_size"], 1)
            if sstats["mean_batch_size"] is not None else None
        )
        runs.append(d)

    # Acceptance: the highest offered rate whose p99 held within 5x the
    # single-request time while achieving >= 3x the naive throughput.
    p99_budget_s = 5.0 * single_s
    accepted = None
    for d in runs:
        if d["p99_latency_ms"] is None or d["achieved_qps"] is None:
            continue
        if (
            d["p99_latency_ms"] / 1e3 <= p99_budget_s
            and d["achieved_qps"] >= 3.0 * naive_qps
        ):
            accepted = d
    headline = accepted or max(
        (d for d in runs if d["p99_latency_ms"] is not None),
        key=lambda d: d["achieved_qps"] or 0.0,
        default=runs[-1],
    )
    value_s = (
        headline["p99_latency_ms"] / 1e3
        if headline["p99_latency_ms"] is not None else -1.0
    )
    return make_row(
        "serving_mnist_open_loop_p99",
        round(value_s, 5),
        "s",
        round(headline["achieved_qps"] / naive_qps, 2)
        if headline["achieved_qps"] else None,
        "open_loop_latency",
        {
            "pipeline": "mnist_random_fft (fit n=16384, served online)",
            "d_in": d_in, "num_ffts": num_ffts, "block_size": bs,
            "max_batch": max_batch,
            "max_wait_ms": round(max_wait_ms, 2),
            "buckets": plan.buckets,
            "plan_compiled_single_program": plan.compiled,
            "plan_pinned_weight_bytes": plan.pinned_bytes,
            "single_request_s": round(single_s, 6),
            "naive_batch1": {
                "qps": round(naive_qps, 2),
                "num_samples": naive["num_samples"],
                # Closed loop: offered == achieved by construction (one
                # dispatch per request, next request waits for this one).
                "offered_qps_closed_loop": round(naive_qps, 2),
                "p50_latency_ms": round(naive["p50_latency_s"] * 1e3, 3),
                "p99_latency_ms": round(naive["p99_latency_s"] * 1e3, 3),
            },
            "open_loop_rates": runs,
            "headline_rate": headline,
            "acceptance": {
                "tail_budget_s_p99_max": round(p99_budget_s, 6),
                "throughput_multiple_target": 3.0,
                "met": accepted is not None,
            },
            "timing_note": (
                "value = p99 latency (s) at the highest offered Poisson "
                "rate meeting the acceptance gate (p99 <= 5x single-"
                "request time AND throughput >= 3x batch-size-1); "
                "vs_baseline = achieved qps / naive batch-size-1 qps at "
                "that rate; each rate ran an independent "
                f"{duration_s:.0f}s open-loop window"
            ),
            "device": str(jax.devices()[0]),
        },
    )


def serving_model_zoo_isolation_metric():
    """The multi-tenant model zoo's isolation contract under load
    (ISSUE 14 tentpole): >= 8 tenants, each with its own exported plan,
    per-tenant SLO tracker, and deficit-weighted admission share, driven
    by aggregate open-loop Poisson through three legs:

      1. ``steady``    — every tenant at the base rate: the baseline
         per-tenant p99 and an all-OK verdict row.
      2. ``spike``     — ONE tenant offers 8x the aggregate of the
         others, far past its admission share. The contract: the hot
         tenant's own sheds drive ITS verdict past WARN while every
         other tenant's verdict stays OK — the row RAISES otherwise.
         value = the worst NON-spiking tenant's p99 during the spike;
         vs_baseline = steady worst-other p99 / spike worst-other p99
         (~1.0 when isolation holds).
      3. ``coldstart`` — the budget binds (2 of 8 tenants resident);
         explicit page-ins exercise LRU-by-cost eviction, and a storm
         of DEADLINED requests against cold tenants fast-fails with the
         named TenantColdStart (counted) instead of wedging behind
         multi-second weight rebuilds, while the resident tenants keep
         completing.

    Every leg's per-tenant accounting must balance (offered ==
    completed + rejected + failed, loadgen-side AND zoo-side — zero
    silent drops), and the zoo's paging decisions (page_in / page_out /
    evict audit events) land in the row. Env knobs:
    BENCH_ZOO_DURATION_S (per-leg window, default 3),
    BENCH_ZOO_TENANTS (default 8).
    """
    from keystone_tpu import obs
    from keystone_tpu.data import Dataset
    from keystone_tpu.ops.learning.block import BlockLeastSquaresEstimator
    from keystone_tpu.ops.util import ClassLabelIndicatorsFromIntLabels
    from keystone_tpu.pipelines.mnist_random_fft import (
        MnistRandomFFTConfig,
        build_featurizer,
    )
    from keystone_tpu.serving import (
        ModelZoo,
        export_plan,
        run_multi_tenant_open_loop,
    )

    num_tenants = max(int(os.environ.get("BENCH_ZOO_TENANTS", "8")), 8)
    duration_s = float(os.environ.get("BENCH_ZOO_DURATION_S", "3"))
    d_in, num_ffts, bs, n_fit = 64, 2, 64, 512

    def fit_one(seed):
        rng = np.random.default_rng(seed)
        X = jnp.asarray(rng.normal(size=(n_fit, d_in)).astype(np.float32))
        y = rng.integers(0, 10, size=n_fit)
        labels = ClassLabelIndicatorsFromIntLabels(10)(
            Dataset.of(jnp.asarray(y))
        )
        return build_featurizer(
            MnistRandomFFTConfig(
                num_ffts=num_ffts, block_size=bs, image_size=d_in
            )
        ).and_then(
            BlockLeastSquaresEstimator(bs, 1, 1e-3), Dataset.of(X), labels
        ).fit()

    names = [f"t{i}" for i in range(num_tenants - 1)] + ["hot"]
    plans = {
        name: export_plan(
            fit_one(seed), np.zeros(d_in, np.float32), max_batch=8
        )
        for seed, name in enumerate(names)
    }
    per_bytes = {n: max(p.pinned_bytes, 1) for n, p in plans.items()}
    rng = np.random.default_rng(29)
    pool = rng.normal(size=(256, d_in)).astype(np.float32)

    def fresh_slos():
        return {
            name: obs.SLOTracker([
                obs.SLOObjective(
                    "availability", kind="availability", target=0.95,
                ),
            ])
            for name in names
        }

    def run_leg(rates, slos, zoo, deadline_ms=None):
        report = run_multi_tenant_open_loop(
            zoo.submit, lambda tenant, i: pool[i % len(pool)],
            rates_hz=rates, duration_s=duration_s, seed=31,
            deadline_ms=deadline_ms, slos=slos,
        )
        stats = zoo.stats()
        leg = report.to_row_dict()
        leg["tenant_slo_states"] = report.tenant_states()
        leg["zoo"] = {
            k: stats[k]
            for k in (
                "num_tenants", "residents", "resident_bytes",
                "budget_bytes", "page_ins", "page_outs", "quarantined",
                "coldstart_failfast", "accounting_ok", "num_decisions",
            )
        }
        if not (report.accounting_ok() and stats["accounting_ok"]):
            raise RuntimeError(
                f"zoo leg lost requests: loadgen "
                f"{report.accounting_ok()}, zoo {stats['accounting_ok']}"
            )
        return leg, report, stats

    base = 25.0
    zoo_kwargs = dict(
        max_batch=8, max_wait_ms=10.0,
        tenant_queue_cap=8, max_outstanding_total=8 * num_tenants,
    )

    # Leg 1: steady — everyone at the base rate, verdicts all OK.
    slos = fresh_slos()
    zoo = ModelZoo(
        budget_bytes=sum(per_bytes.values()) + num_tenants, **zoo_kwargs
    )
    try:
        for name in names:
            zoo.add_tenant(name, plans[name], slo=slos[name])
        steady_leg, steady_report, _ = run_leg(
            {name: base for name in names}, slos, zoo
        )
    finally:
        zoo.close()
    if any(
        s not in (None, "OK")
        for s in steady_leg["tenant_slo_states"].values()
    ):
        raise RuntimeError(
            f"steady leg not all-OK: {steady_leg['tenant_slo_states']}"
        )

    # Leg 2: one tenant spikes to 8x the aggregate of the others.
    slos = fresh_slos()
    zoo = ModelZoo(
        budget_bytes=sum(per_bytes.values()) + num_tenants, **zoo_kwargs
    )
    try:
        for name in names:
            zoo.add_tenant(name, plans[name], slo=slos[name])
        rates = {name: base for name in names}
        rates["hot"] = 8.0 * base * (num_tenants - 1)
        spike_leg, spike_report, _ = run_leg(rates, slos, zoo)
    finally:
        zoo.close()
    states = spike_leg["tenant_slo_states"]
    if states["hot"] not in ("WARN", "BREACH"):
        raise RuntimeError(
            f"the spiking tenant never degraded: {states['hot']} "
            "(the leg proved nothing)"
        )
    bad_others = {
        n: s for n, s in states.items() if n != "hot" and s != "OK"
    }
    if bad_others:
        raise RuntimeError(
            f"isolation violated: non-spiking tenants left OK under the "
            f"hot tenant's load: {bad_others}"
        )

    def worst_other_p99(report):
        vals = [
            r.p99_latency_s for n, r in report.tenants.items()
            if n != "hot" and r.p99_latency_s is not None
        ]
        return max(vals) if vals else None

    steady_p99 = worst_other_p99(steady_report)
    spike_p99 = worst_other_p99(spike_report)
    if steady_p99 is None or spike_p99 is None:
        raise RuntimeError("a leg completed zero non-hot requests")

    # Leg 3: the budget binds — 2 of 8 resident; explicit page-ins
    # exercise priced eviction, deadlined cold submits fast-fail.
    slos = fresh_slos()
    two = per_bytes[names[0]] + per_bytes[names[1]] + 2
    zoo = ModelZoo(
        budget_bytes=two, cold_start_estimate_s=30.0, **zoo_kwargs
    )
    try:
        for name in names:
            zoo.add_tenant(
                name, plans[name], slo=slos[name], resident=False,
                resident_bytes=per_bytes[name],
            )
        for name in names[:3]:  # 3rd page-in must evict (budget = 2)
            zoo.page_in(name)
        cold_leg, _, cold_stats = run_leg(
            {name: base for name in names}, slos, zoo, deadline_ms=250.0,
        )
        decisions = zoo.decision_log()
    finally:
        zoo.close()
    if cold_stats["coldstart_failfast"] < 1:
        raise RuntimeError(
            "the cold-start storm never fast-failed a deadlined request"
        )
    actions = {d["action"] for d in decisions}
    if not {"page_in", "page_out", "evict"} <= actions:
        raise RuntimeError(
            f"paging decisions missing from the audit log: {actions}"
        )
    if cold_leg["completed_total"] < 1:
        raise RuntimeError(
            "no resident tenant completed anything during the cold-start "
            "storm"
        )

    return make_row(
        "serving_model_zoo_isolation",
        round(spike_p99, 5),
        "s",
        round(steady_p99 / spike_p99, 3),
        "open_loop_latency",
        {
            "num_tenants": num_tenants,
            "pipeline": f"mnist_random_fft x{num_tenants} "
            f"(d_in={d_in}, independent exports)",
            "per_tenant_weight_bytes": per_bytes,
            "zoo_knobs": {
                "max_batch": zoo_kwargs["max_batch"],
                "max_wait_ms": zoo_kwargs["max_wait_ms"],
                "tenant_queue_cap": zoo_kwargs["tenant_queue_cap"],
                "max_outstanding_total":
                    zoo_kwargs["max_outstanding_total"],
            },
            "legs": {
                "steady": steady_leg,
                "spike": spike_leg,
                "coldstart": cold_leg,
            },
            "isolation": {
                "hot_state": states["hot"],
                "others_all_ok": not bad_others,
                "steady_worst_other_p99_s": round(steady_p99, 6),
                "spike_worst_other_p99_s": round(spike_p99, 6),
            },
            "paging_decisions": decisions[-32:],
            "timing_note": (
                "value = worst NON-spiking tenant p99 (s) during the "
                "8x one-tenant spike leg; vs_baseline = steady worst-"
                "other p99 / spike worst-other p99 (~1.0 = isolation "
                f"held); each leg ran an independent {duration_s:.0f}s "
                "open-loop window against a fresh zoo + fresh per-"
                "tenant SLO trackers"
            ),
            "device": str(jax.devices()[0]),
        },
    )


def serving_replicated_chaos_metric():
    """The replicated serving plane under chaos (ISSUE 7 tentpole):
    N micro-batch replicas behind one admission-controlled front door
    (serving/replicas.py), driven open-loop at a fixed Poisson rate
    through three legs of equal duration:

      1. ``steady``   — no faults: the plane's baseline p99.
      2. ``kill``     — a deterministic ``serving.replica.execute``
         fault kills one replica worker mid-storm; the watchdog
         restarts it from the exported plan. The LEG's p99 is the
         degraded-window p99 the row reports as its value.
      3. ``swap``     — ``swap_plan`` hot-swaps every replica onto a
         second fitted model mid-storm: zero requests dropped, both
         plan fingerprints attributed on completions.

    value = degraded-window (kill-leg) p99 seconds; vs_baseline =
    steady p99 / degraded p99 (1.0 = no degradation; smaller = the kill
    window cost more tail). Every leg dict carries num_samples + the
    offered rate (make_row's latency-audit rule), and zero-drop
    accounting (offered == completed + rejected + failed) is asserted
    into the row.

    The SLO leg (ISSUE 10): a live :class:`SLOTracker` (p99-latency +
    availability objectives, short burn windows scaled to the leg
    length) rides the plane's front door through all three legs. The
    row asserts the measured-policy story the mechanisms alone cannot:
    the STEADY leg ends in state OK, the KILL leg produces a
    BREACH transition, and the plane RECOVERS out of breach by the end
    — with the error-budget ledger attributing the spend to the
    degraded window. Any of those failing raises (a chaos row that
    silently measured a healthy run is the same lie as the
    kill-never-fired case below).

    The autoscale leg (ISSUE 12): a FRESH one-replica plane with the
    SLO-closed-loop :class:`Autoscaler` thread live — open-loop Poisson
    at 1x one replica's naive rate, then a 4x spike (the first scale-up
    spawn attempt chaos-killed through ``serving.autoscale.spawn`` and
    absorbed by the restart budget), then quiesce. The row RAISES
    unless: the spike drives a WARN/BREACH transition AND a scale-up,
    the post-scale quiesce p99 recovers under the calibrated bound,
    sustained idle drives a scale-down, and per-leg accounting shows
    zero silent drops. The controller's decision log lands in the row.

    Env knobs: BENCH_REPLICAS (default 3), BENCH_REPLICA_DURATION_S
    (per-leg window, default 4), BENCH_REPLICA_RATE_X (offered rate as
    a multiple of one replica's naive single-request throughput,
    default 4).
    """
    from keystone_tpu.data import Dataset
    from keystone_tpu.ops.learning.block import BlockLeastSquaresEstimator
    from keystone_tpu.ops.util import ClassLabelIndicatorsFromIntLabels
    from keystone_tpu.pipelines.mnist_random_fft import (
        MnistRandomFFTConfig,
        build_featurizer,
    )
    from keystone_tpu import obs
    from keystone_tpu.serving import (
        Autoscaler,
        ReplicatedServer,
        export_plan,
        run_open_loop,
    )
    from keystone_tpu.utils.faults import FaultPlan, FaultRule

    n, d_in, num_ffts, bs = 8_192, 784, 2, 1_024
    num_replicas = int(os.environ.get("BENCH_REPLICAS", "3"))
    duration_s = float(os.environ.get("BENCH_REPLICA_DURATION_S", "4"))
    rate_x = float(os.environ.get("BENCH_REPLICA_RATE_X", "4"))
    rng = np.random.default_rng(17)

    def fit_model(seed):
        r = np.random.default_rng(seed)
        X = jnp.asarray(r.normal(size=(n, d_in)).astype(np.float32))
        y = r.integers(0, 10, size=n)
        labels = Dataset.of(jnp.asarray(np.asarray(
            ClassLabelIndicatorsFromIntLabels(10)(Dataset.of(y)).array
        )))
        cfg = MnistRandomFFTConfig(
            num_ffts=num_ffts, block_size=bs, image_size=d_in
        )
        return build_featurizer(cfg).and_then(
            BlockLeastSquaresEstimator(bs, 1, 1e-4), Dataset.of(X), labels
        ).fit()

    plan = export_plan(fit_model(17), np.zeros(d_in, np.float32),
                       max_batch=128)
    plan2 = export_plan(fit_model(18), np.zeros(d_in, np.float32),
                        max_batch=128)
    single_s = plan.measure_single_request_s(reps=5)
    rate_hz = rate_x / single_s  # rate_x x one replica's naive throughput
    pool = rng.normal(size=(512, d_in)).astype(np.float32)

    def req(i):
        return pool[i % len(pool)]

    # CALIBRATE the latency SLO bound from a short uninstrumented storm
    # at the same offered rate: on a host where batching does not
    # amortize (CPU), steady-state latency is queue-wait-dominated and
    # any bound derived from single_s alone pages on healthy traffic —
    # the objective must be "3x the MEASURED healthy p99", the same
    # measured-over-assumed discipline every other row follows.
    calib_srv = ReplicatedServer(
        plan, num_replicas=num_replicas,
        max_wait_ms=min(25.0, max(2.0, 1.5e3 * single_s)),
        max_queue_depth=4096, watchdog_interval_s=0.02,
    )
    try:
        calib = run_open_loop(
            calib_srv.submit, req, rate_hz=rate_hz,
            duration_s=duration_s, seed=20,
        )
    finally:
        calib_srv.close()
    # The bound covers BOTH the healthy tail (3x p99) and the host's
    # observed stall magnitude (1.25x the calibration storm's worst
    # latency): a shared/noisy host's scheduler hiccup lands a whole
    # fast window over any p99-derived bound and pages the STEADY
    # control leg — the calibration storm runs the full leg duration so
    # it samples the same noise the legs will see.
    calib_max_s = max(calib.latencies_s) if calib.latencies_s else 0.0
    latency_bound_s = max(3.0 * calib.p99_latency_s, 1.25 * calib_max_s,
                          40.0 * single_s, 0.05)

    # The live SLO plane over the whole storm (ISSUE 10): a p99-latency
    # objective at the calibrated bound plus an availability objective,
    # burn windows scaled to the leg length so the kill's failure burst
    # is a fast-window event and the recovery is observable within the
    # same run.
    slo_tracker = obs.SLOTracker([
        obs.SLOObjective(
            "latency", kind="latency",
            threshold_s=latency_bound_s, target=0.9,
            fast_window_s=max(duration_s / 8.0, 0.25),
            slow_window_s=max(duration_s / 2.0, 1.0),
            breach_burn=4.0,
        ),
        obs.SLOObjective(
            # Planet-scale availability budget (0.1%): a replica-kill
            # burst that fails even a handful of in-flight requests in
            # one fast window burns visibly, while the steady leg (no
            # injected faults, no sheds) spends nothing. The PR-7
            # failover is GOOD enough that a 1% budget would hide a
            # clean single-kill — the point of the leg is that the
            # ledger sees the degraded window anyway.
            "availability", kind="availability", target=0.999,
            fast_window_s=max(duration_s / 8.0, 0.25),
            slow_window_s=max(duration_s / 2.0, 1.0),
            breach_burn=4.0,
        ),
    ])

    def breach_count(verdict):
        return sum(
            1 for o in verdict["objectives"].values()
            for t in o["transitions"] if t["to"] == "BREACH"
        )

    def run_leg(srv, seed, fault_plan=None, mid_leg=None):
        import threading

        timer = None
        mid_errors = []
        if mid_leg is not None:
            def guarded_mid_leg():
                try:
                    mid_leg()
                except BaseException as e:  # noqa: BLE001 — re-raised below
                    mid_errors.append(e)

            timer = threading.Timer(duration_s / 2.0, guarded_mid_leg)
            timer.start()
        try:
            if fault_plan is not None:
                with fault_plan:
                    report = run_open_loop(
                        srv.submit, req, rate_hz=rate_hz,
                        duration_s=duration_s, seed=seed, slo=slo_tracker,
                    )
            else:
                report = run_open_loop(
                    srv.submit, req, rate_hz=rate_hz,
                    duration_s=duration_s, seed=seed, slo=slo_tracker,
                )
        finally:
            if timer is not None:
                timer.cancel()  # no-op if already fired; unarms on error
                timer.join()
        if mid_errors:
            # A swallowed swap failure would leave a clean-looking leg
            # that silently tested nothing — fail the row instead.
            raise RuntimeError(
                f"mid-leg action failed: {mid_errors[0]!r}"
            ) from mid_errors[0]
        d = report.to_row_dict()
        d["accounting_ok"] = (
            report.completed + report.rejected + report.failed
            == report.num_offered
        )
        return report, d

    legs = {}
    swap_report = {}
    srv = ReplicatedServer(plan, num_replicas=num_replicas,
                           max_wait_ms=min(25.0, max(2.0, 1.5e3 * single_s)),
                           max_queue_depth=4096, watchdog_interval_s=0.02,
                           slo=slo_tracker)
    try:
        steady_report, legs["steady"] = run_leg(srv, seed=21)
        if steady_report.slo["state"] != "OK" or breach_count(
            steady_report.slo
        ):
            # The steady leg IS the control: an SLO that pages with no
            # fault injected would make the kill leg's breach claim
            # meaningless.
            raise RuntimeError(
                "serving_replicated_chaos: the STEADY leg did not end "
                f"in SLO state OK (got {steady_report.slo['state']}, "
                f"{breach_count(steady_report.slo)} breaches) — the "
                "objective bounds are miscalibrated for this host"
            )
        # Kill whichever replica executes the mid-storm batch: scale the
        # call index off the steady leg's observed batch count so the
        # kill lands inside the window at any offered rate.
        batches_est = max(10, int(
            legs["steady"]["num_samples"]
            / max(srv.stats()["per_replica"][0].get("mean_batch_size")
                  or 1.0, 1.0)
        ))
        # A kill STORM, not a single kill: four loop-level worker kills
        # in quick succession mid-leg (whichever replicas execute those
        # batches die and restart — within the aggregate restart
        # budget, so the plane recovers rather than evicts). One kill's
        # failed in-flight batch can be a handful of requests — routing
        # around a single death is exactly what PR 7 built — but four
        # concentrated in one fast window are an unambiguous burst the
        # availability objective must page on.
        kill_at = max(5, batches_est // 2)
        kill = FaultPlan([FaultRule(
            "serving.replica.execute", "error",
            calls=[kill_at, kill_at + 2, kill_at + 4, kill_at + 6],
        )])
        kill_report, legs["kill"] = run_leg(srv, seed=22, fault_plan=kill)
        kill_stats = srv.stats()
        if kill_stats["restarts_total"] < 1:
            # The row's VALUE is the degraded-window p99 — if the
            # call-indexed kill never landed (batch-count estimate off),
            # a fault-free leg would silently masquerade as it.
            raise RuntimeError(
                "serving_replicated_chaos: the injected replica kill "
                f"never fired (estimated batch index {batches_est // 2}); "
                "the kill leg measured nothing"
            )
        if breach_count(kill_report.slo) < 1:
            # The SLO plane must SEE the kill: a degraded window that
            # never breached means the objectives watched nothing.
            raise RuntimeError(
                "serving_replicated_chaos: the replica kill produced NO "
                "SLO BREACH transition — the degraded window was "
                f"invisible to the objectives (verdict: "
                f"{kill_report.slo['state']})"
            )
        _, legs["swap"] = run_leg(
            srv, seed=23,
            mid_leg=lambda: swap_report.update(srv.swap_plan(plan2)),
        )
        final_stats = srv.stats()
        final_verdict = slo_tracker.verdict()
        if final_verdict["state"] == "BREACH":
            raise RuntimeError(
                "serving_replicated_chaos: the plane never RECOVERED "
                "out of SLO breach after the kill window — the row "
                "cannot claim graceful degradation"
            )
    finally:
        srv.close()

    # ---- autoscale leg (ISSUE 12): the SLO-closed loop end to end ----
    # A FRESH plane starting at ONE replica with the Autoscaler thread
    # driving elasticity from its own tracker: open-loop Poisson at 1x
    # the naive single-request rate (healthy), then a 4x spike that must
    # drive WARN/BREACH -> scale-up (with a chaos kill injected into the
    # FIRST scale-up spawn, absorbed by the restart budget), then a
    # quiesce leg whose p99 must recover under the calibrated bound
    # while sustained idle drives scale-down. Zero silent drops on every
    # leg; the controller block carries the decision-event count and
    # replica bounds beside the scale counters (make_row's audit rule).
    as_base_rate = rate_hz / 4.0  # 1x one replica's naive throughput
    as_slo = obs.SLOTracker([
        obs.SLOObjective(
            "latency", kind="latency",
            threshold_s=latency_bound_s, target=0.9,
            fast_window_s=max(duration_s / 8.0, 0.25),
            slow_window_s=max(duration_s / 2.0, 1.0),
            breach_burn=4.0,
        ),
        obs.SLOObjective(
            "availability", kind="availability", target=0.999,
            fast_window_s=max(duration_s / 8.0, 0.25),
            slow_window_s=max(duration_s / 2.0, 1.0),
            breach_burn=4.0,
        ),
    ])
    as_srv = ReplicatedServer(
        plan, num_replicas=1,
        max_wait_ms=min(25.0, max(2.0, 1.5e3 * single_s)),
        max_queue_depth=512, watchdog_interval_s=0.02, slo=as_slo,
    )
    as_ctl = Autoscaler(
        as_srv, as_slo, min_replicas=1, max_replicas=num_replicas,
        tick_interval_s=0.02,
        scale_up_sustain_s=max(duration_s / 16.0, 0.25),
        scale_down_sustain_s=max(duration_s / 8.0, 0.5),
        cooldown_s=max(duration_s / 8.0, 0.5),
        idle_queue_depth=4, idle_outstanding_per_replica=1.0,
        metrics=as_srv.metrics,
    ).start()
    spawn_kill = FaultPlan([FaultRule(
        "serving.autoscale.spawn", "error", calls=[0],
    )])
    as_legs = {}

    def as_leg(name, rate, seed):
        report = run_open_loop(
            as_srv.submit, req, rate_hz=rate, duration_s=duration_s,
            seed=seed, slo=as_slo,
        )
        d = report.to_row_dict()
        d["accounting_ok"] = (
            report.completed + report.rejected + report.failed
            == report.num_offered
        )
        if not d["accounting_ok"]:
            raise RuntimeError(
                f"serving_replicated_chaos: autoscale {name} leg has a "
                f"SILENT drop (offered {report.num_offered} != "
                f"{report.completed}+{report.rejected}+{report.failed})"
            )
        if not report.completed:
            raise RuntimeError(
                f"serving_replicated_chaos: autoscale {name} leg "
                "completed zero requests — no p99 to report"
            )
        as_legs[name] = d
        return report

    try:
        as_leg("base", as_base_rate, seed=24)
        with spawn_kill:
            spike_report = as_leg("spike", rate_hz, seed=25)
        if as_ctl.scale_ups < 1:
            raise RuntimeError(
                "serving_replicated_chaos: the 4x spike never drove a "
                f"scale-up (verdict {spike_report.slo['state']}, "
                f"decisions {as_ctl.decision_log()})"
            )
        spike_transitions = [
            t for o in spike_report.slo["objectives"].values()
            for t in o["transitions"]
        ]
        if not any(
            t["to"] in ("WARN", "BREACH") for t in spike_transitions
        ):
            raise RuntimeError(
                "serving_replicated_chaos: the spike scaled up without "
                "any WARN/BREACH transition — the control loop acted on "
                "nothing the SLO plane saw"
            )
        if spawn_kill.calls_seen("serving.autoscale.spawn") < 2:
            raise RuntimeError(
                "serving_replicated_chaos: the injected scale-up spawn "
                "kill was never retried — the restart budget did not "
                "absorb it"
            )
        # Settle: let the spike's queued backlog drain before the
        # quiesce leg, so its p99 measures recovered steady state, not
        # the spike's tail working through the queue.
        settle_deadline = time.perf_counter() + 30.0
        while (as_srv.autoscale_signals()["queue_depth"] > 0
               and time.perf_counter() < settle_deadline):
            time.sleep(0.05)
        quiesce_report = as_leg("quiesce", as_base_rate, seed=26)
        if quiesce_report.p99_latency_s > latency_bound_s:
            raise RuntimeError(
                "serving_replicated_chaos: post-scale p99 "
                f"({quiesce_report.p99_latency_s * 1e3:.1f}ms) never "
                f"recovered under the calibrated bound "
                f"({latency_bound_s * 1e3:.1f}ms)"
            )
        # Quiesce drives scale-down (the loadgen window may end inside
        # the idle-sustain window — poll past it).
        down_deadline = time.perf_counter() + 30.0
        while (as_ctl.scale_downs < 1
               and time.perf_counter() < down_deadline):
            time.sleep(0.05)
        if as_ctl.scale_downs < 1:
            raise RuntimeError(
                "serving_replicated_chaos: sustained quiesce never "
                f"drove a scale-down (decisions {as_ctl.decision_log()})"
            )
        as_stats = as_ctl.stats()
        as_verdict = as_slo.verdict()
        if as_verdict["state"] == "BREACH":
            raise RuntimeError(
                "serving_replicated_chaos: the autoscale plane never "
                "recovered out of SLO breach after the spike"
            )
    finally:
        as_ctl.close()
        as_srv.close()

    for leg_name, leg in legs.items():
        if not leg["num_samples"]:
            # A leg with zero completions has no p99 — publishing a
            # sentinel as the row's headline value would dress a broken
            # window (total eviction, all-shed overload) as a clean
            # measurement. Fail loudly like the kill-never-fired guard.
            raise RuntimeError(
                f"serving_replicated_chaos: the {leg_name} leg completed "
                f"zero requests (offered {leg['num_offered']}, rejected "
                f"{leg['rejected']}, failed {leg['failed']}) — no p99 to "
                "report"
            )
    p99_steady_s = legs["steady"]["p99_latency_ms"] / 1e3
    p99_degraded_s = legs["kill"]["p99_latency_ms"] / 1e3
    return make_row(
        "serving_replicated_chaos",
        round(p99_degraded_s, 5),
        "s",
        round(p99_steady_s / p99_degraded_s, 3),
        "open_loop_latency",
        {
            "pipeline": "mnist_random_fft (fit n=8192, replicated online)",
            "num_replicas": num_replicas,
            "single_request_s": round(single_s, 6),
            "offered_rate_hz": round(rate_hz, 2),
            "buckets": plan.buckets,
            "legs": legs,
            "kill_leg": {
                "restarts_total": kill_stats["restarts_total"],
                "healthy_after": kill_stats["healthy_replicas"],
                "evicted": kill_stats["evicted_replicas"],
            },
            "swap_leg": {
                "swap_report": swap_report.get("replicas"),
                "old_fingerprint": plan.fingerprint,
                "new_fingerprint": plan2.fingerprint,
                "per_fingerprint_completed": legs["swap"].get(
                    "per_fingerprint_completed"
                ),
                # Requests that resolved with a NAMED error (e.g. a sync
                # degraded reject through a drain window) — NOT drops;
                # zero silent drops is what accounting_ok asserts.
                "failed_named": legs["swap"]["failed"],
            },
            "final_degraded": final_stats["degraded"],
            # The SLO-closed loop (ISSUE 12): 1x base -> 4x spike ->
            # quiesce on a fresh one-replica plane with the Autoscaler
            # thread live; asserted above: spike drove WARN/BREACH ->
            # scale-up (with the first spawn attempt CHAOS-KILLED and
            # absorbed by the restart budget), quiesce p99 recovered
            # under the calibrated bound, sustained idle drove
            # scale-down, zero silent drops on every leg. The
            # controller block carries num_decisions + min/max replica
            # bounds beside the scale counters (make_row audit rule).
            "autoscale_leg": {
                "base_rate_hz": round(as_base_rate, 2),
                "spike_rate_hz": round(rate_hz, 2),
                "spawn_kill_absorbed": True,
                "legs": as_legs,
                "controller": {
                    k: as_stats[k] for k in (
                        "min_replicas", "max_replicas", "replicas_low",
                        "replicas_high", "scale_ups", "scale_downs",
                        "failed_scale_ups", "brownout_steps_entered",
                        "brownout_steps_exited", "num_decisions",
                        "ticks",
                    )
                },
                "decisions": as_stats["decisions"],
                "slo": {
                    "state": as_verdict["state"],
                    "spike_leg_state": as_legs["spike"]["slo"]["state"],
                    "latency_bound_ms": round(latency_bound_s * 1e3, 3),
                },
            },
            # The SLO story (ISSUE 10): final per-objective verdict with
            # the FULL transition log and error-budget ledger — the
            # degraded window's spend is a ledger read (asserted above:
            # steady OK, kill BREACHes, final recovered).
            "slo": {
                "state": final_verdict["state"],
                "steady_leg_state": legs["steady"]["slo"]["state"],
                "kill_leg_breaches": breach_count(kill_report.slo),
                "latency_bound_ms": round(latency_bound_s * 1e3, 3),
                "calibration_p99_ms": round(
                    calib.p99_latency_s * 1e3, 3
                ),
                "objectives": final_verdict["objectives"],
            },
            "timing_note": (
                "value = p99 latency (s) over the KILL leg (the "
                "degraded window: a four-kill storm of loop-level "
                "replica worker deaths mid-leg, each restarted by the "
                "watchdog); vs_baseline = steady-leg p99 / "
                "kill-leg p99 (1.0 = kill invisible in the tail); all "
                f"legs open-loop Poisson at the same offered rate for "
                f"{duration_s:.0f}s each; accounting_ok per leg asserts "
                "offered == completed + rejected + failed (zero silent "
                "drops); the slo block carries the live verdict "
                "(steady OK -> kill BREACH -> recovery) with the "
                "error-budget ledger attributing spend per state window"
            ),
            "device": str(jax.devices()[0]),
        },
    )


def serving_fleet_chaos_metric():
    """The multi-process serving fleet under chaos (ISSUE 20 tentpole):
    N crash-contained planes — each a FULL per-process ReplicatedServer
    stack behind a stdlib-socket RPC — fronted by one FleetRouter doing
    least-loaded + per-tenant deficit-fair admission, driven by >= 8
    independent open-loop Poisson tenants at an aggregate offered rate
    >= 4x ONE plane's sustainable throughput, through three legs:

      1. ``steady`` — no faults: the fleet's baseline worst-tenant p99.
      2. ``kill``   — ``SIGKILL`` of a whole plane PROCESS mid-storm
         (not a thread, not an injected exception: the OS takes the
         process). The watchdog declares it dead off missed heartbeats,
         fails its in-flight requests LOUDLY, folds its last-scraped
         latency state into the fleet merge, and respawns it from the
         shipped plan within the restart budget. The LEG's
         worst-tenant p99 is the degraded-window value the row reports.
      3. ``roll``   — mid-storm, ``offer_canary`` rolls a second fitted
         model across the SURVIVING fleet: every eligible plane's own
         LifecycleController runs gate -> canary -> zero-drop promote
         and publishes the new fingerprint.

    value = degraded-window (kill-leg) worst-tenant p99 seconds;
    vs_baseline = steady worst-tenant p99 / kill worst-tenant p99
    (1.0 = the process death was invisible in the tail). The row RAISES
    unless: every leg's books balance per tenant (loadgen side), the
    router's fleet-wide books balance EXACTLY after the drain
    (offered == completed + rejected + failed with zero in flight —
    across a process SIGKILL), the two sides AGREE on total offered,
    the watchdog respawn actually fired (new pid), and the canary roll
    published on every surviving plane. The ``fleet`` block is
    ``FleetRouter.stats()`` verbatim — it satisfies make_row's
    ``_fleet_violations`` audit (fleet_p99/aggregate_offered claims
    ride beside ``num_planes`` + per-plane books) by construction.

    Env knobs: BENCH_FLEET_PLANES (default 4), BENCH_FLEET_TENANTS
    (default 8), BENCH_FLEET_REPLICAS (replicas per plane, default 2),
    BENCH_FLEET_DURATION_S (per-leg window, default 4),
    BENCH_FLEET_RATE_X (aggregate offered rate as a multiple of one
    plane's sustainable throughput, default 4).
    """
    import signal
    import threading

    from keystone_tpu.data import Dataset
    from keystone_tpu.ops.learning.block import BlockLeastSquaresEstimator
    from keystone_tpu.ops.util import ClassLabelIndicatorsFromIntLabels
    from keystone_tpu.pipelines.mnist_random_fft import (
        MnistRandomFFTConfig,
        build_featurizer,
    )
    from keystone_tpu.serving import export_plan
    from keystone_tpu.serving.fleet import FleetRouter
    from keystone_tpu.serving.fleet_plane import encode_plan_ship
    from keystone_tpu.serving.loadgen import run_multi_tenant_open_loop

    n, d_in, num_ffts, bs = 8_192, 784, 2, 1_024
    num_planes = int(os.environ.get("BENCH_FLEET_PLANES", "4"))
    num_tenants = int(os.environ.get("BENCH_FLEET_TENANTS", "8"))
    replicas_per_plane = int(os.environ.get("BENCH_FLEET_REPLICAS", "2"))
    duration_s = float(os.environ.get("BENCH_FLEET_DURATION_S", "4"))
    rate_x = float(os.environ.get("BENCH_FLEET_RATE_X", "4"))
    if num_planes < 4 or num_tenants < 8:
        raise RuntimeError(
            "serving_fleet_chaos: the row's claim is a FLEET under "
            "multi-tenant load — >= 4 planes and >= 8 tenants "
            f"(got {num_planes} planes, {num_tenants} tenants)"
        )
    rng = np.random.default_rng(29)

    def fit_model(seed):
        r = np.random.default_rng(seed)
        X = jnp.asarray(r.normal(size=(n, d_in)).astype(np.float32))
        y = r.integers(0, 10, size=n)
        labels = Dataset.of(jnp.asarray(np.asarray(
            ClassLabelIndicatorsFromIntLabels(10)(Dataset.of(y)).array
        )))
        cfg = MnistRandomFFTConfig(
            num_ffts=num_ffts, block_size=bs, image_size=d_in
        )
        return build_featurizer(cfg).and_then(
            BlockLeastSquaresEstimator(bs, 1, 1e-4), Dataset.of(X), labels
        ).fit()

    fitted = fit_model(29)
    fitted2 = fit_model(30)
    # ONE padding bucket: the per-plane lifecycle gate dry-runs the
    # padded-bucket bit-identity contract, and this FFT plan's outputs
    # are NOT bit-identical across buckets on CPU (XLA tiles the padded
    # matmuls differently) — a multi-bucket candidate would be
    # (correctly) gate-rejected before the canary ever ran.
    plan = export_plan(fitted, np.zeros(d_in, np.float32),
                       max_batch=128, buckets=[128])
    plan2 = export_plan(fitted2, np.zeros(d_in, np.float32),
                        max_batch=128, buckets=[128])
    ship = encode_plan_ship(fitted, plan)
    ship2 = encode_plan_ship(fitted2, plan2)
    single_s = plan.measure_single_request_s(reps=5)
    pool = rng.normal(size=(512, d_in)).astype(np.float32)

    def req(tenant, i):
        return pool[i % len(pool)]

    # Bounded doors: at 4x overload an unbounded-ish queue converts the
    # surplus into tens-of-seconds of queue wait for the requests it
    # DOES admit. Small admission bounds shed the surplus at the door
    # instead, so the headline p99 prices the served path, not the
    # backlog.
    plane_cfg = {
        "max_wait_ms": min(25.0, max(2.0, 1.5e3 * single_s)),
        "max_queue_depth": 256,
    }
    # MEASURE one plane's sustainable rate through the REAL serving
    # path (router + RPC + dispatch concurrency + in-plane batching) —
    # the naive 1/single_s convention overstates a cross-process
    # plane's capacity by the whole RPC round trip, and a rate derived
    # from it would drown every leg in admission sheds. A short
    # deliberately-saturating storm against a ONE-plane fleet (same
    # per-plane dispatcher share as the real fleet) measures what the
    # plane actually completes per second.
    probe_rate_hz = 4.0 * replicas_per_plane / single_s
    probe_rates = {f"t{i}": probe_rate_hz / num_tenants
                   for i in range(num_tenants)}
    calib_fleet = FleetRouter(
        ship, num_planes=1, replicas_per_plane=replicas_per_plane,
        max_outstanding=8192, dispatchers=4,
        plane_cfg=dict(plane_cfg),
    )
    try:
        calib = run_multi_tenant_open_loop(
            calib_fleet.submit_tenant, req, probe_rates,
            duration_s=duration_s, seed=30,
        )
    finally:
        calib_fleet.close()
    calib_d = calib.to_row_dict()
    one_plane_rate_hz = calib_d["completed_total"] / duration_s
    if not one_plane_rate_hz:
        raise RuntimeError(
            "serving_fleet_chaos: the calibration plane completed "
            "ZERO requests — no sustainable rate to scale from"
        )
    rate_hz_total = rate_x * one_plane_rate_hz
    rates = {f"t{i}": rate_hz_total / num_tenants
             for i in range(num_tenants)}

    legs = {}
    reports = {}

    def run_leg(fleet, name, seed, mid_leg=None):
        timer = None
        mid_errors = []
        if mid_leg is not None:
            def guarded_mid_leg():
                try:
                    mid_leg()
                except BaseException as e:  # noqa: BLE001 — re-raised
                    mid_errors.append(e)

            timer = threading.Timer(duration_s / 2.0, guarded_mid_leg)
            timer.start()
        try:
            report = run_multi_tenant_open_loop(
                fleet.submit_tenant, req, rates,
                duration_s=duration_s, seed=seed,
            )
        finally:
            if timer is not None:
                timer.cancel()
                timer.join()
        if mid_errors:
            # A swallowed kill/roll failure would leave a clean-looking
            # leg that tested nothing — fail the row instead.
            raise RuntimeError(
                f"serving_fleet_chaos: {name} mid-leg action failed: "
                f"{mid_errors[0]!r}"
            ) from mid_errors[0]
        if not report.accounting_ok():
            d = report.to_row_dict()
            raise RuntimeError(
                f"serving_fleet_chaos: the {name} leg has a SILENT "
                f"drop on the loadgen's books (offered "
                f"{d['offered_total']} != {d['completed_total']}+"
                f"{d['rejected_total']}+{d['failed_total']})"
            )
        for t, r in sorted(report.tenants.items()):
            if not r.completed:
                # A tenant with zero completions has no p99 — the
                # worst-tenant headline would silently skip it.
                raise RuntimeError(
                    f"serving_fleet_chaos: tenant {t} completed ZERO "
                    f"requests in the {name} leg (offered "
                    f"{r.num_offered}, rejected {r.rejected}, failed "
                    f"{r.failed}) — no p99 to report"
                )
        reports[name] = report
        legs[name] = report.to_row_dict()
        return report

    def worst_tenant_p99_s(name):
        return max(
            t["p99_latency_ms"] for t in legs[name]["tenants"].values()
        ) / 1e3

    victim = {}

    def kill_one_plane():
        pids = fleet.plane_pids()
        name = sorted(pids)[0]
        victim["name"] = name
        victim["pid"] = pids[name]
        os.kill(pids[name], signal.SIGKILL)

    roll = {}

    fleet = FleetRouter(
        ship, num_planes=num_planes,
        replicas_per_plane=replicas_per_plane,
        max_outstanding=1024,
        heartbeat_interval_s=0.1, heartbeat_timeout_s=2.0,
        restart_budget=2,
        plane_cfg=dict(plane_cfg),
    )
    try:
        run_leg(fleet, "steady", seed=31)
        run_leg(fleet, "kill", seed=32, mid_leg=kill_one_plane)
        # The respawn races the leg's tail: poll the watchdog's work to
        # completion (bounded) before asserting on it.
        deadline = time.perf_counter() + 60.0
        while time.perf_counter() < deadline:
            ks = fleet.stats()
            if (ks["restarts_total"] >= 1
                    and ks["healthy_planes"] == num_planes):
                break
            time.sleep(0.05)
        kill_stats = fleet.stats()
        if kill_stats["restarts_total"] < 1:
            raise RuntimeError(
                "serving_fleet_chaos: the SIGKILL of plane "
                f"{victim.get('name')} (pid {victim.get('pid')}) never "
                "drove a watchdog respawn — the kill leg measured a "
                "healthy fleet"
            )
        if kill_stats["healthy_planes"] != num_planes:
            raise RuntimeError(
                "serving_fleet_chaos: the fleet never RECOVERED to "
                f"{num_planes} healthy planes after the kill (got "
                f"{kill_stats['healthy_planes']}, evicted "
                f"{kill_stats['evicted_planes']})"
            )
        respawned_pid = fleet.plane_pids()[victim["name"]]
        if respawned_pid == victim["pid"]:
            raise RuntimeError(
                "serving_fleet_chaos: the respawned plane reports the "
                f"DEAD pid {victim['pid']} — the watchdog restarted "
                "nothing"
            )
        run_leg(
            fleet, "roll", seed=33,
            mid_leg=lambda: roll.update(fleet.offer_canary(ship2)),
        )
        not_rolled = sorted(
            name for name, r in roll.items()
            if not (r.get("ok")
                    and r.get("result", {}).get("published"))
        )
        if not_rolled:
            raise RuntimeError(
                "serving_fleet_chaos: the canary roll did not publish "
                f"on every surviving plane (failed: "
                f"{ {p: roll[p] for p in not_rolled} })"
            )
        # The router learns the rolled fingerprint off the planes' next
        # exporter snapshot — poll past one export+scrape interval.
        fp_deadline = time.perf_counter() + 30.0
        stale = None
        while time.perf_counter() < fp_deadline:
            rolled_stats = fleet.stats()
            stale = sorted(
                name for name, p in rolled_stats["planes"].items()
                if p["fingerprint"] != plan2.fingerprint
            )
            if not stale:
                break
            time.sleep(0.05)
        if stale:
            raise RuntimeError(
                "serving_fleet_chaos: planes still advertise the OLD "
                f"fingerprint after the roll: {stale}"
            )
        # Drain, then the fleet invariant: the router's own books must
        # balance EXACTLY across a process SIGKILL, and agree with the
        # loadgen's independent count of what it offered.
        drain_deadline = time.perf_counter() + 30.0
        while (not fleet.accounting_ok()
               and time.perf_counter() < drain_deadline):
            time.sleep(0.05)
        final_stats = fleet.stats()
        if not fleet.accounting_ok():
            raise RuntimeError(
                "serving_fleet_chaos: the fleet books do NOT balance "
                f"after the drain: offered "
                f"{final_stats['aggregate_offered']} != completed "
                f"{final_stats['completed']} + rejected "
                f"{final_stats['rejected']} + failed "
                f"{final_stats['failed']} (inflight "
                f"{final_stats['inflight']})"
            )
        offered_by_loadgen = sum(
            legs[name]["offered_total"] for name in legs
        )
        if final_stats["aggregate_offered"] != offered_by_loadgen:
            raise RuntimeError(
                "serving_fleet_chaos: the router and the loadgen "
                "DISAGREE on total offered ("
                f"{final_stats['aggregate_offered']} vs "
                f"{offered_by_loadgen}) — requests entered the fleet "
                "outside the front door's books"
            )
    finally:
        fleet.close()

    p99_steady_s = worst_tenant_p99_s("steady")
    p99_degraded_s = worst_tenant_p99_s("kill")
    return make_row(
        "serving_fleet_chaos",
        round(p99_degraded_s, 5),
        "s",
        round(p99_steady_s / p99_degraded_s, 3),
        "open_loop_latency",
        {
            "pipeline": "mnist_random_fft (fit n=8192, process fleet)",
            "num_planes": num_planes,
            "replicas_per_plane": replicas_per_plane,
            "num_tenants": num_tenants,
            "single_request_s": round(single_s, 6),
            "one_plane_sustainable_hz": round(one_plane_rate_hz, 2),
            "calibration": {
                "probe_rate_hz": round(probe_rate_hz, 2),
                "offered": calib_d["offered_total"],
                "completed": calib_d["completed_total"],
                "note": "one-plane fleet saturated through the real "
                        "router/RPC path; sustainable = completed/s",
            },
            "offered_rate_hz": round(rate_hz_total, 2),
            "rate_multiple_of_one_plane": rate_x,
            "legs": legs,
            "kill_leg": {
                "victim": victim["name"],
                "victim_pid": victim["pid"],
                "respawned_pid": respawned_pid,
                "restarts_total": kill_stats["restarts_total"],
                "healthy_after": kill_stats["healthy_planes"],
                "evicted": kill_stats["evicted_planes"],
                # Requests that died WITH the process resolved as NAMED
                # failures — not drops; the balanced books above are
                # the zero-silent-drop claim.
                "failed_named": legs["kill"]["failed_total"],
            },
            "canary_roll": {
                "old_fingerprint": plan.fingerprint,
                "new_fingerprint": plan2.fingerprint,
                "planes_rolled": sorted(roll),
            },
            # FleetRouter.stats() verbatim: fleet_p99/aggregate_offered
            # beside num_planes + per-plane books — the
            # _fleet_violations audit's required shape.
            "fleet": final_stats,
            "timing_note": (
                "value = worst-tenant p99 latency (s) over the KILL "
                "leg (the degraded window: one whole plane PROCESS "
                "SIGKILLed mid-storm, declared dead off missed "
                "heartbeats, in-flight requests failed loudly, plane "
                "respawned from the shipped plan); vs_baseline = "
                "steady worst-tenant p99 / kill worst-tenant p99 "
                f"(1.0 = process death invisible in the tail); "
                f"{num_tenants} independent Poisson tenants at an "
                f"aggregate {rate_x:g}x one plane's sustainable rate "
                f"for {duration_s:.0f}s per leg; asserted: per-leg "
                "loadgen books, EXACT router books across the SIGKILL "
                "(offered == completed + rejected + failed, zero in "
                "flight), router/loadgen offered agreement, watchdog "
                "respawn (new pid), canary published on every "
                "surviving plane"
            ),
            "device": str(jax.devices()[0]),
        },
    )


def continuous_learning_staleness_metric():
    """The continuous-learning control plane end to end (ISSUE 15
    tentpole): a ContinuousTrainer incrementally re-fitting over
    arriving synthetic segments while the 2-replica plane serves
    open-loop Poisson traffic, publishing every K segments through the
    LifecycleController's gate → canary → promote path. Value = MEDIAN
    model staleness (newest covered shard arrival -> first response
    served under the covering fingerprint). The row RAISES unless:

      1. ``learn``   — >= 3 candidates published with measured
         staleness, and the leg's serving p99 holds under a bound
         calibrated on this host (1.25x the calibration storm's max).
      2. ``bad_candidate`` — an injected NaN-weighted candidate dies at
         the validation gate with a ``lifecycle.decision`` audit and
         ZERO requests served under its fingerprint.
      3. ``canary_regression`` — an injected exec-latency regression
         (same weights + a host sleep) passes the gate, is caught by
         the canary comparison under sustained load, and rolls back —
         the full plane never serves it.

    Every leg asserts zero silent drops
    (offered == completed + rejected + failed)."""
    import threading

    from keystone_tpu import obs
    from keystone_tpu.learning import ContinuousTrainer, TimedSegmentFeed
    from keystone_tpu.ops.learning.linear import LinearMapper
    from keystone_tpu.serving import (
        LifecycleController,
        ReplicatedServer,
        export_plan,
        run_open_loop,
    )
    from keystone_tpu.workflow import Transformer
    from keystone_tpu.workflow.pipeline import (
        FittedPipeline,
        TransformerGraph,
    )

    d, k = 16, 4
    max_batch = 64
    rate_hz = 250.0
    learn_duration_s = 8.0
    leg_duration_s = 3.0
    num_segments, publish_k = 12, 3
    rng = np.random.default_rng(7)
    W_true = rng.normal(size=(d, k)).astype(np.float32)

    def segment(n=256, noise=0.01):
        X = rng.normal(size=(n, d)).astype(np.float32)
        y = (X @ W_true
             + noise * rng.normal(size=(n, k))).astype(np.float32)
        return X, y

    def fitted_of(transformer):
        pipe = transformer.to_pipeline()
        return FittedPipeline(
            TransformerGraph.from_graph(pipe.executor.graph),
            pipe.source, pipe.sink,
        )

    def solve_W(X, y):
        X64 = X.astype(np.float64)
        return np.linalg.solve(
            X64.T @ X64 + 1e-3 * np.eye(d),
            X64.T @ y.astype(np.float64),
        ).astype(np.float32)

    class _SlowLinear(Transformer):
        """The injected canary regression: the incumbent's exact GEMM
        plus a deliberate host sleep per batch — quality-identical
        (passes the gate), latency-regressed (the canary must catch
        it). Host-path on purpose: the exec regression rides the eager
        fallback, bucket bit-identity still holds."""

        def __init__(self, W, delay_s):
            self.W = np.asarray(W, np.float32)
            self.delay_s = float(delay_s)

        def apply(self, x):
            time.sleep(self.delay_s)
            return jnp.asarray(np.asarray(x) @ self.W)

        def batch_apply(self, ds):
            time.sleep(self.delay_s)
            return ds.map_batch(
                lambda X: jnp.asarray(np.asarray(X)) @ jnp.asarray(self.W)
            )

    X0, y0 = segment()
    W0 = solve_W(X0, y0)
    plan0 = export_plan(
        fitted_of(LinearMapper(W0)), np.zeros(d, np.float32),
        max_batch=max_batch,
    )
    single_s = plan0.measure_single_request_s()
    holdout = segment(1024)
    pool = rng.normal(size=(256, d)).astype(np.float32)

    def storm(server, duration, seed):
        return run_open_loop(
            server.submit, lambda i: pool[i % len(pool)],
            rate_hz=rate_hz, duration_s=duration, seed=seed,
        )

    def leg_dict(rep):
        out = rep.to_row_dict()
        out["accounting_ok"] = (
            rep.num_offered == rep.completed + rep.rejected + rep.failed
        )
        if not out["accounting_ok"]:
            raise RuntimeError(
                "continuous_learning_staleness: SILENT DROPS — offered "
                f"{rep.num_offered} != completed {rep.completed} + "
                f"rejected {rep.rejected} + failed {rep.failed}"
            )
        return out

    # Calibrate the p99 bound on THIS host, same discipline as the
    # replicated-chaos row: the bound covers 1.25x the calibration
    # storm's observed max latency, measured over the full leg length.
    calib_srv = ReplicatedServer(
        plan0, num_replicas=2, max_batch=max_batch, max_wait_ms=1.0,
    )
    try:
        calib = storm(calib_srv, leg_duration_s, seed=11)
    finally:
        calib_srv.close()
    if not calib.latencies_s:
        raise RuntimeError(
            "continuous_learning_staleness: calibration storm completed "
            "zero requests"
        )
    # The bound the learn leg's p99 must hold under: 1.25x the steady
    # calibration storm's observed max (shared-host noise cover, the
    # replicated-chaos row's discipline) times a DECLARED
    # publication-churn allowance — the learn leg inherently pays for
    # canary windows, rolling swap drains, and the trainer's
    # export/compile work on the same host, none of which the steady
    # calibration storm sees. The allowance is part of the row's
    # stated claim, recorded in serving_bound below.
    churn_allowance = 4.0
    steady_cover_s = 1.25 * max(calib.latencies_s)
    bound_s = churn_allowance * steady_cover_s

    slo = obs.SLOTracker([
        obs.SLOObjective("latency", kind="latency", threshold_s=bound_s,
                         target=0.99),
        obs.SLOObjective("availability", kind="availability",
                         target=0.999),
    ])
    server = ReplicatedServer(
        plan0, num_replicas=2, max_batch=max_batch, max_wait_ms=1.0,
        slo=slo,
    )
    ctl = None
    legs = {}
    try:
        ctl = LifecycleController(
            server, plan0, holdout=holdout, quality_bound=0.05,
            canary_sustain_s=0.6, canary_min_samples=10, slo=slo,
        ).start()

        # ---- leg 1: learn — republish every K arriving segments ----
        offsets = [
            0.6 * learn_duration_s * i / (num_segments - 1)
            for i in range(num_segments)
        ]
        feed = TimedSegmentFeed(
            [segment() for _ in range(num_segments)],
            arrival_offsets=offsets,
        )
        trainer = ContinuousTrainer(feed, ctl,
                                    publish_every_k=publish_k)
        trainer.start()
        learn_rep = storm(server, learn_duration_s, seed=12)
        trainer.join(timeout=60.0)
        ctl.poll()  # settle the final staleness clock
        if trainer.error is not None:
            raise RuntimeError(
                f"continuous_learning_staleness: trainer died: "
                f"{trainer.error!r}"
            )
        legs["learn"] = leg_dict(learn_rep)
        lc_after_learn = ctl.stats()
        if lc_after_learn["published"] < 3:
            raise RuntimeError(
                "continuous_learning_staleness: fewer than 3 candidates "
                f"published ({lc_after_learn['published']}) — no "
                "staleness claim"
            )
        staleness = ctl.staleness_samples()
        if len(staleness) < 3:
            raise RuntimeError(
                "continuous_learning_staleness: fewer than 3 staleness "
                f"samples ({len(staleness)}) across the publications"
            )
        learn_p99_s = (learn_rep.p99_latency_s
                       if learn_rep.p99_latency_s is not None
                       else float("inf"))
        if learn_p99_s > bound_s:
            raise RuntimeError(
                "continuous_learning_staleness: serving p99 "
                f"{learn_p99_s * 1e3:.2f}ms did NOT hold under the "
                f"calibrated bound {bound_s * 1e3:.2f}ms across the "
                "publications"
            )

        def leg_with_offer(candidate, seed):
            """One open-loop leg with a mid-storm controller offer()
            (the storm rides a thread; the offer — which may span a
            full canary window — runs on this one)."""
            holder = {}

            def _storm():
                holder["rep"] = storm(server, leg_duration_s, seed)

            st = threading.Thread(target=_storm)
            st.start()
            time.sleep(0.5)  # warm the window so incumbents have stats
            result = ctl.offer(candidate)
            st.join()
            return result, holder["rep"]

        # ---- leg 2: injected NaN candidate dies at the gate ----
        bad = fitted_of(
            LinearMapper(np.full((d, k), np.nan, np.float32))
        )
        bad_result, bad_rep = leg_with_offer(bad, seed=13)
        legs["bad_candidate"] = leg_dict(bad_rep)
        if bad_result["published"] or (
            bad_result["reason"] != "non_finite_weights"
        ):
            raise RuntimeError(
                "continuous_learning_staleness: the NaN candidate was "
                f"NOT gate-rejected ({bad_result})"
            )
        bad_fp = bad_result["fingerprint"]
        served_fps = set(
            legs["bad_candidate"].get("per_fingerprint_completed") or {}
        ) | set(server.first_completion_times())
        if bad_fp in served_fps:
            raise RuntimeError(
                "continuous_learning_staleness: requests were served "
                f"under the REJECTED fingerprint {bad_fp}"
            )
        legs["bad_candidate"]["rejected_fingerprint"] = bad_fp
        legs["bad_candidate"]["gate_reason"] = bad_result["reason"]

        # ---- leg 3: injected canary latency regression rolls back ----
        incumbent_before = ctl.incumbent_fingerprint
        slow = fitted_of(_SlowLinear(
            np.asarray(_incumbent_W(ctl), np.float32), delay_s=0.03,
        ))
        slow_result, slow_rep = leg_with_offer(slow, seed=14)
        legs["canary_regression"] = leg_dict(slow_rep)
        if slow_result["published"] or (
            slow_result["reason"] != "canary_latency_regression"
        ):
            raise RuntimeError(
                "continuous_learning_staleness: the injected latency "
                "regression was NOT caught by the canary "
                f"({slow_result})"
            )
        if ctl.incumbent_fingerprint != incumbent_before:
            raise RuntimeError(
                "continuous_learning_staleness: the canary rollback did "
                "not restore the incumbent fingerprint"
            )
        final_stats = server.stats()
        live_fps = {
            r["plan_fingerprint"]
            for r in final_stats["per_replica"].values()
            if r["in_rotation"]
        }
        if live_fps != {incumbent_before}:
            raise RuntimeError(
                "continuous_learning_staleness: rotation is not fully "
                f"back on the incumbent ({live_fps})"
            )
        legs["canary_regression"]["canary"] = slow_result["canary"]
        lc = ctl.stats()
        if lc["rollbacks"] < 1 or lc["rejected"] < 1:
            raise RuntimeError(
                "continuous_learning_staleness: the rollback/reject "
                f"counters did not move ({lc['rollbacks']}, "
                f"{lc['rejected']})"
            )
        verdict = slo.verdict()
        decisions = ctl.decision_log()
    finally:
        if ctl is not None:
            ctl.close()
        server.close()

    staleness_median_s = float(np.median(staleness))
    return make_row(
        "continuous_learning_staleness",
        round(staleness_median_s, 5),
        "s",
        round(bound_s / learn_p99_s, 3),
        "open_loop_latency",
        {
            "pipeline": (
                f"continuous linear d={d} k={k} over {num_segments} "
                "arriving synthetic segments (2-replica plane)"
            ),
            "num_replicas": 2,
            "single_request_s": round(single_s, 6),
            "offered_rate_hz": rate_hz,
            "num_published": lc["num_published"],
            "publish_every_k": publish_k,
            "num_segments": num_segments,
            "trainer": {
                k_: trainer.stats()[k_]
                for k_ in ("segments_fit", "resumes", "publishes")
            },
            "staleness": {
                "median_s": round(staleness_median_s, 6),
                "min_s": round(min(staleness), 6),
                "max_s": round(max(staleness), 6),
                "num_samples": len(staleness),
                "num_published": lc["num_published"],
                "offered_rate_hz": rate_hz,
            },
            "legs": legs,
            # The lifecycle block carries its own num_published; the
            # offered rate of the load every claim was measured under
            # rides beside it (the make_row lifecycle audit rule).
            "lifecycle": {
                **{k_: v for k_, v in lc.items() if k_ != "decisions"},
                "offered_rate_hz": rate_hz,
            },
            "decisions": decisions,
            "serving_bound": {
                "p99_bound_s": round(bound_s, 6),
                "calibration_max_s": round(max(calib.latencies_s), 6),
                "steady_cover_s": round(steady_cover_s, 6),
                "publication_churn_allowance": churn_allowance,
                "learn_leg_p99_s": round(learn_p99_s, 6),
                # The bound's own evidence: the calibration storm it
                # was measured over (the latency-audit rule).
                "num_samples": calib.completed,
                "offered_rate_hz": rate_hz,
            },
            "slo": {
                "state": verdict["state"],
                "objectives": {
                    name: {
                        "state": o["state"],
                        "budget_spent_fraction":
                            o["budget_spent_fraction"],
                    }
                    for name, o in verdict["objectives"].items()
                },
            },
            "timing_note": (
                "value = MEDIAN model staleness (s): newest covered "
                "shard arrival -> first response served under the "
                "covering plan fingerprint, across the learn leg's "
                "publications under open-loop Poisson at "
                f"{rate_hz:.0f} req/s; vs_baseline = calibrated p99 "
                "bound / learn-leg p99 (>1 = the tail held with "
                "headroom while the trainer republished); the "
                "bad_candidate and canary_regression legs are the "
                "gate/rollback proofs; accounting_ok per leg asserts "
                "offered == completed + rejected + failed"
            ),
            "device": str(jax.devices()[0]),
        },
    )


def placement_whatif_fidelity_metric():
    """ISSUE 19 acceptance row: record a real decision storm, replay it
    through the trace-driven capacity planner
    (keystone_tpu/placement/planner.py), and report how far the
    planner's 1x tail prediction lands from the storm's measured p99.

    The storm (everything under one ``obs.tracing`` dir):

      - a REAL ``LeastSquaresEstimator.optimize`` at the TIMIT-resident
        geometry (48 GB HBM budget) — emits the calibrated
        ``cost.decision`` plus its ``placement.solver`` mirror;
      - a REAL ``choose_mesh_layout`` over 8 devices — ``cost.decision``
        plus ``placement.mesh_layout``;
      - a REAL ``PlacementEngine``-priced model-zoo page-in, stamped
        with a measured wall 5% off its prediction (the planner's
        fidelity gate compares the two);
      - the REAL ``Autoscaler`` state machine (stub serving plane + SLO
        on a fake clock — the harness tests/test_serving_autoscale.py
        pins) scaling 1 -> 4 replicas under sustained WARN with the
        backlog ramping to queue=6 / outstanding=6, then walking the
        brownout ladder at max capacity — every action emits a genuine
        ``autoscale.decision`` (occupancy snapshots the queueing model
        reads) plus its ``placement.replica_count`` / ``.brownout``
        audit;
      - 100 ``serving.batch`` spans: a 10 ms service floor with the
        tail stretched to 35 ms by the storm.

    value = |ln(predicted 1x p99 / measured p99)| from
    ``CapacityPlanner.whatif_traffic(1.0)`` — the planner's admission
    ticket; vs_baseline = DEFAULT_DRIFT_THRESHOLD / value (>1 = the
    prediction sits inside the calibration plane's error bars with
    headroom). detail carries the full fidelity dict (every recorded
    argmin must reproduce through the replay) and the 2x-traffic /
    half-HBM / +1-tenant what-if rows ``bin/plan --whatif`` renders —
    each self-satisfying make_row's ``_whatif_violations`` audit
    (num_decisions + weights_family + a measured baseline on every
    capacity claim)."""
    import shutil
    import tempfile

    from keystone_tpu import obs
    from keystone_tpu.data import Dataset
    from keystone_tpu.obs.export import load_events
    from keystone_tpu.ops.learning import cost as cost_mod
    from keystone_tpu.ops.learning.cost import LeastSquaresEstimator
    from keystone_tpu.placement.engine import (
        KIND_ZOO_PAGE_IN,
        PlacementEngine,
    )
    from keystone_tpu.placement.planner import (
        DEFAULT_DRIFT_THRESHOLD,
        CapacityPlanner,
    )
    from keystone_tpu.serving import Autoscaler

    class _Clock:  # injectable monotonic time — determinism, no sleeps
        def __init__(self):
            self.t = 0.0

        def __call__(self):
            return self.t

    class _StormSLO:  # sustained WARN with a non-falling fast burn
        def __init__(self):
            self.state = "OK"
            self.burn = 0.0

        def evaluate(self):
            return {"latency": self.state}

        def burn_rates(self):
            return {"latency": (self.burn, self.burn)}

    class _StormPlane:  # the occupancy signals the controller scales on
        def __init__(self):
            self.replicas = 1
            self.queue_depth = 0.0
            self.outstanding = 0.0
            self.brownout_level = 0
            self.brownout_steps = []
            self.metrics = obs.MetricsRegistry()

        def autoscale_signals(self):
            return {
                "replicas": self.replicas,
                "queue_depth": self.queue_depth,
                "outstanding": self.outstanding,
                "brownout_level": self.brownout_level,
            }

        def add_replica(self):
            self.replicas += 1
            return self.replicas - 1

        def remove_replica(self):
            self.replicas -= 1
            return self.replicas

        def enter_brownout_step(self):
            from keystone_tpu.serving import BROWNOUT_STEPS

            step = BROWNOUT_STEPS[self.brownout_level]
            self.brownout_level += 1
            self.brownout_steps.append(step)
            return step

        def exit_brownout_step(self):
            self.brownout_level -= 1
            return self.brownout_steps.pop()

    td = tempfile.mkdtemp(prefix="bench_placement_plan_")
    try:
        rng = np.random.default_rng(0)
        sample = Dataset.of(
            rng.normal(size=(24, NUM_FEATURES)).astype(np.float32)
        )
        sample.total_n = 262_144
        sample.source_row_bytes = 4.0 * TIMIT_INPUT_DIMS
        labels = Dataset.of(
            rng.normal(size=(24, TIMIT_NUM_CLASSES)).astype(np.float32)
        )
        t_wall = time.perf_counter()
        with obs.tracing(td) as tracer:
            est = LeastSquaresEstimator(
                lam=1e-4, hbm_bytes=48 << 30, num_machines=1
            )
            est.optimize(sample, labels)
            cost_mod.choose_mesh_layout(
                65_000_000, 16_385, 2, nnz_per_row=83, num_devices=8
            )
            eng = PlacementEngine()
            priced = eng.price_page_in(1 << 28)
            ref = eng.audit(
                KIND_ZOO_PAGE_IN, "tenant-a",
                [{"label": "tenant-a", "cost_s": priced,
                  "feasible": True, "resident_bytes": float(1 << 28)}],
                reason="page_fault", context={},
            )
            ref.stamp(priced * 1.05, timing="single_run_cold")

            clock = _Clock()
            slo = _StormSLO()
            plane = _StormPlane()
            scaler = Autoscaler(
                plane, slo, clock=clock, min_replicas=1, max_replicas=4,
                scale_up_sustain_s=1.0, scale_down_sustain_s=60.0,
                cooldown_s=0.5, metrics=plane.metrics,
            )
            slo.state = "WARN"
            for _ in range(12):  # backlog ramps while WARN holds
                slo.burn += 0.5
                plane.queue_depth = min(plane.queue_depth + 1.0, 6.0)
                plane.outstanding = min(plane.outstanding + 1.0, 6.0)
                scaler.tick()
                clock.t += 1.0

            t0 = time.perf_counter()
            for i in range(100):
                dur = 0.010 if i < 98 else 0.035
                start = t0 + i * 0.05
                tracer.add_span("serving.batch", start, start + dur)
        wall_s = time.perf_counter() - t_wall

        planner = CapacityPlanner(load_events(td))
        fidelity = planner.fidelity()
        traffic_1x = planner.whatif_traffic(1.0)
        traffic_2x = planner.whatif_traffic(2.0)
        hbm_half = planner.whatif_hbm(0.5)
        tenants_plus1 = planner.whatif_tenants(1)
        autoscale_stats = scaler.stats()
        err = traffic_1x["abs_log_error_1x"]
    finally:
        shutil.rmtree(td, ignore_errors=True)

    if err is None:
        raise RuntimeError(
            "planner produced no 1x prediction — storm trace incomplete"
        )
    value = round(float(err), 4)
    return make_row(
        "placement_whatif_fidelity", value, "abs_log_error",
        round(DEFAULT_DRIFT_THRESHOLD / max(err, 1e-9), 2),
        "single_run_cold",
        {
            "fidelity": fidelity,
            "whatifs": {
                "traffic_1x": traffic_1x,
                "traffic_2x": traffic_2x,
                "hbm_half": hbm_half,
                "tenants_plus_1": tenants_plus1,
            },
            "autoscaler": autoscale_stats,
            "drift_threshold": DEFAULT_DRIFT_THRESHOLD,
            "storm": {
                "num_batch_spans": 100,
                "service_floor_s": 0.010,
                "storm_tail_s": 0.035,
                "wall_s": round(wall_s, 3),
            },
            "timing_note": (
                "value = |ln(predicted 1x p99 / measured p99)| from the "
                "capacity planner replaying the recorded storm; the "
                "solver/mesh/zoo/autoscale decisions are REAL (live "
                "optimizer, live controller on a fake clock), the "
                "serving.batch latency profile is synthesized at a "
                "declared 10 ms floor / 35 ms tail so the row is "
                "deterministic; vs_baseline = drift_threshold / value "
                "(>1 = the queueing model's prediction sits inside the "
                "calibration plane's error bars); fidelity.num_replayed "
                "recorded argmins all reproduce through the unified "
                "replay or the row is lying — see mismatches"
            ),
        },
    )


def _incumbent_W(ctl):
    """The incumbent plan's LinearMapper weights (the canary-regression
    leg reuses them so the slow candidate is quality-identical)."""
    graph = ctl._incumbent.graph
    for node in graph.nodes:
        op = graph.get_operator(node)
        if hasattr(op, "x"):
            return np.asarray(op.x)
        from keystone_tpu.workflow.fusion import fused_members

        for m in fused_members(op):
            if hasattr(m, "x"):
                return np.asarray(m.x)
    raise RuntimeError("no LinearMapper weights found in the incumbent")


def main():
    from keystone_tpu.utils.startup import enable_compile_cache

    enable_compile_cache()
    headline = timit_streaming_metric()
    if os.environ.get("BENCH_ONLY", "") != "timit":
        extras = []
        for fn in (
            timit_metric,  # the rounds-1..3 resident-feature geometry
            amazon_sparse_metric,
            amazon_fulln_metric,
            multichip_amazon_fulln_metric,
            multichip_timit_scaling_metric,
            amazon_resident_compressed_metric,
            outofcore_prefetch_metric,
            recovery_overhead_metric,
            observability_overhead_metric,
            krr_metric,
            mnist_fft_metric,
            serving_mnist_metric,
            serving_replicated_chaos_metric,
            serving_fleet_chaos_metric,
            serving_model_zoo_isolation_metric,
            continuous_learning_staleness_metric,
            autocache_metric,
            autocache_host_boundary_metric,
            stupidbackoff_metric,
            amazon_sketched_frontier_metric,
            image_conv_featurize_solve_metric,
            placement_whatif_fidelity_metric,
        ):
            try:
                extras.append(fn())
            except Exception as e:  # a broken extra must not kill the headline
                extras.append({"metric": fn.__name__, "error": str(e)[:300]})
        headline["detail"]["additional_metrics"] = extras

    # Full result: committed artifact (the driver's stdout capture keeps only
    # the LAST ~2000 chars, which round 4's single giant line overflowed —
    # the headline number physically missing from BENCH_r04.json).
    full_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "BENCH_FULL_r09.json")
    with open(full_path, "w") as f:
        json.dump(headline, f, indent=1)
    print(json.dumps(headline))

    # Compact headline LAST, so a tail capture always contains
    # metric/value/vs_baseline/MFU without re-running anything.
    compact = {
        "metric": headline["metric"],
        "value": headline["value"],
        "unit": headline["unit"],
        "vs_baseline": headline["vs_baseline"],
        "mfu": headline.get("detail", {}).get("mfu"),
        "achieved_tflops": headline.get("detail", {}).get("achieved_tflops"),
        "full_results": "BENCH_FULL_r09.json",
    }
    print(json.dumps(compact))


if __name__ == "__main__":
    main()
