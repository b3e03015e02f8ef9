"""Regenerate the golden calibration-trace fixture
(``tests/data/calibration_trace/events.jsonl``).

The fixture is one recorded trace combining every evidence class the
calibration plane (``obs/calibrate.py``) must join and score — the
tier-1 tests in ``tests/test_calibrate.py`` pin the join logic,
per-engine error math, regret computation and refit round-trip against
it:

  1. a REAL small disk-streamed fold on this host, preceded by an
     unstamped ``least_squares_solver`` decision — the span-window join
     leg (measured seconds = the fold.segment chunks that followed,
     matched by run_id/timestamps);
  2. a REAL out-of-core ``Pipeline.fit`` routed through the selector —
     the back-annotation leg (the executor stamps the winner's measured
     wall + span id onto the decision record);
  3. ``calibration_sweep`` decisions replaying the RECORDED r05 bench
     device times (the same measured constants ``tests/
     test_cost_replay.py`` is built from: TIMIT-resident block 0.327 s,
     TIMIT full-n streamed 4.107 s, Amazon n=500k gram 1.805 s vs
     gather 7.903 s) — the refit rows, so refitting the fixture lands
     near the shipped TPU family and reproduces the recorded winners;
  4. a deliberately MIS-ROUTED decision: the gather engine recorded as
     winner (measured 7.903 s) while the gram engine's measured
     1.805 s at the SAME geometry sits in the trace — the worked
     regret-table case (regret ≈ 6.098 s, evidence="measured";
     docs/observability.md walks this exact postmortem).

Span durations in legs 1–2 are host-dependent; the tests assert
structure and the seeded constants, never this host's wall times.

Usage: JAX_PLATFORMS=cpu python scripts/make_calibration_fixture.py
"""

import os
import shutil
import sys
import tempfile

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

FIXTURE_DIR = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "tests", "data",
    "calibration_trace",
)

# The r05 recorded device times (BENCH_r05.json / ROADMAP Queue 1 — the
# same constants tests/test_cost_replay.py replays).
TIMIT_RESIDENT = {"n": 262_144, "d": 16_384, "k": 147, "sparsity": 1.0,
                  "machines": 1}
TIMIT_FULLN = {"n": 2_200_000, "d": 16_384, "k": 147, "sparsity": 1.0,
               "machines": 1}
AMAZON = {"n": 500_000, "d": 16_384, "k": 2, "sparsity": 82 / 16_384,
          "machines": 1}
RECORDED = [
    ("BlockLeastSquaresEstimator", TIMIT_RESIDENT, 0.327),
    ("StreamingLeastSquaresChoice", TIMIT_FULLN, 4.107),
    ("SparseLBFGSwithL2[gram]", AMAZON, 1.805),
    ("SparseLBFGSwithL2[gather]", AMAZON, 7.903),
]


def record_sweep_point(label, context, measured_s):
    from keystone_tpu import obs
    from keystone_tpu.obs import calibrate as cal
    from keystone_tpu.ops.learning import cost as cost_mod

    cpu, mem, net = cost_mod.active_weights()
    weights = {"cpu": cpu, "mem": mem, "network": net,
               "family": cost_mod.weights_family_name()}
    predicted = cal.predict_seconds(label, context, {
        "cpu": cpu, "mem": mem, "network": net,
        "sparse_gather_overhead": cost_mod.sparse_gather_overhead(),
    })
    ref = obs.record_cost_decision(obs.CostDecision(
        decision="calibration_sweep",
        winner=label,
        candidates=[{"label": label, "cost_s": predicted,
                     "feasible": True}],
        reason="sweep",
        context={**context, "weights": weights},
    ))
    ref.stamp(measured_s, timing="min_of_N_warm")


def main():
    from keystone_tpu import obs
    from keystone_tpu.data import LabeledData
    from keystone_tpu.data.shards import DiskDenseShards
    from keystone_tpu.obs import calibrate as cal
    from keystone_tpu.ops.learning.cost import LeastSquaresEstimator
    from keystone_tpu.ops.learning.streaming_ls import CosineBankFeaturize
    from keystone_tpu.ops.stats import CosineRandomFeatures
    from keystone_tpu.parallel import streaming
    from keystone_tpu.workflow.env import PipelineEnv

    work = tempfile.mkdtemp(prefix="keystone_cal_fixture_")
    trace_dir = os.path.join(work, "trace")
    rng = np.random.default_rng(0)
    try:
        with obs.tracing(trace_dir, run_id="calfixture0001"):
            # -- leg 1: span-window join — an unstamped decision, then
            # the disk-streamed fold it priced (real spans).
            n1, d_in1, d_feat1, k1 = 2_048, 16, 64, 4
            X = rng.normal(size=(n1, d_in1)).astype(np.float32)
            Y = rng.normal(size=(n1, k1)).astype(np.float32)
            DiskDenseShards.write(
                os.path.join(work, "sh1"), X, Y, tile_rows=256,
                tiles_per_segment=1,
            )
            source = DiskDenseShards(os.path.join(work, "sh1")).as_source()
            fold_ctx = {"n": n1, "d": d_feat1, "k": k1, "sparsity": 1.0,
                        "machines": 1}
            obs.record_cost_decision(obs.CostDecision(
                decision="least_squares_solver",
                winner="StreamingLeastSquaresChoice",
                candidates=[
                    {"label": "DenseLBFGSwithL2", "cost_s": None,
                     "feasible": False},
                    {"label": "StreamingLeastSquaresChoice",
                     "cost_s": cal.predict_seconds(
                         "StreamingLeastSquaresChoice", fold_ctx,
                         cal.family_weights("tpu")),
                     "feasible": True},
                ],
                reason="argmin",
                context={**fold_ctx, "weights": {
                    **{k: v for k, v in cal.family_weights("tpu").items()
                       if k in ("cpu", "mem", "network")},
                    "family": "tpu"}},
            ))
            rng2 = np.random.default_rng(1)
            bank = CosineBankFeaturize(
                rng2.normal(size=(d_feat1, d_in1)).astype(np.float32) * 0.3,
                rng2.uniform(0, 6, d_feat1).astype(np.float32),
            )
            streaming.streaming_bcd_fit_segments(
                source, bank=bank, d_feat=d_feat1, block_size=32,
                lam=1e-3, num_iter=1, center=False, prefetch_depth=2,
            )

            # -- leg 2: the back-annotation path — a real out-of-core
            # Pipeline.fit whose executor stamps the decision.
            PipelineEnv.get_or_create().reset()
            sld = LabeledData(X, Y).to_disk_shards(
                os.path.join(work, "sh2"), shard_rows=256,
                tiles_per_segment=1,
            )
            crf = CosineRandomFeatures(d_in1, d_feat1, 0.2, seed=1)
            os.environ["KEYSTONE_HOST_BUDGET_BYTES"] = str(64 << 10)
            try:
                auto = LeastSquaresEstimator(lam=0.1)
                p = crf.to_pipeline().and_then(
                    auto, sld.data, sld.labels
                )
                p.fit()
            finally:
                del os.environ["KEYSTONE_HOST_BUDGET_BYTES"]

            # -- leg 3: the recorded r05 sweep rows (the refit corpus).
            for label, ctx, measured in RECORDED:
                record_sweep_point(label, ctx, measured)

            # -- leg 4: the worked mis-route — gather recorded as the
            # winner at the Amazon geometry where gram measured 4.4x
            # faster in leg 3 (a deliberately wrong weight family made
            # the call; the calibrator must flag it with the regret).
            ref = obs.record_cost_decision(obs.CostDecision(
                decision="least_squares_solver",
                winner="SparseLBFGSwithL2[gather]",
                candidates=[
                    {"label": "SparseLBFGSwithL2[gather]",
                     "cost_s": 1.2, "feasible": True},
                    {"label": "SparseLBFGSwithL2[gram]",
                     "cost_s": 3.4, "feasible": True},
                ],
                reason="argmin",
                context={**AMAZON, "weights": {
                    "cpu": 1e-12, "mem": 1e-13, "network": 1e-11,
                    "family": "custom"}},
            ))
            ref.stamp(7.903)

        os.makedirs(FIXTURE_DIR, exist_ok=True)
        for name in ("events.jsonl", "meta.json"):
            shutil.copy(
                os.path.join(trace_dir, name),
                os.path.join(FIXTURE_DIR, name),
            )
        events = obs.load_events(FIXTURE_DIR)
        outcomes = cal.join_decisions(events)
        print(f"fixture written: {FIXTURE_DIR}")
        print(f"  {len(events)} records, {len(outcomes)} decisions")
        for o in outcomes:
            print(f"  {o.decision:<22} {o.winner:<36} "
                  f"via={o.joined_via} measured={o.measured_s}")
    finally:
        shutil.rmtree(work, ignore_errors=True)


if __name__ == "__main__":
    main()
