"""Cost-model calibration plane (ISSUE 13): the golden-trace fixture
(``tests/data/calibration_trace`` — recorded spans + decisions from a
small disk-streamed fold plus the r05 measured sweep rows, regenerated
by ``scripts/make_calibration_fixture.py``) pins the decision↔span join
logic, per-engine error math, regret computation and the refit
round-trip; live tests pin the executor's measured-outcome
back-annotation, the ``calibrated:<path>`` weight family, the drift
gate, and the ``bin/calibrate`` CLI."""

import json
import math
import os

import numpy as np
import pytest

import jax.numpy as jnp

from keystone_tpu import obs
from keystone_tpu.data import Dataset
from keystone_tpu.obs import calibrate as cal
from keystone_tpu.obs import flight
from keystone_tpu.obs import tracer as tracer_mod
from keystone_tpu.obs.metrics import MetricsRegistry
from keystone_tpu.ops.learning import cost as cost_mod
from keystone_tpu.ops.learning.cost import (
    LeastSquaresEstimator,
    candidate_label,
)

FIXTURE = os.path.join(
    os.path.dirname(__file__), "data", "calibration_trace"
)

# The r05 recorded constants the fixture's sweep rows replay (the same
# measured device times tests/test_cost_replay.py is built from).
BLOCK_MEASURED = 0.327
STREAM_MEASURED = 4.107
GRAM_MEASURED = 1.805
GATHER_MEASURED = 7.903


@pytest.fixture(autouse=True)
def _no_leaked_tracer():
    yield
    tracer_mod._ACTIVE = None


@pytest.fixture(scope="module")
def events():
    return obs.load_events(FIXTURE)


@pytest.fixture(scope="module")
def outcomes(events):
    return cal.join_decisions(events)


def _by_winner(outcomes, winner, decision=None):
    return [
        o for o in outcomes
        if o.winner == winner
        and (decision is None or o.decision == decision)
    ]


class TestJoin:
    def test_fixture_joins_every_evidence_class(self, outcomes):
        assert len(outcomes) == 7
        via = sorted(o.joined_via for o in outcomes)
        # 6 back-annotated outcomes + 1 span-window join; nothing
        # unjoined.
        assert via == ["outcome"] * 6 + ["spans"]
        assert all(o.measured_s is not None for o in outcomes)

    def test_recorded_sweep_values_joined_exactly(self, outcomes):
        sweeps = {
            o.winner: o for o in outcomes
            if o.decision == "calibration_sweep"
        }
        assert sweeps["BlockLeastSquaresEstimator"].measured_s == (
            BLOCK_MEASURED
        )
        assert sweeps["StreamingLeastSquaresChoice"].measured_s == (
            STREAM_MEASURED
        )
        assert sweeps["SparseLBFGSwithL2[gram]"].measured_s == (
            GRAM_MEASURED
        )
        assert sweeps["SparseLBFGSwithL2[gather]"].measured_s == (
            GATHER_MEASURED
        )
        # Every sweep row carries its weight-family provenance.
        assert all(
            o.weights.get("family") == "tpu" for o in sweeps.values()
        )

    def test_span_window_join_sums_fold_chunks(self, events, outcomes):
        """The unstamped decision's measured seconds are the fold.segment
        chunks between it and the next decision, matched by run_id and
        timestamps — recomputed here independently of join_decisions."""
        joined = [o for o in outcomes if o.joined_via == "spans"]
        assert len(joined) == 1
        o = joined[0]
        decisions = sorted(
            (e for e in events
             if e.get("type") == "event" and e["name"] == "cost.decision"),
            key=lambda e: e["ts_us"],
        )
        t0 = decisions[0]["ts_us"]
        t1 = decisions[1]["ts_us"]
        expected = sum(
            s["dur_us"] for s in events
            if s.get("type") == "span" and s["name"] == "fold.segment"
            and t0 <= s["ts_us"] < t1
        ) / 1e6
        assert expected > 0
        assert o.measured_s == pytest.approx(expected, abs=1e-9)
        # The window's span families are counted for provenance.
        assert o.span_counts.get("fold.segment", 0) > 0
        assert o.span_counts.get("prefetch.read", 0) > 0
        assert o.span_counts.get("runtime.task", 0) > 0

    def test_back_annotated_decision_links_its_fit_span(
        self, events, outcomes
    ):
        """The executor-stamped decision carries the estimator.fit span
        id, and that span exists in the trace."""
        stamped = [
            o for o in outcomes
            if o.decision == "least_squares_solver"
            and o.joined_via == "outcome"
            and o.winner == "StreamingLeastSquaresChoice"
        ]
        assert len(stamped) == 1
        o = stamped[0]
        assert o.span_id is not None
        fit_spans = [
            s for s in events
            if s.get("type") == "span" and s["name"] == "estimator.fit"
            and s["span_id"] == o.span_id
        ]
        assert len(fit_spans) == 1
        # The stamped wall covers at least the span's own duration
        # (span closes inside the timed region).
        assert o.measured_s >= fit_spans[0]["dur_us"] / 1e6 - 1e-3


class TestErrorMath:
    def test_log_error_definition(self):
        o = cal.DecisionOutcome(
            run_id="r", decision="d", winner="w", reason="argmin",
            predicted_s=2.0, measured_s=4.0,
        )
        assert o.log_error() == pytest.approx(math.log(2.0))
        assert o.log_error(predicted=8.0) == pytest.approx(-math.log(2.0))
        assert cal.DecisionOutcome(
            run_id="r", decision="d", winner="w", reason="argmin",
            predicted_s=None, measured_s=4.0,
        ).log_error() is None

    def test_per_engine_medians_match_hand_math(self, outcomes):
        sweep = [o for o in outcomes if o.decision == "calibration_sweep"]
        report = cal.calibration_report(
            sweep, kinds=("calibration_sweep",)
        )
        assert report["num_decisions"] == 4
        assert report["num_scored"] == 4
        for o in sweep:
            eng = report["per_engine"][o.winner]
            expected = math.log(o.measured_s / o.predicted_s)
            assert eng["count"] == 1
            assert eng["median_log_error"] == pytest.approx(expected)
            assert eng["median_abs_log_error"] == pytest.approx(
                abs(expected)
            )
            assert eng["median_measured_s"] == o.measured_s
        all_errs = sorted(
            abs(math.log(o.measured_s / o.predicted_s)) for o in sweep
        )
        assert report["median_abs_log_error"] == pytest.approx(
            (all_errs[1] + all_errs[2]) / 2
        )

    def test_reprediction_under_recorded_family_matches(self, outcomes):
        """Re-predicting under the tpu family reproduces the recorded
        predictions for the sweep rows (they were recorded under tpu) —
        the label→estimator reconstruction is faithful."""
        sweep = [o for o in outcomes if o.decision == "calibration_sweep"]
        tpu = cal.family_weights("tpu")
        for o in sweep:
            repredicted = cal.predict_seconds(o.winner, o.context, tpu)
            assert repredicted == pytest.approx(o.predicted_s, rel=1e-9)

    def test_timing_mix_surfaced(self, outcomes):
        """Every outcome carries its measurement convention, and the
        report states the mix — a DRIFT verdict over compile-inclusive
        cold walls must be distinguishable from a warm-row constants
        regression."""
        by_timing = {}
        for o in outcomes:
            by_timing.setdefault(o.timing, []).append(o)
        # The sweep rows are warm device time; the executor's
        # production stamp is a cold single fit; the window-joined
        # decision reads "spans".
        assert len(by_timing.get("min_of_N_warm", [])) == 4
        assert len(by_timing.get("spans", [])) == 1
        cold_or_unlabeled = (
            len(by_timing.get("single_run_cold", []))
            + len(by_timing.get(None, []))
        )
        assert cold_or_unlabeled == 2
        report = cal.calibration_report(list(outcomes))
        assert report["timings"]["min_of_N_warm"] == 4
        verdict = cal.drift_gate(report)
        assert verdict["timings"] == report["timings"]

    def test_registry_metrics_published(self, outcomes):
        reg = MetricsRegistry()
        cal.calibration_report(list(outcomes), registry=reg)
        snap = reg.snapshot()
        assert snap["calibration.decisions"] == 7
        assert snap["calibration.misroutes"] == 1
        assert snap["calibration.regret_s"] == pytest.approx(
            GATHER_MEASURED - GRAM_MEASURED, abs=1e-6
        )
        gather_err = snap[
            "calibration.error{engine=SparseLBFGSwithL2[gather]}.count"
        ]
        assert gather_err >= 1


class TestMisroute:
    def test_worked_misroute_measured_evidence(self, outcomes):
        """The fixture's deliberately mis-routed decision: gather won
        (measured 7.903 s) while gram measured 1.805 s at the SAME
        geometry — flagged with the regret, on measured evidence."""
        report = cal.calibration_report(list(outcomes))
        assert len(report["misroutes"]) == 1
        m = report["misroutes"][0]
        assert m["winner"] == "SparseLBFGSwithL2[gather]"
        assert m["faster_candidate"] == "SparseLBFGSwithL2[gram]"
        assert m["evidence"] == "measured"
        assert m["winner_measured_s"] == GATHER_MEASURED
        assert m["faster_estimate_s"] == GRAM_MEASURED
        assert m["regret_s"] == pytest.approx(
            GATHER_MEASURED - GRAM_MEASURED, abs=1e-6
        )
        assert report["total_regret_s"] == pytest.approx(
            m["regret_s"], abs=1e-6
        )

    def _decision(self, winner, candidates, ctx, measured, run="r1",
                  ts=0):
        return {
            "type": "event", "name": "cost.decision", "run_id": run,
            "ts_us": ts, "args": {
                "decision": "least_squares_solver", "winner": winner,
                "reason": "argmin", "candidates": candidates,
                "outcome": {"measured_s": measured}, **ctx,
            },
        }

    def test_no_claim_without_evidence(self):
        """A feasible loser whose engine was never measured anywhere in
        the trace set makes NO mis-route claim — the table must not be
        built from the very predictions under audit."""
        ctx = {"n": 1000, "d": 64, "k": 2, "sparsity": 1.0,
               "machines": 1}
        recs = [self._decision(
            "DenseLBFGSwithL2",
            [{"label": "DenseLBFGSwithL2", "cost_s": 0.5,
              "feasible": True},
             {"label": "BlockLeastSquaresEstimator", "cost_s": 0.001,
              "feasible": True}],
            ctx, measured=10.0,
        )]
        report = cal.calibration_report(recs)
        assert report["misroutes"] == []

    def test_calibrated_evidence_regret(self):
        """The calibrated-estimate evidence path: the loser's prediction
        is corrected by its engine's own measured error ratio before any
        claim is made."""
        ctx_a = {"n": 1000, "d": 64, "k": 2, "sparsity": 1.0,
                 "machines": 1}
        ctx_b = {"n": 2000, "d": 64, "k": 2, "sparsity": 1.0,
                 "machines": 1}
        # Block measured at ctx_a: ratio = measured/predicted = 4x.
        recs = [
            self._decision(
                "BlockLeastSquaresEstimator",
                [{"label": "BlockLeastSquaresEstimator", "cost_s": 0.5,
                  "feasible": True}],
                ctx_a, measured=2.0, ts=0,
            ),
            # At ctx_b the dense engine won, measured 10 s; block
            # predicted 1.0 s there -> calibrated estimate 4.0 s.
            self._decision(
                "DenseLBFGSwithL2",
                [{"label": "DenseLBFGSwithL2", "cost_s": 9.0,
                  "feasible": True},
                 {"label": "BlockLeastSquaresEstimator", "cost_s": 1.0,
                  "feasible": True}],
                ctx_b, measured=10.0, ts=10,
            ),
        ]
        report = cal.calibration_report(recs)
        assert len(report["misroutes"]) == 1
        m = report["misroutes"][0]
        assert m["evidence"] == "calibrated"
        assert m["faster_estimate_s"] == pytest.approx(4.0)
        assert m["regret_s"] == pytest.approx(6.0)

    def test_infeasible_candidates_never_claim(self):
        ctx = {"n": 1000, "d": 64, "k": 2, "sparsity": 1.0,
               "machines": 1}
        recs = [
            self._decision(
                "BlockLeastSquaresEstimator",
                [{"label": "BlockLeastSquaresEstimator", "cost_s": 0.5,
                  "feasible": True}],
                ctx, measured=2.0, ts=0,
            ),
            self._decision(
                "DenseLBFGSwithL2",
                [{"label": "DenseLBFGSwithL2", "cost_s": 9.0,
                  "feasible": True},
                 {"label": "BlockLeastSquaresEstimator", "cost_s": 1.0,
                  "feasible": False}],
                ctx, measured=10.0, ts=10,
            ),
        ]
        report = cal.calibration_report(recs)
        # Same-geometry measured evidence exists for block, but the
        # candidate was infeasible at the decision — no claim.
        assert report["misroutes"] == []


class TestRefitRoundTrip:
    @pytest.fixture(scope="class")
    def refit_result(self, events, tmp_path_factory):
        out = str(tmp_path_factory.mktemp("cal") / "calibration.json")
        return cal.refit(
            events, out_path=out, kinds=("calibration_sweep",)
        )

    def test_refit_improves_on_perturbed_family(self, events,
                                                refit_result):
        """The acceptance criterion: a deliberately perturbed family is
        flagged by the drift gate, and the refit weights reduce the
        median |log error| vs the perturbed weights on the recorded
        geometries."""
        perturbed = dict(cal.family_weights("tpu"))
        perturbed["cpu"] *= 25.0
        perturbed["mem"] *= 25.0
        perturbed["name"] = "perturbed"
        rep_pert = cal.calibration_report(
            events, weights=perturbed, kinds=("calibration_sweep",)
        )
        verdict = cal.drift_gate(rep_pert)
        assert verdict["drifted"], rep_pert["median_abs_log_error"]
        after = refit_result["after"]["median_abs_log_error"]
        assert after < rep_pert["median_abs_log_error"]
        assert after <= refit_result["before"]["median_abs_log_error"]
        # The refit lands in a sane band of the shipped TPU constants
        # (the sweep rows ARE the rows those constants came from).
        w = refit_result["weights"]
        assert 0.3 < w["cpu"] / cost_mod.TPU_CPU_WEIGHT < 3.0
        assert 0.3 < w["mem"] / cost_mod.TPU_MEM_WEIGHT < 3.0
        assert 0.3 < (
            w["sparse_gather_overhead"]
            / cost_mod.TPU_SPARSE_GATHER_OVERHEAD
        ) < 3.0
        assert w["network"] == cost_mod.TPU_NETWORK_WEIGHT  # pinned

    def test_artifact_provenance(self, refit_result):
        path = refit_result["artifact_path"]
        doc = cal.load_calibration_artifact(path)
        assert doc["format"] == cal.ARTIFACT_FORMAT
        assert doc["version"] == cal.ARTIFACT_VERSION
        prov = doc["provenance"]
        assert prov["run_ids"] == ["calfixture0001"]
        assert prov["num_decisions"] == 4
        assert prov["num_measured"] == 4
        assert "fit_date" in prov and "fit_unix_s" in prov
        assert "median_abs_log_error" in prov["residuals"]
        assert set(prov["fitted"]) == {
            "cpu", "mem", "sparse_gather_overhead"
        }

    def test_calibrated_family_reproduces_recorded_winners(
        self, refit_result, monkeypatch
    ):
        """Loading the refit artifact reproduces the recorded winners at
        the test_cost_replay.py geometries: the streamed tier past HBM
        (feasibility), the gram engine over gather at the Amazon
        geometry, and the measured orderings at TIMIT-resident (block
        under streamed and under 20-iteration LBFGS)."""
        monkeypatch.setenv(
            "KEYSTONE_COST_WEIGHTS",
            f"calibrated:{refit_result['artifact_path']}",
        )
        w = refit_result["weights"]
        assert cost_mod.active_weights() == (
            w["cpu"], w["mem"], w["network"]
        )
        assert cost_mod.weights_family_name() == "calibrated"

        rng = np.random.default_rng(0)

        def dense_sample(n_total, d, k):
            s = Dataset.of(rng.normal(size=(24, d)).astype(np.float32))
            s.total_n = n_total
            s.source_row_bytes = 4.0 * 440
            ls = Dataset.of(
                rng.normal(size=(24, k)).astype(np.float32)
            )
            return s, ls

        # TIMIT full-n: the streamed tier is the only feasible fit.
        from keystone_tpu.ops.learning.streaming_ls import (
            StreamingLeastSquaresChoice,
        )

        est = LeastSquaresEstimator(
            lam=1e-4, hbm_bytes=16 << 30, num_machines=1
        )
        s, ls = dense_sample(2_200_000, 16_384, 147)
        assert isinstance(
            est.optimize(s, ls), StreamingLeastSquaresChoice
        )

        # Amazon sparse: gram over gather, as measured.
        from keystone_tpu.ops.learning.lbfgs import SparseLBFGSwithL2

        idx = rng.integers(0, 16_384, size=(24, 82)).astype(np.int32)
        idx[0, 0] = 16_383
        sp = Dataset(
            {"indices": jnp.asarray(idx),
             "values": jnp.asarray(
                 rng.normal(size=(24, 82)).astype(np.float32))},
            n=24,
        )
        sp.total_n = 500_000
        sp.source_row_bytes = 82 * 4.0
        lsp = Dataset.of(rng.normal(size=(24, 2)).astype(np.float32))
        est2 = LeastSquaresEstimator(
            lam=1e-3, hbm_bytes=16 << 30, num_machines=1
        )
        inner = est2.optimize(sp, lsp).estimator
        assert isinstance(inner, SparseLBFGSwithL2)
        assert inner.solver == "gram"

        # TIMIT-resident measured orderings: the r05 record measured
        # block (0.327 s) against the streamed rate and bounds LBFGS
        # from below — both orderings must survive the refit.
        est3 = LeastSquaresEstimator(
            lam=1e-4, hbm_bytes=48 << 30, num_machines=1
        )
        by_label = {candidate_label(o[0]): o[0] for o in est3.options}
        n, d, k = 262_144, 16_384, 147

        def cost_of(opt):
            return opt.cost(
                n, d, k, 1.0, 1,
                est3.cpu_weight, est3.mem_weight, est3.network_weight,
            )

        c_block = cost_of(by_label["BlockLeastSquaresEstimator"])
        c_stream = cost_of(by_label["StreamingLeastSquaresChoice"])
        c_lbfgs = cost_of(by_label["DenseLBFGSwithL2"])
        assert c_block < c_stream, (c_block, c_stream)
        assert c_block < c_lbfgs, (c_block, c_lbfgs)


class TestArtifact:
    def _weights(self, **over):
        w = {"cpu": 1e-14, "mem": 1e-11, "network": 1e-11,
             "sparse_gather_overhead": 400.0,
             "fitted": ["cpu"], "num_rows": {}}
        w.update(over)
        return w

    def test_round_trip(self, tmp_path):
        path = str(tmp_path / "a.json")
        cal.write_calibration_artifact(
            path, self._weights(), {"run_ids": ["r"]}
        )
        doc = cal.load_calibration_artifact(path)
        assert doc["weights"]["cpu"] == 1e-14
        assert doc["provenance"]["run_ids"] == ["r"]

    def test_malformed_artifacts_raise_naming_path(self, tmp_path):
        p = tmp_path / "bad.json"
        cases = [
            "not json at all",
            json.dumps({"format": "something-else", "version": 1}),
            json.dumps({"format": cal.ARTIFACT_FORMAT, "version": 99,
                        "weights": {}}),
            json.dumps({"format": cal.ARTIFACT_FORMAT, "version": 1}),
            json.dumps({"format": cal.ARTIFACT_FORMAT, "version": 1,
                        "weights": {"cpu": -1, "mem": 1, "network": 1}}),
            json.dumps({"format": cal.ARTIFACT_FORMAT, "version": 1,
                        "weights": {"cpu": 1, "mem": 1, "network": 1,
                                    "sparse_gather_overhead": "x"}}),
        ]
        for content in cases:
            p.write_text(content)
            with pytest.raises(ValueError) as ei:
                cal.load_calibration_artifact(str(p))
            assert "bad.json" in str(ei.value)

    def test_env_with_missing_artifact_raises_naming_variable(
        self, monkeypatch, tmp_path
    ):
        monkeypatch.setenv(
            "KEYSTONE_COST_WEIGHTS",
            f"calibrated:{tmp_path}/nope.json",
        )
        with pytest.raises(ValueError) as ei:
            cost_mod.active_weights()
        assert "KEYSTONE_COST_WEIGHTS" in str(ei.value)

    def test_refreshed_artifact_is_picked_up(self, monkeypatch,
                                             tmp_path):
        """The loader caches by mtime: a refit-in-place artifact must be
        re-read, not served stale."""
        path = str(tmp_path / "w.json")
        cal.write_calibration_artifact(
            path, self._weights(cpu=1e-14), {}
        )
        monkeypatch.setenv("KEYSTONE_COST_WEIGHTS", f"calibrated:{path}")
        assert cost_mod.active_weights()[0] == 1e-14
        cal.write_calibration_artifact(
            path, self._weights(cpu=2e-14), {}
        )
        os.utime(path, ns=(1, 1))  # force a distinct mtime
        assert cost_mod.active_weights()[0] == 2e-14

    def test_null_gather_overhead_falls_back_to_tpu(self, monkeypatch,
                                                    tmp_path):
        path = str(tmp_path / "w.json")
        cal.write_calibration_artifact(
            path, self._weights(sparse_gather_overhead=None), {}
        )
        monkeypatch.setenv("KEYSTONE_COST_WEIGHTS", f"calibrated:{path}")
        assert cost_mod.sparse_gather_overhead() == (
            cost_mod.TPU_SPARSE_GATHER_OVERHEAD
        )

    def test_unknown_family_raises_naming_variable(self, monkeypatch):
        """A typo'd family must not silently select the TPU default —
        the exact silent mis-pricing this plane exists to catch."""
        for bad in ("calibratd:/x.json", "gpu", "tpu2"):
            monkeypatch.setenv("KEYSTONE_COST_WEIGHTS", bad)
            with pytest.raises(ValueError) as ei:
                cost_mod.active_weights()
            assert "KEYSTONE_COST_WEIGHTS" in str(ei.value)
        monkeypatch.setenv("KEYSTONE_COST_WEIGHTS", "tpu")
        assert cost_mod.active_weights() == (
            cost_mod.TPU_CPU_WEIGHT, cost_mod.TPU_MEM_WEIGHT,
            cost_mod.TPU_NETWORK_WEIGHT,
        )

    def test_calibrated_prefix_case_insensitive(self, monkeypatch,
                                                tmp_path):
        """The family part matches case-insensitively (like 'ec2'/'EC2')
        while the artifact path keeps its case — cost.py and
        cal.family_weights agree on the same spec."""
        path = str(tmp_path / "Case.json")
        cal.write_calibration_artifact(path, self._weights(cpu=5e-15), {})
        monkeypatch.setenv("KEYSTONE_COST_WEIGHTS", f"Calibrated:{path}")
        assert cost_mod.active_weights()[0] == 5e-15
        assert cost_mod.weights_family_name() == "calibrated"

    def test_family_names(self, monkeypatch, tmp_path):
        monkeypatch.delenv("KEYSTONE_COST_WEIGHTS", raising=False)
        assert cost_mod.weights_family_name() == "tpu"
        monkeypatch.setenv("KEYSTONE_COST_WEIGHTS", "ec2")
        assert cost_mod.weights_family_name() == "ec2"
        path = str(tmp_path / "w.json")
        cal.write_calibration_artifact(path, self._weights(), {})
        monkeypatch.setenv("KEYSTONE_COST_WEIGHTS", f"calibrated:{path}")
        assert cost_mod.weights_family_name() == "calibrated"
        w = cal.family_weights(f"calibrated:{path}")
        assert w["name"] == "calibrated" and w["cpu"] == 1e-14


class TestOutcomeStamping:
    def _problem(self, n=512, d=32, k=3):
        rng = np.random.default_rng(7)
        X = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
        Y = jnp.asarray(rng.normal(size=(n, k)).astype(np.float32))
        s = Dataset.of(X[:24])
        s.total_n = n
        return (Dataset.of(X), Dataset.of(Y), s, Dataset.of(Y[:24]))

    def test_executor_stamps_measured_outcome(self):
        data, labels, s, ls = self._problem()
        est = LeastSquaresEstimator(
            lam=1e-3, hbm_bytes=48 << 30, num_machines=1
        )
        with obs.tracing() as t:
            chosen = est.optimize(s, ls)
            chosen.fit_datasets([data, labels])
        decisions = [
            e for e in t.events
            if e.get("type") == "event" and e["name"] == "cost.decision"
        ]
        assert len(decisions) == 1
        outcome = decisions[0]["args"].get("outcome")
        assert outcome is not None
        assert outcome["measured_s"] > 0
        fit_spans = t.spans("estimator.fit")
        assert len(fit_spans) == 1
        assert outcome["span_id"] == fit_spans[0]["span_id"]
        # The joined view agrees.
        (o,) = cal.join_decisions(t.events)
        assert o.joined_via == "outcome"
        assert o.measured_s == outcome["measured_s"]

    def test_ref_consumed_once(self):
        data, labels, s, ls = self._problem()
        est = LeastSquaresEstimator(
            lam=1e-3, hbm_bytes=48 << 30, num_machines=1
        )
        with obs.tracing() as t:
            chosen = est.optimize(s, ls)
            chosen.fit_datasets([data, labels])
            chosen.fit_datasets([data, labels])  # re-fit: no new stamp
        # every traced fit has its ``estimator.fit`` span (PR 26); the
        # decision stays stamped with the first one's
        first, second = sorted(t.spans("estimator.fit"), key=lambda s: s["ts_us"])
        (decision,) = [e for e in t.events if e["name"] == "cost.decision"]
        assert decision["args"]["outcome"]["span_id"] == first["span_id"]
        assert decision["args"]["outcome"]["measured_s"] < (
            second["ts_us"] - first["ts_us"]) / 1e6 + 1e-3
        assert getattr(chosen, "_pending_cost_outcome", None) is None

    def test_no_tracer_no_stamp(self):
        data, labels, s, ls = self._problem()
        est = LeastSquaresEstimator(
            lam=1e-3, hbm_bytes=48 << 30, num_machines=1
        )
        chosen = est.optimize(s, ls)
        assert getattr(chosen, "_pending_cost_outcome", None) is None
        fitted = chosen.fit_datasets([data, labels])
        assert fitted is not None

    def test_pickled_ref_drops_annotation(self):
        import cloudpickle

        data, labels, s, ls = self._problem()
        est = LeastSquaresEstimator(
            lam=1e-3, hbm_bytes=48 << 30, num_machines=1
        )
        with obs.tracing():
            chosen = est.optimize(s, ls)
            ref = chosen._pending_cost_outcome
            assert ref is not None
            revived = cloudpickle.loads(cloudpickle.dumps(ref))
        revived.stamp(1.0)  # must be a no-op, not a crash

    def test_fused_streamed_fit_inherits_ref(self):
        """The StreamedFitFusionRule path: when the streaming choice
        wins and is fused with its upstream featurizer, the pending
        back-annotation follows the fused estimator — the decision
        record still gets its measured outcome."""
        from keystone_tpu.ops.learning.streaming_ls import (
            StreamingLeastSquaresChoice,
        )

        choice = StreamingLeastSquaresChoice(num_iter=1, lam=1e-3)

        class _Ref:
            def __init__(self):
                self.stamped = None

            def stamp(self, measured_s, span_id=None, **extra):
                self.stamped = measured_s

        ref = _Ref()
        choice._pending_cost_outcome = ref
        fused = choice.fuse_with_members([])
        assert fused._pending_cost_outcome is ref
        assert choice._pending_cost_outcome is None


class TestDriftGate:
    def test_perturbed_family_flagged_with_flight_note(self, events):
        flight.default_flight_recorder().clear()
        perturbed = dict(cal.family_weights("tpu"))
        perturbed["cpu"] *= 25.0
        perturbed["mem"] *= 25.0
        perturbed["name"] = "perturbed"
        reg = MetricsRegistry()
        report = cal.calibration_report(
            events, weights=perturbed, kinds=("calibration_sweep",)
        )
        verdict = cal.drift_gate(report, registry=reg)
        assert verdict["drifted"]
        assert verdict["median_abs_log_error"] > (
            cal.DEFAULT_DRIFT_THRESHOLD
        )
        assert reg.snapshot()["calibration.drift"] == 1.0
        notes = [
            n for n in flight.flight_snapshot()
            if n["name"] == "calibration.drift" and n["kind"] == "warn"
        ]
        assert notes, "drift must leave a WARN flight note"
        assert notes[-1]["attrs"]["weights_family"] == "perturbed"

    def test_shipped_family_passes_on_its_own_rows(self, events):
        reg = MetricsRegistry()
        report = cal.calibration_report(
            events, weights=cal.family_weights("tpu"),
            kinds=("calibration_sweep",),
        )
        verdict = cal.drift_gate(report, registry=reg)
        assert not verdict["drifted"]
        assert reg.snapshot()["calibration.drift"] == 0.0

    def test_no_data_verdict(self):
        report = cal.calibration_report([])
        verdict = cal.drift_gate(report)
        assert not verdict["drifted"]
        assert verdict["median_abs_log_error"] is None
        assert verdict["num_scored"] == 0


class TestCalibrateCLI:
    def test_cli_renders_report_and_exits_clean(self, capsys):
        from keystone_tpu.tools.calibrate import main

        rc = main([FIXTURE])
        out = capsys.readouterr().out
        assert rc == 0
        assert "per-engine predicted vs measured" in out
        assert "mis-routes (1 total" in out
        assert "drift verdict: OK" in out
        assert "SparseLBFGSwithL2[gather]" in out

    def test_cli_flags_perturbed_weights_as_drift(self, tmp_path,
                                                  capsys):
        from keystone_tpu.tools.calibrate import main

        perturbed = dict(cal.family_weights("tpu"))
        perturbed["cpu"] *= 25.0
        perturbed["mem"] *= 25.0
        path = str(tmp_path / "perturbed.json")
        cal.write_calibration_artifact(
            path, perturbed, {"note": "test-seeded perturbation"}
        )
        rc = main([FIXTURE, "--weights", f"calibrated:{path}"])
        out = capsys.readouterr().out
        assert rc == 2
        assert "drift verdict: DRIFT" in out

    def test_cli_refit_writes_artifact(self, tmp_path, capsys):
        from keystone_tpu.tools.calibrate import main

        out_path = str(tmp_path / "refit.json")
        rc = main([FIXTURE, "--refit", out_path])
        out = capsys.readouterr().out
        assert rc == 0
        assert os.path.exists(out_path)
        assert "trace-driven refit" in out
        assert "KEYSTONE_COST_WEIGHTS=calibrated:" in out
        cal.load_calibration_artifact(out_path)  # validates

    def test_cli_json_form(self, capsys):
        from keystone_tpu.tools.calibrate import main

        rc = main([FIXTURE, "--json"])
        doc = json.loads(capsys.readouterr().out)
        assert rc == 0
        assert doc["report"]["num_decisions"] == 7
        assert doc["verdict"]["drifted"] is False

    def test_cli_errors_on_missing_dir(self, tmp_path, capsys):
        from keystone_tpu.tools.calibrate import main

        rc = main([str(tmp_path / "nope")])
        assert rc == 1
        assert "cannot read" in capsys.readouterr().err

    def test_cli_no_data_fails_closed(self, tmp_path, capsys):
        """A trace with events but no joinable decision exits 3 — a
        scripted calibration gate with zero evidence must not pass
        vacuously (e.g. tracing misconfigured)."""
        from keystone_tpu.tools.calibrate import main

        d = tmp_path / "tr"
        d.mkdir()
        (d / "events.jsonl").write_text(json.dumps({
            "type": "span", "name": "fold.segment", "run_id": "r",
            "ts_us": 1, "dur_us": 5, "span_id": 1, "parent_id": None,
            "tid": 1, "thread": "t", "args": {},
        }) + "\n")
        rc = main([str(d)])
        out = capsys.readouterr().out
        assert rc == 3
        assert "NO-DATA" in out
        # --refit on the same zero-evidence trace refuses to write an
        # artifact (it would just re-package the base family).
        art = str(tmp_path / "cal.json")
        rc = main([str(d), "--refit", art])
        captured = capsys.readouterr()
        assert rc == 3
        assert "refusing --refit" in captured.err
        assert not os.path.exists(art)

    def test_cli_corrupt_events_named_diagnostic(self, tmp_path,
                                                 capsys):
        """A truncated events.jsonl (run killed mid-write) exits 1 with
        the named diagnostic, not a raw JSONDecodeError traceback."""
        from keystone_tpu.tools.calibrate import main

        d = tmp_path / "tr"
        d.mkdir()
        (d / "events.jsonl").write_text('{"type": "event", "na')
        rc = main([str(d)])
        assert rc == 1
        assert "cannot read" in capsys.readouterr().err

    def test_bin_calibrate_wraps_the_module(self):
        path = os.path.join(
            os.path.dirname(__file__), "..", "bin", "calibrate"
        )
        assert os.path.exists(path)
        assert os.access(path, os.X_OK)
        with open(path) as f:
            assert "keystone_tpu.tools.calibrate" in f.read()

    def test_trace_cli_prints_predicted_vs_measured(self, capsys):
        from keystone_tpu.tools.trace import main

        rc = main([FIXTURE])
        out = capsys.readouterr().out
        assert rc == 0
        assert "predicted=" in out and "measured=" in out
        assert "log_err=" in out


class TestSketchedFamilyRefit:
    """ISSUE 17 satellite: ``bin/calibrate --refit`` re-estimates the
    two sketched-engine overhead families from a trace of
    ``calibration_sweep`` rows won by the sketched engines, and the
    artifact provenance names exactly them (the exact-engine constants
    pass through unfitted — no gather or sequential rows here)."""

    GEOMETRIES = (
        {"n": 500_000, "d": 16_384, "k": 2, "sparsity": 82 / 16_384,
         "machines": 1},
        {"n": 250_000, "d": 16_384, "k": 2, "sparsity": 82 / 16_384,
         "machines": 1},
    )
    # The "true" overheads of the machine the synthetic trace pretends
    # to be: 1.5x the shipped constants — inside the drift-gate bound
    # (ln 1.5 < 0.7) yet clearly distinguishable from the base family.
    SRHT_TRUE = cost_mod.TPU_SRHT_SKETCH_OVERHEAD * 1.5
    CS_TRUE = cost_mod.TPU_COUNTSKETCH_OVERHEAD * 1.5

    @pytest.fixture(scope="class")
    def trace_dir(self, tmp_path_factory):
        work = str(tmp_path_factory.mktemp("sketch_sweep"))
        base = {
            "cpu": cost_mod.TPU_CPU_WEIGHT,
            "mem": cost_mod.TPU_MEM_WEIGHT,
            "network": 0.0,  # single-chip sweep: no network term
            "sparse_gather_overhead": cost_mod.TPU_SPARSE_GATHER_OVERHEAD,
        }
        with obs.tracing(work, run_id="sketchsweep01"):
            for label, family, true_ov in (
                ("SketchedLeastSquares", "srht_sketch_overhead",
                 self.SRHT_TRUE),
                ("IterativeHessianSketch", "countsketch_overhead",
                 self.CS_TRUE),
            ):
                for ctx in self.GEOMETRIES:
                    predicted = cal.predict_seconds(label, ctx, base)
                    measured = cal.predict_seconds(
                        label, ctx, {**base, family: true_ov}
                    )
                    ref = obs.record_cost_decision(obs.CostDecision(
                        decision="calibration_sweep",
                        winner=label,
                        candidates=[{"label": label, "cost_s": predicted,
                                     "feasible": True}],
                        reason="sweep",
                        context=dict(ctx),
                    ))
                    ref.stamp(measured, timing="min_of_N_warm")
        return work

    def test_cli_refit_names_sketched_families(self, trace_dir,
                                               tmp_path, capsys):
        from keystone_tpu.tools.calibrate import main

        out_path = str(tmp_path / "cal.json")
        rc = main([trace_dir, "--refit", out_path])
        capsys.readouterr()
        assert rc == 0
        doc = cal.load_calibration_artifact(out_path)
        prov = doc["provenance"]
        assert set(prov["fitted"]) == {
            "srht_sketch_overhead", "countsketch_overhead"
        }
        w = doc["weights"]
        assert w["srht_sketch_overhead"] == pytest.approx(
            self.SRHT_TRUE, rel=1e-3)
        assert w["countsketch_overhead"] == pytest.approx(
            self.CS_TRUE, rel=1e-3)
        # Families with no rows in this trace keep the base constants.
        assert w["cpu"] == pytest.approx(cost_mod.TPU_CPU_WEIGHT)
        assert w["mem"] == pytest.approx(cost_mod.TPU_MEM_WEIGHT)
        assert w["sparse_gather_overhead"] == pytest.approx(
            cost_mod.TPU_SPARSE_GATHER_OVERHEAD)

    def test_refit_reduces_error_on_its_own_rows(self, trace_dir):
        events = obs.load_events(trace_dir)
        result = cal.refit(events, kinds=("calibration_sweep",))
        assert result["after"]["median_abs_log_error"] <= (
            result["before"]["median_abs_log_error"])
        assert result["after"]["median_abs_log_error"] < 1e-6

    def test_sweep_trace_passes_drift_gate_as_recorded(self, trace_dir,
                                                       capsys):
        """1.5x overhead drift is within the gate's bound — the CLI
        audits clean (exit 0), and the refit is the precision upgrade,
        not a fire drill."""
        from keystone_tpu.tools.calibrate import main

        rc = main([trace_dir])
        out = capsys.readouterr().out
        assert rc == 0, out
        assert "drift verdict" in out
