"""Serving export (ISSUE 4 tentpole): the apply-only subgraph freezes to
a bucketed pre-compiled plan — transformer-only enforced, fusion reused,
warm path never traces, padding masked off responses."""

import numpy as np
import jax.numpy as jnp
import pytest

from keystone_tpu.data import Dataset
from keystone_tpu.serving import export_plan
from keystone_tpu.serving.export import ExportedPlan, _default_buckets
from keystone_tpu.workflow import Transformer
from keystone_tpu.workflow.graph import Graph, NodeId, SinkId, SourceId
from keystone_tpu.workflow.pipeline import FittedPipeline

from tests._serving_util import (
    TINY_D_IN,
    TraceCountingScale,
    fit_tiny_mnist,
    fitted_from_transformer,
)


class TestExportValidation:
    def test_rejects_unfitted_pipeline(self):
        t = TraceCountingScale()
        with pytest.raises(TypeError, match="FittedPipeline"):
            export_plan(t.to_pipeline(), np.zeros(4, np.float32))

    def test_rejects_graph_with_estimator_state(self):
        # A hand-built FittedPipeline smuggling an estimator operator must
        # fail at EXPORT (no fit_datasets can run at request time), not
        # mid-request.
        from keystone_tpu.workflow.operators import EstimatorOperator

        est = EstimatorOperator()
        graph = Graph(
            sources=frozenset({SourceId(0)}),
            sink_dependencies={SinkId(0): NodeId(0)},
            operators={NodeId(0): est},
            dependencies={NodeId(0): (SourceId(0),)},
        )
        fitted = FittedPipeline(graph, SourceId(0), SinkId(0))
        with pytest.raises(TypeError, match="Non-transformer"):
            export_plan(fitted, np.zeros(4, np.float32))

    def test_buckets_are_powers_of_two_up_to_max(self):
        # Bucket 1 is deliberately absent (batch-1 XLA codepaths differ
        # by a ulp — singletons pad to 2 to keep bit-identity).
        assert _default_buckets(256) == [2, 4, 8, 16, 32, 64, 128, 256]
        assert _default_buckets(1) == [1]
        assert _default_buckets(2) == [2]
        # Non-power-of-two max stays reachable as the final bucket.
        assert _default_buckets(48) == [2, 4, 8, 16, 32, 48]

    def test_batch_over_max_rejected(self):
        fitted = fitted_from_transformer(TraceCountingScale())
        plan = export_plan(fitted, np.zeros(4, np.float32), max_batch=8)
        with pytest.raises(ValueError, match="max_batch"):
            plan.apply_batch([np.zeros(4, np.float32)] * 9)


class TestWarmPathNeverTraces:
    def test_precompile_covers_every_bucket_then_zero_traces(self):
        t = TraceCountingScale()
        plan = export_plan(
            fitted_from_transformer(t), np.zeros(6, np.float32), max_batch=16
        )
        assert plan.compiled
        # Export-time traces: ONE abstract evaluation by the static plan
        # verifier (jax.eval_shape typechecks the chain against the
        # example input — workflow/verify.py) plus once per bucket shape
        # for AOT compilation. Nothing more.
        assert len(plan.buckets) == 4
        assert t.traces == len(plan.buckets) + 1
        rng = np.random.default_rng(0)
        for m in (1, 3, 4, 5, 11, 16, 2, 7):
            X = rng.normal(size=(m, 6)).astype(np.float32)
            out = plan.apply_batch(list(X))
            np.testing.assert_array_equal(out, X * 2.0)
        assert t.traces == 5, "warm-path request triggered a re-trace"
        # trace_count counts the jit's traces only (the verifier's
        # eval_shape never enters the jitted counter).
        assert plan.trace_count == 4

    def test_mnist_plan_compiles_to_one_program(self):
        fitted, _ = fit_tiny_mnist()
        plan = export_plan(
            fitted, np.zeros(TINY_D_IN, np.float32), max_batch=8
        )
        # The fusion passes collapse featurize gather + model into a
        # single-program plan (the compiled fast path, not the per-node
        # eager fallback).
        assert plan.compiled
        assert plan.pinned_bytes > 0


class TestServedOutputs:
    def test_padding_masked_and_rows_match_offline(self):
        fitted, _ = fit_tiny_mnist()
        plan = export_plan(
            fitted, np.zeros(TINY_D_IN, np.float32), max_batch=16
        )
        rng = np.random.default_rng(1)
        X = rng.normal(size=(5, TINY_D_IN)).astype(np.float32)
        out, info = plan.apply_batch_info(list(X))
        assert out.shape[0] == 5  # padding rows masked off the response
        assert info.bucket == 8 and info.batch_size == 5
        assert info.pad_fraction == pytest.approx(3 / 8)
        offline = np.asarray(fitted.apply(Dataset.of(jnp.asarray(X))).array)
        np.testing.assert_array_equal(out, offline)

    def test_eager_fallback_for_host_stage(self):
        class HostSquash(Transformer):
            """No device_fn: forces the non-composable fallback path."""

            def apply(self, x):
                return np.tanh(np.asarray(x))

            def batch_apply(self, ds):
                return Dataset(
                    jnp.asarray(np.tanh(np.asarray(ds.array))), n=ds.n
                )

        fitted = fitted_from_transformer(HostSquash())
        plan = export_plan(fitted, np.zeros(4, np.float32), max_batch=8)
        assert not plan.compiled
        X = np.random.default_rng(2).normal(size=(3, 4)).astype(np.float32)
        out = plan.apply_batch(list(X))
        np.testing.assert_allclose(out, np.tanh(X), rtol=1e-6)

    def test_singleton_request_bitwise_matches_offline(self):
        """Regression pin for the bucket-1 exclusion: a lone request —
        the case XLA's batch-1 codepath put a ulp off at FFT widths >= 32
        — now rides the 2-bucket and matches offline apply exactly."""
        fitted, _ = fit_tiny_mnist(d_in=32, block_size=32, seed=4)
        plan = export_plan(fitted, np.zeros(32, np.float32), max_batch=8)
        rng = np.random.default_rng(6)
        X = rng.normal(size=(6, 32)).astype(np.float32)
        offline = np.asarray(fitted.apply(Dataset.of(jnp.asarray(X))).array)
        for i in range(len(X)):
            out, info = plan.apply_batch_info([X[i]])
            assert info.bucket == 2 and info.pad_fraction == 0.5
            np.testing.assert_array_equal(out[0], offline[i])

    def test_single_request_measure(self):
        fitted, _ = fit_tiny_mnist()
        plan = export_plan(
            fitted, np.zeros(TINY_D_IN, np.float32), max_batch=4
        )
        s = plan.measure_single_request_s(reps=3)
        assert s > 0.0


class TestExportKnobs:
    def test_custom_buckets_must_reach_max_batch(self):
        fitted = fitted_from_transformer(TraceCountingScale())
        with pytest.raises(ValueError, match="max_batch"):
            ExportedPlan(
                fitted.transformer_graph, fitted.source, fitted.sink,
                np.zeros(4, np.float32), max_batch=16, buckets=[1, 4],
            )

    def test_bucket_for_picks_smallest_fitting(self):
        fitted = fitted_from_transformer(TraceCountingScale())
        plan = export_plan(
            fitted, np.zeros(4, np.float32), max_batch=32, precompile=False
        )
        assert plan.bucket_for(1) == 2  # singletons pad to the 2-bucket
        assert plan.bucket_for(3) == 4
        assert plan.bucket_for(17) == 32
        with pytest.raises(ValueError):
            plan.bucket_for(0)
        with pytest.raises(ValueError):
            plan.bucket_for(33)


class TestPlanFingerprint:
    def test_distinct_weights_distinct_fingerprints(self):
        f1, _ = fit_tiny_mnist(seed=0)
        f2, _ = fit_tiny_mnist(seed=1)
        example = np.zeros(TINY_D_IN, np.float32)
        p1 = export_plan(f1, example, max_batch=8, precompile=False)
        p2 = export_plan(f2, example, max_batch=8, precompile=False)
        assert p1.fingerprint != p2.fingerprint
        # Same fitted state => same identity (stable across exports).
        p1b = export_plan(f1, example, max_batch=8, precompile=False)
        assert p1b.fingerprint == p1.fingerprint

    def test_bucket_ladder_is_part_of_the_identity(self):
        """Review regression: buckets are part of the served bits — an
        explicit bucket-1 export serves singletons through XLA's batch-1
        codepath (a ulp off every other batch size, the PR 4 finding),
        so it must NOT share a fingerprint with the default-bucket
        export of the same weights."""
        f1, _ = fit_tiny_mnist(seed=0)
        example = np.zeros(TINY_D_IN, np.float32)
        default = export_plan(f1, example, max_batch=8, precompile=False)
        singleton = export_plan(f1, example, max_batch=8,
                                buckets=[1, 2, 4, 8], precompile=False)
        assert default.fingerprint != singleton.fingerprint

    def test_dict_valued_operator_state_reaches_fingerprint(self):
        """Review regression: fingerprint_token degrades a dict to its
        bare type name, so container-valued operator state (vocabulary
        maps, feature spaces) must be recursed into by plan_fingerprint
        itself — two plans differing ONLY in a dict attribute sharing a
        fingerprint would void the per-fingerprint bit-identity
        contract."""

        class VocabScale(Transformer):
            def __init__(self, vocab):
                self.vocab = vocab  # dict state, no arrays

            def apply(self, x):
                return jnp.asarray(x) * float(len(self.vocab))

            def device_operands(self):
                return (float(len(self.vocab)),), ()

            @staticmethod
            def device_apply(static_key, params, X):
                return X * static_key[0]

        example = np.zeros(4, np.float32)

        def fp(vocab):
            fitted = fitted_from_transformer(VocabScale(vocab))
            return export_plan(
                fitted, example, max_batch=4, precompile=False
            ).fingerprint

        base = {"a": 0, "b": 1}
        assert fp(base) != fp({"a": 0, "c": 1})
        assert fp(base) != fp({"a": 0, "b": 1, "c": 2})
        # Iteration order must NOT matter — only contents.
        assert fp(base) == fp({"b": 1, "a": 0})
        # Nested containers and sets recurse too.
        assert fp({"a": {"x", "y"}}) != fp({"a": {"x", "z"}})
        assert fp({"a": [1, {"k": 2}]}) != fp({"a": [1, {"k": 3}]})
