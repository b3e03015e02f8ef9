"""Static plan verifier (ISSUE 6 tentpole): every seeded violation class
is caught with a report naming the offending node; the bundled pipelines
dry-run with ZERO findings; fit / optimizer / export all run the
verifier by default and the env knob disables it; runtime node failures
carry the same coordinates as verifier reports."""

import numpy as np
import pytest

import jax.numpy as jnp

from keystone_tpu.data import Dataset
from keystone_tpu.ops.stats import CosineRandomFeatures, LinearRectifier, RandomSignNode
from keystone_tpu.ops.util import Cacher, MaxClassifier
from keystone_tpu.workflow import (
    Graph,
    LambdaTransformer,
    PipelineDataset,
    PlanVerificationError,
    SourceId,
    Transformer,
    verify_graph,
)
from keystone_tpu.workflow.pipeline import Estimator, LabelEstimator
from keystone_tpu.workflow.operators import DatasetOperator
from keystone_tpu.workflow.verify import (
    CACHE_SPLITS_FUSION,
    DTYPE_DRIFT,
    ESTIMATOR_IN_APPLY,
    GATHER_MISMATCH,
    HOST_SIGNATURE_MISMATCH,
    SHAPE_MISMATCH,
    UNDECLARED_SIGNATURE,
    ArraySig,
    HostSig,
    verification_mode,
)


class _IdentityFit(Transformer):
    def apply(self, x):
        return x

    def device_operands(self):
        return (), ()

    @staticmethod
    def device_apply(static_key, params, X):
        return X


class _MeanEstimator(LabelEstimator):
    """Minimal estimator: fits a bias, applies identity+bias."""

    def fit(self, data, labels):
        return _IdentityFit()


class _UnaryMeanEstimator(Estimator):
    def fit(self, data):
        return _IdentityFit()


class _CastsToBf16(Transformer):
    """Seeded dtype-drift violation: silently narrows f32 -> bf16."""

    def apply(self, x):
        return jnp.asarray(x, jnp.bfloat16)

    def device_operands(self):
        return (), ()

    @staticmethod
    def device_apply(static_key, params, X):
        return X.astype(jnp.bfloat16)


def _data(n=4, d=5, dtype=np.float32):
    return Dataset(np.zeros((n, d), dtype))


def _labels(n=4, k=3):
    return Dataset(np.zeros((n, k), np.float32))


class TestSeededViolations:
    def test_shape_mismatch_names_node(self):
        # 16 random features over an 8-wide input, fed a d=5 dataset.
        rf = CosineRandomFeatures(8, 16, 1.0, seed=0)
        applied = rf.to_pipeline().apply(PipelineDataset.of(_data(d=5)))
        report = verify_graph(applied.executor.graph)
        findings = report.by_code(SHAPE_MISMATCH)
        assert len(findings) == 1
        f = findings[0]
        assert f.operator == "CosineRandomFeaturesModel"
        assert f.node in applied.executor.graph.nodes
        assert f.severity == "error"

    def test_a_rejected_plan_lets_its_operators_arrays_go(self):
        """A trace that fails is let go with its constants: jax alone keeps
        it, and with it the operator's bank, for the life of the process (a
        later test file of the same process then finds ``_DRAWN_BANKS`` held)."""
        import gc
        import weakref

        rf = CosineRandomFeatures(8, 16, 1.0, seed=0)
        bank = weakref.ref(rf.W)
        applied = rf.to_pipeline().apply(PipelineDataset.of(_data(d=5)))
        assert verify_graph(applied.executor.graph).by_code(SHAPE_MISMATCH)
        del rf, applied
        gc.collect()
        assert bank() is None

    @pytest.mark.parametrize("inner_jit", [False, True])
    def test_a_failed_abstract_evaluation_keeps_no_constant(self, inner_jit):
        import gc
        import weakref

        import jax
        from keystone_tpu.workflow.verify import _eval_shape

        W = jnp.ones((16, 8))
        held = weakref.ref(W)

        def fn(X):
            return X @ W.T

        with pytest.raises(TypeError):
            _eval_shape(jax.jit(fn) if inner_jit else fn,
                        jax.ShapeDtypeStruct((4, 5), jnp.float32))
        del W, fn
        gc.collect()
        assert held() is None

    def test_dtype_drift_is_reported(self):
        chain = RandomSignNode.create(5).and_then(_CastsToBf16()).and_then(
            LinearRectifier()
        )
        applied = chain.apply(PipelineDataset.of(_data(d=5)))
        report = verify_graph(applied.executor.graph)
        drift = report.by_code(DTYPE_DRIFT)
        assert len(drift) == 1
        assert drift[0].operator == "_CastsToBf16"
        assert "bfloat16" in drift[0].message
        # Drift is warning-severity: it reports, it does not reject.
        assert not report.errors

    def test_declared_dtype_change_is_silent(self):
        class Declared(_CastsToBf16):
            declares_dtype_change = True

        applied = (
            RandomSignNode.create(5).and_then(Declared())
        ).apply(PipelineDataset.of(_data(d=5)))
        assert not verify_graph(applied.executor.graph).by_code(DTYPE_DRIFT)

    def test_estimator_output_consumed_as_data(self):
        g = Graph()
        g, data = g.add_node(DatasetOperator(_data()), [])
        g, est = g.add_node(_UnaryMeanEstimator(), [data])
        # A transformer eating the ESTIMATOR output as if it were data.
        g, bad = g.add_node(MaxClassifier(), [est])
        g, _ = g.add_sink(bad)
        report = verify_graph(g)
        leaks = report.by_code(ESTIMATOR_IN_APPLY)
        assert len(leaks) == 1
        assert leaks[0].node == bad
        assert leaks[0].severity == "error"

    def test_cache_cut_splitting_fusable_chain(self):
        chain = (
            RandomSignNode.create(5)
            .and_then(Cacher())
            .and_then(LinearRectifier())
        )
        applied = chain.apply(PipelineDataset.of(_data(d=5)))
        report = verify_graph(applied.executor.graph)
        cuts = report.by_code(CACHE_SPLITS_FUSION)
        assert len(cuts) == 1
        assert cuts[0].operator == "Cacher"
        assert "RandomSignNode" in cuts[0].message
        assert "LinearRectifier" in cuts[0].message

    def test_cache_after_multi_consumer_node_is_clean(self):
        """The dependency feeds a SECOND consumer besides the cacher: it
        is a materialization point in the fused plan already
        (StageFusionRule only chains single-consumer links), so the
        cache cut is legitimate — the check must agree with the
        authoritative fusion.cache_would_split_fusion predicate."""
        g = Graph()
        g, data = g.add_node(DatasetOperator(_data(d=5)), [])
        g, d = g.add_node(RandomSignNode.create(5), [data])
        g, cache = g.add_node(Cacher(), [d])
        g, b = g.add_node(LinearRectifier(), [cache])
        g, other = g.add_node(MaxClassifier(), [d])  # second consumer of d
        g, _ = g.add_sink(b)
        g, _ = g.add_sink(other)
        assert not verify_graph(g).by_code(CACHE_SPLITS_FUSION)

    def test_cache_on_fusion_boundary_is_clean(self):
        # A cache AFTER the full device chain (feeding only the sink)
        # sits on a materialization boundary — no finding.
        chain = RandomSignNode.create(5).and_then(LinearRectifier()).and_then(
            Cacher()
        )
        applied = chain.apply(PipelineDataset.of(_data(d=5)))
        assert not verify_graph(applied.executor.graph).by_code(
            CACHE_SPLITS_FUSION
        )

    def test_undeclared_host_op_strict(self):
        host_data = Dataset(["a b", "c d"])
        chain = LambdaTransformer(lambda s: s.split())
        applied = chain.to_pipeline().apply(PipelineDataset.of(host_data))
        strict = verify_graph(applied.executor.graph, strict=True)
        undeclared = strict.by_code(UNDECLARED_SIGNATURE)
        assert len(undeclared) == 1
        assert undeclared[0].operator.startswith("Lambda")
        # Default mode: unknown propagation, no finding.
        assert not verify_graph(applied.executor.graph).findings

    def test_host_kind_mismatch(self):
        from keystone_tpu.ops.nlp import NGramsFeaturizer, Trim

        chain = Trim().and_then(NGramsFeaturizer([1, 2]))
        applied = chain.apply(PipelineDataset.of(Dataset(["doc one"])))
        report = verify_graph(applied.executor.graph)
        bad = report.by_code(HOST_SIGNATURE_MISMATCH)
        assert len(bad) == 1
        assert "tokens" in bad[0].message

    def test_estimator_input_size_mismatch(self):
        pipe = _MeanEstimator().with_data(_data(n=4), _labels(n=6))
        report = verify_graph(pipe.executor.graph)
        sizes = report.by_code(GATHER_MISMATCH)
        assert len(sizes) == 1
        assert "4" in sizes[0].message and "6" in sizes[0].message


class TestDryRunNoFalsePositives:
    def test_all_bundled_pipelines_verify_clean_strict(self):
        from keystone_tpu.tools.dryrun import BUILDERS, dryrun

        reports = dryrun(strict=True)
        assert set(reports) == set(BUILDERS) and len(reports) == 5
        for name, report in reports.items():
            assert not report.findings, (
                f"{name}: false positives: "
                + "; ".join(str(f) for f in report.findings)
            )
            # The interpretation actually propagated signatures (the
            # clean report is not an everything-was-unknown vacuity).
            assert len(report.sigs) > 5, name


def _bad_fit_pipeline():
    """16 cosine features over 8 inputs, composed on d=5 training data:
    the estimator fit would crash mid-GEMM at runtime."""
    from keystone_tpu.ops.learning.linear import LinearMapEstimator

    rf = CosineRandomFeatures(8, 16, 1.0, seed=0)
    return rf.and_then(LinearMapEstimator(lam=1.0), _data(d=5), _labels())


class TestPrepassIntegration:
    def test_fit_rejects_invalid_plan(self):
        with pytest.raises(PlanVerificationError) as exc:
            _bad_fit_pipeline().fit()
        assert "shape-mismatch" in str(exc.value)
        assert "CosineRandomFeaturesModel" in str(exc.value)

    def test_optimizer_rejects_invalid_plan(self):
        from keystone_tpu.workflow.optimizer import DefaultOptimizer

        pipe = _bad_fit_pipeline()
        with pytest.raises(PlanVerificationError):
            DefaultOptimizer().execute(pipe.executor.graph, {})

    def test_apply_rejects_invalid_plan(self):
        rf = CosineRandomFeatures(8, 16, 1.0, seed=0)
        result = rf.to_pipeline().apply(PipelineDataset.of(_data(d=5)))
        with pytest.raises(PlanVerificationError):
            result.get()

    def test_env_knob_disables(self, monkeypatch):
        monkeypatch.setenv("KEYSTONE_VERIFY", "off")
        assert verification_mode() == "off"
        # The invalid plan now sails past the pre-pass and fails at
        # RUNTIME instead (some shape error from the actual execution).
        with pytest.raises(Exception) as exc:
            _bad_fit_pipeline().fit()
        assert not isinstance(exc.value, PlanVerificationError)

    def test_env_knob_strict(self, monkeypatch):
        monkeypatch.setenv("KEYSTONE_VERIFY", "strict")
        assert verification_mode() == "strict"
        monkeypatch.setenv("KEYSTONE_VERIFY", "on")
        assert verification_mode() == "on"

    def test_export_rejects_wrong_example_shape(self):
        rf = CosineRandomFeatures(8, 16, 1.0, seed=0)
        fitted = rf.to_pipeline().fit()
        from keystone_tpu.serving.export import export_plan

        with pytest.raises(PlanVerificationError):
            export_plan(fitted, np.zeros(5, np.float32), precompile=False)
        # Correct example shape exports fine.
        plan = export_plan(fitted, np.zeros(8, np.float32), precompile=False)
        assert plan.compiled

    def test_export_estimator_leak_reported(self):
        from keystone_tpu.workflow.verify import verify_apply_graph

        g = Graph()
        g, data = g.add_node(DatasetOperator(_data()), [])
        g, est = g.add_node(_UnaryMeanEstimator(), [data])
        g, sink = g.add_sink(est)
        g, src = g.add_source()
        with pytest.raises(PlanVerificationError) as exc:
            verify_apply_graph(g, src, sink)
        assert "estimator-in-apply" in str(exc.value)


class _Boom(Transformer):
    def apply(self, x):
        raise ValueError("boom inside node")

    def batch_apply(self, data):
        raise ValueError("boom inside node")


class TestRuntimeErrorCoordinates:
    def test_executor_failure_names_node_and_inputs(self):
        chain = RandomSignNode.create(5).and_then(_Boom())
        result = chain.apply(PipelineDataset.of(_data(d=5)))
        with pytest.raises(ValueError) as exc:
            result.get()
        msg = str(exc.value)
        assert "boom inside node" in msg
        assert "keystone node" in msg
        assert "_Boom" in msg
        assert "Node(" in msg
        # Inferred input signature of the failing node's dep is cited.
        assert "f[4,5]" in msg

    def test_annotation_applies_once_at_deepest_node(self):
        chain = RandomSignNode.create(5).and_then(_Boom()).and_then(
            LinearRectifier()
        )
        result = chain.apply(PipelineDataset.of(_data(d=5)))
        with pytest.raises(ValueError) as exc:
            result.get()
        assert str(exc.value).count("keystone node") == 1

    def test_fitted_pipeline_failure_names_node(self):
        fitted = _Boom().to_pipeline().fit()
        with pytest.raises(ValueError) as exc:
            fitted.apply(_data(d=5))
        assert "keystone node" in str(exc.value)
        assert "_Boom" in str(exc.value)

    def test_exception_type_is_preserved(self):
        class Custom(Exception):
            pass

        class RaisesCustom(Transformer):
            def batch_apply(self, data):
                raise Custom("custom")

            def apply(self, x):
                raise Custom("custom")

        result = RaisesCustom().to_pipeline().apply(
            PipelineDataset.of(_data(d=5))
        )
        with pytest.raises(Custom):
            result.get()


class TestSignatureHelpers:
    def test_array_sig_describe(self):
        assert ArraySig((None, 4), "float32").describe() == "batch f[?,4]:float32"
        assert HostSig("tokens").describe() == "host[tokens]"

    def test_signature_of_dataset_forms(self):
        from keystone_tpu.workflow.verify import signature_of_value

        s = signature_of_value(_data(n=3, d=7))
        assert isinstance(s, ArraySig) and s.shape == (3, 7) and s.n == 3
        h = signature_of_value(Dataset(["a", "b"]))
        assert isinstance(h, HostSig) and h.kind == "str" and h.n == 2
        sp = signature_of_value(Dataset(
            {"indices": np.zeros((2, 3), np.int32),
             "values": np.zeros((2, 3), np.float32)}, n=2
        ))
        assert isinstance(sp, HostSig) and sp.kind == "sparse"
