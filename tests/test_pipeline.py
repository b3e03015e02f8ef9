"""Pipeline semantics tests (contract from reference PipelineSuite.scala:28-520):
chaining, estimators fit exactly once, prefix state reuse across applications,
gather, fit() producing transformer-only serializable pipelines.
"""

import numpy as np
import jax.numpy as jnp
import pytest

from keystone_tpu import Dataset, Pipeline, PipelineEnv, Transformer
from keystone_tpu.workflow import Estimator, Identity, LabelEstimator, transformer
from keystone_tpu.ops.util import Cacher


class Double(Transformer):
    def apply(self, x):
        return x * 2


class AddOne(Transformer):
    def apply(self, x):
        return x + 1


class AddConst(Transformer):
    def __init__(self, c):
        self.c = float(c)

    def apply(self, x):
        return x + self.c


class CountingEstimator(Estimator):
    """Estimator that counts fits and produces a transformer adding the dataset mean."""

    def __init__(self):
        self.fit_count = 0

    def fit(self, data: Dataset):
        self.fit_count += 1
        return AddConst(jnp.mean(data.array[: data.n]))


class CountingLabelEstimator(LabelEstimator):
    def __init__(self):
        self.fit_count = 0

    def fit(self, data: Dataset, labels: Dataset):
        self.fit_count += 1
        shift = jnp.mean(data.array[: data.n]) + jnp.mean(labels.array[: labels.n])

        class Shift(Transformer):
            def apply(self, x, _s=shift):
                return x + _s

        return Shift()


def dataset(values):
    return Dataset.of(np.asarray(values, dtype=np.float64))


class TestChaining:
    def test_transformer_chain_datum(self):
        pipe = Double().and_then(AddOne())
        assert float(pipe.apply(3.0).get()) == 7.0

    def test_transformer_chain_dataset(self):
        pipe = Double().and_then(AddOne())
        out = pipe.apply(dataset([1.0, 2.0, 3.0])).get()
        np.testing.assert_allclose(out.to_numpy(), [3.0, 5.0, 7.0])

    def test_or_sugar(self):
        pipe = Double() | AddOne() | Double()
        assert float(pipe.apply(1.0).get()) == 6.0

    def test_identity(self):
        pipe = Identity().and_then(Double())
        assert float(pipe.apply(2.0).get()) == 4.0

    def test_result_memoized(self):
        calls = []

        class Tracking(Transformer):
            def apply(self, x):
                calls.append(x)
                return x

        pipe = Tracking().to_pipeline()
        res = pipe.apply(1.0)
        res.get()
        res.get()
        assert len(calls) == 1


class TestEstimators:
    def test_estimator_fit_and_apply(self):
        est = CountingEstimator()
        data = dataset([0.0, 2.0, 4.0])  # mean 2
        pipe = Double().and_then(est, data)
        # train data passes through Double -> mean 4
        assert float(pipe.apply(1.0).get()) == pytest.approx(6.0)  # 1*2 + 4

    def test_estimator_fits_only_once(self):
        est = CountingEstimator()
        data = dataset([1.0, 2.0, 3.0])
        pipe = Double().and_then(est, data)
        pipe.apply(1.0).get()
        pipe.apply(2.0).get()
        pipe.apply(dataset([1.0, 4.0])).get()
        assert est.fit_count == 1

    def test_label_estimator(self):
        est = CountingLabelEstimator()
        data = dataset([0.0, 2.0])  # doubled: mean 2
        labels = dataset([10.0, 20.0])  # mean 15
        pipe = Double().and_then(est, data, labels)
        assert float(pipe.apply(0.0).get()) == pytest.approx(17.0)
        assert est.fit_count == 1

    def test_state_reuse_across_pipeline_applications(self):
        """Fitted state is reused via the prefix table across separately
        constructed pipelines over the same data (PipelineSuite.scala:115-326)."""
        data = dataset([1.0, 2.0, 3.0])
        est = CountingEstimator()
        dbl = Double()
        pipe1 = dbl.and_then(est, data)
        pipe1.apply(1.0).get()
        assert est.fit_count == 1
        # A second pipeline with identical (operator, data) prefix structure:
        pipe2 = dbl.and_then(est, data)
        pipe2.apply(5.0).get()
        assert est.fit_count == 1  # loaded from PipelineEnv.state, not refit


class TestGather:
    def test_gather_datum(self):
        pipe = Pipeline.gather([Double().to_pipeline(), AddOne().to_pipeline()])
        out = pipe.apply(3.0).get()
        assert [float(x) for x in out] == [6.0, 4.0]

    def test_gather_dataset(self):
        pipe = Pipeline.gather([Double().to_pipeline(), AddOne().to_pipeline()])
        out = pipe.apply(dataset([1.0, 2.0])).get()
        items = out.to_list()
        assert len(items) == 2
        assert [float(v) for v in items[0]] == [2.0, 2.0]
        assert [float(v) for v in items[1]] == [4.0, 3.0]


class TestFit:
    def test_fit_produces_transformer_only_pipeline(self):
        est = CountingEstimator()
        data = dataset([0.0, 4.0])  # doubled: mean 4
        pipe = Double().and_then(est, data)
        fitted = pipe.fit()
        assert est.fit_count == 1
        assert float(fitted.apply(1.0)) == pytest.approx(6.0)
        # Applying fitted pipeline does not refit
        fitted.apply(2.0)
        assert est.fit_count == 1

    def test_fitted_pipeline_on_dataset(self):
        est = CountingEstimator()
        data = dataset([0.0, 4.0])
        fitted = Double().and_then(est, data).fit()
        out = fitted.apply(dataset([0.0, 1.0]))
        np.testing.assert_allclose(out.to_numpy(), [4.0, 6.0])

    def test_fit_publishes_prefix_state(self):
        """fit() publishes fitted estimators to the prefix table so later
        pipelines over the same logical prefix don't refit."""
        est = CountingEstimator()
        data = dataset([1.0, 2.0])
        dbl = Double()
        dbl.and_then(est, data).fit()
        assert est.fit_count == 1
        pipe2 = dbl.and_then(est, data)
        pipe2.apply(5.0).get()
        assert est.fit_count == 1

    def test_fitted_pipeline_save_load(self, tmp_path):
        est = CountingEstimator()
        data = dataset([0.0, 4.0])
        fitted = Double().and_then(est, data).fit()
        path = str(tmp_path / "pipe.pkl")
        fitted.save(path)
        loaded = type(fitted).load(path)
        assert float(loaded.apply(1.0)) == pytest.approx(6.0)


class TestCacher:
    def test_cacher_prefix_state_saved(self):
        data = dataset([1.0, 2.0])
        pipe = Double().and_then(Cacher())
        out = pipe.apply(data)
        out.get()
        # The Cacher node's prefix should now be in the global state table.
        assert len(PipelineEnv.get_or_create().state) >= 1


class TestLambdaAndCSE:
    def test_lambda_transformer(self):
        pipe = transformer(lambda x: x * 3).to_pipeline()
        assert float(pipe.apply(2.0).get()) == 6.0

    def test_equal_transformers_merge(self):
        """Structurally equal dataclass transformers trigger CSE."""
        from dataclasses import dataclass

        calls = []

        @dataclass(frozen=True)
        class Stamp(Transformer):
            tag: int

            def apply(self, x):
                calls.append(self.tag)
                return x + self.tag

        branch = Stamp(5).to_pipeline()
        pipe = Pipeline.gather([branch, Stamp(5).to_pipeline()])
        out = pipe.apply(1.0).get()
        assert [float(v) for v in out] == [6.0, 6.0]
        # CSE merged the two equal nodes: only one execution.
        assert len(calls) == 1


class TestBatchApplyDefault:
    """Transformer.batch_apply derives from device_fn: batched on device
    datasets AND on rectangular host collections (one dispatch, not one per
    item); ragged host items fall back to per-item apply."""

    def test_rectangular_host_list_takes_batched_path(self, monkeypatch):
        from keystone_tpu.ops.util import FloatToDouble

        items = [np.full(3, i, dtype=np.float32) for i in range(4)]
        # Direct construction keeps the list (host form) — Dataset.of would
        # eagerly stack a rectangular list, bypassing the branch under test.
        ds = Dataset(list(items))
        assert ds.is_host
        calls = []
        t = FloatToDouble()
        orig = FloatToDouble.device_apply
        monkeypatch.setattr(
            FloatToDouble, "device_apply",
            staticmethod(lambda k, p, X: calls.append(X.shape) or orig(k, p, X)),
        )
        out = t.batch_apply(ds)
        assert calls == [(4, 3)]  # one batched call over the stacked array
        assert not out.is_host
        assert out.n == 4
        np.testing.assert_allclose(np.asarray(out.array), np.stack(items))

    def test_ragged_host_items_fall_back_per_item(self):
        from keystone_tpu.ops.images.core import GrayScaler

        rng = np.random.default_rng(0)
        imgs = [rng.random((5 + i, 4, 3)).astype(np.float32) for i in range(3)]
        out = GrayScaler().batch_apply(Dataset.of(imgs))
        shapes = [np.asarray(a).shape for a in out.to_list()]
        assert shapes == [(5, 4, 1), (6, 4, 1), (7, 4, 1)]

    def test_no_device_fn_maps_apply(self):
        class PlusOne(Transformer):
            def apply(self, x):
                return x + 1

        out = PlusOne().batch_apply(Dataset.of([1.0, 2.0]))
        assert [float(v) for v in out.to_list()] == [2.0, 3.0]


class TestDatumApplyCompileCache:
    """ISSUE 4 satellite: repeated single-datum FittedPipeline.apply calls
    with the same shape reuse ONE compiled executable — the trace-counter
    fixture pins the compile count."""

    def _fitted_chain(self, counter):
        from keystone_tpu.workflow.pipeline import (
            FittedPipeline,
            TransformerGraph,
        )

        pipe = counter.to_pipeline()
        return FittedPipeline(
            TransformerGraph.from_graph(pipe.executor.graph),
            pipe.source,
            pipe.sink,
        )

    def test_same_shape_compiles_once(self):
        from tests._serving_util import TraceCountingScale

        t = TraceCountingScale()
        fitted = self._fitted_chain(t)
        x = np.arange(6, dtype=np.float32)
        outs = [np.asarray(fitted.apply(x + i)) for i in range(4)]
        assert t.traces == 1, "same-shape datum applies re-traced"
        for i, o in enumerate(outs):
            np.testing.assert_array_equal(o, (x + i) * 2.0)

    def test_new_shape_compiles_again_and_caps(self):
        from tests._serving_util import TraceCountingScale

        t = TraceCountingScale()
        fitted = self._fitted_chain(t)
        fitted.apply(np.zeros(3, np.float32))
        fitted.apply(np.zeros(5, np.float32))
        fitted.apply(np.zeros(3, np.float32))  # cache hit
        assert t.traces == 2

    def test_non_traceable_pipeline_keeps_per_node_path(self):
        class HostOnly(Transformer):
            def apply(self, x):
                return np.asarray(x) + 1.0

        fitted = self._fitted_chain(HostOnly())
        out = fitted.apply(np.zeros(4, np.float32))
        np.testing.assert_array_equal(np.asarray(out), np.ones(4))

    def test_save_load_drops_and_rebuilds_datum_cache(self, tmp_path):
        from tests._serving_util import TraceCountingScale

        t = TraceCountingScale()
        fitted = self._fitted_chain(t)
        fitted.apply(np.zeros(4, np.float32))
        path = str(tmp_path / "fitted.pkl")
        fitted.save(path)
        from keystone_tpu.workflow.pipeline import FittedPipeline

        loaded = FittedPipeline.load(path)
        out = loaded.apply(np.ones(4, np.float32))
        np.testing.assert_array_equal(np.asarray(out), np.ones(4) * 2.0)
