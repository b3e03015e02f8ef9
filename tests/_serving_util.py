"""Shared fixtures for the serving tests: a tiny fitted mnist-shaped
pipeline (2 FFT branches, 16-dim input, single solver block) and a
trace-counting transformer for warm-path compile pins."""

import numpy as np
import jax.numpy as jnp

from keystone_tpu.data import Dataset
from keystone_tpu.ops.learning.block import BlockLeastSquaresEstimator
from keystone_tpu.ops.util import ClassLabelIndicatorsFromIntLabels
from keystone_tpu.pipelines.mnist_random_fft import (
    MnistRandomFFTConfig,
    build_featurizer,
)
from keystone_tpu.workflow import Transformer
from keystone_tpu.workflow.pipeline import (
    FittedPipeline,
    TransformerGraph,
)

TINY_D_IN = 16


def fit_tiny_mnist(n=96, d_in=TINY_D_IN, num_ffts=2, block_size=16, seed=0):
    """Fit the mnist_random_fft featurizer + BlockLS at toy scale; returns
    (fitted, X_train). Single solver block (block_size == d_feat) so the
    offline per-block apply and the fused flat-GEMM serve path run the
    same contraction."""
    rng = np.random.default_rng(seed)
    X = jnp.asarray(rng.normal(size=(n, d_in)).astype(np.float32))
    y = rng.integers(0, 10, size=n)
    labels = ClassLabelIndicatorsFromIntLabels(10)(Dataset.of(jnp.asarray(y)))
    cfg = MnistRandomFFTConfig(
        num_ffts=num_ffts, block_size=block_size, image_size=d_in
    )
    fitted = build_featurizer(cfg).and_then(
        BlockLeastSquaresEstimator(block_size, 1, 1e-3), Dataset.of(X), labels
    ).fit()
    return fitted, np.asarray(X)


class TraceCountingScale(Transformer):
    """Device-pure x -> 2x whose traced-function body counts traces: the
    python body of a jitted function runs once per TRACE, never on a
    compiled-cache hit, so ``traces`` is exactly the compile count. The
    count lives on the node, which rides in ``static_key`` (by identity)."""

    def __init__(self):
        self.traces = 0

    def apply(self, x):
        return jnp.asarray(x) * 2.0

    def device_operands(self):
        return (self,), ()

    @staticmethod
    def device_apply(static_key, params, X):
        static_key[0].traces += 1
        return X * 2.0


def fitted_from_transformer(t) -> FittedPipeline:
    """Wrap a single transformer as a FittedPipeline (no estimators to
    fit — the minimal transformer-only graph)."""
    pipe = t.to_pipeline()
    return FittedPipeline(
        TransformerGraph.from_graph(pipe.executor.graph),
        pipe.source,
        pipe.sink,
    )
