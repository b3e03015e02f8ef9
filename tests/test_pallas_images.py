"""The convolution's XLA path and dtype contract, and the featurize's
FLOP model. The Pallas kernel that takes a convolution with its rectifier
and sum pools is tested in tests/test_conv_pool_kernel.py."""

import numpy as np
import jax.numpy as jnp
import pytest

from keystone_tpu.ops import pallas_images as pi
from keystone_tpu.ops.images.conv import (
    Convolver,
    im2col,
    normalize_patch_rows,
)

rng = np.random.default_rng(7)


def _xla_reference(images, filters, means=None, *, patch_size,
                   normalize_patches=True, var_constant=10.0):
    patches = im2col(jnp.asarray(images, jnp.float32), patch_size)
    if normalize_patches:
        patches = normalize_patch_rows(patches, var_constant)
    if means is not None:
        patches = patches - jnp.asarray(means, jnp.float32)
    return np.asarray(
        jnp.einsum(
            "nxyd,kd->nxyk", patches, jnp.asarray(filters, jnp.float32),
            preferred_element_type=jnp.float32,
        )
    )


class TestConvFeaturizeKernel:
    def test_flop_model(self):
        assert pi.conv_featurize_flops(2, 3, 4, 5, 6) == 2.0 * 2 * 3 * 4 * 5 * 6


class TestConvolverTakesTheXlaPath:
    def test_kernel_is_not_dispatched(self, monkeypatch):
        """A convolution alone never dispatches a kernel — not even with
        kernels forced on: the XLA path is its stated path (a fused chain
        with a rectifier and sum pools takes ``conv_pool``:
        tests/test_conv_pool_kernel.py)."""
        from keystone_tpu.ops import pallas_ops

        monkeypatch.setenv("KEYSTONE_PALLAS", "1")
        filters = rng.normal(size=(4, 3 * 3 * 3)).astype(np.float32)
        conv = Convolver(filters, img_x=8, img_y=8, img_channels=3)
        images = rng.normal(size=(4, 8, 8, 3)).astype(np.float64)
        with pallas_ops.record_dispatches() as dispatched:
            got = np.asarray(conv.apply(images))
        assert dispatched == []
        assert got.dtype == np.float32  # declared compute dtype, f64 input
        want = _xla_reference(
            images.astype(np.float32), filters, None, patch_size=3
        )
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


class TestConvolverDtypeContract:
    """ISSUE 18 satellite 2: the f64→f32 narrowing in Convolver is a
    DECLARED compute-dtype contract, not silent drift — the class
    carries ``declares_dtype_change`` and a strict verifier dry-run of
    the image featurizer pipeline over float64 loader output is clean."""

    def test_convolver_declares_dtype_change(self):
        assert Convolver.declares_dtype_change is True

    def test_eager_apply_narrows_to_f32(self):
        conv = Convolver(
            rng.normal(size=(4, 2 * 2 * 3)).astype(np.float32),
            img_x=8, img_y=8, img_channels=3,
        )
        out = conv.apply(jnp.asarray(
            rng.uniform(0, 255, size=(2, 8, 8, 3)), jnp.float64))
        assert out.dtype == jnp.float32

    def test_image_pipeline_strict_verify_clean_on_f64_source(self):
        from keystone_tpu.data import Dataset
        from keystone_tpu.ops.images.conv import Pooler, SymmetricRectifier
        from keystone_tpu.ops.images.core import ImageVectorizer
        from keystone_tpu.workflow import PipelineDataset, verify_graph
        from keystone_tpu.workflow.verify import DTYPE_DRIFT

        conv = Convolver(
            rng.normal(size=(8, 5 * 5 * 3)).astype(np.float32),
            img_x=32, img_y=32, img_channels=3,
        )
        pipe = (
            conv.to_pipeline()
            .and_then(SymmetricRectifier(alpha=0.25))
            .and_then(Pooler(14, 14, pool_function="sum"))
            .and_then(ImageVectorizer())
        )
        # synthetic_cifar-shaped loader output: float64 in [0, 255].
        images = Dataset(np.asarray(
            rng.uniform(0, 255, size=(6, 32, 32, 3)), np.float64))
        applied = pipe.apply(PipelineDataset.of(images))
        report = verify_graph(applied.executor.graph, strict=True)
        assert not report.by_code(DTYPE_DRIFT), (
            "declared f64→f32 narrowing reported as drift: "
            + "; ".join(str(f) for f in report.by_code(DTYPE_DRIFT))
        )
        assert not report.findings, (
            "image pipeline not strict-clean: "
            + "; ".join(str(f) for f in report.findings)
        )
