"""CIFAR-10 pipelines (reference: pipelines/images/cifar/).

- LinearPixels: grayscale pixels → exact least squares
  (LinearPixels.scala:18-56).
- RandomCifar: random gaussian conv filters → rectify → pool → least squares
  (RandomCifar.scala:20-77).
- RandomPatchCifar: ZCA-whitened random training patches as conv filters →
  rectify → pool → standardize → block least squares
  (RandomPatchCifar.scala:21-86).
- RandomPatchCifarKernel: same featurization → Gaussian-kernel ridge
  regression (RandomPatchCifarKernel.scala:33-76).
- RandomPatchCifarAugmented: random train crops + center/corner test crops,
  vote over augmented copies (RandomPatchCifarAugmented.scala:27-90).
"""

from __future__ import annotations

import argparse
import functools
import logging
import time
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from keystone_tpu import obs
from keystone_tpu.data import Dataset
from keystone_tpu.data.loaders import load_cifar_binary, synthetic_cifar
from keystone_tpu.evaluation import (
    AugmentedExamplesEvaluator,
    MulticlassClassifierEvaluator,
)
from keystone_tpu.ops.images.conv import (
    Convolver,
    Pooler,
    SymmetricRectifier,
    conv_form,
    normalize_patch_rows,
)
from keystone_tpu.ops.images.core import (
    CenterCornerPatcher,
    GrayScaler,
    ImageVectorizer,
    PixelScaler,
    RandomPatcher,
    gather_patches,
    patch_positions,
)
from keystone_tpu.ops.learning.block import BlockLeastSquaresEstimator
from keystone_tpu.ops.learning.kernel import (
    GaussianKernelGenerator,
    KernelRidgeRegression,
)
from keystone_tpu.ops.learning.linear import LinearMapEstimator
from keystone_tpu.ops.learning.pca import ZCAWhitener, ZCAWhitenerEstimator
from keystone_tpu.ops.stats import StandardScaler
from keystone_tpu.ops.util import (
    Cacher,
    ClassLabelIndicatorsFromIntLabels,
    MaxClassifier,
)
from keystone_tpu.utils.profiling import follow_profiler
from keystone_tpu.workflow import Pipeline

logger = logging.getLogger("keystone_tpu.pipelines.cifar")

NUM_CLASSES = 10


@dataclass
class CifarConfig:
    train_location: str = ""
    test_location: str = ""
    num_filters: int = 100
    whitener_size: int = 1000  # patches sampled for the ZCA fit
    patch_size: int = 6
    pool_size: int = 10
    pool_stride: int = 9
    alpha: float = 0.25
    lam: float = 10.0
    # Kernel variant (RandomPatchCifarKernel.scala:33-76)
    kernel_gamma: float = 5e-4
    block_size: int = 512
    num_epochs: int = 1
    # Preemption-safe KRR fits: segment the fused sweep and persist
    # (position, stack) here; a rerun with the same config+data resumes.
    checkpoint_path: str = ""
    checkpoint_every_blocks: int = 25
    # Augmented variant (RandomPatchCifarAugmented.scala:27-90).
    # horizontal_flips=None auto-selects: flips on real data (the reference
    # behavior) and off for the synthetic demo, whose phase-sensitive
    # sinusoid classes are not flip-invariant like real photos.
    augment_patch_size: int = 24
    augment_patches: int = 8
    horizontal_flips: "bool | None" = None
    seed: int = 0
    synthetic_n: int = 512


def _load(config: CifarConfig):
    """Returns (train, test, is_synthetic) — the one place that decides the
    data source, so policies keyed on it (flip augmentation) cannot drift."""
    if config.train_location:
        train = load_cifar_binary(config.train_location)
        test = load_cifar_binary(config.test_location)
        return train, test, False
    train = synthetic_cifar(config.synthetic_n, seed=config.seed)
    test = synthetic_cifar(max(config.synthetic_n // 4, 128), seed=config.seed + 1)
    return train, test, True


PATCH_VAR_CONSTANT = 10.0  # Stats.normalizeRows(_, 10.0) in the Scala pipeline
ZCA_EPS = 0.1


@functools.partial(jax.jit, static_argnames=("n", "seed", "count", "num_filters",
                                             "patch_size"))
def _draw_whitened_filters(images, *, n: int, seed: int, count: int, num_filters: int,
                           patch_size: int):
    """The filter draw as one program (scope ``ks.patch_whiten``): patch
    positions from ``jax.random.key(seed)``, only those patches gathered, rows normalized,
    ZCA fitted on them, the first ``num_filters`` whitened, renormalized
    and mapped back through the whitener. Returns (filters, whitener
    matrix, whitener means)."""
    with jax.named_scope("ks.patch_whiten"):
        _, X, Y, _ = images.shape
        k_img, k_pos = jax.random.split(jax.random.key(seed))
        img = jax.random.randint(k_img, (count,), 0, n)
        sx, sy = patch_positions(k_pos, (count,), X, Y, patch_size, patch_size)
        patches = gather_patches(images, img, sx, sy, patch_size, patch_size)
        patches = normalize_patch_rows(
            patches.reshape(count, -1).astype(jnp.float32), PATCH_VAR_CONSTANT)
        zca = ZCAWhitenerEstimator(eps=ZCA_EPS).fit_single(patches)
        sampled = zca.apply(patches[:num_filters])
        norms = jnp.sqrt(jnp.sum(sampled * sampled, axis=1, keepdims=True))
        return (sampled / (norms + 1e-10)) @ zca.whitener.T, zca.whitener, zca.means


def sample_whitened_filters(images: Dataset, config: CifarConfig):
    """Random training patches, row-normalized, ZCA-whitened, subsampled to a
    conv filter bank (RandomPatchCifar.scala:36-58), on the device: returns
    ``(filters (num_filters, patch²·C), ZCAWhitener)``.

    THE draw contract (a reference restates it): ``k_img, k_pos =
    jax.random.split(jax.random.key(config.seed))``; ``whitener_size``
    patches, patch i from image ``randint(k_img, (whitener_size,), 0, n)[i]``
    at ``patch_positions(k_pos, (whitener_size,), ...)``; each row minus its
    mean over ``sqrt(var + 10)`` (variance over d - 1); ZCA with ε = 0.1 on
    those rows; the filters are the FIRST ``num_filters`` rows whitened,
    each over its norm + 1e-10, times the whitener's transpose (the Scala
    pipeline samples its filter rows at random from the whitener's sample,
    which is itself a uniform draw). Only the drawn patches are read."""
    if config.whitener_size < config.num_filters:
        raise ValueError(f"whitener_size {config.whitener_size} < num_filters "
                         f"{config.num_filters}: the filters are rows of its sample")
    filters, W, means = _draw_whitened_filters(
        images.array, n=images.n, seed=config.seed, count=config.whitener_size, num_filters=config.num_filters,
        patch_size=config.patch_size)
    return filters, ZCAWhitener(W, means)


def pooled_features(config: CifarConfig, image_size: int = 32) -> int:
    """Features a ``image_size``² image gives: filters × 2 (the two-sided
    rectifier) × the pools ``Pooler(pool_stride, pool_size)`` lays on the
    conv map in each axis."""
    side = image_size - config.patch_size + 1
    pools = -(-(side - config.pool_size // 2) // config.pool_stride)
    return config.num_filters * 2 * pools * pools


def _convolver(filters, whitener, image_size: int = 32) -> Convolver:
    return Convolver(
        jnp.asarray(filters, jnp.float32).reshape(len(filters), -1),
        img_x=image_size,
        img_y=image_size,
        img_channels=3,
        whitener=whitener,
        normalize_patches=True,
        var_constant=PATCH_VAR_CONSTANT,
    )


def _conv_members(conv: Convolver, config: CifarConfig) -> list:
    """Convolver → SymmetricRectifier → Pooler(sum) → vectorize."""
    return [conv, SymmetricRectifier(alpha=config.alpha),
            Pooler(config.pool_stride, config.pool_size, pool_function="sum"),
            ImageVectorizer()]


def _conv_featurizer(conv: Convolver, config: CifarConfig) -> Pipeline:
    """:func:`_conv_members` as one chain, its features cached."""
    pipeline = conv.to_pipeline()
    for member in _conv_members(conv, config)[1:]:
        pipeline = pipeline.and_then(member)
    return pipeline.and_then(Cacher())


def build_random_patch(config: CifarConfig, images: Dataset, labels: Dataset,
                       lam: Optional[float] = None) -> Pipeline:
    """RandomPatchCifar's fit graph over arrays on the device
    (RandomPatchCifar.scala:21-86): the filters drawn from ``images``
    (:func:`sample_whitened_filters`, every build draws them, as every
    Scala run does), the conv featurizer, ``StandardScaler``, and
    ``BlockLeastSquaresEstimator(block_size, 1, λ)`` — the scores, no
    classifier. ``labels``: the ±1 class indicators. ``lam``: the
    config's where None."""
    follow_profiler()
    with obs.span("pipeline.build", entry="random_patch",
                  filters=config.num_filters,
                  patches_sampled=config.whitener_size) as span:
        filters, whitener = sample_whitened_filters(images, config)
        image_size = int(images.array.shape[1])
        conv = _convolver(filters, whitener, image_size)
        # the form the fused featurize program takes for these members (the
        # rule fusion applies), and the images it takes at a time: a grid
        # step of the kernel, or a step of the XLA program's loop
        form = conv_form(_conv_members(conv, config))
        if form == "pallas_pool":
            from keystone_tpu.ops import pallas_images

            image_batch = pallas_images.LANES
        else:
            image_batch = conv.device_row_batch()
        span.set(features=pooled_features(config, image_size), conv_form=form,
                 image_batch=image_batch)
        return _conv_featurizer(conv, config).and_then(StandardScaler(), images).and_then(
            BlockLeastSquaresEstimator(
                config.block_size, 1, config.lam if lam is None else lam),
            images, labels)


def run_linear_pixels(config: CifarConfig):
    """GrayScaler → vectorize → exact least squares → argmax
    (LinearPixels.scala:18-56)."""
    start = time.time()
    train, test, _ = _load(config)
    labels = ClassLabelIndicatorsFromIntLabels(NUM_CLASSES)(train.labels)
    pipeline = (
        PixelScaler()
        .to_pipeline()
        .and_then(GrayScaler())
        .and_then(ImageVectorizer())
        .and_then(LinearMapEstimator(lam=None), train.data, labels)
        .and_then(MaxClassifier())
    )
    evaluator = MulticlassClassifierEvaluator(NUM_CLASSES)
    train_eval = evaluator.evaluate(pipeline.apply(train.data), train.labels)
    test_eval = evaluator.evaluate(pipeline.apply(test.data), test.labels)
    logger.info(
        "LinearPixels train %.2f%% test %.2f%% (%.1fs)",
        100 * train_eval.total_error,
        100 * test_eval.total_error,
        time.time() - start,
    )
    return pipeline, train_eval, test_eval


def run_random_cifar(config: CifarConfig):
    """Random (unwhitened) gaussian filters (RandomCifar.scala:20-77)."""
    start = time.time()
    train, test, _ = _load(config)
    rng = np.random.default_rng(config.seed)
    filters = rng.normal(
        size=(config.num_filters, config.patch_size, config.patch_size, 3)
    )
    filters /= np.linalg.norm(filters.reshape(config.num_filters, -1), axis=1)[
        :, None, None, None
    ]
    labels = ClassLabelIndicatorsFromIntLabels(NUM_CLASSES)(train.labels)
    pipeline = (
        _conv_featurizer(_convolver(filters, None), config)
        .and_then(StandardScaler(), train.data)
        .and_then(
            BlockLeastSquaresEstimator(config.block_size, 1, config.lam),
            train.data,
            labels,
        )
        .and_then(MaxClassifier())
    )
    evaluator = MulticlassClassifierEvaluator(NUM_CLASSES)
    train_eval = evaluator.evaluate(pipeline.apply(train.data), train.labels)
    test_eval = evaluator.evaluate(pipeline.apply(test.data), test.labels)
    logger.info(
        "RandomCifar train %.2f%% test %.2f%% (%.1fs)",
        100 * train_eval.total_error,
        100 * test_eval.total_error,
        time.time() - start,
    )
    return pipeline, train_eval, test_eval


def _on_device(data: Dataset) -> Dataset:
    """Loaded images as one float32 array on the device: moved once, read
    by the filter draw, the featurizer and the scaler."""
    return Dataset(jnp.asarray(data.array, jnp.float32), n=data.n)


def run_random_patch_cifar(config: CifarConfig):
    """Whitened random-patch filters + block least squares
    (RandomPatchCifar.scala:21-86): :func:`build_random_patch` and a
    classifier on its scores."""
    start = time.time()
    train, test, _ = _load(config)
    images = _on_device(train.data)
    labels = ClassLabelIndicatorsFromIntLabels(NUM_CLASSES)(train.labels)
    pipeline = build_random_patch(config, images, labels).and_then(MaxClassifier())
    evaluator = MulticlassClassifierEvaluator(NUM_CLASSES)
    train_eval = evaluator.evaluate(pipeline.apply(images), train.labels)
    test_eval = evaluator.evaluate(pipeline.apply(test.data), test.labels)
    logger.info(
        "RandomPatchCifar train %.2f%% test %.2f%% (%.1fs)",
        100 * train_eval.total_error,
        100 * test_eval.total_error,
        time.time() - start,
    )
    return pipeline, train_eval, test_eval


def run_random_patch_cifar_kernel(config: CifarConfig):
    """Same featurization, Gaussian-kernel ridge regression solver
    (RandomPatchCifarKernel.scala:33-76)."""
    start = time.time()
    train, test, _ = _load(config)
    images = _on_device(train.data)
    filters, whitener = sample_whitened_filters(images, config)
    labels = ClassLabelIndicatorsFromIntLabels(NUM_CLASSES)(train.labels)
    featurizer = _conv_featurizer(_convolver(filters, whitener), config).and_then(
        StandardScaler(), images
    )
    pipeline = featurizer.and_then(
        KernelRidgeRegression(
            GaussianKernelGenerator(config.kernel_gamma),
            config.lam,
            config.block_size,
            config.num_epochs,
            checkpoint_path=config.checkpoint_path or None,
            checkpoint_every_blocks=config.checkpoint_every_blocks,
        ),
        images,
        labels,
    ).and_then(MaxClassifier())
    evaluator = MulticlassClassifierEvaluator(NUM_CLASSES)
    train_eval = evaluator.evaluate(pipeline.apply(images), train.labels)
    test_eval = evaluator.evaluate(pipeline.apply(test.data), test.labels)
    logger.info(
        "RandomPatchCifarKernel train %.2f%% test %.2f%% (%.1fs)",
        100 * train_eval.total_error,
        100 * test_eval.total_error,
        time.time() - start,
    )
    return pipeline, train_eval, test_eval


def run_random_patch_cifar_augmented(config: CifarConfig):
    """Random train crops; center/corner test crops (plus horizontal flips
    per ``config.horizontal_flips``) voted per image
    (RandomPatchCifarAugmented.scala:27-90)."""
    start = time.time()
    train, test, is_synthetic = _load(config)

    aug = config.augment_patch_size
    train_patcher = RandomPatcher(config.augment_patches, aug, aug, seed=config.seed)
    flips = config.horizontal_flips
    if flips is None:
        flips = not is_synthetic  # see CifarConfig comment
    test_patcher = CenterCornerPatcher(aug, aug, horizontal_flips=flips)

    train_images = train_patcher.batch_apply(train.data)
    train_label_ints = np.repeat(
        np.asarray(train.labels.array)[: train.labels.n], config.augment_patches
    )
    test_images = test_patcher.batch_apply(test.data)
    n_test = test.labels.n
    per_image = test_patcher.patches_per_image
    test_names = list(np.repeat(np.arange(n_test), per_image))

    filters, whitener = sample_whitened_filters(train_images, config)
    labels = ClassLabelIndicatorsFromIntLabels(NUM_CLASSES)(
        Dataset.of(train_label_ints)
    )
    featurizer = _conv_featurizer(_convolver(filters, whitener, aug), config).and_then(
        StandardScaler(), train_images
    )
    # Keep raw scores (no MaxClassifier) so the evaluator can vote.
    pipeline = featurizer.and_then(
        BlockLeastSquaresEstimator(config.block_size, 1, config.lam),
        train_images,
        labels,
    )
    evaluator = AugmentedExamplesEvaluator(test_names, NUM_CLASSES)
    # Labels align with the augmented copies (one per patch).
    test_label_copies = np.repeat(
        np.asarray(test.labels.array)[:n_test], per_image
    )
    test_eval = evaluator.evaluate(
        pipeline.apply(test_images), Dataset.of(test_label_copies)
    )
    logger.info(
        "RandomPatchCifarAugmented test %.2f%% (%.1fs)",
        100 * test_eval.total_error,
        time.time() - start,
    )
    return pipeline, test_eval


RUNNERS = {
    "LinearPixels": run_linear_pixels,
    "RandomCifar": run_random_cifar,
    "RandomPatchCifar": run_random_patch_cifar,
    "RandomPatchCifarKernel": run_random_patch_cifar_kernel,
    "RandomPatchCifarAugmented": run_random_patch_cifar_augmented,
}


def main(argv=None, variant: str = "RandomPatchCifar"):
    parser = argparse.ArgumentParser(f"Cifar:{variant}")
    parser.add_argument("--trainLocation", default="")
    parser.add_argument("--testLocation", default="")
    parser.add_argument("--numFilters", type=int, default=100)
    parser.add_argument("--whitenerSize", type=int, default=1000)
    parser.add_argument("--patchSize", type=int, default=6)
    parser.add_argument("--poolSize", type=int, default=10)
    parser.add_argument("--poolStride", type=int, default=9)
    parser.add_argument("--alpha", type=float, default=0.25)
    parser.add_argument("--lambda", dest="lam", type=float, default=10.0)
    parser.add_argument("--gamma", type=float, default=5e-4)
    parser.add_argument("--blockSize", type=int, default=512)
    parser.add_argument("--numEpochs", type=int, default=1)
    parser.add_argument(
        "--checkpointPath", default="",
        help="kernel variant: mid-solver checkpoint/resume file",
    )
    parser.add_argument(
        "--checkpointEveryBlocks", type=int, default=25,
        help="kernel variant: block updates between checkpoint saves",
    )
    parser.add_argument(
        "--horizontalFlips", choices=["auto", "on", "off"], default="auto",
        help="augmented variant's test-crop flips (auto: on for real data)",
    )
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args(argv)
    logging.basicConfig(level=logging.INFO)
    config = CifarConfig(
        train_location=args.trainLocation,
        test_location=args.testLocation,
        num_filters=args.numFilters,
        whitener_size=args.whitenerSize,
        patch_size=args.patchSize,
        pool_size=args.poolSize,
        pool_stride=args.poolStride,
        alpha=args.alpha,
        lam=args.lam,
        kernel_gamma=args.gamma,
        block_size=args.blockSize,
        num_epochs=args.numEpochs,
        checkpoint_path=args.checkpointPath,
        checkpoint_every_blocks=args.checkpointEveryBlocks,
        horizontal_flips={"auto": None, "on": True, "off": False}[args.horizontalFlips],
        seed=args.seed,
    )
    results = RUNNERS[variant](config)
    test_eval = results[-1]
    print(f"TEST Error is {100 * test_eval.total_error:.2f}%")


if __name__ == "__main__":
    main()
