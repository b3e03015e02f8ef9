"""Accuracy-parity artifact: error/loss columns next to wall-clock.

The reference's acceptance story is error numbers
(scripts/solver-comparisons-final.csv: TIMIT Block d=16384 -> train err
35.73%, loss 1.2658, csv:26; Amazon 11.4%). This script produces the
framework's error/loss evidence:

1. **Real data** (`mnist_randomfft_real_digits`): the MnistRandomFFT
   composition (gather of numFFTs x [RandomSign -> PaddedFFT ->
   LinearRectifier] -> VectorCombiner -> BlockLeastSquares -> MaxClassifier,
   MnistRandomFFT.scala:21-70) on the real UCI handwritten-digits dataset
   (1797 8x8 images, bundled with scikit-learn). Real MNIST/TIMIT downloads
   are impossible in this zero-egress environment and TIMIT is
   LDC-licensed; the digits set is the real handwritten-digit data
   available offline. Parity target: an *independent* float64 numpy exact
   ridge solve (same centering conventions) on the identical features —
   the BCD solver must reach the same train/test error.

2. **Solver loss parity at TIMIT geometry** (`timit_shaped_loss_parity`):
   CosineRandomFeatures(440 -> d) -> BlockLeastSquares at the csv:26
   hyperparameter shape (blockSize 4096 on TPU, 3 epochs) on TIMIT-shaped
   class-structured synthetic data, reporting the BCD ridge loss against
   the exact normal-equations optimum loss on the same features. A BCD/exact
   loss ratio ~1 at equal hyperparameters is the solver-parity claim the
   CSV row's 35.73%/1.2658 rests on; the real-TIMIT numbers themselves are
   not reproducible without the licensed data.

Prints ONE JSON document and writes PARITY_RESULTS.json.
"""

import json
import time

import numpy as np


def _exact_ridge_errors(F_train, Y_train, F_test, lam):
    """Independent float64 exact ridge with mean-centering (numpy only):
    returns (train_preds, test_preds)."""
    F = np.asarray(F_train, dtype=np.float64)
    Y = np.asarray(Y_train, dtype=np.float64)
    f_mean = F.mean(axis=0)
    y_mean = Y.mean(axis=0)
    Fc = F - f_mean
    G = Fc.T @ Fc + lam * np.eye(F.shape[1])
    W = np.linalg.solve(G, Fc.T @ (Y - y_mean))
    train_preds = (F - f_mean) @ W + y_mean
    test_preds = (np.asarray(F_test, np.float64) - f_mean) @ W + y_mean
    return train_preds, test_preds


def digits_parity(lam=1e-6):
    import jax

    from keystone_tpu.ops.util import ClassLabelIndicatorsFromIntLabels
    from keystone_tpu.pipelines import mnist_random_fft as mp

    # blockSize covers all 4x32 features — the README config's shape
    # (blockSize 2048 ≥ the 4-FFT feature width on MNIST), where the
    # single numIter=1 BCD pass is the full solve.
    config = mp.MnistRandomFFTConfig(
        num_ffts=4, block_size=128, lam=lam, image_size=64, use_digits=True
    )
    t0 = time.perf_counter()
    pipeline, train_eval, test_eval = mp.run(config)
    wall = time.perf_counter() - t0

    # Independent exact solve on the identical features.
    from keystone_tpu.data.loaders import load_digits_real

    train, test = load_digits_real(seed=config.seed)
    featurizer = mp.build_featurizer(config)
    F_train = np.asarray(featurizer.apply(train.data).get().array)
    F_test = np.asarray(featurizer.apply(test.data).get().array)
    Y = np.asarray(
        ClassLabelIndicatorsFromIntLabels(10)(train.labels).array
    )
    p_tr, p_te = _exact_ridge_errors(F_train, Y, F_test, lam)
    exact_train_err = float(
        (p_tr.argmax(1) != np.asarray(train.labels.array)).mean()
    )
    exact_test_err = float(
        (p_te.argmax(1) != np.asarray(test.labels.array)).mean()
    )
    return {
        "workload": "mnist_randomfft_real_digits",
        "data": "real UCI handwritten digits (sklearn load_digits, 1797x64)",
        "config": "numFFTs=4, blockSize=128 (covers all features, as README's 2048 does for MNIST), lam=%g" % lam,
        "train_err": round(float(train_eval.total_error), 4),
        "test_err": round(float(test_eval.total_error), 4),
        "exact_train_err": round(exact_train_err, 4),
        "exact_test_err": round(exact_test_err, 4),
        "wallclock_s": round(wall, 2),
        "wallclock_note": "dominated by per-FFT compile; not a perf claim (see bench.py)",
        "device": str(jax.devices()[0]),
    }


def timit_loss_parity():
    import jax
    import jax.numpy as jnp

    from keystone_tpu.data import Dataset
    from keystone_tpu.data.loaders import synthetic_classification
    from keystone_tpu.ops.learning.block import BlockLeastSquaresEstimator
    from keystone_tpu.ops.stats import CosineRandomFeatures

    on_tpu = jax.default_backend() == "tpu"
    # TPU: the csv:26 geometry (d=16384, bs=4096). CPU fallback is a scaled
    # shape so the artifact stays runnable anywhere.
    d = 16384 if on_tpu else 1024
    bs = 4096 if on_tpu else 256
    n = 65536 if on_tpu else 16384
    epochs = 3  # the baseline row's sweep count (constantEstimator.R:12)
    lam = 1e-4
    k = 147

    # TIMIT geometry with overlapping classes so the error columns are
    # non-degenerate (~tens of percent, like the CSV's 35.73%).
    data = synthetic_classification(n, 440, k, seed=0, class_sep=0.12)
    X = np.asarray(data.data.array, dtype=np.float32)
    labels = np.asarray(data.labels.array)
    Y = (2.0 * np.eye(k)[labels] - 1.0).astype(np.float32)

    rfs = [
        CosineRandomFeatures(440, bs, gamma=0.05, seed=i)
        for i in range(d // bs)
    ]
    Wrf = jnp.concatenate([rf.W for rf in rfs], axis=0)
    brf = jnp.concatenate([rf.b for rf in rfs])
    if on_tpu:
        # Fused Pallas matmul+cos with a bf16 feature layout — the bench's
        # recipe; the (n, d) f32 pre-activation would not fit in HBM.
        from keystone_tpu.ops import pallas_ops as po

        F = po.cosine_features(
            jnp.asarray(X), Wrf, brf,
            compute_dtype=jnp.bfloat16, out_dtype=jnp.bfloat16,
        )
    else:
        F = jnp.cos(jnp.asarray(X) @ Wrf.T + brf)
    feats = Dataset.of(F)
    labels_ds = Dataset.of(Y)

    # The SHIPPED estimator (per-block mean-centering + fused BCD sweep —
    # the semantics of mlmatrix solveLeastSquaresWithL2 behind
    # BlockLeastSquaresEstimator, BlockLinearMapper.scala:199-283).
    t0 = time.perf_counter()
    model = BlockLeastSquaresEstimator(bs, epochs, lam).fit(feats, labels_ds)
    preds = np.asarray(model.batch_apply(feats).array)
    wall = time.perf_counter() - t0
    # Loss convention of the CSV's "Loss" column: ||preds − Y||²/n.
    bcd_loss = float(np.sum((preds - Y) ** 2) / n)
    train_err = float((preds.argmax(1) != labels).mean())

    # Exact ridge optimum on the same centered features (f32 accumulation
    # regardless of the storage layout).
    from keystone_tpu.parallel import linalg

    Fc = F.astype(jnp.float32) - jnp.mean(F.astype(jnp.float32), axis=0)
    Yj = jnp.asarray(Y)
    Yc = Yj - jnp.mean(Yj, axis=0)
    W_exact = linalg.normal_equations_solve(Fc, Yc, lam)
    preds_exact = np.asarray(Fc @ W_exact + jnp.mean(Yj, axis=0))
    exact_loss = float(np.sum((preds_exact - Y) ** 2) / n)
    exact_err = float((preds_exact.argmax(1) != labels).mean())

    return {
        "workload": "timit_shaped_loss_parity",
        "data": "TIMIT-shaped synthetic (real TIMIT is LDC-licensed; zero-egress env)",
        "config": f"d={d}, blockSize={bs}, epochs={epochs}, lam={lam}, n={n}",
        "bcd_loss": round(bcd_loss, 6),
        "exact_loss": round(exact_loss, 6),
        "loss_ratio": round(bcd_loss / max(exact_loss, 1e-12), 6),
        "bcd_train_err": round(train_err, 4),
        "exact_train_err": round(exact_err, 4),
        "wallclock_s": round(wall, 2),
        "csv_reference": "TIMIT Block d=16384: err 35.73%, loss 1.2658 (csv:26) — real-data target, unreachable offline",
        "device": str(jax.devices()[0]),
    }


def voc_real_end_to_end():
    """Real-data VOC end-to-end: the full image stack (real JPEG decode →
    SIFT → PCA → GMM Fisher vectors → BlockLeastSquares → MAP) on the
    reference's committed voctest.tar (VOCSIFTFisher.scala:23-105,
    VOCLoaderSuite fixtures). With train == test == the 10 committed
    images, every class present in the data must rank perfectly."""
    import os

    import jax

    from keystone_tpu.pipelines.voc_sift_fisher import VOCConfig, run

    images = "/root/reference/src/test/resources/images"
    if not os.path.exists(os.path.join(images, "voc/voctest.tar")):
        return {
            "workload": "voc_sift_fisher_real_jpegs",
            "skipped": "reference voctest.tar fixture not available",
        }
    cfg = VOCConfig(
        train_location=os.path.join(images, "voc"),
        train_labels=os.path.join(images, "voclabels.csv"),
        test_location=os.path.join(images, "voc"),
        test_labels=os.path.join(images, "voclabels.csv"),
        descriptor_dim=32,
        vocab_size=4,
        sift_scale_step=2,
        lam=0.5,
    )
    t0 = time.perf_counter()
    _, aps, mean_ap = run(cfg)
    wall = time.perf_counter() - t0
    aps = np.asarray(aps)
    return {
        "workload": "voc_sift_fisher_real_jpegs",
        "data": "real VOC2007 sample (committed voctest.tar: 10 JPEGs, 9 distinct classes)",
        "config": "descDim=32, vocabSize=4, scaleStep=2, lam=0.5 (mini config; train==test)",
        "mean_average_precision": round(float(mean_ap), 4),
        "classes_with_perfect_ap": int((aps > 0.99).sum()),
        "classes_present_in_data": 9,
        "expectation": "all 9 present classes AP 1.0 -> MAP 9/20 = 0.45",
        "wallclock_s": round(wall, 2),
        "device": str(jax.devices()[0]),
    }


def imagenet_real_end_to_end():
    """Real-data ImageNetSiftLcsFV end-to-end: real JPEG decode → SIFT + LCS
    branches → PCA → GMM Fisher vectors → BlockWeightedLeastSquares → top-k
    (ImageNetSiftLcsFV.scala:33-135) on a two-synset dataset assembled from
    the committed archives: the real n15075141 synset (5 JPEGs) plus a
    second synset re-tarred from voctest.tar's 10 real VOC JPEGs (bytes
    unchanged; ImageNetLoader only reads the classdir/file layout). Two
    distinct photo sources -> a real two-class separation problem."""
    import os
    import tempfile

    import jax

    images = "/root/reference/src/test/resources/images"
    for need in ("imagenet/n15075141.tar", "voc/voctest.tar"):
        if not os.path.exists(os.path.join(images, need)):
            return {
                "workload": "imagenet_sift_lcs_fv_real_jpegs",
                "skipped": f"reference fixture {need} not available",
            }

    import pathlib
    import sys

    tests_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)), "tests")
    sys.path.insert(0, tests_dir)
    try:
        from test_imagenet_end_to_end_real import _build_two_synset_dir
    finally:
        sys.path.remove(tests_dir)

    from keystone_tpu.pipelines.imagenet_sift_lcs_fv import ImageNetConfig, run

    with tempfile.TemporaryDirectory() as tmp:
        data_dir, labels_path = _build_two_synset_dir(pathlib.Path(tmp))
        cfg = ImageNetConfig(
            train_location=data_dir, train_labels=labels_path,
            test_location=data_dir, test_labels=labels_path,
            num_classes=2, sift_pca_dim=32, lcs_pca_dim=32, vocab_size=4,
            block_size=1024, lam=1e-3,
        )
        t0 = time.perf_counter()
        _, top1_eval, top5_err = run(cfg)
        wall = time.perf_counter() - t0
    return {
        "workload": "imagenet_sift_lcs_fv_real_jpegs",
        "data": (
            "real JPEGs from the committed archives: n15075141.tar (5) + "
            "voctest.tar's 10 VOC photos as a second synset"
        ),
        "config": "pca 32/32, vocab 4, BWLS block 1024, lam 1e-3 (mini; train==test)",
        "top1_train_error": round(float(top1_eval.total_error), 4),
        "images_classified": int(np.asarray(top1_eval.confusion).sum()),
        "expectation": "both branches + BWLS separate the two photo sources (<=0.2)",
        "wallclock_s": round(wall, 2),
        "device": str(jax.devices()[0]),
    }


def cifar_shaped_parity():
    """RandomPatchCifar-shaped parity (RandomPatchCifar.scala:21-86): the
    conv → symmetric-rectify → sum-pool → StandardScaler featurization with
    whitened random-patch filters, then the shipped BCD solver versus an
    independent float64 exact ridge solve on the IDENTICAL features.
    Synthetic 32x32 images — the claim is featurizer/solver parity, not
    CIFAR accuracy (real CIFAR archives are unavailable offline)."""
    import jax

    from keystone_tpu.ops.stats import StandardScaler
    from keystone_tpu.ops.util import ClassLabelIndicatorsFromIntLabels
    from keystone_tpu.pipelines import cifar as cp

    config = cp.CifarConfig(synthetic_n=512, num_filters=64, lam=10.0)
    t0 = time.perf_counter()
    pipeline, train_eval, test_eval = cp.run_random_patch_cifar(config)
    wall = time.perf_counter() - t0

    # Rebuild the identical (seeded) featurization and solve exactly in f64.
    train, test, _ = cp._load(config)
    filters, whitener = cp._sample_whitened_filters(train, config)
    featurizer = cp._conv_featurizer(filters, whitener, config)
    train_feats = featurizer.apply(train.data).get()
    scaler = StandardScaler().fit(train_feats)
    F_train = np.asarray(scaler.batch_apply(train_feats).array)[: train.data.n]
    F_test = np.asarray(
        scaler.batch_apply(featurizer.apply(test.data).get()).array
    )[: test.data.n]
    Y = np.asarray(
        ClassLabelIndicatorsFromIntLabels(10)(train.labels).array
    )[: train.data.n]
    p_tr, p_te = _exact_ridge_errors(F_train, Y, F_test, config.lam)
    exact_train = float((p_tr.argmax(1) != np.asarray(train.labels.array)[: train.data.n]).mean())
    exact_test = float((p_te.argmax(1) != np.asarray(test.labels.array)[: test.data.n]).mean())
    # Per-example agreement with the exact solver (meaningful even when
    # both error columns are 0 on the separable synthetic classes).
    pipe_preds = np.asarray(pipeline.apply(test.data).get().array)[: test.data.n]
    agreement = float((pipe_preds.reshape(-1) == p_te.argmax(1)).mean())
    return {
        "workload": "randompatch_cifar_shaped_parity",
        "prediction_agreement_vs_exact": round(agreement, 4),
        "data": "CIFAR-shaped synthetic 32x32x3 (real CIFAR archive unavailable offline)",
        "config": "numFilters=64, patch=6, pool=10/9, alpha=0.25, lam=10, blockSize=512",
        "train_err": round(float(train_eval.total_error), 4),
        "test_err": round(float(test_eval.total_error), 4),
        "exact_train_err": round(exact_train, 4),
        "exact_test_err": round(exact_test, 4),
        "wallclock_s": round(wall, 2),
        "device": str(jax.devices()[0]),
    }


def amazon_shaped_parity():
    """Amazon-shaped sparse parity (solver-comparisons-final.csv:2-13
    geometry, subsampled): n >> d padded-COO text-like features through the
    never-densify SparseLBFGSwithL2 versus an independent float64 exact
    ridge solve of the same objective (½‖XW−Y‖²/n + ½λ‖W‖², intercept via
    the append-ones column, LBFGS.scala:208-281)."""
    import jax

    from keystone_tpu.data import Dataset
    from keystone_tpu.ops.learning.lbfgs import SparseLBFGSwithL2

    rng = np.random.default_rng(11)
    n, d, k, nnz = 30_000, 2_048, 2, 16  # ~0.8% density, n >> d
    lam = 1e-3
    # Class-dependent sparse features so the error column is non-degenerate.
    labels = rng.integers(0, k, size=n)
    cols = rng.integers(0, d, size=(n, nnz)).astype(np.int32)
    cols.sort(axis=1)
    signal = np.where(cols < d // 8, (2.0 * labels[:, None] - 1.0), 0.0)
    values = (rng.normal(size=(n, nnz)) + 1.5 * signal).astype(np.float32)
    Y = (2.0 * np.eye(k)[labels] - 1.0).astype(np.float32)

    ds = Dataset({"indices": cols, "values": values}, n=n)
    t0 = time.perf_counter()
    model = SparseLBFGSwithL2(
        lam=lam, num_iterations=60, num_features=d
    ).fit(ds, Dataset.of(Y))
    preds = np.asarray(model.batch_apply(ds).array)
    wall = time.perf_counter() - t0
    lbfgs_err = float((preds.argmax(1) != labels).mean())
    lbfgs_loss = float(0.5 * np.sum((preds - Y) ** 2) / n)

    # Independent f64 exact solve of the identical objective (dense is
    # feasible at this subsampled geometry: 30k x 2k).
    X = np.zeros((n, d + 1))
    np.add.at(X, (np.arange(n)[:, None], cols), values.astype(np.float64))
    X[:, d] = 1.0
    G = X.T @ X + n * lam * np.eye(d + 1)
    W1 = np.linalg.solve(G, X.T @ Y.astype(np.float64))
    p_exact = X @ W1
    exact_err = float((p_exact.argmax(1) != labels).mean())
    exact_loss = float(0.5 * np.sum((p_exact - Y) ** 2) / n)
    return {
        "workload": "amazon_shaped_sparse_parity",
        "data": "Amazon-geometry synthetic sparse COO (real reviews corpus unavailable offline)",
        "config": f"n={n}, d={d}, nnz/row={nnz} (~{nnz/d:.3%}), lam={lam}, iters=60, never-densify",
        "lbfgs_err": round(lbfgs_err, 4),
        "exact_err": round(exact_err, 4),
        "lbfgs_loss": round(lbfgs_loss, 6),
        "exact_loss": round(exact_loss, 6),
        "loss_ratio": round(lbfgs_loss / max(exact_loss, 1e-12), 6),
        "csv_reference": "Amazon LBFGS d=16384: err 11.4%, 52.29s @ 16 nodes (csv:13) — real-data target, unreachable offline",
        "wallclock_s": round(wall, 2),
        "device": str(jax.devices()[0]),
    }


def main():
    from keystone_tpu.utils.startup import enable_compile_cache

    enable_compile_cache()
    results = {
        "rows": [
            digits_parity(),
            timit_loss_parity(),
            voc_real_end_to_end(),
            imagenet_real_end_to_end(),
            cifar_shaped_parity(),
            amazon_shaped_parity(),
        ],
        "note": (
            "Parity evidence: the BCD solver reaches the independent exact "
            "solver's error on real data at equal hyperparameters, its "
            "ridge loss matches the exact optimum at the reference's TIMIT "
            "geometry, the full real-JPEG image stack ranks the committed "
            "VOC sample perfectly (and the two-branch SIFT+LCS ImageNet "
            "pipeline separates the two committed photo sources), and the "
            "CIFAR-shaped conv stack and "
            "Amazon-shaped sparse LBFGS match independent float64 exact "
            "solves. The CSV's absolute error targets require the licensed "
            "TIMIT/ImageNet data, unavailable in this environment. "
            "Wallclocks labeled by device; CPU rows are test-env numbers, "
            "not perf claims (see bench.py for TPU perf)."
        ),
    }
    out = json.dumps(results, indent=2)
    print(out)
    with open("PARITY_RESULTS.json", "w") as f:
        f.write(out + "\n")


if __name__ == "__main__":
    main()
