"""RandomPatchCifar built over device arrays (``pipelines/cifar.py``'s
``build_random_patch``, which ``run_random_patch_cifar`` and the
benchmark's image cell both call) at a small size on the CPU: its scores
against the plain reference, the filter draw the reference restates, a fit
that moves nothing between host and device, a sweep that traces nothing
again, the programs' name scopes, what the build and the featurize record,
and the pieces under it — the row-batched featurize program, the narrower
last block of the fused block solve, the keyed patcher and the ZCA's
covariance form."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.drivers import fit_loop
from benchmarks.drivers import image_fit_loop as driver
from benchmarks.reference import cifar_patch as reference
from keystone_tpu import obs
from keystone_tpu.data import Dataset
from keystone_tpu.pipelines import cifar
from keystone_tpu.workflow import fusion

CONFIG = {"image_size": 32, "channels": 3, "num_classes": 10, "num_filters": 16,
          "patch_size": 6, "whitener_size": 256, "whitener_eps": 0.1,
          "patch_var_constant": 10.0, "pool_size": 14, "pool_stride": 13, "alpha": 0.25,
          "block_size": 64, "num_epochs": 1, "filter_seed": 7}
IMAGES, PROBE = 256, 64


@pytest.fixture(autouse=True)
def float32_mode():
    """The suite's conftest turns 64-bit mode on; the pipeline runs as its
    users run it, without it."""
    with jax.enable_x64(False):
        yield


@pytest.fixture(scope="module")
def toy():
    with jax.enable_x64(False):
        kp, ki, kq = jax.random.split(jax.random.key(11), 3)
        (images, Y), (probe, _) = driver.make_images(kp, [ki, kq], [IMAGES, PROBE], CONFIG)
        return images, Y, probe


def _fit(config, lam, images, Y):
    return driver.fit_at(config, lam, images, Y)


@pytest.mark.parametrize("block", [64, 48])  # 128 features: two blocks, or two and a narrower one
def test_scores_match_the_plain_reference(toy, block):
    images, Y, probe = toy
    config = dict(CONFIG, block_size=block)
    for lam in (1.0, 100.0):
        got = np.asarray(_fit(config, lam, images, Y).apply(Dataset(probe)).array)
        want = reference.fit_and_score(images, Y, probe, [lam], config=config)[lam]
        fro, widest = reference.score_gaps(got, want)
        assert fro < 1e-5 and widest < 5e-5, (lam, fro, widest)


def test_the_filter_draw_is_the_one_the_reference_restates(toy):
    images = toy[0]
    cfg = driver.cifar_config(CONFIG, 1.0)
    filters, whitener = cifar.sample_whitened_filters(Dataset(images), cfg)
    draw = reference.patch_draw(CONFIG["filter_seed"], CONFIG["whitener_size"], IMAGES, 32, 6)
    want, W, means = reference.whitened_filters(
        images, draw, count=CONFIG["whitener_size"], filters=CONFIG["num_filters"], patch=6,
        var_constant=10.0, eps=0.1)
    assert filters.shape == (16, 108)
    np.testing.assert_allclose(np.asarray(whitener.means), np.asarray(means), atol=1e-6)
    np.testing.assert_allclose(np.asarray(whitener.whitener), np.asarray(W), atol=2e-5)
    np.testing.assert_allclose(np.asarray(filters), np.asarray(want), atol=2e-5)
    # another seed is another draw
    other, _ = cifar.sample_whitened_filters(Dataset(images), driver.cifar_config(
        dict(CONFIG, filter_seed=8), 1.0))
    assert not np.allclose(np.asarray(other), np.asarray(filters), atol=1e-2)


def test_a_fit_moves_nothing_between_host_and_device(toy):
    images, Y, probe = toy
    _fit(CONFIG, 3.0, images, Y)  # programs compiled outside the guard
    lam = jax.device_put(np.float32(5.0))
    with jax.transfer_guard("disallow"):
        fitted = driver.fit_once(CONFIG, lam, images, Y)
    got = np.asarray(fitted.apply(Dataset(probe)).array)
    want = reference.fit_and_score(images, Y, probe, [5.0], config=CONFIG)[5.0]
    assert reference.score_gaps(got, want)[0] < 1e-5


def test_a_sweep_of_new_pipelines_traces_nothing_after_its_first_fit(toy):
    images, Y, _ = toy
    compiles = []
    for lam in (2.0, 20.0, 200.0):
        with obs.tracing() as tracer:
            _fit(CONFIG, lam, images, Y)
        compiles.append(len(tracer.spans("jax.compile")))
    assert compiles[1:] == [0, 0]


def test_the_programs_carry_their_scopes(toy):
    images, Y, _ = toy
    fitted = _fit(CONFIG, 1.0, images, Y)
    fused = next(op for op in fit_loop.walk(fitted)
                 if isinstance(op, fusion.FusedBatchTransformer))
    (_, params) = fused._operands
    text = fused._program.lower(params, images).as_text(debug_info=True)
    assert "ks.conv_featurize" in text and "ks.featurize" not in text
    text = cifar._draw_whitened_filters.lower(
        images, n=IMAGES, seed=7, count=256, num_filters=16, patch_size=6).as_text(debug_info=True)
    assert "ks.patch_whiten" in text


def test_the_build_says_what_it_built_and_the_featurize_counts_its_images(toy):
    images, Y, _ = toy
    with obs.tracing() as tracer:
        _fit(CONFIG, 1.0, images, Y)
        _fit(CONFIG, 2.0, images, Y)
    builds = [s["args"] for s in tracer.spans("pipeline.build")]
    assert len(builds) == 2
    assert builds[0] == {"entry": "random_patch", "filters": 16, "patches_sampled": 256,
                         "features": 128, "image_batch": builds[0]["image_batch"],
                         "conv_form": "xla"}  # the Pallas kernels are off on the CPU
    assert builds[0]["image_batch"] >= IMAGES  # a toy set is one batch
    counted = [e["value"] for e in tracer.events
               if e.get("type") == "counter" and e["name"] == "conv.images_featurized"]
    assert counted == [IMAGES, IMAGES]  # one sample a fit


def _operator_names(pipeline):
    graph = pipeline.executor.graph
    return [type(graph.operators[n]).__name__ for n in sorted(graph.operators, key=lambda n: n.id)]


def test_run_random_patch_cifar_builds_the_graph_the_benchmark_fits():
    cfg = cifar.CifarConfig(synthetic_n=64, num_filters=8, whitener_size=64, block_size=48,
                            pool_size=14, pool_stride=13)
    pipeline, train_eval, _ = cifar.run_random_patch_cifar(cfg)
    kp, ki = jax.random.split(jax.random.key(1))
    [(images, Y)] = driver.make_images(kp, [ki], [64], CONFIG)
    built = driver.build_pipeline(dict(CONFIG, num_filters=8, whitener_size=64, block_size=48),
                                  1.0, images, Y)
    names = _operator_names(pipeline)
    assert names[-1] == "MaxClassifier" and names[:-1] == _operator_names(built)
    assert names[:5] == ["Convolver", "SymmetricRectifier", "Pooler", "ImageVectorizer", "Cacher"]
    assert names[-3:-1] == ["BlockLeastSquaresEstimator", "DelegatingOperator"]
    assert "StandardScaler" in names
    assert train_eval.total_error < 0.5  # chance is 0.9


@pytest.mark.parametrize("rows", [None, 7, 16, 40, 64])
def test_a_row_local_chain_in_row_batches_equals_one_pass(rows):
    X = jax.random.normal(jax.random.key(2), (40, 5, 3))

    def chain(x):
        return jnp.tanh(x.reshape(x.shape[0], -1) @ jnp.ones((15, 4))) * 2.0

    got = jax.jit(lambda x: fusion.in_row_batches(chain, x, rows))(X)
    np.testing.assert_allclose(np.asarray(got), np.asarray(chain(X)), rtol=1e-6, atol=1e-6)


def test_the_convolution_asks_for_image_batches_by_its_memory(monkeypatch):
    from keystone_tpu.ops.images import conv as conv_module

    conv = conv_module.Convolver(jnp.zeros((1600, 108)), 32, 32, 3)
    assert conv.device_row_batch() == 152 == (
        conv_module.CONV_BATCH_BYTES // (3 * 27 * 27 * 1600 * 4) // 8 * 8)
    assert conv_module.Convolver.build(
        jnp.zeros((4, 6, 6, 3))).device_row_batch() is None  # size unknown
    monkeypatch.setattr(conv_module, "CONV_BATCH_BYTES", 1)
    assert conv.device_row_batch() == 8


def test_a_featurize_in_small_batches_equals_one_pass(toy, monkeypatch):
    from keystone_tpu.ops.images import conv as conv_module

    images = toy[0][:40]
    cfg = driver.cifar_config(CONFIG, 1.0)
    filters, whitener = cifar.sample_whitened_filters(Dataset(images), cfg)
    outs = []
    for batch_bytes in (1 << 40, 27 * 27 * 16 * 4 * 3 * 16):  # one batch; batches of 16
        monkeypatch.setattr(conv_module, "CONV_BATCH_BYTES", batch_bytes)
        featurizer = cifar._conv_featurizer(cifar._convolver(filters, whitener), cfg)
        outs.append(np.asarray(featurizer.apply(Dataset(images)).get().array))
    assert outs[0].shape == (40, 128)
    np.testing.assert_allclose(outs[1], outs[0], rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("epochs", [1, 2])
def test_a_narrower_last_block_rides_the_fused_sweep(epochs):
    from keystone_tpu.parallel import linalg

    key = jax.random.split(jax.random.key(3), 2)
    A = jax.random.normal(key[0], (200, 40))
    B = jax.random.normal(key[1], (200, 3))
    blocks = [A[:, 0:16], A[:, 16:32], A[:, 32:40]]
    W, W_t = linalg.bcd_least_squares_fused(
        jnp.stack(blocks[:2]), B, lam=0.5, num_iter=epochs, use_pallas=False, tail=blocks[2])
    want = linalg.bcd_least_squares(blocks, B, lam=0.5, num_iter=epochs)
    for got, ref in zip([W[0], W[1], W_t], want):
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-4, atol=1e-5)


def test_random_patcher_draws_its_positions_from_a_key():
    from keystone_tpu.ops.images.core import RandomPatcher, patch_positions

    images = jax.random.normal(jax.random.key(4), (3, 10, 9, 2))
    got = np.asarray(RandomPatcher(5, 4, 3, seed=21)._patches(images))
    sx, sy = patch_positions(jax.random.key(21), (3, 5), 10, 9, 4, 3)
    for i in range(3):
        for k in range(5):
            x, y = int(sx[i, k]), int(sy[i, k])
            np.testing.assert_array_equal(got[i, k], np.asarray(images[i, x:x + 4, y:y + 3]))
    assert got.shape == (3, 5, 4, 3, 2)


@pytest.mark.parametrize("rows", [300, 60])  # more rows than columns: the covariance form
def test_zca_from_the_covariance_is_the_svd_whitener(rows):
    from keystone_tpu.ops.learning.pca import ZCAWhitenerEstimator

    X = jax.random.normal(jax.random.key(5), (rows, 40)) @ jax.random.normal(
        jax.random.key(6), (40, 40))
    got = ZCAWhitenerEstimator(eps=0.1).fit_single(X)
    centred = X - X.mean(axis=0)
    _, s, vt = np.linalg.svd(np.asarray(centred, np.float64), full_matrices=False)
    want = vt.T @ np.diag((s * s / (rows - 1.0) + 0.1) ** -0.5) @ vt
    np.testing.assert_allclose(np.asarray(got.whitener), want, rtol=2e-3, atol=2e-4)
