"""The convolution featurize as one Pallas kernel (``pallas_images.
conv_pool_features``, run for a fused chain by ``conv.PooledConvolution``):
the kernel in interpret mode against the XLA chain it replaces, the order of
its features and of its patch columns, where the fused program takes it and
where it keeps the XLA program, the kernel over a mesh, the counter and the
build's ``conv_form``, the kept keys and lowered text of chains and gathers
with no convolution, and the featurize program compiled for a described v5e
at the image cell's shape."""

import functools
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from keystone_tpu import obs
from keystone_tpu.data import Dataset
from keystone_tpu.ops import pallas_images, pallas_ops
from keystone_tpu.ops.images.conv import (
    Convolver,
    PooledConvolution,
    Pooler,
    SymmetricRectifier,
    conv_form,
)
from keystone_tpu.ops.images.core import ImageVectorizer
from keystone_tpu.ops.learning.pca import ZCAWhitener
from keystone_tpu.workflow import fusion

rng = np.random.default_rng(42)

# (stride, pool_size) on a 27 x 27 map: the image cell's overlapping 2 x 2
# pools (they share row and column 13) and CifarConfig's 3 x 3
POOLS = {"cell_2x2": (13, 14), "config_3x3": (9, 10)}


@pytest.fixture(autouse=True)
def float32_mode():
    """The suite's conftest turns 64-bit mode on; the kernel runs as users
    run it, without it."""
    with jax.enable_x64(False):
        yield


@pytest.fixture
def kernels_on(monkeypatch):
    """The Pallas kernels on, as on the chip (here in the interpreter)."""
    monkeypatch.setenv("KEYSTONE_PALLAS", "1")
    monkeypatch.delenv("KEYSTONE_NO_PALLAS", raising=False)


def _images(n, side=32):
    return rng.uniform(0.0, 255.0, size=(n, side, side, 3)).astype(np.float32)


def _members(k, pool, whiten=True, normalize=True, vectorize=True, pooler=None, rectify=True):
    filters = rng.normal(size=(k, 108)).astype(np.float32)
    whitener = (ZCAWhitener(jnp.eye(108), jnp.asarray(rng.normal(size=108), jnp.float32))
                if whiten else None)
    conv = Convolver(filters, 32, 32, 3, whitener=whitener, normalize_patches=normalize)
    stride, size = POOLS[pool]
    members = [conv] + ([SymmetricRectifier(alpha=0.25)] if rectify else [])
    members.append(pooler or Pooler(stride, size, pool_function="sum"))
    return members + ([ImageVectorizer()] if vectorize else [])


def _xla_chain(members, X):
    """What the members compute one after the other, each its own XLA form."""
    for m in members:
        static_key, params = m.device_operands()
        X = type(m).device_apply(static_key, params, X)
    return np.asarray(X)


def _rel(got, want):
    return float(np.max(np.abs(np.asarray(got) - want)) / np.max(np.abs(want)))


@pytest.mark.parametrize("k,pool,whiten,normalize", list(itertools.product(
    [16, 136, 256], sorted(POOLS), [True, False], [True, False])))
def test_kernel_equals_the_xla_chain(k, pool, whiten, normalize):
    members = _members(k, pool, whiten, normalize)
    conv = members[0]
    stride, size = POOLS[pool]
    X = _images(3)
    got = pallas_images.conv_pool_features(
        X, conv.filters, None if conv.whitener is None else conv.whitener.means,
        patch_size=6, stride=stride, pool_size=size, normalize_patches=normalize,
        var_constant=10.0, alpha=0.25, interpret=True)
    assert got.shape == (3, 2 * k * (4 if pool == "cell_2x2" else 9))
    assert _rel(got, _xla_chain(members, X)) < 1e-5


def test_a_short_last_grid_step_equals_the_xla_chain():
    """130 images: a grid step of 128, then one of 2."""
    members = _members(16, "cell_2x2")
    X = _images(130)
    fused = PooledConvolution(members[0], members[1], members[2], vectorize=True)
    static_key, params = fused.device_operands()
    with pallas_ops.record_dispatches() as dispatched:
        got = PooledConvolution.device_apply(static_key, params, X)
    assert dispatched == [("conv_pool", True)]
    assert got.shape == (130, 128) and _rel(got, _xla_chain(members, X)) < 1e-5


def test_feature_order_is_image_vectorizers():
    """Feature ``(px·npy + py)·2k + s·k + c``: pool-major, then the
    rectifier's positive half before its negative half, then the filter."""
    k = 16
    members = _members(k, "config_3x3", vectorize=False)
    X = _images(2)
    pooled = _xla_chain(members, X)  # (n, 3, 3, 2k), the Pooler's own output
    fused = PooledConvolution(members[0], members[1], members[2], vectorize=True)
    got = np.asarray(PooledConvolution.device_apply(*fused.device_operands(), X))
    for px, py, s, c in itertools.product(range(3), range(3), range(2), [0, 7, k - 1]):
        np.testing.assert_allclose(got[:, (px * 3 + py) * 2 * k + s * k + c],
                                   pooled[:, px, py, s * k + c], rtol=1e-5)
    unvectorized = PooledConvolution(members[0], members[1], members[2], vectorize=False)
    assert np.asarray(PooledConvolution.device_apply(
        *unvectorized.device_operands(), X)).shape == pooled.shape


def test_images_the_kernel_has_no_plan_for_raise():
    """The fused program takes the kernel only for the images the
    convolution declares (``PooledConvolution.absorbing``); other images
    are an error, not a second path."""
    members = _members(8, "cell_2x2")
    X = _images(2, side=200)  # a row of windows would not fit the VMEM plan
    assert pallas_images.conv_pool_plan((200, 200, 3), 8, 6, 13, 14) is None
    fused = PooledConvolution(members[0], members[1], members[2], vectorize=True)
    with pytest.raises(ValueError, match="no kernel plan"):
        PooledConvolution.device_apply(*fused.device_operands(), X)


@pytest.mark.parametrize("pool,patch", [("cell_2x2", 6), ("cell_2x2", 3),
                                        ("config_3x3", 6), ("config_3x3", 3)])
def test_patch_columns_are_px_py_c_row_major(pool, patch):
    """One-hot filters read the patch columns back out: filter j selects
    the pixel ``(x + px, y + py, c)`` of window ``(x, y)`` with ``j =
    (px·p + py)·C + c`` — the ``pack_filters`` contract — so with no
    normalisation, no rectifier offset and positive pixels each positive
    feature is the sum of that pixel over the pool's windows, worked out
    here in numpy."""
    stride, size = POOLS[pool]
    d = patch * patch * 3
    k = -(-d // 8) * 8
    filters = np.eye(k, d, dtype=np.float32)
    X = _images(2)
    got = np.asarray(pallas_images.conv_pool_features(
        X, filters, patch_size=patch, stride=stride, pool_size=size,
        normalize_patches=False, interpret=True))
    side = 32 - patch + 1
    spans = [(q * stride, min(q * stride + size, side))
             for q in range(-(-(side - size // 2) // stride))]
    assert got.shape == (2, len(spans) ** 2 * 2 * k)
    for (qx, (x0, x1)), (qy, (y0, y1)) in itertools.product(enumerate(spans), repeat=2):
        base = (qx * len(spans) + qy) * 2 * k
        for px, py, c in itertools.product(range(patch), range(patch), range(3)):
            want = X[:, x0 + px:x1 + px, y0 + py:y1 + py, c].sum(axis=(1, 2))
            np.testing.assert_allclose(got[:, base + (px * patch + py) * 3 + c], want,
                                       rtol=1e-5)
        assert not np.any(got[:, base + k:base + 2 * k])  # no negative pixel


def test_batches_featurize_as_the_whole():
    """Features of images taken a few at a time — a ragged last batch —
    equal the features of all of them at once, and so do the Gramians a
    fold would sum from them."""
    members = _members(16, "config_3x3")
    conv = members[0]
    X = _images(10)
    features = functools.partial(
        pallas_images.conv_pool_features, filters=conv.filters,
        means=conv.whitener.means, patch_size=6, stride=9, pool_size=10,
        alpha=0.25, interpret=True)
    whole = np.asarray(features(X))
    parts = [np.asarray(features(X[lo:lo + 3])) for lo in range(0, 10, 3)]
    np.testing.assert_allclose(np.concatenate(parts), whole, rtol=1e-6)
    np.testing.assert_allclose(sum(p.T @ p for p in parts), whole.T @ whole, rtol=1e-4)


@pytest.mark.parametrize("taken", [True, False], ids=["vectorized", "pools_out"])
def test_the_fused_chain_runs_the_kernel_and_counts_its_images(kernels_on, taken):
    members = _members(16, "cell_2x2", vectorize=taken)
    assert conv_form(members) == "pallas_pool"
    fused = fusion.FusedBatchTransformer(members)
    X = _images(6)
    with obs.tracing() as tracer, pallas_ops.record_dispatches() as dispatched:
        got = fused.batch_apply(Dataset(jnp.asarray(X))).array
    assert [name for name, _ in dispatched] == ["conv_pool"]
    assert _rel(got, _xla_chain(members, X)) < 1e-5
    counted = {e["name"]: e["value"] for e in tracer.events if e.get("type") == "counter"}
    assert counted == {"conv.images_featurized": 6, "conv.images_pooled_in_kernel": 6}
    (_, ((identities,), _)), _ = fused._operands
    assert [cls for cls, _ in identities] == [PooledConvolution]  # the vectorizer too


@pytest.mark.parametrize("chain", ["max_pool", "pixel_function", "no_rectifier",
                                   "size_unknown", "kernels_off"])
def test_other_chains_keep_the_xla_program(kernels_on, monkeypatch, chain):
    pooler = {"max_pool": Pooler(13, 14, pool_function="max"),
              "pixel_function": Pooler(13, 14, pixel_function=jnp.abs)}.get(chain)
    members = _members(16, "cell_2x2", pooler=pooler, rectify=chain != "no_rectifier")
    if chain == "size_unknown":
        members[0] = Convolver.build(rng.normal(size=(16, 6, 6, 3)))
    if chain == "kernels_off":
        monkeypatch.setenv("KEYSTONE_NO_PALLAS", "1")
    assert conv_form(members) == "xla"
    assert fusion.absorbed(members) == members
    fused = fusion.FusedBatchTransformer(members)
    (_, ((identities,), _)), _ = fused._operands
    assert [cls for cls, _ in identities] == [type(m) for m in members]
    X = _images(3)
    with obs.tracing() as tracer, pallas_ops.record_dispatches() as dispatched:
        got = fused.batch_apply(Dataset(jnp.asarray(X))).array
    assert dispatched == []
    assert _rel(got, _xla_chain(members, X)) < 1e-6
    assert [e["name"] for e in tracer.events if e.get("type") == "counter"] == [
        "conv.images_featurized"]


def test_a_chain_over_a_mesh_runs_the_kernel_on_each_devices_rows(kernels_on):
    """A Pallas call is not partitioned by the compiler: over a mesh the
    kernel runs in a ``shard_map`` over the ``data`` axis, each device on
    its own rows, and the program holds no gather of the images."""
    from keystone_tpu.parallel import mesh as mesh_lib

    members = _members(16, "cell_2x2")
    fused = fusion.FusedBatchTransformer(members)
    mesh = mesh_lib.make_mesh()
    X = _images(2 * mesh.size)
    data = Dataset(jnp.asarray(X)).shard(mesh)
    with obs.tracing() as tracer, pallas_ops.record_dispatches() as dispatched:
        got = fused.batch_apply(data)
    assert [name for name, _ in dispatched] == ["conv_pool"]
    assert got.array.sharding.spec[0] == mesh_lib.DATA_AXIS
    assert _rel(got.to_numpy(), _xla_chain(members, X)) < 1e-5
    counted = {e["name"]: e["value"] for e in tracer.events if e.get("type") == "counter"}
    assert counted == {"conv.images_featurized": X.shape[0],
                       "conv.images_pooled_in_kernel": X.shape[0]}
    text = fused._program.lower(fused._operands[1], data.array).as_text()
    assert "sdy.manual_computation" in text and "all-gather" not in text


def _bank(seed, d_in=24, d_out=16):
    from keystone_tpu.ops.stats import CosineRandomFeaturesModel

    r = np.random.default_rng(seed)
    return CosineRandomFeaturesModel(r.normal(size=(d_out, d_in)).astype(np.float32),
                                     r.uniform(0, 6.28, size=d_out).astype(np.float32))


def _cosine_chain():
    from keystone_tpu.ops.learning.linear import LinearMapper
    from keystone_tpu.ops.stats import StandardScalerModel

    r = np.random.default_rng(4)
    return [_bank(3), LinearMapper(
        r.normal(size=(16, 5)).astype(np.float32), r.normal(size=(5,)).astype(np.float32),
        StandardScalerModel(r.normal(size=(16,)).astype(np.float32)))]


def _cosine_gather():
    """The TIMIT cells' featurize: a gather of cosine banks, concatenated."""
    from keystone_tpu.ops.util import VectorCombiner

    return fusion.FusedGatherTransformer([[_bank(s)] for s in (5, 6, 7)], VectorCombiner())


@pytest.mark.parametrize("form", ["chain", "gather"])
def test_a_chain_without_a_convolution_keeps_its_kept_key_and_lowered_text(
        kernels_on, monkeypatch, form):
    """The cosine chain and the cosine gather of the TIMIT cells: the kept
    key is the members' own identities, as before the convolution could
    take its successors, and the program lowers to the same text with the
    offer taken away."""
    def build():
        monkeypatch.setattr(fusion, "_KEPT_PROGRAMS", {})
        if form == "chain":
            return fusion.FusedBatchTransformer(_cosine_chain())
        return _cosine_gather()

    fused = build()
    (key,) = fusion._KEPT_PROGRAMS
    branches = [fused.members] if form == "chain" else fused.branches
    identities = tuple(tuple((type(m), m.device_operands()[0]) for m in br)
                       for br in branches)
    assert key[0] is fusion._compose and key[1][0] == identities
    assert key[2:] == (None, "ks.featurize")
    X = jnp.asarray(rng.normal(size=(8, 24)), jnp.float32)
    text = fused._program.lower(fused._operands[1], X).as_text()
    assert "jit_composed" in text and "tpu_custom_call" not in text
    monkeypatch.setattr(fusion, "absorbed", list)  # no member may take another
    plain = build()
    assert list(fusion._KEPT_PROGRAMS) == [key]
    assert plain._program.lower(plain._operands[1], X).as_text() == text


def test_a_streamed_featurize_without_a_convolution_keeps_its_key(kernels_on, monkeypatch):
    """The streamed tier's composed featurize (``ComposedDeviceFeaturize``)
    keys its program by the members' own identities."""
    from keystone_tpu.ops.learning.streaming_ls import ComposedDeviceFeaturize

    chain = _cosine_chain()
    key = ComposedDeviceFeaturize(chain).static_key()
    assert key == tuple((type(m), m.device_operands()[0]) for m in chain)
    monkeypatch.setattr(fusion, "absorbed", list)
    assert ComposedDeviceFeaturize(chain).static_key() == key


def test_the_image_fit_says_pallas_pool_and_counts_its_images(kernels_on):
    """RandomPatchCifar's build and fit with the kernels on: ``conv_form``
    on every ``pipeline.build`` and one count a fit on each track."""
    from benchmarks.drivers import image_fit_loop as driver

    config = {"image_size": 32, "channels": 3, "num_classes": 10, "num_filters": 16,
              "patch_size": 6, "whitener_size": 256, "whitener_eps": 0.1,
              "patch_var_constant": 10.0, "pool_size": 14, "pool_stride": 13, "alpha": 0.25,
              "block_size": 64, "num_epochs": 1, "filter_seed": 7}
    kp, ki = jax.random.split(jax.random.key(1))
    [(images, Y)] = driver.make_images(kp, [ki], [192], config)
    with obs.tracing() as tracer:
        for lam in (1.0, 2.0):
            driver.fit_at(config, lam, images, Y)
    builds = [s["args"] for s in tracer.spans("pipeline.build")]
    assert [(b["conv_form"], b["image_batch"]) for b in builds] == [
        ("pallas_pool", pallas_images.LANES)] * 2
    for name in ("conv.images_featurized", "conv.images_pooled_in_kernel"):
        assert [e["value"] for e in tracer.events
                if e.get("type") == "counter" and e["name"] == name] == [192, 192]


@pytest.fixture(scope="module")
def v5e():
    """One chip of a described v5e host, to compile for (nothing runs)."""
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e topology can be described here: {e}")
    return jax.sharding.SingleDeviceSharding(topo.devices[0])


def test_the_cells_featurize_compiles_for_the_chip_as_the_kernel(v5e, compile_for_chip,
                                                                   kernels_on, monkeypatch):
    """The fused featurize program over 50,000 images at K = 1,600: the
    kernel's custom call, no conv map and no patch matrix among its
    buffers, and its temporaries under the XLA program's ≈ 2.1 GB."""
    monkeypatch.setattr(pallas_ops, "_interpret", lambda: False)  # else it is interpreted
    n, k = 50000, 1600
    members = [Convolver(jnp.zeros((k, 108)), 32, 32, 3,
                         whitener=ZCAWhitener(jnp.eye(108), jnp.zeros(108))),
               SymmetricRectifier(alpha=0.25), Pooler(13, 14, pool_function="sum"),
               ImageVectorizer()]
    fused = fusion.FusedBatchTransformer(members)
    (_, ((identities,), _)), _ = fused._operands
    assert [cls for cls, _ in identities] == [PooledConvolution]
    shape = functools.partial(jax.ShapeDtypeStruct, sharding=v5e)
    params = jax.tree_util.tree_map(lambda a: shape(a.shape, a.dtype), fused._operands[1])
    featurize = compile_for_chip(lambda p, X: fused._program(p, X), params,
                                 shape((n, 32, 32, 3), jnp.float32))
    text = featurize.as_text()
    assert text.count("tpu_custom_call") == 1 and "conv_pool" in text
    assert "27,27,1600" not in text and "729" not in text  # no conv map, no patch rows
    memory = featurize.memory_analysis()
    assert memory.output_size_in_bytes == n * 12800 * 4
    assert memory.temp_size_in_bytes < 1.6e9
