"""The streamed fit over a ``data`` mesh (PR 35), through the public entry
at toy widths on 4 of the suite's 8 CPU devices: the same model as one
device and as the plain reference, one program a process over a sweep of
new pipelines, rows placed with no trip through the host, and the spans,
attributes and counter the mesh branch puts on the record.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import timit as reference
from keystone_tpu import obs
from keystone_tpu.data import Dataset
from keystone_tpu.ops.learning.streaming_ls import mesh_psum_bytes
from keystone_tpu.parallel import mesh as mesh_lib
from keystone_tpu.pipelines import timit
from keystone_tpu.workflow import PipelineEnv

D_IN, K = timit.NUM_INPUT_FEATURES, 147
COSINES, BLOCK, EPOCHS, GAMMA, BANK_SEED = 2, 128, 3, 0.05555, 16384
D_FEAT = COSINES * BLOCK


@pytest.fixture(autouse=True)
def float32_mode():
    """As the users of the entry run it (in 64-bit mode the bank is drawn
    in float64: another bank than the reference's)."""
    with jax.enable_x64(False):
        yield


@pytest.fixture
def mesh4():
    return mesh_lib.make_mesh((4,), (mesh_lib.DATA_AXIS,), devices=jax.devices()[:4])


def rows(n, seed=0):
    rng = np.random.default_rng(seed)
    labels = rng.integers(0, K, n)
    X = 0.6 * rng.normal(size=(K, D_IN))[labels] + rng.normal(size=(n, D_IN))
    return jnp.asarray(X, jnp.float32), 2 * jax.nn.one_hot(labels, K, dtype=jnp.float32) - 1


def fit(data, labels, lam, bank_seed=BANK_SEED):
    """One whole new fit: a new pipeline, a new bank, no saved state."""
    PipelineEnv.get_or_create().reset()
    cfg = timit.TimitConfig(num_cosines=COSINES, gamma=GAMMA, rf_type="gaussian",
                            block_size=BLOCK, num_epochs=EPOCHS, lam=lam, seed=bank_seed,
                            solver="streaming")
    return timit.streaming_estimator(cfg).with_data(data, labels).fit()


def scores(fitted, probe):
    return np.asarray(fitted.apply(Dataset.of(probe)).array, np.float32)


@pytest.mark.parametrize("n", [2048, 2048 - 37], ids=["even_shards", "masked_last_shard"])
def test_the_mesh_fit_is_the_one_device_fit_and_the_reference(mesh4, n):
    X, Y = rows(n)
    probe, _ = rows(256, seed=1)
    lam = 1e-4
    data, labels = Dataset.of(X).shard(mesh4), Dataset.of(Y).shard(mesh4)
    assert data.array.shape[0] % 4 == 0 and data.n == n
    on_mesh = scores(fit(data, labels, lam), probe)
    on_one = scores(fit(Dataset.of(X), Dataset.of(Y), lam), probe)
    want = reference.fit_and_score(X, Y, probe, [lam], bank_seed=BANK_SEED,
                                   num_cosines=COSINES, block=BLOCK, gamma=GAMMA,
                                   epochs=EPOCHS)[lam]
    for got in (on_mesh, on_one):
        rel_fro, widest = reference.score_gaps(got, want)
        assert rel_fro < 1e-5 and widest < 1e-5
    assert reference.score_gaps(on_mesh, on_one)[0] < 1e-5


def test_a_sweep_of_new_pipelines_traces_nothing_after_the_first_fit(mesh4):
    X, Y = rows(1024)
    data, labels = Dataset.of(X).shard(mesh4), Dataset.of(Y).shard(mesh4)
    sweep = [(1e-5, BANK_SEED), (1e-4, BANK_SEED + 100), (1e-3, BANK_SEED + 200)]
    fitted = []
    with obs.tracing() as tracer:
        for lam, bank_seed in sweep:  # new banks of one shape, three lambdas
            fitted.append(fit(data, labels, lam, bank_seed))
    fits = sorted(tracer.spans("pipeline.fit"), key=lambda s: s["ts_us"])
    assert len(fits) == 3
    first_ends = fits[0]["ts_us"] + fits[0]["dur_us"]
    late = [s["args"] for s in tracer.spans("jax.compile")
            if s["args"].get("stage") == "trace" and s["ts_us"] >= first_ends]
    assert late == []
    # three models, not one: the banks and the lambdas reached the program as operands
    probe, _ = rows(64, seed=2)
    a, b, c = (scores(f, probe) for f in fitted)
    assert np.abs(a - b).max() > 1e-3 and np.abs(b - c).max() > 1e-3


def test_shard_keeps_a_device_array_on_the_devices(mesh4):
    X, _ = rows(1024)
    X, short = jax.block_until_ready((X, X[:1001]))
    with obs.tracing() as tracer, jax.transfer_guard("disallow"):
        data = Dataset.of(X).shard(mesh4)
        ragged = Dataset.of(short).shard(mesh4)  # padded on the device
    assert data.array.sharding.is_equivalent_to(
        jax.sharding.NamedSharding(mesh4, jax.sharding.PartitionSpec("data")), 2)
    np.testing.assert_array_equal(np.asarray(data.array), np.asarray(X))
    assert ragged.array.shape == (1004, D_IN) and ragged.n == 1001
    assert not np.asarray(ragged.array)[1001:].any()
    spans = [s["args"] for s in tracer.spans("data.shard")]
    assert [s["moved"] for s in spans] == ["device", "device"]
    assert spans[0]["bytes"] == X.nbytes and spans[0]["devices"] == 4


def test_shard_of_sharded_rows_returns_the_same_buffers(mesh4):
    X, _ = rows(1024)
    once = Dataset.of(X).shard(mesh4)
    with obs.tracing() as tracer, jax.transfer_guard("disallow"):
        twice = Dataset.of(once.array).shard(mesh4)
    assert twice.array is once.array
    assert {s.data.unsafe_buffer_pointer() for s in twice.array.addressable_shards} == \
        {s.data.unsafe_buffer_pointer() for s in once.array.addressable_shards}
    assert [s["args"]["moved"] for s in tracer.spans("data.shard")] == ["none"]


def test_shard_of_host_rows_goes_over_the_host(mesh4):
    X = np.ones((10, 3), np.float32)
    with obs.tracing() as tracer:
        data = Dataset.of(X).shard(mesh4)
    assert data.array.shape == (12, 3) and data.n == 10
    assert [s["args"]["moved"] for s in tracer.spans("data.shard")] == ["host"]


def test_the_mesh_fit_is_on_the_record(mesh4):
    X, Y = rows(2048)
    data, labels = Dataset.of(X).shard(mesh4), Dataset.of(Y).shard(mesh4)
    with obs.tracing() as tracer:
        fit(data, labels, 1e-4)
    psum_bytes = 4 * (D_FEAT * D_FEAT + D_FEAT * K + 1 + D_FEAT + K)
    assert mesh_psum_bytes(D_FEAT, K) == psum_bytes
    fit_span = next(s["args"] for s in tracer.spans("estimator.fit"))
    assert (fit_span["engine"], fit_span["devices"], fit_span["psum_bytes"]) == \
        ("stream_mesh", 4, psum_bytes)
    stream = next(s["args"] for s in tracer.spans("solver.stream_fit"))
    assert stream["mesh_shape"] == (4,) and stream["rows_local"] == 512
    assert stream["tile_rows"] == 512 and stream["rows"] == 2048
    samples = [e["value"] for e in tracer.events
               if e["type"] == "counter" and e["name"] == "mesh.psum_bytes"]
    assert samples == [float(psum_bytes)]  # one sample a fit
    assert tracer.spans("data.shard") == []  # the rows were placed before the fit


def test_one_device_says_nothing_of_a_mesh():
    X, Y = rows(512)
    with obs.tracing() as tracer:
        fit(Dataset.of(X), Dataset.of(Y), 1e-4)
    assert "engine" not in next(s["args"] for s in tracer.spans("estimator.fit"))
    stream = next(s["args"] for s in tracer.spans("solver.stream_fit"))
    assert "mesh_shape" not in stream and stream["rows"] == 512
    assert not [e for e in tracer.events if e.get("name") == "mesh.psum_bytes"]


def test_the_psums_are_under_their_name_scope(mesh4):
    from keystone_tpu.parallel import streaming

    X, Y = rows(512)
    Xs, Ys = mesh_lib.shard_rows(X, mesh4), mesh_lib.shard_rows(Y, mesh4)
    text = jax.jit(lambda a, b: streaming.gram_stats_mesh(
        a, b, lambda x: jnp.cos(x[:, :8]), 8, 128, mesh4, moments=True)).lower(Xs, Ys).as_text(
            debug_info=True)
    assert "ks.gram_psum" in text
