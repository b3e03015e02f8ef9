"""Equal bank draws share their device buffers (``ops/stats.py``'s weak-valued
table): a sweep that builds a new TIMIT pipeline a fit draws its cosine banks
once, every later fit takes the same arrays, and nothing outlives its holders.
"""

import gc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from keystone_tpu import obs
from keystone_tpu.data import Dataset
from keystone_tpu.ops import stats
from keystone_tpu.pipelines import timit
from keystone_tpu.workflow import PipelineEnv

CFG = dict(num_cosines=3, block_size=32, num_epochs=2, lam=1e-3, seed=77)


@pytest.fixture(autouse=True)
def no_holder_left():
    """Every test starts and ends with nothing held, so with an empty table."""
    gc.collect()
    assert len(stats._DRAWN_BANKS) == 0
    yield
    gc.collect()
    assert len(stats._DRAWN_BANKS) == 0


def _banks(entry, **changes):
    """The (W, b) pairs a new pipeline of ``entry`` holds."""
    cfg = timit.TimitConfig(**{**CFG, **changes})
    if entry == "streaming":
        bank = timit.streaming_estimator(cfg).featurize
        return [(bank.Wrf, bank.brf)]
    graph = timit.build_featurizer(cfg).executor.graph
    return [(op.W, op.b) for op in graph.operators.values()
            if isinstance(op, stats.CosineRandomFeaturesModel)]


def _same_buffers(one, other):
    return len(one) == len(other) and all(
        w1 is w2 and b1 is b2 for (w1, b1), (w2, b2) in zip(one, other))


@pytest.mark.parametrize("entry", ["streaming", "featurizer"])
def test_equal_configurations_hold_the_same_buffers(entry):
    first, second = _banks(entry), _banks(entry)
    assert len(first) == (1 if entry == "streaming" else CFG["num_cosines"])
    assert _same_buffers(first, second)
    # the draw itself is what it was: the reference's split of the branch's key
    kw, kb = jax.random.split(jax.random.key(CFG["seed"]))
    W, b = first[0]
    rows = slice(0, CFG["block_size"])
    np.testing.assert_array_equal(
        W[rows], jax.random.normal(kw, (CFG["block_size"], timit.NUM_INPUT_FEATURES)) * 0.05555)
    np.testing.assert_array_equal(
        b[rows], jax.random.uniform(kb, (CFG["block_size"],)) * (2 * jnp.pi))


@pytest.mark.parametrize("entry", ["streaming", "featurizer"])
@pytest.mark.parametrize("change", [
    {"seed": CFG["seed"] + 1000}, {"gamma": 0.1}, {"rf_type": "cauchy"}, "dtype"])
def test_another_draw_shares_nothing(entry, change):
    first = _banks(entry)
    if change == "dtype":
        with jax.enable_x64(False):  # the suite runs in 64-bit mode
            second = _banks(entry)
        assert second[0][0].dtype == jnp.float32 and first[0][0].dtype == jnp.float64
    else:
        second = _banks(entry, **change)
    assert not any(w1 is w2 or b1 is b2
                   for (w1, b1), (w2, b2) in zip(first, second))
    assert _same_buffers(first, _banks(entry))  # the first draw is still shared


def test_the_table_is_empty_once_every_holder_is_gone():
    held = _banks("streaming") + _banks("featurizer")
    assert len(stats._DRAWN_BANKS) == 2 * (1 + CFG["num_cosines"])
    del held
    gc.collect()
    assert len(stats._DRAWN_BANKS) == 0
    # and a bank half gone is drawn again whole, not handed out by halves
    W = _banks("streaming")[0][0]  # its b is dropped here
    gc.collect()
    again_W, again_b = _banks("streaming")[0]
    assert again_W is not W and again_b.shape == (CFG["num_cosines"] * CFG["block_size"],)
    np.testing.assert_array_equal(W, again_W)


def test_two_fits_of_a_sweep_are_equal_bit_for_bit_with_the_table_on_the_path():
    rng = np.random.default_rng(5)
    X = jnp.asarray(rng.normal(size=(96, timit.NUM_INPUT_FEATURES)), jnp.float32)
    Y = jnp.asarray(rng.normal(size=(96, 4)), jnp.float32)

    def fit():
        with jax.enable_x64(False):
            PipelineEnv.get_or_create().reset()  # a sweep's fits reuse no saved state
            est = timit.streaming_estimator(timit.TimitConfig(**CFG))
            fitted = est.with_data(Dataset.of(X), Dataset.of(Y)).fit()
            return est.featurize, np.asarray(fitted.apply(Dataset.of(X)).array)

    with jax.enable_x64(False):
        alone = fit()[1]  # nothing kept: the next fit draws anew
        PipelineEnv.get_or_create().reset()
        gc.collect()
        assert len(stats._DRAWN_BANKS) == 0
        bank1, scores1 = fit()
        bank2, scores2 = fit()  # takes the first fit's bank
    assert bank1.Wrf is bank2.Wrf and bank1.brf is bank2.brf
    np.testing.assert_array_equal(scores1, scores2)
    np.testing.assert_array_equal(alone, scores1)
    del bank1, bank2
    PipelineEnv.get_or_create().reset()


@pytest.mark.parametrize("entry", ["streaming", "featurizer"])
def test_pipeline_build_says_what_was_drawn_and_what_was_shared(entry):
    n = CFG["num_cosines"]
    with obs.tracing() as tracer:
        first = _banks(entry)
        second = _banks(entry)
        other = _banks(entry, seed=CFG["seed"] + 1)  # branches 1.. are the first's 2..
    builds = [s["args"] for s in tracer.spans("pipeline.build")]
    assert [(b["banks_drawn"], b["banks_shared"]) for b in builds] == [
        (n, 0), (0, n), (1, n - 1) if entry == "featurizer" else (n, 0)]
    assert all(b["branches"] == n for b in builds)
    samples = [r["value"] for r in tracer.events
               if r["type"] == "counter" and r["name"] == "bank.shared"]
    assert samples == [b["banks_shared"] for b in builds]
    del first, second, other
