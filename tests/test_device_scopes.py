"""The ``ks.*`` name scopes a device profile files the device's time under
(``obs.device``; the benchmark's ``featurize_device_ms``, ``gram_device_ms``,
``solve_device_ms``): one toy fit of each of the five tiers through the
entry its cell takes, and the lowered text of every program it compiled
holds the scopes the account reads there."""

import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from keystone_tpu.data import Dataset
from keystone_tpu.parallel import mesh as mesh_lib
from keystone_tpu.pipelines import timit
from keystone_tpu.workflow import PipelineEnv

import test_block_tier
import test_mesh_stream_fit
import test_obs_profile_bridge as bridge
import test_sparse_fit_rows


@pytest.fixture
def lowered(tmp_path):
    """Every program lowered inside the test, as text with its name scopes.
    The caches go first, so that a program an earlier test compiled is
    lowered again here."""
    before = jax.config.read("jax_dump_ir_to"), jax.config.read("jax_dump_ir_modes")
    jax.clear_caches()
    jax.config.update("jax_dump_ir_to", str(tmp_path))
    jax.config.update("jax_dump_ir_modes", "stablehlo")

    def text():
        return "\n".join(open(p).read() for p in glob.glob(os.path.join(str(tmp_path), "*")))

    try:
        yield text
    finally:
        jax.config.update("jax_dump_ir_to", before[0])
        jax.config.update("jax_dump_ir_modes", before[1])


def stream_fit():
    bridge.toy_fit("streaming")


def resident_fit():
    """Cell 2's path: the features made by the fused featurizer's batch
    program, then the block solver on them (split, centred, stacked, one
    fused sweep)."""
    from keystone_tpu.ops.learning.block import BlockLeastSquaresEstimator

    PipelineEnv.get_or_create().reset()
    cfg = timit.TimitConfig(num_cosines=bridge.BRANCHES, block_size=bridge.BLOCK, num_epochs=2,
                            lam=1e-3, seed=7)
    X, Y = bridge.toy_rows()
    features = timit.build_featurizer(cfg).fit().apply(X)
    BlockLeastSquaresEstimator(bridge.BLOCK, 2, 1e-3).fit(features, Y)


def sparse_fit():
    data, labels = test_sparse_fit_rows.rows()
    test_sparse_fit_rows.SparseLBFGSwithL2(**test_sparse_fit_rows.GRAM).fit(data, labels)


def block_fit():
    d_feat = 4 * test_block_tier.BS
    _, bank = test_block_tier._choice(None, d_feat)
    rng = np.random.default_rng(4)
    X = rng.normal(size=(512, test_block_tier.D_IN)).astype(np.float32)
    Y = rng.normal(size=(512, test_block_tier.K)).astype(np.float32)
    test_block_tier.BlockStreamedLeastSquares(
        bank, d_feat, test_block_tier.BS, num_iter=3, lam=1e-2, tile_rows=128,
    ).fit(Dataset.of(X), Dataset.of(Y))


def mesh_fit():
    mesh = mesh_lib.make_mesh((4,), (mesh_lib.DATA_AXIS,), devices=jax.devices()[:4])
    X, Y = test_mesh_stream_fit.rows(512)
    with jax.enable_x64(False):
        test_mesh_stream_fit.fit(Dataset.of(X).shard(mesh), Dataset.of(Y).shard(mesh), 1e-3)


TIERS = {
    "stream": (stream_fit, ("ks.featurize", "ks.gram_fold", "ks.bcd")),
    "resident": (resident_fit, ("ks.featurize", "ks.split", "ks.center", "ks.stack",
                                "ks.gram_corr_fold", "ks.bcd_step")),
    "sparse": (sparse_fit, ("ks.sparse_densify", "ks.sparse_gram_acc", "ks.lbfgs_gram")),
    "block": (block_fit, ("ks.block_featurize", "ks.block_gram", "ks.block_factor",
                          "ks.block_update")),
    "mesh": (mesh_fit, ("ks.featurize", "ks.gram_fold", "ks.gram_psum", "ks.bcd")),
}


@pytest.mark.parametrize("tier", sorted(TIERS))
def test_the_fit_programs_of_a_tier_hold_the_scopes_the_account_reads(lowered, tier):
    fit, scopes = TIERS[tier]
    fit()
    text = lowered()
    for scope in scopes:
        assert scope in text, f"{scope} is in no program the {tier} fit lowered"


def test_an_eager_operation_takes_no_scope_from_its_caller(lowered):
    """Why the scaler, the splitter and the stack are programs of their own
    with the scope inside: around an eager call a scope names nothing."""
    x = jnp.ones((8, 3))
    with jax.named_scope("ks.around_an_eager_call"):
        (x - jnp.ones((3,))).block_until_ready()
    assert "jit_subtract" in "".join(os.listdir(jax.config.read("jax_dump_ir_to")))
    assert "ks.around_an_eager_call" not in lowered()
