"""The block-streamed tier as PR 32 left it: the sweep's two phases as
programs of their own against the one-program form (bit for bit), row tiles
and the factor-only stash against the plain sweep, the block size the tier
keeps and the refusal aloud where it cannot, and the selector's price of the
tier it will build. Since PR 33: epoch 1's block Gramians as the upper
block-triangle, panel by panel and mirrored, against the plain product at
every block width, and ``gram`` on ``estimator.fit`` saying so."""

import logging

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from keystone_tpu import obs
from keystone_tpu.ops.learning.streaming_ls import (
    BlockStreamedLeastSquares,
    CosineBankFeaturize,
    StreamingFeaturizedLeastSquares,
    StreamingLeastSquaresChoice,
    block_stash_bytes,
)
from keystone_tpu.parallel import mesh as mesh_lib
from keystone_tpu.parallel import streaming
from keystone_tpu.parallel.linalg import (
    _factor_matvec,
    _psd_factor,
    _solve_psd,
    _solve_psd_from_factor,
)

D_IN, K, BS = 22, 5, 64
LAM = 1e-2


def _problem(devices: int, n_true=700, n_pad=704, blocks=4, seed=0):
    rng = np.random.default_rng(seed)
    d_feat = blocks * BS
    Wrf = jnp.asarray(rng.normal(size=(d_feat, D_IN)).astype(np.float32) * 0.3)
    brf = jnp.asarray(rng.uniform(0, 2 * np.pi, size=(d_feat,)).astype(np.float32))
    mesh = mesh_lib.make_mesh(devices=jax.devices()[:devices])
    X = rng.normal(size=(n_pad, D_IN)).astype(np.float32)
    Y = rng.normal(size=(n_pad, K)).astype(np.float32) + 0.5
    return (mesh_lib.shard_rows(jnp.asarray(X), mesh), mesh_lib.shard_rows(jnp.asarray(Y), mesh),
            Wrf, brf, mesh, n_true)


def _two_dispatches(Xs, Ys, Wrf, brf, epochs, **kw):
    carry, ymean, first = streaming.block_bcd_first_epoch(Xs, Ys, Wrf, brf, LAM, **kw)
    R, W, later = streaming.block_bcd_later_epochs(
        carry[0], carry[1], carry[2:], Xs, Wrf, brf, LAM, epochs=epochs - 1, **kw)
    return W, carry[4].reshape(-1), ymean, float(first), float(later)


@pytest.mark.parametrize("devices", [1, 8])
@pytest.mark.parametrize("center", [True, False])
@pytest.mark.parametrize("tile_rows,stash", [(None, "gram+factor"), (32, "gram+factor"),
                                             (40, "factor"), (None, "factor")])
def test_two_dispatches_equal_the_one_program_fit_bit_for_bit(devices, center, tile_rows, stash):
    """Epoch 1 and epochs 2+ as programs of their own, the carry handed from
    one to the other as device arrays, against ``streaming_block_bcd_mesh``'s
    one program: the same step functions, so the same bits — and the residual
    norm each phase returns falls."""
    Xs, Ys, Wrf, brf, mesh, n_true = _problem(devices)
    kw = dict(block_size=BS, mesh=mesh, n_true=n_true, center=center, tile_rows=tile_rows,
              stash=stash)
    whole = streaming.streaming_block_bcd_mesh(Xs, Ys, Wrf, brf, lam=LAM, num_iter=3, **kw)
    W, fmean, ymean, first, later = _two_dispatches(Xs, Ys, Wrf, brf, 3, **kw)
    if center:
        assert all(bool(jnp.array_equal(a, b)) for a, b in zip(whole, (W, fmean, ymean)))
    else:
        assert bool(jnp.array_equal(whole, W))
    assert 0 < later < first
    # tiles and the factor-only stash change the order of sums, not the model
    plain = streaming.streaming_block_bcd_mesh(
        Xs, Ys, Wrf, brf, lam=LAM, num_iter=3, block_size=BS, mesh=mesh, n_true=n_true,
        center=center)
    np.testing.assert_allclose(np.asarray(W), np.asarray(plain[0] if center else plain),
                               atol=2e-5, rtol=2e-5)


def test_the_slab_a_tiled_step_holds_does_not_grow_with_the_rows():
    """With a tile, no (local rows, block) array is in the program; without
    one, that is the step's slab."""
    def shapes(tile_rows, n):
        Xs, Ys, Wrf, brf, mesh, _ = _problem(1, n_true=n, n_pad=n)
        text = str(jax.make_jaxpr(lambda *a: streaming.block_bcd_first_epoch(
            *a, LAM, block_size=BS, mesh=mesh, center=True, tile_rows=tile_rows))(
                Xs, Ys, Wrf, brf))
        return text

    assert f"f32[1024,{BS}]" in shapes(None, 1024)
    tiled = shapes(128, 1024)
    assert f"f32[1024,{BS}]" not in tiled and f"f32[128,{BS}]" in tiled


def test_solve_from_the_factor_alone_is_the_solve_from_the_gramian():
    rng = np.random.default_rng(3)
    A = rng.normal(size=(200, 48)).astype(np.float32)
    gram, rhs = jnp.asarray(A.T @ A), jnp.asarray(rng.normal(size=(48, K)).astype(np.float32))
    lam = jnp.float32(1e-2)
    chol = _psd_factor(gram, lam)
    w = jnp.asarray(rng.normal(size=(48, K)).astype(np.float32))
    np.testing.assert_allclose(_factor_matvec(chol, w, lam), gram @ w, rtol=2e-4, atol=2e-3)
    np.testing.assert_allclose(_solve_psd_from_factor(chol, rhs, lam),
                               _solve_psd(gram, rhs, lam, chol=chol), rtol=1e-6)
    # a factor that does not solve its system falls into the same rescue
    bad = chol.at[0, 0].set(1e-12)
    assert bool(jnp.all(jnp.isfinite(_solve_psd_from_factor(bad, rhs, lam))))


def _choice(budget, d_feat, hint=BS):
    choice = StreamingLeastSquaresChoice(num_iter=3, lam=LAM, block_size_hint=hint)
    choice.budget_bytes = budget
    choice.raw_row_bytes = 4.0 * D_IN
    rng = np.random.default_rng(5)
    bank = CosineBankFeaturize(
        rng.normal(size=(d_feat, D_IN)).astype(np.float32) * 0.3,
        rng.uniform(0, 6, d_feat).astype(np.float32))
    return choice, bank


def _fixed(choice, bank, d_feat, rows, k=K):
    """What ``build_estimator`` reckons the fit holds beside its stash."""
    return choice._block_fixed_bytes(rows, d_feat, k, 4.0 * D_IN,
                                     float(bank.Wrf.nbytes + bank.brf.nbytes))


def test_the_block_tier_keeps_the_block_size_it_is_given(caplog):
    """Feasibility is reckoned against the whole budget: with the rest of
    what the fit holds counted, both stashes where they fit, then the factor
    alone, and only then a smaller block — said aloud, in the decision and in
    a warning."""
    d_feat, rows = 16 * BS, 512
    choice, bank = _choice(None, d_feat)
    both, factor = (block_stash_bytes(d_feat, BS, s) for s in streaming.BLOCK_STASHES)
    assert (both, factor) == (8 * d_feat * BS, 4 * d_feat * BS)
    fixed = _fixed(choice, bank, d_feat, rows)
    assert fixed + both < 8.0 * d_feat * d_feat  # every budget below is under the gram tier's

    def built(budget):
        choice.budget_bytes = budget
        with obs.tracing() as tracer, caplog.at_level(logging.WARNING, "keystone_tpu.streaming"):
            caplog.clear()
            est = choice.build_estimator(bank, d_feat, local_rows=rows, k=K)
        decision = next(e["args"] for e in tracer.events if e["type"] == "event"
                        and e["name"] == "cost.decision"
                        and e["args"]["decision"] == "streaming_tier")
        return est, decision, [r.getMessage() for r in caplog.records]

    # room for both stashes: kept whole (the parent's quarter rule cut this block to 32)
    est, decision, warned = built(fixed + both + 1)
    assert isinstance(est, BlockStreamedLeastSquares)
    assert (est.block_size, est.stash, warned) == (BS, "gram+factor", [])
    assert decision["winner"] == "block" and decision["reason"] == "gramian_exceeds_budget"
    assert decision["block_size"] == BS == decision["configured_block_size"]
    assert decision["stash_bytes"] == both == est.stash_bytes
    # the Gramian stash goes first; the block stays
    est, decision, warned = built(fixed + factor + 1)
    assert (est.block_size, est.stash, warned) == (BS, "factor", [])
    assert decision["stash"] == "factor" and decision["stash_bytes"] == factor
    # only then does the block shrink — and the fit says that it is another model
    est, decision, warned = built(fixed + factor // 2 + 1)
    assert (est.block_size, est.stash) == (BS // 2, "factor")
    assert decision["reason"] == "block_shrunk_to_fit_budget"
    assert decision["block_size"] == BS // 2
    assert decision["configured_block_size"] == BS
    assert len(warned) == 1 and "NOT the configured model" in warned[0]
    # nothing fits, whatever the block: shrinking cannot help, the block stays
    est, decision, _ = built(fixed / 2)
    assert (est.block_size, est.stash) == (BS, "factor")
    # a budget the Gramian fits keeps the gram tier
    est, decision, _ = built(1e12)
    assert isinstance(est, StreamingFeaturizedLeastSquares) and decision["winner"] == "gram"


def test_capacity_model_counts_the_stash_the_plan_keeps():
    d_feat, rows = 16 * BS, 512
    choice, bank = _choice(None, d_feat)
    choice.budget_bytes = 4.0 * d_feat * d_feat  # under the gram tier's 8 d^2
    need = choice.resident_bytes(rows, d_feat, K, 1.0, 1)
    assert need <= choice.budget_bytes
    bs, stash = choice._block_tier_plan(d_feat, need - block_stash_bytes(d_feat, BS, "gram+factor"))
    assert (bs, stash) == (BS, "gram+factor")
    # twice the rows on two machines hold the same
    assert choice.resident_bytes(2 * rows, d_feat, K, 1.0, 2) == pytest.approx(need)


def test_the_selector_prices_the_tier_it_will_build():
    """The gram tier pays n d (d + k) for its one pass; the block tier
    n d (bs + k) for the epoch that builds the per-block Gramians, n d k for
    each later one and the features made anew each epoch: about d / bs less
    in the leading term. Which is priced follows ``_gram_tier_ok``, the test
    ``build_estimator`` takes its tier from."""
    n, d, k, bs = 131072, 204800, 147, 4096
    choice = StreamingLeastSquaresChoice(num_iter=5, lam=LAM, block_size_hint=bs)
    choice.raw_row_bytes = 4.0 * 440
    weights = (1.0, 0.0, 0.0)  # operations alone
    choice.budget_bytes = None  # no budget: the Gramian "fits"
    gram = choice.cost(n, d, k, 1.0, 1, *weights)
    choice.budget_bytes = 0.85 * 16.909e9  # a v5e: 8 d^2 = 336 GB does not
    assert not choice._gram_tier_ok(d)
    block = choice.cost(n, d, k, 1.0, 1, *weights)
    over = choice._STREAM_OVERHEAD
    assert gram == pytest.approx(over * (n * d * (d + k) + 5 * d * d * k))
    assert block == pytest.approx(over * n * d * ((bs + k) + 4 * k + 5 * 440))
    assert 25 < gram / block < 50 and block / over == pytest.approx(1.89e14, rel=0.01)
    # at cell 1-3's width the Gramian fits a v5e: the present form, to the bit
    assert choice._gram_tier_ok(16384)
    assert choice.cost(n, 16384, k, 1.0, 1, 1.0, 2.0, 3.0) == over * max(
        n * 16384 * (16384 + k) + 5 * 16384 * 16384 * k, 2.0 * (n * 16384 + 2.0 * 16384 ** 2)
    ) + 3.0 * 16384 * (16384 + k)


def test_estimator_fit_says_what_it_ran_and_counts_its_steps():
    """The fit's two dispatches as spans, the host's two waits, the counters
    and the attributes on ``estimator.fit`` — and the model it gives is the
    one-program fit's."""
    from keystone_tpu.data import Dataset
    from keystone_tpu.workflow.pipeline import _stamped_fit

    d_feat = 4 * BS
    _, bank = _choice(None, d_feat)
    rng = np.random.default_rng(4)
    X = rng.normal(size=(512, D_IN)).astype(np.float32)
    Y = rng.normal(size=(512, K)).astype(np.float32) + 0.3
    est = BlockStreamedLeastSquares(bank, d_feat, BS, num_iter=3, lam=LAM, tile_rows=128)
    with obs.tracing() as tracer:
        model = _stamped_fit(est, lambda: est.fit(Dataset.of(X), Dataset.of(Y)))
    attrs = next(s["args"] for s in tracer.spans("estimator.fit"))
    assert {"engine": "block_stream", "block_size": BS, "blocks": 4, "stash": "gram+factor",
            "stash_bytes": 8 * d_feat * BS}.items() <= attrs.items()
    epochs = [s["args"] for s in tracer.spans("solver.block_epoch")]
    assert [(e["epoch_from"], e["epoch_to"], e["tile_rows"]) for e in epochs] == [
        (1, 1, 128), (2, 3, 128)]
    assert len([s for s in tracer.spans("executor.drain")
                if s["args"]["site"] == "block_epoch"]) == 2
    counters = {}
    for c in tracer.events:
        if c["type"] == "counter":
            counters.setdefault(c["name"], []).append(c["value"])
    assert counters["block.steps"] == [4, 8]
    assert counters["block.rows_featurized"] == [4 * 2 * 512, 8 * 2 * 512]  # two passes a tile
    first, later = counters["block.residual_fro"]
    assert 0 < later < first
    mesh = mesh_lib.make_mesh(devices=jax.devices()[:1])
    W, fmean, ymean = streaming.streaming_block_bcd_mesh(
        mesh_lib.shard_rows(jnp.asarray(X), mesh), mesh_lib.shard_rows(jnp.asarray(Y), mesh),
        bank.Wrf, bank.brf, block_size=BS, lam=LAM, num_iter=3, mesh=mesh, center=True,
        tile_rows=128)
    assert bool(jnp.array_equal(model.W_stack, W)) and bool(jnp.array_equal(model.fmean, fmean))


def test_a_small_batch_of_cosine_features_takes_no_kernel(monkeypatch):
    """The optimizer's sample (a few rows a branch) goes by XLA's shared
    programs: the Pallas kernel would be lowered anew for every branch of
    every fit, 59 ms apiece on the chip (PERF.md section 5)."""
    from keystone_tpu.data import Dataset
    from keystone_tpu.ops import pallas_ops
    from keystone_tpu.ops.stats import CosineRandomFeatures

    monkeypatch.setenv("KEYSTONE_PALLAS", "1")  # the kernels on, interpreted
    rf = CosineRandomFeatures(D_IN, 128, 0.3, seed=1)
    rng = np.random.default_rng(0)
    few, many = (rng.normal(size=(n, D_IN)).astype(np.float32) for n in (3, 256))
    with pallas_ops.record_dispatches() as log:
        out = rf.batch_apply(Dataset.of(few)).to_numpy()
    assert log == []
    np.testing.assert_allclose(out, np.cos(few @ np.asarray(rf.W).T + np.asarray(rf.b)), atol=1e-5)
    with pallas_ops.record_dispatches() as log:
        rf.batch_apply(Dataset.of(many))
    assert [name for name, _ in log] == ["cosine_features"]


# --- epoch 1's Gramians: the upper block-triangle, panel by panel, mirrored once a block (PR 33) ---


def _first_epoch(n_pad, n_true, tile_rows, *, bs, devices=1, center=True, use_pallas=False):
    """Epoch 1 of a two-block toy fit: the program's carry and ‖R‖², whether
    the program holds a full (bs, bs) product, and the same epoch done
    plainly here — each block's FᵀF as one float64 product of the float32
    slab, its system solved, the residual updated — as (R, W, G, ‖Y‖²)."""
    rng = np.random.default_rng(11)
    d_feat = 2 * bs
    Wrf = jnp.asarray(rng.normal(size=(d_feat, D_IN)).astype(np.float32) * 0.3)
    brf = jnp.asarray(rng.uniform(0, 2 * np.pi, size=(d_feat,)).astype(np.float32))
    X = jnp.asarray(rng.normal(size=(n_pad, D_IN)).astype(np.float32))
    Y = jnp.asarray(rng.normal(size=(n_pad, K)).astype(np.float32) + 0.5)
    mesh = mesh_lib.make_mesh(devices=jax.devices()[:devices])
    Xs, Ys = mesh_lib.shard_rows(X, mesh), mesh_lib.shard_rows(Y, mesh)
    kw = dict(block_size=bs, mesh=mesh, n_true=n_true, center=center, tile_rows=tile_rows,
              use_pallas=use_pallas)
    text = str(jax.make_jaxpr(
        lambda *a: streaming.block_bcd_first_epoch(*a, LAM, **kw))(Xs, Ys, Wrf, brf))
    carry, _, residual_sq = streaming.block_bcd_first_epoch(Xs, Ys, Wrf, brf, LAM, **kw)
    n = n_pad if n_true is None else n_true
    R = np.asarray(Y[:n], np.float64)
    y_sq = float((R * R).sum())
    R = R - R.mean(0) if center else R
    W, G = [], []
    for b in range(2):
        cols = slice(b * bs, (b + 1) * bs)
        F = np.asarray(jnp.cos(X[:n] @ Wrf[cols].T + brf[cols]), np.float64)
        F = F - F.mean(0) if center else F
        G.append(F.T @ F)
        W.append(np.linalg.solve(G[-1] + LAM * np.eye(bs), F.T @ R))
        R = R - F @ W[-1]
    one_product = f"f32[{bs},{bs}] = dot_general" in text  # FᵀF would be the step's one (bs, bs) product
    return carry, float(residual_sq), one_product, (R, np.stack(W), np.stack(G), y_sq)


def _assert_the_plain_epoch(carry, residual_sq, plain, n_true):
    """The Gramians to 2e-6 of their largest entry (float32 sums of a
    thousand products against float64's: they read 6e-7 at most), the stash
    symmetric to the bit, the factor that of the stash (1e-6 read), weights
    and residual to 2e-4 (a float32 solve of these toy systems against a
    float64 one reads 5e-5 and 6e-5 at most)."""
    (R, W, G, C, _), (R0, W0, G0, y_sq) = carry, plain
    assert bool(jnp.array_equal(G, jnp.swapaxes(G, 1, 2)))
    np.testing.assert_allclose(np.asarray(G), G0, atol=2e-6 * np.abs(G0).max(), rtol=0)
    L = np.tril(np.asarray(C, np.float64))
    np.testing.assert_allclose(L @ np.swapaxes(L, 1, 2), G0 + LAM * np.eye(G0.shape[-1]),
                               atol=2e-5 * np.abs(G0).max(), rtol=0)
    np.testing.assert_allclose(np.asarray(W), W0, atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(np.asarray(R)[:n_true], R0, atol=2e-4, rtol=2e-4)
    assert 0 < residual_sq < y_sq and residual_sq == pytest.approx((R0 * R0).sum(), rel=1e-4)


@pytest.mark.parametrize("center", [True, False])
@pytest.mark.parametrize("devices,n_pad,n_true,tile_rows", [
    (1, 1024, None, None),   # one slab, cell 4's form
    (1, 704, 700, 256),      # two whole tiles and a ragged third, its last rows masked
    (8, 1600, 1594, None),   # a slab a device, the psum before the mirror
])
def test_first_epoch_gramians_are_the_plain_product(devices, n_pad, n_true, tile_rows, center):
    """Epoch 1's carry at the least block made of several panels (3 of its 4
    panel products are computed) against FᵀF as one product made here."""
    bs = 2 * streaming._GRAM_PANEL
    carry, residual_sq, one_product, plain = _first_epoch(
        n_pad, n_true, tile_rows, bs=bs, devices=devices, center=center)
    assert not one_product
    _assert_the_plain_epoch(carry, residual_sq, plain, n_true)


@pytest.mark.parametrize("bs", [BS, 256, 384, 512, 768])
def test_every_block_width_goes_through_the_panels(bs):
    """Under a panel, one panel, a panel and a half, two, three: one form —
    a block of one panel is the full product (mirrored all the same), a last
    panel may be narrower. Rows ragged, masked and tiled with a remainder,
    the kernels on (interpreted here)."""
    # twice the rows of the widest block: the toy systems stay well conditioned
    carry, residual_sq, one_product, plain = _first_epoch(1700, 1690, 512, bs=bs, use_pallas=True)
    assert one_product == (bs <= streaming._GRAM_PANEL)
    _assert_the_plain_epoch(carry, residual_sq, plain, 1690)


@pytest.mark.parametrize("bs,products", [(4096, 16), (4352, 9), (8192, 16), (16384, 16)])
def test_a_wider_block_takes_wider_panels_not_more(bs, products):
    """Sixteen products at most, whatever the block: the width grows in
    whole 256-column steps (nothing runs: the products are counted)."""
    text = str(jax.make_jaxpr(lambda F: streaming._gram_upper_panels(F, jnp.float32))(
        jax.ShapeDtypeStruct((8, bs), jnp.float32)))
    assert text.count("dot_general") == products


@pytest.mark.parametrize("bs", [BS, 256, 384, 512, 768])
def test_estimator_fit_says_which_form_its_gramians_took(bs):
    """``gram`` on ``estimator.fit`` and in the ``streaming_tier`` decision:
    the one form every block width takes."""
    from keystone_tpu.data import Dataset
    from keystone_tpu.workflow.pipeline import _stamped_fit

    d_feat = 2 * bs
    _, bank = _choice(None, d_feat)
    rng = np.random.default_rng(6)
    X = rng.normal(size=(300, D_IN)).astype(np.float32)
    Y = rng.normal(size=(300, K)).astype(np.float32) + 0.3
    est = BlockStreamedLeastSquares(bank, d_feat, bs, num_iter=2, lam=LAM, tile_rows=128)
    with obs.tracing() as tracer:
        model = _stamped_fit(est, lambda: est.fit(Dataset.of(X), Dataset.of(Y)))
    attrs = next(s["args"] for s in tracer.spans("estimator.fit"))
    assert attrs["gram"] == "sym_dot"
    assert bool(jnp.all(jnp.isfinite(model.W_stack)))
    wide = 16 * bs  # wide enough that the Gramian is past a budget the stash fits
    choice, bank = _choice(None, wide, hint=bs)
    choice.budget_bytes = _fixed(choice, bank, wide, 300) + 8 * wide * bs + 1
    with obs.tracing() as tracer:
        choice.build_estimator(bank, wide, local_rows=300, k=K)
    decision = next(e["args"] for e in tracer.events if e["type"] == "event"
                    and e["name"] == "cost.decision")
    assert decision["winner"] == "block" and decision["gram"] == "sym_dot"


def test_the_cell_gramian_panels_compile_with_no_copy_of_the_slab(one_chip, compile_for_chip):
    """The panels at ``timit_block_fit_131k``'s slab, 131,072 x 4,096
    float32, compiled for the described chip (nothing runs): the column
    slices must fuse into the products — a copy of one would be up to the
    slab's 2.1 GB."""
    slab = jax.ShapeDtypeStruct((131072, 4096), jnp.float32, sharding=one_chip)
    compiled = compile_for_chip(
        lambda F: streaming._gram_upper_panels(F, jnp.float32), slab)
    # the panels and their joined triangle: a few (4096, 4096) arrays, nothing of the slab's size
    assert compiled.memory_analysis().temp_size_in_bytes <= 4 * 4 * 4096 * 4096
