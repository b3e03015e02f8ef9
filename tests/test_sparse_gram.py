"""Sparse gram-engine LBFGS vs the gather-path oracle.

The gram engine folds G = AᵀA once over densified row chunks and runs the
SAME L-BFGS iterates against G (hvp = GP/n + λP ≡ Aᵀ(AP)/n + λP), so the
two solvers must agree to summation-order noise. Also pins the
compressed-COO resident format (int16 indices + bf16 values — 4 bytes/nnz)
through the same fit.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from keystone_tpu.data import Dataset
from keystone_tpu.ops.learning.lbfgs import (
    SparseLBFGSwithL2,
    run_lbfgs_gram_streamed,
)
from keystone_tpu.ops.sparse import gram_pad_dim, sparse_gram_stream

N, D, W_NNZ, K = 3000, 200, 12, 3


def _problem(seed=0, idx_dtype=np.int32, val_dtype=np.float32):
    rng = np.random.default_rng(seed)
    idx = rng.integers(0, D, size=(N, W_NNZ)).astype(idx_dtype)
    vals = rng.normal(size=(N, W_NNZ)).astype(val_dtype)
    labels = rng.integers(0, K, size=N)
    Y = (2.0 * np.eye(K)[labels] - 1.0).astype(np.float32)
    ds = Dataset(
        {"indices": jnp.asarray(idx), "values": jnp.asarray(vals)}, n=N
    )
    return ds, Dataset.of(jnp.asarray(Y)), idx, vals, Y


class TestSparseGramStream:
    def test_gram_matches_dense_oracle(self):
        _, _, idx, vals, Y = _problem()
        dense = np.zeros((N, D), np.float64)
        np.add.at(dense, (np.arange(N)[:, None], idx), vals)

        c = 512
        nchunks = -(-N // c)
        pad = nchunks * c - N
        idx_t = jnp.asarray(
            np.pad(idx, ((0, pad), (0, 0)), constant_values=-1)
        ).reshape(nchunks, c, W_NNZ)
        val_t = jnp.asarray(np.pad(vals, ((0, pad), (0, 0)))).reshape(
            nchunks, c, W_NNZ
        )
        Y_t = jnp.asarray(np.pad(Y, ((0, pad), (0, 0)))).reshape(
            nchunks, c, K
        )
        import jax

        G, AtY, yty = jax.jit(
            lambda a, b, y: sparse_gram_stream(
                lambda cid: (a[cid], b[cid], y[cid]), nchunks, D, K
            )
        )(idx_t, val_t, Y_t)
        d_pad = gram_pad_dim(D, jnp.float32)
        assert G.shape == (d_pad, d_pad)
        np.testing.assert_allclose(
            np.asarray(G)[:D, :D], dense.T @ dense, rtol=2e-4, atol=2e-3
        )
        # Padding rows/cols of G and AtY are exactly zero.
        assert np.all(np.asarray(G)[D:, :] == 0)
        assert np.all(np.asarray(AtY)[D:, :] == 0)
        np.testing.assert_allclose(
            np.asarray(AtY)[:D], dense.T @ Y, rtol=2e-4, atol=2e-3
        )
        np.testing.assert_allclose(float(yty), (Y * Y).sum(), rtol=1e-6)

    def test_duplicate_indices_accumulate(self):
        # COO rows may repeat a column; densify must add, not overwrite.
        idx = jnp.asarray([[1, 1, 3]], dtype=jnp.int32)
        vals = jnp.asarray([[2.0, 3.0, 4.0]], dtype=jnp.float32)
        Y = jnp.asarray([[1.0]], dtype=jnp.float32)
        import jax

        G, AtY, _ = jax.jit(
            lambda a, b, y: sparse_gram_stream(
                lambda cid: (a, b, y), 1, 8, 1
            )
        )(idx, vals, Y)
        dense = np.zeros(8)
        dense[1], dense[3] = 5.0, 4.0
        np.testing.assert_allclose(
            np.asarray(G)[:8, :8], np.outer(dense, dense), atol=1e-5
        )
        np.testing.assert_allclose(np.asarray(AtY)[:8, 0], dense, atol=1e-5)


class TestGramSolverMatchesGather:
    @pytest.mark.slow
    def test_same_model_as_gather_path(self):
        ds, ys, *_ = _problem()
        m_gather = SparseLBFGSwithL2(
            lam=1e-3, num_iterations=25, num_features=D
        ).fit(ds, ys)
        m_gram = SparseLBFGSwithL2(
            lam=1e-3, num_iterations=25, num_features=D, solver="gram",
            gram_chunk_rows=512,
        ).fit(ds, ys)
        np.testing.assert_allclose(
            np.asarray(m_gram.x), np.asarray(m_gather.x), rtol=5e-3,
            atol=5e-4,
        )
        np.testing.assert_allclose(
            np.asarray(m_gram.b_opt), np.asarray(m_gather.b_opt),
            rtol=5e-3, atol=5e-4,
        )
        # Predictions agree tightly on held-out rows too (the model
        # difference is fp noise, not a train-set artifact).
        ds_test = _problem(seed=5)[0]
        for probe in (ds, ds_test):
            p1 = np.asarray(m_gather.batch_apply(probe).array)
            p2 = np.asarray(m_gram.batch_apply(probe).array)
            np.testing.assert_allclose(p2, p1, rtol=1e-2, atol=1e-3)

    def test_compressed_int16_bf16_storage(self):
        # 4-bytes-per-nnz resident format: int16 indices + bf16 values.
        ds16, ys, idx, vals, Y = _problem(
            idx_dtype=np.int16, val_dtype=np.float32
        )
        ds16 = Dataset(
            {
                "indices": jnp.asarray(idx.astype(np.int16)),
                "values": jnp.asarray(vals).astype(jnp.bfloat16),
            },
            n=N,
        )
        m16 = SparseLBFGSwithL2(
            lam=1e-3, num_iterations=25, num_features=D, solver="gram",
            gram_chunk_rows=512,
        ).fit(ds16, ys)
        ds32, _, _, _, _ = _problem()
        m32 = SparseLBFGSwithL2(
            lam=1e-3, num_iterations=25, num_features=D
        ).fit(ds32, ys)
        # bf16 values quantize the data itself (~0.4% relative), so the
        # tolerance is bf16-resolution, not fp32-noise.
        np.testing.assert_allclose(
            np.asarray(m16.x), np.asarray(m32.x), rtol=0.05, atol=0.02
        )

    def test_segmented_dispatch_equals_single(self):
        # The dispatch-bounded fold (phantom-padded final segment, donated
        # carry, traced cid0) must reproduce the one-dispatch fit exactly.
        _, _, idx, vals, Y = _problem()
        c = 500
        nchunks = N // c  # 6 chunks -> segments of 4 = [4, phantom-padded 4]
        idx_t = jnp.asarray(idx).reshape(nchunks, c, W_NNZ)
        val_t = jnp.asarray(vals).reshape(nchunks, c, W_NNZ)
        Y_t = jnp.asarray(Y).reshape(nchunks, c, K)

        def cf(cid, it, vt, yt):
            cid = jnp.minimum(cid, nchunks - 1)  # phantom ids slice safely
            return it[cid], vt[cid], yt[cid]

        kw = dict(lam=1e-3, num_iterations=25, n=N,
                  operands=(idx_t, val_t, Y_t))
        W_one, loss_one = run_lbfgs_gram_streamed(
            cf, nchunks, D, K, **kw
        )
        W_seg, loss_seg = run_lbfgs_gram_streamed(
            cf, nchunks, D, K, max_chunks_per_dispatch=4, **kw
        )
        np.testing.assert_allclose(
            np.asarray(W_seg), np.asarray(W_one), atol=1e-5, rtol=1e-5
        )
        np.testing.assert_allclose(float(loss_seg), float(loss_one), rtol=1e-6)

    def test_streamed_regenerated_chunks(self):
        # Chunks produced by a generator (nothing resident) must equal the
        # resident fit on the same data.
        import jax

        ds, ys, idx, vals, Y = _problem()
        c = 500
        nchunks = N // c

        idx_t = jnp.asarray(idx).reshape(nchunks, c, W_NNZ)
        val_t = jnp.asarray(vals).reshape(nchunks, c, W_NNZ)
        Y_t = jnp.asarray(Y).reshape(nchunks, c, K)

        W_s, loss = run_lbfgs_gram_streamed(
            lambda cid, it, vt, yt: (it[cid], vt[cid], yt[cid]),
            nchunks, D, K, lam=1e-3, num_iterations=25, n=N,
            operands=(idx_t, val_t, Y_t),
        )
        m_gather = SparseLBFGSwithL2(
            lam=1e-3, num_iterations=25, num_features=D
        ).fit(ds, ys)
        # No intercept lane in this direct call: compare to gather WITHOUT
        # intercept by refitting through run_lbfgs on the raw COO.
        from keystone_tpu.ops.learning.lbfgs import run_lbfgs

        W_ref = run_lbfgs(
            {"indices": jnp.asarray(idx), "values": jnp.asarray(vals)},
            jnp.asarray(Y), lam=1e-3, num_iterations=25, n=N,
            W_init=jnp.zeros((D, K), jnp.float32),
        )
        assert np.isfinite(float(loss))
        np.testing.assert_allclose(
            np.asarray(W_s), np.asarray(W_ref), rtol=5e-3, atol=5e-4
        )


# -- the intercept as a border of the fold (PR 38) ----------------------------


def _binary_rows(n, d, w=6, n_pad=0, seed=0):
    """0/1 rows with +-1 targets, ``n_pad`` rows of junk past the true n."""
    rng = np.random.default_rng(seed)
    idx = np.stack([
        np.sort(rng.choice(d, w, replace=False)) for _ in range(n + n_pad)
    ]).astype(np.int32)
    vals = np.ones((n + n_pad, w), np.float32)
    Y = np.where(rng.normal(size=(n + n_pad, 2)) > 0, 1.0, -1.0)
    return idx, vals, Y.astype(np.float32)


def _row_chunks(n, c, border, d=None):
    """Chunk function over (n_pad, .) operands, ragged last chunk and rows
    past ``n`` masked: with ``border`` the ones column is handed over with
    the targets; without, it is a lane at index ``d`` of the caller's own —
    what the estimator did before PR 38 and what any caller may still do."""
    from keystone_tpu.ops.learning.lbfgs import _LanedRowChunks

    source = _LanedRowChunks(c, n)
    if border:
        return source

    def laned(cid, indices, values, Y):
        idx, val, targets = source(cid, indices, values, Y)
        live = targets[:, -1:]
        lane = jnp.where(live > 0, d, -1).astype(idx.dtype)
        return (
            jnp.concatenate([idx, lane], axis=1),
            jnp.concatenate([val, jnp.ones_like(live, val.dtype)], axis=1),
            targets[:, :-1],
        )

    return laned


class TestInterceptBorder:
    @pytest.mark.parametrize("val_dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("pipeline", [False, True])
    def test_border_is_the_last_row_and_column_of_the_laned_gramian(
        self, val_dtype, pipeline
    ):
        """0/1 rows, ±1 targets: every sum is an integer float32 holds, so
        the border's pieces ARE the (d + 1)-wide Gramian's last row and
        column — bit for bit, in both slab types."""
        import jax

        n, d, c = 700, 96, 256  # 2 x 256 + a ragged 188, 68 junk rows past n
        ops = tuple(map(jnp.asarray, _binary_rows(n, d, n_pad=68)))
        nchunks = -(-ops[0].shape[0] // c)

        def fold(border, width):
            cf = _row_chunks(n, c, border, d)
            return jax.jit(lambda *o: sparse_gram_stream(
                lambda cid: cf(cid, *o), nchunks, width, 2,
                val_dtype=val_dtype, pipeline=pipeline, border=border,
            ))(*ops)

        G1, AtY1, yty1 = map(np.asarray, fold(False, d + 1))
        G, AtY, yty, ysum = map(np.asarray, fold(True, d))
        assert AtY.shape[1] == 3 and ysum.shape == (2,)
        np.testing.assert_array_equal(G[:d, :d], G1[:d, :d])
        np.testing.assert_array_equal(AtY[:d, :2], AtY1[:d])
        np.testing.assert_array_equal(AtY[:d, 2], G1[:d, d])  # s = Xᵀ1
        np.testing.assert_array_equal(AtY[:d, 2], G1[d, :d])
        np.testing.assert_array_equal(ysum, AtY1[d])  # 1ᵀY
        assert G1[d, d] == n and yty == yty1 == 2.0 * n
        assert np.all(AtY[d:] == 0) and np.all(G[d:] == 0)

    @pytest.mark.parametrize("d", [512, 513, 511])
    @pytest.mark.parametrize("seg", [None, 2])
    def test_border_fit_equals_the_laned_fit_through_the_generic_fold(
        self, d, seg
    ):
        """One model, two ways to its normal equations — at a width that
        is a multiple of the float32 tile (where the lane costs a tile
        row), one over and one under; a ragged last chunk, rows past n;
        in one dispatch and segmented (phantom chunk ids past the end)."""
        n, c = 700, 256
        idx, vals, Y = _binary_rows(n, d, n_pad=68, seed=d)
        vals = vals * np.random.default_rng(1).normal(size=vals.shape)
        ops = (jnp.asarray(idx), jnp.asarray(vals.astype(np.float32)),
               jnp.asarray(Y))
        how = dict(lam=1e-1, num_iterations=80, convergence_tol=1e-7, n=n,
                   operands=ops, max_chunks_per_dispatch=seg)
        nchunks = -(-idx.shape[0] // c)
        W_b, loss_b = run_lbfgs_gram_streamed(
            _row_chunks(n, c, True), nchunks, d, 2, border=True, **how)
        W_l, loss_l = run_lbfgs_gram_streamed(
            _row_chunks(n, c, False, d), nchunks, d + 1, 2, **how)
        assert W_b.shape == W_l.shape == (d + 1, 2)  # the intercept last
        scale = np.abs(np.asarray(W_l)).max()
        np.testing.assert_allclose(
            np.asarray(W_b), np.asarray(W_l), rtol=0, atol=1e-6 * scale)
        assert float(loss_b) == pytest.approx(float(loss_l), rel=1e-6)

    def test_mesh_fold_reduces_the_border_with_the_rest(self):
        """The border's pieces are psum'd with G and AᵀY: the mesh fit is
        the one-device fit."""
        import jax

        from keystone_tpu.parallel.mesh import make_mesh

        n, d, c = 1024, 64, 128
        idx, vals, Y = _binary_rows(n, d)
        ops = tuple(a.reshape(n // c, c, -1) for a in (
            idx, vals, np.concatenate([Y, np.ones((n, 1), np.float32)], 1)))

        def cf(cid, it, vt, yt):
            return it[cid], vt[cid], yt[cid]

        how = dict(lam=1e-3, num_iterations=30, n=n, border=True)
        W1, _ = run_lbfgs_gram_streamed(
            cf, n // c, d, 2, operands=tuple(map(jnp.asarray, ops)), **how)
        W4, _ = run_lbfgs_gram_streamed(
            cf, n // c, d, 2, operands=ops,
            mesh=make_mesh((4,), ("data",), jax.devices()[:4]), **how)
        assert W4.shape == (d + 1, 2)
        np.testing.assert_allclose(
            np.asarray(W4), np.asarray(W1), rtol=1e-5, atol=1e-6)

    def test_a_checkpoint_a_column_wider_is_refused_by_shape(
        self, tmp_path, monkeypatch
    ):
        """A snapshot written by a fit that laned its intercept holds a
        (d + 1)-wide carry: refused, never reinterpreted."""
        from keystone_tpu.data.durable import CheckpointSpec
        from keystone_tpu.ops.sparse import sparse_gram_init

        n, d, c = 1024, 600, 256  # d + 1 pads like d here: only G's width tells
        ops = tuple(map(jnp.asarray, _binary_rows(n, d)))
        wide = [np.asarray(a) for a in sparse_gram_init(d + 1025, 2)]
        monkeypatch.setattr(
            CheckpointSpec, "restore", lambda self, fingerprint: (wide, 1))
        with pytest.raises(ValueError, match="discard the checkpoint"):
            run_lbfgs_gram_streamed(
                _row_chunks(n, c, True), n // c, d, 2, n=n, border=True,
                operands=ops, max_chunks_per_dispatch=2,
                checkpoint=CheckpointSpec(str(tmp_path / "ck")),
            )
        # ... and so is a laned fit's three-piece carry of the SAME width
        laned = [np.asarray(a) for a in sparse_gram_init(d, 2)]
        monkeypatch.setattr(
            CheckpointSpec, "restore", lambda self, fingerprint: (laned, 1))
        with pytest.raises(ValueError, match="discard the checkpoint"):
            run_lbfgs_gram_streamed(
                _row_chunks(n, c, True), n // c, d, 2, n=n, border=True,
                operands=ops, max_chunks_per_dispatch=2,
                checkpoint=CheckpointSpec(str(tmp_path / "ck")),
            )
