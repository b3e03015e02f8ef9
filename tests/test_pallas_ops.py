"""Pallas kernel parity tests (interpret mode on CPU) + fused BCD solver.

The kernels are exercised through the Pallas interpreter so the exact same
kernel code paths that run on TPU are validated on the CPU test platform —
the kernel-level analog of the "Spark local mode" strategy (SURVEY.md §4).
"""

import jax
import numpy as np
import jax.numpy as jnp
import pytest

from keystone_tpu.ops import pallas_ops as po
from keystone_tpu.parallel import linalg


rng = np.random.default_rng(42)


import contextlib


@contextlib.contextmanager
def force_interpret():
    """Route pallas dispatch through the interpreter, then restore and drop
    jit executables compiled against the patched interpreter so later
    same-shape calls re-lower for the real backend."""
    import jax

    orig = po._interpret
    po._interpret = lambda: True
    try:
        yield
    finally:
        po._interpret = orig
        jax.clear_caches()


class TestGaussianKernelBlock:
    def test_matches_reference_algebra(self):
        X = rng.normal(size=(70, 50)).astype(np.float32)
        Y = rng.normal(size=(40, 50)).astype(np.float32)
        xn = (X**2).sum(1)
        yn = (Y**2).sum(1)
        K = po.gaussian_kernel_block(X, Y, xn, yn, 0.07, interpret=True)
        sq = xn[:, None] + yn[None, :] - 2 * X @ Y.T
        K_ref = np.exp(-0.07 * np.maximum(sq, 0))
        np.testing.assert_allclose(np.asarray(K), K_ref, atol=1e-5)

    def test_ragged_shapes_padded_correctly(self):
        # Non-multiples of every tile dimension.
        X = rng.normal(size=(13, 9)).astype(np.float32)
        Y = rng.normal(size=(17, 9)).astype(np.float32)
        xn = (X**2).sum(1)
        yn = (Y**2).sum(1)
        K = po.gaussian_kernel_block(X, Y, xn, yn, 0.5, interpret=True)
        assert K.shape == (13, 17)
        sq = xn[:, None] + yn[None, :] - 2 * X @ Y.T
        np.testing.assert_allclose(
            np.asarray(K), np.exp(-0.5 * np.maximum(sq, 0)), atol=1e-5
        )


class TestCosineFeatures:
    def test_matches_reference_algebra(self):
        X = rng.normal(size=(60, 30)).astype(np.float32)
        W = rng.normal(size=(50, 30)).astype(np.float32)
        b = rng.uniform(0, 2 * np.pi, 50).astype(np.float32)
        F = po.cosine_features(X, W, b, interpret=True)
        np.testing.assert_allclose(np.asarray(F), np.cos(X @ W.T + b), atol=1e-5)

    def test_bf16_out_dtype(self):
        X = rng.normal(size=(16, 8)).astype(np.float32)
        W = rng.normal(size=(8, 8)).astype(np.float32)
        b = np.zeros(8, dtype=np.float32)
        F = po.cosine_features(X, W, b, out_dtype=jnp.bfloat16, interpret=True)
        assert F.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            np.asarray(F, dtype=np.float32), np.cos(X @ W.T), atol=2e-2
        )


class TestGramCorr:
    @pytest.mark.parametrize("fn", [po.gram_corr, po.gram_corr_sym])
    def test_matches_two_gemms(self, fn):
        A = rng.normal(size=(90, 70)).astype(np.float32)
        R = rng.normal(size=(90, 11)).astype(np.float32)
        gram, corr = fn(A, R, interpret=True)
        np.testing.assert_allclose(np.asarray(gram), A.T @ A, atol=1e-4)
        np.testing.assert_allclose(np.asarray(corr), A.T @ R, atol=1e-4)

    def test_sym_multi_tile_symmetry(self):
        # d > 512 forces nt > 1 column tiles: exercises the scalar-prefetched
        # triangular pair enumeration, off-diagonal writeback, and mirror.
        A = rng.normal(size=(64, 700)).astype(np.float32)
        R = rng.normal(size=(64, 5)).astype(np.float32)
        gram, corr = po.gram_corr_sym(A, R, interpret=True)
        np.testing.assert_allclose(np.asarray(gram), A.T @ A, atol=1e-4)
        np.testing.assert_allclose(
            np.asarray(gram), np.asarray(gram).T, atol=0
        )
        np.testing.assert_allclose(np.asarray(corr), A.T @ R, atol=1e-4)

    def test_bf16_input(self):
        A = rng.normal(size=(40, 20)).astype(np.float32)
        R = rng.normal(size=(40, 3)).astype(np.float32)
        gram, corr = po.gram_corr_sym(
            jnp.asarray(A, dtype=jnp.bfloat16), R, interpret=True
        )
        assert gram.dtype == jnp.float32  # f32 accumulation
        np.testing.assert_allclose(
            np.asarray(gram), A.T @ A, rtol=2e-2, atol=2e-1
        )


class TestFusedBCD:
    def test_matches_per_block_solver(self):
        n, db, nb, k = 64, 8, 3, 4
        A = rng.normal(size=(n, nb * db)).astype(np.float32)
        W_true = rng.normal(size=(nb * db, k)).astype(np.float32)
        B = A @ W_true
        blocks = [A[:, i * db : (i + 1) * db] for i in range(nb)]

        Ws_ref = linalg.bcd_least_squares(blocks, B, lam=0.1, num_iter=3)
        W_fused = linalg.bcd_least_squares_fused(
            np.stack(blocks), B, lam=0.1, num_iter=3, use_pallas=False
        )
        for i in range(nb):
            np.testing.assert_allclose(
                np.asarray(W_fused[i]), np.asarray(Ws_ref[i]), atol=1e-3
            )

    def test_exact_recovery_full_rank(self):
        # One block spanning all features + enough iterations recovers W.
        n, d, k = 80, 12, 3
        A = rng.normal(size=(n, d)).astype(np.float32)
        W_true = rng.normal(size=(d, k)).astype(np.float32)
        B = A @ W_true
        W = linalg.bcd_least_squares_fused(
            A[None], B, lam=1e-6, num_iter=1, use_pallas=False
        )
        np.testing.assert_allclose(np.asarray(W[0]), W_true, atol=1e-3)

    def test_warm_start(self):
        n, db, nb, k = 48, 6, 2, 2
        A = rng.normal(size=(n, nb * db)).astype(np.float32)
        B = rng.normal(size=(n, k)).astype(np.float32)
        stack = np.stack([A[:, i * db : (i + 1) * db] for i in range(nb)])
        W1 = linalg.bcd_least_squares_fused(
            stack, B, lam=0.5, num_iter=2, use_pallas=False
        )
        W2 = linalg.bcd_least_squares_fused(
            stack, B, lam=0.5, num_iter=2, W_init=W1, use_pallas=False
        )
        W4 = linalg.bcd_least_squares_fused(
            stack, B, lam=0.5, num_iter=4, use_pallas=False
        )
        np.testing.assert_allclose(np.asarray(W2), np.asarray(W4), atol=1e-4)

    @pytest.mark.slow
    def test_fused_with_pallas_interpret(self):
        with force_interpret():
            n, db, nb, k = 32, 8, 2, 3
            A = rng.normal(size=(nb, n, db)).astype(np.float32)
            B = rng.normal(size=(n, k)).astype(np.float32)
            W_pl = linalg.bcd_least_squares_fused(
                A, B, lam=0.2, num_iter=2, use_pallas=True
            )
            W_ref = linalg.bcd_least_squares_fused(
                A, B, lam=0.2, num_iter=2, use_pallas=False
            )
            np.testing.assert_allclose(
                np.asarray(W_pl), np.asarray(W_ref), atol=1e-3
            )


class TestBf16SolveQuality:
    def test_bf16_features_preserve_solve_quality(self):
        """The bench's bf16 feature layout must not degrade the solve beyond
        feature-level noise: solutions from bf16 and f32 layouts of the same
        problem agree to ~1%."""
        n, db, nb, k = 128, 16, 2, 3
        A = rng.normal(size=(nb, n, db)).astype(np.float32)
        W_true = rng.normal(size=(nb, db, k)).astype(np.float32)
        B = sum(A[i] @ W_true[i] for i in range(nb))
        W32 = linalg.bcd_least_squares_fused(
            A, B, lam=1e-3, num_iter=4, use_pallas=False
        )
        W16 = linalg.bcd_least_squares_fused(
            jnp.asarray(A, dtype=jnp.bfloat16), B, lam=1e-3, num_iter=4,
            use_pallas=False,
        )
        denom = np.abs(np.asarray(W32)).max()
        rel = np.abs(np.asarray(W16) - np.asarray(W32)).max() / denom
        assert rel < 2e-2, rel


class TestFusedFlatBCD:
    def test_flat_matches_stacked(self):
        n, db, nb, k = 96, 8, 3, 4
        F = rng.normal(size=(n, nb * db)).astype(np.float32)
        B = rng.normal(size=(n, k)).astype(np.float32)
        stacked = np.stack([F[:, i * db : (i + 1) * db] for i in range(nb)])
        W_stacked = linalg.bcd_least_squares_fused(
            stacked, B, lam=0.3, num_iter=3, use_pallas=False
        )
        W_flat = linalg.bcd_least_squares_fused_flat(
            F, B, db, lam=0.3, num_iter=3, use_pallas=False
        )
        np.testing.assert_allclose(
            np.asarray(W_flat), np.asarray(W_stacked), atol=1e-4
        )

    def test_indivisible_block_raises(self):
        F = rng.normal(size=(16, 10)).astype(np.float32)
        B = rng.normal(size=(16, 2)).astype(np.float32)
        with pytest.raises(ValueError):
            linalg.bcd_least_squares_fused_flat(F, B, 4, use_pallas=False)

    def test_strided_window_path_matches_sliced(self):
        """At tile-aligned shapes the fused solver takes the strided
        column-window kernels (no per-block dynamic_slice copy of F, and a
        lane-padded label buffer); the weights must match the XLA sliced
        path, including multi-epoch stashed-factor reuse."""
        from keystone_tpu.ops import pallas_ops

        n, db, nb, k = 512, 256, 2, 3  # n % 512 == 0, db % ti(256) == 0
        F = rng.normal(size=(n, nb * db)).astype(np.float32)
        B = rng.normal(size=(n, k)).astype(np.float32)
        assert pallas_ops.strided_gram_ok(F, db)
        with force_interpret():
            W_strided = linalg.bcd_least_squares_fused_flat(
                F, B, db, lam=0.2, num_iter=3, use_pallas=True
            )
        W_ref = linalg.bcd_least_squares_fused_flat(
            F, B, db, lam=0.2, num_iter=3, use_pallas=False
        )
        assert W_strided.shape == W_ref.shape  # lane padding sliced away
        np.testing.assert_allclose(
            np.asarray(W_strided), np.asarray(W_ref), atol=1e-4
        )

    def test_strided_kernels_match_dense_math(self):
        """block_corr / block_residual_update against plain numpy on an
        interior column window."""
        from keystone_tpu.ops import pallas_ops

        n, d, blk, k = 512, 1024, 256, 5
        F = rng.normal(size=(n, d)).astype(np.float32)
        R = rng.normal(size=(n, k)).astype(np.float32)
        dW = rng.normal(size=(blk, k)).astype(np.float32)
        start = 512
        with force_interpret():
            corr = np.asarray(pallas_ops.block_corr(F, start, blk, R))
            r_new = np.asarray(
                pallas_ops.block_residual_update(F, start, blk, dW, R)
            )
        blkF = F[:, start : start + blk]
        np.testing.assert_allclose(corr, blkF.T @ R, atol=1e-3)
        np.testing.assert_allclose(r_new, R - blkF @ dW, atol=1e-3)

    def test_strided_gram_matches_full(self):
        from keystone_tpu.ops import pallas_ops

        n, d, blk = 512, 512, 256
        F = rng.normal(size=(n, d)).astype(np.float32)
        R = rng.normal(size=(n, 3)).astype(np.float32)
        with force_interpret():
            g = pallas_ops.block_gram_sym(F, 256, blk)
            c = pallas_ops.block_corr(F, 256, blk, R)
        blkF = F[:, 256:512]
        np.testing.assert_allclose(np.asarray(g), blkF.T @ blkF, atol=1e-3)
        np.testing.assert_allclose(np.asarray(c), blkF.T @ R, atol=1e-3)

    def test_flat_with_pallas_interpret(self):
        with force_interpret():
            F = rng.normal(size=(32, 16)).astype(np.float32)
            B = rng.normal(size=(32, 3)).astype(np.float32)
            W_pl = linalg.bcd_least_squares_fused_flat(
                F, B, 8, lam=0.1, num_iter=2, use_pallas=True
            )
            W_ref = linalg.bcd_least_squares_fused_flat(
                F, B, 8, lam=0.1, num_iter=2, use_pallas=False
            )
            np.testing.assert_allclose(
                np.asarray(W_pl), np.asarray(W_ref), atol=1e-3
            )


class TestF64Preservation:
    def test_fused_f64_warm_start_matches_stepwise(self):
        """The W_init path must keep f64 precision too (regression: features
        were downcast to f32 in the warm-start residual)."""
        n, db, nb, k = 48, 6, 2, 2
        A = rng.normal(size=(n, nb * db))  # float64
        B = rng.normal(size=(n, k))
        blocks = [A[:, i * db : (i + 1) * db] for i in range(nb)]
        stack = np.stack(blocks)
        W1 = linalg.bcd_least_squares_fused(
            stack, B, lam=0.5, num_iter=2, use_pallas=False
        )
        W_ref = linalg.bcd_least_squares(
            blocks, B, lam=0.5, num_iter=4, W_init=None
        )
        W2 = linalg.bcd_least_squares_fused(
            stack, B, lam=0.5, num_iter=2, W_init=W1, use_pallas=False
        )
        for i in range(nb):
            np.testing.assert_allclose(
                np.asarray(W2[i]), np.asarray(W_ref[i]), rtol=0, atol=1e-12
            )

    def test_fused_f64_pallas_flag_falls_back_to_xla(self):
        """f64 inputs must not route through the f32-accumulating pallas
        kernels even when use_pallas=True."""
        with force_interpret():
            A = rng.normal(size=(2, 32, 8))  # float64
            B = rng.normal(size=(32, 3))
            W_pl = linalg.bcd_least_squares_fused(
                A, B, lam=0.2, num_iter=1, use_pallas=True
            )
            W_ref = linalg.bcd_least_squares_fused(
                A, B, lam=0.2, num_iter=1, use_pallas=False
            )
            np.testing.assert_allclose(
                np.asarray(W_pl), np.asarray(W_ref), atol=1e-12
            )


class TestGramCorrSymAcc:
    """ISSUE 3 fused-kernel pinning: the one-kernel chunk step (syrk +
    correlation accumulating through riding operands) against its unfused
    composition, on the CPU interpreter."""

    def test_matches_unfused_composition_f32(self):
        n, d, k = 512, 1024, 3
        F = rng.normal(size=(n, d)).astype(np.float32)
        R = rng.normal(size=(n, k)).astype(np.float32)
        G0 = rng.normal(size=(d, d)).astype(np.float32)
        C0 = rng.normal(size=(d, k)).astype(np.float32)
        assert po.gram_corr_acc_ok(jnp.asarray(F))
        G1, C1 = po.gram_corr_sym_acc(G0, C0, F, R, interpret=True)
        # Unfused composition: the accumulating gram-only kernel + an
        # XLA FᵀR GEMM — the round-5 chunk step.
        G_ref = po.gram_sym_acc(G0, F, interpret=True)
        C_ref = C0 + F.T @ R
        np.testing.assert_allclose(
            np.triu(np.asarray(G1)), np.triu(np.asarray(G_ref)), atol=1e-3
        )
        np.testing.assert_allclose(np.asarray(C1), C_ref, atol=1e-3)

    def test_matches_unfused_composition_bf16(self):
        n, d, k = 512, 1024, 2
        F32 = rng.normal(size=(n, d)).astype(np.float32)
        F = jnp.asarray(F32, dtype=jnp.bfloat16)
        R = rng.normal(size=(n, k)).astype(np.float32)
        G0 = np.zeros((d, d), np.float32)
        C0 = np.zeros((d, k), np.float32)
        G1, C1 = po.gram_corr_sym_acc(G0, C0, F, R, interpret=True)
        Fq = np.asarray(F, dtype=np.float32)  # the bf16 quantization
        Rq = np.asarray(jnp.asarray(R).astype(jnp.bfloat16), np.float32)
        np.testing.assert_allclose(
            np.triu(np.asarray(G1)), np.triu(Fq.T @ Fq), rtol=2e-2, atol=2e-1
        )
        np.testing.assert_allclose(
            np.asarray(C1), Fq.T @ Rq, rtol=2e-2, atol=2e-1
        )

    def test_accumulates_across_chunks(self):
        # Three folds through the fused kernel == one big unfused gram.
        n, d, k = 512, 512, 2
        chunks = [rng.normal(size=(n, d)).astype(np.float32) for _ in range(3)]
        Rs = [rng.normal(size=(n, k)).astype(np.float32) for _ in range(3)]
        G = jnp.zeros((d, d), jnp.float32)
        C = jnp.zeros((d, k), jnp.float32)
        for F, R in zip(chunks, Rs):
            G, C = po.gram_corr_sym_acc(G, C, F, R, interpret=True)
        F_all = np.concatenate(chunks)
        R_all = np.concatenate(Rs)
        np.testing.assert_allclose(
            np.triu(np.asarray(G)), np.triu(F_all.T @ F_all), atol=5e-3
        )
        np.testing.assert_allclose(np.asarray(C), F_all.T @ R_all, atol=5e-3)

    def test_fold_level_fused_matches_xla_fold(self):
        # sparse_gram_fold with the fused kernel (use_pallas, interpret)
        # against the pure-XLA fold — the composition the bench runs.
        from keystone_tpu.ops.sparse import sparse_gram_stream

        c, w, d, k, nchunks = 512, 9, 700, 3, 3
        idx = jnp.asarray(
            rng.integers(-1, d, size=(nchunks, c, w)).astype(np.int32)
        )
        val = jnp.asarray(
            rng.normal(size=(nchunks, c, w)).astype(np.float32)
        )
        Y = jnp.asarray(rng.normal(size=(nchunks, c, k)).astype(np.float32))

        def cf(cid):
            return idx[cid], val[cid], Y[cid]

        with force_interpret():
            G_pl, A_pl, y_pl = sparse_gram_stream(
                cf, nchunks, d, k, use_pallas=True
            )
        G_ref, A_ref, y_ref = sparse_gram_stream(
            cf, nchunks, d, k, use_pallas=False
        )
        np.testing.assert_allclose(np.asarray(G_pl), np.asarray(G_ref),
                                   atol=1e-3)
        np.testing.assert_allclose(np.asarray(A_pl), np.asarray(A_ref),
                                   atol=1e-3)

    @pytest.mark.parametrize("kernel", ["gram_sym_acc", "gram_corr_sym_acc"])
    def test_two_calls_on_one_running_gramian_update_it_in_place(self, kernel):
        """PR 38: the running operands are aliased to the outputs. Two
        calls on the same G give the two-slab sum on the upper triangle,
        the strictly-lower blocks are WHAT WENT IN (they were undefined
        memory before), and the caller's own G0 is not touched."""
        n, d, k = 512, 1024, 2  # two 512-column tiles: block (1, 0) is never written
        F1, F2 = (rng.normal(size=(n, d)).astype(np.float32) for _ in range(2))
        R1, R2 = (rng.normal(size=(n, k)).astype(np.float32) for _ in range(2))
        G0 = jnp.asarray(rng.normal(size=(d, d)).astype(np.float32))
        C0 = jnp.asarray(rng.normal(size=(d, k)).astype(np.float32))
        kept = np.array(G0)
        if kernel == "gram_sym_acc":
            G = po.gram_sym_acc(po.gram_sym_acc(G0, F1, interpret=True), F2,
                                interpret=True)
        else:
            G, C = po.gram_corr_sym_acc(G0, C0, F1, R1, interpret=True)
            G, C = po.gram_corr_sym_acc(G, C, F2, R2, interpret=True)
            np.testing.assert_allclose(
                np.asarray(C), np.asarray(C0) + F1.T @ R1 + F2.T @ R2, atol=2e-3)
        G = np.asarray(G)
        want = kept + F1.T @ F1 + F2.T @ F2
        np.testing.assert_allclose(np.triu(G), np.triu(want), atol=2e-3)
        np.testing.assert_array_equal(G[512:, :512], kept[512:, :512])
        np.testing.assert_array_equal(np.asarray(G0), kept)

    @pytest.mark.parametrize("kernel,aliases", [
        ("gram_sym_acc", ((2, 0),)),
        ("gram_corr_sym_acc", ((2, 0), (3, 1))),
    ])
    def test_the_running_operands_are_aliased_to_the_outputs(self, kernel, aliases):
        """The jaxpr's ``pallas_call`` carries ``input_output_aliases`` (the
        operand index counts the two scalar-prefetch arrays). Whether XLA
        then drops the chunk loop's copy of its carry is the chip's to say
        (the cell's device account)."""
        n, d, k = 512, 1024, 2
        G = jnp.zeros((d, d), jnp.float32)
        C = jnp.zeros((d, k), jnp.float32)
        F = jnp.zeros((n, d), jnp.bfloat16)
        R = jnp.zeros((n, k), jnp.float32)
        if kernel == "gram_sym_acc":
            call, args = (lambda G, F: po.gram_sym_acc(G, F, interpret=False)), (G, F)
        else:
            call, args = (lambda *a: po.gram_corr_sym_acc(*a, interpret=False)), (G, C, F, R)
        eqns = [e for e in jax.make_jaxpr(call)(*args).jaxpr.eqns
                if e.primitive.name == "pallas_call"]
        assert len(eqns) == 1
        assert tuple(eqns[0].params["input_output_aliases"]) == aliases

    def test_pipelined_fold_bit_identical_to_serial(self):
        from keystone_tpu.ops.sparse import sparse_gram_stream

        c, w, d, k, nchunks = 256, 5, 300, 2, 4
        idx = jnp.asarray(
            rng.integers(-1, d, size=(nchunks, c, w)).astype(np.int32)
        )
        val = jnp.asarray(rng.normal(size=(nchunks, c, w)).astype(np.float32))
        Y = jnp.asarray(rng.normal(size=(nchunks, c, k)).astype(np.float32))

        def cf(cid):
            return idx[cid], val[cid], Y[cid]

        G1, A1, y1 = sparse_gram_stream(cf, nchunks, d, k, pipeline=False)
        G2, A2, y2 = sparse_gram_stream(cf, nchunks, d, k, pipeline=True)
        np.testing.assert_array_equal(np.asarray(G1), np.asarray(G2))
        np.testing.assert_array_equal(np.asarray(A1), np.asarray(A2))
        np.testing.assert_array_equal(np.asarray(y1), np.asarray(y2))


class TestGaussianResidBlock:
    """ISSUE 3 fused-kernel pinning: the KRR residual epilogue (kernel
    block generated in VMEM, contracted into K_blockᵀW, never written)
    against the unfused gaussian_kernel_block + GEMM composition."""

    def test_matches_unfused_composition(self):
        m, nb, d, k = 96, 40, 30, 5
        X = rng.normal(size=(m, d)).astype(np.float32)
        Y = rng.normal(size=(nb, d)).astype(np.float32)
        W = rng.normal(size=(m, k)).astype(np.float32)
        xn = (X**2).sum(1)
        yn = (Y**2).sum(1)
        resid = po.gaussian_resid_block(X, Y, xn, yn, W, 0.07, interpret=True)
        K = po.gaussian_kernel_block(X, Y, xn, yn, 0.07, interpret=True)
        np.testing.assert_allclose(
            np.asarray(resid), np.asarray(K).T @ W, atol=1e-3
        )

    def test_ghost_w_rows_contribute_zero(self):
        # The solver invariant the fused path relies on: W rows past the
        # true train count are zero, so masking K's ghost rows is not
        # needed — assert the unmasked fused result equals the masked
        # unfused one.
        m, nb, d, k, n_true = 64, 32, 16, 3, 50
        X = rng.normal(size=(m, d)).astype(np.float32)
        Y = rng.normal(size=(nb, d)).astype(np.float32)
        W = rng.normal(size=(m, k)).astype(np.float32)
        W[n_true:] = 0.0
        xn = (X**2).sum(1)
        yn = (Y**2).sum(1)
        resid = po.gaussian_resid_block(X, Y, xn, yn, W, 0.3, interpret=True)
        K = np.array(
            po.gaussian_kernel_block(X, Y, xn, yn, 0.3, interpret=True)
        )
        K[n_true:] = 0.0  # the round-5 valid_row mask
        np.testing.assert_allclose(np.asarray(resid), K.T @ W, atol=1e-3)

    def test_krr_sweep_fused_matches_xla(self):
        # The whole fused KRR sweep with the Pallas residual epilogue
        # (interpret) against the XLA path — ragged final block included.
        from keystone_tpu.ops.learning.kernel import _krr_fit_fused

        n, d, k, bs, nb, n_train = 96, 20, 3, 32, 3, 90
        X = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
        Y = jnp.asarray(rng.normal(size=(n, k)).astype(np.float32))
        order = jnp.asarray(np.tile(np.arange(nb, dtype=np.int32), 2))
        _, ws_xla = _krr_fit_fused(
            X, Y, order, 0.05, 1e-2, bs, n_train, nb, False
        )
        with force_interpret():
            _, ws_pl = _krr_fit_fused(
                X, Y, order, 0.05, 1e-2, bs, n_train, nb, True
            )
        np.testing.assert_allclose(
            np.asarray(ws_pl), np.asarray(ws_xla), atol=2e-4
        )


class TestCountSketchScatter:
    """Fused sparse×dense-random product (the remaining PAPERS.md item):
    interpreter equality against the numpy scatter reference, pinned at
    1e-5 relative (the kernel accumulates in tiled MXU order, the
    reference in scatter order), including chunk-fold composition."""

    @staticmethod
    def _reference(idx, val, bucket, sign, m, d1):
        SA = np.zeros((m, d1), dtype=np.float32)
        c, s = idx.shape
        for i in range(c):
            for t in range(s):
                j = idx[i, t]
                if 0 <= j < d1:
                    SA[bucket[i], j] += sign[i] * val[i, t]
        return SA

    @staticmethod
    def _chunk(c, s, m, d1, seed, duplicate_cols=False):
        r = np.random.default_rng(seed)
        idx = r.integers(0, d1, size=(c, s)).astype(np.int32)
        if duplicate_cols:
            idx[:, 1::2] = idx[:, ::2][:, : idx[:, 1::2].shape[1]]
        val = r.normal(size=(c, s)).astype(np.float32)
        # mask a ragged tail of slots per row, the raw_chunk_tiles pad shape
        drop = r.random(size=(c, s)) < 0.3
        idx = np.where(drop, -1, idx)
        val = np.where(drop, 0.0, val).astype(np.float32)
        bucket = r.integers(0, m, size=(c,)).astype(np.int32)
        sign = r.choice([-1.0, 1.0], size=(c,)).astype(np.float32)
        return idx, val, bucket, sign

    def test_matches_numpy_scatter(self):
        m, d1 = 13, 37
        idx, val, bucket, sign = self._chunk(50, 4, m, d1, seed=0)
        got = po.countsketch_scatter(idx, val, bucket, sign, m, d1, interpret=True)
        want = self._reference(idx, val, bucket, sign, m, d1)
        assert got.shape == (m, d1)
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)

    def test_duplicate_columns_within_a_row_accumulate(self):
        # Two nnz slots of one row can hit the SAME column; the densify
        # loop must sum them, not overwrite.
        m, d1 = 7, 19
        idx, val, bucket, sign = self._chunk(
            24, 6, m, d1, seed=1, duplicate_cols=True
        )
        got = po.countsketch_scatter(idx, val, bucket, sign, m, d1, interpret=True)
        want = self._reference(idx, val, bucket, sign, m, d1)
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)

    def test_multi_tile_shapes(self):
        # m and d1 past one tile, c past one contraction tile: exercises
        # the grid index maps and the pad rows (sign 0 ⇒ no contribution).
        m, d1 = 600, 300
        idx, val, bucket, sign = self._chunk(300, 3, m, d1, seed=2)
        got = po.countsketch_scatter(idx, val, bucket, sign, m, d1, interpret=True)
        want = self._reference(idx, val, bucket, sign, m, d1)
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-4)

    def test_fold_composition_across_chunks(self):
        # Σ_chunks kernel(chunk) must equal the one-shot scatter over the
        # concatenated stream — the shape of the IHS fold_pass carry.
        m, d1 = 11, 23
        chunks = [self._chunk(16, 3, m, d1, seed=10 + i) for i in range(4)]
        acc = np.zeros((m, d1), dtype=np.float32)
        want = np.zeros((m, d1), dtype=np.float32)
        for idx, val, bucket, sign in chunks:
            acc += np.asarray(
                po.countsketch_scatter(idx, val, bucket, sign, m, d1, interpret=True)
            )
            want += self._reference(idx, val, bucket, sign, m, d1)
        np.testing.assert_allclose(acc, want, rtol=1e-5, atol=1e-5)

    def test_ihs_sparse_fit_matches_scatter_path(self, monkeypatch):
        # End-to-end: the IHS sparse fold with the kernel engaged
        # (KEYSTONE_PALLAS ⇒ interpret-mode dispatch on CPU) returns the
        # same model as the flattened scatter-add path.
        from keystone_tpu.data import Dataset
        from keystone_tpu.ops.learning.sketch import IterativeHessianSketch

        r = np.random.default_rng(3)
        n, d, nnz, k = 48, 12, 4, 2
        idx = np.sort(r.integers(0, d, size=(n, nnz)).astype(np.int32), axis=1)
        val = r.normal(size=(n, nnz)).astype(np.float32)
        B = r.normal(size=(n, k)).astype(np.float32)
        data = Dataset({"indices": idx, "values": val}, n=n)
        labels = Dataset(B)

        def fit():
            est = IterativeHessianSketch(
                lam=1e-2, sketch_factor=4, outer_iters=2, seed=0,
                chunk_rows=16, num_features=d,
            )
            return np.asarray(est.fit(data, labels).x)

        with force_interpret():
            monkeypatch.setenv("KEYSTONE_NO_PALLAS", "1")
            w_scatter = fit()
            monkeypatch.delenv("KEYSTONE_NO_PALLAS")
            monkeypatch.setenv("KEYSTONE_PALLAS", "1")
            w_kernel = fit()
        np.testing.assert_allclose(w_kernel, w_scatter, rtol=1e-4, atol=1e-5)
