"""Tests for the wider solver library: PCA/ZCA, clustering, classifiers, KRR,
BWLS, cost-model selection (contracts from the reference's PCASuite,
ZCAWhitenerSuite, KMeansPlusPlusSuite, GMMSuite, NaiveBayesSuite, LDASuite,
KernelModelSuite, BlockWeightedLeastSquaresSuite, LeastSquaresEstimatorSuite).
"""

import numpy as np
import pytest

from keystone_tpu.data import Dataset
from keystone_tpu.data.loaders import synthetic_classification
from keystone_tpu.ops.learning import (
    ApproximatePCAEstimator,
    BlockWeightedLeastSquaresEstimator,
    DenseLBFGSwithL2,
    DistributedPCAEstimator,
    GaussianKernelGenerator,
    GaussianMixtureModelEstimator,
    KernelRidgeRegression,
    KMeansPlusPlusEstimator,
    LeastSquaresEstimator,
    LinearDiscriminantAnalysis,
    LinearMapEstimator,
    LogisticRegressionEstimator,
    NaiveBayesEstimator,
    PCAEstimator,
    ZCAWhitenerEstimator,
)
from keystone_tpu.ops.learning.cost import TransformerLabelEstimatorChain
from keystone_tpu.ops.util import ClassLabelIndicatorsFromIntLabels


class TestPCA:
    def setup_method(self):
        rng = np.random.default_rng(0)
        # Anisotropic data with a clear principal direction.
        base = rng.normal(size=(500, 8)) * np.array([10, 5, 2, 1, 0.5, 0.2, 0.1, 0.05])
        Q, _ = np.linalg.qr(rng.normal(size=(8, 8)))
        self.X = base @ Q + 3.0

    def numpy_pca(self, dims):
        Xc = self.X - self.X.mean(0)
        _, _, vt = np.linalg.svd(Xc, full_matrices=False)
        V = vt.T
        # matlab sign convention
        signs = np.where(V.max(0) == np.abs(V).max(0), 1.0, -1.0)
        return (V * signs)[:, :dims]

    def test_local_pca_matches_numpy(self):
        model = PCAEstimator(3).fit(Dataset.of(self.X))
        np.testing.assert_allclose(np.asarray(model.pca_mat), self.numpy_pca(3), atol=1e-8)

    def test_distributed_pca_matches_local(self, mesh8):
        local = PCAEstimator(3).fit(Dataset.of(self.X))
        dist = DistributedPCAEstimator(3).fit(Dataset.of(self.X).shard(mesh8))
        # Directions may differ in sign only if convention differs; compare projections.
        P1 = np.asarray(local.pca_mat)
        P2 = np.asarray(dist.pca_mat)
        np.testing.assert_allclose(np.abs(P1.T @ P2), np.eye(3), atol=1e-6)

    def test_approximate_pca_subspace(self):
        approx = ApproximatePCAEstimator(2, q=8, seed=1).fit(Dataset.of(self.X))
        exact = self.numpy_pca(2)
        P = np.asarray(approx.pca_mat)
        # Same subspace: projections align up to rotation.
        s = np.linalg.svd(exact.T @ P, compute_uv=False)
        np.testing.assert_allclose(s, 1.0, atol=1e-4)

    def test_zca_whitening_identity_covariance(self):
        model = ZCAWhitenerEstimator(eps=1e-8).fit_single(self.X)
        out = np.asarray(model.apply(self.X))
        cov = out.T @ out / (self.X.shape[0] - 1)
        np.testing.assert_allclose(cov, np.eye(8), atol=1e-2)


class TestClustering:
    def test_kmeans_recovers_blobs(self):
        rng = np.random.default_rng(1)
        centers = np.array([[5.0, 0.0], [-5.0, 0.0], [0.0, 6.0]])
        X = np.vstack([c + 0.3 * rng.normal(size=(100, 2)) for c in centers])
        model = KMeansPlusPlusEstimator(3, 20, seed=2).fit(Dataset.of(X))
        learned = np.asarray(model.means)
        # Each true center has a learned center within 0.3
        for c in centers:
            assert np.min(np.linalg.norm(learned - c, axis=1)) < 0.3
        # one-hot assignments
        assigns = model.batch_apply(Dataset.of(X)).to_numpy()
        assert assigns.shape == (300, 3)
        np.testing.assert_allclose(assigns.sum(1), 1.0)

    def test_gmm_recovers_blobs(self):
        rng = np.random.default_rng(3)
        X = np.vstack([
            np.array([4.0, 0.0]) + 0.5 * rng.normal(size=(200, 2)),
            np.array([-4.0, 0.0]) + 0.5 * rng.normal(size=(200, 2)),
        ])
        gmm = GaussianMixtureModelEstimator(2, max_iterations=50, seed=4).fit(Dataset.of(X))
        mu = np.asarray(gmm.means).T  # (k, d)
        for c in [np.array([4.0, 0.0]), np.array([-4.0, 0.0])]:
            assert np.min(np.linalg.norm(mu - c, axis=1)) < 0.3
        post = gmm.batch_apply(Dataset.of(X)).to_numpy()
        np.testing.assert_allclose(post.sum(1), 1.0, atol=1e-6)
        # First/second halves should be assigned to opposite components.
        assert (post[:200].argmax(1) == post[0].argmax()).mean() > 0.99


class TestClassifiers:
    def setup_method(self):
        self.train = synthetic_classification(600, 10, 3, seed=0)
        self.test = synthetic_classification(300, 10, 3, seed=1)

    def test_naive_bayes(self):
        # NB expects count-like nonneg features
        Xtr = np.abs(self.train.data.to_numpy())
        Xte = np.abs(self.test.data.to_numpy())
        model = NaiveBayesEstimator(3).fit(Dataset.of(Xtr), self.train.labels)
        preds = model.batch_apply(Dataset.of(Xte)).to_numpy().argmax(1)
        acc = (preds == self.test.labels.to_numpy()).mean()
        assert acc > 0.5

    def test_logistic_regression(self):
        model = LogisticRegressionEstimator(3, num_iters=100).fit(
            self.train.data, self.train.labels)
        preds = model.batch_apply(self.test.data).to_numpy()
        acc = (preds == self.test.labels.to_numpy()).mean()
        assert acc > 0.9

    def test_lda_separates(self):
        model = LinearDiscriminantAnalysis(2).fit(self.train.data, self.train.labels)
        proj = model.batch_apply(self.train.data).to_numpy()
        assert proj.shape == (600, 2)
        # Class means in projected space should be distinct.
        y = self.train.labels.to_numpy()
        means = np.stack([proj[y == c].mean(0) for c in range(3)])
        dists = np.linalg.norm(means[:, None] - means[None, :], axis=-1)
        assert dists[np.triu_indices(3, 1)].min() > 1.0


class TestKRR:
    def test_xor(self):
        X = np.array([[0.0, 0.0], [0.0, 1.0], [1.0, 0.0], [1.0, 1.0]] * 8)
        Y = np.array([[1.0, -1.0], [-1.0, 1.0], [-1.0, 1.0], [1.0, -1.0]] * 8)
        krr = KernelRidgeRegression(
            GaussianKernelGenerator(2.0), lam=0.01, block_size=16, num_epochs=4)
        model = krr.fit(Dataset.of(X), Dataset.of(Y))
        preds = model.batch_apply(Dataset.of(X)).to_numpy()
        assert (preds.argmax(1) == Y.argmax(1)).all()

    def test_matches_reference_gauss_seidel_iteration(self):
        """Exact parity with a host numpy block-Gauss-Seidel at equal epochs,
        including the ragged (clamp-prone) final block."""
        rng = np.random.default_rng(5)
        X = rng.normal(size=(60, 4))
        Y = rng.normal(size=(60, 2))
        gamma, lam, bs, epochs = 0.5, 0.1, 25, 8
        sq = ((X[:, None] - X[None, :]) ** 2).sum(-1)
        K = np.exp(-gamma * sq)

        W_ref = np.zeros((60, 2))
        for _ in range(epochs):
            for s in range(0, 60, bs):
                e = min(s + bs, 60)
                resid = K[:, s:e].T @ W_ref
                rhs = Y[s:e] - (resid - K[s:e, s:e].T @ W_ref[s:e])
                W_ref[s:e] = np.linalg.solve(K[s:e, s:e] + lam * np.eye(e - s), rhs)

        krr = KernelRidgeRegression(
            GaussianKernelGenerator(gamma), lam=lam, block_size=bs, num_epochs=epochs)
        model = krr.fit(Dataset.of(X), Dataset.of(Y))
        W = np.vstack([np.asarray(w) for w in model.w_locals])[:60]
        np.testing.assert_allclose(W, W_ref, atol=1e-9)

    def test_converges_to_closed_form(self):
        rng = np.random.default_rng(5)
        X = rng.normal(size=(60, 4))
        Y = rng.normal(size=(60, 2))
        gamma, lam = 0.5, 1.0
        sq = ((X[:, None] - X[None, :]) ** 2).sum(-1)
        K = np.exp(-gamma * sq)
        W_exact = np.linalg.solve(K + lam * np.eye(60), Y)
        krr = KernelRidgeRegression(
            GaussianKernelGenerator(gamma), lam=lam, block_size=25, num_epochs=40)
        model = krr.fit(Dataset.of(X), Dataset.of(Y))
        preds = model.batch_apply(Dataset.of(X)).to_numpy()
        np.testing.assert_allclose(preds, K @ W_exact, atol=1e-4)


class TestBWLS:
    def test_classifies_and_respects_weighting(self):
        train = synthetic_classification(400, 12, 4, seed=6)
        labels = ClassLabelIndicatorsFromIntLabels(4)(train.labels)
        est = BlockWeightedLeastSquaresEstimator(
            block_size=6, num_iter=2, lam=0.1, mixture_weight=0.5)
        model = est.fit(train.data, labels)
        preds = model.batch_apply(train.data).to_numpy().argmax(1)
        assert (preds == train.labels.to_numpy()).mean() > 0.95

    def test_weight(self):
        est = BlockWeightedLeastSquaresEstimator(4, 3, 0.1, 0.5)
        assert est.weight == 10

    @pytest.mark.slow
    def test_sharded_matches_unsharded(self, mesh8):
        """Rows stay on the mesh: a sharded fit must equal the local fit
        (round 2 removed the host-f64 round trip; stats are device segment
        sums over the class-sorted sharded rows)."""
        train = synthetic_classification(160, 8, 3, seed=11)
        labels = ClassLabelIndicatorsFromIntLabels(3)(train.labels)
        est = BlockWeightedLeastSquaresEstimator(
            block_size=4, num_iter=2, lam=0.1, mixture_weight=0.4)
        m_local = est.fit(train.data, labels)
        m_sharded = est.fit(train.data.shard(mesh8), labels.shard(mesh8))
        p_local = m_local.batch_apply(train.data).to_numpy()
        p_sharded = m_sharded.batch_apply(train.data).to_numpy()
        np.testing.assert_allclose(p_sharded, p_local, atol=1e-8)

    @pytest.mark.slow
    def test_mw_zero_close_to_unweighted(self):
        """mixture_weight→0 should approach the population (unweighted) solve."""
        train = synthetic_classification(300, 8, 3, seed=7)
        labels = ClassLabelIndicatorsFromIntLabels(3)(train.labels)
        bwls = BlockWeightedLeastSquaresEstimator(
            block_size=8, num_iter=8, lam=0.01, mixture_weight=1e-6)
        m1 = bwls.fit(train.data, labels)
        exact = LinearMapEstimator(0.01).fit(train.data, labels)
        p1 = m1.batch_apply(train.data).to_numpy()
        p2 = exact.batch_apply(train.data).to_numpy()
        assert (p1.argmax(1) == p2.argmax(1)).mean() > 0.98


class TestLeastSquaresEstimatorSelection:
    def test_picks_an_option_and_fits(self):
        train = synthetic_classification(200, 8, 2, seed=8)
        labels = ClassLabelIndicatorsFromIntLabels(2)(train.labels)
        est = LeastSquaresEstimator(lam=0.1)
        chosen = est.optimize(train.data, labels)
        assert chosen is not None
        model = chosen.fit(train.data, labels) if not isinstance(
            chosen, TransformerLabelEstimatorChain) else chosen.fit(train.data, labels)
        preds = model.batch_apply(train.data).to_numpy().argmax(1)
        assert (preds == train.labels.to_numpy()).mean() > 0.9

    def test_dense_default(self):
        est = LeastSquaresEstimator(lam=0.1)
        assert isinstance(est.default, DenseLBFGSwithL2)

    def test_sparse_data_changes_costs(self):
        """Sparsity drives the sparse solver's cost below the dense one at scale."""
        est = LeastSquaresEstimator(lam=0.1)
        dense_cost = est.options[0][0].cost(1e7, 1e5, 2, 1.0, 16, 3.8e-4, 2.9e-1, 1.32)
        sparse_cost = est.options[1][0].cost(1e7, 1e5, 2, 0.001, 16, 3.8e-4, 2.9e-1, 1.32)
        assert sparse_cost < dense_cost


class TestSampler:
    def test_samples_rows_without_replacement(self):
        import numpy as np
        from keystone_tpu.data import Dataset
        from keystone_tpu.ops.stats import Sampler

        X = np.arange(40, dtype=np.float32).reshape(20, 2)
        out = Sampler(8, seed=1)(Dataset.of(X)).to_numpy()
        assert out.shape == (8, 2)
        # Rows come from X, all distinct.
        rows = {tuple(r) for r in out}
        assert len(rows) == 8
        all_rows = {tuple(r) for r in X}
        assert rows <= all_rows

    def test_caps_at_dataset_size(self):
        import numpy as np
        from keystone_tpu.data import Dataset
        from keystone_tpu.ops.stats import Sampler

        X = np.ones((5, 3), dtype=np.float32)
        assert Sampler(100)(Dataset.of(X)).to_numpy().shape == (5, 3)


class TestSharedRfftEpilogue:
    """ISSUE 17 satellite: the pad→rfft→real-half epilogue lived as
    three inline copies in ops/stats.py (PaddedFFT.apply, its batch fn,
    the packed odd-branch tail) before ``rfft_real_half`` factored it;
    the SRHT engine is the fourth caller. Pin the shared helper against
    the naive construction and the batched path against the
    one-row-at-a-time path."""

    def test_rfft_real_half_matches_naive(self):
        import jax.numpy as jnp
        from keystone_tpu.ops.stats import padded_pow2, rfft_real_half

        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=24).astype(np.float32))
        p = padded_pow2(24)
        assert p == 32
        padded = jnp.pad(x, [(0, p - 24)])
        out = rfft_real_half(padded, p)
        naive = np.real(np.fft.fft(np.asarray(padded)))[: p // 2]
        np.testing.assert_allclose(np.asarray(out), naive, atol=1e-4)

    def test_padded_fft_batched_matches_single(self):
        from keystone_tpu.ops.stats import PaddedFFT

        rng = np.random.default_rng(1)
        X = rng.normal(size=(7, 45)).astype(np.float32)
        node = PaddedFFT()
        batched = np.asarray(node.device_fn()(X))
        singles = np.stack([np.asarray(node.apply(row)) for row in X])
        assert batched.shape == singles.shape == (7, 32)
        np.testing.assert_allclose(batched, singles, atol=1e-5)

    def test_srht_chunk_sketch_matches_dense_reference(self):
        import jax.numpy as jnp
        from keystone_tpu.ops.stats import (
            padded_pow2, rfft_real_half, srht_chunk_sketch,
        )

        rng = np.random.default_rng(2)
        c, d, m = 12, 5, 4
        rows = rng.normal(size=(c, d)).astype(np.float32)
        signs = rng.choice([-1.0, 1.0], size=c).astype(np.float32)
        p = padded_pow2(c)
        bins = rng.integers(0, p // 2, size=m)
        scale = float(np.sqrt(2.0 / m))
        out = srht_chunk_sketch(
            jnp.asarray(rows), jnp.asarray(signs), jnp.asarray(bins), scale
        )
        Z = np.zeros((p, d), np.float32)
        Z[:c] = rows * signs[:, None]
        F = np.real(np.fft.fft(Z, axis=0))[: p // 2]
        np.testing.assert_allclose(
            np.asarray(out), scale * F[bins], atol=1e-4)
