"""PCA family + ZCA whitening (reference: nodes/learning/PCA.scala:19-248,
DistributedPCA.scala:21-74, ApproximatePCA.scala:22-85, ZCAWhitener.scala:12-80).

Three PCA algorithms, mirroring the reference's optimizable set:
  - local SVD on collected data (PCAEstimator / sgesvd),
  - distributed via TSQR of the mean-centered sharded matrix then local SVD
    of R (DistributedPCAEstimator / mlmatrix TSQR),
  - randomized sketch (ApproximatePCAEstimator / Halko-Martinsson-Tropp).
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from keystone_tpu.data import Dataset
from keystone_tpu.parallel import linalg
from keystone_tpu.workflow import Estimator, Transformer
from keystone_tpu.workflow.optimizable import OptimizableEstimator


def enforce_matlab_sign_convention(pca):
    """Largest-|coefficient| element of each column gets a positive sign
    (reference: PCA.scala:238-247)."""
    pca = jnp.asarray(pca)
    col_max = jnp.max(pca, axis=0)
    abs_col_max = jnp.max(jnp.abs(pca), axis=0)
    signs = jnp.where(col_max == abs_col_max, 1.0, -1.0)
    return pca * signs[None, :]


def compute_pca(data, dims: int):
    """Principal directions of mean-centered rows: V[:, :dims] of the SVD,
    matlab sign convention (reference: PCA.scala:179-247)."""
    data = jnp.asarray(data)
    centered = data - jnp.mean(data, axis=0)
    _, _, vt = jnp.linalg.svd(centered, full_matrices=False)
    pca = enforce_matlab_sign_convention(vt.T)
    return pca[:, :dims]


class PCATransformer(Transformer):
    """x -> pcaMatᵀ x (reference: PCA.scala:19-30)."""

    def __init__(self, pca_mat):
        self.pca_mat = jnp.asarray(pca_mat)

    def apply(self, x):
        return jnp.asarray(x) @ self.pca_mat

    def batch_apply(self, data: Dataset) -> Dataset:
        return data.map_batch(lambda X: X @ self.pca_mat)


class BatchPCATransformer(Transformer):
    """Per-item (d, cols) matrix -> (dims, cols): pcaMatᵀ · in
    (reference: PCA.scala:37-43)."""

    def __init__(self, pca_mat):
        self.pca_mat = jnp.asarray(pca_mat)

    def apply(self, x):
        return self.pca_mat.T @ jnp.asarray(x)

    def batch_apply(self, data: Dataset) -> Dataset:
        if data.is_host:
            return Dataset.of([np.asarray(self.apply(x)) for x in data.to_list()])
        return data.map_batch(lambda X: jnp.einsum("dk,ndc->nkc", self.pca_mat, X))


class PCAEstimator(Estimator):
    """Local PCA: collect sample rows, SVD on device (reference: PCA.scala:163-231)."""

    def __init__(self, dims: int):
        self.dims = dims

    def fit(self, data: Dataset) -> PCATransformer:
        X = jnp.asarray(data.to_numpy() if data.is_host else data.array[: data.n])
        return PCATransformer(compute_pca(X, self.dims))

    def cost(self, n, d, k, sparsity, num_machines, cpu_w, mem_w, net_w) -> float:
        flops = n * d * d
        return max(cpu_w * flops, mem_w * n * d) + net_w * n * d


class DistributedPCAEstimator(Estimator):
    """PCA via TSQR of the mean-centered sharded matrix, then SVD of R
    (reference: DistributedPCA.scala:21-74; subsumes mlmatrix TSQR)."""

    def __init__(self, dims: int):
        self.dims = dims

    def fit(self, data: Dataset) -> PCATransformer:
        X = jnp.asarray(data.array)
        mean = jnp.sum(X, axis=0) / data.n
        centered = X - mean
        # Re-zero padding rows (centering made them -mean).
        centered = centered * (jnp.arange(X.shape[0]) < data.n)[:, None].astype(X.dtype)
        R = linalg.tsqr_r(centered, data.mesh)
        _, _, vt = jnp.linalg.svd(R, full_matrices=False)
        pca = enforce_matlab_sign_convention(vt.T)
        return PCATransformer(pca[:, : self.dims])

    def cost(self, n, d, k, sparsity, num_machines, cpu_w, mem_w, net_w) -> float:
        flops = 2.0 * n * d * d / num_machines + (d ** 3) * math.log(max(num_machines, 2), 2)
        network = d * d * math.log(max(num_machines, 2), 2)
        return max(cpu_w * flops, mem_w * n * d / num_machines) + net_w * network


class ApproximatePCAEstimator(Estimator):
    """Randomized PCA, Halko-Martinsson-Tropp alg 4.4/5.1: Gaussian sketch +
    q power iterations of QR (reference: ApproximatePCA.scala:22-85)."""

    def __init__(self, dims: int, q: int = 10, p: int = 5, seed: int = 0):
        self.dims = dims
        self.q = q
        self.p = p
        self.seed = seed

    def fit(self, data: Dataset) -> PCATransformer:
        X = jnp.asarray(data.array)
        mean = jnp.sum(X, axis=0) / data.n
        A = (X - mean) * (jnp.arange(X.shape[0]) < data.n)[:, None].astype(X.dtype)
        l = self.dims + self.p
        omega = jax.random.normal(jax.random.key(self.seed), (A.shape[1], l), dtype=A.dtype)
        Y = A @ omega
        Q, _ = jnp.linalg.qr(Y)
        for _ in range(self.q):
            Z = A.T @ Q
            Qz, _ = jnp.linalg.qr(Z)
            Y = A @ Qz
            Q, _ = jnp.linalg.qr(Y)
        B = Q.T @ A  # (l, d)
        _, _, vt = jnp.linalg.svd(B, full_matrices=False)
        pca = enforce_matlab_sign_convention(vt.T)
        return PCATransformer(pca[:, : self.dims])

    def cost(self, n, d, k, sparsity, num_machines, cpu_w, mem_w, net_w) -> float:
        flops = n * d * (self.dims + self.p) * (self.q + 1) / num_machines
        return max(cpu_w * flops, mem_w * n * d / num_machines) + net_w * d * (self.dims + self.p)


class LocalColumnPCAEstimator(Estimator):
    """Column-matrix PCA, local SVD: items are (d, cols) matrices whose columns
    are treated as points (reference: PCA.scala:45-77)."""

    def __init__(self, dims: int):
        self.dims = dims

    def fit(self, data: Dataset) -> BatchPCATransformer:
        cols = np.concatenate([np.asarray(x).T for x in data.to_list()], axis=0)
        return BatchPCATransformer(compute_pca(cols, self.dims))


class DistributedColumnPCAEstimator(Estimator):
    """Column-matrix PCA via the distributed path (reference: PCA.scala:79-116)."""

    def __init__(self, dims: int):
        self.dims = dims

    def fit(self, data: Dataset) -> BatchPCATransformer:
        cols = np.concatenate([np.asarray(x).T for x in data.to_list()], axis=0)
        ds = Dataset.of(cols)
        pca = DistributedPCAEstimator(self.dims).fit(ds)
        return BatchPCATransformer(pca.pca_mat)


class ColumnPCAEstimator(OptimizableEstimator):
    """Optimizable column PCA: sample-driven local-vs-distributed choice
    (reference: PCA.scala:118-156)."""

    def __init__(
        self,
        dims: int,
        num_machines: Optional[int] = None,
        cpu_weight: float = 3.8e-4,
        mem_weight: float = 2.9e-1,
        network_weight: float = 1.32,
    ):
        self.dims = dims
        self.num_machines = num_machines
        self.cpu_weight = cpu_weight
        self.mem_weight = mem_weight
        self.network_weight = network_weight
        self._local = LocalColumnPCAEstimator(dims)
        self._distributed = DistributedColumnPCAEstimator(dims)

    @property
    def default(self):
        return self._distributed

    def optimize(self, sample: Dataset):
        items = sample.to_list()
        if not items:
            return None
        d = np.asarray(items[0]).shape[0]
        cols_per_item = float(np.mean([np.asarray(x).shape[1] for x in items]))
        n = int(cols_per_item * getattr(sample, "total_n", sample.n))
        machines = self.num_machines or max(len(jax.devices()), 1)
        local_cost = PCAEstimator(self.dims).cost(
            n, d, self.dims, 1.0, machines,
            self.cpu_weight, self.mem_weight, self.network_weight)
        dist_cost = DistributedPCAEstimator(self.dims).cost(
            n, d, self.dims, 1.0, machines,
            self.cpu_weight, self.mem_weight, self.network_weight)
        return self._local if local_cost < dist_cost else self._distributed


class ZCAWhitener(Transformer):
    """(in − means) · whitener on per-item (rows, d) matrices
    (reference: ZCAWhitener.scala:12-18)."""

    def __init__(self, whitener, means):
        self.whitener = jnp.asarray(whitener)
        self.means = jnp.asarray(means)

    def apply(self, x):
        return (jnp.asarray(x) - self.means) @ self.whitener

    def batch_apply(self, data: Dataset) -> Dataset:
        return data.map_batch(lambda X: (X - self.means) @ self.whitener)


class ZCAWhitenerEstimator(Estimator):
    """V·diag((s²/(n−1)+ε)^−½)·Vᵀ from the SVD of the centered sample
    (reference: ZCAWhitener.scala:30-80)."""

    def __init__(self, eps: float = 0.1):
        self.eps = eps

    def fit(self, data: Dataset) -> ZCAWhitener:
        # The reference fits on the first item (a sample matrix).
        first = data.to_list()[0] if data.is_host else np.asarray(data.array[0])
        return self.fit_single(jnp.asarray(first))

    def fit_single(self, X) -> ZCAWhitener:
        """Traceable: runs inside a caller's program. A sample with at
        least as many rows as columns takes the eigendecomposition of its
        (d, d) covariance, whose eigenvalues are the SVD's s²/(n−1) and its
        eigenvectors the SVD's V — the same whitener, where the SVD of a
        tall (n, d) matrix compiles for minutes on a TPU (100,000 × 108:
        241 s against 4.6, compiled for a v5e). A wider sample keeps the
        SVD, whose whitener spans the sample's rows alone."""
        X = jnp.asarray(X)
        means = jnp.mean(X, axis=0)
        centered = X - means
        n, d = X.shape
        if n >= d:
            cov = jax.lax.dot_general(
                centered, centered, (((0,), (0,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST,
            ) / (n - 1.0)
            lam, V = jnp.linalg.eigh(cov)
            scaled = (jnp.maximum(lam, 0.0) + self.eps) ** -0.5
            return ZCAWhitener((V * scaled[None, :]) @ V.T, means)
        _, s, vt = jnp.linalg.svd(centered, full_matrices=False)
        s2 = (s * s) / (X.shape[0] - 1.0)
        scaled = jnp.diag((s2 + self.eps) ** -0.5)
        whitener = vt.T @ scaled @ vt
        return ZCAWhitener(whitener, means)


def _zca_cov_fold(sums, gram, X):
    """One segment's contribution to (Σx, XᵀX). Exact-f32 gram (HIGHEST:
    the eigendecomposition downstream amplifies covariance error by
    (λ+ε)^−3/2); zero-padded tail rows contribute zero to both terms, so
    no masking is needed — only the true-row count matters."""
    sums = sums + jnp.sum(X, axis=0)
    gram = gram + jax.lax.dot_general(
        X, X,
        dimension_numbers=(((0,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )
    return sums, gram


class StreamedZCAWhitenerEstimator(Estimator):
    """ZCA whitening as a streamed covariance fold over a
    :class:`~keystone_tpu.data.prefetch.ShardSource` — the out-of-core
    form of :class:`ZCAWhitenerEstimator` for patch sets that never fit
    in host RAM.

    Algebra: the batch estimator's SVD singular values satisfy
    s²/(n−1) = eigvals of the centered covariance, so folding
    (Σx, XᵀX, n) and finalizing with

        μ = Σx/n,  C = (XᵀX − n·μμᵀ)/(n−1),  C = V·Λ·Vᵀ,
        whitener = V·diag((Λ+ε)^−½)·Vᵀ

    reproduces ``fit_single`` up to eigenbasis roundoff (pinned in
    tests/test_zca_stream.py). The fold rides the standard streaming
    stack: segments arrive through ``iter_segments`` (prefetched on the
    read lane), and the (Σx, XᵀX, n) carry snapshots through
    :class:`~keystone_tpu.data.durable.CheckpointSpec` — a fit killed
    mid-stream and re-run with the same spec resumes BIT-IDENTICALLY
    (chaos-marked test, same discipline as the streamed gram solvers).
    """

    def __init__(
        self,
        eps: float = 0.1,
        checkpoint=None,
        prefetch_depth: int = 2,
    ):
        self.eps = eps
        self.checkpoint = checkpoint
        self.prefetch_depth = prefetch_depth

    def fit(self, data: Dataset) -> ZCAWhitener:
        if getattr(data, "is_shard_backed", False):
            return self.fit_source(data.shard_source)
        X = jnp.asarray(data.to_numpy() if data.is_host else data.array[: data.n])
        return ZCAWhitenerEstimator(self.eps).fit_single(X)

    def fit_source(self, source, stats=None) -> ZCAWhitener:
        """Fold (Σx, XᵀX, n) over the source's segments and finalize.

        Segment payloads may be ``(X, Y, valid_rows)`` triples (the
        DenseShardSource / image-tier contract; X is flattened to rows)
        or bare row blocks — those count all rows as true, clamped
        against the source's declared ``n_true``: fixed-shape shard
        views (``DenseShardView``) zero-pad the tail segment, and pad
        rows are zero in (Σx, XᵀX) but must not inflate ``n`` or the
        mean/covariance shrink toward zero."""
        from keystone_tpu.data.durable import (
            resolve_checkpoint,
            source_fingerprint,
        )
        from keystone_tpu.data.prefetch import iter_segments

        checkpoint = resolve_checkpoint(self.checkpoint)
        num_segments = int(source.num_segments)

        # Row width from the source's shape metadata when it has any
        # (EncodedImageSource.d, DenseShardSource.d_in, DenseShardView
        # .width). load(0) is only the fallback for bare sources: on an
        # image source it would decode a whole extra segment — and fire
        # the decode/augment fault sites once more — even when a
        # checkpoint restore resumes past segment 0.
        d = next(
            (
                int(v)
                for attr in ("d", "d_in", "width")
                if (v := getattr(source, attr, None)) is not None
            ),
            None,
        )
        if d is None:
            d = int(self._rows(source.load(0))[0].shape[-1])

        sums = jnp.zeros((d,), jnp.float32)
        gram = jnp.zeros((d, d), jnp.float32)
        count = 0
        start_seg = 0
        fingerprint = None
        if checkpoint is not None:
            fingerprint = {
                "kind": "zca_stream",
                "eps": float(self.eps),
                "d": d,
                "num_segments": num_segments,
                "source": source_fingerprint(source),
            }
            arrays, start_seg = checkpoint.restore(fingerprint)
            if arrays is not None:
                sums = jnp.asarray(arrays[0])
                gram = jnp.asarray(arrays[1])
                count = int(np.asarray(arrays[2])[0])

        fold = jax.jit(_zca_cov_fold)
        n_true = getattr(source, "n_true", None)
        for s, payload in iter_segments(
            source,
            prefetch_depth=self.prefetch_depth,
            stats=stats,
            start=start_seg,
        ):
            X, valid = self._rows(payload)
            sums, gram = fold(sums, gram, jnp.asarray(X, jnp.float32))
            if n_true is not None:
                valid = min(valid, int(n_true) - count)
            count += valid
            if checkpoint is not None:
                checkpoint.maybe_save(
                    [sums, gram, np.asarray([count], np.int64)],
                    s, num_segments, fingerprint, stats=stats,
                )
        if checkpoint is not None:
            checkpoint.clear(fingerprint)
        return self._finalize(sums, gram, count)

    @staticmethod
    def _rows(payload):
        """Normalize a segment payload to (rows (r, d), valid_count)."""
        if isinstance(payload, tuple):
            X = np.asarray(payload[0])
            valid = int(payload[2]) if len(payload) > 2 else X.shape[0]
        else:
            X = np.asarray(payload)
            valid = X.shape[0]
        return X.reshape(-1, X.shape[-1]), valid

    def _finalize(self, sums, gram, n: int) -> ZCAWhitener:
        if n < 2:
            raise ValueError(f"streamed ZCA needs n >= 2 rows, saw {n}")
        means = sums / n
        cov = (gram - n * jnp.outer(means, means)) / (n - 1.0)
        lam, V = jnp.linalg.eigh(cov)
        # eigh of a PSD-up-to-roundoff covariance can return tiny
        # negative eigenvalues; clamp before the inverse square root.
        scaled = (jnp.maximum(lam, 0.0) + self.eps) ** -0.5
        whitener = (V * scaled[None, :]) @ V.T
        return ZCAWhitener(whitener, means)

    def cost(self, n, d, k, sparsity, num_machines, cpu_w, mem_w, net_w) -> float:
        flops = n * d * d + d ** 3
        # Streaming holds one (d, d) gram + a segment, not the n×d matrix.
        return max(cpu_w * flops, mem_w * d * d) + net_w * d * d
