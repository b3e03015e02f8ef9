"""Dense linear models and exact least-squares estimators.

Reference: nodes/learning/LinearMapper.scala (apply + NormalEquations solve),
nodes/learning/LocalLeastSquaresEstimator.scala (collect-and-solve).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from keystone_tpu.data import Dataset
from keystone_tpu.ops.stats import StandardScaler, StandardScalerModel
from keystone_tpu.parallel import linalg
from keystone_tpu.workflow import LabelEstimator, Transformer


def affine_apply(params, X):
    """``((X - mean) / std) @ W + b`` with ``params = (W, b, mean, std)``,
    any of the last three None: the operand-form computation
    (``Transformer.device_apply``) of the dense linear models."""
    W, b, mean, std = params
    if mean is not None:
        X = X - mean
    if std is not None:
        X = X / std
    out = X @ W
    return out if b is None else out + b


class LinearMapper(Transformer):
    """x -> xᵀX + b, with optional feature scaling
    (reference: LinearMapper.scala:45-62)."""

    def __init__(self, x, b_opt=None, feature_scaler: Optional[StandardScalerModel] = None):
        self.x = jnp.asarray(x)
        self.b_opt = None if b_opt is None else jnp.asarray(b_opt)
        self.feature_scaler = feature_scaler

    def apply(self, v):
        v = jnp.asarray(v)
        if self.feature_scaler is not None:
            v = self.feature_scaler.apply(v)
        out = v @ self.x
        if self.b_opt is not None:
            out = out + self.b_opt
        return out

    def batch_apply(self, data: Dataset) -> Dataset:
        return data.map_batch(self.apply)

    def device_operands(self):
        """Stage-fusion contract: center-scale + GEMM + intercept as one
        row-local array function, so apply chains fuse through the model.
        Weights, intercept and the scaler's mean/std ride as arguments
        (absent ones as None), so every refit of one geometry applies
        through one compiled chain. A feature scaler that is not a
        mean/std scaler has no such form: the model then applies as a
        node of its own (``batch_apply``)."""
        scaler = self.feature_scaler
        if scaler is not None and type(scaler) is not StandardScalerModel:
            return None
        mean, std = (None, None) if scaler is None else (scaler.mean, scaler.std)
        return (), (self.x, self.b_opt, mean, std)

    @staticmethod
    def device_apply(static_key, params, X):
        return affine_apply(params, X)


class SparseLinearMapper(Transformer):
    """Sparse-input dense-model apply: ``out = X W + b`` over padded-COO
    batches via a model-row gather + nnz reduction — the design matrix is
    never densified (reference: SparseLinearMapper.scala:13-50, the apply
    used by SparseLBFGS's fitted models). Dense inputs fall through to a
    plain GEMM so the mapper slots anywhere a LinearMapper does.
    """

    def __init__(self, x, b_opt=None):
        self.x = jnp.asarray(x)
        self.b_opt = None if b_opt is None else jnp.asarray(b_opt)

    def apply(self, v):
        if isinstance(v, dict) and set(v.keys()) == {"indices", "values"}:
            idx = np.asarray(v["indices"])
            val = np.asarray(v["values"])
            # Drop out-of-range indices on both sides, matching
            # sparse_matmul's documented drop semantics (a bare idx >= 0
            # would clamp idx >= d to the last model row under JAX fancy
            # indexing and add a spurious contribution).
            m = (idx >= 0) & (idx < self.x.shape[0])
            out = jnp.asarray(val[m]) @ self.x[jnp.asarray(idx[m])]
        else:
            out = jnp.asarray(v) @ self.x
        if self.b_opt is not None:
            out = out + self.b_opt
        return out

    def batch_apply(self, data: Dataset) -> Dataset:
        from keystone_tpu.ops.sparse import is_sparse_dataset, sparse_matmul

        if is_sparse_dataset(data):
            out = sparse_matmul(
                jnp.asarray(data.data["indices"]),
                jnp.asarray(data.data["values"]),
                self.x,
            )
            if self.b_opt is not None:
                out = out + self.b_opt
            return Dataset(out, n=data.n, mesh=data.mesh)._rezero_padding()
        return data.map_batch(self.apply)


class LinearMapEstimator(LabelEstimator):
    """Exact OLS/ridge via distributed normal equations
    (reference: LinearMapper.scala:64-98): mean-center features and labels,
    solve (AᵀA + λI) X = AᵀB, keep the label mean as intercept."""

    def __init__(self, lam: Optional[float] = None):
        self.lam = lam

    def device_fit_fn(self):
        """Fit-fusion contract (workflow/fusion.py): mean-centering + the
        normal-equations solve as one traceable function, so upstream
        featurization compiles INTO the fit (same pattern as
        BlockLeastSquaresEstimator.device_fit_fn)."""
        from keystone_tpu.parallel.linalg import _solve_psd
        from keystone_tpu.workflow.fusion import DeviceFit, masked_center

        def fit_fn(F, Y, n_true: int, lam):
            Fc, Yc, fmean, ymean = masked_center(F, Y, n_true)
            Yc = Yc.astype(Fc.dtype)
            # Same normal-equations kernel body as the materialized-
            # features fit(), with λ as a traced operand (λ-sweeps share
            # one compiled program).
            gram = Fc.T @ Fc
            corr = Fc.T @ Yc
            x = _solve_psd(gram, corr, jnp.asarray(lam, Fc.dtype))
            return x, fmean, ymean

        def build(params):
            x, fmean, ymean = params
            return LinearMapper(
                x, b_opt=ymean, feature_scaler=StandardScalerModel(fmean)
            )

        return DeviceFit(
            fit_fn, build,
            operands=(jnp.asarray(float(self.lam or 0.0), jnp.float32),),
            program_key=("LinearMap",),
        )

    def fit(self, data: Dataset, labels: Dataset) -> LinearMapper:
        feature_scaler = StandardScaler(normalize_std_dev=False).fit(data)
        label_scaler = StandardScaler(normalize_std_dev=False).fit(labels)

        A = jnp.asarray(feature_scaler.batch_apply(data).array)
        B = jnp.asarray(label_scaler.batch_apply(labels).array)

        x = linalg.normal_equations_solve(A, B, self.lam or 0.0)
        return LinearMapper(x, b_opt=label_scaler.mean, feature_scaler=feature_scaler)

    def cost(
        self, n, d, k, sparsity, num_machines, cpu_weight, mem_weight, network_weight
    ) -> float:
        """Analytic cost model (LinearMapper.scala:100-115)."""
        flops = n * d * (d + k) / num_machines
        bytes_scanned = n * d / num_machines + d * d
        network = d * (d + k)
        return max(cpu_weight * flops, mem_weight * bytes_scanned) + network_weight * network

    def resident_bytes(self, n, d, k, sparsity, num_machines) -> float:
        """Capacity model: the matrix plus its centered copy (f32), labels,
        and the Gramian with its Cholesky factor."""
        return (
            8.0 * n * d / num_machines
            + 8.0 * n * k / num_machines
            + 8.0 * d * d
        )

    @staticmethod
    def compute_cost(data: Dataset, labels: Dataset, lam: float, x, b_opt=None) -> float:
        """Ridge loss ||Ax+b - y||²/(2n) + λ/2 ||x||²
        (reference: LinearMapper.scala:124-160)."""
        X = jnp.asarray(data.array)
        Y = jnp.asarray(labels.array)
        preds = X @ jnp.asarray(x)
        if b_opt is not None:
            preds = preds + jnp.asarray(b_opt)
        # Padding rows are zero in X and Y; (0@x + b) - 0 would pollute the sum,
        # so mask to real rows.
        mask = data.valid_mask().astype(preds.dtype)[:, None]
        cost = jnp.sum(((preds - Y) * mask) ** 2) / (2.0 * data.n)
        if lam != 0:
            cost = cost + lam / 2.0 * jnp.sum(jnp.asarray(x) ** 2)
        return float(cost)


class LocalLeastSquaresEstimator(LabelEstimator):
    """Collect-to-host exact least squares via LAPACK lstsq
    (reference: LocalLeastSquaresEstimator.scala:16-61)."""

    def __init__(self, lam: float = 0.0):
        self.lam = lam

    def fit(self, data: Dataset, labels: Dataset) -> LinearMapper:
        A = np.asarray(data.to_numpy(), dtype=np.float64)
        B = np.asarray(labels.to_numpy(), dtype=np.float64)
        a_mean = A.mean(axis=0)
        b_mean = B.mean(axis=0)
        A = A - a_mean
        B = B - b_mean
        if self.lam > 0:
            d = A.shape[1]
            A = np.vstack([A, np.sqrt(self.lam) * np.eye(d)])
            B = np.vstack([B, np.zeros((d, B.shape[1]))])
        x, *_ = np.linalg.lstsq(A, B, rcond=None)
        return LinearMapper(
            x, b_opt=b_mean, feature_scaler=StandardScalerModel(a_mean)
        )


class SketchedLeastSquaresEstimator(LabelEstimator):
    """Randomized (sketch-and-solve) least squares with optional iterative
    Hessian-sketch refinement.

    Beyond-parity solver motivated by the randomized NLA literature
    (Drineas et al., "Faster Least Squares Approximation", arXiv:0710.1435;
    Pilanci & Wainwright iterative Hessian sketch, cf. arXiv:1910.14166):
    a CountSketch S with m = sketch_factor*d rows compresses (A, B) in ONE
    bandwidth-bound pass — a segment-sum scatter of sign-flipped rows, O(nd)
    versus the normal equations' O(nd²) MXU work — then the m×d sketched
    system solves locally. ``refine_iters`` Hessian-sketch steps close the
    gap to the exact solution using the sketched Gramian as a preconditioner
    with exact full-data gradients (each an O(ndk) pass).

    TPU-native: the scatter is ``jax.ops.segment_sum`` over the sharded row
    axis, with per-row signs/buckets drawn once from the JAX PRNG (two
    n-length vectors — the m×n sketch matrix itself is never formed).
    Refinement is guarded: iterates whose gradient norm stops shrinking are
    rejected, so a poor sketch degrades gracefully to the plain
    sketch-and-solve answer instead of diverging.
    """

    def __init__(
        self,
        lam: float = 0.0,
        sketch_factor: int = 8,
        refine_iters: int = 2,
        seed: int = 0,
    ):
        self.lam = lam
        self.sketch_factor = sketch_factor
        self.refine_iters = refine_iters
        self.seed = seed

    def fit(self, data: Dataset, labels: Dataset) -> LinearMapper:
        import jax

        feature_scaler = StandardScaler(normalize_std_dev=False).fit(data)
        label_scaler = StandardScaler(normalize_std_dev=False).fit(labels)
        A = jnp.asarray(feature_scaler.batch_apply(data).array)
        B = jnp.asarray(label_scaler.batch_apply(labels).array)
        n_pad, d = A.shape
        n = data.n
        m = min(max(self.sketch_factor * d, d + 1), max(n, d + 1))

        key = jax.random.key(self.seed)
        kb, ks = jax.random.split(key)
        buckets = jax.random.randint(kb, (n_pad,), 0, m)
        signs = jax.random.rademacher(ks, (n_pad,), dtype=A.dtype)
        # Padding rows are zero, so their scattered contribution is zero.
        SA = jax.ops.segment_sum(A * signs[:, None], buckets, num_segments=m)
        SB = jax.ops.segment_sum(B * signs[:, None], buckets, num_segments=m)

        # One factorization serves both the initial sketched solve and the
        # refinement preconditioner.
        gram_s = SA.T @ SA + (self.lam + 1e-8) * jnp.eye(d, dtype=A.dtype)
        chol = jax.scipy.linalg.cholesky(gram_s, lower=True)
        x = jax.scipy.linalg.cho_solve((chol, True), SA.T @ SB)

        # Iterative Hessian sketch refinement: exact gradient, sketched
        # Hessian. x ← x − H_s⁻¹ (Aᵀ(Ax − B) + λx). Guarded: a step is only
        # accepted while the gradient norm shrinks (an undamped fixed point
        # can diverge when the sketch approximates the Gramian poorly).
        prev_gnorm = None
        for _ in range(max(self.refine_iters, 0)):
            grad = A.T @ (A @ x - B) + self.lam * x
            gnorm = float(jnp.linalg.norm(grad))
            if prev_gnorm is not None and gnorm >= prev_gnorm:
                break
            prev_gnorm = gnorm
            x = x - jax.scipy.linalg.cho_solve((chol, True), grad)

        return LinearMapper(x, b_opt=label_scaler.mean, feature_scaler=feature_scaler)

    def cost(
        self, n, d, k, sparsity, num_machines, cpu_weight, mem_weight, network_weight
    ) -> float:
        """Sketch pass O(nd) + local solve O(m d²) + refinement passes O(ndk),
        with the same m clamp fit() applies and a per-iteration d*k gradient
        all-reduce in the network term."""
        m = min(max(self.sketch_factor * d, d + 1), max(n, d + 1))
        flops = (n * d + m * d * d + self.refine_iters * n * d * k) / num_machines
        bytes_scanned = (1 + self.refine_iters) * n * d / num_machines
        network = d * (d + k) + self.refine_iters * d * k
        return max(cpu_weight * flops, mem_weight * bytes_scanned) + network_weight * network

    def resident_bytes(self, n, d, k, sparsity, num_machines) -> float:
        """Capacity model: the matrix, the (m, d) sketch, and the sketched
        Gramian + factor."""
        m = min(max(self.sketch_factor * d, d + 1), max(n, d + 1))
        return (
            4.0 * n * d / num_machines
            + 4.0 * n * k / num_machines
            + 4.0 * m * d
            + 8.0 * d * d
        )
