"""Block-partitioned linear models and the block least squares solver.

Reference: nodes/learning/BlockLinearMapper.scala — the model is a sequence of
per-feature-block weight matrices; applying it sums per-block GEMM partial
products plus an intercept; fitting runs block coordinate descent with L2
(via the in-tree BCD of :mod:`keystone_tpu.parallel.linalg`, subsuming mlmatrix
``BlockCoordinateDescent`` + ``NormalEquations``).

This is the reference's model-parallel axis: feature blocks over devices map
to the mesh ``model`` axis, while rows stay sharded over ``data``.
"""

from __future__ import annotations

from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp

from keystone_tpu import obs
from keystone_tpu.data import Dataset
from keystone_tpu.ops.learning.linear import affine_apply
from keystone_tpu.ops.stats import StandardScaler
from keystone_tpu.ops.util import VectorSplitter
from keystone_tpu.parallel import linalg
from keystone_tpu.utils.startup import device_memory_limit
from keystone_tpu.workflow import LabelEstimator, Transformer


class BlockLinearMapper(Transformer):
    """Apply a block-partitioned linear model: sum per-block GEMMs + intercept
    (reference: BlockLinearMapper.scala:22-138)."""

    def __init__(
        self,
        xs: Sequence,
        block_size: int,
        b_opt=None,
        feature_scalers: Optional[Sequence[Transformer]] = None,
    ):
        self.xs = [jnp.asarray(x) for x in xs]
        self.block_size = block_size
        self.b_opt = None if b_opt is None else jnp.asarray(b_opt)
        self.feature_scalers = feature_scalers
        self.splitter = VectorSplitter(block_size)

    def _scaled_block(self, block, i: int):
        if self.feature_scalers is None:
            return block
        return self.feature_scalers[i].apply(block)

    def apply(self, x):
        blocks = self.splitter.split_vector(x)
        out = sum(
            self._scaled_block(blk, i) @ self.xs[i] for i, blk in enumerate(blocks)
        )
        if self.b_opt is not None:
            out = out + self.b_opt
        return out

    def _flat_params(self):
        """``(W_flat, b, mean, std)`` of the whole blockwise model as one
        flat affine map (``linear.affine_apply``), or None when a feature
        scaler is not a mean/std scaler."""
        W_flat = jnp.concatenate(list(self.xs), axis=0)
        mean = std = None
        if self.feature_scalers is not None:
            if any(getattr(s, "mean", None) is None for s in self.feature_scalers):
                return None  # non-scaler transformers: keep the block path
            mean = jnp.concatenate(
                [jnp.asarray(s.mean) for s in self.feature_scalers]
            )
            stds = [getattr(s, "std", None) for s in self.feature_scalers]
            if any(s is not None for s in stds):
                std = jnp.concatenate(
                    [
                        jnp.ones_like(jnp.asarray(self.feature_scalers[i].mean))
                        if stds[i] is None else jnp.asarray(stds[i])
                        for i in range(len(stds))
                    ]
                )
        return W_flat, self.b_opt, mean, std

    def device_operands(self):
        """Stage-fusion contract: the whole blockwise model as one
        row-local array function — center by the concatenated means, one
        flat GEMM, add the intercept — so the apply path fuses with an
        upstream featurize program into a single dispatch. The flat model
        rides as arguments, so every refit of one geometry applies
        through one compiled chain."""
        params = self._flat_params()
        return None if params is None else ((), params)

    @staticmethod
    def device_apply(static_key, params, X):
        return affine_apply(params, X)

    def batch_apply(self, data: Dataset) -> Dataset:
        blocks = self.splitter.apply(data)
        return self.apply_blocks(blocks)

    def apply_blocks(self, blocks: List[Dataset]) -> Dataset:
        """Apply to pre-split feature blocks (BlockLinearMapper.scala:50-73)."""
        first = blocks[0]
        out = None
        for i, block in enumerate(blocks):
            X = jnp.asarray(block.array)
            if self.feature_scalers is not None:
                X = X - self.feature_scalers[i].mean
                if self.feature_scalers[i].std is not None:
                    X = X / self.feature_scalers[i].std
            partial = X @ self.xs[i]
            out = partial if out is None else out + partial
        if self.b_opt is not None:
            out = out + self.b_opt
        result = Dataset(out, n=first.n, mesh=first.mesh)
        return result._rezero_padding()

    def apply_and_evaluate(self, data: Dataset, evaluator) -> None:
        """Stream per-block partial predictions to an evaluator callback
        (BlockLinearMapper.scala:95-137)."""
        blocks = self.splitter.apply(data)
        acc = None
        for i, block in enumerate(blocks):
            X = jnp.asarray(block.array)
            if self.feature_scalers is not None:
                X = X - self.feature_scalers[i].mean
                if self.feature_scalers[i].std is not None:
                    X = X / self.feature_scalers[i].std
            partial = X @ self.xs[i]
            acc = partial if acc is None else acc + partial
            preds = acc if self.b_opt is None else acc + self.b_opt
            evaluator(Dataset(preds, n=data.n, mesh=data.mesh)._rezero_padding())


# An eager ``jnp.stack`` dispatches a lifting copy a block and one
# concatenate: a full second copy of the blocks beside the stack, in programs
# that take no name scope from their caller. Stacked inside one program the
# blocks are read once into the stack, under the scope ``ks.stack``.


@jax.jit
def _stack_blocks(*A_blocks):
    """``jnp.stack(A_blocks)``: the blocks as one (blocks, n, width) array."""
    with jax.named_scope("ks.stack"):
        return jnp.stack(A_blocks)


@jax.jit
def _unstacked(W_stack):
    """The stacked blocks' weights one by one, in one program (an eager
    ``W_stack[i]`` sends ``i`` from the host)."""
    return tuple(W_stack[i] for i in range(W_stack.shape[0]))


def _stack_fits_memory(A_blocks, num_iter: int) -> bool:
    """True when the fused path's transient peak fits comfortably in device
    memory. At stack time up to THREE full-size copies of the feature blocks
    are live (the unscaled splits, the scaled list, and the stack), plus the
    multi-epoch Gramian stash (nb * d_b^2)."""
    limit = device_memory_limit()
    if limit is None:
        return True  # CPU test mesh reports no memory limit: no constraint
    total = sum(int(a.nbytes) for a in A_blocks)
    stash = 0
    if num_iter > 1 and A_blocks:
        d_b = int(A_blocks[0].shape[1])
        stash = len(A_blocks) * d_b * d_b * max(A_blocks[0].dtype.itemsize, 4)
    return 3 * total + stash < 0.6 * limit


class BlockLeastSquaresEstimator(LabelEstimator):
    """Block coordinate descent ridge regression
    (reference: BlockLinearMapper.scala:199-283).

    Label and per-block feature mean-centering via StandardScaler
    (normalize_std_dev=False), then Gauss-Seidel BCD over feature blocks;
    weight = 3*num_iter + 1 passes over the input.
    """

    def __init__(
        self,
        block_size: int,
        num_iter: int,
        lam: float = 0.0,
        num_features: Optional[int] = None,
    ):
        self.block_size = block_size
        self.num_iter = num_iter
        self.lam = lam
        self.num_features = num_features

    @property
    def weight(self) -> int:
        return 3 * self.num_iter + 1

    def device_fit_fn(self):
        """Fit-fusion contract (workflow/fusion.py): the whole fit —
        feature/label mean-centering + the fused-flat BCD sweep — as one
        traceable function, so the optimizer can compile upstream
        featurization INTO it (featurize + solve = ONE program; the
        feature matrix never materializes between dispatches)."""
        from keystone_tpu.workflow.fusion import DeviceFit, masked_center
        from keystone_tpu.ops.stats import StandardScalerModel

        bs, num_iter = self.block_size, self.num_iter

        def fit_fn(F, Y, n_true: int, lam):
            Fc, Yc, fmean, ymean = masked_center(F, Y, n_true)
            W_stack = linalg.bcd_least_squares_fused_flat(
                Fc, Yc, bs, lam=lam, num_iter=num_iter
            )
            return W_stack, fmean, ymean

        def build(params):
            W_stack, fmean, ymean = params
            nb = W_stack.shape[0]
            scalers = [
                StandardScalerModel(fmean[i * bs : (i + 1) * bs])
                for i in range(nb)
            ]
            return BlockLinearMapper(
                [W_stack[i] for i in range(nb)], bs, b_opt=ymean,
                feature_scalers=scalers,
            )

        def supports(d_feat: int) -> bool:
            return d_feat % bs == 0 and self.num_features in (None, d_feat)

        # λ rides as a traced operand and the program is shared by logical
        # identity: a λ-sweep building a fresh estimator per λ compiles
        # the fused featurize+fit ONCE (workflow/fusion.py DeviceFit).
        return DeviceFit(
            fit_fn, build, supports,
            operands=(jnp.asarray(self.lam, jnp.float32),),
            program_key=("BlockLS", bs, num_iter, self.num_features),
        )

    def fit(self, data: Dataset, labels: Dataset) -> BlockLinearMapper:
        splitter = VectorSplitter(self.block_size, self.num_features)
        blocks = splitter.apply(data)
        return self.fit_blocks(blocks, labels)

    def fit_blocks(self, blocks: List[Dataset], labels: Dataset) -> BlockLinearMapper:
        with obs.span("solver.scale", blocks=len(blocks)):
            label_scaler = StandardScaler(normalize_std_dev=False).fit(labels)
            B = jnp.asarray(label_scaler.batch_apply(labels).array)

            feature_scalers = [
                StandardScaler(normalize_std_dev=False).fit(block) for block in blocks
            ]
            A_blocks = [
                jnp.asarray(scaler.batch_apply(block).array)
                for block, scaler in zip(blocks, feature_scalers)
            ]

        def _is_multi(ds):
            return ds.mesh is not None and any(
                s > 1 for s in dict(ds.mesh.shape).values()
            )

        multi_device = _is_multi(labels) or any(_is_multi(b) for b in blocks)
        # A last block narrower than the others (d not a multiple of the
        # block) rides the same program as a tail after the stack.
        head, tail = A_blocks, None
        if len(A_blocks) > 1 and A_blocks[-1].shape[1] < A_blocks[0].shape[1]:
            head, tail = A_blocks[:-1], A_blocks[-1]
        if (
            len({a.shape for a in head}) == 1
            and not multi_device
            and _stack_fits_memory(A_blocks, self.num_iter)
        ):
            # Equal-size blocks on one device (the common case): the whole
            # (epochs x blocks) sweep is one compiled program. Multi-device
            # data keeps the stepwise path (per-block programs partition
            # cleanly and match the unsharded reduction order); so do fits
            # whose stacked copy would not fit beside the blocks in HBM.
            with obs.span("solver.stack"):
                stacked = _stack_blocks(*head)
                # the stack is a full second copy; drop the lists
                del A_blocks, head
            with obs.span("solver.bcd", epochs=self.num_iter):
                fitted = linalg.bcd_least_squares_fused(
                    stacked, B, lam=self.lam, num_iter=self.num_iter, tail=tail
                )
                W_stack, W_tail = (fitted, None) if tail is None else fitted
                Ws = list(_unstacked(W_stack))
                if W_tail is not None:
                    Ws.append(W_tail)
        else:
            mesh = next(
                (d.mesh for d in [labels, *blocks] if d.mesh is not None), None
            )
            Ws = linalg.bcd_least_squares(
                A_blocks, B, lam=self.lam, num_iter=self.num_iter,
                mesh=mesh if multi_device else None,
            )
        return BlockLinearMapper(
            Ws, self.block_size, b_opt=label_scaler.mean, feature_scalers=feature_scalers
        )

    def cost(
        self, n, d, k, sparsity, num_machines, cpu_weight, mem_weight, network_weight
    ) -> float:
        """Analytic cost model (BlockLinearMapper.scala:268-282)."""
        import math

        flops = n * d * (self.block_size + k) / num_machines
        bytes_scanned = n * d / num_machines + d * k
        network = 2.0 * (d * (self.block_size + k)) * math.log2(max(num_machines, 2))
        return self.num_iter * (
            max(cpu_weight * flops, mem_weight * bytes_scanned)
            + network_weight * network
        )

    def resident_bytes(self, n, d, k, sparsity, num_machines) -> float:
        """Capacity model for the selector's HBM feasibility cut: the fit
        holds the feature blocks plus a scaled/stacked second copy (f32),
        labels twice (raw + centered), and the multi-epoch Gramian stash."""
        return (
            8.0 * n * d / num_machines
            + 8.0 * n * k / num_machines
            + 4.0 * d * self.block_size
        )
