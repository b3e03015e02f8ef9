"""Distributed L-BFGS least-squares solvers.

Reference: nodes/learning/LBFGS.scala:14-281 and Gradient.scala:10-123 — a
Breeze LBFGS optimizer driving a cost function whose gradient is computed
per-partition and treeReduce-summed; loss = lossSum/n + ½λ‖W‖².

TPU-native: the full-batch loss+gradient is one jit-compiled sharded
computation (two GEMMs; the reduction over the sharded row axis is an XLA
all-reduce), and the whole optimizer loop is one lax.while_loop. Because the
objective is the ridge *quadratic*, no generic linesearch is needed: the
step along the two-loop L-BFGS direction is exact,
``α = −gᵀp / pᵀHp`` with one Hessian-apply ``Hp = Aᵀ(Ap)/n + λp`` per
iteration, and the gradient updates incrementally (``g += α·Hp`` — the
gradient is linear in W). One data pass per iteration total, versus the
several loss/gradient evaluations per zoom-linesearch step a generic
optimizer pays (Breeze's Wolfe search in the reference, LBFGS.scala:87-103).
"""

from __future__ import annotations

import dataclasses
import functools
import logging
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from keystone_tpu.data import Dataset
from keystone_tpu.ops.stats import StandardScaler
from keystone_tpu.ops.learning.linear import LinearMapper
from keystone_tpu.workflow import LabelEstimator

logger = logging.getLogger("keystone_tpu.lbfgs")


def _matmul(X, P):
    """X @ P where X is a dense array or a padded-COO dict (never densified)."""
    if isinstance(X, dict):
        from keystone_tpu.ops.sparse import sparse_matmul

        return sparse_matmul(X["indices"], X["values"], P)
    return X @ P


def _rmatmul(X, V, d: int):
    """Xᵀ @ V for dense or padded-COO X."""
    if isinstance(X, dict):
        from keystone_tpu.ops.sparse import sparse_matmul_t

        return sparse_matmul_t(X["indices"], X["values"], V, d)
    return X.T @ V


def least_squares_loss(W, X, Y, lam: float, n: int):
    """½‖XW − Y‖²/n + ½λ‖W‖² (LBFGS.scala:105-119).

    Padding rows of X and Y are zero, so their residual (0·W − 0) contributes
    nothing; only the divisor uses the true n. X may be dense or a
    padded-COO dict.
    """
    residual = _matmul(X, W) - Y
    data_loss = 0.5 * jnp.sum(residual * residual) / n
    return data_loss + 0.5 * lam * jnp.sum(W * W)


def run_lbfgs(
    X,
    Y,
    lam: float = 0.0,
    num_iterations: int = 100,
    convergence_tol: float = 1e-4,
    n: Optional[int] = None,
    W_init=None,
    info: Optional[dict] = None,
):
    """Minimize the ridge least-squares loss with L-BFGS.

    X: (n_pad, d) row-sharded features — a dense array OR a padded-COO dict
    ``{"indices", "values"}`` (sparse input requires ``W_init``, whose row
    count fixes d), in which case every data pass runs
    through the gather/segment-sum sparse kernels and the dense design
    matrix never exists. Y: (n_pad, k) labels. Returns (d, k). The whole
    optimization loop (two-loop direction, exact quadratic step, convergence
    test) is a single compiled while_loop on device. ``info``, when given,
    receives ``loss`` and ``iterations`` (the steps the loop ran).
    """
    Y = jnp.asarray(Y)
    if isinstance(X, dict):
        values = jnp.asarray(X["values"])
        dtype = jnp.result_type(values.dtype, Y.dtype)
        X = {
            "indices": jnp.asarray(X["indices"]),
            "values": values.astype(dtype),
        }
        n_rows = X["indices"].shape[0]
        if W_init is None:
            raise ValueError(
                "sparse run_lbfgs needs W_init (or use SparseLBFGSwithL2, "
                "which sizes the model from num_features)"
            )
    else:
        X = jnp.asarray(X)
        # Mixed-precision inputs (e.g. f32 sparse values + f64 labels) must
        # agree so the while_loop carry has one consistent dtype.
        dtype = jnp.result_type(X.dtype, Y.dtype)
        X = X.astype(dtype)
        n_rows = X.shape[0]
    Y = Y.astype(dtype)
    n = n or n_rows
    W0 = (
        jnp.asarray(W_init, dtype=dtype)
        if W_init is not None
        else jnp.zeros((X.shape[1], Y.shape[1]), dtype=dtype)
    )

    W, final_loss, count = _lbfgs_core(
        X, Y, W0,
        jnp.asarray(lam, dtype=dtype),
        jnp.asarray(num_iterations),
        jnp.asarray(convergence_tol, dtype=dtype),
        jnp.asarray(n, dtype=dtype),
    )
    _report_solve(final_loss, count, info, "LBFGS")
    return W


def _report_solve(final_loss, count, info: Optional[dict], what: str) -> None:
    """Read a finished solve's loss and iteration count — the host's one
    wait for the device in a sparse fit, spanned as such — and hand them
    to the log, the ``lbfgs.iterations`` counter and the caller's ``info``."""
    from keystone_tpu import obs

    with obs.span("executor.drain", site="solver_loss"):
        final_loss, count = jax.device_get((final_loss, count))
    logger.info("%s final loss: %s", what, float(final_loss))
    obs.counter_track("lbfgs.iterations", int(count))
    if info is not None:
        info.update(loss=float(final_loss), iterations=int(count))


_LBFGS_HISTORY = 10  # standard L-BFGS memory


def _lbfgs_quad_loop(hvp, AtB, W0, lam, num_iterations, tol):
    """The L-BFGS loop on the ridge quadratic, generic over the Hessian
    apply: ``hvp`` may be the data-pass form Aᵀ(A·)/n + λ· or the
    Gramian form G·/n + λ· — algebraically identical operators, so the
    iterate sequences coincide (up to summation order). Traceable.
    Returns ``(W, iterations run)``."""
    history = _LBFGS_HISTORY
    dtype = W0.dtype

    def vdot(a, b):
        return jnp.sum(a * b)

    def direction(grad, S, Yh, rho, count):
        """Two-loop recursion over the circular (history, d, k) buffers."""
        m = jnp.minimum(count, history)

        def bwd(i, carry):
            q, alphas = carry
            # i-th most recent pair: slot (count - 1 - i) mod history
            slot = jnp.mod(count - 1 - i, history)
            valid = i < m
            a = jnp.where(valid, rho[slot] * vdot(S[slot], q), 0.0)
            q = q - a * Yh[slot]
            return q, alphas.at[i].set(a)

        q, alphas = jax.lax.fori_loop(
            0, history, bwd, (grad, jnp.zeros((history,), dtype=dtype))
        )
        last = jnp.mod(count - 1, history)
        ys = vdot(S[last], Yh[last])
        yy = vdot(Yh[last], Yh[last])
        # Guard on ys > 0 (not just count): a degenerate zero pair stored
        # after an alpha=0 step must fall back to the steepest-descent
        # scaling, not zero the direction forever.
        gamma = jnp.where(ys > 0, ys / jnp.maximum(yy, 1e-30), 1.0)
        r = gamma * q

        def fwd(j, r):
            i = history - 1 - j  # oldest -> newest
            slot = jnp.mod(count - 1 - i, history)
            valid = i < m
            beta = jnp.where(valid, rho[slot] * vdot(Yh[slot], r), 0.0)
            return r + jnp.where(valid, alphas[i] - beta, 0.0) * S[slot]

        r = jax.lax.fori_loop(0, history, fwd, r)
        return -r

    def step(carry):
        W, grad, S, Yh, rho, count, _ = carry
        p = direction(grad, S, Yh, rho, count)
        Hp = hvp(p)
        denom = vdot(p, Hp)
        alpha = jnp.where(denom > 0, -vdot(grad, p) / denom, 0.0)
        s = alpha * p
        y = alpha * Hp  # grad(W+s) − grad(W) for the quadratic
        W = W + s
        grad = grad + y
        slot = jnp.mod(count, history)
        sy = vdot(s, y)
        S = S.at[slot].set(s)
        Yh = Yh.at[slot].set(y)
        rho = rho.at[slot].set(jnp.where(sy > 0, 1.0 / sy, 0.0))
        return W, grad, S, Yh, rho, count + 1, jnp.linalg.norm(grad)

    def cond(carry):
        _, _, _, _, _, count, gnorm = carry
        return (count < num_iterations) & (gnorm > tol)

    d, k = W0.shape
    grad0 = hvp(W0) - AtB
    S0 = jnp.zeros((history, d, k), dtype=dtype)
    Y0 = jnp.zeros((history, d, k), dtype=dtype)
    rho0 = jnp.zeros((history,), dtype=dtype)
    carry = (W0, grad0, S0, Y0, rho0, 0, jnp.linalg.norm(grad0))
    W, _, _, _, _, count, _ = jax.lax.while_loop(cond, step, carry)
    return W, count


def _lbfgs_body(X, Y, W0, lam, num_iterations, tol, n):
    """Traceable LBFGS fit body — shared by the jitted core and the
    fit-fusion path (which traces it INSIDE a featurize+fit program)."""
    d = W0.shape[0]

    def hvp(P):
        # H P = Aᵀ(A P)/n + λP — the one data pass per iteration. For
        # padded-COO X this is a gather pass + a segment-sum scatter pass;
        # the dense matrix never exists.
        return _rmatmul(X, _matmul(X, P), d) / n + lam * P

    AtB = _rmatmul(X, Y, d) / n  # constant term of the gradient
    W, count = _lbfgs_quad_loop(hvp, AtB, W0, lam, num_iterations, tol)
    return W, least_squares_loss(W, X, Y, lam, n), count


@jax.jit
def _lbfgs_core(X, Y, W0, lam, num_iterations, tol, n):
    """Module-level jitted core (one executable per shape set, reused across
    fits; hyperparameters are traced scalars so they never trigger
    recompiles)."""
    return _lbfgs_body(X, Y, W0, lam, num_iterations, tol, n)


@jax.jit
def _lbfgs_gram_core(G, AtY, yty, W0, lam, num_iterations, tol, n,
                     border=None):
    """L-BFGS on the accumulated normal equations: hvp = G·/n + λ· — the
    same operator as the data-pass core (G = AᵀA), so the iterates match
    the gather path while each iteration costs one (d, d)×(d, k) GEMM
    instead of a full data pass. Used by the streamed sparse tier, where
    G is folded once over (regenerated or resident) chunks.

    ``border=(s, ysum)`` — the column sums Xᵀ1 (d,) and 1ᵀY (k,) of a
    bordered fold (``sparse.sparse_gram_fold``) — solves for the model
    with an intercept, ``[X, 1]``, without the (d + 1)-wide Gramian ever
    being assembled: ``W0`` carries the intercept as its LAST row, the
    data part of the Hessian apply is ``[G·P_d + s·p₀ ; sᵀP_d + n·p₀]``
    and the constant term ``[XᵀY ; 1ᵀY]`` — block for block what the
    appended ones column puts in G's last row and column. The intercept
    takes λ with the rest (LBFGS.scala:208-281)."""
    HIGHEST = jax.lax.Precision.HIGHEST

    if border is None:
        def gram_apply(P):
            return jnp.dot(G, P, precision=HIGHEST)
    else:
        s, ysum = border
        AtY = jnp.concatenate([AtY, ysum[None]])

        def gram_apply(P):
            Pd, p0 = P[:-1], P[-1:]
            return jnp.concatenate([
                jnp.dot(G, Pd, precision=HIGHEST) + s[:, None] * p0,
                jnp.dot(s[None], Pd, precision=HIGHEST) + n * p0,
            ])

    def hvp(P):
        return gram_apply(P) / n + lam * P

    with jax.named_scope("ks.lbfgs_gram"):  # names the phase in a device profile
        W, count = _lbfgs_quad_loop(hvp, AtY / n, W0, lam, num_iterations, tol)
        # ½‖AW−Y‖²/n + ½λ‖W‖² expanded through G/AtY/yty (no data pass).
        data_loss = 0.5 * (
            jnp.sum(W * gram_apply(W)) - 2.0 * jnp.sum(W * AtY) + yty
        ) / n
        return W, data_loss + 0.5 * lam * jnp.sum(W * W), count


def _solve_gram_carry(carry, d: int, k: int, border: bool, hyper):
    """``(W, loss, iterations)`` from a FINALIZED fold carry. Solved at the
    padded width: padded rows of AtY are zero and G's padded rows/cols are
    zero, so those W rows stay exactly zero through every iterate (pure-λ
    ridge on a zero gradient). With ``border`` W is (d + 1, k), the
    intercept its last row — the layout an appended ones column gives."""
    G, AtY, yty, *ysum = carry
    d_pad = G.shape[0]
    W, loss, count = _lbfgs_gram_core(
        G, AtY[:, :k], yty,
        jnp.zeros((d_pad + int(border), k), jnp.float32), *hyper,
        border=(AtY[:, k], ysum[0]) if border else None,
    )
    return jnp.concatenate([W[:d], W[d_pad:]]), loss, count


class DenseLBFGSwithL2(LabelEstimator):
    """Dense-input LBFGS ridge solver with mean-centering intercepts
    (reference: LBFGS.scala:135-192)."""

    def __init__(
        self,
        lam: float = 0.0,
        num_iterations: int = 100,
        convergence_tol: float = 1e-4,
    ):
        self.lam = lam
        self.num_iterations = num_iterations
        self.convergence_tol = convergence_tol

    @property
    def weight(self) -> int:
        return self.num_iterations + 1

    def device_fit_fn(self):
        """Fit-fusion contract (workflow/fusion.py): mean-centering + the
        whole L-BFGS while_loop as one traceable function, so the
        optimizer compiles upstream featurization INTO the fit — one
        dispatch, the feature matrix never round-trips HBM between
        featurize and solve."""
        from keystone_tpu.ops.stats import StandardScalerModel
        from keystone_tpu.workflow.fusion import DeviceFit, masked_center

        num_iterations, tol = self.num_iterations, self.convergence_tol

        def fit_fn(F, Y, n_true: int, lam):
            Fc, Yc, fmean, ymean = masked_center(F, Y, n_true)
            dtype = jnp.result_type(Fc.dtype, Yc.dtype)
            W0 = jnp.zeros((Fc.shape[1], Yc.shape[1]), dtype=dtype)
            W, *_ = _lbfgs_body(
                Fc.astype(dtype), Yc.astype(dtype), W0,
                lam.astype(dtype),
                jnp.asarray(num_iterations),
                jnp.asarray(tol, dtype),
                jnp.asarray(n_true, dtype),
            )
            return W, fmean, ymean

        def build(params):
            W, fmean, ymean = params
            return LinearMapper(
                W, b_opt=ymean, feature_scaler=StandardScalerModel(fmean)
            )

        return DeviceFit(
            fit_fn, build,
            operands=(jnp.asarray(self.lam, jnp.float32),),
            program_key=("DenseLBFGS", num_iterations, tol),
        )

    def fit(self, data: Dataset, labels: Dataset) -> LinearMapper:
        feature_scaler = StandardScaler(normalize_std_dev=False).fit(data)
        label_scaler = StandardScaler(normalize_std_dev=False).fit(labels)
        A = jnp.asarray(feature_scaler.batch_apply(data).array)
        B = jnp.asarray(label_scaler.batch_apply(labels).array)
        W = run_lbfgs(
            A, B, lam=self.lam,
            num_iterations=self.num_iterations,
            convergence_tol=self.convergence_tol,
            n=data.n,
        )
        return LinearMapper(W, b_opt=label_scaler.mean, feature_scaler=feature_scaler)

    def cost(
        self, n, d, k, sparsity, num_machines, cpu_weight, mem_weight, network_weight
    ) -> float:
        """Analytic cost model (LBFGS.scala:175-191)."""
        import math

        flops = n * d * k / num_machines
        bytes_scanned = n * d / num_machines
        network = 2.0 * d * k * math.log2(max(num_machines, 2))
        return self.num_iterations * (
            max(cpu_weight * flops, mem_weight * bytes_scanned)
            + network_weight * network
        )

    def resident_bytes(self, n, d, k, sparsity, num_machines) -> float:
        """Capacity model: the dense matrix plus its centered copy (f32),
        labels twice, and the L-BFGS history pairs (2 x history x d x k)."""
        return (
            8.0 * n * d / num_machines
            + 8.0 * n * k / num_machines
            + 8.0 * _LBFGS_HISTORY * d * k
        )


def _resident_chunk_fn(cid, idx_t, val_t, Y_t):
    """Chunk source slicing pre-tiled resident buffers (module-level so the
    compiled streamed program caches across fits)."""
    return idx_t[cid], val_t[cid], Y_t[cid]


def _fold_stepper(throttle, prefetch_stats):
    """One owner for the per-segment fold step's accounting: transfer +
    fold dispatch + the inflight throttle's blocking, stamped into the
    ``compute`` site of the per-site overlap report
    (``utils.profiling.overlap_report``). Both streamed entry points —
    :func:`run_lbfgs_gram_streamed` and :func:`run_lbfgs_gram_hybrid`
    (which swaps fold programs between its resident and tail legs) —
    fold through this, so the timing/throttle wiring cannot diverge."""
    import time as _time

    from keystone_tpu import obs

    def step(fold, carry, cid0, ops):
        t0 = _time.perf_counter()
        # The fold chunk span (obs plane, ISSUE 9) covers EXACTLY the
        # region the `compute` busy counter covers — transfer + fold
        # dispatch + throttle block — so trace sums and
        # PrefetchStats.site_busy_s agree (tests/test_obs_trace.py).
        # One no-op branch when tracing is off.
        with obs.span("fold.segment", chunk0=int(cid0)):
            carry = fold(
                carry, jnp.asarray(cid0, jnp.int32),
                tuple(jnp.asarray(o) for o in ops),
            )
            throttle.admit(carry[2])
        if prefetch_stats is not None:
            prefetch_stats.add_busy("compute", _time.perf_counter() - t0)
        return carry

    return step


def run_lbfgs_gram_streamed(
    chunk_fn,
    num_chunks: int,
    d: int,
    k: int,
    lam: float = 0.0,
    num_iterations: int = 100,
    convergence_tol: float = 1e-4,
    n: Optional[int] = None,
    use_pallas: bool = False,
    val_dtype=jnp.float32,
    operands=(),
    max_chunks_per_dispatch: Optional[int] = None,
    segment_source=None,
    inflight: int = 2,
    prefetch_depth: int = 2,
    pipeline: bool = True,
    prefetch_stats=None,
    checkpoint=None,
    mesh=None,
    mesh_axis: Optional[str] = None,
    info: Optional[dict] = None,
    border: bool = False,
):
    """Streamed sparse ridge fit: fold G = AᵀA over COO chunks ONCE
    (``sparse.sparse_gram_stream`` — chunks may be regenerated/loaded per
    call, so the full dataset never exists on device), then run the SAME
    L-BFGS iterates as the gather path against G at one (d, d)×(d, k)
    GEMM per iteration. Returns (W (d, k), final_loss); ``info``, when
    given, receives ``iterations`` — the steps the loop ran, a device
    scalar like the loss (reading either waits for the fit).

    ``lam``, ``num_iterations``, ``convergence_tol`` and ``n`` ride into
    the compiled programs as OPERANDS: a ridge sweep reuses one program
    (as constants every new ``lam`` compiled the whole chunk scan anew).

    ``d`` and ``k`` are the chunk's own columns and targets. ``border``:
    ``chunk_fn`` hands ``[Y, live]`` (k + 1 target columns, the last the
    0/1 mask of the rows the chunk folds) and the fit learns an intercept
    from the fold's border (``sparse.sparse_gram_fold``): W comes back
    (d + 1, k), the intercept its last row, regularised with the rest.
    A static key of the fold and solve programs, on every tier below.

    ``operands``: arrays ``chunk_fn`` slices from, passed as
    ``chunk_fn(cid, *operands)``. Resident buffers MUST ride here — a
    chunk_fn that closes over concrete device arrays embeds them as
    program CONSTANTS (hundreds of MB of HLO at Amazon scale — copied
    into the executable and every compile-cache entry, and recompiled
    per instance).

    ``max_chunks_per_dispatch``: bound the fold's program length. By
    default the whole fit is ONE dispatch; very long streams (the full
    n=65e6 Amazon fold is ~1000 chunks ≈ minutes of device time) are
    segmented so the host regains control between segments — the
    points where a fold can be checkpointed, traced and cancelled.
    Segments reuse one compiled fold program (chunk id is a traced
    operand); chunk ids past ``num_chunks`` in the final ragged segment
    contribute exactly zero.

    ``segment_source``: per-SEGMENT operand loader — the disk-bounded
    tier: neither device HBM nor host RAM ever holds the dataset, only
    ``seg`` chunks at a time. Accepts

      - a :class:`keystone_tpu.data.shards.DiskCOOShards` or its
        prefetchable ``as_source(chunks_per_segment)`` form: segment k+1
        is read from disk on a background thread while segment k's
        transfer + fold are in flight (``prefetch_depth`` bounds staged
        host buffers; 0 reads serially — byte-identical results), or
      - the legacy callable ``segment_source(cid0, seg) -> (idx_t,
        val_t, Y_t)`` (loaded serially: a callable makes no
        thread-safety promise).

    ``chunk_fn`` then receives SEGMENT-RELATIVE ids. Requires
    ``max_chunks_per_dispatch`` (defaulted from a source's
    ``chunks_per_segment``).

    ``inflight``: segments allowed in the device queue before the host
    blocks — bounds the staged segment buffers held in HBM
    (``streaming.BoundedInflight``) while segment i+1's host load and
    transfer overlap segment i's fold.

    ``pipeline``: double-buffer the densified chunk slab inside the fold
    (``sparse.sparse_gram_fold``) so chunk k+1's regen+densify (the
    densify a one-hot contraction that writes the slab,
    ``sparse_densify.densify_rows`` — no scatter) is schedulable against chunk
    k's accumulating syrk; costs one extra resident slab — pass False
    beside large resident operands.

    ``prefetch_stats``: a :class:`keystone_tpu.data.prefetch.
    PrefetchStats` filled by the prefetched source path (overlap +
    retry/backoff accounting — ``utils.profiling``).

    ``mesh``: a ``jax.sharding.Mesh`` — the multi-chip tier (ISSUE 16).
    The chunk stream partitions CONTIGUOUSLY over ``mesh_axis`` (default
    the ``data`` axis): device j folds chunks ``[j·cpd, (j+1)·cpd)``
    (``cpd = ceil(num_chunks / m)``) into its own local (G, AtY, yty)
    partial — no collective crosses the ICI during the fold — and ONE
    ``lax.psum`` tree reduction of the carry per fit precedes the
    replicated solve. Resident ``operands`` are sharded over their
    leading chunk axis (each device's shard lives in ITS HBM — the
    8-chip form of the compressed-resident tier); a ``segment_source``
    must then be a SEQUENCE of per-device sources whose segment ``s``
    carries device j's segment-relative chunks, read concurrently on
    per-device ``read.d<j>`` lanes (``data/prefetch.py::
    iter_mesh_segments``). ``chunk_fn`` receives device-LOCAL (resident)
    or segment-relative (streamed) ids either way. Checkpointing is not
    supported on the mesh path yet (an explicit ``checkpoint`` raises).

    ``checkpoint``: a :class:`keystone_tpu.data.durable.CheckpointSpec`
    (or directory path; None consults ``KEYSTONE_CHECKPOINT_DIR``)
    snapshotting the (G, AtY, yty) carry + segment cursor every
    ``every_segments`` segments, atomically. A fit killed mid-stream and
    re-run with the same spec resumes from the snapshot BIT-IDENTICALLY
    (tests/test_chaos.py). Requires a segmented fit — an explicit
    checkpoint with the whole fold in one dispatch raises (there is no
    boundary to snapshot at); the env-default spec is simply ignored
    there so a global ``--checkpoint-dir`` drill never breaks
    single-dispatch fits.
    """
    from keystone_tpu import obs
    from keystone_tpu.data.durable import (
        fingerprint_token,
        resolve_checkpoint,
        source_fingerprint,
    )

    if n is None:
        raise ValueError("streamed fit needs the true row count n")
    hyper = _solve_operands(lam, num_iterations, convergence_tol, n)
    if mesh is not None:
        if checkpoint is not None:
            raise ValueError(
                "mesh-sharded streamed fits do not checkpoint yet: the "
                "carry is a per-device partial on every chip (snapshot "
                "would need a gather); drop checkpoint= or mesh="
            )
        return _solved(info, _run_lbfgs_gram_streamed_mesh(
            chunk_fn, int(num_chunks), int(d), int(k), mesh,
            mesh_axis=mesh_axis, hyper=hyper, use_pallas=use_pallas,
            val_dtype=val_dtype, border=bool(border), operands=operands,
            max_chunks_per_dispatch=max_chunks_per_dispatch,
            segment_sources=segment_source, inflight=inflight,
            prefetch_depth=prefetch_depth, prefetch_stats=prefetch_stats,
        ))
    explicit_checkpoint = checkpoint is not None
    checkpoint = resolve_checkpoint(checkpoint)
    seg = max_chunks_per_dispatch
    source = None
    if segment_source is not None and not callable(segment_source):
        from keystone_tpu.data.prefetch import COOShardSource, is_shard_source

        if is_shard_source(segment_source):
            source = segment_source
        elif hasattr(segment_source, "segment_source"):
            # A DiskCOOShards-like object: group chunks into segments.
            source = COOShardSource(
                segment_source, seg if seg else min(int(num_chunks), 8)
            )
        else:
            raise TypeError(
                f"segment_source must be callable, a ShardSource, or "
                f"have .segment_source; got {type(segment_source).__name__}"
            )
        if seg is None:
            seg = source.chunks_per_segment
        elif seg != source.chunks_per_segment:
            raise ValueError(
                f"max_chunks_per_dispatch {seg} != the source's "
                f"chunks_per_segment {source.chunks_per_segment}"
            )
    if segment_source is None and (seg is None or seg >= num_chunks):
        if explicit_checkpoint:
            raise ValueError(
                "checkpointing needs a segmented fit: pass "
                "max_chunks_per_dispatch (or a segment_source) so there "
                "are fold boundaries to snapshot at"
            )
        program = _gram_streamed_program(
            chunk_fn, int(num_chunks), int(d), int(k),
            bool(use_pallas), jnp.dtype(val_dtype), bool(pipeline),
            bool(border),
        )
        # One dispatch holds the fold AND the solve: both spans' work.
        with obs.span("solver.gram_fold", chunks=int(num_chunks),
                      dispatches=1, with_solve=True):
            return _solved(info, program(tuple(operands), hyper))

    from keystone_tpu.ops.sparse import sparse_gram_init
    from keystone_tpu.parallel.streaming import BoundedInflight

    if segment_source is not None:
        if seg is None:
            raise ValueError("segment_source requires max_chunks_per_dispatch")
        fold = _gram_fold_program_rel(
            chunk_fn, int(num_chunks), int(d), int(k), int(seg),
            bool(use_pallas), jnp.dtype(val_dtype), bool(pipeline),
            bool(border),
        )
    else:
        fold = _gram_fold_program(
            chunk_fn, int(num_chunks), int(d), int(k), int(seg),
            bool(use_pallas), jnp.dtype(val_dtype), bool(pipeline),
            bool(border),
        )
    solve = _gram_solve_program(int(d), int(k), bool(border))
    num_segs = -(-int(num_chunks) // int(seg))
    carry = None
    start_seg = 0
    fingerprint = None
    if checkpoint is not None:
        # Geometry + fold-program identity (chunk_fn, dtype/engine
        # flags, operand shapes) + source identity — a stale snapshot
        # from a different chunk source must never seed this fold.
        # Resident operands are fingerprinted by shape/dtype only (a
        # content digest would transfer the dataset host-side); disk
        # sources carry a free content digest via their recorded
        # checksums.
        fingerprint = {
            "kind": "coo_gram_segments", "num_chunks": int(num_chunks),
            "d": int(d), "k": int(k), "seg": int(seg), "n": int(n),
            "val_dtype": str(jnp.dtype(val_dtype)),
            "use_pallas": bool(use_pallas), "pipeline": bool(pipeline),
            "chunk_fn": fingerprint_token(chunk_fn),
            "operands": [
                {"shape": [int(v) for v in getattr(o, "shape", ())],
                 "dtype": str(getattr(o, "dtype", "?"))}
                for o in operands
            ],
            "source": source_fingerprint(
                source if source is not None else segment_source
            ),
        }
        if border:
            fingerprint["border"] = True
        arrays, start_seg = checkpoint.restore(fingerprint)
        if arrays is not None:
            # A snapshot is a carry of THIS fold or it is refused: a fit
            # that lanes its intercept holds the ones column inside a G
            # one column wider, a bordered fit holds it beside G — the
            # one is never read as the other.
            want = [
                tuple(a.shape) for a in jax.eval_shape(
                    lambda: sparse_gram_init(d, k, val_dtype, border))
            ]
            got = [tuple(np.shape(a)) for a in arrays]
            if got != want:
                raise ValueError(
                    f"checkpoint under {checkpoint.directory!r} holds a "
                    f"carry of shapes {got}, this fold's is {want} (an "
                    f"intercept laned as a slab column and one kept as "
                    f"the fold's border do not resume each other): "
                    f"discard the checkpoint and restart the fit"
                )
            carry = tuple(jnp.asarray(a) for a in arrays)
    if carry is None:
        carry = sparse_gram_init(d, k, val_dtype, border)
    throttle = BoundedInflight(inflight)
    step = _fold_stepper(throttle, prefetch_stats)

    def folded(cid0, ops):
        nonlocal carry
        carry = step(fold, carry, cid0, ops)

    def maybe_snapshot(s):
        if checkpoint is not None:
            checkpoint.maybe_save(carry, s, num_segs, fingerprint,
                                  stats=prefetch_stats)

    def finish():
        with obs.span("solver.lbfgs", engine="gram"):
            result = _solved(info, solve(carry, hyper))
        if checkpoint is not None:
            checkpoint.clear(fingerprint)  # this fit's snapshot only
        return result

    with obs.span("solver.gram_fold", chunks=int(num_chunks),
                  dispatches=num_segs - start_seg, with_solve=False):
        if source is not None:
            from keystone_tpu.data.prefetch import iter_segments

            for s, ops in iter_segments(
                source, prefetch_depth=prefetch_depth, stats=prefetch_stats,
                start=start_seg,
            ):
                folded(s * int(seg), ops)
                maybe_snapshot(s)
        else:
            for s in range(start_seg, num_segs):
                cid0 = s * int(seg)
                if segment_source is not None:
                    ops = segment_source(int(cid0), int(seg))
                else:
                    ops = operands
                folded(cid0, ops)
                maybe_snapshot(s)
    return finish()


def run_lbfgs_gram_hybrid(
    resident_chunk_fn,
    num_resident_chunks: int,
    resident_operands,
    num_chunks: int,
    d: int,
    k: int,
    *,
    lam: float = 0.0,
    num_iterations: int = 100,
    convergence_tol: float = 1e-4,
    n: Optional[int] = None,
    use_pallas: bool = False,
    val_dtype=jnp.float32,
    max_chunks_per_dispatch: int = 8,
    chunk_fn=None,
    segment_source=None,
    prefetch_depth: int = 2,
    prefetch_stats=None,
    pipeline: bool = True,
    inflight: int = 2,
):
    """Hybrid resident+streamed sparse gram fit — the compressed tier's
    full-working-set form (ISSUE 8): chunks ``[0, num_resident_chunks)``
    fold from device-RESIDENT operands (the int16+bf16 compressed COO of
    ``data/resident.py`` — ``resident_chunk_fn(cid, *operands)`` slices
    them; ``pipeline=False`` for this leg, since there is no regen work
    to overlap and no slab headroom beside the resident buffers), and
    chunks ``[num_resident_chunks, num_chunks)`` — the part that truly
    cannot fit — stream exactly as in :func:`run_lbfgs_gram_streamed`:
    either ``chunk_fn(cid)`` regenerated per scan step, or a
    ``segment_source`` ShardSource whose segment ``s`` carries the
    SEGMENT-RELATIVE operands for chunks ``num_resident_chunks +
    [s·seg, (s+1)·seg)``, read ahead on the data-plane runtime
    (``prefetch_depth``; ``prefetch_stats`` collects the per-site
    overlap accounting). One solve runs on the combined G.

    Bit-identity contract: same chunk order, same per-chunk densify +
    fold arithmetic, same carry — the result equals a single streamed
    fit over all ``num_chunks`` chunks with the same ``val_dtype`` and
    per-leg pipeline flags (tests/test_resident.py pins it).
    """
    if n is None:
        raise ValueError("hybrid streamed fit needs the true row count n")
    if num_resident_chunks > num_chunks:
        raise ValueError(
            f"num_resident_chunks {num_resident_chunks} > num_chunks "
            f"{num_chunks}"
        )
    from keystone_tpu.data.prefetch import is_shard_source, iter_segments
    from keystone_tpu.ops.sparse import sparse_gram_init
    from keystone_tpu.parallel.streaming import BoundedInflight

    seg = int(max_chunks_per_dispatch)
    carry = sparse_gram_init(d, k, val_dtype)
    throttle = BoundedInflight(inflight)
    step = _fold_stepper(throttle, prefetch_stats)

    def folded(fold, cid0, ops):
        nonlocal carry
        carry = step(fold, carry, cid0, ops)

    if num_resident_chunks:
        # Phantom ids in a ragged final resident segment are masked dead
        # (live = cid < num_resident_chunks); the SAME chunk ids then
        # fold live through the streamed tail — no chunk is ever folded
        # twice or skipped.
        fold_res = _gram_fold_program(
            resident_chunk_fn, int(num_resident_chunks), int(d), int(k),
            seg, bool(use_pallas), jnp.dtype(val_dtype), False,
        )
        ops_res = tuple(jnp.asarray(o) for o in resident_operands)
        for cid0 in range(0, int(num_resident_chunks), seg):
            folded(fold_res, cid0, ops_res)

    tail = int(num_chunks) - int(num_resident_chunks)
    if tail > 0:
        if segment_source is not None:
            if not is_shard_source(segment_source):
                raise TypeError(
                    "hybrid segment_source must be a ShardSource whose "
                    f"segments carry {seg} segment-relative chunks; got "
                    f"{type(segment_source).__name__}"
                )
            if chunk_fn is None:
                chunk_fn = _resident_chunk_fn
            fold_tail = _gram_fold_program_rel(
                chunk_fn, int(num_chunks), int(d), int(k), seg,
                bool(use_pallas), jnp.dtype(val_dtype), bool(pipeline),
            )
            for s, ops in iter_segments(
                segment_source, prefetch_depth=prefetch_depth,
                stats=prefetch_stats,
            ):
                folded(fold_tail, int(num_resident_chunks) + s * seg, ops)
        else:
            if chunk_fn is None:
                raise ValueError(
                    "a streamed tail needs chunk_fn or segment_source"
                )
            fold_tail = _gram_fold_program(
                chunk_fn, int(num_chunks), int(d), int(k), seg,
                bool(use_pallas), jnp.dtype(val_dtype), bool(pipeline),
            )
            for cid0 in range(int(num_resident_chunks), int(num_chunks),
                              seg):
                folded(fold_tail, cid0, ())

    solve = _gram_solve_program(int(d), int(k))
    return _solved(None, solve(
        carry, _solve_operands(lam, num_iterations, convergence_tol, n)
    ))


@functools.lru_cache(maxsize=16)
def _gram_fold_program(chunk_fn, num_chunks, d, k, seg, use_pallas,
                       val_dtype, pipeline=True, border=False):
    """Compiled fold of ``seg`` consecutive chunks into the (G, AtY, yty)
    carry; the starting chunk id is a traced operand so every segment —
    including the phantom-padded final one — reuses this one executable.
    The carry is donated (G is ~1.2 GB at Amazon geometry)."""
    from keystone_tpu.ops.sparse import sparse_gram_fold

    @functools.partial(jax.jit, donate_argnums=(0,))
    def fold(carry, cid0, operands):
        def cf(cid):
            indices, values, Yc = chunk_fn(cid, *operands)
            live = cid < num_chunks
            return (
                indices,
                jnp.where(live, values, jnp.zeros_like(values)),
                jnp.where(live, Yc, jnp.zeros_like(Yc)),
            )

        return sparse_gram_fold(
            carry, cid0 + jnp.arange(seg), cf, d, k,
            use_pallas=use_pallas, val_dtype=val_dtype, pipeline=pipeline,
            border=border,
        )

    return fold


@functools.lru_cache(maxsize=16)
def _gram_fold_program_rel(chunk_fn, num_chunks, d, k, seg, use_pallas,
                           val_dtype, pipeline=True, border=False):
    """Segment fold over SEGMENT-RELATIVE chunk ids: operands hold only
    this segment's ``seg`` chunks (a disk-backed loader's slice), so
    ``chunk_fn`` slices by rel id while liveness masks by the absolute
    id ``cid0 + rel``."""
    from keystone_tpu.ops.sparse import sparse_gram_fold

    @functools.partial(jax.jit, donate_argnums=(0,))
    def fold(carry, cid0, operands):
        def cf(rel):
            indices, values, Yc = chunk_fn(rel, *operands)
            live = (cid0 + rel) < num_chunks
            return (
                indices,
                jnp.where(live, values, jnp.zeros_like(values)),
                jnp.where(live, Yc, jnp.zeros_like(Yc)),
            )

        return sparse_gram_fold(
            carry, jnp.arange(seg), cf, d, k,
            use_pallas=use_pallas, val_dtype=val_dtype, pipeline=pipeline,
            border=border,
        )

    return fold


def _solve_operands(lam, num_iterations, convergence_tol, n):
    """(lam, num_iterations, tol, n) as the scalar operands of a compiled
    solve: hyperparameters are traced, so they never trigger recompiles."""
    return (
        jnp.asarray(lam, jnp.float32),
        jnp.asarray(num_iterations, jnp.int32),
        jnp.asarray(convergence_tol, jnp.float32),
        jnp.asarray(n, jnp.float32),
    )


def _solved(info: Optional[dict], result):
    """(W, loss) of a compiled solve's (W, loss, iterations); the count
    goes to the caller's ``info``, unread (a device scalar)."""
    W, loss, count = result
    if info is not None:
        info["iterations"] = count
    return W, loss


@functools.lru_cache(maxsize=16)
def _gram_solve_program(d, k, border=False):
    """Compiled finalize + L-BFGS-on-G tail of the segmented fold:
    ``solve(carry, hyper)`` with ``hyper`` of :func:`_solve_operands`."""
    from keystone_tpu.ops.sparse import gram_finalize

    @jax.jit
    def solve(carry, hyper):
        G, *rest = carry
        return _solve_gram_carry(
            (gram_finalize(G), *rest), d, k, border, hyper)

    return solve


def _mesh_fold_axis(mesh, mesh_axis: Optional[str]) -> str:
    """Resolve (and validate) the fold's data-parallel mesh axis."""
    from keystone_tpu.parallel import mesh as mesh_lib

    axis = mesh_axis or mesh_lib.DATA_AXIS
    if axis not in mesh.shape:
        raise ValueError(
            f"mesh {dict(mesh.shape)} has no {axis!r} axis to shard the "
            f"chunk stream over"
        )
    return axis


def _mesh_gram_init(d, k, val_dtype, mesh, axis, border=False):
    """Per-device zero carries: stacked (m, ...) arrays sharded over
    ``axis`` so device j's partial lives only in device j's HBM."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from keystone_tpu.ops.sparse import sparse_gram_init

    m = int(mesh.shape[axis])
    sharding = NamedSharding(mesh, P(axis))
    pieces = jax.eval_shape(lambda: sparse_gram_init(d, k, val_dtype, border))
    return tuple(
        jax.device_put(np.zeros((m, *p.shape), np.float32), sharding)
        for p in pieces
    )


@functools.lru_cache(maxsize=8)
def _gram_fold_program_mesh(chunk_fn, num_chunks, d, k, seg, use_pallas,
                            val_dtype, pipeline, mesh, axis,
                            segment_relative, border=False):
    """Mesh-sharded segment fold: each device folds ``seg`` chunks of ITS
    contiguous chunk shard into ITS local (G, AtY, yty) partial. NO
    collective runs here — the single per-fit psum lives in
    :func:`_gram_mesh_solve_program` — so every dispatched step is pure
    device-local syrk work and scaling is bounded only by the one final
    tree reduction.

    Chunk ownership is contiguous: device j owns local ids [0, cpd)
    mapping to global chunks ``j·cpd + local`` (``cpd =
    ceil(num_chunks / m)``); phantom ids past a device's ragged tail are
    masked dead, so no chunk is folded twice or skipped
    (tests/test_multichip.py pins parity with the 1-device fold).
    ``segment_relative``: operands hold only this dispatch's ``seg``
    chunks, stacked (m, seg, ...) and sharded — the per-device-lane
    streamed ingestion path; otherwise operands are the full resident
    shard (leading dim m·cpd, sharded) and ``chunk_fn`` slices by the
    device-local id.
    """
    from jax.sharding import PartitionSpec as P

    from keystone_tpu.ops.sparse import sparse_gram_fold
    from keystone_tpu.parallel import mesh as mesh_lib

    m = int(mesh.shape[axis])
    cpd = -(-int(num_chunks) // m)

    def local(carry, cid0, operands):
        if segment_relative:
            operands = tuple(o[0] for o in operands)
        base = jax.lax.axis_index(axis) * cpd

        def cf(loc):
            sl = loc - cid0 if segment_relative else loc
            indices, values, Yc = chunk_fn(sl, *operands)
            live = (loc < cpd) & (base + loc < num_chunks)
            return (
                indices,
                jnp.where(live, values, jnp.zeros_like(values)),
                jnp.where(live, Yc, jnp.zeros_like(Yc)),
            )

        folded = sparse_gram_fold(
            tuple(piece[0] for piece in carry),
            cid0 + jnp.arange(seg), cf, d, k,
            use_pallas=use_pallas, val_dtype=val_dtype, pipeline=pipeline,
            border=border,
        )
        return tuple(piece[None] for piece in folded)

    sharded = P(axis)
    fold = mesh_lib.shard_map(
        local,
        mesh=mesh,
        in_specs=(sharded, P(), sharded),  # a spec a carry, whatever its pieces
        out_specs=sharded,
        check_vma=False,
    )
    return functools.partial(jax.jit, donate_argnums=(0,))(fold)


@functools.lru_cache(maxsize=8)
def _gram_mesh_solve_program(d, k, mesh, axis, border=False):
    """The fit's ONE cross-device collective: ``lax.psum`` of the
    (G, AtY, yty) pytree over ``axis`` — the border's pieces with the
    rest — (a pytree psum lowers to a single fused all-reduce over the
    ICI), replicated out, then the standard finalize + L-BFGS-on-G
    solve — identical iterates to the 1-device fold up to the
    reduction's float reassociation."""
    from jax.sharding import PartitionSpec as P

    from keystone_tpu.parallel import mesh as mesh_lib

    def local(carry):
        return jax.lax.psum(tuple(piece[0] for piece in carry), axis)

    reduce = mesh_lib.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(axis),),
        out_specs=P(),
        check_vma=False,
    )
    solve = _gram_solve_program(d, k, border)

    def run(carry, hyper):
        return solve(reduce(carry), hyper)

    return run


def _run_lbfgs_gram_streamed_mesh(
    chunk_fn, num_chunks, d, k, mesh, *, mesh_axis, hyper, use_pallas,
    val_dtype, border, operands, max_chunks_per_dispatch, segment_sources,
    inflight, prefetch_depth, prefetch_stats,
):
    """Mesh driver for :func:`run_lbfgs_gram_streamed` (ISSUE 16): the
    host loop dispatches one shard_map fold per LOCAL segment (all
    devices fold their own shard inside it), throttles inflight
    dispatches, and barriers per step on the CPU backend
    (``mesh_lib.sync_if_cpu`` — the forced-host multi-device queue
    deadlock guard); one psum + replicated solve finish the fit."""
    import time as _time

    from keystone_tpu import obs
    from keystone_tpu.parallel import mesh as mesh_lib
    from keystone_tpu.parallel.streaming import BoundedInflight

    axis = _mesh_fold_axis(mesh, mesh_axis)
    m = int(mesh.shape[axis])
    cpd = -(-int(num_chunks) // m)
    throttle = BoundedInflight(inflight)
    dev_tag = f"{axis}[0-{m - 1}]"

    def step(fold, carry, cid0, ops):
        t0 = _time.perf_counter()
        # The mesh fold is ONE dispatch covering every device's shard;
        # the span carries the device-group tag (satellite: per-device
        # occupancy) and the same compute-site accounting as the
        # single-device stepper.
        with obs.span("fold.segment", chunk0=int(cid0), device=dev_tag,
                      num_devices=m):
            carry = fold(
                carry, jnp.asarray(cid0, jnp.int32),
                tuple(jnp.asarray(o) for o in ops),
            )
            throttle.admit(jnp.sum(carry[2]))
            mesh_lib.sync_if_cpu(carry[2])
        if prefetch_stats is not None:
            prefetch_stats.add_busy("compute", _time.perf_counter() - t0)
        return carry

    carry = _mesh_gram_init(d, k, val_dtype, mesh, axis, border)
    solve = _gram_mesh_solve_program(int(d), int(k), mesh, axis, border)

    if segment_sources is not None:
        from keystone_tpu.data.prefetch import iter_mesh_segments

        seg = max_chunks_per_dispatch
        sources = list(segment_sources)
        if len(sources) != m:
            raise ValueError(
                f"mesh fold over {axis}={m} needs {m} per-device segment "
                f"sources, got {len(sources)}"
            )
        if seg is None:
            raise ValueError(
                "mesh segment sources need max_chunks_per_dispatch (the "
                "per-device chunks carried by one segment)"
            )
        fold = _gram_fold_program_mesh(
            chunk_fn, int(num_chunks), int(d), int(k), int(seg),
            bool(use_pallas), jnp.dtype(val_dtype), bool(pipeline_ok(seg)),
            mesh, axis, True, border,
        )
        for s, payloads in iter_mesh_segments(
            sources, prefetch_depth=prefetch_depth, stats=prefetch_stats,
        ):
            # Stack device payloads host-side; device_put inside the fold
            # call shards the (m, seg, ...) stack so each lane's bytes
            # land only on its device.
            ops = tuple(
                np.stack([p[i] for p in payloads])
                for i in range(len(payloads[0]))
            )
            carry = step(fold, carry, s * int(seg), ops)
        return solve(carry, hyper)

    # Resident path: pad the chunk axis to m·cpd and shard it so each
    # device holds exactly its contiguous shard (8-chip chip-residency).
    seg = int(max_chunks_per_dispatch) if max_chunks_per_dispatch else cpd
    seg = min(seg, cpd)
    ops = []
    for o in operands:
        o = np.asarray(o)
        pad = m * cpd - o.shape[0]
        if pad:
            fill = -1 if np.issubdtype(o.dtype, np.integer) else 0
            o = np.pad(
                o, [(0, pad)] + [(0, 0)] * (o.ndim - 1),
                constant_values=fill,
            )
        ops.append(mesh_lib.shard_rows(o, mesh, axis=axis))
    ops = tuple(ops)
    fold = _gram_fold_program_mesh(
        chunk_fn, int(num_chunks), int(d), int(k), seg, bool(use_pallas),
        jnp.dtype(val_dtype), False, mesh, axis, False, border,
    )
    for cid0 in range(0, cpd, seg):
        carry = step(fold, carry, cid0, ops)
    return solve(carry, hyper)


def pipeline_ok(seg: int) -> bool:
    """Streamed mesh segments double-buffer only when there is more than
    one chunk to overlap inside a dispatch."""
    return int(seg) > 1


@functools.lru_cache(maxsize=16)
def _gram_streamed_program(chunk_fn, num_chunks, d, k, use_pallas, val_dtype,
                           pipeline=True, border=False):
    """Compiled streamed-fit program, cached per (chunk_fn identity, fit
    geometry). Building the jit inside every call would make EVERY fit —
    including the timed second run of a warm benchmark — retrace and
    recompile the whole chunk scan (~30 s at Amazon geometry). Callers
    therefore pass a STABLE chunk_fn (module-level function or one object
    reused across fits), with per-fit arrays in ``operands`` and the
    hyperparameters in ``hyper`` (:func:`_solve_operands`)."""
    from keystone_tpu.ops.sparse import sparse_gram_stream

    @jax.jit
    def _run(operands, hyper):
        def cf(cid):
            return chunk_fn(cid, *operands)

        carry = sparse_gram_stream(
            cf, num_chunks, d, k, use_pallas=use_pallas,
            val_dtype=val_dtype, pipeline=pipeline, border=border,
        )
        return _solve_gram_carry(carry, d, k, border, hyper)

    return _run


# Largest per-row sum of |value| the range probe lets through: every slab
# entry (a row's lanes that share an id add) is then an integer of magnitude
# <= 255, under 256 — the last integer below which bfloat16 (8 significant
# bits) has no gaps. One to spare: it leaves room for an intercept lane's 1
# on the same entry, which a caller's own ones column may put there — the
# estimator's rides the fold's targets and never enters the slab.
_BF16_EXACT_ROW_SUM = 255.0


@jax.jit
def _slabs_exact_in_bf16(values, Y):
    """Whether a bfloat16 slab fold of these rows and targets is the SAME
    fold as the float32 one (a device scalar; one pass over the whole
    arrays, nothing sampled, no array made). Sufficient, not necessary:

    - every value is an integer and no row's |value|s sum past
      ``_BF16_EXACT_ROW_SUM`` — what binary and count term frequencies
      are. The densify ADDS a row's lanes that share an id (a contraction
      over the lanes, summed in float32 and rounded to the slab's type),
      so a row that repeats an id sums its values there: under this bound
      every entry is an integer of magnitude <= 255, which bfloat16 holds
      exactly (no intercept's 1 shares the slab: the ones column rides
      the targets, and an id outside [0, d) is dropped). Masked lanes
      are counted as if live (refuses more, never wrongly admits); a
      NaN fails the integer test, an infinity the sum.
    - the targets survive the round trip through bfloat16, because the
      accumulate kernels round them to the slab's type (the border's
      0/1 column beside them does).

    A product of two such slab entries, or of one and a target, is then
    exact in the float32 accumulator (<= 17 significant bits), so G and
    AᵀY come out as the float32 slabs would give them: bit for bit where
    the sums are themselves exact in float32 (counts below 2²⁴), and to
    the order of summation beyond."""
    v = values.astype(jnp.float32)
    y = Y.astype(jnp.float32)
    rows_ok = jnp.all(v == jnp.round(v)) & (
        jnp.max(jnp.sum(jnp.abs(v), axis=1)) <= _BF16_EXACT_ROW_SUM
    )
    return rows_ok & jnp.all(y.astype(jnp.bfloat16).astype(jnp.float32) == y)


def _with_intercept_lane(indices, values, d: int, n: int):
    """Append the ones column as one more active lane at index ``d``
    (LBFGS.scala:208-281 learns the intercept jointly); padding rows past
    ``n`` get an inactive (−1) lane."""
    valid = jnp.arange(indices.shape[0]) < n
    idx1 = jnp.concatenate(
        [indices, jnp.where(valid, d, -1)[:, None].astype(indices.dtype)],
        axis=1,
    )
    val1 = jnp.concatenate(
        [values, valid.astype(values.dtype)[:, None]], axis=1
    )
    return idx1, val1


@dataclasses.dataclass(frozen=True)
class _LanedRowChunks:
    """Chunk source over the caller's OWN padded-COO rows: chunk ``cid`` is
    rows ``[cid·c, (cid+1)·c)`` sliced from the ``(n_pad, w)`` operands
    inside the fold program — no laned or tiled copy of the dataset, and
    no lane for the intercept: the ones column goes to the TARGETS, as
    the 0/1 mask of the rows this chunk folds (``[Y, live]``, the bordered
    fold's contract — ``sparse.sparse_gram_fold``), so the slab is the
    rows' own ``d`` columns wide. A ragged last chunk starts at
    ``n_pad − c`` (a slice never leaves the array) and masks the rows an
    earlier chunk already folded; rows past the true ``n`` are masked
    dead like any padding. Frozen, so equal sources hash alike and fits
    of one geometry share a compiled program."""

    chunk_rows: int
    n: int

    def __call__(self, cid, indices, values, Y):
        c = self.chunk_rows
        start = jnp.minimum(cid * c, indices.shape[0] - c)
        rows = start + jnp.arange(c)
        live = ((rows >= cid * c) & (rows < self.n))[:, None]
        idx = jax.lax.dynamic_slice_in_dim(indices, start, c, 0)
        val = jax.lax.dynamic_slice_in_dim(values, start, c, 0)
        Yc = jax.lax.dynamic_slice_in_dim(Y, start, c, 0)
        targets = jnp.concatenate(
            [jnp.where(live, Yc, 0), live], axis=1, dtype=jnp.float32)
        return jnp.where(live, idx, -1), val, targets


class SparseLBFGSwithL2(LabelEstimator):
    """Sparse-input LBFGS ridge solver (reference: LBFGS.scala:208-281).

    Padded-COO input datasets run the whole optimization through the sparse
    gather/segment-sum kernels (the TPU form of the reference's active-index
    gradient loops, Gradient.scala:58-123) — the dense design matrix never
    exists, so Amazon-scale problems (n·d ≈ 1e12 dense elements at
    sparsity 0.005) fit in HBM. The append-ones intercept of the
    reference is kept, regularised with the weights: the gather engine
    gives every row one extra active index at column d with value 1; the
    gram engine learns the same intercept from the BORDER of its fold
    (column sums, target sums and n — ``sparse.sparse_gram_fold``), so
    its slabs stay d columns wide. Dense input datasets take the
    ordinary dense core.

    ``solver`` picks the iteration engine for sparse input:
      - "gather" (default, the reference-shaped path): every L-BFGS
        iteration is a gather + segment-sum data pass — bounded by the
        chip's random-access rate (~2e8 idx/s).
      - "gram": fold G = AᵀA once over densified row chunks (MXU syrk,
        ``sparse.sparse_gram_stream``), then run the SAME iterates against
        G at one small GEMM per iteration. ~10x faster end-to-end at
        Amazon geometry when iterations > ~2, at the cost of a (d_pad)²
        f32 Gramian in HBM — prefer it whenever d ≲ 40k.

    ``gram_dtype`` (gram solver only) is the type of the densified slabs.
    ``None`` (default) follows the values' range: float32 values that are
    exact in bfloat16 — integers whose |value|s sum to at most 255 a row,
    with targets that survive the round trip (one device reduction over
    the whole arrays, read by the host before the fold program is chosen)
    — fold in ONE MXU pass through bfloat16 slabs, to the same Gramian;
    any other float32 values fold through float32 slabs (six passes).
    ``"f32"`` forces the latter, ``"bf16"`` forces bfloat16 slabs on any
    values (lossy: the data is rounded inside the fold).

    ``compress`` (gram solver only) selects the COMPRESSED-RESIDENT
    storage class (``data/resident.py``, ISSUE 8): ``"int16_bf16"``
    encodes the padded-COO operands at 4 bytes/nnz (int16 index + bf16
    value) before the fold, with the decode fused into the fold's
    densify casts — the same iterates as ``gram_dtype="bf16"`` (the
    fold quantizes values to bf16 either way, so results are
    bit-identical), at HALF the resident operand. This is a capacity
    play: the cost model prices it as a third tier between HBM-raw and
    disk, so working sets that bust HBM raw but fit compressed stay
    chip-resident with no flag. Requires every index to fit int16 —
    encode raises at the overflow boundary rather than ever wrapping.
    """

    def __init__(
        self,
        lam: float = 0.0,
        num_iterations: int = 100,
        convergence_tol: float = 1e-4,
        num_features: Optional[int] = None,
        solver: str = "gather",
        gram_chunk_rows: int = 65536,
        gram_dtype: Optional[str] = None,
        compress: Optional[str] = None,
    ):
        if solver not in ("gather", "gram"):
            raise ValueError(f'solver must be "gather" or "gram", got {solver!r}')
        if gram_dtype not in (None, "f32", "bf16"):
            raise ValueError(
                f'gram_dtype must be None, "f32" or "bf16", got {gram_dtype!r}'
            )
        if compress not in (None, "int16_bf16"):
            raise ValueError(
                f'compress must be None or "int16_bf16", got {compress!r}'
            )
        if compress is not None and solver != "gram":
            raise ValueError(
                'compress requires solver="gram" (the gather engine reads '
                "COO lanes directly and has no densify to fuse the decode "
                "into)"
            )
        if compress is not None and gram_dtype == "f32":
            raise ValueError(
                'compress="int16_bf16" stores bf16 values — an exact-f32 '
                "fold over them would be paying full precision for "
                "already-quantized data; drop one of the two"
            )
        self.lam = lam
        self.num_iterations = num_iterations
        self.convergence_tol = convergence_tol
        self.num_features = num_features
        self.solver = solver
        self.compress = compress
        self.gram_chunk_rows = gram_chunk_rows
        # Densified-slab dtype for the gram fold (class docstring). None
        # follows the input values' RANGE (_slabs_exact_in_bf16): bf16
        # slabs — the MXU-native single-pass recipe, ~6x the 6-pass f32
        # syrk — only where they give the same Gramian. "bf16" folds ANY
        # f32 input through them, at the cost of bf16-quantizing the DATA
        # inside the fold (G error ~0.4% relative — the iterates shift by
        # the same order; quantified in tests/test_sparse_gram.py).
        self.gram_dtype = gram_dtype
        # Resolved at CONSTRUCTION like the selector's cpu/mem/network
        # weights (cost.py) — a mid-process KEYSTONE_COST_WEIGHTS flip
        # must not mix weight families within one estimator's ranking.
        from keystone_tpu.ops.learning import cost as cost_mod

        self._sparse_overhead = cost_mod.sparse_gather_overhead()

    @property
    def weight(self) -> int:
        return self.num_iterations + 1

    def fit(self, data: Dataset, labels: Dataset):
        from keystone_tpu import obs
        from keystone_tpu.ops.sparse import is_sparse_dataset
        from keystone_tpu.ops.learning.linear import SparseLinearMapper

        B = jnp.asarray(labels.array)
        info: dict = {}
        if is_sparse_dataset(data):
            indices = jnp.asarray(data.data["indices"])
            values = jnp.asarray(data.data["values"])
            d = self.num_features or int(jnp.max(indices)) + 1
            if self.solver == "gram":
                W1 = self._fit_gram(indices, values, B, d, data.n, info)
            else:
                obs.set_on_open("estimator.fit", engine="gather", d_pad=d + 1)
                with obs.span("solver.gather_lbfgs"):
                    idx1, val1 = _with_intercept_lane(indices, values, d, data.n)
                    dtype = jnp.result_type(values.dtype, B.dtype)
                    W1 = run_lbfgs(
                        {"indices": idx1, "values": val1}, B, lam=self.lam,
                        num_iterations=self.num_iterations,
                        convergence_tol=self.convergence_tol,
                        n=data.n,
                        W_init=jnp.zeros((d + 1, B.shape[1]), dtype=dtype),
                        info=info,
                    )
            mapper = SparseLinearMapper(W1[:-1], b_opt=W1[-1])
        else:
            A = jnp.asarray(data.array)
            npad = A.shape[0]
            ones = (jnp.arange(npad) < data.n).astype(A.dtype)[:, None]
            A1 = jnp.concatenate([A, ones], axis=1)
            W1 = run_lbfgs(
                A1, B, lam=self.lam,
                num_iterations=self.num_iterations,
                convergence_tol=self.convergence_tol,
                n=data.n, info=info,
            )
            mapper = LinearMapper(W1[:-1], b_opt=W1[-1])
        # What the solve ran, for whoever audits the fit (plain numbers).
        mapper.lbfgs_iterations = info.get("iterations")
        mapper.lbfgs_loss = info.get("loss")
        return mapper

    def _fit_gram(self, indices, values, B, d: int, n: int, info: dict):
        """Gram-engine fit over RESIDENT padded-COO buffers: fold G once
        over chunks of ``gram_chunk_rows`` rows, iterate on it. The raw
        tier folds straight from the caller's arrays — each chunk is
        sliced INSIDE the fold program (:class:`_LanedRowChunks`), so no
        laned or tiled copy of the dataset is ever made (at the Amazon
        cell's 4.2M rows each such copy is 2.8 GB beside the caller's
        own). With ``compress="int16_bf16"`` the operands are encoded
        through the compressed-resident tier (``data/resident.py``)
        first — 4 bytes/nnz resident, decode fused into the fold's
        densify casts. Either way the intercept is the fold's border:
        the chunks hand ``[Y, live]`` and no ones lane."""
        from keystone_tpu import obs
        from keystone_tpu.ops import pallas_ops
        from keystone_tpu.ops.sparse import gram_pad_dim, gram_tile_pairs
        from keystone_tpu.ops.sparse_densify import densify_form

        npad, lanes = int(indices.shape[0]), int(indices.shape[1])
        c = min(self.gram_chunk_rows, npad)
        with obs.span("solver.chunk_tiles", compress=self.compress):
            if self.compress == "int16_bf16":
                from keystone_tpu.data.resident import CompressedCOOChunks

                live = (np.arange(npad) < n).astype(np.float32)[:, None]
                chunks = CompressedCOOChunks.encode(
                    np.asarray(indices), np.asarray(values),
                    np.concatenate([np.asarray(B, np.float32), live], axis=1),
                    chunk_rows=c, d=d, n_true=n,
                )
                operands = chunks.operands()
                chunk_fn = _resident_chunk_fn  # stable identity -> program reuse
                nchunks = chunks.num_chunks
            else:
                operands = (indices, values, B)
                chunk_fn = _LanedRowChunks(c, int(n))  # equal across fits
                nchunks = -(-npad // c)

        probed = {}  # slab_exact, where the range probe decided and no flag
        if self.gram_dtype == "f32":
            # Explicit f32 wins even over bf16-compressed values: the
            # slabs upcast losslessly and the syrk runs the exact 6-pass
            # recipe (the caller is paying for precision on purpose).
            val_dtype = jnp.float32
        elif (
            self.compress is not None
            or self.gram_dtype == "bf16"
            or values.dtype == jnp.bfloat16
        ):
            val_dtype = jnp.bfloat16
        elif isinstance(values, jax.core.Tracer) or isinstance(B, jax.core.Tracer):
            val_dtype = jnp.float32  # a fit under a trace: nothing to observe
        else:
            # No flag decides: the slab type follows the values' RANGE.
            # The verdict is a static key of the fold program, so the host
            # reads it first — its one other wait for the device.
            verdict = _slabs_exact_in_bf16(values, B)
            with obs.span("executor.drain", site="slab_probe"):
                exact = probed["slab_exact"] = bool(jax.device_get(verdict))
            val_dtype = jnp.bfloat16 if exact else jnp.float32
            obs.counter_track("sparse.exact_bf16_fits", int(exact))
        use_pallas = pallas_ops.pallas_direct_ok(*operands)
        d_pad = gram_pad_dim(d, val_dtype)
        obs.set_on_open(
            "estimator.fit", engine="gram", compress=self.compress,
            slab_dtype=jnp.dtype(val_dtype).name, chunks=nchunks,
            d_pad=d_pad, tile_pairs=gram_tile_pairs(d, val_dtype),
            intercept="border", pallas=bool(use_pallas),
            densify=densify_form(use_pallas, c, d_pad, val_dtype),
            **probed,
        )
        solved: dict = {}
        W, final_loss = run_lbfgs_gram_streamed(
            chunk_fn, nchunks, d, B.shape[1],
            lam=self.lam, num_iterations=self.num_iterations,
            convergence_tol=self.convergence_tol, n=n,
            use_pallas=use_pallas,
            val_dtype=val_dtype,
            border=True,
            operands=operands,
            # Resident operands already hold the whole dataset: the
            # double-buffered second slab would be pure extra HBM beside
            # them (the measured resident-capacity cliff sits at n=30e6 /
            # 9.8 GB — bench.py's probe), and there is no regen work to
            # overlap — chunks are slices of the resident buffers.
            pipeline=False,
            info=solved,
        )
        obs.counter_track("sparse.rows_folded", nchunks * c)
        obs.counter_track("sparse.nnz_folded", nchunks * c * lanes)
        with obs.span("solver.lbfgs", engine="gram", stage="read"):
            _report_solve(final_loss, solved["iterations"], info, "LBFGS(gram)")
        return W

    # Measured on-chip calibration (BENCH_r04 amazon row): the gram
    # engine's one-time densify+syrk fold plus 20 G-space iterations cost
    # ~4.5 gather-engine iterations end-to-end at the Amazon geometry —
    # the MXU-vs-random-access gap the reference's CPU-fitted weights
    # cannot express analytically.
    _GRAM_FOLD_ITER_EQUIV = 4.5

    def cost(
        self, n, d, k, sparsity, num_machines, cpu_weight, mem_weight, network_weight,
        sparse_overhead: Optional[float] = None,
    ) -> float:
        """Analytic cost model (LBFGS.scala:264-280). The ``gram`` engine
        is priced as a measured iteration-equivalent of the gather engine
        (fold once, then data-free iterations) — see _GRAM_FOLD_ITER_EQUIV.
        ``sparse_overhead`` (the gather engine's random-access multiplier
        on the sequential mem rate) defaults from the weight family active
        at CONSTRUCTION (cost.sparse_gather_overhead): 500 for the TPU
        weights — measured 2.1e8 random cells/s vs the sequential-scan
        rate on the amazon bench row — 8 for the reference's EC2 set."""
        import math

        if sparse_overhead is None:
            # getattr: instances unpickled from pre-round-6 saves lack the
            # construction-time attribute — resolve from the env then.
            sparse_overhead = getattr(self, "_sparse_overhead", None)
        if sparse_overhead is None:
            from keystone_tpu.ops.learning import cost as cost_mod

            sparse_overhead = cost_mod.sparse_gather_overhead()
        flops = n * sparsity * d * k / num_machines
        bytes_scanned = n * d * sparsity / num_machines
        network = 2.0 * d * k * math.log2(max(num_machines, 2))
        per_iter = (
            sparse_overhead * max(cpu_weight * flops, mem_weight * bytes_scanned)
            + network_weight * network
        )
        if self.solver == "gram":
            iters_equiv = min(self._GRAM_FOLD_ITER_EQUIV, self.num_iterations)
            return iters_equiv * per_iter + mem_weight * d * d / num_machines
        return self.num_iterations * per_iter

    def resident_bytes(self, n, d, k, sparsity, num_machines) -> float:
        """Capacity model: padded-COO operand (int32 index + f32 value
        per stored cell — or the compressed tier's 4 B/nnz int16+bf16
        encoding when ``compress`` is set, infeasible past the int16
        index boundary), labels, history pairs; the gram engine adds
        its (d_pad)^2 f32 Gramian."""
        if self.compress is not None:
            from keystone_tpu.data import resident as resident_mod

            # The intercept is the fold's border: no lane at index d.
            if not resident_mod.compressible_dim(d):
                return float("inf")
            bytes_per_nnz = resident_mod.COMPRESSED_BYTES_PER_NNZ
        else:
            bytes_per_nnz = 8.0
        coo = bytes_per_nnz * n * d * sparsity / num_machines
        gram = 4.0 * d * d if self.solver == "gram" else 0.0
        return (
            coo
            + 4.0 * n * k / num_machines
            + 8.0 * _LBFGS_HISTORY * d * k
            + gram
        )
