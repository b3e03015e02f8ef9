"""Pallas TPU kernels for the framework's hot ops.

The reference's performance-critical inner loops are per-partition BLAS-3
calls (Convolver im2col GEMM, KernelGenerator's blocked ``‖x−y‖²`` + exp,
CosineRandomFeatures' broadcast-W GEMM + cos, the BCD solvers' Gramian /
correlation GEMMs — nodes/learning/*, nodes/stats/CosineRandomFeatures.scala).
On TPU those are MXU matmuls; the wins left on the table by stock XLA are
(a) fusing the elementwise epilogue (exp/cos) into the matmul's output tiles
so the (m, n) intermediate never round-trips HBM, and (b) computing AᵀA and
AᵀR in a single pass over A (one HBM read instead of two).

Every kernel here is a tiled matmul with a K-innermost accumulation grid:

    grid = (m_tiles, n_tiles, k_tiles)        # k varies fastest
    acc  = VMEM scratch, zeroed at k == 0
    epilogue applied and written out at k == k_tiles - 1

All kernels take a ``compute_dtype``: with ``bfloat16`` the operand tiles are
cast before hitting the MXU while the accumulator and epilogue stay float32
(preferred_element_type) — the standard TPU mixed-precision recipe.

Wrappers pad inputs to tile multiples (zero rows/cols are exact for the dot
contractions) and slice the result. On the TPU backend every kernel is
compiled by Mosaic unless a caller passes ``interpret=True``; off it the
Pallas interpreter runs them (logged once at WARNING), so the same code
paths are unit-testable on CPU. Every ``pallas_call`` goes through
:func:`_pallas_call`, which names the kernel in the lowered program and
reports the dispatch to :func:`record_dispatches` listeners.
"""

from __future__ import annotations

import contextlib
import functools
import logging
import os
from typing import Iterator, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "record_dispatches",
    "countsketch_scatter",
    "gaussian_kernel_block",
    "gaussian_resid_block",
    "cosine_features",
    "gram_corr",
    "gram_corr_sym",
    "gram_corr_sym_acc",
    "gram_corr_acc_ok",
    "pallas_enabled",
    "pallas_direct_ok",
]

_TILE_M = 256
_TILE_N = 256
_TILE_K = 512

logger = logging.getLogger(__name__)


@functools.cache
def _warn_interpreting(backend: str) -> None:
    logger.warning(
        "Pallas kernels are running in the INTERPRETER (backend %r is not "
        "tpu): results are for correctness only, never a device timing",
        backend,
    )


def _interpret() -> bool:
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    _warn_interpreting(backend)
    return True


# Lists handed out by record_dispatches(); _pallas_call appends to each.
_dispatch_logs: List[List[Tuple[str, bool]]] = []


@contextlib.contextmanager
def record_dispatches() -> Iterator[List[Tuple[str, bool]]]:
    """Yield a list that receives ``(kernel_name, interpreted)`` for every
    kernel this package dispatches while the block is open. Dispatch is a
    TRACE-time event: a jitted caller reports its kernels when it is
    traced, not on each cached execution — which is exactly what says
    which side (kernel or XLA) a freshly built program took."""
    log: List[Tuple[str, bool]] = []
    _dispatch_logs.append(log)
    try:
        yield log
    finally:
        _dispatch_logs.remove(log)


def _pallas_call(name: str, kernel, *, interpret: Optional[bool], **kwargs):
    """The one ``pl.pallas_call`` site: resolves ``interpret`` (None =
    Mosaic on TPU, interpreter elsewhere), stamps ``name`` on the custom
    call (``kernel_name`` in the lowered text and in device traces), and
    reports the dispatch."""
    interpret = _interpret() if interpret is None else bool(interpret)
    for log in _dispatch_logs:
        log.append((name, interpret))
    return pl.pallas_call(kernel, name=name, interpret=interpret, **kwargs)


def _dot_kwargs(compute_dtype):
    """MXU precision recipe: float32 operands need precision=HIGHEST (the
    TPU hardware default is a single bf16 pass, ~1e-1 absolute error on O(1)
    data); bfloat16 operands hit the MXU natively and accumulate in float32
    via preferred_element_type — with precision pinned to DEFAULT so the
    package-wide f32 matmul default cannot leak a contract_precision<fp32>
    attribute onto bf16 vectors (which crashes Mosaic)."""
    if compute_dtype == jnp.float32:
        return dict(
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        )
    return dict(
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.DEFAULT,
    )


def pallas_enabled() -> bool:
    """True when the Pallas kernels should be used.

    Requires the TPU backend. Multi-device callers reach the kernels through
    ``shard_map`` wrappers (each shard's tile is unsharded inside the body,
    so ``pallas_call`` composes; the collectives around it are explicit
    psums/ppermutes) — see ``parallel.linalg`` (sharded BCD gram+corr) and
    ``parallel.ring`` (ring kernel blocks). Callers that dispatch a kernel
    *directly* on eager arrays must additionally check
    :func:`pallas_direct_ok`, since GSPMD cannot partition a bare
    ``pallas_call`` over a sharded operand. ``KEYSTONE_PALLAS=1`` forces the
    kernels on off-TPU (interpret mode); ``KEYSTONE_NO_PALLAS=1`` forces
    them off.
    """
    if os.environ.get("KEYSTONE_NO_PALLAS"):
        return False
    if os.environ.get("KEYSTONE_PALLAS"):
        return True
    return jax.default_backend() == "tpu"


def pallas_direct_ok(*arrays) -> bool:
    """True when a *direct* (non-shard_map) kernel dispatch is safe for these
    eager operands: Pallas enabled and no operand sharded across devices.
    A bare ``pallas_call`` on a multi-device-sharded array would force XLA
    to gather it to one device — such callers should take a shard_map
    wrapper or the XLA path instead."""
    if not pallas_enabled():
        return False
    for a in arrays:
        sharding = getattr(a, "sharding", None)
        if sharding is None:
            continue
        try:
            if len(sharding.device_set) > 1 and not sharding.is_fully_replicated:
                return False
        except Exception:
            return False
    return True


def _pad_to(x, multiple: int, axis: int):
    size = x.shape[axis]
    rem = (-size) % multiple
    if rem == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, rem)
    return jnp.pad(x, widths)


# ---------------------------------------------------------------------------
# Fused Gaussian kernel block: exp(-gamma * (‖x‖² + ‖y‖² − 2 x·y))
# ---------------------------------------------------------------------------


def _gaussian_kernel_kernel(
    x_ref, y_ref, xn_ref, yn_ref, out_ref, acc_ref, *, gamma, nk, compute_dtype
):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    acc_ref[:] += jax.lax.dot_general(
        x_ref[:].astype(compute_dtype),
        y_ref[:].astype(compute_dtype),
        dimension_numbers=(((1,), (1,)), ((), ())),
        **_dot_kwargs(compute_dtype),
    )

    @pl.when(k == nk - 1)
    def _():
        sq = xn_ref[:] + yn_ref[:] - 2.0 * acc_ref[:]
        out_ref[:] = jnp.exp(-gamma * jnp.maximum(sq, 0.0)).astype(out_ref.dtype)


def gaussian_kernel_block(
    X,
    Y,
    x_norms,
    y_norms,
    gamma: float,
    compute_dtype=jnp.float32,
    interpret: Optional[bool] = None,
):
    """K[i, j] = exp(-gamma * ‖X_i − Y_j‖²) as one fused Pallas kernel.

    X: (m, d), Y: (n, d), x_norms: (m,), y_norms: (n,). The distance matrix
    is never materialized in HBM — the norm-broadcast + exp epilogue runs on
    the accumulator tile in VMEM (reference computes the same algebra
    unfused: KernelGenerator.scala:121-205). (The bf16x3 / Precision.HIGH
    kernel mode lives on the XLA path only — Mosaic has no 3-pass dot
    lowering; see kernel.py::_gaussian_block.)
    """
    X = jnp.asarray(X, dtype=jnp.float32)
    Y = jnp.asarray(Y, dtype=jnp.float32)
    m, d = X.shape
    n = Y.shape[0]
    xn = jnp.asarray(x_norms, dtype=jnp.float32).reshape(m, 1)
    yn = jnp.asarray(y_norms, dtype=jnp.float32).reshape(1, n)

    tm, tn, tk = min(_TILE_M, m), min(_TILE_N, n), min(_TILE_K, d)
    Xp = _pad_to(_pad_to(X, tm, 0), tk, 1)
    Yp = _pad_to(_pad_to(Y, tn, 0), tk, 1)
    xnp = _pad_to(xn, tm, 0)
    ynp = _pad_to(yn, tn, 1)
    mp, dp = Xp.shape
    np_ = Yp.shape[0]
    nk = dp // tk

    out = _pallas_call(
        "gaussian_kernel_block",
        functools.partial(
            _gaussian_kernel_kernel,
            gamma=float(gamma),
            nk=nk,
            compute_dtype=compute_dtype,
        ),
        grid=(mp // tm, np_ // tn, nk),
        in_specs=[
            pl.BlockSpec((tm, tk), lambda i, j, k: (i, k)),
            pl.BlockSpec((tn, tk), lambda i, j, k: (j, k)),
            pl.BlockSpec((tm, 1), lambda i, j, k: (i, 0)),
            pl.BlockSpec((1, tn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((tm, tn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.float32),
        scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
        interpret=interpret,
    )(Xp, Yp, xnp, ynp)
    return out[:m, :n]


# ---------------------------------------------------------------------------
# Fused Gaussian kernel block + residual epilogue: (K_block)ᵀ W
# ---------------------------------------------------------------------------


def _gaussian_resid_kernel(
    x_ref, y_ref, xn_ref, yn_ref, w_ref, out_ref, acc_ref, *,
    gamma, nk, compute_dtype
):
    """Grid (j, i, k): j over the block's columns (slowest — the resid tile
    (j, 0) stays resident across the whole i sweep), i over train-row
    tiles, k over feature tiles. The kernel tile K(i, j) is assembled in
    VMEM at k == nk − 1 and immediately contracted into the residual —
    it is never written to HBM."""
    i = pl.program_id(1)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    acc_ref[:] += jax.lax.dot_general(
        x_ref[:].astype(compute_dtype),
        y_ref[:].astype(compute_dtype),
        dimension_numbers=(((1,), (1,)), ((), ())),
        **_dot_kwargs(compute_dtype),
    )

    @pl.when((i == 0) & (k == 0))
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    @pl.when(k == nk - 1)
    def _():
        sq = xn_ref[:] + yn_ref[:] - 2.0 * acc_ref[:]
        kt = jnp.exp(-gamma * jnp.maximum(sq, 0.0))
        # The residual contraction runs exact-f32 (kt is f32 from the exp
        # epilogue); its MXU cost is one 128-lane tile per K tile — noise
        # beside the kernel-generation GEMM it rides on.
        out_ref[:] += jax.lax.dot_general(
            kt, w_ref[:],
            dimension_numbers=(((0,), (0,)), ((), ())),
            **_dot_kwargs(jnp.float32),
        )


def gaussian_resid_block(
    X,
    Y,
    x_norms,
    y_norms,
    W,
    gamma: float,
    compute_dtype=jnp.float32,
    interpret: Optional[bool] = None,
):
    """resid = K(X, Y)ᵀ @ W with the kernel block fused away.

    X: (m, d) train rows, Y: (n, d) the block's rows, W: (m, k) the dual
    model. Computes K[i, j] = exp(-γ‖X_i − Y_j‖²) tile-by-tile in VMEM and
    contracts each finished tile into the (n, k) residual in the same grid
    step — the (m, n) kernel block never exists in HBM (the separate
    ``gaussian_kernel_block`` + XLA ``K.T @ W`` composition writes and
    re-reads it: 2·m·n·4 bytes per block step at the KRR geometry).

    Padding is exact: ghost train rows have nonzero kernel values but zero
    W rows; ghost feature columns are zero in both operands; ghost block
    rows are sliced off the result. The caller masks ghost-column rows of
    the residual downstream (the same ``valid_col`` mask the unfused path
    applies). Requires W's rows beyond the true train count to be zero —
    the KRR solver's invariant (ghost solves are exactly zero).
    """
    X = jnp.asarray(X, dtype=jnp.float32)
    Y = jnp.asarray(Y, dtype=jnp.float32)
    W = jnp.asarray(W, dtype=jnp.float32)
    m, d = X.shape
    n = Y.shape[0]
    kdim = W.shape[1]
    xn = jnp.asarray(x_norms, dtype=jnp.float32).reshape(m, 1)
    yn = jnp.asarray(y_norms, dtype=jnp.float32).reshape(1, n)

    tm, tn, tk = min(_TILE_M, m), min(_TILE_N, n), min(_TILE_K, d)
    tr = max(128, ((kdim + 127) // 128) * 128)
    Xp = _pad_to(_pad_to(X, tm, 0), tk, 1)
    Yp = _pad_to(_pad_to(Y, tn, 0), tk, 1)
    xnp = _pad_to(xn, tm, 0)
    ynp = _pad_to(yn, tn, 1)
    Wp = _pad_to(_pad_to(W, tm, 0), tr, 1)
    mp, dp = Xp.shape
    np_ = Yp.shape[0]
    nk = dp // tk

    out = _pallas_call(
        "gaussian_resid_block",
        functools.partial(
            _gaussian_resid_kernel,
            gamma=float(gamma),
            nk=nk,
            compute_dtype=compute_dtype,
        ),
        grid=(np_ // tn, mp // tm, nk),
        in_specs=[
            pl.BlockSpec((tm, tk), lambda j, i, k: (i, k)),
            pl.BlockSpec((tn, tk), lambda j, i, k: (j, k)),
            pl.BlockSpec((tm, 1), lambda j, i, k: (i, 0)),
            pl.BlockSpec((1, tn), lambda j, i, k: (0, j)),
            pl.BlockSpec((tm, tr), lambda j, i, k: (i, 0)),
        ],
        out_specs=pl.BlockSpec((tn, tr), lambda j, i, k: (j, 0)),
        out_shape=jax.ShapeDtypeStruct((np_, tr), jnp.float32),
        scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
        interpret=interpret,
    )(Xp, Yp, xnp, ynp, Wp)
    return out[:n, :kdim]


# ---------------------------------------------------------------------------
# Fused cosine random features: cos(X Wᵀ + b)
# ---------------------------------------------------------------------------

# Even minimax polynomial for cos on [-π, π] (degree 12, fitted by iterated
# weighted lstsq; max abs error 3.8e-7 in f32 Horner — ~f32 ulp). The VPU's
# library cos costs ~50ms over the bench's 4.3e9 outputs; this Horner form
# is ~2x cheaper and exact to well below bf16 resolution.
_COS_COEFFS = (
    9.999999892578e-01,
    -4.999998919802e-01,
    4.166649038026e-02,
    -1.388780871411e-03,
    2.476998508524e-05,
    -2.707995836252e-07,
    1.724826627109e-09,
)
_TWO_PI = 6.283185307179586


def _fast_cos(x):
    """Range-reduce to [-π, π] and evaluate the even minimax polynomial.

    Accuracy is |x|-proportional through the single-constant f32 range
    reduction: ~4e-7 for |x| ≲ 10 (the cosine-feature regime — O(1)
    pre-activations plus a [0, 2π) phase), ~6e-6 at |x| ≈ 100, ~2e-5 at
    |x| ≈ 300 — the same order as f32's own argument-rounding error for
    the library cos at those magnitudes."""
    q = jnp.floor(x * (1.0 / _TWO_PI) + 0.5)
    r = x - q * _TWO_PI
    r2 = r * r
    acc = jnp.full_like(x, _COS_COEFFS[-1])
    for c in _COS_COEFFS[-2::-1]:
        acc = acc * r2 + c
    return acc


def _cosine_kernel(x_ref, w_ref, b_ref, out_ref, acc_ref, *, nk, compute_dtype):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    acc_ref[:] += jax.lax.dot_general(
        x_ref[:].astype(compute_dtype),
        w_ref[:].astype(compute_dtype),
        dimension_numbers=(((1,), (1,)), ((), ())),
        **_dot_kwargs(compute_dtype),
    )

    @pl.when(k == nk - 1)
    def _():
        out_ref[:] = _fast_cos(acc_ref[:] + b_ref[:]).astype(out_ref.dtype)


def cosine_features(
    X,
    W,
    b,
    compute_dtype=jnp.float32,
    out_dtype=None,
    interpret: Optional[bool] = None,
):
    """cos(X @ Wᵀ + b) fused into the matmul epilogue.

    X: (m, d), W: (num_out, d), b: (num_out,). The featurized (m, num_out)
    matrix is written once; the pre-activation never exists in HBM
    (reference: CosineRandomFeatures.scala:19-45). ``out_dtype=bfloat16``
    writes the feature matrix at half the HBM footprint for downstream
    bf16 solvers.
    """
    out_dtype = jnp.float32 if out_dtype is None else out_dtype
    X = jnp.asarray(X, dtype=jnp.float32)
    W = jnp.asarray(W, dtype=jnp.float32)
    m, d = X.shape
    n = W.shape[0]
    bias = jnp.asarray(b, dtype=jnp.float32).reshape(1, n)

    tm, tn, tk = min(_TILE_M, m), min(_TILE_N, n), min(_TILE_K, d)
    Xp = _pad_to(_pad_to(X, tm, 0), tk, 1)
    Wp = _pad_to(_pad_to(W, tn, 0), tk, 1)
    bp = _pad_to(bias, tn, 1)
    mp, dp = Xp.shape
    np_ = Wp.shape[0]
    nk = dp // tk

    out = _pallas_call(
        "cosine_features",
        functools.partial(_cosine_kernel, nk=nk, compute_dtype=compute_dtype),
        grid=(mp // tm, np_ // tn, nk),
        in_specs=[
            pl.BlockSpec((tm, tk), lambda i, j, k: (i, k)),
            pl.BlockSpec((tn, tk), lambda i, j, k: (j, k)),
            pl.BlockSpec((1, tn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((tm, tn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), out_dtype),
        scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
        interpret=interpret,
    )(Xp, Wp, bp)
    return out[:m, :n]


# ---------------------------------------------------------------------------
# One-pass Gramian + correlation: (AᵀA, AᵀR)
# ---------------------------------------------------------------------------


def _gram_corr_kernel(
    ai_ref, aj_ref, r_ref, gram_ref, corr_ref, gacc_ref, cacc_ref, *, nk, compute_dtype
):
    """Grid (i, j, k): gram tile (i, j) accumulates AᵢᵀAⱼ over row-tiles k;
    the corr tile (i, :) piggybacks on Aᵢ's residency when j == 0."""
    j = pl.program_id(1)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _():
        gacc_ref[:] = jnp.zeros_like(gacc_ref)

    ai = ai_ref[:].astype(compute_dtype)
    gacc_ref[:] += jax.lax.dot_general(
        ai,
        aj_ref[:].astype(compute_dtype),
        dimension_numbers=(((0,), (0,)), ((), ())),
        **_dot_kwargs(compute_dtype),
    )

    @pl.when(k == nk - 1)
    def _():
        gram_ref[:] = gacc_ref[:].astype(gram_ref.dtype)

    @pl.when((j == 0) & (k == 0))
    def _():
        cacc_ref[:] = jnp.zeros_like(cacc_ref)

    @pl.when(j == 0)
    def _():
        cacc_ref[:] += jax.lax.dot_general(
            ai,
            r_ref[:].astype(compute_dtype),
            dimension_numbers=(((0,), (0,)), ((), ())),
            **_dot_kwargs(compute_dtype),
        )

    @pl.when((j == 0) & (k == nk - 1))
    def _():
        corr_ref[:] = cacc_ref[:].astype(corr_ref.dtype)


def gram_corr(
    A,
    R,
    compute_dtype=jnp.float32,
    interpret: Optional[bool] = None,
):
    """(AᵀA, AᵀR) in a single pass over A's rows.

    A: (n, d), R: (n, k). This is the hot contraction of every normal-
    equations / BCD step (reference: mlmatrix NormalEquations; the in-tree
    pattern at BlockWeightedLeastSquares.scala:212-221 computes exactly this
    pair per block). Fusing them halves HBM traffic for A on the correlation
    side and shares the row-tile DMA schedule.
    """
    A = jnp.asarray(A)
    R = jnp.asarray(R, dtype=jnp.float32)
    if A.dtype == jnp.bfloat16:
        compute_dtype = jnp.bfloat16
    n, d = A.shape
    kdim = R.shape[1]

    ti = min(_TILE_M, d)
    tk = min(_TILE_K, n)
    Ap = _pad_to(_pad_to(A, tk, 0), ti, 1)
    # R's column count is small (num classes); pad to the 128-lane minimum.
    tr = max(128, ((kdim + 127) // 128) * 128)
    Rp = _pad_to(_pad_to(R, tk, 0), tr, 1)
    npad, dp = Ap.shape
    nk = npad // tk

    gram, corr = _pallas_call(
        "gram_corr",
        functools.partial(_gram_corr_kernel, nk=nk, compute_dtype=compute_dtype),
        grid=(dp // ti, dp // ti, nk),
        in_specs=[
            pl.BlockSpec((tk, ti), lambda i, j, k: (k, i)),
            pl.BlockSpec((tk, ti), lambda i, j, k: (k, j)),
            pl.BlockSpec((tk, tr), lambda i, j, k: (k, 0)),
        ],
        out_specs=[
            pl.BlockSpec((ti, ti), lambda i, j, k: (i, j)),
            pl.BlockSpec((ti, tr), lambda i, j, k: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((dp, dp), jnp.float32),
            jax.ShapeDtypeStruct((dp, tr), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((ti, ti), jnp.float32),
            pltpu.VMEM((ti, tr), jnp.float32),
        ],
        interpret=interpret,
    )(Ap, Ap, Rp)
    return gram[:d, :d], corr[:d, :kdim]


# ---------------------------------------------------------------------------
# Symmetric one-pass Gramian + correlation (upper-triangle blocks only)
# ---------------------------------------------------------------------------


def _gram_corr_sym_kernel(
    ii_ref, jj_ref, ai_ref, aj_ref, r_ref, gram_ref, corr_ref, *,
    nk, compute_dtype
):
    """Grid (p, k): p walks the upper-triangle block pairs (ii[p], jj[p]) in
    row-major order; k sweeps row tiles. The correlation AᵀR rides along on
    the diagonal pairs (one per block row) where Aᵢ is already resident.

    Accumulation happens directly in the f32 OUTPUT tiles: their block
    indices are k-invariant, so Mosaic keeps them resident in VMEM across
    the whole k sweep. With the riding R/corr buffers the column tile must
    stay at 512 (1024-wide bf16 tiles measure ~16.01 MB scoped VMEM — just
    over the limit; see the tiling comment in :func:`gram_corr_sym`); the
    1024-wide layout lives in the R-free split kernels."""
    p = pl.program_id(0)
    k = pl.program_id(1)
    diag = ii_ref[p] == jj_ref[p]

    @pl.when(k == 0)
    def _():
        gram_ref[:] = jnp.zeros_like(gram_ref)

    ai = ai_ref[:].astype(compute_dtype)
    gram_ref[:] += jax.lax.dot_general(
        ai,
        aj_ref[:].astype(compute_dtype),
        dimension_numbers=(((0,), (0,)), ((), ())),
        **_dot_kwargs(compute_dtype),
    )

    @pl.when(diag & (k == 0))
    def _():
        corr_ref[:] = jnp.zeros_like(corr_ref)

    @pl.when(diag)
    def _():
        corr_ref[:] += jax.lax.dot_general(
            ai,
            r_ref[:].astype(compute_dtype),
            dimension_numbers=(((0,), (0,)), ((), ())),
            **_dot_kwargs(compute_dtype),
        )


def gram_corr_sym(
    A,
    R,
    compute_dtype=jnp.float32,
    interpret: Optional[bool] = None,
):
    """(AᵀA, AᵀR) computing only the upper triangle of AᵀA and mirroring.

    Does ~half the MXU work and HBM traffic of the dense version for the
    Gramian — the symmetric-rank-k update (BLAS ``syrk``) the reference gets
    from netlib and XLA does not exploit. Block pairs are enumerated
    row-major via scalar-prefetched index arrays.

    A may be bfloat16 — tiles then hit the MXU natively with float32
    accumulation, and HBM traffic is half that of an f32 layout.

    (Column-window variants for the fused BCD solvers live in
    :func:`block_gram_sym` / :func:`block_corr` — those read the window
    strided out of the flat feature buffer with no slice copy.)
    """
    A = jnp.asarray(A)
    R = jnp.asarray(R, dtype=jnp.float32)
    if A.dtype == jnp.bfloat16:
        compute_dtype = jnp.bfloat16
    n, d = A.shape
    kdim = R.shape[1]

    # 512-wide column tiles: with R riding along (corr output + its tile
    # double-buffered next to the gram tile), 1024-wide bf16 tiles measure
    # ~16.01 MB scoped VMEM — 12 KB OVER the 16 MB limit at bs=4096
    # blocks (found by parity.py's TIMIT row through the stacked BCD
    # path). The 1024-wide bf16 layout lives in the R-free split kernels
    # (:func:`block_gram_sym` / :func:`block_corr`), which the flat BCD
    # path uses. Smaller models fall back to one 128-multiple tile.
    ti = min(512, ((d + 127) // 128) * 128)
    tk = min(_TILE_K, n)
    Ap = _pad_to(_pad_to(A, tk, 0), ti, 1)
    Rp = _pad_to(R, tk, 0)
    tr = max(128, ((kdim + 127) // 128) * 128)
    Rp = _pad_to(Rp, tr, 1)
    npad, dp = Ap.shape
    nk = npad // tk
    nt = dp // ti

    pairs = [(i, j) for i in range(nt) for j in range(i, nt)]
    ii = jnp.asarray(np.array([p[0] for p in pairs], dtype=np.int32))
    jj = jnp.asarray(np.array([p[1] for p in pairs], dtype=np.int32))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(len(pairs), nk),
        in_specs=[
            pl.BlockSpec((tk, ti), lambda p, k, ii, jj: (k, ii[p])),
            pl.BlockSpec((tk, ti), lambda p, k, ii, jj: (k, jj[p])),
            # Off-diagonal pairs never read R: pin their index to block
            # (0, 0) so the tile stays resident instead of streaming the
            # whole of R past every pair.
            pl.BlockSpec(
                (tk, tr),
                lambda p, k, ii, jj: (jnp.where(ii[p] == jj[p], k, 0), 0),
            ),
        ],
        out_specs=[
            pl.BlockSpec((ti, ti), lambda p, k, ii, jj: (ii[p], jj[p])),
            pl.BlockSpec((ti, tr), lambda p, k, ii, jj: (ii[p], 0)),
        ],
    )
    gram_u, corr = _pallas_call(
        "gram_corr_sym",
        functools.partial(
            _gram_corr_sym_kernel, nk=nk, compute_dtype=compute_dtype
        ),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((dp, dp), jnp.float32),
            jax.ShapeDtypeStruct((dp, tr), jnp.float32),
        ],
        interpret=interpret,
    )(ii, jj, Ap, Ap, Rp)
    # Mirror the (written) upper triangle; lower-triangle blocks are
    # undefined memory, so build from triu explicitly.
    upper = jnp.triu(gram_u)
    gram = upper + jnp.triu(gram_u, 1).T
    return gram[:d, :d], corr[:d, :kdim]


def _strided_ti(dtype, block: int) -> int:
    """Column-tile width for the strided window kernels: 1024 for bf16
    layouts, 512 for f32 (whose doubled tile bytes overflow the 16 MB
    scoped-VMEM limit at 1024)."""
    wide = 1024 if dtype == jnp.bfloat16 else 512
    return min(wide, ((block + 127) // 128) * 128)


def _gram_sym_kernel(ii_ref, jj_ref, ai_ref, aj_ref, gram_ref, *, nk,
                     compute_dtype):
    """Gram-only variant of _gram_corr_sym_kernel: no R operand, no corr
    output — the in-loop strided BCD path computes the correlation with
    :func:`block_corr` instead, because the riding-R buffers are exactly
    what pushes the 1024-tile layout past the 16 MB scoped-VMEM limit
    inside a while_loop."""
    p = pl.program_id(0)
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _():
        gram_ref[:] = jnp.zeros_like(gram_ref)

    gram_ref[:] += jax.lax.dot_general(
        ai_ref[:].astype(compute_dtype),
        aj_ref[:].astype(compute_dtype),
        dimension_numbers=(((0,), (0,)), ((), ())),
        **_dot_kwargs(compute_dtype),
    )


def block_gram_sym(F, col_start, block: int, interpret: Optional[bool] = None):
    """Symmetric Gramian of a column window of F, tiles read strided (no
    slice copy); ``col_start`` may be traced. Requires ``strided_gram_ok``."""
    F = jnp.asarray(F)
    compute_dtype = jnp.bfloat16 if F.dtype == jnp.bfloat16 else jnp.float32
    n, d = F.shape
    ti = _strided_ti(F.dtype, block)
    tk = min(_TILE_K, n)
    nt = block // ti
    nk = n // tk
    base = jnp.asarray(col_start, jnp.int32) // ti
    pairs = [(i, j) for i in range(nt) for j in range(i, nt)]
    ii = base + jnp.asarray(np.array([p[0] for p in pairs], dtype=np.int32))
    jj = base + jnp.asarray(np.array([p[1] for p in pairs], dtype=np.int32))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(len(pairs), nk),
        in_specs=[
            pl.BlockSpec((tk, ti), lambda p, k, ii, jj: (k, ii[p])),
            pl.BlockSpec((tk, ti), lambda p, k, ii, jj: (k, jj[p])),
        ],
        out_specs=pl.BlockSpec(
            (ti, ti), lambda p, k, ii, jj: (ii[p] - ii[0], jj[p] - ii[0])
        ),
    )
    gram_u = _pallas_call(
        "block_gram_sym",
        functools.partial(_gram_sym_kernel, nk=nk, compute_dtype=compute_dtype),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((block, block), jnp.float32),
        interpret=interpret,
    )(ii, jj, F, F)
    upper = jnp.triu(gram_u)
    return upper + jnp.triu(gram_u, 1).T


def strided_gram_ok(F, block: int) -> bool:
    """Static alignment check for the strided column-window kernels: row
    count divisible by the k tile, block width by the column tile."""
    n, d = F.shape
    ti = _strided_ti(F.dtype, block)
    return n % min(_TILE_K, n) == 0 and block % ti == 0 and d % block == 0


def _gram_sym_acc_kernel(ii_ref, jj_ref, g_ref, ai_ref, aj_ref, out_ref, *,
                         compute_dtype):
    """out[pair p] = g[pair p] + Σ_k AᵢᵀAⱼ — the accumulating syrk the
    streaming (out-of-core) fit path folds over row tiles: the running
    Gramian rides through as an operand, so the per-tile contribution never
    materializes as a separate (d, d) buffer + add. Upper-triangle pairs
    only (mirror once at the end of the sweep)."""
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _():
        out_ref[:] = g_ref[:]

    out_ref[:] += jax.lax.dot_general(
        ai_ref[:].astype(compute_dtype),
        aj_ref[:].astype(compute_dtype),
        dimension_numbers=(((0,), (0,)), ((), ())),
        **_dot_kwargs(compute_dtype),
    )


def gram_sym_acc(G, F, interpret: Optional[bool] = None):
    """G + FᵀF, accumulating only upper-triangle blocks of the Gramian.

    G: (d, d) float32 with a *meaningful upper triangle only*; F: (n, d).
    G is updated IN PLACE — the operand is aliased to the output, so a
    loop that carries G (``sparse.sparse_gram_fold``'s chunk scan) hands
    the kernel its carry and copies nothing; a caller that still uses
    its G afterwards gets a copy made for it by XLA, as for any donated
    operand. The upper-triangle blocks of the result hold the
    accumulation; the strictly-lower blocks are written by no grid step
    and keep WHAT WENT IN (the carry's zeros), not a sum — do not read
    them. Callers mirror once after the last accumulation
    (``jnp.triu(G) + jnp.triu(G, 1).T``). This is the
    per-partition Gramian accumulation of the reference's streaming
    solvers (BlockWeightedLeastSquares.scala:177-313's per-partition
    AᵀA + treeReduce) as a TPU kernel folded over row tiles.

    Alignment: requires ``gram_acc_ok(F)`` (row count divisible by the k
    tile, d by the column tile).
    """
    F = jnp.asarray(F)
    G = jnp.asarray(G, dtype=jnp.float32)
    compute_dtype = jnp.bfloat16 if F.dtype == jnp.bfloat16 else jnp.float32
    n, d = F.shape
    ti = _strided_ti(F.dtype, d)
    tk = min(_TILE_K, n)
    nt = d // ti
    nk = n // tk
    pairs = [(i, j) for i in range(nt) for j in range(i, nt)]
    ii = jnp.asarray(np.array([p[0] for p in pairs], dtype=np.int32))
    jj = jnp.asarray(np.array([p[1] for p in pairs], dtype=np.int32))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(len(pairs), nk),
        in_specs=[
            pl.BlockSpec((ti, ti), lambda p, k, ii, jj: (ii[p], jj[p])),
            pl.BlockSpec((tk, ti), lambda p, k, ii, jj: (k, ii[p])),
            pl.BlockSpec((tk, ti), lambda p, k, ii, jj: (k, jj[p])),
        ],
        out_specs=pl.BlockSpec(
            (ti, ti), lambda p, k, ii, jj: (ii[p], jj[p])
        ),
    )
    return _pallas_call(
        "gram_sym_acc",
        functools.partial(
            _gram_sym_acc_kernel, compute_dtype=compute_dtype
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((d, d), jnp.float32),
        # G is updated in place (operand 2, after the two scalar-prefetch
        # arrays, is output 0): block (i, j) is read at its pair's first
        # row tile and written back when the pair ends, and no later pair
        # reads it, so the pipeline never sees a block it has overwritten.
        input_output_aliases={2: 0},
        # The riding G operand (f32 in + out at (ti, ti)) pushes scoped
        # VMEM to ~20 MB at 1024-wide bf16 tiles — past the compiler's
        # conservative 16 MB default but well under the chip's 128 MB.
        # Raising the limit keeps the wide tiles (F is re-read (nt+1)
        # times per row tile, so halving nt halves that traffic).
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=48 * 1024 * 1024
        ),
        interpret=interpret,
    )(ii, jj, G, F, F)


def gram_acc_ok(F) -> bool:
    """Static alignment check for :func:`gram_sym_acc`."""
    n, d = F.shape
    ti = _strided_ti(F.dtype, d)
    return n % min(_TILE_K, n) == 0 and d % ti == 0


def _gram_corr_sym_acc_kernel(
    ii_ref, jj_ref, g_ref, c_ref, ai_ref, aj_ref, r_ref, gout_ref, cout_ref,
    *, compute_dtype
):
    """The streaming fold's ONE-kernel chunk step: grid (p, k) over
    upper-triangle block pairs × row tiles, accumulating BOTH

        gout[pair p] = g[pair p] + Σ_k FᵢᵀFⱼ        (the syrk)
        cout[row i]  = c[row i]  + Σ_k FᵢᵀR          (the correlation)

    with the correlation riding the diagonal pairs exactly like
    :func:`gram_corr_sym` — Fᵢ's tiles are already resident there, so the
    correlation adds one (tk, tr) R stream and zero extra reads of F. The
    running (G, C) ride through as operands aliased to the outputs (same
    contract as :func:`gram_sym_acc`): the per-chunk contribution never materializes
    as separate (d, d)/(d, k) buffers + adds, and the separate XLA FᵀR
    GEMM — which re-read the whole chunk slab from HBM — disappears."""
    p = pl.program_id(0)
    k = pl.program_id(1)
    diag = ii_ref[p] == jj_ref[p]

    @pl.when(k == 0)
    def _():
        gout_ref[:] = g_ref[:]

    ai = ai_ref[:].astype(compute_dtype)
    gout_ref[:] += jax.lax.dot_general(
        ai,
        aj_ref[:].astype(compute_dtype),
        dimension_numbers=(((0,), (0,)), ((), ())),
        **_dot_kwargs(compute_dtype),
    )

    @pl.when(diag & (k == 0))
    def _():
        cout_ref[:] = c_ref[:]

    @pl.when(diag)
    def _():
        cout_ref[:] += jax.lax.dot_general(
            ai,
            r_ref[:].astype(compute_dtype),
            dimension_numbers=(((0,), (0,)), ((), ())),
            **_dot_kwargs(compute_dtype),
        )


def gram_corr_sym_acc(G, C, F, R, interpret: Optional[bool] = None):
    """(G + FᵀF, C + FᵀR) in a single pass over F — the fused form of
    ``gram_sym_acc(G, F)`` + an XLA ``FᵀR`` GEMM.

    G: (d, d) f32 with a *meaningful upper triangle only* (the
    :func:`gram_sym_acc` contract — updated in place, strictly-lower
    blocks of the result keep what went in; mirror once after the last
    accumulation). C: (d, k) f32, fully valid in and out (its
    lane-padded copy is the aliased operand). F: (n, d), R: (n, k) — R is
    quantized to F's compute dtype inside the kernel, matching the
    unfused composition's ``FᵀR.astype(F.dtype)`` recipe bit-for-bit in
    operand precision. Requires :func:`gram_corr_acc_ok`.
    """
    F = jnp.asarray(F)
    G = jnp.asarray(G, dtype=jnp.float32)
    R = jnp.asarray(R, dtype=jnp.float32)
    C = jnp.asarray(C, dtype=jnp.float32)
    compute_dtype = jnp.bfloat16 if F.dtype == jnp.bfloat16 else jnp.float32
    n, d = F.shape
    kdim = R.shape[1]
    ti = _strided_ti(F.dtype, d)
    tk = min(_TILE_K, n)
    tr = max(128, ((kdim + 127) // 128) * 128)
    Cp = _pad_to(C, tr, 1)
    Rp = _pad_to(R, tr, 1)
    nt = d // ti
    nk = n // tk
    pairs = [(i, j) for i in range(nt) for j in range(i, nt)]
    ii = jnp.asarray(np.array([p[0] for p in pairs], dtype=np.int32))
    jj = jnp.asarray(np.array([p[1] for p in pairs], dtype=np.int32))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(len(pairs), nk),
        in_specs=[
            pl.BlockSpec((ti, ti), lambda p, k, ii, jj: (ii[p], jj[p])),
            pl.BlockSpec((ti, tr), lambda p, k, ii, jj: (ii[p], 0)),
            pl.BlockSpec((tk, ti), lambda p, k, ii, jj: (k, ii[p])),
            pl.BlockSpec((tk, ti), lambda p, k, ii, jj: (k, jj[p])),
            # Off-diagonal pairs never read R: pin their index so the tile
            # stays resident instead of streaming R past every pair.
            pl.BlockSpec(
                (tk, tr),
                lambda p, k, ii, jj: (jnp.where(ii[p] == jj[p], k, 0), 0),
            ),
        ],
        out_specs=[
            pl.BlockSpec((ti, ti), lambda p, k, ii, jj: (ii[p], jj[p])),
            # The corr tile (i, 0) is written on row i's diagonal pair —
            # FIRST in the row-major pair order — then stays resident
            # (untouched) across the row's off-diagonal pairs and flushes
            # at the row boundary.
            pl.BlockSpec((ti, tr), lambda p, k, ii, jj: (ii[p], 0)),
        ],
    )
    gout, cout = _pallas_call(
        "gram_corr_sym_acc",
        functools.partial(
            _gram_corr_sym_acc_kernel, compute_dtype=compute_dtype
        ),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((d, d), jnp.float32),
            jax.ShapeDtypeStruct((d, tr), jnp.float32),
        ],
        # G and the lane-padded C are updated in place (operands 2 and 3,
        # after the two scalar-prefetch arrays, are outputs 0 and 1): see
        # gram_sym_acc; C's row block is read on the row's diagonal pair,
        # the row's first, and written back at the row boundary.
        input_output_aliases={2: 0, 3: 1},
        # Riding G in+out at (ti, ti) f32 plus the corr/R tiles measures
        # ~22 MB scoped VMEM at 1024-wide bf16 tiles — past the compiler's
        # conservative 16 MB default, well under the chip's 128 MB (same
        # reasoning as gram_sym_acc, plus the ~3 MB corr ride).
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=64 * 1024 * 1024
        ),
        interpret=interpret,
    )(ii, jj, G, Cp, F, F, Rp)
    return gout, cout[:, :kdim]


def gram_corr_acc_ok(F) -> bool:
    """Static alignment check for :func:`gram_corr_sym_acc` (same tiling
    as the gram-only accumulator; R/C widths are lane-padded internally)."""
    return gram_acc_ok(F)


def _block_corr_kernel(base_ref, f_ref, r_ref, out_ref, *, compute_dtype):
    """out[p] = F_windowᵀ R accumulated over row tiles (grid (p, k))."""
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    out_ref[:] += jax.lax.dot_general(
        f_ref[:].astype(compute_dtype),
        r_ref[:].astype(compute_dtype),
        dimension_numbers=(((0,), (0,)), ((), ())),
        **_dot_kwargs(compute_dtype),
    )


def block_corr(F, col_start, block: int, R, interpret: Optional[bool] = None):
    """F[:, col_start:col_start+block]ᵀ @ R with strided reads of F (no
    column-slice copy). ``col_start`` may be traced. Returns (block, k) f32.
    Requires ``strided_gram_ok``."""
    F = jnp.asarray(F)
    R = jnp.asarray(R, dtype=jnp.float32)
    compute_dtype = jnp.bfloat16 if F.dtype == jnp.bfloat16 else jnp.float32
    n, d = F.shape
    kdim = R.shape[1]
    ti = _strided_ti(F.dtype, block)
    tk = min(_TILE_K, n)
    tr = max(128, ((kdim + 127) // 128) * 128)
    Rp = _pad_to(R, tr, 1)
    nt = block // ti
    nk = n // tk
    base = jnp.asarray(col_start, jnp.int32).reshape(1) // ti

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nt, nk),
        in_specs=[
            pl.BlockSpec((tk, ti), lambda p, k, b: (k, b[0] + p)),
            pl.BlockSpec((tk, tr), lambda p, k, b: (k, 0)),
        ],
        out_specs=pl.BlockSpec((ti, tr), lambda p, k, b: (p, 0)),
    )
    corr = _pallas_call(
        "block_corr",
        functools.partial(_block_corr_kernel, compute_dtype=compute_dtype),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((block, tr), jnp.float32),
        interpret=interpret,
    )(base, F, Rp)
    return corr[:, :kdim]


def _block_resid_kernel(base_ref, f_ref, w_ref, r_ref, out_ref, *, compute_dtype):
    """out[m] = R[m] − F_window[m] @ dW accumulated over column tiles
    (grid (m, dstep); the R tile is resident across dstep)."""
    dstep = pl.program_id(1)

    @pl.when(dstep == 0)
    def _():
        out_ref[:] = r_ref[:]

    out_ref[:] -= jax.lax.dot_general(
        f_ref[:].astype(compute_dtype),
        w_ref[:].astype(compute_dtype),
        dimension_numbers=(((1,), (0,)), ((), ())),
        **_dot_kwargs(compute_dtype),
    )


def block_residual_update(
    F, col_start, block: int, dW, R, interpret: Optional[bool] = None
):
    """R − F[:, col_start:col_start+block] @ dW with strided reads of F —
    the Gauss-Seidel residual update without the column-slice copy. dW is
    (block, k) (cast to F's compute dtype by the caller for MXU-native
    bf16); R is (n, k) f32 and the result keeps f32 accumulation. Requires
    ``strided_gram_ok``."""
    F = jnp.asarray(F)
    R = jnp.asarray(R, dtype=jnp.float32)
    dW = jnp.asarray(dW)
    compute_dtype = jnp.bfloat16 if F.dtype == jnp.bfloat16 else jnp.float32
    n, d = F.shape
    kdim = R.shape[1]
    ti = _strided_ti(F.dtype, block)
    tm = min(_TILE_K, n)
    tr = max(128, ((kdim + 127) // 128) * 128)
    Rp = _pad_to(R, tr, 1)
    Wp = _pad_to(jnp.asarray(dW, dtype=compute_dtype), tr, 1)
    nd = block // ti
    nm = n // tm
    base = jnp.asarray(col_start, jnp.int32).reshape(1) // ti

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nm, nd),
        in_specs=[
            pl.BlockSpec((tm, ti), lambda m, ds, b: (m, b[0] + ds)),
            pl.BlockSpec((ti, tr), lambda m, ds, b: (ds, 0)),
            pl.BlockSpec((tm, tr), lambda m, ds, b: (m, 0)),
        ],
        out_specs=pl.BlockSpec((tm, tr), lambda m, ds, b: (m, 0)),
    )
    out = _pallas_call(
        "block_residual_update",
        functools.partial(_block_resid_kernel, compute_dtype=compute_dtype),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, tr), jnp.float32),
        interpret=interpret,
    )(base, F, Wp, Rp)
    return out[:, :kdim]


# ---------------------------------------------------------------------------
# Fused CountSketch sparse×dense-random product: S·A without the HBM scatter
# ---------------------------------------------------------------------------


def _countsketch_kernel(
    bucket_ref, sign_ref, idx_ref, val_ref, out_ref, acc_ref, *, s, nc
):
    """Grid (m_tiles, n_tiles, c_tiles), c fastest. Each step forms two
    VMEM tiles and contracts them on the MXU:

      B (tm, tc): the one-hot sketch tile, B[b, i] = sign_i·[bucket_i = b]
                  via a broadcasted-iota comparison against the global
                  bucket row.
      D (tc, tn): the densified chunk-row tile, accumulated over the s
                  nnz slots by one-hot column comparison (a masked slot
                  carries idx = −1 and never matches).

    The densify loop re-runs for every m tile, amortized over the tm
    output rows of the MXU contraction it feeds: its VPU cost is s/tm of
    the MXU MAC count, which is why tm is the largest tile."""
    i = pl.program_id(0)
    j = pl.program_id(1)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    tm, tn = acc_ref.shape
    tc = bucket_ref.shape[1]
    b_iota = jax.lax.broadcasted_iota(jnp.int32, (tm, tc), 0) + i * tm
    B = jnp.where(bucket_ref[:] == b_iota, sign_ref[:], 0.0)
    col_iota = jax.lax.broadcasted_iota(jnp.int32, (tc, tn), 1) + j * tn
    D = jnp.zeros((tc, tn), jnp.float32)
    for t in range(s):
        D = D + jnp.where(idx_ref[:, t:t + 1] == col_iota, val_ref[:, t:t + 1], 0.0)
    acc_ref[:] += jax.lax.dot_general(
        B, D,
        dimension_numbers=(((1,), (0,)), ((), ())),
        **_dot_kwargs(jnp.float32),
    )

    @pl.when(k == nc - 1)
    def _():
        out_ref[:] = acc_ref[:]


def countsketch_scatter(
    idx, val, bucket, sign, m: int, d1: int,
    interpret: Optional[bool] = None,
):
    """SA[b, j] = Σ_{i: bucket_i = b} sign_i · Σ_{t: idx[i,t] = j} val[i,t]
    — one chunk's CountSketch contribution S·A as a fused kernel (the
    remaining PAPERS.md item: fast sparse × dense-random products).

    idx: (c, s) int32 global column ids with −1 marking masked/pad slots;
    val: (c, s) float32 with 0 on masked slots; bucket: (c,) int32 in
    [0, m); sign: (c,) float32 ±1 (0 on pad rows). Returns (m, d1) f32.

    The XLA path this replaces flattens (bucket, column) to a scatter-add
    into an (m·d1,) HBM buffer — random single-element updates that
    serialize on TPU. Here the sketch matrix is never materialized in HBM
    at all: both operand tiles are built in VMEM from the (c, s) operands
    and contracted immediately. Accumulation order differs from the
    scatter (tiled f32 MXU sums), so equality against the numpy reference
    is pinned at 1e-5 relative in tests/test_pallas_ops.py, including
    chunk-fold composition.
    """
    idx = jnp.asarray(idx, jnp.int32)
    val = jnp.asarray(val, jnp.float32)
    c, s = idx.shape
    tm = min(512, max(8, ((m + 7) // 8) * 8))
    tn = min(_TILE_N, max(128, ((d1 + 127) // 128) * 128))
    tc = min(_TILE_N, max(128, ((c + 127) // 128) * 128))
    idx_p = jnp.pad(idx, ((0, (-c) % tc), (0, 0)), constant_values=-1)
    val_p = _pad_to(val, tc, 0)
    bkt = _pad_to(jnp.asarray(bucket, jnp.int32).reshape(1, c), tc, 1)
    sgn = _pad_to(jnp.asarray(sign, jnp.float32).reshape(1, c), tc, 1)
    mp = m + ((-m) % tm)
    np_ = d1 + ((-d1) % tn)
    cp = idx_p.shape[0]
    nc = cp // tc

    out = _pallas_call(
        "countsketch_scatter",
        functools.partial(_countsketch_kernel, s=s, nc=nc),
        grid=(mp // tm, np_ // tn, nc),
        in_specs=[
            pl.BlockSpec((1, tc), lambda i, j, k: (0, k)),
            pl.BlockSpec((1, tc), lambda i, j, k: (0, k)),
            pl.BlockSpec((tc, s), lambda i, j, k: (k, 0)),
            pl.BlockSpec((tc, s), lambda i, j, k: (k, 0)),
        ],
        out_specs=pl.BlockSpec((tm, tn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.float32),
        scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
        # At the Amazon chunk's tiles (tm=512, tn=tc=256, s=82) Mosaic
        # asks for 18.1 MB of scoped VMEM — the unrolled s-slot densify
        # keeps (tc, tn) temporaries live — against the 16 MB default
        # (chip run, PR 21). Same remedy as the *_acc kernels: raise the
        # limit, keep the tiles.
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=32 * 1024 * 1024
        ),
        interpret=interpret,
    )(bkt, sgn, idx_p, val_p)
    return out[:m, :d1]
