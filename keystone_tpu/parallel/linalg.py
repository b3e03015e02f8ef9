"""Distributed linear algebra: the in-tree replacement for mlmatrix.

The reference leans on the out-of-tree `edu.berkeley.cs.amplab.mlmatrix`
package for distributed solves (RowPartitionedMatrix, NormalEquations, TSQR,
BlockCoordinateDescent, treeReduce). Here those become sharded-array
computations: rows live sharded over the mesh ``data`` axis, Gramian/correlation
reductions are XLA all-reduces inserted by the compiler from sharding
annotations, and the small per-block solves are replicated Cholesky factorizations.

Conventions (matching the reference solvers):
  - ridge solve is ``(AᵀA + λI) x = AᵀB`` with *raw* λ (not scaled by n)
    (reference: nodes/learning/LinearMapper.scala:80-98 via mlmatrix
    NormalEquations; BlockWeightedLeastSquares.scala:270-276).
  - block coordinate descent is Gauss-Seidel over feature blocks maintaining
    the residual ``R = B - Σ_b A_b W_b`` (the in-tree pattern at
    BlockWeightedLeastSquares.scala:177-313, subsuming mlmatrix
    BlockCoordinateDescent.solveLeastSquaresWithL2 / solveOnePassL2).
"""

from __future__ import annotations

import functools
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from . import mesh as mesh_lib


def _corr(a, r):
    """AᵀR with at-least-f32 accumulation and the f32-operand precision
    pin — the correlation contraction shared by every BCD path."""
    acc = jnp.promote_types(a.dtype, jnp.float32)
    return jax.lax.dot_general(
        a, r.astype(a.dtype), (((0,), (0,)), ((), ())),
        preferred_element_type=acc, **_hi_kwargs(a.dtype),
    )


def _psd_factor(gram, lam):
    """Cholesky factor of (gram + lam I) — loop-invariant across BCD epochs
    for a fixed block, so multi-epoch sweeps stash it next to the Gramian
    and later epochs pay only the two triangular solves."""
    eye = jnp.eye(gram.shape[0], dtype=gram.dtype)
    return jax.scipy.linalg.cholesky(gram + lam * eye, lower=True)


def _rescue_solve(gram, rhs, lam):
    """The solve a failed f32 Cholesky falls back to: a second Cholesky
    with a strong scale-relative jitter, then a diagonal-preconditioned
    step."""
    d = gram.shape[0]
    eye = jnp.eye(d, dtype=gram.dtype)
    # 1e-3·(tr/d) keeps the condition number within f32 Cholesky's
    # reliable range (~1e6) while shrinking the fit by ~0.1%. Should a
    # concentrated spectrum defeat even the jittered factorization, the
    # last resort is a diagonal-preconditioned step — always finite, and
    # still a descent direction for the BCD sweep.
    mean_diag = jnp.trace(gram) / d
    jitter = mean_diag * jnp.asarray(1e-3, gram.dtype) + lam
    chol_j = jax.scipy.linalg.cholesky(gram + jitter * eye, lower=True)
    sol_j = jax.scipy.linalg.cho_solve((chol_j, True), rhs)
    fallback = rhs / (mean_diag + lam + jnp.asarray(1e-30, gram.dtype))
    return jnp.where(jnp.all(jnp.isfinite(sol_j)), sol_j, fallback)


def _accepted(sol, lin_res, rhs):
    """Acceptance of a Cholesky solve by the linear system's relative
    residual, not factor finiteness: a failed f32 Cholesky can also produce
    finite-but-garbage factors (observed on TPU) whose solutions blow up
    the BCD sweep."""
    return jnp.all(jnp.isfinite(sol)) & (
        jnp.linalg.norm(lin_res)
        <= jnp.asarray(1e-2, rhs.dtype) * (jnp.linalg.norm(rhs) + 1e-30)
    )


def _solve_psd(gram, rhs, lam, chol=None):
    """Solve (gram + lam I) x = rhs via Cholesky (gram PSD).

    Rank-deficient Gramians (fewer rows than block columns — demo-scale fits
    of wide blocks) with zero/tiny lam defeat the f32 Cholesky (negative
    pivots from rounding -> NaN factor). Those solves rescue through a
    second Cholesky with a strong scale-relative jitter (TPU's LU kernel
    cannot compile at d=16384 — scoped-VMEM overflow — so the rescue stays
    Cholesky-shaped); healthy Gramians keep the exact path bit for bit.
    (The reference inherits robustness from Breeze's `\\`, which LU-solves.)

    Pass ``chol`` (from :func:`_psd_factor` on the same gram/lam) to skip
    the factorization; acceptance is still checked per solve, so a stale or
    unhealthy factor falls into the same rescue path.
    """
    if chol is None:
        chol = _psd_factor(gram, lam)
    sol = jax.scipy.linalg.cho_solve((chol, True), rhs)
    # The check costs one (d,d)@(d,k) GEMM — noise next to the Gramian build.
    ok = _accepted(sol, gram @ sol + lam * sol - rhs, rhs)
    return jax.lax.cond(
        ok, lambda _: sol, lambda _: _rescue_solve(gram, rhs, lam), None
    )


def _factor_matvec(chol, w, lam):
    """``gram @ w`` from the factor of (gram + lam I) alone:
    L (Lᵀ w) − lam w — what lets a sweep keep the factor stash and drop
    the Gramian's (NORTHSTAR.md section 3)."""
    return chol @ (chol.T @ w) - lam * w


def _solve_psd_from_factor(chol, rhs, lam):
    """:func:`_solve_psd` for a caller that kept only ``chol``: the same
    acceptance check (through the factor) and the same rescue, on the
    Gramian rebuilt from the factor — L Lᵀ − lam I, formed only inside
    the rescue branch."""
    sol = jax.scipy.linalg.cho_solve((chol, True), rhs)
    ok = _accepted(sol, chol @ (chol.T @ sol) - rhs, rhs)

    def rescue(_):
        eye = jnp.eye(chol.shape[0], dtype=chol.dtype)
        return _rescue_solve(chol @ chol.T - lam * eye, rhs, lam)

    return jax.lax.cond(ok, lambda _: sol, rescue, None)


@functools.partial(jax.jit, static_argnames=("lam",))
def _normal_equations_kernel(A, B, lam: float):
    gram = A.T @ A
    corr = A.T @ B
    return _solve_psd(gram, corr, jnp.asarray(lam, dtype=A.dtype))


def normal_equations_solve(A, B, lam: float = 0.0):
    """Exact least-squares / ridge solve via normal equations.

    A: (n, d) rows (may be sharded over the mesh data axis; zero-padding rows
    are harmless). B: (n, k). Returns (d, k) replicated.

    The AᵀA / AᵀB contractions over the sharded n axis compile to per-shard
    GEMMs + an all-reduce — the direct analog of the reference's per-partition
    Gramians + treeReduce (mlmatrix NormalEquations).
    """
    return _normal_equations_kernel(jnp.asarray(A), jnp.asarray(B), float(lam))


# ---------------------------------------------------------------------------
# Block coordinate descent least squares
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("lam",), donate_argnums=(2,))
def _bcd_block_step(Ab, Wb, R, lam: float):
    """One Gauss-Seidel block update.

    Solves (AbᵀAb + λI) Wb' = Abᵀ(R + Ab Wb), returns (Wb', R', AbᵀAb) with
    R' = R - Ab (Wb' - Wb). R is donated (updated in place on device).
    """
    gram = Ab.T @ Ab
    rhs = Ab.T @ R + gram @ Wb
    Wb_new = _solve_psd(gram, rhs, jnp.asarray(lam, dtype=Ab.dtype))
    R_new = R - Ab @ (Wb_new - Wb)
    return Wb_new, R_new, gram


@functools.partial(jax.jit, static_argnames=("lam",), donate_argnums=(2,))
def _bcd_block_step_cached(Ab, Wb, R, lam: float, gram):
    """Later-epoch block update reusing a stashed Gramian: only the
    correlation re-reads the data, and the pass-through gram is not a jit
    output (which would copy it every step)."""
    rhs = Ab.T @ R + gram @ Wb
    Wb_new = _solve_psd(gram, rhs, jnp.asarray(lam, dtype=Ab.dtype))
    return Wb_new, R - Ab @ (Wb_new - Wb)


def _gram_cache_ok(num_iter: int, gram_bytes: int) -> bool:
    """Stash per-block Gramians across epochs only when the stash is small
    beside HBM (shared policy of the stepwise and fused flat paths)."""
    return num_iter > 1 and gram_bytes <= (1 << 30)


@functools.lru_cache(maxsize=8)  # bounded: cached meshes pin compiled executables
def _mesh_bcd_step(mesh, lam: float, use_pallas: bool):
    """Compiled per-block BCD step for a row-sharded design matrix.

    The Gramian + correlation are computed per shard — through the fused
    Pallas ``gram_corr_sym`` kernel when enabled (each shard's tile is
    unsharded inside shard_map, so ``pallas_call`` composes with the mesh)
    — then psum'd over the ``data`` axis: the explicit-collective form of
    the reference's per-partition Gramians + treeReduce (mlmatrix
    NormalEquations). Solve and weight update are replicated; the residual
    update partitions as a plain sharded GEMM.
    """
    axis = mesh_lib.DATA_AXIS

    def gram_corr_body(a, r):
        if use_pallas:
            from keystone_tpu.ops import pallas_ops

            gram, corr = pallas_ops.gram_corr_sym(a, r)
        else:
            acc = jnp.promote_types(a.dtype, jnp.float32)
            gram = jax.lax.dot_general(
                a, a, (((0,), (0,)), ((), ())), preferred_element_type=acc,
                **_hi_kwargs(a.dtype),
            )
            corr = _corr(a, r)
        return jax.lax.psum(gram, axis), jax.lax.psum(corr, axis)

    # check_vma=False: pallas_call outputs carry no varying-mesh-axes info,
    # so the static replication checker cannot see through them; the psums
    # above establish the replicated out_specs regardless.
    sharded_gram_corr = mesh_lib.shard_map(
        gram_corr_body,
        mesh=mesh,
        in_specs=(P(axis), P(axis)),
        out_specs=(P(), P()),
        check_vma=False,
    )

    sharded_corr = mesh_lib.shard_map(
        lambda a, r: jax.lax.psum(_corr(a, r), axis),
        mesh=mesh, in_specs=(P(axis), P(axis)), out_specs=P(),
        check_vma=False,
    )

    def finish(Ab, Wb, R, gram, corr):
        Wb = Wb.astype(gram.dtype)
        rhs = corr + gram @ Wb
        Wb_new = _solve_psd(gram, rhs, jnp.asarray(lam, dtype=gram.dtype))
        delta = (Ab @ (Wb_new - Wb).astype(Ab.dtype)).astype(R.dtype)
        return Wb_new, R - delta

    @functools.partial(jax.jit, donate_argnums=(2,))
    def step(Ab, Wb, R):
        gram, corr = sharded_gram_corr(Ab, R)
        Wb_new, R_new = finish(Ab, Wb, R, gram, corr)
        return Wb_new, R_new, gram

    @functools.partial(jax.jit, donate_argnums=(2,))
    def step_cached(Ab, Wb, R, gram):
        """Later epochs: the Gramian is loop-invariant — only the
        correlation re-reads the sharded rows."""
        corr = sharded_corr(Ab, R)
        Wb_new, R_new = finish(Ab, Wb, R, gram, corr)
        return Wb_new, R_new

    return step, step_cached


def bcd_least_squares(
    A_blocks: Sequence,
    B,
    lam: float = 0.0,
    num_iter: int = 1,
    W_init: Optional[List] = None,
    mesh=None,
    use_pallas: Optional[bool] = None,
) -> List:
    """Block coordinate descent ridge regression over feature blocks.

    A_blocks: list of (n, d_b) arrays (feature-axis blocks of the design
    matrix, rows sharded over the data axis). B: (n, k). Returns the list of
    per-block weights W_b, each (d_b, k), minimizing
    ``||B - Σ_b A_b W_b||² + λ Σ_b ||W_b||²``.

    Host Python drives the (epoch × block) loop — the analog of the Spark
    driver — while each block step is one compiled sharded computation. All
    equally-shaped blocks share a single compiled executable. Pass ``mesh``
    (multi-device) to run each step's Gramian+correlation as an explicit
    shard_map program — with the fused Pallas kernels inside when enabled.
    """
    from keystone_tpu.ops import pallas_ops

    B = jnp.asarray(B)
    k = B.shape[1]
    Ws = (
        list(W_init)
        if W_init is not None
        else [jnp.zeros((Ab.shape[1], k), dtype=B.dtype) for Ab in A_blocks]
    )
    if W_init is not None:
        R = B - sum(Ab @ Wb for Ab, Wb in zip(A_blocks, Ws))
    else:
        # Fresh buffer: the block step donates R, and aliasing the caller's B
        # would delete it out from under them.
        R = jnp.array(B, copy=True)

    multi = mesh is not None and mesh_lib.axis_size(mesh, mesh_lib.DATA_AXIS) > 1
    if multi:
        if use_pallas is None:
            use_pallas = pallas_ops.pallas_enabled()
        step, step_cached = _mesh_bcd_step(mesh, float(lam), bool(use_pallas))
    else:
        step = step_cached = None

    # Stash loop-invariant per-block Gramians across epochs when the stash
    # is small beside HBM (shared policy with the fused flat path).
    # jnp.result_type reads the dtype without transferring host blocks.
    gram_bytes = sum(
        int(a.shape[1]) ** 2
        * jnp.promote_types(jnp.result_type(a), jnp.float32).itemsize
        for a in A_blocks
    )
    cache_grams = _gram_cache_ok(max(num_iter, 1), gram_bytes)
    grams: List = [None] * len(A_blocks)

    for _ in range(max(num_iter, 1)):
        for b, Ab in enumerate(A_blocks):
            Ab = jnp.asarray(Ab)
            if grams[b] is not None:
                if step_cached is not None:
                    Ws[b], R = step_cached(Ab, Ws[b], R, grams[b])
                else:
                    Ws[b], R = _bcd_block_step_cached(
                        Ab, Ws[b], R, float(lam), grams[b]
                    )
            else:
                if step is not None:
                    Ws[b], R, gram = step(Ab, Ws[b], R)
                else:
                    Ws[b], R, gram = _bcd_block_step(
                        Ab, Ws[b], R, float(lam)
                    )
                if cache_grams:
                    grams[b] = gram
            mesh_lib.sync_if_cpu(R)
    return Ws


# ---------------------------------------------------------------------------
# Fused (single-dispatch) block coordinate descent
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("shape", "dtype"))
def _zeros(shape, dtype):
    """Zeros made on the device by a program (an eager ``jnp.zeros`` sends
    its fill value from the host)."""
    return jnp.zeros(shape, dtype)


# ``lam`` is a TRACED operand: λ-sweeps over one geometry reuse one
# compiled sweep (it reaches the solves as a numeric jitter only).
@functools.partial(
    jax.jit,
    static_argnames=("num_iter", "use_pallas", "sym", "cache_stash"),
)
@jax.named_scope("ks.bcd_step")  # the sweeps' own slices of the stacked blocks too
def _bcd_fused_kernel(A_stack, B, W0, lam, num_iter: int,
                      use_pallas: bool, sym: bool, cache_stash: bool = True,
                      tail=None):
    """The sweep over the stacked blocks and, where ``tail`` (an (n, d_t)
    block) is given, one narrower last block after them in every epoch
    (``d`` not a multiple of the block), from W_t = 0. Returns ``(W, R,
    W_t)``."""
    if tail is not None:
        A_t, W_t = tail, jnp.zeros((tail.shape[1], B.shape[1]), B.dtype)

    def first_epoch_step(R, xs):
        """First sweep: compute (and, when caching, stash) each block's
        Gramian + Cholesky factor. Single-epoch runs — and models past the
        _gram_cache_ok budget (the stash is 2x nb*db^2 f32, ~536 MB at
        bench shapes) — skip the stash."""
        Ab, Wb = xs
        R, Wb_new, gram, chol = _bcd_block_update(Ab, R, Wb, lam, use_pallas, sym)
        empty = jnp.zeros((0,))
        stash = (
            (Wb_new, gram, chol)
            if (num_iter > 1 and cache_stash)
            else (Wb_new, empty, empty)
        )
        return R, stash

    def later_epoch_step(R, xs):
        """Later sweeps reuse the loop-invariant Gramians and factors —
        only the correlation AᵀR depends on the evolving residual."""
        Ab, Wb, gram, chol = xs
        R, Wb_new, _, _ = _bcd_block_update(
            Ab, R, Wb, lam, use_pallas, sym, gram=gram, chol=chol
        )
        return R, Wb_new

    R, (W, grams, chols) = jax.lax.scan(first_epoch_step, B, (A_stack, W0))
    if tail is not None:
        R, W_t, gram_t, chol_t = _bcd_block_update(A_t, R, W_t, lam, use_pallas, sym)
    else:
        W_t = None
    if num_iter == 1:
        return W, R, W_t

    if cache_stash:
        def epoch(carry, _):
            R, W, W_t = carry
            R, W = jax.lax.scan(
                later_epoch_step, R, (A_stack, W, grams, chols)
            )
            if tail is not None:
                R, W_t, _, _ = _bcd_block_update(
                    A_t, R, W_t, lam, use_pallas, sym, gram=gram_t, chol=chol_t)
            return (R, W, W_t), None
    else:
        # Over-budget stash: later epochs recompute Gramian + factor
        # (rematerialization economics — the same policy as the flat path).
        def epoch(carry, _):
            R, W, W_t = carry
            R, (W, _, _) = jax.lax.scan(first_epoch_step, R, (A_stack, W))
            if tail is not None:
                R, W_t, _, _ = _bcd_block_update(A_t, R, W_t, lam, use_pallas, sym)
            return (R, W, W_t), None

    (R, W, W_t), _ = jax.lax.scan(epoch, (R, W, W_t), None, length=num_iter - 1)
    return W, R, W_t


def _residual_dtype(feat_dtype, label_dtype):
    """Residual/solve dtype: at least f32 (bf16 features still accumulate in
    f32), promoted to f64 when either operand is double so fused results
    match the stepwise solver bit for bit."""
    acc = jnp.promote_types(feat_dtype, jnp.float32)
    return jnp.promote_types(acc, jnp.promote_types(label_dtype, jnp.float32))


def _hi_kwargs(feat_dtype):
    """f32 operands force HIGHEST precision (the TPU default is a single
    lossy bf16 pass); bf16 operands hit the MXU natively."""
    if feat_dtype == jnp.float32:
        return dict(precision=jax.lax.Precision.HIGHEST)
    return {}


def _bcd_block_update(Ab, R, Wb, lam: float, use_pallas: bool, sym: bool,
                      gram=None, chol=None):
    """One Gauss-Seidel block update shared by the fused solvers.

    Solves (AbᵀAb + λI) Wb' = AbᵀR + (AbᵀAb) Wb and returns
    (R - Ab (Wb' - Wb), Wb', AbᵀAb, cholesky). The residual delta is
    accumulated in f32 regardless of the feature layout dtype
    (preferred_element_type) so bf16 GEMM inputs never quantize the running
    residual. Pass ``gram`` (and ``chol``) to reuse the precomputed,
    loop-invariant Gramian/factor — only the correlation then recomputes.
    """
    from keystone_tpu.ops import pallas_ops

    feat_dtype = Ab.dtype
    # Accumulate in at least f32; f64 inputs keep f64 (a preferred type of
    # plain f32 would silently downcast double-precision accumulations).
    acc_dtype = jnp.promote_types(feat_dtype, jnp.float32)
    hi = _hi_kwargs(feat_dtype)
    # The scopes name the phases in a device profile (trace-time only).
    with jax.named_scope("ks.gram_corr_fold"):
        if gram is None and use_pallas and acc_dtype == jnp.float32:
            # The Pallas kernels accumulate in f32; f64 inputs keep the XLA
            # path so the double-precision promotion below is honored.
            fn = pallas_ops.gram_corr_sym if sym else pallas_ops.gram_corr
            gram, corr = fn(Ab, R)
        else:
            if gram is None:
                gram = jax.lax.dot_general(
                    Ab, Ab, (((0,), (0,)), ((), ())),
                    preferred_element_type=acc_dtype, **hi,
                )
            corr = _corr(Ab, R)
    with jax.named_scope("ks.bcd_step"):
        lam_t = jnp.asarray(lam, dtype=gram.dtype)
        if chol is None:
            chol = _psd_factor(gram, lam_t)
        rhs = corr + gram @ Wb
        Wb_new = _solve_psd(gram, rhs, lam_t, chol=chol)
        delta = jax.lax.dot_general(
            Ab, (Wb_new - Wb).astype(feat_dtype), (((1,), (0,)), ((), ())),
            preferred_element_type=acc_dtype, **hi,
        )
        return R - delta, Wb_new, gram, chol


@functools.partial(
    jax.jit,
    static_argnames=("block", "num_iter", "use_pallas", "sym",
                     "cache_grams", "strided"),
)
def _bcd_fused_flat_kernel(F, B, W0, block: int, lam, num_iter: int,
                           use_pallas: bool, sym: bool,
                           cache_grams: bool = False, strided: bool = False):
    nb = F.shape[1] // block
    acc_dtype = jnp.promote_types(F.dtype, jnp.float32)

    def slice_block(F, W, bi):
        Ab = jax.lax.dynamic_slice_in_dim(F, bi * block, block, axis=1)
        Wb = jax.lax.dynamic_index_in_dim(W, bi, axis=0, keepdims=False)
        return Ab, Wb

    from keystone_tpu.ops import pallas_ops

    def strided_update(bi, R, Wb, gram=None, chol=None):
        """Block update where every F access streams the column window
        straight out of the flat buffer (scalar-prefetched base index) —
        no 2 GB dynamic_slice copy per block, which is pure HBM traffic
        the MXU never sees."""
        if gram is None:
            gram = pallas_ops.block_gram_sym(F, bi * block, block)
        corr = pallas_ops.block_corr(F, bi * block, block, R)
        lam_b = jnp.asarray(lam, dtype=gram.dtype)
        if chol is None:
            chol = _psd_factor(gram, lam_b)
        rhs = corr + gram @ Wb
        Wb_new = _solve_psd(gram, rhs, lam_b, chol=chol)
        R_new = pallas_ops.block_residual_update(
            F, bi * block, block, (Wb_new - Wb).astype(F.dtype), R
        )
        return R_new, Wb_new, gram, chol

    def first_block(bi, carry):
        """First sweep: compute (and, when caching, stash) each block's
        Gramian AND its Cholesky factor — both are loop-invariant across
        epochs; the Gramian recompute is the dominant per-epoch GEMM cost
        (n·d_b² vs the correlation's n·d_b·k) and the factorization is the
        dominant per-epoch non-GEMM cost."""
        R, W, G, C = carry
        if strided:
            Wb = jax.lax.dynamic_index_in_dim(W, bi, axis=0, keepdims=False)
            R, Wb_new, gram, chol = strided_update(bi, R, Wb)
        else:
            Ab, Wb = slice_block(F, W, bi)
            R, Wb_new, gram, chol = _bcd_block_update(
                Ab, R, Wb, lam, use_pallas, sym
            )
        W = jax.lax.dynamic_update_index_in_dim(W, Wb_new, bi, 0)
        if cache_grams:
            G = jax.lax.dynamic_update_index_in_dim(
                G, gram.astype(acc_dtype), bi, 0
            )
            C = jax.lax.dynamic_update_index_in_dim(
                C, chol.astype(acc_dtype), bi, 0
            )
        return R, W, G, C

    def later_block(bi, carry):
        R, W, G, C = carry
        gram = jax.lax.dynamic_index_in_dim(G, bi, axis=0, keepdims=False)
        chol = jax.lax.dynamic_index_in_dim(C, bi, axis=0, keepdims=False)
        if strided:
            Wb = jax.lax.dynamic_index_in_dim(W, bi, axis=0, keepdims=False)
            R, Wb_new, _, _ = strided_update(bi, R, Wb, gram=gram, chol=chol)
        else:
            Ab, Wb = slice_block(F, W, bi)
            R, Wb_new, _, _ = _bcd_block_update(
                Ab, R, Wb, lam, use_pallas, sym, gram=gram, chol=chol
            )
        return R, jax.lax.dynamic_update_index_in_dim(W, Wb_new, bi, 0), G, C

    stash_shape = (nb, block, block) if cache_grams else (0, 0, 0)
    G0 = jnp.zeros(stash_shape, dtype=acc_dtype)
    C0 = jnp.zeros(stash_shape, dtype=acc_dtype)
    R, W, G, C = jax.lax.fori_loop(0, nb, first_block, (B, W0, G0, C0))

    if num_iter > 1:
        body = later_block if cache_grams else first_block

        def epoch(_, carry):
            return jax.lax.fori_loop(0, nb, body, carry)

        R, W, G, C = jax.lax.fori_loop(0, num_iter - 1, epoch, (R, W, G, C))
    return W, R


def bcd_least_squares_fused_flat(
    F,
    B,
    block_size: int,
    lam: float = 0.0,
    num_iter: int = 1,
    use_pallas: Optional[bool] = None,
    return_residual: bool = False,
):
    """Single-dispatch BCD over a *flat* (n, d) feature matrix.

    Functionally identical to ``bcd_least_squares_fused`` on the column
    blocks ``F[:, i*block : (i+1)*block]``, but the features live in one
    contiguous buffer — at large n the stacked layout cannot be produced
    without a second full-size copy (stack of independently-computed block
    buffers), which is the difference between fitting in HBM and not.
    Multi-epoch runs stash the loop-invariant per-block Gramians when the
    (nb, d_b, d_b) buffer is small next to HBM (≤1 GB), making epochs 2+
    pay only the correlation + solve + residual update; larger models fall
    back to recomputation (rematerialization economics).
    """
    from keystone_tpu.ops import pallas_ops

    F = jnp.asarray(F)
    B = jnp.asarray(B)
    B = B.astype(_residual_dtype(F.dtype, B.dtype))
    if F.dtype != jnp.bfloat16:
        F = F.astype(B.dtype)
    n, d = F.shape
    if d % block_size != 0:
        raise ValueError(f"feature dim {d} not divisible by block {block_size}")
    nb = d // block_size
    if use_pallas is None:
        use_pallas = pallas_ops.pallas_direct_ok(F)
    W0 = jnp.zeros((nb, block_size, B.shape[1]), dtype=B.dtype)
    acc_itemsize = jnp.promote_types(F.dtype, jnp.float32).itemsize
    # x2: the stash holds Gramians AND their Cholesky factors.
    cache_grams = _gram_cache_ok(
        int(num_iter), 2 * nb * block_size * block_size * acc_itemsize
    )
    # Strided column-window kernels (no per-block dynamic_slice copy of F)
    # need tile-aligned shapes and an f32 accumulation dtype; everything in
    # the update then runs lane-padded to a 128 multiple, so pad the labels
    # once up front and slice the model on the way out (the padded label
    # columns are zero, and stay zero through every solve).
    strided = (
        bool(use_pallas)
        and jnp.promote_types(F.dtype, jnp.float32) == jnp.float32
        and pallas_ops.strided_gram_ok(F, block_size)
    )
    k_orig = B.shape[1]
    if strided and k_orig % 128:
        tr = ((k_orig + 127) // 128) * 128
        B = jnp.pad(B, ((0, 0), (0, tr - k_orig)))
        W0 = jnp.zeros((nb, block_size, tr), dtype=B.dtype)
    W, R = _bcd_fused_flat_kernel(
        F, B, W0, int(block_size), lam, max(int(num_iter), 1),
        bool(use_pallas), True, cache_grams, strided,
    )
    if W.shape[2] != k_orig:
        W = W[:, :, :k_orig]
        R = R[:, :k_orig]
    return (W, R) if return_residual else W


def bcd_least_squares_fused(
    A_stack,
    B,
    lam: float = 0.0,
    num_iter: int = 1,
    W_init=None,
    use_pallas: Optional[bool] = None,
    return_residual: bool = False,
    tail=None,
):
    """Single-dispatch block coordinate descent over equal-sized blocks.

    ``tail``: an (n, d_t) last block narrower than the stacked ones (the
    feature count not a multiple of the block), swept after them in every
    epoch inside the same program; its weights then come back as well:
    ``(W, W_t)`` or ``(W, W_t, R)``.

    A_stack: (num_blocks, n, d_b) stacked feature blocks — may be bfloat16,
    in which case GEMMs run natively on the MXU with float32 accumulation
    (the solve and residual stay float32). The entire (epochs × blocks)
    Gauss-Seidel sweep is one compiled program: ``lax.scan`` over blocks
    inside ``lax.scan`` over epochs, with the Gramian+correlation computed by
    the fused Pallas ``gram_corr_sym`` kernel on TPU (upper-triangle blocks
    only — the BLAS ``syrk`` trick) and plain XLA contractions elsewhere.

    Against the per-block host-driven loop (``bcd_least_squares``), this
    removes every intermediate host dispatch — the analog of replacing the
    reference's per-block Spark job waves (mlmatrix BlockCoordinateDescent)
    with one compiled program over the mesh.
    """
    from keystone_tpu.ops import pallas_ops

    A_stack = jnp.asarray(A_stack)
    B = jnp.asarray(B)
    B = B.astype(_residual_dtype(A_stack.dtype, B.dtype))
    if A_stack.dtype != jnp.bfloat16:
        # Unify operand dtypes up front (except the intentional bf16 feature
        # layout) so the block updates run entirely in the residual dtype —
        # e.g. f32 features with f64 labels solve in f64.
        A_stack = A_stack.astype(B.dtype)
    nb, n, db = A_stack.shape
    k = B.shape[1]
    if use_pallas is None:
        use_pallas = pallas_ops.pallas_direct_ok(A_stack)
    W0 = (
        jnp.asarray(W_init, dtype=B.dtype)
        if W_init is not None
        else _zeros((nb, db, k), B.dtype)
    )
    if W_init is not None:
        # A_stack is already unified with B's dtype (bf16 features upcast
        # here so the warm-start residual keeps full precision).
        B = B - sum(
            jnp.dot(
                A_stack[i].astype(B.dtype), W0[i],
                precision=jax.lax.Precision.HIGHEST,
            )
            for i in range(nb)
        )
    acc_itemsize = jnp.promote_types(A_stack.dtype, jnp.float32).itemsize
    d_t = 0
    if tail is not None:
        if W_init is not None:
            raise ValueError("a tail block starts from W = 0: no W_init with it")
        tail = jnp.asarray(tail).astype(A_stack.dtype)
        d_t = tail.shape[1]
    # x2: the stash holds Gramians AND their Cholesky factors (same budget
    # policy as the flat path).
    cache_stash = _gram_cache_ok(
        int(num_iter), 2 * (nb * db * db + d_t * d_t) * acc_itemsize
    )
    W, R, W_t = _bcd_fused_kernel(
        A_stack, B, W0, lam, max(int(num_iter), 1),
        bool(use_pallas), True, cache_stash, tail=tail,
    )
    out = (W,) if tail is None else (W, W_t)
    if return_residual:
        out += (R,)
    return out if len(out) > 1 else W


# ---------------------------------------------------------------------------
# TSQR
# ---------------------------------------------------------------------------


def tsqr_r(A, mesh=None) -> jax.Array:
    """R factor of a tall-skinny QR, computed shard-locally then combined.

    The analog of mlmatrix ``TSQR().qrR``: each data shard computes a local
    (d, d) R; the stacked Rs get a final QR. Sign convention: R has
    non-negative diagonal. Falls back to a direct QR when unsharded.
    """
    A = jnp.asarray(A)
    d = A.shape[1]
    sharding = getattr(A, "sharding", None)
    mesh = mesh or (getattr(sharding, "mesh", None) if sharding is not None else None)

    if mesh is None or mesh_lib.DATA_AXIS not in getattr(mesh, "shape", {}):
        r = jnp.linalg.qr(A, mode="r")
    else:
        num = mesh.shape[mesh_lib.DATA_AXIS]

        def local_qr(a_shard):
            r_local = jnp.linalg.qr(a_shard, mode="r")
            # (1, d, d) leaf per shard -> stacked on the data axis
            return r_local[None]

        stacked = mesh_lib.shard_map(
            local_qr,
            mesh=mesh,
            in_specs=P(mesh_lib.DATA_AXIS),
            out_specs=P(mesh_lib.DATA_AXIS),
        )(A)
        stacked = stacked.reshape(num * d, d)
        r = jnp.linalg.qr(stacked, mode="r")

    # Fix signs so the diagonal is non-negative (deterministic convention).
    signs = jnp.sign(jnp.diagonal(r))
    signs = jnp.where(signs == 0, 1.0, signs)
    return r * signs[:, None]


def distributed_gram(A):
    """AᵀA over sharded rows (per-shard GEMM + all-reduce)."""
    A = jnp.asarray(A)
    return A.T @ A


def column_means(A, n: Optional[int] = None):
    """Column means over the true row count (padding rows are zero)."""
    A = jnp.asarray(A)
    count = A.shape[0] if n is None else n
    return jnp.sum(A, axis=0) / count
