"""Out-of-core (streaming / tiled) least-squares: the memory-wall crosser.

The reference's substrate streams by construction: ``CsvDataLoader`` is a
lazy ``textFile`` (CsvDataLoader.scala:10-31), and the block solvers
accumulate per-partition Gramians + correlations into a ``treeReduce``
(BlockWeightedLeastSquares.scala:177-313) — the full feature matrix never
exists on any machine. This module is the TPU-native analog: features are
*generated per row tile* inside a scanned sweep (fused featurize kernel),
each tile contributes

    G  += triu(FₜᵀFₜ)    (a symmetric rank-k update: the upper
                          block-triangle alone, mirrored once a fit)
    FY += FₜᵀYₜ
    yty += ΣYₜ²

and the (tile_rows, d) feature slab is the only feature storage that ever
exists. At TIMIT's real scale (n=2.2e6, d=16384) the materialized feature
matrix would be 72 GB of bf16 against 16 GB of HBM; the streamed state is
G (1.07 GB f32) + one slab (~2 GB bf16) + the raw input (3.9 GB f32).

The solve then runs block Gauss-Seidel directly on the normal equations:

    W_b ← (G_bb + λI)⁻¹ (FY_b − Σ_{j≠b} G_bj W_j)

which is algebraically the SAME iterate sequence as residual-maintaining
BCD (``linalg.bcd_least_squares_fused_flat``) — the residual is simply
eliminated through R = Y − F W. Extra epochs cost only (d, block)×(block,
k) GEMMs on the cached Gramian — no data pass — where the residual form
pays a full re-featurize per block per epoch.

Mesh story: rows shard over the ``data`` axis; each device folds its local
tiles, then ONE psum of (G, FY, yty) per fit crosses the interconnect —
the explicit-collective form of the reference's treeReduce, and the
minimum possible communication for this algorithm.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from . import mesh as mesh_lib
from .linalg import _psd_factor, _solve_psd

Array = jax.Array

# Default HBM budget for one feature slab (the streamed working set).
_DEFAULT_SLAB_BYTES = 2 << 30
# Row alignment the Pallas accumulating-syrk kernel needs (its k-tile).
_ROW_ALIGN = 512


def pick_tile_rows(
    d_feat: int,
    feat_itemsize: int = 2,
    slab_bytes: int = _DEFAULT_SLAB_BYTES,
) -> int:
    """Largest _ROW_ALIGN-multiple tile whose feature slab fits the budget."""
    rows = max(slab_bytes // max(d_feat * feat_itemsize, 1), _ROW_ALIGN)
    return max((rows // _ROW_ALIGN) * _ROW_ALIGN, _ROW_ALIGN)


class BoundedInflight:
    """Bound the device dispatch queue of a host-driven segment loop.

    ``admit(x)`` enqueues a tiny NON-donated probe derived from the
    segment's carry (the ``+ 0.0`` keeps it off the donated buffers) and
    blocks on the oldest once more than ``limit`` are in flight — the
    next segment's host load/transfer overlaps device compute, while the
    host never runs more than ``limit`` segments ahead of the device:
    each queued segment pins its staged input buffers in HBM until its
    fold has run, so an unbounded queue is an unbounded working set.
    Shared by the dense and sparse segmented folds.
    """

    def __init__(self, limit: int):
        from collections import deque

        self._limit = max(int(limit), 1)
        self._probes = deque()

    def admit(self, scalar) -> None:
        self._probes.append(scalar + 0.0)
        while len(self._probes) > self._limit:
            float(self._probes.popleft())


def _row_mask(M, valid):
    """Zero rows at index >= valid (padding rows must not touch G/FY)."""
    idx = jax.lax.broadcasted_iota(jnp.int32, (M.shape[0], 1), 0)
    return jnp.where(idx < valid, M, jnp.zeros((), M.dtype))


# What ``estimator.fit`` says of a fit's Gramians (``gram``), in the block
# tier (epoch 1's, a block) and in the streamed fold (a tile's share of the
# d x d one): the upper block-triangle alone in plain ``dot_general``s,
# mirrored once.
BLOCK_GRAM = "sym_dot"

# Least column width of the panels that triangle is made of, and the most
# panels a width is cut into (wider slabs take wider panels, so that a
# step's products and their temporaries do not grow in number). Read on a
# v5e at 131,072 x 4,096 float32: 74.0 ms at 256, 77.8 at 512, 86.3 at
# 1,024, one full dot 137.0 (PERF.md section 6, PR 33).
_GRAM_PANEL = 256
_GRAM_PANELS = 16


def gram_panel_width(d: int) -> int:
    """Columns a panel of a ``d``-wide slab's triangle takes: 256 up to a
    width of 4,096, then the sixteenth of the width rounded up to 256s."""
    return _GRAM_PANEL * max(1, -(-d // (_GRAM_PANELS * _GRAM_PANEL)))


def gram_panels(d: int) -> int:
    """How many panels that makes (``estimator.fit``'s ``gram_panels``)."""
    return -(-d // gram_panel_width(d))


def _gram_panel_products(F, acc):
    """(first column, product) of each panel of FᵀF's upper block-triangle:
    the panel's columns against the columns from its own on, as plain
    ``dot_general``s at the slab's own precision (the column slices fuse
    into the products: no copy of the slab). Any width: a slab of one panel
    is the full product, and a last panel may be narrower."""
    width = gram_panel_width(F.shape[1])
    for lo in range(0, F.shape[1], width):
        yield lo, jax.lax.dot_general(
            F[:, lo:lo + width], F[:, lo:], (((0,), (0,)), ((), ())),
            preferred_element_type=acc,
        ).astype(jnp.float32)


def _gram_upper_panels(F, acc):
    """The upper block-triangle of FᵀF, zeros under it — 136 of the 256
    panel pairs at sixteen panels. The block tier's epoch-1 Gramian of one
    tile (``_block_sweep``); the streamed fold adds the same products into
    its carry without joining them (:func:`_tile_update`)."""
    return jnp.concatenate(
        [jnp.pad(panel, ((0, 0), (lo, 0)))
         for lo, panel in _gram_panel_products(F, acc)],
        axis=0,
    )


def _tile_update(G, FY, yty, fsum, ysum, X_t, Y_t, featurize, use_pallas,
                 valid: Optional[Array]):
    """Fold one row tile into (G, FY, yty, fsum, ysum). ``valid`` (traced
    scalar) masks rows >= valid; None means the whole tile is valid (no
    mask pass).

    Masking zeroes the *feature* rows, not just X rows: a zero input row
    still featurizes to cos(b) — a nonzero constant — so padding must be
    excluded after featurization.

    The column sums (fsum, ysum) ride the same pass so the centered
    solvers get their means for free — two vector reductions per tile,
    ~1/d_feat of the syrk's work.

    G takes the tile's UPPER block-triangle alone (the strict lower
    triangle of the carry stays what it was): the caller mirrors once,
    after its last tile. ``use_pallas`` is not read — one Gramian form
    whatever the entry passes (ROADMAP D4 takes the argument's thread out).
    """
    # The scopes name the phases in a device profile (trace-time only).
    with jax.named_scope("ks.featurize"):
        F_t = featurize(X_t)
        if valid is not None:
            F_t = _row_mask(F_t, valid)
            Y_t = _row_mask(Y_t, valid)
    acc = jnp.promote_types(F_t.dtype, jnp.float32)
    with jax.named_scope("ks.gram_fold"):
        # The upper block-triangle of F_tᵀF_t alone, panel by panel into
        # its own slice of the carry: XLA makes each panel one fusion of
        # the product, the add and the in-place update.
        for lo, panel in _gram_panel_products(F_t, acc):
            G = jax.lax.dynamic_update_slice(
                G, G[lo:lo + panel.shape[0], lo:] + panel, (lo, lo)
            )
        FY = FY + jax.lax.dot_general(
            F_t, Y_t.astype(F_t.dtype), (((0,), (0,)), ((), ())),
            preferred_element_type=acc,
        ).astype(jnp.float32)
        Yf = Y_t.astype(jnp.float32)
        # dtype=f32 so bf16 feature slabs accumulate their column sums at the
        # same precision as the G/FY folds (a bf16 reduction would bias the
        # centered solve: cos features have near-zero means, all cancellation).
        fsum = fsum + jnp.sum(F_t, axis=0, dtype=jnp.float32)
        ysum = ysum + jnp.sum(Yf, axis=0)
    return G, FY, yty + jnp.sum(Yf * Yf), fsum, ysum


@jax.named_scope("ks.gram_fold")  # the loop's own copies and the mirror too
def gram_stats(
    X: Array,
    Y: Array,
    featurize: Callable[[Array], Array],
    d_feat: int,
    tile_rows: int,
    use_pallas: bool = False,
    valid=None,
    labelize: Optional[Callable[[Array], Array]] = None,
    moments: bool = False,
) -> Tuple[Array, ...]:
    """Accumulate (G = FᵀF, FY = FᵀY, yty = ΣY²) over row tiles of X.

    With ``moments=True`` also returns the per-column sums
    (fsum = Σᵢ fᵢ, ysum = Σᵢ yᵢ) accumulated in the SAME pass — the
    centered solvers' means, so mean-centering costs no extra data pass
    (the streamed analog of BlockLinearMapper.scala:224-243's per-block
    StandardScalers). Returns (G, FY, yty) or (G, FY, yty, fsum, ysum).

    Traceable (call under jit). X: (n, d_in) — or PRE-TILED (T, tile_rows,
    d_in), which large fits should prefer: handing the program already-
    tiled operands removes the in-program reshape, which XLA materializes
    as a second full-size (lane-padded) copy of X — ~5 GB at the TIMIT
    geometry. Y: (n, k) / (T, tile_rows, k), or raw per-row labels of any
    trailing shape when ``labelize`` is given (e.g. int class ids;
    ``labelize`` maps a (tile_rows, ...) label slice to the (tile_rows, k)
    regression target per tile — a one-hot target then never exists at
    full n).

    The feature matrix F = featurize(X) — (n, d_feat), conceptually — is
    produced one (tile_rows, d_feat) slab at a time and never
    materialized. Full tiles run through a ``lax.scan``; a ragged
    remainder is padded to the kernel's row alignment and masked.

    ``valid`` excludes trailing padding rows (their FEATURE rows are
    zeroed — a zero input row still featurizes to cos(b) ≠ 0). A static
    int masks only the boundary tile (full tiles before it run unmasked,
    tiles past it are skipped at trace time); a traced scalar masks every
    tile — mesh callers with per-shard counts use that form.

    Every tile adds the upper block-triangle of its F_tᵀF_t alone
    (:func:`_gram_panel_products`: sixteen panels of 1,024 columns at
    d = 16,384, 136 of the 256 panel pairs); the sum is mirrored once,
    here, so G returns with BOTH triangles valid, symmetric to the bit.
    """
    pre_tiled = X.ndim == 3
    if pre_tiled:
        num_full, tile_rows = int(X.shape[0]), int(X.shape[1])
        rem = 0
        Xs, Ys = X, Y
    else:
        n = X.shape[0]
        num_full = n // tile_rows
        rem = n - num_full * tile_rows
        if num_full:
            Xs = X[: num_full * tile_rows].reshape(
                (num_full, tile_rows) + X.shape[1:]
            )
            Ys = Y[: num_full * tile_rows].reshape(
                (num_full, tile_rows) + Y.shape[1:]
            )
        else:
            Xs = Ys = None

    if labelize is None:
        labelize = lambda y_t: y_t  # noqa: E731 — identity target map
        k = int(Y.shape[-1])
    else:
        y_slice = jax.eval_shape(lambda a: a[0], Ys) if num_full else Y
        k = int(jax.eval_shape(labelize, y_slice).shape[-1])

    static_valid = valid is not None and not isinstance(valid, jax.core.Tracer)
    if static_valid:
        valid = int(valid)
        # Full tiles entirely inside `valid` run unmasked; the boundary
        # tile masks once; tiles entirely past `valid` never execute.
        num_unmasked = min(valid // tile_rows, num_full)
    else:
        num_unmasked = num_full if valid is None else 0

    carry = (
        jnp.zeros((d_feat, d_feat), jnp.float32),
        jnp.zeros((d_feat, k), jnp.float32),
        jnp.zeros((), jnp.float32),
        jnp.zeros((d_feat,), jnp.float32),
        jnp.zeros((k,), jnp.float32),
    )

    def fold(carry, X_t, y_t, tile_valid):
        return _tile_update(
            *carry, X_t, labelize(y_t), featurize, use_pallas, tile_valid
        )

    if num_unmasked:

        def body(carry, xs):
            X_t, y_t = xs
            return fold(carry, X_t, y_t, None), None

        carry, _ = jax.lax.scan(
            body, carry, (Xs[:num_unmasked], Ys[:num_unmasked])
        )

    if static_valid:
        for t in range(num_unmasked, num_full):
            tile_valid = min(max(valid - t * tile_rows, 0), tile_rows)
            if tile_valid == 0:
                break
            carry = fold(
                carry, Xs[t], Ys[t], jnp.asarray(tile_valid, jnp.int32)
            )
    elif valid is not None and num_full:

        def body(carry, xs):
            X_t, y_t, t = xs
            tile_valid = jnp.clip(valid - t * tile_rows, 0, tile_rows)
            return fold(carry, X_t, y_t, tile_valid.astype(jnp.int32)), None

        carry, _ = jax.lax.scan(body, carry, (Xs, Ys, jnp.arange(num_full)))

    if rem:
        pad = (-rem) % _ROW_ALIGN
        X_r = jnp.pad(X[num_full * tile_rows :], ((0, pad), (0, 0)))
        y_r = jnp.pad(
            Y[num_full * tile_rows :],
            ((0, pad),) + ((0, 0),) * (Y.ndim - 1),
        )
        rem_valid = rem
        if static_valid:
            rem_valid = min(max(valid - num_full * tile_rows, 0), rem)
        if rem_valid:
            rv = jnp.asarray(rem_valid, jnp.int32)
            if valid is not None and not static_valid:
                rv = jnp.minimum(
                    rv, jnp.clip(valid - num_full * tile_rows, 0, rem)
                ).astype(jnp.int32)
            carry = fold(carry, X_r, y_r, rv)

    G, FY, yty, fsum, ysum = carry
    # The fold adds upper-triangle panels only: the fit's one mirror.
    G = jnp.triu(G) + jnp.triu(G, 1).T
    if moments:
        return G, FY, yty, fsum, ysum
    return G, FY, yty


@jax.named_scope("ks.bcd")  # names the phase in a device profile
def bcd_from_gram(
    G: Array,
    FY: Array,
    block_size: int,
    lam: float,
    num_iter: int,
) -> Array:
    """Block Gauss-Seidel ridge solve on accumulated normal equations.

    Returns W as (nb, block_size, k) — the same iterate sequence as
    residual-form BCD (the residual is eliminated algebraically; see module
    docstring). Per-block Cholesky factors are computed once; every epoch
    costs nb (d, block)×(block, k) GEMMs against the cached G — no data.
    """
    d, k = FY.shape
    if num_iter < 1:
        raise ValueError(f"num_iter must be >= 1, got {num_iter}")
    if d % block_size:
        raise ValueError(f"feature dim {d} not divisible by {block_size}")
    nb = d // block_size
    lam_t = jnp.asarray(lam, G.dtype)

    # (nb, bs, bs) stack of diagonal blocks + factors (loop-invariant).
    diag = jnp.stack(
        [
            G[b * block_size : (b + 1) * block_size,
              b * block_size : (b + 1) * block_size]
            for b in range(nb)
        ]
    )
    chols = jax.vmap(lambda g: _psd_factor(g, lam_t))(diag)

    W0 = jnp.zeros((nb, block_size, k), G.dtype)
    S0 = jnp.zeros((d, k), G.dtype)  # S = G @ W_flat, maintained

    def block_step(b, carry):
        W, S = carry
        Wb = jax.lax.dynamic_index_in_dim(W, b, 0, keepdims=False)
        Gbb = jax.lax.dynamic_index_in_dim(diag, b, 0, keepdims=False)
        ch = jax.lax.dynamic_index_in_dim(chols, b, 0, keepdims=False)
        Sb = jax.lax.dynamic_slice_in_dim(S, b * block_size, block_size, 0)
        FYb = jax.lax.dynamic_slice_in_dim(FY, b * block_size, block_size, 0)
        # S_b = Σ_j G_bj W_j includes j = b; add G_bb W_b back to exclude it.
        rhs = FYb - Sb + Gbb @ Wb
        Wb_new = _solve_psd(Gbb, rhs, lam_t, chol=ch)
        # Column block of G via transposed row slice (G symmetric) — the
        # row slice is contiguous; a column slice is a strided gather.
        Gcol = jax.lax.dynamic_slice_in_dim(
            G, b * block_size, block_size, 0
        ).T
        S = S + Gcol @ (Wb_new - Wb)
        return jax.lax.dynamic_update_index_in_dim(W, Wb_new, b, 0), S

    def epoch(_, carry):
        return jax.lax.fori_loop(0, nb, block_step, carry)

    W, _ = jax.lax.fori_loop(0, num_iter, epoch, (W0, S0))
    return W


class BankFeaturize:
    """Featurize whose array parameters ride as jit OPERANDS, not trace
    constants.

    The closure-based fit programs key their compile cache on the
    featurize CALLABLE's identity and embed any captured arrays as HLO
    constants — so rebuilding a logically-equal bank (λ-sweeps, pipeline
    re-optimization) recompiles the whole tile scan, and a TIMIT-scale
    bank (~360 MB) becomes a constant baked into the executable
    (and into every persistent-cache entry for it). Subclasses instead expose

      - ``params``: pytree of arrays (passed as traced operands),
      - ``static_key()``: hashable non-array config,
      - classmethod ``apply_bank(static_key, params, X_t)``: the traceable
        featurize, resolved through the CLASS (stable identity),

    and the fit dispatchers key the program on (class, static_key, operand
    shapes) — one executable per geometry, shared across bank instances.
    ``__call__`` keeps instances usable as plain featurize callables
    (predict path, gram_stats, tests).
    """

    @property
    def params(self):
        raise NotImplementedError

    def static_key(self) -> tuple:
        return ()

    @classmethod
    def apply_bank(cls, static_key, params, X_t):
        raise NotImplementedError

    def __call__(self, X_t):
        return type(self).apply_bank(self.static_key(), self.params, X_t)


class CallableBank(BankFeaturize):
    """Any traceable featurize callable through the BankFeaturize
    contract: no operand arrays; the callable itself is the static key,
    so the segmented folds' jit cache keys on its identity exactly like
    the closure-path fits (one executable per callable per geometry).
    Lets ``streaming_bcd_fit_segments`` — whose fold is bank-keyed —
    drive composed/fused featurize programs and the identity path."""

    def __init__(self, fn: Callable):
        self.fn = fn

    @property
    def params(self):
        return ()

    def static_key(self) -> tuple:
        return (self.fn,)

    @classmethod
    def apply_bank(cls, static_key, params, X_t):
        return static_key[0](X_t)


def as_bank(featurize) -> BankFeaturize:
    """Normalize a featurize to the BankFeaturize contract."""
    if isinstance(featurize, BankFeaturize):
        return featurize
    return CallableBank(featurize)


def _fit_core(X, Y, featurize, d_feat, tile_rows, block_size, lam,
              num_iter, use_pallas, valid, labelize, center, mesh=None):
    """Shared traceable fit body: tile folds → (optional rank-1 centering)
    → BCD on the normal equations. Returns (W, loss, yty, fmean, ymean);
    fmean/ymean are None when ``center`` is False (static branch).

    ``mesh``: the rows lie sharded over its data axis; each device folds
    its own (:func:`gram_stats_mesh`, one psum round) and the solve is
    replicated. ``valid`` is then the true GLOBAL row count."""
    n_true = valid if valid is not None else (
        X.shape[0] if X.ndim == 2 else X.shape[0] * X.shape[1]
    )
    if mesh is not None:
        if labelize is not None or X.ndim != 2:
            raise ValueError(
                "the mesh fold takes (n, d_in) rows and ready targets: "
                "pre-apply labelize to Y and leave the tiling to the fold"
            )
        stats = gram_stats_mesh(
            X, Y, featurize, d_feat, tile_rows, mesh,
            use_pallas=use_pallas, n_true=valid, moments=center,
        )
    else:
        stats = gram_stats(
            X, Y, featurize, d_feat, tile_rows, use_pallas=use_pallas,
            valid=valid, labelize=labelize, moments=center,
        )
    G, FY, yty, fsum, ysum = stats if center else (*stats, None, None)
    # W blocks are laid out [b*block : (b+1)*block] along d, so Wf rows
    # align with G/FY rows (shared solve tail).
    W, loss, fmean, ymean = _solve_from_stats_core(
        G, FY, yty, fsum, ysum, n_true, lam, block_size, num_iter, center
    )
    return W, loss, yty, fmean, ymean


@functools.partial(
    jax.jit,
    static_argnames=(
        "featurize", "d_feat", "tile_rows", "block_size", "num_iter",
        "use_pallas", "valid", "labelize", "center", "mesh",
    ),
)
def _streaming_fit_closure(X, Y, *, featurize, d_feat, tile_rows,
                           block_size, lam, num_iter, use_pallas, valid,
                           labelize, center, mesh=None):
    return _fit_core(X, Y, featurize, d_feat, tile_rows, block_size, lam,
                     num_iter, use_pallas, valid, labelize, center, mesh)


@functools.partial(
    jax.jit,
    static_argnames=(
        "bank_type", "bank_key", "d_feat", "tile_rows", "block_size",
        "num_iter", "use_pallas", "valid", "labelize", "center", "mesh",
    ),
)
def _streaming_fit_bank(X, Y, bank_params, *, bank_type, bank_key, d_feat,
                        tile_rows, block_size, lam, num_iter, use_pallas,
                        valid, labelize, center, mesh=None):
    featurize = lambda X_t: bank_type.apply_bank(bank_key, bank_params, X_t)  # noqa: E731
    return _fit_core(X, Y, featurize, d_feat, tile_rows, block_size, lam,
                     num_iter, use_pallas, valid, labelize, center, mesh)


def _dispatch_fit(X, Y, featurize, center, kw):
    """The one program of a streamed fit, on one device or (``kw["mesh"]``)
    over a mesh: a bank's arrays ride as operands either way."""
    if isinstance(featurize, BankFeaturize):
        params = featurize.params
        if kw["mesh"] is not None:
            # A bank drawn on one device goes where the rows are.
            params = mesh_lib.replicate(params, kw["mesh"])
        return _streaming_fit_bank(
            X, Y, params, bank_type=type(featurize),
            bank_key=featurize.static_key(), center=center, **kw,
        )
    return _streaming_fit_closure(
        X, Y, featurize=featurize, center=center, **kw,
    )


# ``lam`` is a TRACED operand (not static): a λ-sweep over one geometry
# reuses one compiled program instead of recompiling the whole tile scan
# per λ (VERDICT r4 Weak #3). A :class:`BankFeaturize` featurize further
# keys the program on bank SHAPES rather than callable identity.
def streaming_bcd_fit(
    X: Array,
    Y: Array,
    *,
    featurize: Callable[[Array], Array],
    d_feat: int,
    tile_rows: int,
    block_size: int,
    lam: float,
    num_iter: int,
    use_pallas: bool = False,
    valid: Optional[int] = None,
    labelize: Optional[Callable[[Array], Array]] = None,
    mesh=None,
) -> Tuple[Array, Array, Array]:
    """One-dispatch streamed fit: tiles → (G, FY, yty) → BCD epochs.

    X may be (n, d_in) or pre-tiled (T, tile_rows, d_in) — see
    :func:`gram_stats` for why large fits should pre-tile (and for the
    ``valid`` / ``labelize`` contracts; both must be static here).
    Returns (W, train_loss, yty) with W: (nb, block_size, k). The train
    loss ||Y − FW||²/n comes algebraically from the accumulated stats —
    (yty − 2·tr(Wᵀ FY) + tr(Wᵀ G W))/n — two small GEMMs, no data pass.

    ``mesh`` (ISSUE 16): shard the tile folds over the mesh's data axis
    (each device folds its row shard locally; ONE psum of the stats
    crosses the ICI — :func:`gram_stats_mesh`) with a replicated solve —
    the same iterates as the 1-device fit up to reduction order, and the
    same one program a geometry (a bank's arrays are its operands). X rows
    must divide evenly over the axis (pad and pass the true GLOBAL count
    as ``valid``; padding rows may hold any value, their feature rows are
    zeroed); ``labelize`` is not supported on this path (pre-apply it to Y).
    """
    W, loss, yty, _, _ = _dispatch_fit(
        X, Y, featurize, False,
        dict(d_feat=d_feat, tile_rows=tile_rows, block_size=block_size,
             lam=lam, num_iter=num_iter, use_pallas=use_pallas,
             valid=valid, labelize=labelize, mesh=mesh),
    )
    return W, loss, yty


@functools.partial(jax.jit, donate_argnums=(0,), static_argnames=(
    "bank_type", "bank_key", "tile_rows", "use_pallas",
))
def _dense_segment_fold(carry, X_seg, Y_seg, valid_rows, bank_params, *,
                        bank_type, bank_key, tile_rows, use_pallas):
    """Fold one SEGMENT of pre-tiled rows into the (G, FY, yty, fsum,
    ysum) carry — the dense analog of the sparse segmented fold: segments
    may be loaded from disk one at a time, so neither HBM nor host RAM
    ever holds the dataset. The carry is donated (G dominates);
    ``valid_rows`` (traced) masks the ragged tail of the LAST segment.
    The featurize bank rides as traced operands (BankFeaturize contract:
    one compiled fold for every segment and every logically-equal bank).
    G's carry holds the UPPER block-triangle of the sum (every tile adds
    that alone, :func:`_tile_update`); the caller mirrors it once, after
    the last segment.
    """
    featurize = lambda X_t: bank_type.apply_bank(bank_key, bank_params, X_t)  # noqa: E731
    G, FY, yty, fsum, ysum = carry

    def body(c, xs):
        X_t, Y_t, t0 = xs
        tile_valid = jnp.clip(valid_rows - t0, 0, tile_rows).astype(jnp.int32)
        return _tile_update(
            *c, X_t, Y_t, featurize, use_pallas, tile_valid
        ), None

    starts = jnp.arange(X_seg.shape[0]) * tile_rows
    (G, FY, yty, fsum, ysum), _ = jax.lax.scan(
        body, (G, FY, yty, fsum, ysum), (X_seg, Y_seg, starts)
    )
    return G, FY, yty, fsum, ysum


def streaming_bcd_fit_segments(
    segment_source,
    num_segments: Optional[int] = None,
    n_true: Optional[int] = None,
    bank=None,
    d_feat: int = None,
    tile_rows: int = None,
    block_size: int = None,
    lam=0.0,
    num_iter: int = 1,
    use_pallas: bool = False,
    center: bool = True,
    inflight: int = 2,
    prefetch_depth: int = 2,
    prefetch_stats=None,
    checkpoint=None,
):
    """Disk-bounded dense streamed fit: fold (G, FY, moments) over
    segments delivered one at a time (e.g.
    :class:`keystone_tpu.data.shards.DiskDenseShards.segment_source` over
    memory-mapped tiles), then solve with (optionally centered) BCD on
    the normal equations. The dense analog of
    ``run_lbfgs_gram_streamed(segment_source=...)``: n is bounded by
    DISK, not host RAM or HBM.

    ``segment_source``: either a :class:`keystone_tpu.data.prefetch.
    ShardSource` (then ``num_segments``/``n_true`` default from it and a
    background reader thread prefetches segment k+1 while segment k's
    H2D transfer + fold are in flight — ``prefetch_depth`` bounds the
    staged-host-buffer depth; 0 loads serially, byte-identical results),
    or the legacy callable ``segment_source(s) -> (X_seg (T, tile_rows,
    d_in), Y_seg (T, tile_rows, k), valid_rows)`` — valid_rows counts the
    segment's true rows (phantom/padding tiles past it are masked); the
    callable form loads serially (a callable makes no thread-safety
    promise). ``bank`` may be any featurize callable (wrapped via
    :class:`CallableBank` when not already a BankFeaturize). Returns
    (W, fmean, ymean, loss) when centered, else (W, None, None, loss).

    ``checkpoint``: a :class:`keystone_tpu.data.durable.CheckpointSpec`
    (or directory path; None consults ``KEYSTONE_CHECKPOINT_DIR``) that
    atomically snapshots the fold carry — the (G, FY, yty, fsum, ysum)
    accumulators plus the segment cursor — every ``every_segments``
    segments. A fit killed mid-stream and re-run with the same spec
    resumes at the last snapshot and produces BIT-IDENTICAL results to
    the uninterrupted run (the carry round-trips as raw f32 bytes and
    the remaining segments fold through the same compiled program —
    proven under injected kills in tests/test_chaos.py). The snapshot is
    cleared on successful completion.
    """
    from keystone_tpu.data.durable import (
        fingerprint_token,
        resolve_checkpoint,
        source_fingerprint,
    )
    from keystone_tpu.data.prefetch import is_shard_source, iter_segments

    checkpoint = resolve_checkpoint(checkpoint)

    if is_shard_source(segment_source):
        if num_segments is None:
            num_segments = segment_source.num_segments
        if n_true is None:
            n_true = segment_source.n_true
        if tile_rows is None:
            tile_rows = segment_source.tile_rows
    else:
        prefetch_depth = 0  # plain callables make no thread-safety promise
    if num_segments is None or n_true is None:
        raise ValueError(
            "callable segment sources need explicit num_segments and n_true"
        )
    if bank is None or d_feat is None or tile_rows is None or block_size is None:
        # Fail here, not as a cryptic NoneType error mid-trace: only
        # tile_rows defaults (from a ShardSource) — the rest are required.
        raise ValueError(
            "streamed segment fit needs bank, d_feat, block_size, and "
            "tile_rows (tile_rows defaults only from a ShardSource)"
        )
    bank = as_bank(bank)
    bank_type, bank_key = type(bank), bank.static_key()
    bank_params = bank.params  # raw pytree — the BankFeaturize contract
    carry = None
    start = 0
    fingerprint = None
    if checkpoint is not None:
        # Geometry + featurizer identity (type, static key, parameter
        # digests) + source identity: a stale snapshot from a different
        # bank or a re-ingested shard directory must never seed this
        # fold's accumulators.
        fingerprint = {
            "kind": "dense_bcd_segments",
            "num_segments": int(num_segments), "n_true": int(n_true),
            "d_feat": int(d_feat), "tile_rows": int(tile_rows),
            "bank": {
                "type": bank_type.__name__,
                "key": fingerprint_token(bank_key),
                "params": fingerprint_token(
                    tuple(jax.tree_util.tree_leaves(bank_params))
                ),
            },
            "source": source_fingerprint(segment_source),
        }
        arrays, start = checkpoint.restore(fingerprint)
        if arrays is not None:
            carry = tuple(jnp.asarray(a) for a in arrays)
    throttle = BoundedInflight(inflight)
    import time as _time

    from keystone_tpu import obs as _obs

    for s, (X_seg, Y_seg, valid_rows) in iter_segments(
        segment_source, num_segments=num_segments,
        prefetch_depth=prefetch_depth, stats=prefetch_stats, start=start,
    ):
        if carry is None:
            k = int(Y_seg.shape[-1])
            carry = (
                jnp.zeros((d_feat, d_feat), jnp.float32),
                jnp.zeros((d_feat, k), jnp.float32),
                jnp.zeros((), jnp.float32),
                jnp.zeros((d_feat,), jnp.float32),
                jnp.zeros((k,), jnp.float32),
            )
        t0 = _time.perf_counter()
        # Fold chunk span (obs plane): same region as the `compute` busy
        # counter below, so the trace audits the fold floor per segment.
        with _obs.span("fold.segment", segment=int(s)):
            carry = _dense_segment_fold(
                carry, jnp.asarray(X_seg), jnp.asarray(Y_seg),
                jnp.asarray(int(valid_rows), jnp.int32), bank_params,
                bank_type=bank_type, bank_key=bank_key, tile_rows=tile_rows,
                use_pallas=use_pallas,
            )
            throttle.admit(carry[2])
        if prefetch_stats is not None:
            # The `compute` site: transfer + fold dispatch + the inflight
            # throttle's blocking — the denominator phase of the per-site
            # overlap report (utils.profiling.overlap_report).
            prefetch_stats.add_busy(
                "compute", _time.perf_counter() - t0
            )
        if checkpoint is not None:
            checkpoint.maybe_save(carry, s, num_segments, fingerprint,
                                  stats=prefetch_stats)
    G, FY, yty, fsum, ysum = carry
    G = jnp.triu(G) + jnp.triu(G, 1).T
    # The accumulated moments ride into the shared jitted solve either
    # way; the static ``center`` branch simply ignores them when False.
    W, loss, fmean, ymean = _solve_from_stats(
        G, FY, yty, fsum, ysum,
        jnp.asarray(n_true, jnp.float32), jnp.asarray(lam, jnp.float32),
        block_size=block_size, num_iter=num_iter, center=center,
    )
    if checkpoint is not None:
        # The fit completed: a later fit with this fingerprint must
        # start fresh, not resume a finished run's final carry. Only
        # THIS fit's snapshot — other fits sharing the directory keep
        # theirs.
        checkpoint.clear(fingerprint)
    return W, fmean, ymean, loss


def _solve_from_stats_core(G, FY, yty, fsum, ysum, n_true, lam,
                           block_size, num_iter, center):
    """Traceable solve tail shared by every gram-stats fit entry point:
    (optional rank-1 centering) -> BCD on the normal equations -> loss.
    ``G`` must have BOTH triangles valid. Returns
    (W, loss, fmean, ymean) — fmean/ymean None when not centering."""
    fmean = ymean = None
    # The fold ends with the sums; what is made of them is the solve's: the
    # rank-1 centring before it and the fitted loss (one more product on G)
    # after it read as ``ks.bcd`` in a device profile, with the sweeps.
    if center:
        with jax.named_scope("ks.bcd"):
            G, FY, yty, fmean, ymean = center_gram_stats(
                G, FY, yty, fsum, ysum, n_true
            )
    W = bcd_from_gram(G, FY, block_size, lam, num_iter)
    with jax.named_scope("ks.bcd"):
        Wf = W.reshape(G.shape[0], W.shape[2])
        loss = (yty - 2.0 * jnp.vdot(Wf, FY) + jnp.vdot(Wf, G @ Wf)) / n_true
    return W, loss, fmean, ymean


@functools.partial(
    jax.jit, static_argnames=("block_size", "num_iter", "center")
)
def _solve_from_stats(G, FY, yty, fsum, ysum, n_true, lam, *,
                      block_size, num_iter, center):
    return _solve_from_stats_core(
        G, FY, yty, fsum, ysum, n_true, lam, block_size, num_iter, center
    )


def center_gram_stats(G, FY, yty, fsum, ysum, n):
    """Rank-1-correct accumulated stats to their mean-centered form.

    With μ = fsum/n and ȳ = ysum/n over the n VALID rows (padding rows
    contribute zero to every accumulator):

        Gc   = Σ(fᵢ−μ)(fᵢ−μ)ᵀ = G  − fsum·fsumᵀ/n
        FYc  = Σ(fᵢ−μ)(yᵢ−ȳ)ᵀ = FY − fsum·ysumᵀ/n
        ytyc = Σ‖yᵢ−ȳ‖²        = yty − ysum·ysum/n

    exactly — centering costs two rank-1 updates instead of a second data
    pass. Returns (Gc, FYc, ytyc, fmean, ymean).
    """
    n = jnp.asarray(n, G.dtype)
    fmean = fsum / n
    ymean = ysum / n
    Gc = G - jnp.outer(fsum, fmean)
    FYc = FY - jnp.outer(fsum, ymean)
    ytyc = yty - jnp.dot(ysum, ymean)
    return Gc, FYc, ytyc, fmean, ymean


def streaming_bcd_fit_centered(
    X: Array,
    Y: Array,
    *,
    featurize: Callable[[Array], Array],
    d_feat: int,
    tile_rows: int,
    block_size: int,
    lam,
    num_iter: int,
    use_pallas: bool = False,
    valid: Optional[int] = None,
    labelize: Optional[Callable[[Array], Array]] = None,
    mesh=None,
) -> Tuple[Array, Array, Array, Array]:
    """Mean-centered one-dispatch streamed fit — the streamed form of
    ``BlockLeastSquaresEstimator`` semantics (per-block feature centering +
    label centering + intercept, BlockLinearMapper.scala:224-243): column
    sums accumulate in the same tile pass as G/FY, the normal equations
    get rank-1 centering corrections, and BCD runs on the centered system.

    Returns (W, fmean, ymean, loss): predictions are
    (F − fmean) @ W_flat + ymean — the same affine model BlockLinearMapper
    applies. ``lam`` is traced (λ-sweeps share one executable). ``mesh``:
    as :func:`streaming_bcd_fit` — the column sums ride the same psum
    round as G and FᵀY.
    """
    W, loss, _, fmean, ymean = _dispatch_fit(
        X, Y, featurize, True,
        dict(d_feat=d_feat, tile_rows=tile_rows, block_size=block_size,
             lam=lam, num_iter=num_iter, use_pallas=use_pallas,
             valid=valid, labelize=labelize, mesh=mesh),
    )
    return W, fmean, ymean, loss


def streaming_predict(
    X: Array,
    W: Array,
    featurize: Callable[[Array], Array],
    tile_rows: int,
) -> Array:
    """Predictions F @ W_flat computed tile-wise (F never materialized).

    W: (nb, block, k) from the fit. X may be (n, d_in) or pre-tiled
    (T, tile_rows, d_in) — predictions come back flattened to (n, k)
    either way. Traceable; pads a ragged remainder internally
    (predictions for padding rows are dropped).
    """
    Wf = W.reshape(-1, W.shape[2])

    def tile_preds(X_t):
        F_t = featurize(X_t)
        return (F_t @ Wf.astype(F_t.dtype)).astype(jnp.float32)

    if X.ndim == 3:
        _, P_full = jax.lax.scan(lambda _, X_t: (None, tile_preds(X_t)), None, X)
        return P_full.reshape(X.shape[0] * X.shape[1], -1)

    n = X.shape[0]
    num_full = n // tile_rows
    rem = n - num_full * tile_rows
    outs = []
    if num_full:
        Xs = X[: num_full * tile_rows].reshape(num_full, tile_rows, -1)
        _, P_full = jax.lax.scan(
            lambda _, X_t: (None, tile_preds(X_t)), None, Xs
        )
        outs.append(P_full.reshape(num_full * tile_rows, -1))
    if rem:
        outs.append(tile_preds(X[num_full * tile_rows :]))
    return jnp.concatenate(outs, axis=0) if len(outs) > 1 else outs[0]


# What the block sweep keeps of every block's system between epochs:
# the Gramian and its factor, or the factor alone (``gram @ w`` is then
# rebuilt as L(Lᵀw) − λw: half the stash, NORTHSTAR.md section 3).
BLOCK_STASHES = ("gram+factor", "factor")


def _row_tiles(ln: int, tile_rows: Optional[int]) -> Tuple[int, int, int]:
    """(tile, full tiles, remainder rows) of ``ln`` local rows. One tile
    holds them all where no tile is asked for or they fit one."""
    if tile_rows is None or ln <= tile_rows:
        return ln, 1, 0
    return tile_rows, ln // tile_rows, ln % tile_rows


def _block_sweep(x_local, Wrf, brf, lam_t, valid, *, axis, block_size,
                 n_eff, feat_dtype, center, tile_rows, use_pallas):
    """The two steps of the block-streamed sweep over one device's rows —
    ``first_step`` (epoch 1: builds and stashes the block's system) and
    ``later_step`` (epochs 2+: reads the stash) — as scan bodies over the
    block index, for the carry ``(R, Wst, G, C, M)``: residual, block
    weights, Gramian stash (None under ``stash="factor"``), factor stash,
    block means. Shared by the one-program form
    (:func:`streaming_block_bcd_mesh`) and the two-dispatch form
    (:func:`block_bcd_first_epoch` / :func:`block_bcd_later_epochs`).

    A step walks the local rows in tiles of ``tile_rows`` so that the
    feature slab it holds is (tile, block_size) whatever n is. Where one
    tile holds every local row the slab is made once a step and serves
    the correlation and the update both; over several tiles the update
    pass featurizes each tile again (nothing of a step outlives it but
    the (bs, bs) and (bs, k) sums).

    Epoch 1's Gramian F_tᵀF_t is a tile's upper block-triangle alone
    (:func:`_gram_upper_panels`: a symmetric rank-k update in plain
    ``dot_general``s, whatever the rows, the block and the backend); the
    block's summed — and, on a mesh, psum'd and centred — upper triangle
    is mirrored once, so that the stash, the factor and ``gram @ w`` see a
    full matrix, symmetric to the bit."""
    from .linalg import _factor_matvec, _solve_psd_from_factor

    ln, d_in = x_local.shape
    tile, num_full, rem = _row_tiles(ln, tile_rows)
    one_slab = num_full == 1 and rem == 0
    acc = jnp.promote_types(feat_dtype, jnp.float32)

    def bank_slice(b):
        Wb = jax.lax.dynamic_slice(
            Wrf, (b * block_size, 0), (block_size, d_in)
        )
        bb = jax.lax.dynamic_slice(brf, (b * block_size,), (block_size,))
        return Wb, bb

    def featurize(bank, x_t, valid_t):
        Wb, bb = bank
        with jax.named_scope("ks.block_featurize"):
            if use_pallas:
                from keystone_tpu.ops import pallas_ops

                F = pallas_ops.cosine_features(
                    x_t, Wb, bb, compute_dtype=feat_dtype,
                    out_dtype=feat_dtype,
                )
            else:
                F = jnp.cos(x_t @ Wb.T + bb).astype(feat_dtype)
            if valid_t is not None:
                F = F * valid_t.astype(F.dtype)
        return F

    def tile_sums(F, R_t, with_gram: bool):
        """(FᵀR, Σ R rows[, FᵀF, Σ F rows]) of one tile."""
        with jax.named_scope("ks.block_update"):
            out = (
                jax.lax.dot_general(
                    F, R_t.astype(F.dtype), (((0,), (0,)), ((), ())),
                    preferred_element_type=acc,
                ).astype(jnp.float32),
                jnp.sum(R_t, axis=0),
            )
        if with_gram:
            with jax.named_scope("ks.block_gram"):
                out += (
                    _gram_upper_panels(F, acc),
                    jnp.sum(F, axis=0, dtype=jnp.float32),
                )
        return out

    def tile_update(F, R_t, dw, const, valid_t):
        """R_t − Fc·Δw = R_t − F·Δw + 1·(μᵀΔw); the constant term must
        not leak into padding rows."""
        with jax.named_scope("ks.block_update"):
            delta = jax.lax.dot_general(
                F, dw.astype(F.dtype), (((1,), (0,)), ((), ())),
                preferred_element_type=acc,
            ).astype(R_t.dtype)
            if const is not None:
                delta = delta - (
                    const[None, :] if valid_t is None
                    else const[None, :] * valid_t.astype(R_t.dtype)
                )
            return R_t - delta

    def tiled(a):
        """(the full tiles of ``a``, stacked; its remainder rows)."""
        if a is None:
            return None, None
        full = a[: num_full * tile].reshape((num_full, tile) + a.shape[1:])
        return full, (a[num_full * tile:] if rem else None)

    def local_sums(bank, R, with_gram: bool):
        """The step's sums over every local row, and the slab where one
        tile holds them all."""
        if one_slab:
            F = featurize(bank, x_local, valid)
            return tile_sums(F, R, with_gram), F

        def one(x_t, R_t, valid_t):
            return tile_sums(featurize(bank, x_t, valid_t), R_t, with_gram)

        (xs, x_r), (Rs, R_r), (vs, v_r) = tiled(x_local), tiled(R), tiled(valid)
        k = R.shape[1]
        zero = (jnp.zeros((block_size, k), jnp.float32),
                jnp.zeros((k,), jnp.float32))
        if with_gram:
            zero += (jnp.zeros((block_size, block_size), jnp.float32),
                     jnp.zeros((block_size,), jnp.float32))

        def body(sums, t):
            got = one(xs[t], Rs[t], None if vs is None else vs[t])
            return tuple(a + g for a, g in zip(sums, got)), None

        sums, _ = jax.lax.scan(body, zero, jnp.arange(num_full))
        if rem:
            sums = tuple(a + g for a, g in zip(sums, one(x_r, R_r, v_r)))
        return sums, None

    def local_update(bank, F, R, dw, const):
        if one_slab:
            return tile_update(F, R, dw, const, valid)

        def one(x_t, R_t, valid_t):
            return tile_update(
                featurize(bank, x_t, valid_t), R_t, dw, const, valid_t
            )

        (xs, x_r), (Rs, R_r), (vs, v_r) = tiled(x_local), tiled(R), tiled(valid)
        _, out = jax.lax.scan(
            lambda _, t: (None, one(
                xs[t], Rs[t], None if vs is None else vs[t])),
            None, jnp.arange(num_full),
        )
        out = out.reshape((num_full * tile,) + R.shape[1:])
        if rem:
            out = jnp.concatenate([out, one(x_r, R_r, v_r)], axis=0)
        return out

    @jax.named_scope("ks.block_update")
    def solve_and_update(b, bank, F, R, Wst, local, rsum, gram, chol, mu):
        """One block solve + residual update from the step's local sums.
        ``gram``/``chol`` are the (centered, when ``center``) block system
        (``gram`` None: the factor alone is kept); ``mu`` is the block's
        feature mean (None when not centering)."""
        if mu is not None:
            # Centered correlation: FcᵀR = FᵀR − μ·(Σᵢ Rᵢ)ᵀ. The row
            # sum rides the SAME psum as the correlation (stacked as
            # one extra row) — one collective per block step, as the
            # dossier's cost model states.
            stacked = jax.lax.psum(
                jnp.concatenate([local, rsum[None, :]], axis=0), axis
            )
            corr = stacked[:-1] - jnp.outer(mu, stacked[-1])
        else:
            corr = jax.lax.psum(local, axis)
        w_old = jax.lax.dynamic_index_in_dim(Wst, b, 0, keepdims=False)
        if gram is None:
            rhs = corr + _factor_matvec(chol, w_old, lam_t)
            w_new = _solve_psd_from_factor(chol, rhs, lam_t)
        else:
            rhs = corr + gram @ w_old
            w_new = _solve_psd(gram, rhs, lam_t, chol=chol)
        dw = w_new - w_old
        const = None if mu is None else (mu @ dw).astype(R.dtype)
        R = local_update(bank, F, R, dw, const)
        return R, jax.lax.dynamic_update_index_in_dim(Wst, w_new, b, 0)

    # A device profile names the innermost scope. ``ks.block_gram`` is the
    # tiles' panels and column sums alone (``tile_sums``); what epoch 1 makes
    # of them — psum, centring, mirror, Cholesky factor, the stash's writes —
    # is ``ks.block_factor``, so a change to the factor does not move the
    # Gramian's time. The stash's and the bank's slices of a later step read
    # with the update they serve.
    @jax.named_scope("ks.block_factor")
    def first_step(carry, b):
        R, Wst, G, C, M = carry
        bank = bank_slice(b)
        (local, rsum, gram_l, fsum_l), F = local_sums(bank, R, True)
        gram = jax.lax.psum(gram_l, axis)
        if center:
            fsum = jax.lax.psum(fsum_l, axis)
            mu = fsum / n_eff
            gram = gram - jnp.outer(fsum, mu)  # = G − n μμᵀ, exact
            M = jax.lax.dynamic_update_index_in_dim(M, mu, b, 0)
        else:
            mu = None
        # The tiles gave the upper triangle. Mirrored once a block, after
        # their sums, the psum and the centring: symmetric to the bit.
        gram = jnp.triu(gram) + jnp.triu(gram, 1).T
        chol = _psd_factor(gram, lam_t)
        R, Wst = solve_and_update(
            b, bank, F, R, Wst, local, rsum, gram, chol, mu
        )
        if G is not None:
            G = jax.lax.dynamic_update_index_in_dim(G, gram, b, 0)
        C = jax.lax.dynamic_update_index_in_dim(C, chol, b, 0)
        return (R, Wst, G, C, M), None

    @jax.named_scope("ks.block_update")
    def later_step(carry, b):
        R, Wst, G, C, M = carry
        bank = bank_slice(b)
        gram = (
            None if G is None
            else jax.lax.dynamic_index_in_dim(G, b, 0, keepdims=False)
        )
        chol = jax.lax.dynamic_index_in_dim(C, b, 0, keepdims=False)
        mu = (
            jax.lax.dynamic_index_in_dim(M, b, 0, keepdims=False)
            if center else None
        )
        (local, rsum), F = local_sums(bank, R, False)
        R, Wst = solve_and_update(
            b, bank, F, R, Wst, local, rsum, gram, chol, mu
        )
        return (R, Wst, G, C, M), None

    return first_step, later_step


def _block_bcd_shard_fns(*, block_size, mesh, n_pad, n_true, feat_dtype,
                         center, tile_rows, use_pallas, stash):
    """(first_epoch, later_epochs): the block-streamed sweep's two phases
    as per-device bodies (call them inside a ``shard_map`` over the mesh's
    ``data`` axis).

    ``first_epoch(x_local, y_local, Wrf, brf, lam)`` gives the carry
    ``(R, Wst, G, C, M)`` after epoch 1 and the label mean (zeros when not
    centering); ``later_epochs(carry, x_local, Wrf, brf, lam, epochs)``
    gives the carry after ``epochs`` more. :func:`residual_sq` reads a
    carry's residual norm."""
    if stash not in BLOCK_STASHES:
        raise ValueError(f"stash must be one of {BLOCK_STASHES}, got {stash!r}")
    axis = mesh_lib.DATA_AXIS
    num = mesh_lib.axis_size(mesh, axis)
    ln = n_pad // num
    n_eff = n_true if n_true is not None else n_pad

    def steps(x_local, Wrf, brf, lam):
        if n_true is not None and n_true != n_pad:
            start = jax.lax.axis_index(axis) * ln
            valid = (
                (start + jnp.arange(ln)) < n_true
            ).astype(jnp.float32)[:, None]
        else:
            valid = None
        return valid, _block_sweep(
            x_local, Wrf, brf, jnp.asarray(lam, jnp.float32), valid,
            axis=axis, block_size=block_size, n_eff=n_eff,
            feat_dtype=feat_dtype, center=center, tile_rows=tile_rows,
            use_pallas=use_pallas,
        )

    def first_epoch(x_local, y_local, Wrf, brf, lam):
        valid, (first_step, _) = steps(x_local, Wrf, brf, lam)
        nb, k = Wrf.shape[0] // block_size, y_local.shape[1]
        R0 = y_local.astype(jnp.float32)
        if valid is not None:
            R0 = R0 * valid
        ymean = jnp.zeros((k,), jnp.float32)
        if center:
            ymean = jax.lax.psum(jnp.sum(R0, axis=0), axis) / n_eff
            R0 = R0 - (
                ymean[None, :] if valid is None
                else ymean[None, :] * valid
            )
        stack = jnp.zeros((nb, block_size, block_size), jnp.float32)
        carry0 = (
            R0,
            jnp.zeros((nb, block_size, k), jnp.float32),
            stack if stash == "gram+factor" else None,
            stack,
            jnp.zeros((nb, block_size), jnp.float32),
        )
        carry, _ = jax.lax.scan(first_step, carry0, jnp.arange(nb))
        return carry, ymean

    def later_epochs(carry, x_local, Wrf, brf, lam, epochs: int):
        _, (_, later_step) = steps(x_local, Wrf, brf, lam)
        order = jnp.arange(Wrf.shape[0] // block_size)

        def epoch(carry, _):
            carry, _ = jax.lax.scan(later_step, carry, order)
            return carry, None

        carry, _ = jax.lax.scan(epoch, carry, None, length=epochs)
        return carry

    return first_epoch, later_epochs


def _residual_sq(R):
    """‖R‖² over every device's rows (inside the shard_map)."""
    return jax.lax.psum(jnp.sum(R * R), mesh_lib.DATA_AXIS)


def _check_blocks(Wrf, block_size: int) -> None:
    if Wrf.shape[0] % block_size:
        raise ValueError(
            f"d_feat {Wrf.shape[0]} not divisible by {block_size}"
        )


_BLOCK_STATICS = (
    "block_size", "mesh", "n_true", "feat_dtype", "center", "tile_rows",
    "use_pallas", "stash",
)


# ``lam`` is a TRACED operand (λ-sweeps share one compiled sweep).
@functools.partial(jax.jit, static_argnames=_BLOCK_STATICS + ("num_iter",))
def streaming_block_bcd_mesh(
    X: Array,
    Y: Array,
    Wrf: Array,
    brf: Array,
    *,
    block_size: int,
    lam: float,
    num_iter: int,
    mesh,
    n_true: Optional[int] = None,
    feat_dtype=jnp.float32,
    center: bool = False,
    tile_rows: Optional[int] = None,
    use_pallas: bool = False,
    stash: str = "gram+factor",
):
    """The north-star program: cosine-featurize + block coordinate descent
    where feature BLOCKS are generated per step and discarded — the plan
    that runs TIMIT at ~200k feature dims on a v5e-16 (NORTHSTAR.md).

    Rows of X (n_pad, d_in) and Y (n_pad, k) shard over the mesh ``data``
    axis; the random-feature bank Wrf (d_feat, d_in) / brf (d_feat,)
    replicates (352 MB at the full 200k×440 — small beside HBM). The whole
    (epochs × blocks) sweep is ONE shard_map program:

      per block b:  F_b = cos(X_local Wrf_bᵀ + brf_b)   local slab, freed
                    gram, corr = psum(F_bᵀF_b), psum(F_bᵀR)   ← the ONLY
                        per-step collective: bs² + bs·k floats over ICI
                    W_b ← replicated Cholesky solve
                    R_local ← R_local − F_b ΔW_b

    so the (n × d_feat) feature matrix — 880 GB of bf16 at the full
    geometry — never exists; the resident working set per device is the
    raw rows, the residual, one block slab and the epoch-invariant
    Gramian/factor stash (HBM table in NORTHSTAR.md). Epochs 2+ reuse the
    stashed factors and pay only featurize + correlation + update.

    ``tile_rows`` bounds the slab a step holds to (tile_rows, block_size)
    (None: every local row in one slab); ``use_pallas`` featurizes through
    the ``cosine_features`` Mosaic kernel; ``stash`` is what is kept of a
    block's system between epochs (:data:`BLOCK_STASHES`). The steps are
    :func:`_block_sweep`'s, shared with the two-dispatch form
    (:func:`block_bcd_first_epoch` / :func:`block_bcd_later_epochs`), which
    gives the same weights bit for bit.

    Padding rows (``n_true``) are masked AFTER featurization (a zero row
    featurizes to cos(b) ≠ 0). Returns the (nb, bs, k) block weights,
    replicated — or, with ``center=True``, (W, fmean, ymean):
    per-block feature means and the label mean accumulate in the same
    block steps (one extra bs-vector in the epoch-1 psum and a k-vector
    per correlation psum), the per-block systems solve on their CENTERED
    Gramians, and the model is the BlockLeastSquares affine form
    (F − fmean) @ W + ymean — full semantics parity with the resident
    Block solver at geometries where only this tier runs.
    """
    axis = mesh_lib.DATA_AXIS
    _check_blocks(Wrf, block_size)
    first_epoch, later_epochs = _block_bcd_shard_fns(
        block_size=block_size, mesh=mesh, n_pad=X.shape[0], n_true=n_true,
        feat_dtype=feat_dtype, center=center, tile_rows=tile_rows,
        use_pallas=use_pallas, stash=stash,
    )

    def body(x_local, y_local, Wrf, brf):
        carry, ymean = first_epoch(x_local, y_local, Wrf, brf, lam)
        if num_iter > 1:
            carry = later_epochs(carry, x_local, Wrf, brf, lam, num_iter - 1)
        if center:
            return carry[1], carry[4].reshape(Wrf.shape[0]), ymean
        return carry[1]

    out_specs = (P(), P(), P()) if center else P()
    return mesh_lib.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(axis), P(axis), P(), P()),
        out_specs=out_specs,
        check_vma=False,
    )(X, Y, Wrf, brf)


def _carry_specs(stash: str):
    """Partition specs of the sweep's carry ``(R, Wst, G, C, M)``: the
    residual's rows shard over ``data``, the rest replicates."""
    return (P(mesh_lib.DATA_AXIS), P(),
            P() if stash == "gram+factor" else None, P(), P())


@functools.partial(jax.jit, static_argnames=_BLOCK_STATICS)
def block_bcd_first_epoch(
    X: Array, Y: Array, Wrf: Array, brf: Array, lam, *, block_size: int,
    mesh, n_true: Optional[int] = None, feat_dtype=jnp.float32,
    center: bool = False, tile_rows: Optional[int] = None,
    use_pallas: bool = False, stash: str = "gram+factor",
):
    """Epoch 1 of :func:`streaming_block_bcd_mesh` as a program of its own:
    the sweep that builds every block's system. Returns
    ``(carry, ymean, residual_sq)`` — the carry ``(R, Wst, G, C, M)`` as
    device arrays (residual row-sharded, block weights, Gramian stash or
    None, factor stash, block means), the label mean, and ‖R‖² after the
    epoch — for :func:`block_bcd_later_epochs` to take up, for a snapshot,
    or for the host to read between the phases."""
    axis = mesh_lib.DATA_AXIS
    _check_blocks(Wrf, block_size)
    first_epoch, _ = _block_bcd_shard_fns(
        block_size=block_size, mesh=mesh, n_pad=X.shape[0], n_true=n_true,
        feat_dtype=feat_dtype, center=center, tile_rows=tile_rows,
        use_pallas=use_pallas, stash=stash,
    )

    def body(x_local, y_local, Wrf, brf, lam):
        carry, ymean = first_epoch(x_local, y_local, Wrf, brf, lam)
        return carry, ymean, _residual_sq(carry[0])

    return mesh_lib.shard_map(
        body, mesh=mesh,
        in_specs=(P(axis), P(axis), P(), P(), P()),
        out_specs=(_carry_specs(stash), P(), P()),
        check_vma=False,
    )(X, Y, Wrf, brf, jnp.asarray(lam, jnp.float32))


@functools.partial(
    jax.jit, static_argnames=_BLOCK_STATICS + ("epochs",),
    donate_argnames=("R", "Wst"),
)
def block_bcd_later_epochs(
    R: Array, Wst: Array, stashes, X: Array, Wrf: Array, brf: Array, lam, *,
    epochs: int, block_size: int, mesh, n_true: Optional[int] = None,
    feat_dtype=jnp.float32, center: bool = False,
    tile_rows: Optional[int] = None, use_pallas: bool = False,
    stash: str = "gram+factor",
):
    """``epochs`` more sweeps from the carry :func:`block_bcd_first_epoch`
    left. The residual and the block weights are DONATED and come back
    updated, with ‖R‖² after the last sweep; ``stashes`` = ``(G, C, M)``
    is read and stays the caller's (epochs 2+ change none of it)."""
    axis = mesh_lib.DATA_AXIS
    _, later_epochs = _block_bcd_shard_fns(
        block_size=block_size, mesh=mesh, n_pad=X.shape[0], n_true=n_true,
        feat_dtype=feat_dtype, center=center, tile_rows=tile_rows,
        use_pallas=use_pallas, stash=stash,
    )

    def body(R, Wst, stashes, x_local, Wrf, brf, lam):
        carry = later_epochs(
            (R, Wst) + tuple(stashes), x_local, Wrf, brf, lam, epochs
        )
        return carry[0], carry[1], _residual_sq(carry[0])

    specs = _carry_specs(stash)
    return mesh_lib.shard_map(
        body, mesh=mesh,
        in_specs=(specs[0], specs[1], specs[2:], P(axis), P(), P(), P()),
        out_specs=(specs[0], specs[1], P()),
        check_vma=False,
    )(R, Wst, tuple(stashes), X, Wrf, brf, jnp.asarray(lam, jnp.float32))


@functools.partial(
    jax.jit,
    static_argnames=(
        "block_size", "num_iter", "mesh", "n_true", "feat_dtype",
        "center",
    ),
)
def streaming_block_bcd_mesh_2d(
    X: Array,
    Y: Array,
    Wrf: Array,
    brf: Array,
    *,
    block_size: int,
    lam: float,
    num_iter: int,
    mesh,
    n_true: Optional[int] = None,
    feat_dtype=jnp.float32,
    center: bool = False,
):
    """2-D (data × model) form of the north-star program: the Gramian/
    factor stash, the block weights AND the feature bank shard over the
    ``model`` axis (reference analog: VectorSplitter.scala:10-36 feature
    blocks over workers), while rows shard over BOTH axes so every device
    computes on every block step.

    Per-device stash drops from nb·bs² to (nb/model_size)·bs² — the lever
    NORTHSTAR.md §3 names for d ≫ 200k: at d_feat = 409,600 (100 blocks
    of 4096) the replicated stash would be 13.4 GB (Gramian + factor);
    over model=4 it is 3.4 GB.

    Block b's owner is model index b // (nb/model_size) (contiguous
    assignment matches the bank's natural sharding). Per block step:

      bank slice  psum over model (bs·d_in floats — owner broadcasts)
      F           local cos slab over the device's rows, freed per step
      gram/corr   psum over BOTH axes (epoch 1) / corr only (later)
      solve       epoch 1: replicated (gram is replicated post-psum);
                  later: the OWNER computes gram@w_old and the Cholesky
                  solve from its stash, then broadcasts w_new/w_old
                  (2·bs·k floats) — the stash itself never crosses the
                  interconnect
      R update    local rows

    Returns (nb, bs, k) block weights sharded over ``model`` on axis 0.
    X/Y rows must be sharded over (data, model) flattened (data-major).
    With ``center=True`` (same semantics as the 1-D form): returns
    (W, fmean (nb, bs) sharded over model, ymean replicated); per-block
    means live in the owner's stash and are owner-broadcast (bs floats)
    in later epochs alongside w_new/w_old.
    """
    data_ax = mesh_lib.DATA_AXIS
    model_ax = mesh_lib.MODEL_AXIS
    d_feat = Wrf.shape[0]
    d_in = X.shape[1]
    k = Y.shape[1]
    if d_feat % block_size:
        raise ValueError(f"d_feat {d_feat} not divisible by {block_size}")
    nb = d_feat // block_size
    mc = mesh_lib.axis_size(mesh, model_ax)
    dr = mesh_lib.axis_size(mesh, data_ax)
    if nb % mc:
        raise ValueError(f"nb {nb} not divisible by model axis {mc}")
    nb_local = nb // mc
    n_pad = X.shape[0]
    ln = n_pad // (dr * mc)
    bs = block_size
    n_eff = n_true if n_true is not None else n_pad

    def body(x_local, y_local, wrf_local, brf_local):
        lam_t = jnp.asarray(lam, jnp.float32)
        mi = jax.lax.axis_index(model_ax)
        if n_true is not None and n_true != n_pad:
            # P((data, model)) splits rows data-major.
            start = (jax.lax.axis_index(data_ax) * mc + mi) * ln
            valid = (
                (start + jnp.arange(ln)) < n_true
            ).astype(jnp.float32)[:, None]
        else:
            valid = None

        def bank_block(b):
            slot = jnp.mod(b, nb_local)
            owner = b // nb_local
            is_owner = (mi == owner)
            sl = jax.lax.dynamic_slice(
                wrf_local, (slot * bs, 0), (bs, d_in)
            )
            bb = jax.lax.dynamic_slice(brf_local, (slot * bs,), (bs,))
            own_f = is_owner.astype(sl.dtype)
            Wb = jax.lax.psum(sl * own_f, model_ax)
            bv = jax.lax.psum(bb * own_f, model_ax)
            return Wb, bv, is_owner, slot

        def featurize(x, Wb, bv):
            F = jnp.cos(x @ Wb.T + bv).astype(feat_dtype)
            if valid is not None:
                F = F * valid.astype(F.dtype)
            return F

        acc = jnp.promote_types(feat_dtype, jnp.float32)

        def psum2(v):
            return jax.lax.psum(jax.lax.psum(v, data_ax), model_ax)

        def corr_of(F, R, mu):
            local = jax.lax.dot_general(
                F, R.astype(F.dtype), (((0,), (0,)), ((), ())),
                preferred_element_type=acc,
            ).astype(jnp.float32)
            if mu is None:
                return psum2(local)
            # Centered correlation: FcᵀR = FᵀR − μ·(Σᵢ Rᵢ)ᵀ. The row sum
            # rides the SAME psum2 as the correlation (one extra stacked
            # row) — the per-step collective count stays at one pair.
            stacked = psum2(
                jnp.concatenate([local, jnp.sum(R, axis=0)[None, :]], axis=0)
            )
            return stacked[:-1] - jnp.outer(mu, stacked[-1])

        def apply_delta(R, F, w_new, w_old, mu):
            dw = w_new - w_old
            delta = jax.lax.dot_general(
                F, dw.astype(F.dtype),
                (((1,), (0,)), ((), ())), preferred_element_type=acc,
            ).astype(R.dtype)
            if mu is not None:
                # R ← R − Fc·Δw = R − F·Δw + 1·(μᵀΔw), padding-masked.
                const = (mu @ dw).astype(R.dtype)
                term = (
                    const[None, :] if valid is None
                    else const[None, :] * valid.astype(R.dtype)
                )
                delta = delta - term
            return R - delta

        def mask_store(stash, slot, value, is_owner):
            old = jax.lax.dynamic_index_in_dim(stash, slot, 0, keepdims=False)
            new = jnp.where(is_owner, value, old)
            return jax.lax.dynamic_update_index_in_dim(stash, new, slot, 0)

        def first_step(carry, b):
            R, Wst, G, C, M = carry
            Wb, bv, is_owner, slot = bank_block(b)
            F = featurize(x_local, Wb, bv)
            gram = psum2(
                jax.lax.dot_general(
                    F, F, (((0,), (0,)), ((), ())),
                    preferred_element_type=acc,
                )
            )
            if center:
                fsum = psum2(jnp.sum(F, axis=0, dtype=jnp.float32))
                mu = fsum / n_eff
                gram = gram - jnp.outer(fsum, mu)  # = G − n μμᵀ, exact
                M = mask_store(M, slot, mu, is_owner)
            else:
                mu = None
            chol = _psd_factor(gram, lam_t)
            corr = corr_of(F, R, mu)
            # w_old is zero in epoch 1 (fresh W) — rhs is just corr.
            w_new = _solve_psd(gram, corr, lam_t, chol=chol)
            R = apply_delta(R, F, w_new, jnp.zeros_like(w_new), mu)
            G = mask_store(G, slot, gram, is_owner)
            C = mask_store(C, slot, chol, is_owner)
            Wst = mask_store(Wst, slot, w_new, is_owner)
            return (R, Wst, G, C, M), None

        def later_step(carry, b):
            R, Wst, G, C, M = carry
            Wb, bv, is_owner, slot = bank_block(b)
            F = featurize(x_local, Wb, bv)
            own_f = is_owner.astype(jnp.float32)
            if center:
                # Owner broadcasts the block's mean (bs floats).
                mu_l = jax.lax.dynamic_index_in_dim(
                    M, slot, 0, keepdims=False
                )
                mu = jax.lax.psum(mu_l * own_f, model_ax)
            else:
                mu = None
            corr = corr_of(F, R, mu)
            gram_l = jax.lax.dynamic_index_in_dim(G, slot, 0, keepdims=False)
            chol_l = jax.lax.dynamic_index_in_dim(C, slot, 0, keepdims=False)
            w_old_l = jax.lax.dynamic_index_in_dim(
                Wst, slot, 0, keepdims=False
            )
            # Non-owners hold garbage stash slots; guard the factor with I
            # so their (masked-out) solves stay finite — NaN·0 would leak.
            chol_safe = jnp.where(
                is_owner, chol_l, jnp.eye(bs, dtype=chol_l.dtype)
            )
            rhs = corr + gram_l @ w_old_l
            w_new_l = _solve_psd(gram_l, rhs, lam_t, chol=chol_safe)
            w_new = jax.lax.psum(w_new_l * own_f, model_ax)
            w_old = jax.lax.psum(w_old_l * own_f, model_ax)
            R = apply_delta(R, F, w_new, w_old, mu)
            Wst = mask_store(Wst, slot, w_new, is_owner)
            return (R, Wst, G, C, M), None

        R0 = y_local.astype(jnp.float32)
        if valid is not None:
            R0 = R0 * valid
        if center:
            ymean = psum2(jnp.sum(R0, axis=0)) / n_eff
            R0 = R0 - (
                ymean[None, :] if valid is None
                else ymean[None, :] * valid
            )
        Wst0 = jnp.zeros((nb_local, bs, k), jnp.float32)
        G0 = jnp.zeros((nb_local, bs, bs), jnp.float32)
        C0 = jnp.zeros((nb_local, bs, bs), jnp.float32)
        M0 = jnp.zeros((nb_local, bs), jnp.float32)
        order = jnp.arange(nb)
        carry, _ = jax.lax.scan(first_step, (R0, Wst0, G0, C0, M0), order)
        if num_iter > 1:
            def epoch(carry, _):
                carry, _ = jax.lax.scan(later_step, carry, order)
                return carry, None
            carry, _ = jax.lax.scan(epoch, carry, None, length=num_iter - 1)
        if center:
            return carry[1], carry[4], ymean
        return carry[1]

    out_specs = (
        (P(model_ax), P(model_ax), P()) if center else P(model_ax)
    )
    return mesh_lib.shard_map(
        body,
        mesh=mesh,
        in_specs=(
            P((data_ax, model_ax)), P((data_ax, model_ax)),
            P(model_ax), P(model_ax),
        ),
        out_specs=out_specs,
        check_vma=False,
    )(X, Y, Wrf, brf)


def gram_stats_mesh(
    X: Array,
    Y: Array,
    featurize: Callable[[Array], Array],
    d_feat: int,
    tile_rows: int,
    mesh,
    use_pallas: bool = False,
    n_true: Optional[int] = None,
    moments: bool = False,
) -> Tuple[Array, ...]:
    """Mesh-parallel gram_stats: rows sharded over ``data``; each device
    folds its local tiles, then ONE psum of (G, FY, yty) crosses the
    interconnect — the treeReduce analog, one collective per fit.

    ``n_true`` (static): the true global row count when X was padded to
    shard evenly — trailing padding rows are masked out per shard.
    ``moments=True`` additionally psums the column sums (see
    :func:`gram_stats`) for the centered solvers.
    """
    axis = mesh_lib.DATA_AXIS
    n_padded = X.shape[0]
    num = mesh_lib.axis_size(mesh, axis)
    local_rows = n_padded // num

    def local(xs, ys):
        if n_true is not None and n_true != n_padded:
            start = jax.lax.axis_index(axis) * local_rows
            valid = jnp.clip(n_true - start, 0, local_rows)
        else:
            valid = None
        stats = gram_stats(
            xs, ys, featurize, d_feat, tile_rows, use_pallas=use_pallas,
            valid=valid, moments=moments,
        )
        with jax.named_scope("ks.gram_psum"):  # the fit's one collective round
            return jax.lax.psum(stats, axis)

    n_out = 5 if moments else 3
    return mesh_lib.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(axis), P(axis)),
        out_specs=tuple(P() for _ in range(n_out)),
        check_vma=False,
    )(X, Y)
