"""Plain reference for the TimitPipeline fit at its published width: cosine
random features, a mean-centred ridge system, block Gauss-Seidel IN
RESIDUAL FORM — the form ``BlockLeastSquaresEstimator`` itself has
(``BlockLinearMapper.scala:199-283``): no d x d Gramian ever exists (168 GB
at 204,800 features), every block's features are made again from the raw
rows whenever a step needs them, and what is carried from step to step is
the residual R = Y - ymean - sum_b (F_b - fmean_b) W_b.

One step, for block b and every ridge value side by side:

    W_b <- (Gc_b + lam I)^-1 ((F_b - fmean_b)^T R + Gc_b W_b)
    R   <- R - (F_b - fmean_b) (W_b_new - W_b_old)

with Gc_b the block's mean-centred Gramian, built in the first sweep and
shared by the ridge values. These are the iterates of
``reference/timit.py`` (Gauss-Seidel on the whole normal equations) with
the residual kept instead of eliminated; a test holds the two together
where both can run.

Straightforward ``jax.numpy`` float32, rows in blocks of ``rows_per_block``
so that no (rows, block) slab of all rows exists; no kernel, no tiling
scheme or stash of the program's, no import from ``keystone_tpu``. The
bank draw, the contraction helper and the two compared numbers are
``reference/timit.py``'s; ``precision`` gives the controls as there
(``"high"``: three bf16 passes, ``"default"`` / ``"bf16"``: one).

Departures from the Scala, as the configuration file states them: the bank
is drawn with ``jax.random``; labels arrive as the +-1 indicator matrix.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp

from benchmarks.reference.timit import F32, draw_bank, features, matmul, score_gaps  # noqa: F401


@functools.partial(jax.jit, static_argnames=("precision",), donate_argnums=(0, 1))
def _gram_fold(G, fsum, X_blk, Wb, bb, precision):
    F = features(X_blk, Wb, bb, precision)
    return G + matmul(F.T, F, precision), fsum + F.sum(axis=0)


@functools.partial(jax.jit, static_argnames=("precision",), donate_argnums=(0,))
def _corr_fold(C, X_blk, R_blk, Wb, bb, fmean, precision):
    """C[l] += (F - fmean)^T R[l] over one block of rows."""
    Fc = features(X_blk, Wb, bb, precision) - fmean
    return C + jnp.stack([matmul(Fc.T, R, precision) for R in R_blk])


@functools.partial(jax.jit, static_argnames=("precision",))
def _solve(Gc, C, W_old, lams, precision):
    """Exact solve of the block for each ridge value:
    (Gc + lam I) W = C + Gc W_old."""
    eye = jnp.eye(Gc.shape[0], dtype=F32)

    def one(c, w_old, lam):
        chol = jax.scipy.linalg.cho_factor(Gc + lam * eye, lower=True)
        return jax.scipy.linalg.cho_solve(chol, c + matmul(Gc, w_old, precision))

    return jnp.stack([one(C[i], W_old[i], lams[i]) for i in range(C.shape[0])])


@functools.partial(jax.jit, static_argnames=("precision",), donate_argnums=(0,))
def _update(R_blk, X_blk, Wb, bb, fmean, dW, precision):
    Fc = features(X_blk, Wb, bb, precision) - fmean
    return R_blk - jnp.stack([matmul(Fc, dw, precision) for dw in dW])


@functools.partial(jax.jit, static_argnames=("precision",))
def _block_scores(Xp, Wb, bb, fmean, W_b, precision):
    Fc = features(Xp, Wb, bb, precision) - fmean
    return jnp.stack([matmul(Fc, w, precision) for w in W_b])


def fit_and_score(X, Y, probe, lams: Sequence[float], *, bank_seed: int,
                  num_cosines: int, block: int, gamma: float, epochs: int,
                  precision: str = "highest",
                  rows_per_block: int = 8192) -> Dict[float, jax.Array]:
    """Scores of ``probe`` rows under the model fitted on (X, Y), one per
    ridge ``lam``, after ``epochs`` residual-form sweeps from W = 0."""
    W, b = draw_bank(bank_seed, num_cosines, X.shape[1], block, gamma)
    n, k = X.shape[0], Y.shape[1]
    lam_v = jnp.asarray(list(lams), F32)
    los = range(0, n, rows_per_block)
    X_blks = [X[lo:lo + rows_per_block] for lo in los]
    ymean = sum(Y[lo:lo + rows_per_block].sum(axis=0) for lo in los) / F32(n)
    # the residual, a block of rows at a time, every ridge value's stacked
    R_blks: List[jax.Array] = [
        jnp.broadcast_to(Y[lo:lo + rows_per_block] - ymean,
                         (len(lam_v), min(rows_per_block, n - lo), k)) for lo in los]
    Wt = [jnp.zeros((len(lam_v), block, k), F32) for _ in range(num_cosines)]
    grams, fmeans = {}, {}
    for epoch in range(epochs):
        for blk in range(num_cosines):
            Wb, bb = W[blk * block:(blk + 1) * block], b[blk * block:(blk + 1) * block]
            if epoch == 0:  # the block's centred Gramian, shared by the ridge values
                G, fsum = jnp.zeros((block, block), F32), jnp.zeros((block,), F32)
                for X_blk in X_blks:
                    G, fsum = _gram_fold(G, fsum, X_blk, Wb, bb, precision)
                fmeans[blk] = fsum / F32(n)
                grams[blk] = G - jnp.outer(fsum, fmeans[blk])
            C = jnp.zeros((len(lam_v), block, k), F32)
            for X_blk, R_blk in zip(X_blks, R_blks):
                C = _corr_fold(C, X_blk, R_blk, Wb, bb, fmeans[blk], precision)
            new = _solve(grams[blk], C, Wt[blk], lam_v, precision)
            dW = new - Wt[blk]
            R_blks = [_update(R_blk, X_blk, Wb, bb, fmeans[blk], dW, precision)
                      for X_blk, R_blk in zip(X_blks, R_blks)]
            Wt[blk] = new
    scores = jnp.broadcast_to(ymean, (len(lam_v), probe.shape[0], k))
    for blk in range(num_cosines if epochs else 0):  # no sweep: W = 0, the scores are ymean
        Wb, bb = W[blk * block:(blk + 1) * block], b[blk * block:(blk + 1) * block]
        scores = scores + _block_scores(probe, Wb, bb, fmeans[blk], Wt[blk], precision)
    return {lam: scores[i] for i, lam in enumerate(lams)}
