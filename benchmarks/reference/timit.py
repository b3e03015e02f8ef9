"""Plain reference for the TimitPipeline fit: cosine random features, a
mean-centred ridge system, block Gauss-Seidel on its normal equations.

It follows ``pipelines/speech/TimitPipeline.scala:37-130`` and
``BlockLinearMapper.scala:199-283`` as the configuration files state them,
in straightforward ``jax.numpy`` float32, with no kernel, no tiling scheme
of the program's and no import from ``keystone_tpu``. Rows go through in
blocks so that the (rows, d_feat) feature matrix never exists whole.

``precision`` is the precision of every contraction (see :func:`matmul`):
``"highest"`` is the reference proper; ``"high"`` (three bf16 passes) and
``"default"`` / ``"bf16"`` (one) are the CONTROLS — the reference put in
the program's place below what the configuration states.

Departures from the papers' description, all of them stated in the
configuration file: the bank is drawn with ``jax.random`` as the
configuration's ``bank`` entry says (the Scala draws from Breeze's
generator); labels arrive as the +-1 indicator matrix.
"""

from __future__ import annotations

import functools
from typing import Dict, Sequence, Tuple

import jax
import jax.numpy as jnp

F32 = jnp.float32


def draw_bank(seed: int, num_cosines: int, d_in: int, block: int, gamma: float):
    """W ~ N(0, gamma^2), b ~ U[0, 2 pi): branch i from ``key(seed + i)``
    split in two — the draw the configuration's ``bank`` entry states."""
    Ws, bs = [], []
    for i in range(num_cosines):
        kw, kb = jax.random.split(jax.random.key(seed + i))
        Ws.append(jax.random.normal(kw, (block, d_in), F32) * F32(gamma))
        bs.append(jax.random.uniform(kb, (block,), F32) * F32(2 * jnp.pi))
    return jnp.concatenate(Ws), jnp.concatenate(bs)


def matmul(a, b, precision):
    """``a @ b`` in float32 at ``precision``: ``highest`` / ``high`` /
    ``default`` are the backend's own (on a TPU six, three and one bf16
    pass; a CPU ignores them). ``bf16`` spells the one-pass arithmetic out
    — operands rounded to bfloat16, products summed in float32 — so that
    the tests' control reads alike on a CPU."""
    if precision == "bf16":
        return jnp.matmul(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                          preferred_element_type=F32)
    return jnp.matmul(a, b, precision=precision)


def features(X, W, b, precision):
    return jnp.cos(matmul(X, W.T, precision) + b)


@functools.partial(jax.jit, static_argnames=("precision",), donate_argnums=(0,))
def _fold(stats, X_blk, Y_blk, W, b, precision):
    G, C, fsum, ysum = stats
    F = features(X_blk, W, b, precision)
    G = G + matmul(F.T, F, precision)
    C = C + matmul(F.T, Y_blk, precision)
    return G, C, fsum + F.sum(axis=0), ysum + Y_blk.sum(axis=0)


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _centre(G, C, fsum, ysum, n):
    fmean, ymean = fsum / n, ysum / n
    return G - jnp.outer(fsum, fmean), C - jnp.outer(fsum, ymean), fmean, ymean


def centred_stats(X, Y, W, b, precision="highest", rows_per_block=8192):
    """(G, C, fmean, ymean) of the mean-centred features and labels."""
    d, k = W.shape[0], Y.shape[1]
    stats = (jnp.zeros((d, d), F32), jnp.zeros((d, k), F32),
             jnp.zeros((d,), F32), jnp.zeros((k,), F32))
    for lo in range(0, X.shape[0], rows_per_block):
        stats = _fold(stats, X[lo:lo + rows_per_block],
                      Y[lo:lo + rows_per_block], W, b, precision)
    return _centre(*stats, F32(X.shape[0]))


@functools.partial(jax.jit, static_argnames=("block", "precision"),
                   donate_argnums=(2,))
def _block_step(G, C, Wt, lam, lo, block, precision):
    """Exact solve of block ``lo``: (G_bb + lam I) W_b = C_b - sum_{j != b} G_bj W_j."""
    Gb = jax.lax.dynamic_slice_in_dim(G, lo, block, 0)
    Gbb = jax.lax.dynamic_slice_in_dim(Gb, lo, block, 1)
    Wb = jax.lax.dynamic_slice_in_dim(Wt, lo, block, 0)
    rhs = (jax.lax.dynamic_slice_in_dim(C, lo, block, 0)
           - matmul(Gb, Wt, precision)
           + matmul(Gbb, Wb, precision))
    chol = jax.scipy.linalg.cho_factor(Gbb + lam * jnp.eye(block, dtype=F32),
                                       lower=True)
    return jax.lax.dynamic_update_slice_in_dim(
        Wt, jax.scipy.linalg.cho_solve(chol, rhs), lo, 0)


def block_gauss_seidel(G, C, lam, block, epochs, precision="highest"):
    """Ridge weights after ``epochs`` Gauss-Seidel sweeps over feature
    blocks of width ``block``, from W = 0."""
    Wt = jnp.zeros(C.shape, F32)
    for _ in range(epochs):
        for lo in range(0, C.shape[0], block):
            Wt = _block_step(G, C, Wt, F32(lam), jnp.int32(lo), block, precision)
    return Wt


@functools.partial(jax.jit, static_argnames=("precision",))
def scores(Xp, W, b, Wt, fmean, ymean, precision="highest"):
    F = features(Xp, W, b, precision) - fmean
    return matmul(F, Wt, precision) + ymean


def fit_and_score(X, Y, probe, lams: Sequence[float], *, bank_seed: int,
                  num_cosines: int, block: int, gamma: float, epochs: int,
                  precision: str = "highest",
                  rows_per_block: int = 8192) -> Dict[float, jax.Array]:
    """Scores of ``probe`` rows under the model fitted on (X, Y), one per
    ridge ``lam``; the Gramian is built once and shared by the lams."""
    W, b = draw_bank(bank_seed, num_cosines, X.shape[1], block, gamma)
    G, C, fmean, ymean = centred_stats(X, Y, W, b, precision, rows_per_block)
    out = {}
    for lam in lams:
        Wt = block_gauss_seidel(G, C, F32(lam), block, epochs, precision)
        out[lam] = scores(probe, W, b, Wt, fmean, ymean, precision)
    return out


def score_gaps(got, want) -> Tuple[float, float]:
    """(relative Frobenius gap, widest gap over the widest reference score)."""
    got, want = jnp.asarray(got, F32), jnp.asarray(want, F32)
    diff = got - want
    fro = jnp.linalg.norm(diff) / jnp.linalg.norm(want)
    widest = jnp.max(jnp.abs(diff)) / jnp.max(jnp.abs(want))
    return float(fro), float(widest)
