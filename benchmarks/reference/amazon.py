"""Plain reference for the Amazon sparse least-squares fit: ridge regression
on padded-COO rows by L-BFGS, straight from the rows.

It follows ``LBFGS.scala:208-281`` / ``Gradient.scala:58-123`` as the
configuration file states the deployment: the objective
``1/2 ||XW - Y||^2 / n + 1/2 lam ||W||^2`` with the intercept as an appended
ones column, L-BFGS from W = 0 with a two-loop recursion over the last
``history`` pairs, the exact step along the direction (the objective is a
quadratic, so ``alpha = -g.p / p.Hp``), at most ``iterations`` steps, stop at
``||grad|| <= tol``. Straightforward ``jax.numpy`` float32; no import from
``keystone_tpu``.

The data enter ONLY as the two products of a gradient, ``X P`` and
``X^T R``, made per block of ``rows_per_block`` rows from a dense copy of
the block: **no Gramian is ever formed**, so a program that iterates on
``X^T X`` has its algebra under test, not restated. The L-BFGS of one
ridge value is written once (:func:`lbfgs_steps`, a generator that asks for
``X^T X P / n`` and is sent the answer); :func:`fit_and_score` runs the
ridge values side by side so that one pass over the rows serves them all.

``precision`` is the precision of the two products (``reference.timit.matmul``):
``"highest"`` is the reference proper; ``"bf16"`` (one pass: the direction
and the residual rounded to bfloat16, sums in float32) and ``"high"`` /
``"default"`` (the backend's three- and one-pass forms) are the CONTROLS.
The rows are 0/1, which bfloat16 holds exactly: what a control loses is the
rounding of ``P`` and of ``X P``.
"""

from __future__ import annotations

import functools
from typing import Dict, Iterator, List, Sequence, Tuple

import jax
import jax.numpy as jnp

# one pass spelled out ("bf16") and the two gaps: the TIMIT reference's own
from benchmarks.reference.timit import matmul, score_gaps  # noqa: F401

F32 = jnp.float32


def dense_block(idx, val, d: int):
    """(rows, d + 1) dense copy of a block of padded-COO rows, the ones
    column appended; a negative id is an empty lane."""
    rows = jnp.arange(idx.shape[0])[:, None]
    live = idx >= 0
    X = jnp.zeros((idx.shape[0], d + 1), F32)
    X = X.at[rows, jnp.where(live, idx, 0)].add(jnp.where(live, val, 0).astype(F32))
    return X.at[:, d].set(1.0)


def _blocks(a, rows_per_block: int):
    return a.reshape(a.shape[0] // rows_per_block, rows_per_block, *a.shape[1:])


@functools.partial(jax.jit, static_argnames=("d", "precision", "rows_per_block"))
def xt_y(idx, val, Y, d: int, precision: str, rows_per_block: int):
    """X^T Y over the rows, block by block."""

    def body(acc, blk):
        i, v, y = blk
        return acc + matmul(dense_block(i, v, d).T, y, precision), None

    start = jnp.zeros((d + 1, Y.shape[1]), F32)
    return jax.lax.scan(body, start, tuple(_blocks(a, rows_per_block) for a in (idx, val, Y)))[0]


@functools.partial(jax.jit, static_argnames=("d", "precision", "rows_per_block"))
def xt_x_p(idx, val, P, d: int, precision: str, rows_per_block: int):
    """X^T (X P): two products a block, the (d + 1)^2 matrix never made."""

    def body(acc, blk):
        X = dense_block(*blk, d)
        return acc + matmul(X.T, matmul(X, P, precision), precision), None

    start = jnp.zeros(P.shape, F32)
    return jax.lax.scan(body, start, tuple(_blocks(a, rows_per_block) for a in (idx, val)))[0]


@functools.partial(jax.jit, static_argnames=("d", "precision"))
def scores(idx, val, W, d: int, precision: str = "highest"):
    return matmul(dense_block(idx, val, d), W, precision)


def two_loop(grad, pairs: List[Tuple[jax.Array, jax.Array, jax.Array]]):
    """The L-BFGS direction -H grad from the kept (s, y, rho) pairs, oldest
    first; the initial matrix is ``s.y / y.y`` of the newest pair."""
    q, alphas = grad, []
    for s, y, rho in reversed(pairs):
        a = rho * jnp.sum(s * q)
        q = q - a * y
        alphas.append(a)
    if pairs:
        s, y, _ = pairs[-1]
        sy, yy = jnp.sum(s * y), jnp.sum(y * y)
        q = jnp.where(sy > 0, sy / jnp.maximum(yy, 1e-30), 1.0) * q
    for (s, y, rho), a in zip(pairs, reversed(alphas)):
        q = q + (a - rho * jnp.sum(y * q)) * s
    return -q


def lbfgs_steps(AtY_n, lam, iterations: int, history: int, tol: float) -> Iterator:
    """L-BFGS on ``1/2 W.(A W) - W.AtY_n`` with ``A = X^T X / n + lam``,
    from W = 0. Yields each direction ``P`` and is sent ``X^T X P / n``;
    returns ``(W, iterations run)``."""
    W = jnp.zeros_like(AtY_n)
    grad = -AtY_n  # A 0 - AtY_n
    pairs: List[Tuple[jax.Array, jax.Array, jax.Array]] = []
    count = 0
    while count < iterations and float(jnp.linalg.norm(grad)) > tol:
        p = two_loop(grad, pairs[-history:])
        Hp = (yield p) + lam * p
        curvature = jnp.sum(p * Hp)
        alpha = jnp.where(curvature > 0, -jnp.sum(grad * p) / curvature, 0.0)
        s, y = alpha * p, alpha * Hp  # the gradient of a quadratic moves by A s
        W, grad = W + s, grad + y
        sy = jnp.sum(s * y)
        pairs.append((s, y, jnp.where(sy > 0, 1.0 / sy, 0.0)))
        count += 1
    return W, count


def fit(idx, val, Y, lams: Sequence[float], *, d: int, iterations: int,
        history: int, tol: float, precision: str = "highest",
        rows_per_block: int = 8192) -> Dict[float, Tuple[jax.Array, int]]:
    """``{lam: (W of (d + 1, k), iterations run)}``. The fits advance side by
    side: each asks for a product, and one pass over the rows answers all."""
    rows_per_block = min(rows_per_block, idx.shape[0])
    n, k = F32(idx.shape[0]), Y.shape[1]
    AtY_n = xt_y(idx, val, Y, d, precision, rows_per_block) / n
    running = {lam: lbfgs_steps(AtY_n, F32(lam), iterations, history, tol) for lam in lams}
    asked, done = {}, {}

    def advance(lam, answer=None):
        try:
            asked[lam] = running[lam].send(answer)
        except StopIteration as finished:
            asked.pop(lam, None)
            done[lam] = finished.value

    for lam in lams:
        advance(lam)
    while asked:
        waiting = list(asked)
        P = jnp.concatenate([asked[lam] for lam in waiting], axis=1)
        HP = xt_x_p(idx, val, P, d, precision, rows_per_block) / n
        for i, lam in enumerate(waiting):
            advance(lam, HP[:, i * k:(i + 1) * k])
    return done


def fit_and_score(idx, val, Y, probe_idx, probe_val, lams: Sequence[float],
                  **how) -> Tuple[Dict[float, jax.Array], Dict[float, int]]:
    """Scores of the probe rows under the model fitted on (idx, val, Y), and
    the iterations each fit ran, per ridge value."""
    fitted = fit(idx, val, Y, list(dict.fromkeys(lams)), **how)
    d = how["d"]
    return ({lam: scores(probe_idx, probe_val, W, d) for lam, (W, _) in fitted.items()},
            {lam: its for lam, (_, its) in fitted.items()})
