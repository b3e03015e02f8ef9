"""Plain reference for the RandomPatchCifar fit: whitened random-patch
filters, a patch-normalised convolution, the two-sided rectifier, sum pools,
standardised features and one sweep of block Gauss-Seidel from W = 0.

It follows ``pipelines/images/cifar/RandomPatchCifar.scala:21-86`` (with
``Convolver.scala``, ``SymmetricRectifier.scala``, ``Pooler.scala``,
``StandardScaler.scala`` and ``BlockLinearMapper.scala:199-283``) as the
configuration file states it, in straightforward ``jax.numpy`` float32, with
no import from ``keystone_tpu``: the 6 x 6 windows are cut out one offset
at a time, the pools are sums over explicit index ranges, the whitener is
the eigendecomposition of the patch covariance, and images go through in
blocks so that no (images, 27, 27, filters) map exists for the whole set.

``precision`` is the precision of every contraction (:func:`matmul`):
``"highest"`` is the reference proper; ``"bf16"`` (one pass, operands
rounded to bfloat16) and ``"default"`` are the CONTROLS.

The filter draw is restated from the configuration's ``filter_draw``
entry: the same ``jax.random`` calls on the same key give the same patch
positions; everything after the positions is computed here anew.
"""

from __future__ import annotations

import functools
from typing import Dict, Sequence, Tuple

import jax
import jax.numpy as jnp

F32 = jnp.float32


def matmul(a, b, precision):
    """``a @ b`` in float32 at ``precision`` (``bf16``: operands rounded to
    bfloat16, products summed in float32, on any backend)."""
    if precision == "bf16":
        return jnp.matmul(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                          preferred_element_type=F32)
    return jnp.matmul(a, b, precision=precision)


def normalize_rows(rows, var_constant):
    """Stats.normalizeRows: each row minus its mean, over
    sqrt(variance with d - 1 + var_constant)."""
    centred = rows - rows.mean(axis=-1, keepdims=True)
    var = (centred * centred).sum(axis=-1, keepdims=True) / (rows.shape[-1] - 1.0)
    return centred / jnp.sqrt(var + var_constant)


def patch_draw(seed: int, count: int, n: int, side: int, patch: int):
    """(image, x, y) of the ``count`` patches the configuration's
    ``filter_draw`` names: key(seed) split in (k_img, k_pos), k_pos in
    (kx, ky), each a ``randint`` over its range."""
    k_img, k_pos = jax.random.split(jax.random.key(seed))
    kx, ky = jax.random.split(k_pos)
    return (jax.random.randint(k_img, (count,), 0, n),
            jax.random.randint(kx, (count,), 0, side - patch + 1),
            jax.random.randint(ky, (count,), 0, side - patch + 1))


@functools.partial(jax.jit, static_argnames=("count", "filters", "patch", "precision"))
def whitened_filters(images, draw, *, count, filters, patch, var_constant, eps,
                     precision="highest"):
    """(filters, whitener, means) from the drawn patches, RandomPatchCifar.scala:36-58:
    rows normalised, ZCA (eigendecomposition of the covariance, eps added to
    its eigenvalues), the first ``filters`` rows whitened and set to unit
    norm, then mapped back through the whitener's transpose."""
    img, sx, sy = draw
    # one pixel of every patch at a time, offset by offset, row-major (x, y, c)
    rows = jnp.stack([images[img, sx + dx, sy + dy, :]
                      for dx in range(patch) for dy in range(patch)], axis=1)
    rows = rows.reshape(count, -1)
    rows = normalize_rows(rows, var_constant)
    means = rows.mean(axis=0)
    centred = rows - means
    cov = matmul(centred.T, centred, precision) / (count - 1.0)
    lam, V = jnp.linalg.eigh(cov)
    W = (V * (jnp.maximum(lam, 0.0) + eps) ** -0.5) @ V.T
    sampled = matmul(rows[:filters] - means, W, precision)
    sampled = sampled / (jnp.sqrt((sampled * sampled).sum(axis=1, keepdims=True)) + 1e-10)
    return matmul(sampled, W.T, precision), W, means


def pool_ranges(side: int, pool_size: int, pool_stride: int):
    """Pooler.scala's windows along one axis: centres from pool_size / 2
    in steps of pool_stride while inside the map, each
    [centre - pool_size / 2, min(centre + pool_size / 2, side))."""
    half = pool_size // 2
    return [(c - half, min(c + half, side)) for c in range(half, side, pool_stride)]


def featurize_block(images, filters, means, *, patch, var_constant, alpha, ranges,
                    precision):
    """Pooled features of a block of images: explicit windows, normalised,
    the whitener's means off, the filter products, both rectified halves,
    sums over the pools; flattened (pool x, pool y, channel), the
    rectifier's positive half first."""
    b, side, _, C = images.shape
    o = side - patch + 1
    cut = [images[:, dx:dx + o, dy:dy + o, :] for dx in range(patch) for dy in range(patch)]
    windows = jnp.stack(cut, axis=3).reshape(b * o * o, patch * patch * C)
    windows = normalize_rows(windows, var_constant) - means
    conv = matmul(windows, filters.T, precision).reshape(b, o, o, -1)
    halves = jnp.concatenate([jnp.maximum(conv - alpha, 0.0),
                              jnp.maximum(-conv - alpha, 0.0)], axis=-1)
    pools = [[halves[:, x0:x1, y0:y1, :].sum(axis=(1, 2)) for (y0, y1) in ranges]
             for (x0, x1) in ranges]
    return jnp.stack([jnp.stack(row, axis=1) for row in pools], axis=1).reshape(b, -1)


def _divisor_at_most(n: int, most: int) -> int:
    return max(d for d in range(1, min(n, most) + 1) if n % d == 0)


@functools.partial(jax.jit, static_argnames=("block", "patch", "ranges", "precision"))
def _features(images, filters, means, *, block, patch, var_constant, alpha, ranges,
              precision):
    n = images.shape[0]
    blocks = images.reshape((n // block, block) + images.shape[1:])
    out = jax.lax.map(functools.partial(
        featurize_block, filters=filters, means=means, patch=patch,
        var_constant=var_constant, alpha=alpha, ranges=ranges, precision=precision),
        blocks)
    return out.reshape(n, -1)


def features(images, filters, means, config, precision, images_per_block=200):
    side = images.shape[1]
    ranges = tuple(pool_ranges(side - config["patch_size"] + 1,
                               config["pool_size"], config["pool_stride"]))
    return _features(images, filters, means,
                     block=_divisor_at_most(images.shape[0], images_per_block),
                     patch=config["patch_size"], var_constant=config["patch_var_constant"],
                     alpha=config["alpha"], ranges=ranges, precision=precision)


@jax.jit
def standardize(F):
    """StandardScaler: column means, standard deviations over n - 1 (1 where
    one is under 1e-12)."""
    n = F.shape[0]
    mean = F.mean(axis=0)
    std = jnp.sqrt(((F - mean) ** 2).sum(axis=0) / (n - 1.0))
    std = jnp.where(std < 1e-12, 1.0, std)
    return (F - mean) / std, mean, std


@functools.partial(jax.jit, static_argnames=("block", "precision"))
def gauss_seidel_sweep(Z, Y, lam, *, block, precision):
    """One sweep of block Gauss-Seidel from W = 0 over feature blocks of
    ``block`` columns (the last one narrower where d is not a multiple),
    each block centred by its own means and the targets by theirs; returns
    (block weights, block means, target means)."""
    ymean = Y.mean(axis=0)
    R = Y - ymean
    Ws, mus = [], []
    for lo in range(0, Z.shape[1], block):
        Xb = Z[:, lo:lo + block]
        mu = Xb.mean(axis=0)
        Xb = Xb - mu
        G = matmul(Xb.T, Xb, precision) + lam * jnp.eye(Xb.shape[1], dtype=F32)
        Wb = jax.scipy.linalg.cho_solve(jax.scipy.linalg.cho_factor(G, lower=True),
                                        matmul(Xb.T, R, precision))
        R = R - matmul(Xb, Wb, precision)
        Ws.append(Wb)
        mus.append(mu)
    return jnp.concatenate(Ws), jnp.concatenate(mus), ymean


@functools.partial(jax.jit, static_argnames=("precision",))
def scores(Fp, mean, std, mus, Wt, ymean, precision="highest"):
    return matmul((Fp - mean) / std - mus, Wt, precision) + ymean


def fit_and_score(images, Y, probe, lams: Sequence[float], *, config,
                  precision: str = "highest") -> Dict[float, jax.Array]:
    """Scores of the ``probe`` images under the model fitted on
    (``images``, ``Y``), one per ridge ``lam``."""
    return fit_score_and_features(images, Y, probe, lams, config=config,
                                  precision=precision)[0]


def fit_score_and_features(images, Y, probe, lams: Sequence[float], *, config,
                           precision: str = "highest"):
    """(scores a ridge ``lam``, the probe images' pooled features): the
    filters and the features are made once and shared by the lams."""
    n, side = images.shape[0], images.shape[1]
    draw = patch_draw(config["filter_seed"], config["whitener_size"], n, side,
                      config["patch_size"])
    filters, _, means = whitened_filters(
        images, draw, count=config["whitener_size"], filters=config["num_filters"],
        patch=config["patch_size"], var_constant=config["patch_var_constant"],
        eps=config["whitener_eps"], precision=precision)
    Z, mean, std = standardize(features(images, filters, means, config, precision))
    Fp = features(probe, filters, means, config, precision)
    out = {}
    for lam in lams:
        Wt, mus, ymean = gauss_seidel_sweep(Z, Y, F32(lam), block=config["block_size"],
                                            precision=precision)
        out[lam] = scores(Fp, mean, std, mus, Wt, ymean, precision)
    return out, Fp


def score_gaps(got, want) -> Tuple[float, float]:
    """(relative Frobenius gap, widest gap over the widest reference score)."""
    got, want = jnp.asarray(got, F32), jnp.asarray(want, F32)
    diff = got - want
    fro = jnp.linalg.norm(diff) / jnp.linalg.norm(want)
    widest = jnp.max(jnp.abs(diff)) / jnp.max(jnp.abs(want))
    return float(fro), float(widest)
