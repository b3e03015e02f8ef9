"""Pallas TPU kernel for the image pipeline's convolution featurize.

:func:`conv_pool_features` is the whole featurize of a chain
``Convolver`` → ``SymmetricRectifier`` → ``Pooler`` (sum) → optionally
``ImageVectorizer``: the fused batch program of ``workflow/fusion.py`` runs
it in place of those members where the convolution offers to take them
(``ops/images/conv.Convolver.device_absorb`` → ``PooledConvolution``; the
TPU, float32 filters and a VMEM plan for the images — every other chain
keeps its XLA program). Each grid step takes 128 images, ONE A LANE: XLA
moves the images to ``(steps, X, C, Y·128)`` (a transpose of the images
alone), so a window shifted by ``py`` is a slice of whole lane tiles and
the kernel lays out a row of windows' patches ``(p²·c, y'·128)`` in VMEM
with aligned copies. Then, a row of windows at a time: the per-patch
normalisation (``normalize_patch_rows``, over the rows of each column), the
whitener's means, the filter product at float32 ``HIGHEST``
(``pallas_ops._dot_kwargs``) a filter tile at a time, the two-sided
rectifier, and the sum of each window into the pools it lies in (pools
that overlap share rows of windows: the map is cut where any pool starts
or ends, :func:`axis_pools`). The pools are transposed to one image a row
at the end of the step, in ImageVectorizer's order. Neither the conv map
nor the patch matrix is written to HBM; the traffic is the images in and
the pooled features out.

The in-kernel im2col with the images CHANNELS-LAST, one image a grid step,
is what Mosaic refused: on a TPU v5e (jax 0.9.0 / libtpu 0.0.34) at CIFAR
geometry it asked for 88.62M of scoped VMEM against a 16.00M limit, and
with the limit raised to 100 MB its compilation had not finished after
470 s — a ``(27, 27, 3)`` window puts 3 values in a 128-lane row, so every
window slice and reshape is a relayout of ~97% padding, which the
lane-a-image layout above removes.

The reference's image featurizer is im2col into a reused patch-matrix
buffer followed by one BLAS-3 GEMM per image (nodes/images/
Convolver.scala:128-220). Column order inside a patch is row-major over
``(px, py, c)`` — the contract of ``conv.im2col`` /
``Convolver.pack_filters``. Numerics: float32 with
``preferred_element_type=float32`` and ``precision=HIGHEST`` on the dots,
the normalisation's (d−1) variance denominator; both match the XLA chain to
float-associativity tolerance (the reductions associate differently),
pinned at 1e-5 relative in tests/test_conv_pool_kernel.py.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from keystone_tpu.ops.pallas_ops import _dot_kwargs, _pallas_call

__all__ = [
    "conv_featurize_flops",
    "conv_pool_features",
    "conv_pool_plan",
]


def conv_featurize_flops(n: int, xo: int, yo: int, d: int, k: int) -> float:
    """Executed-FLOP model for a convolution featurize: the filter GEMM's
    2·n·x'·y'·d·k dominates (normalization is O(n·x'·y'·d) — <1% beside a
    k≥128 filter bank and excluded, the same convention as the roofline
    rows in bench.py)."""
    return 2.0 * n * xo * yo * d * k


# ---------------------------------------------------------------------------
# The kernel: windows -> normalised patches -> filters -> two-sided rectifier
# -> sum pools, 128 images a grid step, one in each lane.
# ---------------------------------------------------------------------------

LANES = 128  # images a grid step of :func:`conv_pool_features`: one a lane
# The most filters a product in the kernel takes (the tile is the largest
# divisor of k that is a multiple of 8 and no more): at the image cell's
# shape on a v5e, 160 took 561.8 ms for 50,000 images where 40, 64, 80, 200,
# 320, 400, 800 and 1,600 took 630.6, 581.6, 571.9, 568.9, 574.3, 580.8,
# 585.5 and 598.0 (one v5e chip, the kernel alone)
_FILTER_TILE = 160
_VMEM_LIMIT = 100 << 20  # scoped VMEM the kernel may ask for (v5e: 128 MiB)
_VMEM_PLAN = 72 << 20  # what its blocks and intermediates may take (the rest is Mosaic's)


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def axis_pools(side: int, stride: int, pool_size: int):
    """Pooler's pools along one axis of the conv map — pool k covers
    ``[k·stride, k·stride + pool_size)`` cut at the edge, ``ceil((side −
    pool_size // 2) / stride)`` of them — and the segments their edges cut
    the axis into: ``(segments [(lo, hi)], the segments each pool
    covers)``. Positions in no pool belong to no segment."""
    npools = -(-(side - pool_size // 2) // stride)
    spans = [(k * stride, min(k * stride + pool_size, side)) for k in range(npools)]
    cuts = sorted({c for span in spans for c in span})
    segments = [(a, b) for a, b in zip(cuts, cuts[1:])
                if any(lo <= a and b <= hi for lo, hi in spans)]
    covers = tuple(tuple(i for i, (a, b) in enumerate(segments) if lo <= a and b <= hi)
                   for lo, hi in spans)
    return tuple(segments), covers


class ConvPoolPlan(NamedTuple):
    """How :func:`conv_pool_features` lays out one shape: the segments of
    each axis of the conv map and the pools over them (:func:`axis_pools`),
    the padded patch width, the filters a product takes and the VMEM it
    reckons."""

    xs: Tuple[Tuple[int, int], ...]
    x_covers: Tuple[Tuple[int, ...], ...]
    ys: Tuple[Tuple[int, int], ...]
    y_covers: Tuple[Tuple[int, ...], ...]
    d_pad: int
    filter_tile: int
    vmem_bytes: int

    @property
    def pools(self) -> int:
        return len(self.x_covers) * len(self.y_covers)


def conv_pool_plan(image_shape, num_filters: int, patch_size: int, stride: int,
                   pool_size: int) -> Optional[ConvPoolPlan]:
    """The plan of :func:`conv_pool_features` for ``(X, Y, C)`` images, or
    None where no pool fits the conv map, the filters are not a multiple
    of 8 (a product takes a tile of them, sublane-aligned), or the
    kernel's VMEM does not fit
    ``_VMEM_PLAN``: its double-buffered blocks (a grid step's images,
    the filters, its pooled rows), the pool accumulators, the patch
    matrix of one row of windows and a product tile's intermediates."""
    X, Y, C = image_shape
    xo, yo = X - patch_size + 1, Y - patch_size + 1
    if xo < 1 or yo < 1 or num_filters % 8:
        return None
    xs, x_covers = axis_pools(xo, stride, pool_size)
    ys, y_covers = axis_pools(yo, stride, pool_size)
    if not x_covers or not y_covers:
        return None
    d_pad = _round_up(patch_size * patch_size * C, 128)
    k = num_filters
    step = max(t for t in range(8, min(k, _FILTER_TILE) + 1, 8) if k % t == 0)
    pools = len(x_covers) * len(y_covers)
    widest = yo * LANES
    f32 = 4
    blocks = 2 * f32 * (X * _round_up(C, 8) * Y * LANES + k * d_pad
                        + LANES * 2 * pools * k + d_pad * LANES)
    accumulators = f32 * 2 * pools * k * LANES
    patches = 4 * f32 * d_pad * yo * LANES  # the matrix, its centred and normalised copies
    product = f32 * step * widest + 2 * 3 * d_pad * widest + 2 * 3 * step * d_pad
    vmem = blocks + accumulators + patches + product
    if vmem > _VMEM_PLAN:
        return None
    return ConvPoolPlan(xs, x_covers, ys, y_covers, d_pad, step, vmem)


def _conv_pool_kernel(img_ref, f_ref, mn_ref, o_ref, s_ref, acc_ref, *, plan,
                      patch_size, channels, yo, d, normalize, var_constant,
                      max_val, alpha):
    """One grid step: ``LANES`` images, image i in lane i of every row.

    img_ref (1, X, C, Y·L): the images, lanes (y, image); f_ref (k, d_pad):
    the filters; mn_ref (d_pad, 1): the whitener's means; o_ref (L,
    2·pools·k): the pooled features, ImageVectorizer's order. Scratch:
    s_ref (d_pad, yo·L), the patch matrix of one row of windows, rows in
    ``im2col``'s (px, py, c) order and lanes (y, image) — each row is a
    window of an image row shifted by py lane tiles, so the im2col is
    aligned lane slices; acc_ref (2·pools, k, L), the pools' sums."""
    L = LANES
    k = f_ref.shape[0]
    npy = len(plan.y_covers)
    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)
    s_ref[d:, :] = jnp.zeros((s_ref.shape[0] - d, s_ref.shape[1]), jnp.float32)

    def windows_row(x):
        for dx in range(patch_size):
            for dy in range(patch_size):
                r = (dx * patch_size + dy) * channels
                s_ref[r:r + channels, :] = img_ref[0, x + dx, :, dy * L:(dy + yo) * L]
        p = s_ref[...]
        if normalize:  # normalize_patch_rows, over the d real rows of each column
            row = jax.lax.broadcasted_iota(jnp.int32, p.shape, 0)
            mean = jnp.sum(p, axis=0, keepdims=True) / d
            centered = jnp.where(row < d, p - mean, 0.0)
            var = jnp.sum(centered * centered, axis=0, keepdims=True) / (d - 1.0)
            p = centered / jnp.sqrt(var + var_constant)
        return p - mn_ref[...]

    # the rows of windows each pool along x takes
    spans = [(plan.xs[cov[0]][0], plan.xs[cov[-1]][1]) for cov in plan.x_covers]
    tile = plan.filter_tile

    def one_row(x, carry):
        p = windows_row(x)
        inside = [(x >= lo) & (x < hi) for lo, hi in spans]

        def filter_tile(i, carry):
            k0 = pl.multiple_of(i * tile, 8)
            y = jax.lax.dot_general(
                f_ref[pl.ds(k0, tile), :], p,
                dimension_numbers=(((1,), (0,)), ((), ())),
                **_dot_kwargs(jnp.float32),
            )
            for yi, (y0, y1) in enumerate(plan.ys):
                pos = neg = None
                for j in range(y0, y1):
                    t = y[:, j * L:(j + 1) * L]
                    pj = jnp.maximum(max_val, t - alpha)
                    nj = jnp.maximum(max_val, -alpha - t)
                    pos = pj if pos is None else pos + pj
                    neg = nj if neg is None else neg + nj
                for qx, here in enumerate(inside):
                    pools = [qx * npy + qy for qy, cov in enumerate(plan.y_covers)
                             if yi in cov]

                    @pl.when(here)
                    def _(pools=pools, pos=pos, neg=neg):
                        for q in pools:
                            acc_ref[2 * q, pl.ds(k0, tile), :] += pos
                            acc_ref[2 * q + 1, pl.ds(k0, tile), :] += neg
            return carry

        return jax.lax.fori_loop(0, k // tile, filter_tile, carry)

    jax.lax.fori_loop(plan.xs[0][0], plan.xs[-1][1], one_row, 0)  # the pooled rows

    for j in range(acc_ref.shape[0]):
        o_ref[:, j * k:(j + 1) * k] = acc_ref[j].T


def conv_pool_features(
    images,
    filters,
    means=None,
    *,
    patch_size: int,
    stride: int,
    pool_size: int,
    normalize_patches: bool = True,
    var_constant: float = 10.0,
    max_val: float = 0.0,
    alpha: float = 0.0,
    interpret: Optional[bool] = None,
):
    """``Convolver`` → ``SymmetricRectifier(max_val, alpha)`` →
    ``Pooler(stride, pool_size, pool_function="sum")`` → ``ImageVectorizer``
    in one kernel.

    images: (n, X, Y, C), filters: (k, p²·c) packed rows, means: optional
    (p²·c,) whitening means. Returns (n, npx·npy·2k) float32 in
    ImageVectorizer's order: feature ``(px·npy + py)·2k + c``, channels
    ``[max(max_val, x − α) for the k filters, max(max_val, −x − α) for the
    k filters]``. XLA moves the images to one lane each (a transpose of the
    images alone); each grid step takes ``LANES`` images and, a row of
    windows at a time, lays out their patches, normalises them, subtracts
    the means, multiplies by the filters at float32 ``HIGHEST`` a filter
    tile at a time, rectifies both ways and adds each window into its
    pools — neither the patch matrix nor the conv map leaves VMEM.
    """
    images = jnp.asarray(images, dtype=jnp.float32)
    filters = jnp.asarray(filters, dtype=jnp.float32)
    n, X, Y, C = images.shape
    k, d = filters.shape
    plan = conv_pool_plan((X, Y, C), k, patch_size, stride, pool_size)
    if plan is None:
        raise ValueError(f"no kernel plan for {(X, Y, C)} images, {k} filters, "
                         f"pools of {pool_size} every {stride}")
    L = LANES
    steps = -(-n // L)
    # (n, X, Y, C) -> (steps, X, C, Y·L): image i of a step in lane i
    lanes = jnp.pad(images, ((0, steps * L - n), (0, 0), (0, 0), (0, 0)))
    lanes = lanes.reshape(steps, L, X, Y, C).transpose(0, 2, 4, 3, 1)
    lanes = lanes.reshape(steps, X, C, Y * L)
    f = jnp.pad(filters, ((0, 0), (0, plan.d_pad - d)))
    mn = jnp.zeros((plan.d_pad, 1), jnp.float32)
    if means is not None:
        mn = mn.at[:d, 0].set(jnp.asarray(means, jnp.float32).reshape(d))
    width = 2 * plan.pools * k
    return _pallas_call(
        "conv_pool",
        functools.partial(
            _conv_pool_kernel, plan=plan, patch_size=patch_size, channels=C,
            yo=Y - patch_size + 1, d=d, normalize=bool(normalize_patches),
            var_constant=float(var_constant), max_val=float(max_val),
            alpha=float(alpha)),
        grid=(steps,),
        in_specs=[
            pl.BlockSpec((1, X, C, Y * L), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((k, plan.d_pad), lambda i: (0, 0)),
            pl.BlockSpec((plan.d_pad, 1), lambda i: (0, 0)),
        ],
        # the last step's images past n are zeros, and its rows past n are not written
        out_specs=pl.BlockSpec((L, width), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, width), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((plan.d_pad, (Y - patch_size + 1) * L), jnp.float32),
            pltpu.VMEM((2 * plan.pools, k, L), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(lanes, f, mn)
