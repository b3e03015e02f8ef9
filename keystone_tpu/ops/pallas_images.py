"""Pallas TPU kernel for the image-geometry pipeline's hot path — NOT
DISPATCHED: Mosaic refuses it, so ``Convolver`` runs the XLA path.

On a TPU v5e (jax 0.9.0 / libtpu 0.0.34, PR 21's chip run) at CIFAR
geometry (32x32x3, 6x6 patches, 256 filters) the compiler answers
``RESOURCE_EXHAUSTED: ... Scoped allocation with size 88.62M and limit
16.00M exceeded scoped vmem limit by 72.62M``; with
``vmem_limit_bytes`` raised to 100 MB (128 filters, two images) the
compilation had not finished after 470 s. The likely cause is the layout,
not a tile size: with channels last a (27, 27, 3) window puts 3 values in each
128-lane register row, every one of the 36 window slices, the
``(27, 27, 3) -> (729, 3)`` reshape and the 36-way lane concatenation is
a relayout of ~97% padding. A version that can compile needs lane-dense
patches (channels folded into the row axis before the kernel, or a
strip-mined im2col over W·C) — a rewrite, ROADMAP S7. The kernel and its
interpreter-equality tests stay as the reference for that rewrite.

The reference's image featurizer is im2col into a reused patch-matrix
buffer followed by one BLAS-3 GEMM per image (nodes/images/
Convolver.scala:128-220). The XLA path here (`ops/images/conv.py`)
already fuses the batch into one program, but it still materializes the
full patch tensor ``(n, x', y', p²·c)`` in HBM between the patch
extraction and the filter GEMM — for CIFAR geometry (32×32×3, 6×6
patches) that intermediate is 12× the size of the images themselves, so
the node is HBM-traffic-bound long before the MXU saturates.

The kernel below processes ONE IMAGE PER GRID STEP with the whole
featurization fused in VMEM:

    grid = (n,)
    img (1, X, Y, C) block  ->  in-kernel im2col (static (dx, dy) slices)
                            ->  per-patch mean/variance normalization
                            ->  whitening-mean subtraction
                            ->  (P − μ) @ Fᵀ on the MXU
    out (1, x', y', K) block

so the patch matrix lives only as a (x'·y', p²·c) VMEM tile and the HBM
traffic drops to images-in + features-out. Column order inside a patch
row is row-major over ``(px, py, c)`` — the same contract as
``conv.im2col`` / ``Convolver.pack_filters``, pinned by the
interpreter-equality test against the XLA path.

Numerics: everything is float32 with ``preferred_element_type=float32``
and ``precision=HIGHEST`` on the dot (the same recipe as `pallas_ops`);
the normalization uses the reference's (d−1) variance denominator. The
fused path matches the XLA path to float-associativity tolerance (the
mean/variance reductions associate differently), pinned at 1e-5 relative
in tests/test_pallas_images.py.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from keystone_tpu.ops.pallas_ops import _dot_kwargs, _pallas_call

__all__ = [
    "conv_featurize",
    "conv_featurize_flops",
]


def _conv_featurize_kernel(
    img_ref, ft_ref, mn_ref, out_ref, *,
    patch_size, xo, yo, channels, normalize, var_constant,
):
    img = img_ref[0]  # (X, Y, C)
    cols = []
    # Static-slice im2col: dx-outer / dy-inner with the channel axis kept
    # intact reproduces row-major (px, py, c) patch columns exactly.
    for dx in range(patch_size):
        for dy in range(patch_size):
            window = img[dx:dx + xo, dy:dy + yo, :]
            cols.append(window.reshape(xo * yo, channels))
    patches = jnp.concatenate(cols, axis=1)  # (xo·yo, p²·c)
    d = patch_size * patch_size * channels
    if normalize:
        mean = jnp.mean(patches, axis=-1, keepdims=True)
        centered = patches - mean
        var = jnp.sum(centered * centered, axis=-1, keepdims=True) / (d - 1.0)
        patches = centered / jnp.sqrt(var + var_constant)
    patches = patches - mn_ref[0]
    feats = jax.lax.dot_general(
        patches, ft_ref[:],
        dimension_numbers=(((1,), (0,)), ((), ())),
        **_dot_kwargs(jnp.float32),
    )
    out_ref[0] = feats.reshape(xo, yo, ft_ref.shape[1])


def conv_featurize_flops(n: int, xo: int, yo: int, d: int, k: int) -> float:
    """Executed-FLOP model for the fused featurizer: the filter GEMM's
    2·n·x'·y'·d·k dominates (normalization is O(n·x'·y'·d) — <1% beside a
    k≥128 filter bank and excluded, the same convention as the roofline
    rows in bench.py)."""
    return 2.0 * n * xo * yo * d * k


def conv_featurize(
    images,
    filters,
    means=None,
    *,
    patch_size: int,
    normalize_patches: bool = True,
    var_constant: float = 10.0,
    interpret: Optional[bool] = None,
):
    """Fused im2col + normalize + whiten-center + filter GEMM.

    images: (n, X, Y, C) float32, filters: (k, p²·c) packed rows (the
    `Convolver.pack_filters` layout), means: optional (p²·c,) whitening
    means. Returns (n, X−p+1, Y−p+1, k) float32 — bit-for-bit the same
    contract as ``Convolver._convolve``'s XLA path, to the stated
    associativity tolerance.
    """
    images = jnp.asarray(images, dtype=jnp.float32)
    filters = jnp.asarray(filters, dtype=jnp.float32)
    n, X, Y, C = images.shape
    k, d = filters.shape
    xo, yo = X - patch_size + 1, Y - patch_size + 1
    ft = filters.T  # (d, k): contraction layout for the in-kernel dot
    if means is None:
        mn = jnp.zeros((1, d), dtype=jnp.float32)
    else:
        mn = jnp.asarray(means, dtype=jnp.float32).reshape(1, d)

    return _pallas_call(
        "conv_featurize",
        functools.partial(
            _conv_featurize_kernel,
            patch_size=patch_size,
            xo=xo,
            yo=yo,
            channels=C,
            normalize=bool(normalize_patches),
            var_constant=float(var_constant),
        ),
        grid=(n,),
        in_specs=[
            pl.BlockSpec((1, X, Y, C), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((d, k), lambda i: (0, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, xo, yo, k), lambda i: (i, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, xo, yo, k), jnp.float32),
        interpret=interpret,
    )(images, ft, mn)
