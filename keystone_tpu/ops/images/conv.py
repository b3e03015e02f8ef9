"""Convolution-family image nodes: Convolver, Pooler, Windower,
SymmetricRectifier.

The reference implements convolution as hand-rolled im2col into a reused
patch-matrix buffer followed by one BLAS-3 GEMM per image (reference:
nodes/images/Convolver.scala:128-220). Here the whole batch is one XLA
program: patch extraction (``lax.conv_general_dilated_patches``), per-patch
normalization, whitening-mean subtraction and the filter GEMM all fuse into a
single MXU-friendly computation over ``(n, x, y, c)`` arrays.

Layout note: the reference flattens patches/filters channel-fastest with its
second spatial axis slowest (Convolver.scala:152-190). We flatten row-major
over ``(x, y, c)`` — self-consistent between ``pack_filters`` and the patch
extractor, and the natural order for XLA.

In a fused chain a ``Convolver`` followed by a ``SymmetricRectifier``, a
sum ``Pooler`` and optionally an ``ImageVectorizer`` runs as ONE Pallas
kernel instead (:class:`PooledConvolution`, ``ops/pallas_images.py``): the
conv map and the normalised patch matrix never leave VMEM. The fused
program learns it from :meth:`Convolver.device_absorb`
(``workflow/fusion.absorbed``); every other chain keeps the XLA program.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from keystone_tpu.data import Dataset
from keystone_tpu.utils import images as image_utils
from keystone_tpu.workflow import Transformer


def _as_batch(x) -> tuple:
    """Return (batch array (n, X, Y, C), was_single)."""
    x = jnp.asarray(x)
    if x.ndim == 3:
        return x[None], True
    return x, False


def im2col(images, patch_size: int):
    """(n, X, Y, C) -> (n, X', Y', patch_size²·C) patches, flattened row-major
    over (px, py, c)."""
    n, X, Y, C = images.shape
    patches = lax.conv_general_dilated_patches(
        images,
        filter_shape=(patch_size, patch_size),
        window_strides=(1, 1),
        padding="VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )
    # Feature order from XLA is channel-slowest: (c, px, py). Reorder.
    xo, yo = X - patch_size + 1, Y - patch_size + 1
    patches = patches.reshape(n, xo, yo, C, patch_size, patch_size)
    patches = jnp.transpose(patches, (0, 1, 2, 4, 5, 3))
    return patches.reshape(n, xo, yo, patch_size * patch_size * C)


def normalize_patch_rows(patches, var_constant: float):
    """Per-patch mean/variance normalization, matching the reference's
    Stats.normalizeRows (utils/Stats.scala:112-123): subtract the mean, divide
    by sqrt(var + alpha) with the (d-1) variance denominator."""
    d = patches.shape[-1]
    mean = jnp.mean(patches, axis=-1, keepdims=True)
    centered = patches - mean
    var = jnp.sum(centered * centered, axis=-1, keepdims=True) / (d - 1.0)
    return centered / jnp.sqrt(var + var_constant)


def conv_form(members) -> str:
    """How a fused program runs the convolution among ``members`` (a chain
    in order): ``"pallas_pool"`` where it takes its successors into the
    Pallas kernel (:meth:`Convolver.device_absorb`, through
    ``workflow/fusion.absorbed``), else ``"xla"``."""
    from keystone_tpu.workflow.fusion import absorbed

    runs = absorbed(list(members))
    return "pallas_pool" if any(isinstance(m, PooledConvolution) for m in runs) else "xla"


# What a fused featurize program that starts with a convolution may hold
# for its batch of images at once: the conv map (out_x · out_y · K float32
# an image) and a two-sided rectified copy of it (twice that), three maps
# an image. For CIFAR-10 at K = 1,600 a map is 27 · 27 · 1,600 · 4 B =
# 4.67 MB, so 2 GiB takes 152 images where the whole 50,000 would need
# 233 GB for the map alone.
CONV_BATCH_BYTES = 2 << 30


class Convolver(Transformer):
    """Convolve images with a filter bank via im2col + one GEMM
    (reference: nodes/images/Convolver.scala:20-221).

    ``filters`` is ``(num_filters, patch_size²·channels)``, already whitened
    if a whitener is supplied (see :meth:`build`). Output image is
    ``(X-p+1, Y-p+1, num_filters)``.

    In a fused chain (``workflow/fusion.py``) the convolution names the
    program's scope, ``ks.conv_featurize``, asks it to take
    :meth:`device_row_batch` images at a time, and counts the images the
    program takes on the counter track ``conv.images_featurized``. Where
    the members after it are a rectifier and sum pools the kernel takes,
    it offers to run them with it (:meth:`device_absorb`).
    """

    device_scope = "ks.conv_featurize"
    rows_counter = "conv.images_featurized"

    def __init__(
        self,
        filters,
        img_x: int,
        img_y: int,
        img_channels: int,
        whitener=None,
        normalize_patches: bool = True,
        var_constant: float = 10.0,
    ):
        self.filters = jnp.asarray(filters, dtype=jnp.float32)
        self.img_x = img_x
        self.img_y = img_y
        self.img_channels = img_channels
        self.whitener = whitener
        self.normalize_patches = normalize_patches
        self.var_constant = var_constant
        self.patch_size = int(round((self.filters.shape[1] / img_channels) ** 0.5))

    def device_row_batch(self) -> Optional[int]:
        """Images a fused program featurizes at a time: three conv maps an
        image within :data:`CONV_BATCH_BYTES`, a multiple of 8; None where
        the image size is not known (:meth:`build`)."""
        if self.img_x <= 0 or self.img_y <= 0:
            return None
        out = (self.img_x - self.patch_size + 1) * (self.img_y - self.patch_size + 1)
        per_image = 3 * out * self.filters.shape[0] * 4
        return max(8, CONV_BATCH_BYTES // per_image // 8 * 8)

    def device_absorb(self, successors) -> Optional[tuple]:
        """What a fused program may run with this convolution as one Pallas
        kernel: ``(how many of the members after it, the member that runs
        them all)`` (:class:`PooledConvolution`), or None — the XLA
        program. See :meth:`PooledConvolution.absorbing`."""
        return PooledConvolution.absorbing(self, successors)

    @staticmethod
    def pack_filters(filter_images) -> jnp.ndarray:
        """(k, p, p, c) filter images -> (k, p·p·c) rows, row-major (x, y, c)
        (reference: Convolver.packFilters, Convolver.scala:99-125)."""
        f = jnp.asarray(filter_images, dtype=jnp.float32)
        return f.reshape(f.shape[0], -1)

    @classmethod
    def build(
        cls,
        filter_images,
        whitener=None,
        normalize_patches: bool = True,
        var_constant: float = 10.0,
        flip_filters: bool = False,
    ) -> "Convolver":
        """User-facing factory: takes unwhitened filter images ``(k, p, p, c)``
        and folds the whitening into the filter matrix
        (reference: Convolver.apply, Convolver.scala:60-89)."""
        f = jnp.asarray(filter_images, dtype=jnp.float32)
        if flip_filters:
            # MATLAB convnd parity: full x/y/channel reversal
            # (Convolver.scala:67-70 via ImageUtils.flipImage).
            f = jax.vmap(image_utils.flip_image)(f)
        packed = cls.pack_filters(f)
        if whitener is not None:
            packed = whitener.apply(packed) @ whitener.whitener.T
        k, p = f.shape[0], f.shape[1]
        c = f.shape[3]
        # img dims are supplied at apply time from the data; record patch shape.
        conv = cls(
            packed,
            img_x=-1,
            img_y=-1,
            img_channels=c,
            whitener=whitener,
            normalize_patches=normalize_patches,
            var_constant=var_constant,
        )
        conv.patch_size = p
        return conv

    # The convolution computes in float32 BY DESIGN (filters are cast at
    # construction, the einsum pins preferred_element_type): float64
    # image input narrowing to f32 here is the declared compute dtype,
    # not silent drift — tell the plan verifier so (workflow/verify.py).
    declares_dtype_change = True

    def apply(self, img):
        batch, single = _as_batch(img)
        out = self.device_fn()(batch)
        return out[0] if single else out

    def device_operands(self):
        means = None if self.whitener is None else self.whitener.means
        key = (self.patch_size, bool(self.normalize_patches),
               float(self.var_constant))
        return key, (self.filters, means)

    @staticmethod
    def device_apply(static_key, params, X):
        patch_size, normalize_patches, var_constant = static_key
        filters, means = params
        # Narrow float64 loader output HERE, before any arithmetic: the
        # einsum's preferred_element_type alone would otherwise leave the
        # patch normalization running in f64.
        images = jnp.asarray(X, jnp.float32)
        # A convolution alone takes this XLA path; followed by a rectifier
        # and sum pools in a fused chain it runs as PooledConvolution's
        # kernel instead.
        patches = im2col(images, patch_size)
        if normalize_patches:
            patches = normalize_patch_rows(patches, var_constant)
        if means is not None:
            patches = patches - means
        return jnp.einsum(
            "nxyd,kd->nxyk", patches, filters,
            preferred_element_type=jnp.float32,
        )


class Pooler(Transformer):
    """Strided spatial pooling with a pixel function applied first
    (reference: nodes/images/Pooler.scala:21-69).

    Pool k covers ``[k·stride, k·stride + pool_size)`` in each spatial axis
    (the reference's strideStart = poolSize/2 with windows centered there),
    truncated at the image edge. ``pool_function`` is "sum" or "max".
    """

    def __init__(
        self,
        stride: int,
        pool_size: int,
        pixel_function: Optional[Callable] = None,
        pool_function: Union[str, Callable] = "sum",
    ):
        self.stride = stride
        self.pool_size = pool_size
        self.pixel_function = pixel_function
        if callable(pool_function):
            raise TypeError('pool_function must be "sum" or "max" (XLA reduce_window)')
        if pool_function not in ("sum", "max"):
            raise ValueError(f"unknown pool_function {pool_function}")
        self.pool_function = pool_function

    def apply(self, img):
        batch, single = _as_batch(img)
        out = self.device_fn()(batch)
        return out[0] if single else out

    def device_operands(self):
        # The pixel function rides in the key by identity: a new lambda
        # per instance is a new key (and one bounded entry of the
        # kept-program table), a shared module-level function is one key.
        return (
            (self.stride, self.pool_size, self.pixel_function,
             self.pool_function),
            (),
        )

    @staticmethod
    def device_apply(static_key, params, X):
        stride, pool_size, pixel_function, pool_function = static_key
        images = jnp.asarray(X, jnp.float32)
        _, nx, ny, _ = images.shape
        if pixel_function is not None:
            images = pixel_function(images)
        start = pool_size // 2
        npx = -(-(nx - start) // stride)  # ceil
        npy = -(-(ny - start) // stride)
        ext_x = (npx - 1) * stride + pool_size
        ext_y = (npy - 1) * stride + pool_size
        pad_val = -jnp.inf if pool_function == "max" else 0.0
        images = jnp.pad(
            images,
            ((0, 0), (0, max(0, ext_x - nx)), (0, max(0, ext_y - ny)), (0, 0)),
            constant_values=pad_val,
        )
        images = images[:, :ext_x, :ext_y, :]
        init, op = (
            (-jnp.inf, lax.max) if pool_function == "max" else (0.0, lax.add)
        )
        return lax.reduce_window(
            images,
            jnp.asarray(init, images.dtype),
            op,
            window_dimensions=(1, pool_size, pool_size, 1),
            window_strides=(1, stride, stride, 1),
            padding="VALID",
        )


class Windower(Transformer):
    """Extract all stride-strided windows as separate images
    (reference: nodes/images/Windower.scala:13-56). A batch of n images
    becomes a batch of n·numWindows window images (RDD flatMap analog)."""

    def __init__(self, stride: int, window_size: int):
        self.stride = stride
        self.window_size = window_size

    def _windows(self, images):
        n, X, Y, C = images.shape
        w = self.window_size
        xs = np.arange(0, X - w + 1, self.stride)
        ys = np.arange(0, Y - w + 1, self.stride)
        rows = xs[:, None] + np.arange(w)[None, :]  # (nx, w)
        cols = ys[:, None] + np.arange(w)[None, :]  # (ny, w)
        out = images[:, rows, :, :]  # (n, nx, w, Y, C)
        out = out[:, :, :, cols, :]  # (n, nx, w, ny, w, C)
        out = jnp.transpose(out, (0, 1, 3, 2, 4, 5))  # (n, nx, ny, w, w, C)
        return out.reshape(n, len(xs) * len(ys), w, w, C)

    def apply(self, img):
        batch, single = _as_batch(img)
        out = self._windows(batch)
        return out[0] if single else out.reshape((-1,) + out.shape[2:])

    def batch_apply(self, data: Dataset) -> Dataset:
        out = self._windows(jnp.asarray(data.array, jnp.float32)[: data.n])
        return Dataset(out.reshape((-1,) + out.shape[2:]))


class SymmetricRectifier(Transformer):
    """Two-sided ReLU doubling the channel count: channels c and c+C hold
    max(maxVal, x−α) and max(maxVal, −x−α)
    (reference: nodes/images/SymmetricRectifier.scala:7-32)."""

    def __init__(self, max_val: float = 0.0, alpha: float = 0.0):
        self.max_val = max_val
        self.alpha = alpha

    def device_operands(self):
        return (float(self.max_val), float(self.alpha)), ()

    @staticmethod
    def device_apply(static_key, params, X):
        max_val, alpha = static_key
        pos = jnp.maximum(max_val, X - alpha)
        neg = jnp.maximum(max_val, -X - alpha)
        return jnp.concatenate([pos, neg], axis=-1)


class PooledConvolution(Transformer):
    """``Convolver`` → ``SymmetricRectifier`` → ``Pooler`` (sum, no pixel
    function) → optionally ``ImageVectorizer``, run by ONE Pallas kernel
    per image batch (``pallas_images.conv_pool_features``): the windows,
    their normalisation and whitening means, the filter product at float32
    ``HIGHEST``, the two-sided rectifier and the sum pools, in VMEM. Made
    by a fused program from the members it replaces
    (:meth:`Convolver.device_absorb`), never by hand; its output is theirs
    to float associativity.

    It keeps the convolution's scope, ``ks.conv_featurize``, and counts the
    images its kernel takes on ``conv.images_pooled_in_kernel``. Over a
    mesh each device runs the kernel on its own rows (``shard_map`` over
    the ``data`` axis). It is made only for images the kernel has a plan
    for (:meth:`absorbing`); other images raise.
    """

    device_scope = Convolver.device_scope
    rows_counter = "conv.images_pooled_in_kernel"

    def __init__(self, conv: Convolver, rectifier: SymmetricRectifier,
                 pooler: Pooler, vectorize: bool):
        self.conv = conv
        self.rectifier = rectifier
        self.pooler = pooler
        self.vectorize = vectorize

    @classmethod
    def absorbing(cls, conv: Convolver, successors) -> Optional[tuple]:
        """``(members taken, the member)`` where the members after ``conv``
        are, in order, a ``SymmetricRectifier``, a ``Pooler`` with
        ``pool_function="sum"`` and no pixel function, and optionally an
        ``ImageVectorizer``; the filters are float32; the Pallas kernels
        are on (``pallas_ops.pallas_enabled``: the TPU); and the kernel has
        a plan for the images the convolution declares. Else None."""
        from keystone_tpu.ops import pallas_ops
        from keystone_tpu.ops.images.core import ImageVectorizer
        from keystone_tpu.ops.pallas_images import conv_pool_plan

        after = list(successors[:3])
        if len(after) < 2 or type(after[0]) is not SymmetricRectifier:
            return None
        pooler = after[1]
        if (type(pooler) is not Pooler or pooler.pool_function != "sum"
                or pooler.pixel_function is not None):
            return None
        if conv.filters.dtype != jnp.float32 or not pallas_ops.pallas_enabled():
            return None
        plan = conv_pool_plan((conv.img_x, conv.img_y, conv.img_channels),
                              conv.filters.shape[0], conv.patch_size,
                              pooler.stride, pooler.pool_size)
        if plan is None:
            return None
        vectorize = len(after) > 2 and type(after[2]) is ImageVectorizer
        return 2 + vectorize, cls(conv, after[0], pooler, vectorize)

    def apply(self, img):
        batch, single = _as_batch(img)
        out = self.device_fn()(batch)
        return out[0] if single else out

    def device_operands(self):
        conv_key, params = self.conv.device_operands()
        rect_key, _ = self.rectifier.device_operands()
        return (conv_key, rect_key, (self.pooler.stride, self.pooler.pool_size),
                self.vectorize), params

    @staticmethod
    def device_apply(static_key, params, X):
        from jax.sharding import PartitionSpec as P

        from keystone_tpu.ops.pallas_images import axis_pools, conv_pool_features
        from keystone_tpu.parallel import mesh as mesh_lib

        conv_key, (max_val, alpha), (stride, pool_size), vectorize = static_key
        patch_size, normalize_patches, var_constant = conv_key
        filters, means = params
        images = jnp.asarray(X, jnp.float32)
        n, side_x, side_y, _ = images.shape
        args = (images, filters) if means is None else (images, filters, means)
        features = functools.partial(
            conv_pool_features, patch_size=patch_size, stride=stride,
            pool_size=pool_size, normalize_patches=normalize_patches,
            var_constant=var_constant, max_val=max_val, alpha=alpha)
        # a Pallas call is not partitioned: over a mesh each device runs it
        # on its own rows
        mesh = jax.typeof(images).sharding.mesh
        rows = mesh_lib.axis_size(mesh, mesh_lib.DATA_AXIS)
        if rows > 1 and n % rows == 0:
            features = mesh_lib.shard_map(
                features, mesh,
                in_specs=(P(mesh_lib.DATA_AXIS),) + (P(),) * (len(args) - 1),
                out_specs=P(mesh_lib.DATA_AXIS), check_vma=False)
        out = features(*args)  # raises where the kernel has no plan for these images
        if vectorize:
            return out
        npx = len(axis_pools(side_x - patch_size + 1, stride, pool_size)[1])
        npy = len(axis_pools(side_y - patch_size + 1, stride, pool_size)[1])
        return out.reshape(n, npx, npy, -1)
