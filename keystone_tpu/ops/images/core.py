"""Image plumbing nodes: scalers, croppers, patchers, vectorizer
(reference: nodes/images/{GrayScaler,PixelScaler,Cropper,ImageVectorizer,
RandomImageTransformer,CenterCornerPatcher,RandomPatcher,
LabeledImageExtractors}.scala)."""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from keystone_tpu.data import Dataset
from keystone_tpu.utils import images as image_utils
from keystone_tpu.workflow import Transformer


@dataclass
class LabeledImage:
    """An image with an integer label and optional filename
    (reference: utils/images/LabeledImage in ImageUtils.scala)."""

    image: Any
    label: int
    filename: str = ""


class ImageExtractor(Transformer):
    """LabeledImage -> image (reference: nodes/images/LabeledImageExtractors.scala)."""

    def apply(self, x: LabeledImage):
        return x.image


class LabelExtractor(Transformer):
    """LabeledImage -> label (reference: nodes/images/LabeledImageExtractors.scala)."""

    def apply(self, x: LabeledImage):
        return x.label


class GrayScaler(Transformer):
    """RGB -> luminance (reference: nodes/images/GrayScaler.scala)."""

    def device_operands(self):
        return (), ()

    @staticmethod
    def device_apply(static_key, params, X):
        return image_utils.to_grayscale(X)


class PixelScaler(Transformer):
    """Rescale byte pixels to [0, 1) (reference: nodes/images/PixelScaler.scala)."""

    def device_operands(self):
        return (), ()

    @staticmethod
    def device_apply(static_key, params, X):
        return jnp.asarray(X, jnp.float32) / 255.0


class Cropper(Transformer):
    """Fixed-window crop (reference: nodes/images/Cropper.scala)."""

    def __init__(self, start_x: int, start_y: int, end_x: int, end_y: int):
        self.start_x, self.start_y = start_x, start_y
        self.end_x, self.end_y = end_x, end_y

    def apply(self, img):
        return image_utils.crop(img, self.start_x, self.start_y, self.end_x, self.end_y)

    def batch_apply(self, data: Dataset) -> Dataset:
        if data.is_host:
            return data.map(self.apply)
        return data.map_batch(
            lambda X: X[:, self.start_x : self.end_x, self.start_y : self.end_y, :]
        )


class ImageVectorizer(Transformer):
    """Flatten an image to a vector, row-major over (x, y, c)
    (reference: nodes/images/ImageVectorizer.scala)."""

    def device_operands(self):
        return (), ()

    @staticmethod
    def device_apply(static_key, params, X):
        return X.reshape(X.shape[0], -1)


class RandomImageTransformer(Transformer):
    """Apply a transform to each image with probability `chance`
    (reference: nodes/images/RandomImageTransformer.scala). The default
    transform is a horizontal flip; randomness is seeded explicitly."""

    def __init__(self, chance: float = 0.5, transform: Callable = None, seed: int = 0):
        self.chance = chance
        self.transform = transform or image_utils.flip_horizontal
        self._rng = np.random.default_rng(seed)

    def apply(self, img):
        if self._rng.random() < self.chance:
            return self.transform(img)
        return jnp.asarray(img)

    def batch_apply(self, data: Dataset) -> Dataset:
        X = jnp.asarray(data.array, jnp.float32)
        mask = jnp.asarray(self._rng.random(X.shape[0]) < self.chance)
        transformed = jax.vmap(self.transform)(X)
        out = jnp.where(mask[:, None, None, None], transformed, X)
        return Dataset(out, n=data.n, mesh=data.mesh)


class CenterCornerPatcher(Transformer):
    """Four corner patches + the center patch (optionally with horizontal
    flips): n images -> n·5 (or n·10) patches
    (reference: nodes/images/CenterCornerPatcher.scala:18-50)."""

    def __init__(self, patch_size_x: int, patch_size_y: int, horizontal_flips: bool = False):
        self.patch_size_x = patch_size_x
        self.patch_size_y = patch_size_y
        self.horizontal_flips = horizontal_flips

    def _patches(self, images):
        n, X, Y, C = images.shape
        px, py = self.patch_size_x, self.patch_size_y
        start_xs = [0, X - px, 0, X - px, (X - px) // 2]
        start_ys = [0, 0, Y - py, Y - py, (Y - py) // 2]
        out = []
        for sx, sy in zip(start_xs, start_ys):
            patch = images[:, sx : sx + px, sy : sy + py, :]
            out.append(patch)
            if self.horizontal_flips:
                out.append(patch[:, :, ::-1, :])
        stacked = jnp.stack(out, axis=1)  # (n, patches_per_image, px, py, C)
        return stacked

    def apply(self, img):
        img = jnp.asarray(img)
        return self._patches(img[None])[0]

    def batch_apply(self, data: Dataset) -> Dataset:
        X = jnp.asarray(data.array, jnp.float32)[: data.n]
        out = self._patches(X)
        return Dataset(out.reshape((-1,) + out.shape[2:]))

    @property
    def patches_per_image(self) -> int:
        return 10 if self.horizontal_flips else 5


def patch_positions(key, shape, X: int, Y: int, px: int, py: int):
    """Top-left corners of uniformly placed ``px × py`` patches in an
    ``X × Y`` image, drawn from ``key``: ``(sx, sy)``, each of ``shape``.
    THE draw contract (a reference restates it): ``kx, ky =
    jax.random.split(key)``, ``sx = randint(kx, shape, 0, X - px + 1)``,
    ``sy = randint(ky, shape, 0, Y - py + 1)``."""
    kx, ky = jax.random.split(key)
    return (jax.random.randint(kx, shape, 0, X - px + 1),
            jax.random.randint(ky, shape, 0, Y - py + 1))


def gather_patches(images, img, sx, sy, px: int, py: int):
    """``images[img, sx:sx+px, sy:sy+py, :]`` for every entry of the index
    arrays (all of one shape S): an ``S + (px, py, C)`` array. Only the
    patches asked for are read, element by element from each image's
    flattened pixels (the same gather as a 4-d index compiled for a v5e in
    1.4 s where that took 257 s once a reduction read its output)."""
    n, X, Y, C = images.shape
    rx = sx[..., None, None] + jnp.arange(px)[:, None]  # S + (px, 1)
    ry = sy[..., None, None] + jnp.arange(py)[None, :]  # S + (1, py)
    at = (rx * Y + ry)[..., None] * C + jnp.arange(C)  # S + (px, py, C)
    return jnp.reshape(images, (n, X * Y * C))[img[..., None, None, None], at]


class RandomPatcher(Transformer):
    """Uniformly random patches: n images -> n·num_patches patches
    (reference: nodes/images/RandomPatcher.scala:16-47). Positions come
    from ``jax.random.key(seed)`` (:func:`patch_positions`), on the device."""

    def __init__(self, num_patches: int, patch_size_x: int, patch_size_y: int, seed: int = 12334):
        self.num_patches = num_patches
        self.patch_size_x = patch_size_x
        self.patch_size_y = patch_size_y
        self.seed = seed

    def _patches(self, images):
        n, X, Y, C = images.shape
        px, py = self.patch_size_x, self.patch_size_y
        sx, sy = patch_positions(jax.random.key(self.seed), (n, self.num_patches),
                                 X, Y, px, py)
        img = jnp.broadcast_to(jnp.arange(n)[:, None], sx.shape)
        return gather_patches(images, img, sx, sy, px, py)  # (n, k, px, py, C)

    def apply(self, img):
        img = jnp.asarray(img)
        return self._patches(img[None])[0]

    def batch_apply(self, data: Dataset) -> Dataset:
        X = jnp.asarray(data.array, jnp.float32)[: data.n]
        out = self._patches(X)
        return Dataset(out.reshape((-1,) + out.shape[2:]))
