"""Plumbing nodes (reference: nodes/util/ — Cacher, VectorSplitter, label
indicators, classifiers, combiners, type casts).

Dense-array nodes are implemented as whole-batch jnp ops so XLA fuses them;
sparse-feature nodes live in :mod:`keystone_tpu.ops.nlp_sparse`.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from keystone_tpu.data import Dataset
from keystone_tpu.workflow import Transformer


class FunctionNode:
    """A dataset-level function outside graph tracking
    (reference: pipelines/FunctionNode.scala:3)."""

    def apply(self, data):
        raise NotImplementedError

    def __call__(self, data):
        return self.apply(data)


@dataclass(frozen=True)
class Cacher(Transformer):
    """Materialize-and-hold passthrough (reference: nodes/util/Cacher.scala:15-25).

    On TPU this pins the dataset's buffers on device and marks the node's
    prefix as saveable so the optimizer can reuse the result across pipeline
    applications (the analog of RDD ``.cache()``).
    """

    name: Optional[str] = None

    # Verifier contract (workflow/verify.py): a cache marker is a
    # signature passthrough, and its PLACEMENT is checked — a cut that
    # severs an edge the fusion rules would compile into one program is
    # reported as `cache-splits-fusion`.
    is_cache = True

    def apply(self, x):
        return x

    def batch_apply(self, data: Dataset) -> Dataset:
        return data.cache()

    def output_signature(self, sig):
        return sig


@dataclass(frozen=True)
class ClassLabelIndicatorsFromIntLabels(Transformer):
    """Int label -> ±1 one-hot indicator vector
    (reference: nodes/util/ClassLabelIndicators.scala:15-38)."""

    num_classes: int

    def __post_init__(self):
        if self.num_classes < 2:
            raise ValueError("Must have at least two classes for ClassLabelIndicators")

    def apply(self, label: int):
        return self._encode(jnp.asarray(label))

    def _encode(self, labels):
        return 2.0 * jax.nn.one_hot(labels, self.num_classes, dtype=jnp.float32) - 1.0

    def batch_apply(self, data: Dataset) -> Dataset:
        labels = jnp.asarray(data.array).astype(jnp.int32)
        out = Dataset(self._encode(labels), n=data.n, mesh=data.mesh)
        # ±1 encoding is non-zero-preserving: re-zero padding rows.
        return out._rezero_padding()

    def output_signature(self, sig):
        """Verifier declaration: int labels (lead,) -> ±1 indicators
        (lead, num_classes) float32."""
        from keystone_tpu.workflow.verify import ArraySig, SignatureError

        if not isinstance(sig, ArraySig):
            return None
        if len(sig.shape) > (0 if sig.datum else 1):
            raise SignatureError(
                f"{self.label} expects scalar int labels per example, got "
                f"{sig.describe()}"
            )
        shape = (self.num_classes,) if sig.datum else (
            sig.shape[0], self.num_classes
        )
        return ArraySig(shape, "float32", n=sig.n, mesh=sig.mesh,
                        datum=sig.datum)


@dataclass(frozen=True)
class ClassLabelIndicatorsFromIntArrayLabels(Transformer):
    """Multi-label int array -> ±1 indicator vector
    (reference: nodes/util/ClassLabelIndicators.scala:40-55)."""

    num_classes: int
    valid_check: bool = True

    def __post_init__(self):
        if self.num_classes < 2:
            raise ValueError("Must have at least two classes for ClassLabelIndicators")

    def apply(self, labels):
        labels = np.atleast_1d(np.asarray(labels))
        if self.valid_check and (labels.min() < 0 or labels.max() >= self.num_classes):
            raise ValueError("Class labels out of range")
        out = -np.ones(self.num_classes, dtype=np.float32)
        out[labels] = 1.0
        return jnp.asarray(out)

    def batch_apply(self, data: Dataset) -> Dataset:
        return Dataset.of([self.apply(x) for x in data.to_list()])

    def output_signature(self, sig):
        from keystone_tpu.workflow.verify import ArraySig

        datum = getattr(sig, "datum", False)
        n = getattr(sig, "n", None)
        shape = (self.num_classes,) if datum else (n, self.num_classes)
        return ArraySig(shape, "float32", n=n, datum=datum)


@dataclass(frozen=True)
class MaxClassifier(Transformer):
    """argmax over scores -> int label (reference: nodes/util/MaxClassifier.scala:9-11)."""

    def device_operands(self):
        return (), ()

    @staticmethod
    def device_apply(static_key, params, X):
        return jnp.argmax(X, axis=-1)


@dataclass(frozen=True)
class TopKClassifier(Transformer):
    """Top-k score indices, descending; k clamps at the vector size
    (reference: nodes/util/TopKClassifier.scala:9-14 takes min(k, length))."""

    k: int

    def apply(self, x):
        x = jnp.asarray(x)
        _, idx = jax.lax.top_k(x, min(self.k, x.shape[-1]))
        return idx

    def batch_apply(self, data: Dataset) -> Dataset:
        arr = jnp.asarray(data.array)
        _, idx = jax.lax.top_k(arr, min(self.k, arr.shape[-1]))
        return Dataset(idx, n=data.n, mesh=data.mesh)

    def output_signature(self, sig):
        from keystone_tpu.workflow.verify import ArraySig, SignatureError

        if not isinstance(sig, ArraySig):
            return None
        if not sig.shape:
            raise SignatureError(
                f"{self.label} needs a score vector, got {sig.describe()}"
            )
        d = sig.shape[-1]
        k = min(self.k, d) if d is not None else self.k
        return ArraySig(sig.shape[:-1] + (k,), "int32", n=sig.n,
                        mesh=sig.mesh, datum=sig.datum)


@dataclass(frozen=True)
class VectorCombiner(Transformer):
    """Concatenate gathered branch vectors (reference: nodes/util/VectorCombiner.scala:10-14).

    Input items are tuples of vectors (the output of ``Pipeline.gather``);
    output is their concatenation.
    """

    def apply(self, x):
        return self.device_combine_apply((), (), x)

    def batch_apply(self, data: Dataset) -> Dataset:
        if isinstance(data.data, tuple):
            out = self.device_combine_apply((), (), data.data)
            return Dataset(out, n=data.n, mesh=data.mesh)
        return Dataset.of([self.apply(x) for x in data.to_list()])

    def device_combine_operands(self):
        """Gather-fusion contract: merge branch ARRAYS inside one program
        (workflow/fusion.py::GatherFusionRule). No setting, no array."""
        return (), ()

    @staticmethod
    def device_combine_apply(static_key, params, arrays):
        return jnp.concatenate([jnp.asarray(a) for a in arrays], axis=-1)


@dataclass(frozen=True)
class MatrixVectorizer(Transformer):
    """Flatten a matrix to a vector, column-major to match Breeze's
    ``DenseMatrix.toDenseVector`` (reference: nodes/util/MatrixVectorizer.scala:9-11)."""

    def device_operands(self):
        return (), ()

    @staticmethod
    def device_apply(static_key, params, X):
        return jnp.transpose(X, (0, 2, 1)).reshape(X.shape[0], -1)


@dataclass(frozen=True)
class FloatToDouble(Transformer):
    """float32 -> float64 cast (reference: nodes/util/FloatToDouble.scala:9-11).

    On TPU float64 is emulated and slow; by default this widens to the
    framework's accumulation dtype (float32) and exists for API parity. Pass
    ``strict=True`` for true float64 (CPU meshes / x64-enabled tests).
    """

    strict: bool = False

    # The whole point of this node is a dtype change — tell the plan
    # verifier's drift check it is declared, not silent.
    declares_dtype_change = True

    def device_operands(self):
        return (bool(self.strict),), ()

    @staticmethod
    def device_apply(static_key, params, X):
        return jnp.asarray(X, dtype=jnp.float64 if static_key[0] else jnp.float32)


@dataclass(frozen=True)
class Shuffler(Transformer):
    """Random row permutation (the repartition/shuffle analog;
    reference: nodes/util/Shuffler.scala:14-22)."""

    seed: int = 0

    def apply(self, x):
        return x

    def output_signature(self, sig):
        return sig  # a permutation is a signature passthrough

    def batch_apply(self, data: Dataset) -> Dataset:
        if data.is_host:
            rng = np.random.default_rng(self.seed)
            items = data.to_list()
            return Dataset.of([items[i] for i in rng.permutation(len(items))])
        perm = jax.random.permutation(jax.random.key(self.seed), data.n)
        arr = data.array[: data.n][perm]
        out = Dataset(arr, n=data.n)
        return out.shard(data.mesh) if data.mesh is not None else out


@functools.partial(jax.jit, static_argnames=("bounds",))
def _column_blocks(arr, bounds):
    """``arr[:, lo:hi]`` for every ``(lo, hi)`` of ``bounds``, one program
    for the whole split (static bounds: nothing crosses to the device), under
    a name scope, which an eagerly dispatched operation does not take from
    its caller."""
    with jax.named_scope("ks.split"):
        return tuple(arr[:, lo:hi] for lo, hi in bounds)


class VectorSplitter(FunctionNode):
    """Split a (n, d) dataset into feature-axis blocks — the model-parallel
    partitioner (reference: nodes/util/VectorSplitter.scala:10-36).

    Returns a list of Datasets, each (n, block_size) (last may be smaller).
    On a 2-D mesh the blocks are what the block solvers iterate over; within a
    block, rows stay sharded over the ``data`` axis.
    """

    def __init__(self, block_size: int, num_features: Optional[int] = None):
        self.block_size = block_size
        self.num_features = num_features

    def apply(self, data: Dataset) -> List[Dataset]:
        arr = data.array
        d = self.num_features if self.num_features is not None else int(arr.shape[-1])
        bounds = tuple((start, min(start + self.block_size, d))
                       for start in range(0, d, self.block_size))
        # a host dataset's blocks stay on the host
        cut = (_column_blocks(arr, bounds) if isinstance(arr, jax.Array)
               else [arr[:, lo:hi] for lo, hi in bounds])
        return [Dataset(block, n=data.n, mesh=data.mesh) for block in cut]

    def split_vector(self, vec):
        """Split a single vector into per-block vectors."""
        vec = jnp.asarray(vec)
        d = self.num_features if self.num_features is not None else int(vec.shape[-1])
        return [
            vec[start : min(start + self.block_size, d)]
            for start in range(0, d, self.block_size)
        ]
