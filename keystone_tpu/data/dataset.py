"""Dataset: the distributed-collection abstraction replacing RDDs.

The reference moves `RDD[DenseVector]` (or `RDD[Image]`, `RDD[String]`...)
through pipelines, packing rows into per-partition matrices for BLAS-3
(reference: utils/MatrixUtils.scala:48-61, workflow/Operator.scala:25-38).
The TPU-native analog is batch-major arrays:

  - **Array form** (the common case): ``data`` is a pytree of arrays sharing a
    leading example axis, usually one ``(n, d)`` array; it may be zero-padded
    to a multiple of the mesh ``data`` axis and sharded over the mesh. Padding
    rows are all-zero so Gramians/moment sums are unaffected; ``n`` tracks the
    true example count.
  - **Host form**: a Python list of arbitrary objects (images before decode,
    token sequences) for stages that must run host-side.
  - **Shard form**: ``data`` is a :class:`~keystone_tpu.data.prefetch.
    ShardSource` — ordered disk/host segments delivered one at a time, for
    datasets whose resident size exceeds the host-RAM budget. Streamed
    solvers consume the source directly (prefetched, never resident);
    anything else triggers ``materialize()``, which only small sources
    should ever hit.

Transformers consume and produce Datasets; solvers read ``.array`` +
``.n`` directly and run jit-compiled sharded computations on them.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from keystone_tpu import obs
from keystone_tpu.parallel import mesh as mesh_lib

from .prefetch import ShardSource


@functools.partial(jax.jit, static_argnames=("rows",))
def _pad_rows_on_device(leaf, rows: int):
    """``rows`` zero rows after a device array's last (a program: the zero
    is its constant, nothing comes from the host)."""
    return jnp.pad(leaf, ((0, rows),) + ((0, 0),) * (leaf.ndim - 1))


def _is_arraylike(x: Any) -> bool:
    return isinstance(x, (np.ndarray, jax.Array)) or (
        hasattr(x, "shape") and hasattr(x, "dtype")
    )


class Dataset:
    """A batch of n examples, in device-array or host-list form."""

    def __init__(self, data: Any, n: Optional[int] = None, mesh=None):
        if isinstance(data, Dataset):
            raise TypeError("Dataset(data) may not wrap another Dataset")
        self.data = data
        self.mesh = mesh
        if isinstance(data, list):
            self.n = len(data) if n is None else n
        elif isinstance(data, ShardSource):
            self.n = data.n_true if n is None else n
        else:
            leaves = jax.tree_util.tree_leaves(data)
            if not leaves:
                raise ValueError("Array dataset must contain at least one array")
            self.n = int(leaves[0].shape[0]) if n is None else n

    # -- constructors -------------------------------------------------------

    @staticmethod
    def of(data: Any, mesh=None) -> "Dataset":
        """Wrap a list (host form) or array-like / pytree (array form)."""
        if isinstance(data, Dataset):
            return data
        if isinstance(data, list) and not (data and _is_arraylike(data[0])):
            return Dataset(list(data))
        if isinstance(data, list):
            # list of per-example arrays with identical shapes -> stack;
            # ragged -> host form
            shapes = {np.shape(x) for x in data}
            if len(shapes) == 1:
                return Dataset(np.stack([np.asarray(x) for x in data]), mesh=mesh)
            return Dataset(list(data))
        return Dataset(data, mesh=mesh)

    @staticmethod
    def gather(branches: List["Dataset"]) -> "Dataset":
        """Zip branches into a dataset of tuples (GatherTransformerOperator.scala:9-18)."""
        ns = {b.n for b in branches}
        if len(ns) != 1:
            raise ValueError(f"Gathered branches must have equal sizes, got {ns}")
        if all(not b.is_host for b in branches):
            return Dataset(tuple(b.data for b in branches), n=branches[0].n,
                           mesh=branches[0].mesh)
        items = [b.to_list() for b in branches]
        return Dataset([tuple(vals) for vals in zip(*items)])

    @staticmethod
    def from_shards(source: ShardSource, n: Optional[int] = None) -> "Dataset":
        """A Dataset backed by an out-of-core :class:`ShardSource`."""
        return Dataset(source, n=n)

    # -- properties ---------------------------------------------------------

    @property
    def is_host(self) -> bool:
        return isinstance(self.data, list)

    @property
    def is_shard_backed(self) -> bool:
        return isinstance(self.data, ShardSource)

    @property
    def shard_source(self) -> ShardSource:
        if not self.is_shard_backed:
            raise ValueError("Dataset is not shard-backed")
        return self.data

    def materialize(self) -> "Dataset":
        """Shard form -> array form (concatenates every segment; only
        sources that fit host RAM should ever reach this — the streamed
        solvers consume the source directly instead)."""
        if not self.is_shard_backed:
            return self
        mat = self.data.materialize()
        if isinstance(mat, tuple):
            mat = mat[0]  # a paired (X, Y) source read as a data Dataset
        return Dataset(np.asarray(mat), n=self.n, mesh=self.mesh)

    @property
    def array(self):
        """The single underlying array (errors for host/tuple datasets)."""
        if self.is_shard_backed:
            return self.materialize().array
        if self.is_host:
            arr = np.stack([np.asarray(x) for x in self.data])
            return arr
        leaves = jax.tree_util.tree_leaves(self.data)
        if isinstance(self.data, (tuple, list)) or len(leaves) != 1:
            raise ValueError("Dataset holds a pytree; use .data")
        return leaves[0]

    @property
    def num_padded(self) -> int:
        if self.is_host:
            return len(self.data)
        if self.is_shard_backed:
            return self.n
        return int(jax.tree_util.tree_leaves(self.data)[0].shape[0])

    def __len__(self) -> int:
        return self.n

    # -- transforms ---------------------------------------------------------

    def map(self, fn: Callable[[Any], Any]) -> "Dataset":
        """Apply `fn` per example. Host form: Python map. Array form: vmap,
        falling back to a host loop if `fn` is not traceable."""
        if self.is_shard_backed:
            return self.materialize().map(fn)
        if self.is_host:
            out = [fn(x) for x in self.data]
            return Dataset.of(out)
        try:
            mapped = jax.vmap(fn)(self.data)
            return Dataset(mapped, n=self.n, mesh=self.mesh)._rezero_padding()
        except Exception:
            items = self.to_list()
            return Dataset.of([fn(x) for x in items])

    def map_batch(self, fn: Callable[[Any], Any]) -> "Dataset":
        """Apply a whole-batch (vectorized) function to the array form."""
        if self.is_shard_backed:
            return self.materialize().map_batch(fn)
        out = fn(self.data)
        return Dataset(out, n=self.n, mesh=self.mesh)._rezero_padding()

    def _rezero_padding(self) -> "Dataset":
        """Restore the all-zero-padding invariant after a non-zero-preserving
        transform (padding rows must not pollute Gramians/moment sums)."""
        if self.is_host or self.num_padded == self.n:
            return self
        mask = jnp.arange(self.num_padded) < self.n

        def zero(leaf):
            m = mask.reshape((-1,) + (1,) * (leaf.ndim - 1))
            return jnp.where(m, leaf, jnp.zeros((), dtype=leaf.dtype))

        data = jax.tree_util.tree_map(zero, self.data)
        return Dataset(data, n=self.n, mesh=self.mesh)

    def to_list(self) -> List[Any]:
        """Materialize as a host list of per-example values (padding dropped)."""
        if self.is_shard_backed:
            return self.materialize().to_list()
        if self.is_host:
            return list(self.data)
        if isinstance(self.data, tuple):
            parts = [np.asarray(leaf)[: self.n] for leaf in self.data]
            return [tuple(p[i] for p in parts) for i in range(self.n)]
        return list(np.asarray(self.array)[: self.n])

    def to_numpy(self) -> np.ndarray:
        """The underlying array with padding rows dropped, as numpy."""
        return np.asarray(self.array)[: self.n]

    # -- distribution -------------------------------------------------------

    def shard(self, mesh=None, axis: str = mesh_lib.DATA_AXIS) -> "Dataset":
        """Pad to divisibility and shard the leading axis over the mesh.

        A leaf that lives on a device stays on the devices: it is padded
        there (only if it must be) and laid over the mesh by ``device_put``;
        one that already lies so is returned as it is. Host leaves are
        padded on the host and placed from there."""
        if self.is_shard_backed:
            return self.materialize().shard(mesh, axis)
        if self.is_host:
            raise ValueError("Host datasets cannot be device-sharded; vectorize first")
        mesh = mesh or mesh_lib.default_mesh()
        size = mesh_lib.axis_size(mesh, axis)
        moves = set()

        def place(leaf):
            want = NamedSharding(mesh, P(axis, *([None] * (np.ndim(leaf) - 1))))
            if not isinstance(leaf, jax.Array):
                moves.add("host")
                padded, _ = mesh_lib.pad_rows(np.asarray(leaf), size)
                return jax.device_put(padded, want)
            if leaf.shape[0] % size:
                leaf = _pad_rows_on_device(leaf, (-leaf.shape[0]) % size)
            elif leaf.sharding.is_equivalent_to(want, leaf.ndim):
                moves.add("none")
                return leaf
            moves.add("device")
            return jax.device_put(leaf, want)

        leaves = jax.tree_util.tree_leaves(self.data)
        with obs.span("data.shard", devices=size,
                      bytes=int(sum(leaf.nbytes for leaf in leaves))) as sp:
            data = jax.tree_util.tree_map(place, self.data)
            # the farthest any leaf went: over the host, between devices, nowhere
            sp.set(moved=next(m for m in ("host", "device", "none") if m in moves))
        return Dataset(data, n=self.n, mesh=mesh)

    def cache(self) -> "Dataset":
        """Force materialization now (the Cacher analog). Device arrays are
        already materialized eagerly by JAX; this just blocks until ready."""
        if not self.is_host and not self.is_shard_backed:
            jax.block_until_ready(jax.tree_util.tree_leaves(self.data))
        return self

    def valid_mask(self):
        """(num_padded,) float mask: 1 for real rows, 0 for padding."""
        npad = self.num_padded
        return (jnp.arange(npad) < self.n).astype(jnp.float32)

    def __repr__(self) -> str:
        if self.is_shard_backed:
            return (
                f"Dataset(shards, n={self.n}, "
                f"segments={self.data.num_segments})"
            )
        if self.is_host:
            return f"Dataset(host, n={self.n})"
        shapes = jax.tree_util.tree_map(lambda x: tuple(x.shape), self.data)
        return f"Dataset(array, n={self.n}, shapes={shapes})"


def one_hot_pm1(labels: np.ndarray, num_classes: int) -> np.ndarray:
    """Integer class labels -> the ±1 one-hot regression targets every LS
    pipeline here fits against (the host-side twin of
    ``ClassLabelIndicatorsFromIntLabels``): one shared encoding for every
    spill/bench site instead of hand-rolled copies."""
    return (
        2.0 * np.eye(num_classes, dtype=np.float32)[
            np.asarray(labels, dtype=np.int64).reshape(-1)
        ] - 1.0
    )


class LabeledData:
    """A (data, labels) pair of aligned Datasets (loaders/LabeledData.scala:12-15)."""

    def __init__(self, data: Any, labels: Any):
        self.data = Dataset.of(data)
        self.labels = Dataset.of(labels)
        if self.data.n != self.labels.n:
            raise ValueError(
                f"data ({self.data.n}) and labels ({self.labels.n}) must align"
            )

    def to_disk_shards(
        self,
        path: str,
        shard_rows: int,
        tiles_per_segment: int = 4,
        num_classes: Optional[int] = None,
    ) -> "LabeledData":
        """Spill this (data, labels) pair to pre-tiled disk shards and
        return a SHARD-BACKED LabeledData over the files — the loaders'
        materialize-to-disk-instead-of-RAM path. Integer class labels
        become ±1 one-hot regression targets when ``num_classes`` is
        given (the convention every LS pipeline here uses); otherwise
        labels are stored as-is, reshaped to (n, k)."""
        from .shards import DiskDenseShards

        X = np.asarray(self.data.array)[: self.data.n]
        Y = np.asarray(self.labels.array)[: self.labels.n]
        if num_classes is not None:
            Y = one_hot_pm1(Y, num_classes)
        elif Y.ndim == 1:
            Y = Y[:, None]
        shards = DiskDenseShards.write(
            path, X, Y.astype(np.float32, copy=False),
            tile_rows=int(shard_rows), tiles_per_segment=tiles_per_segment,
        )
        return shards.as_labeled_data()
