"""Device mesh management: the substrate that replaces the Spark cluster.

The reference distributes work as RDD partitions over Spark executors; here the
substrate is a `jax.sharding.Mesh` over TPU chips (ICI) or forced-CPU devices
in tests. Axis conventions:

  - ``data``  — examples (rows). The analog of RDD row-partitioning.
  - ``model`` — features/columns. The analog of VectorSplitter feature blocks
    (reference: nodes/util/VectorSplitter.scala:10-36).

All collectives are XLA collectives inserted by the compiler from sharding
annotations (or explicit psums inside shard_map kernels); nothing here talks to
NCCL/MPI.
"""

from __future__ import annotations

import contextlib
import os
from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DATA_AXIS = "data"
MODEL_AXIS = "model"

_default_mesh: Optional[Mesh] = None


def make_mesh(
    shape: Optional[Tuple[int, ...]] = None,
    axis_names: Sequence[str] = (DATA_AXIS,),
    devices: Optional[Sequence] = None,
) -> Mesh:
    """Build a mesh over the available devices.

    Default: a 1-D ``data`` mesh over all devices. Pass ``shape`` +
    ``axis_names`` for 2-D data×model meshes.
    """
    devs = np.array(devices if devices is not None else jax.devices())
    if shape is None:
        shape = (devs.size,)
    return Mesh(devs.reshape(shape), tuple(axis_names))


def default_mesh() -> Mesh:
    """Process-wide default mesh (1-D over all devices), created on demand."""
    global _default_mesh
    if _default_mesh is None:
        _default_mesh = make_mesh()
    return _default_mesh


def set_default_mesh(mesh: Optional[Mesh]) -> None:
    global _default_mesh
    _default_mesh = mesh


@contextlib.contextmanager
def use_mesh(mesh: Mesh):
    """Temporarily install `mesh` as the process default."""
    global _default_mesh
    prev = _default_mesh
    _default_mesh = mesh
    try:
        yield mesh
    finally:
        _default_mesh = prev


def axis_size(mesh: Mesh, axis: str) -> int:
    return mesh.shape[axis] if axis in mesh.shape else 1


def init_distributed(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> None:
    """Join the multi-host JAX runtime (one process per host over DCN).

    The analog of the Spark driver/executor bring-up in bin/run-pipeline.sh:
    after this, ``jax.devices()`` spans every host's chips and meshes built
    from it produce programs whose collectives ride ICI within a slice and
    DCN across slices. No-op when already initialized or single-process with
    no coordinator configured.
    """
    # NOTE: must not touch jax.devices()/process_count() here — querying the
    # backend initializes it, after which jax.distributed.initialize refuses
    # to run.
    if jax.distributed.is_initialized():
        return
    if coordinator_address is None and "JAX_COORDINATOR_ADDRESS" not in os.environ:
        return  # single-process run
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )


def make_hybrid_mesh(
    ici_shape: Tuple[int, ...],
    dcn_shape: Tuple[int, ...],
    axis_names: Sequence[str],
) -> Mesh:
    """Mesh over a multi-slice topology: ``ici_shape`` axes map within a
    slice (fast ICI), ``dcn_shape`` axes across slices (DCN). Put the
    data-parallel axis on DCN and model/feature axes on ICI — the layout that
    keeps Gramian all-reduces and block broadcasts on the fast interconnect.

    Degenerates to a plain mesh when there is a single slice.
    """
    if int(np.prod(dcn_shape)) == 1:
        full = tuple(d * i for d, i in zip(dcn_shape, ici_shape))
        return make_mesh(full, axis_names)
    from jax.experimental import mesh_utils

    # TPU slices carry a slice_index; hosts without one (multi-process CPU,
    # single-slice-per-host topologies) group by process instead, so the DCN
    # axes land across processes.
    slice_ids = {getattr(d, "slice_index", None) for d in jax.devices()}
    process_is_granule = len(slice_ids) <= 1
    devices = mesh_utils.create_hybrid_device_mesh(
        ici_shape, dcn_shape, devices=jax.devices(),
        process_is_granule=process_is_granule,
    )
    return Mesh(devices, tuple(axis_names))


def pad_rows(x: np.ndarray, multiple: int):
    """Zero-pad the leading axis up to a multiple; returns (padded, n_valid).

    Zero padding is the invariant the solvers rely on: padded rows contribute
    nothing to Gramians (AtA), moment sums, or gradient accumulations, so only
    divisions by n need the true count.
    """
    n = x.shape[0]
    rem = (-n) % multiple
    if rem == 0:
        return x, n
    pad_width = [(0, rem)] + [(0, 0)] * (x.ndim - 1)
    return np.pad(x, pad_width), n


def shard_rows(x, mesh: Optional[Mesh] = None, axis: str = DATA_AXIS):
    """Place an array on the mesh, sharded along its leading (example) axis."""
    mesh = mesh or default_mesh()
    spec = P(axis, *([None] * (np.ndim(x) - 1)))
    return jax.device_put(x, NamedSharding(mesh, spec))


def replicate(x, mesh: Optional[Mesh] = None):
    """Fully replicate an array over the mesh (the `broadcast` analog)."""
    mesh = mesh or default_mesh()
    return jax.device_put(x, NamedSharding(mesh, P()))


def shard_map(f, mesh: Mesh, in_specs, out_specs, check_vma: bool = True):
    """``jax.shard_map`` with this package's keyword order — the one
    import site every shard_map program here routes through."""
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=check_vma,
    )


def sync_if_cpu(x) -> None:
    """Barrier after a dispatched step — on the CPU backend only.

    The forced-host multi-device CPU backend deadlocks when many collective
    programs are queued asynchronously, so host-driven solver loops call
    this after each dispatched step. On TPU it is a no-op: the loop keeps
    async dispatch and step b+1's GEMMs overlap step b's solve.
    """
    if jax.default_backend() == "cpu":
        jax.block_until_ready(x)
