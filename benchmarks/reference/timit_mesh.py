"""Plain reference for the TimitPipeline fit whose rows lie over several
devices: ``reference/timit.py``'s algorithm — cosine random features, a
mean-centred ridge system, block Gauss-Seidel on its normal equations, one
Gramian shared by the lambdas — told where the rows are.

Every device folds the rows that lie on it, in blocks of rows, by
``reference/timit.py``'s own fold in plain ``jax.numpy`` float32 at
``highest``, and hands back its own partial sums (G, C, the column sums):
one program over the devices with NO collective in it, so that it compiles
once (a program a device compiles once a device: four times twenty seconds
in a checkout's first run). The partial sums are then added on the first
shard's device, one after the other, and the centring, the solve and the
scores run there as in ``reference/timit.py``. This is the row-partitioned
fit of the papers (a Gramian a partition, a sum of them, the solve on the
sum), and it imports nothing of ``keystone_tpu``. Rows that lie on one
device fold as one shard.

The rows are taken as they are: a caller whose rows were padded to shard
evenly hands over the rows without the padding.
"""

from __future__ import annotations

import functools
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from benchmarks.reference import timit

F32 = jnp.float32
score_gaps = timit.score_gaps


def _by_rows(array):
    """An array's shards in the order of the rows they hold."""
    return sorted(array.addressable_shards, key=lambda shard: shard.index[0].start or 0)


def row_shards(X, Y):
    """[(device, X's rows there, Y's rows there)] in the order of the rows."""
    shards = []
    for x, y in zip(_by_rows(X), _by_rows(Y)):
        if x.device != y.device or x.data.shape[0] != y.data.shape[0]:
            raise ValueError("X and Y are not sharded over the same devices by the same rows")
        shards.append((x.device, x.data, y.data))
    if sum(x.shape[0] for _, x, _ in shards) != X.shape[0]:
        raise ValueError("the shards do not part the rows (a replicated array?)")
    return shards


@functools.partial(jax.jit, donate_argnums=(0,))
def _add(total, part):
    return jax.tree_util.tree_map(jnp.add, total, part)


@functools.partial(jax.jit, static_argnames=("mesh", "precision", "rows_per_block"))
def _partial_stats(X, Y, W, b, mesh, precision, rows_per_block):
    """(G, C, fsum, ysum) of each device's own rows, stacked by device:
    ``timit._fold`` block after block where the rows lie."""
    d, k = W.shape[0], Y.shape[1]

    def own_rows(x, y, W, b):
        def fold(stats, lo, rows):
            return timit._fold(stats, jax.lax.dynamic_slice_in_dim(x, lo, rows),
                               jax.lax.dynamic_slice_in_dim(y, lo, rows), W, b, precision)

        stats = (jnp.zeros((d, d), F32), jnp.zeros((d, k), F32),
                 jnp.zeros((d,), F32), jnp.zeros((k,), F32))
        blocks, rest = divmod(x.shape[0], rows_per_block)
        if blocks:
            stats = jax.lax.fori_loop(
                0, blocks, lambda i, stats: fold(stats, i * rows_per_block, rows_per_block),
                stats)
        if rest:
            stats = fold(stats, blocks * rows_per_block, rest)
        return jax.tree_util.tree_map(lambda s: s[None], stats)

    rows, everywhere = P(mesh.axis_names[0]), P()
    return jax.shard_map(own_rows, mesh=mesh, in_specs=(rows, rows, everywhere, everywhere),
                         out_specs=rows, check_vma=False)(X, Y, W, b)


def centred_stats(X, Y, W, b, precision="highest", rows_per_block=8192):
    """``timit.centred_stats`` over the shards of (X, Y): (G, C, fmean,
    ymean) on the first shard's device."""
    devices = [device for device, _, _ in row_shards(X, Y)]
    mesh = Mesh(np.array(devices), ("rows",))
    W, b = jax.device_put((W, b), NamedSharding(mesh, P()))
    partial = _partial_stats(X, Y, W, b, mesh, precision, rows_per_block)
    total = None
    for shards in zip(*map(_by_rows, partial)):  # the partitions' sums, added on one device
        part = tuple(shard.data[0] for shard in shards)
        total = part if total is None else _add(total, jax.device_put(part, devices[0]))
    del partial
    return timit._centre(*total, F32(X.shape[0]))


def fit_and_score(X, Y, probe, lams: Sequence[float], *, bank_seed: int,
                  num_cosines: int, block: int, gamma: float, epochs: int,
                  precision: str = "highest",
                  rows_per_block: int = 8192) -> Dict[float, jax.Array]:
    """Scores of ``probe`` rows under the model fitted on the sharded
    (X, Y), one per ridge ``lam``; the Gramian is built once and shared."""
    W, b = timit.draw_bank(bank_seed, num_cosines, X.shape[1], block, gamma)
    G, C, fmean, ymean = centred_stats(X, Y, W, b, precision, rows_per_block)
    home = next(iter(G.devices()))
    W, b, probe = jax.device_put((W, b, probe), home)
    out = {}
    for lam in lams:
        Wt = timit.block_gauss_seidel(G, C, F32(lam), block, epochs, precision)
        out[lam] = timit.scores(probe, W, b, Wt, fmean, ymean, precision)
    return out
