"""Densify a padded-COO row chunk into the Gramian fold's (c, d_pad) slab
by WRITING it — a factored one-hot contraction on the MXU — instead of
scatter-adding into it.

A column id splits into ``hi = id >> 7`` (which 128-wide lane tile) and
``lo = id & 127`` (which lane of it); ``d_pad`` is a multiple of 128
(``sparse.gram_pad_dim`` rounds to 512 or 1024), so row r of the slab,
seen as a (d_pad/128, 128) matrix, is

    slab[r] = Σ_lane  onehot(hi[r, lane])ᵀ · (value[r, lane] · onehot(lo[r, lane]))

— one small product a row, summed over the lanes: repeated ids add, dead
lanes carry value 0 and add nothing. No scatter, no sort of the ids. The
scatter-add this replaces ran at the chip's random-access rate, ~1e8 adds
a second whatever the slab's type (70–73 ms for a 65,536 × 83 chunk on a
v5e); the contraction is bound by the bytes of the slab it writes.

Two forms, the same slab bit for bit (tests/test_sparse_densify.py):

- :func:`contract` — plain XLA: one batched ``einsum`` over the one-hots
  (built inside the convolution fusion, never in HBM) and a relayout of
  its (c, d_pad/128, 128) result. Runs anywhere; 14 ms for that chunk in
  bfloat16, 38 ms in float32.
- :func:`contract_kernel` — a Pallas kernel that builds the one-hots in
  VMEM and writes the slab's own (rows, 128) tiles, so the result never
  takes the relayout's second trip through HBM: 4.4 ms and 7.8 ms, the
  slab's bytes at the rate the chip writes them. Taken where the fold's
  ``use_pallas`` is set and :func:`kernel_block_rows` finds a row block
  (:func:`densify_form`).

``sparse.sparse_gram_fold`` calls :func:`densify_rows`, which masks the
lanes and picks the form.

Float32 slabs stay exact: the ``hi`` one-hot is exact in bfloat16, the
XLA form multiplies at ``HIGHEST`` and the kernel makes three one-pass
products over an exact three-way bfloat16 split of the values.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from keystone_tpu.ops import pallas_ops

_LANES = 128  # ids split at one lane tile: lo = id & 127, hi = id >> 7
_KERNEL_OUT_BLOCK_BYTES = 8 << 20  # one output block of the kernel (two in VMEM)
_KERNEL_MAX_TILES = 256  # lane tiles a row (the kernel unrolls over them)


def _split_ids(safe):
    return safe >> 7, safe & (_LANES - 1)


def contract(safe, vals, d_pad: int):
    """(c, w) in-range ids and values (dead lanes: value 0) -> the (c, d_pad)
    slab of ``vals.dtype``, by XLA's batched contraction."""
    hi, lo = _split_ids(safe)
    at = hi[:, :, None] == jnp.arange(d_pad // _LANES, dtype=jnp.int32)
    bt = lo[:, :, None] == jnp.arange(_LANES, dtype=jnp.int32)
    out = jnp.einsum(
        "rlh,rlc->rhc",
        at.astype(vals.dtype), jnp.where(bt, vals[:, :, None], 0),
        preferred_element_type=jnp.promote_types(vals.dtype, jnp.float32),
        precision=jax.lax.Precision.HIGHEST,  # float32 values stay exact
    )
    return out.reshape(safe.shape[0], d_pad).astype(vals.dtype)


def _group_rows(val_dtype) -> int:
    """Rows one (sublane-packed) tile of the slab holds."""
    return 16 if jnp.dtype(val_dtype) == jnp.bfloat16 else 8


def kernel_block_rows(c: int, d_pad: int, val_dtype) -> Optional[int]:
    """Rows a grid step of :func:`contract_kernel` writes, or None where
    the kernel does not apply (a slab type it was not written for, a chunk
    that is no whole number of tiles, a row too wide to unroll over)."""
    if jnp.dtype(val_dtype) not in (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)):
        return None
    if d_pad % _LANES or d_pad // _LANES > _KERNEL_MAX_TILES:
        return None
    row_bytes = d_pad * jnp.dtype(val_dtype).itemsize
    for rows in (256, 128, 64, 32, 16, 8):
        if (rows % _group_rows(val_dtype) == 0 and c % rows == 0
                and rows * row_bytes <= _KERNEL_OUT_BLOCK_BYTES):
            return rows
    return None


def _scratch_height(tiles: int) -> int:
    """Sublanes one row's product takes in the kernel's scratch: its lane
    tiles, rounded up to an ODD number of 8-sublane tiles. The read-back
    strides over the scratch by this height, and at an even number of
    tiles its eight reads collide: a 65,536-row bfloat16 chunk of 128 lane
    tiles (16,384 columns) takes 4.67 ms at a height of 128, 3.42 at 136
    — 628 GB/s, what 136 lane tiles read at their own height; heights of
    32, 64, 96, 128 and 160 are each the slow ones of their sweep, in both
    slab types (v5e)."""
    eights = -(-tiles // 8)
    return 8 * (eights + 1 - eights % 2)


def _split3(v):
    """v = v1 + v2 + v3 exactly, each part a bfloat16 (24 = 3 × 8 bits)."""
    v1 = v.astype(jnp.bfloat16)
    r = v - v1.astype(jnp.float32)
    v2 = r.astype(jnp.bfloat16)
    return v1, v2, (r - v2.astype(jnp.float32)).astype(jnp.bfloat16)


def _contract_kernel(ids_ref, val_ref, out_ref, rows_ref):
    """One grid step: ``out_ref`` (block rows, d_pad) from ``ids_ref`` /
    ``val_ref`` (block rows, lanes). Row by row the product lands in
    ``rows_ref`` as (tiles, 128) — lane tile on the sublanes; the slab
    wants ROWS on the sublanes, so a tile's rows at a time are read back
    with a stride of one row's height, which hands each lane tile its
    (rows, 128) tile ready to store. Both inner loops are unrolled on
    purpose: rolled (``fori_loop``) the kernel takes 7–17 ms for the
    chunk that takes it 4.4 (v5e), the one-hots no longer hidden behind
    the stores."""
    lanes = ids_ref.shape[1]
    tiles, group = out_ref.shape[1] // _LANES, _group_rows(out_ref.dtype)
    height = rows_ref.shape[0] // group  # _scratch_height(tiles)
    exact_f32 = out_ref.dtype == jnp.float32
    tile_of = jax.lax.broadcasted_iota(jnp.int32, (height, lanes), 0)
    lane_of = jax.lax.broadcasted_iota(jnp.int32, (_LANES, lanes), 0)
    one_pass = dict(
        dimension_numbers=(((1,), (1,)), ((), ())),  # both hold the lanes last
        **pallas_ops._dot_kwargs(jnp.bfloat16),
    )

    def fold_group(g, carry):
        r0 = pl.multiple_of(g * group, group)
        for s in range(group):
            hi, lo = _split_ids(ids_ref[pl.ds(r0 + s, 1), :])
            v = val_ref[pl.ds(r0 + s, 1), :]
            at = jnp.where(tile_of == hi, 1.0, 0.0).astype(jnp.bfloat16)
            hit = lane_of == lo
            out = None
            for part in (_split3(v) if exact_f32 else (v,)):
                bt = jnp.where(hit, part.astype(jnp.float32), 0.0)
                prod = jax.lax.dot_general(at, bt.astype(jnp.bfloat16), **one_pass)
                out = prod if out is None else out + prod
            rows_ref[pl.ds(s * height, height), :] = out
        for t in range(tiles):
            tile = jnp.concatenate(
                [rows_ref[pl.ds(k * 8 * height + t, 8, stride=height), :]
                 for k in range(group // 8)], axis=0,
            )
            out_ref[pl.ds(r0, group), t * _LANES:(t + 1) * _LANES] = (
                tile.astype(out_ref.dtype))
        return carry

    jax.lax.fori_loop(0, out_ref.shape[0] // group, fold_group, 0)


def contract_kernel(safe, vals, d_pad: int, interpret: Optional[bool] = None):
    """:func:`contract`'s slab from the Pallas kernel. Requires
    :func:`kernel_block_rows`; ``interpret`` as ``pallas_ops`` kernels."""
    c, w = safe.shape
    val_dtype = vals.dtype
    rows = kernel_block_rows(c, d_pad, val_dtype)
    if rows is None:
        raise ValueError(f"no kernel for a {(c, d_pad)} slab of {val_dtype}")
    height = _scratch_height(d_pad // _LANES)
    lanes = -(-w // _LANES) * _LANES
    pad = ((0, 0), (0, lanes - w))  # dead lanes: id 0, value 0
    chunk = pl.BlockSpec((rows, lanes), lambda i: (i, 0))
    return pallas_ops._pallas_call(
        "sparse_densify",
        _contract_kernel,
        interpret=interpret,
        out_shape=jax.ShapeDtypeStruct((c, d_pad), val_dtype),
        grid=(c // rows,),
        in_specs=[chunk, chunk],
        out_specs=pl.BlockSpec((rows, d_pad), lambda i: (i, 0)),
        scratch_shapes=[
            pltpu.VMEM((_group_rows(val_dtype) * height, _LANES), jnp.float32)
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=48 * 1024 * 1024,
        ),
    )(jnp.pad(safe, pad), jnp.pad(vals.astype(jnp.float32), pad))


def densify_form(use_pallas: bool, chunk_rows: int, d_pad: int, val_dtype) -> str:
    """Which form makes a chunk's slab (the same slab either way):
    ``"kernel"`` where ``use_pallas`` is set and the chunk and slab shapes
    suit :func:`contract_kernel`, else ``"contract"``. A function of what
    the fold is handed, nothing else; ``estimator.fit``'s span carries it
    as ``densify``."""
    if use_pallas and kernel_block_rows(chunk_rows, d_pad, val_dtype) is not None:
        return "kernel"
    return "contract"


def densify_rows(indices, values, d: int, d_pad: int, val_dtype,
                 use_pallas: bool = False, interpret: Optional[bool] = None):
    """Padded-COO rows ``(c, w)`` -> their dense (c, d_pad) slab of
    ``val_dtype``: ids outside [0, d) are dropped, repeated ids add.
    ``d_pad`` is a multiple of 128, as ``sparse.gram_pad_dim`` makes it.
    Traceable."""
    assert d_pad % _LANES == 0, d_pad  # the ids split at a lane tile
    mask = (indices >= 0) & (indices < d)
    safe = jnp.where(mask, indices, 0).astype(jnp.int32)
    vals = jnp.where(mask, values, 0).astype(val_dtype)
    if densify_form(use_pallas, indices.shape[0], d_pad, val_dtype) == "kernel":
        return contract_kernel(safe, vals, d_pad, interpret)
    return contract(safe, vals, d_pad)
