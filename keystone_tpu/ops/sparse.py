"""Sparse feature nodes (reference: nodes/util/CommonSparseFeatures.scala:20-64,
AllSparseFeatures.scala:15-27, SparseFeatureVectorizer.scala:7-17,
Densify.scala:10-21, Sparsify.scala:10-20).

TPU-native sparse batch format: padded COO per row —
``{"indices": (n, max_nnz) int32 (−1 padding), "values": (n, max_nnz)}``
carried as a Dataset pytree.

The sparse compute tier never densifies: ``sparse_matmul`` (X @ W) is a
gather over the model rows + a reduction over the nnz axis, and
``sparse_matmul_t`` (Xᵀ V) is a segment-sum scatter over the flattened
active indices — the TPU formulation of the reference's hand-rolled
active-index gradient loops (Gradient.scala:58-123). At Amazon-review scale
(n=65e6, d=16384, sparsity≈0.005 — scripts/constantEstimator.R:34) the
padded-COO operands are ~100× smaller than the dense design matrix the old
densify path would have materialized. ``densify_dataset`` remains for small
inputs where one dense GEMM beats gather+scatter dispatch. The Gramian
tier (``sparse_gram_fold``) densifies on purpose, chunk by chunk, and
WRITES each slab by a one-hot contraction (``ops/sparse_densify.py``).

Measured characteristics (v5e): both kernels run at the chip's
random-access rate — 129M indices/s on the raw column-take microbenchmark,
179M/s inside the full LBFGS solve (bench.py's amazon row, round 3; earlier
rounds' 65M/s figure predates the per-column layouts) — which is the honest
TPU trade-off for this workload class: the sparse tier is a *capacity* play
(dense f32 would be 131 GB at n=2e6 and ~4.3 TB at the full n=65e6; the
COO itself is ~43 GB at n=65e6 — int16+bf16 compression and the streamed
gram tier below are what actually cross that wall), not a FLOP play. A
transposed-layout gather variant and a complex-packed gather were measured
and do not beat the scatter, so the simple formulations stay. Layout rule
learned the hard way: never put a tiny label dimension minor-most in a big
intermediate — TPU tiling lane-pads it to 128 (an 85 GB transient at this
scale), hence the per-column small-k formulations below.
"""

from __future__ import annotations

import functools
import heapq
from collections import Counter
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from keystone_tpu.data import Dataset
from keystone_tpu.workflow import Estimator, Transformer


def _item_pairs(item) -> List[Tuple[Any, float]]:
    """Normalize a sparse item: dict or iterable of (feature, value)."""
    if isinstance(item, dict):
        return list(item.items())
    return list(item)


def sparse_batch_from_items(
    items: Sequence, feature_index: Dict[Any, int], max_nnz: Optional[int] = None
) -> Dataset:
    """Host items (feature, value) -> padded-COO device batch over a vocab."""
    rows = []
    for item in items:
        pairs = [
            (feature_index[f], v) for f, v in _item_pairs(item) if f in feature_index
        ]
        pairs.sort()
        rows.append(pairs)
    width = max_nnz or max((len(r) for r in rows), default=1)
    width = max(width, 1)
    n = len(rows)
    indices = np.full((n, width), -1, dtype=np.int32)
    values = np.zeros((n, width), dtype=np.float32)
    for i, pairs in enumerate(rows):
        pairs = pairs[:width]
        if pairs:
            idx, val = zip(*pairs)
            indices[i, : len(idx)] = idx
            values[i, : len(val)] = val
    return Dataset({"indices": indices, "values": values}, n=n)


def is_sparse_dataset(data: Dataset) -> bool:
    return (
        not data.is_host
        and isinstance(data.data, dict)
        and set(data.data.keys()) == {"indices", "values"}
    )


def densify_dataset(data: Dataset, num_features: Optional[int] = None) -> Dataset:
    """Padded-COO batch -> dense (n, d) batch (one scatter-add per batch)."""
    if not is_sparse_dataset(data):
        return data
    indices = jnp.asarray(data.data["indices"])
    values = jnp.asarray(data.data["values"])
    d = num_features if num_features is not None else int(indices.max()) + 1
    return Dataset(_scatter_dense(indices, values, d), n=data.n, mesh=data.mesh)


# Label widths up to this take the per-column formulation, whose
# intermediates are all rank-1/2 with the LARGE axis minor — a (n·max_nnz, k)
# layout with tiny k would be lane-padded to 128 by the TPU tiling (a 64x
# HBM blowup at Amazon scale: 85 GB for n=2e6, k=2).
_COLWISE_MAX_K = 32
_CHUNK_ELEMS = 1 << 20  # row-chunk size divisor for the wide-k paths


def _row_chunks(safe, vals, k, pad_index=0):
    """Split (n, w) index/value arrays into (nchunks, chunk, w) row chunks
    for the wide-k paths, bounding each chunk's (chunk, w, k) transient at
    ~_CHUNK_ELEMS elements. The chunk is capped at n so small batches are
    not inflated to the chunk quantum."""
    n, w = safe.shape
    chunk = min(max(n, 1), max(1, _CHUNK_ELEMS // max(w * k, 1)))
    nchunks = -(-n // chunk)
    pad = nchunks * chunk - n
    safe_p = jnp.pad(safe, ((0, pad), (0, 0)), constant_values=pad_index)
    vals_p = jnp.pad(vals, ((0, pad), (0, 0)))
    return (
        safe_p.reshape(nchunks, chunk, w),
        vals_p.reshape(nchunks, chunk, w),
        nchunks,
        chunk,
        pad,
    )


@jax.jit
def sparse_matmul(indices, values, W):
    """X @ W for a padded-COO X without densifying.

    out[i] = Σ_j values[i, j] · W[indices[i, j], :] — a gather of the model
    rows at the active indices plus a reduction over the nnz axis (the
    active-index inner loops of LeastSquaresSparseGradient,
    Gradient.scala:58-123, become one vectorized gather+sum). Cost is
    O(n · max_nnz · k) independent of d. Indices outside [0, d) are dropped
    (the same semantics as the densify scatter and sparse_matmul_t — the
    X and Xᵀ operators must agree or gradients silently corrupt).

    Small k gathers one model column at a time so every intermediate is
    (n, max_nnz) — no lane-padding blowup; wide k runs the (chunk, w, k)
    gather over row chunks via lax.map to bound the transient.
    """
    k = W.shape[1]
    mask = (indices >= 0) & (indices < W.shape[0])
    safe = jnp.where(mask, indices, 0)
    vals = jnp.where(mask, values, 0.0).astype(W.dtype)
    if k <= _COLWISE_MAX_K:
        cols = [
            jnp.sum(vals * jnp.take(W[:, c], safe), axis=1) for c in range(k)
        ]
        return jnp.stack(cols, axis=1)

    safe_p, vals_p, nchunks, chunk, _ = _row_chunks(safe, vals, k)

    def body(xs):
        s, va = xs
        return jnp.einsum("cw,cwk->ck", va, jnp.take(W, s, axis=0))

    out = jax.lax.map(body, (safe_p, vals_p)).reshape(nchunks * chunk, k)
    return out[: indices.shape[0]]


@functools.partial(jax.jit, static_argnames=("d",))
def sparse_matmul_t(indices, values, V, d: int):
    """Xᵀ @ V for a padded-COO X via segment-sum scatters.

    Every active (i, j) contributes ``values[i, j] · V[i, :]`` to output row
    ``indices[i, j]``; padding and out-of-range lanes scatter into a ghost
    bucket that is sliced off (dropped — matching sparse_matmul). This is
    the transpose pass of the sparse gradient — together
    with :func:`sparse_matmul` it gives the full Xᵀ(XW − Y) gradient without
    ever materializing a dense design matrix.

    Small k scatters one output column at a time (each a flat (n·max_nnz,)
    segment sum — no lane-padded (n·max_nnz, k) tensor); wide k accumulates
    row-chunked scatters in a scan.
    """
    n, w = indices.shape
    k = V.shape[1]
    mask = (indices >= 0) & (indices < d)
    safe = jnp.where(mask, indices, d)  # ghost bucket d for padding
    vals = jnp.where(mask, values, 0.0).astype(V.dtype)
    if k <= _COLWISE_MAX_K:
        flat_ids = safe.reshape(-1)
        cols = [
            jax.ops.segment_sum(
                (vals * V[:, c][:, None]).reshape(n * w),
                flat_ids,
                num_segments=d + 1,
            )
            for c in range(k)
        ]
        return jnp.stack(cols, axis=1)[:d]

    safe_p, vals_p, nchunks, chunk, pad = _row_chunks(
        safe, vals, k, pad_index=d
    )
    V_p = jnp.pad(V, ((0, pad), (0, 0))).reshape(nchunks, chunk, k)

    def body(acc, xs):
        s, va, vv = xs
        contrib = (va[:, :, None] * vv[:, None, :]).reshape(chunk * w, k)
        return acc + jax.ops.segment_sum(
            contrib, s.reshape(-1), num_segments=d + 1
        ), None

    out, _ = jax.lax.scan(
        body,
        jnp.zeros((d + 1, k), dtype=V.dtype),
        (safe_p, vals_p, V_p),
    )
    return out[:d]


def _gram_tile(val_dtype) -> int:
    """Column tile of the accumulating syrk: 1,024 for bfloat16 slabs, 512
    for float32 ones (``pallas_ops._strided_ti``)."""
    return 1024 if jnp.dtype(val_dtype) == jnp.bfloat16 else 512


def gram_pad_dim(d: int, val_dtype) -> int:
    """Column padding for :func:`sparse_gram_stream`'s dense slabs: round d
    up to the accumulating-syrk column tile (zero columns contribute zero
    Gramian rows/cols, and zero-initialized solver blocks stay exactly
    zero, so callers may solve on the padded shape and slice). ``d`` is
    the width of the chunk's OWN columns: an intercept learnt as a border
    (:func:`sparse_gram_fold`, ``border=True``) adds no column, so a
    hashing width that is a multiple of the tile pads to itself — one
    column more would cost a whole tile row of the fold."""
    tile = _gram_tile(val_dtype)
    return -(-d // tile) * tile


def gram_tile_pairs(d: int, val_dtype) -> int:
    """Upper-triangle tile pairs one accumulate call multiplies at width
    ``d``: nt·(nt + 1)/2 over the nt column tiles of :func:`gram_pad_dim`
    (136 at 16,384 bfloat16 columns, 153 at 16,385)."""
    nt = gram_pad_dim(d, val_dtype) // _gram_tile(val_dtype)
    return nt * (nt + 1) // 2


def sparse_gram_stream(
    chunk_fn,
    num_chunks: int,
    d: int,
    k: int,
    use_pallas: bool = False,
    val_dtype=jnp.float32,
    pipeline: bool = True,
    border: bool = False,
):
    """Fold (G = AᵀA, AᵀY, ΣY²) over padded-COO row chunks — the sparse
    arm of the out-of-core streaming tier (parallel/streaming.py).

    ``chunk_fn(cid)`` returns ``(indices (c, w) int, values (c, w), Y
    (c, k))`` for chunk ``cid`` — sliced from resident (possibly
    int16/bf16-compressed) buffers, or REGENERATED/loaded per chunk so the
    full dataset never exists on device. Negative indices are inactive
    lanes.

    Each chunk is DENSIFIED into a (c, d_pad) slab and folded through the
    accumulating symmetric Pallas kernel. Deliberately so: at TPU rates —
    dense bf16 GEMM ~175 TF/s vs ~1e8 random accesses/s — the ~200
    "wasted" multiplies per zero at Amazon sparsity (0.005) still beat
    per-element gather/scatter by an order of magnitude for AᵀA, and the
    L-BFGS iterations on the folded G then cost no data pass at all
    (ops/learning/lbfgs.py::_lbfgs_gram_core). The densify itself makes no
    random access either: the slab is WRITTEN by a one-hot contraction on
    the MXU (``ops/sparse_densify.py``), at the rate the chip writes its bytes,
    where a scatter-add into it ran at that 1e8 a second. This is the same
    per-partition Gramian + treeReduce pattern as the dense tier
    (BlockWeightedLeastSquares.scala:177-313), with densify-then-syrk as
    the per-partition kernel.

    Returns (G, AtY, yty) at d_pad = :func:`gram_pad_dim` (slice [:d] to
    drop the padding) — with ``border`` the four pieces of
    :func:`sparse_gram_fold`'s bordered carry, G mirrored. Traceable —
    call under jit. For dispatch-bounded
    SEGMENTED folding (a long chunk stream run as one multi-minute program
    can be neither checkpointed nor cancelled), use :func:`sparse_gram_fold`
    over cid ranges and :func:`gram_finalize` once at the end.
    ``pipeline`` is the double-buffer knob of :func:`sparse_gram_fold` —
    pass False when an extra resident chunk slab would bust HBM (e.g. the
    bench's resident-capacity probe beside a 9.8 GB COO).
    """
    G, *rest = sparse_gram_fold(
        None, jnp.arange(num_chunks), chunk_fn, d, k,
        use_pallas=use_pallas, val_dtype=val_dtype, pipeline=pipeline,
        border=border,
    )
    return (gram_finalize(G), *rest)


def sparse_gram_init(d: int, k: int, val_dtype=jnp.float32,
                     border: bool = False):
    """Zero (G_raw, AtY, yty) carry for :func:`sparse_gram_fold`; with
    ``border`` AtY is one column wider (the column sums Xᵀ1 ride it) and
    a fourth piece, 1ᵀY (k,), follows."""
    d_pad = gram_pad_dim(d, val_dtype)
    carry = (
        jnp.zeros((d_pad, d_pad), jnp.float32),
        jnp.zeros((d_pad, k + int(border)), jnp.float32),
        jnp.zeros((), jnp.float32),
    )
    return carry + (jnp.zeros((k,), jnp.float32),) if border else carry


@jax.named_scope("ks.sparse_gram_acc")  # the fold's one mirror
def gram_finalize(G):
    """Mirror the accumulated upper triangle into a full symmetric G."""
    return jnp.triu(G) + jnp.triu(G, 1).T


@jax.named_scope("ks.sparse_gram_acc")  # the chunk loop's own copies of its carry too
def sparse_gram_fold(
    carry,
    cids,
    chunk_fn,
    d: int,
    k: int,
    use_pallas: bool = False,
    val_dtype=jnp.float32,
    pipeline: bool = True,
    border: bool = False,
):
    """Fold the chunk ids ``cids`` into the (G_raw, AtY, yty) carry.

    ``carry=None`` starts fresh (:func:`sparse_gram_init`). G_raw carries
    the accumulating-syrk upper-triangle contract — call
    :func:`gram_finalize` after the LAST fold. Traceable.

    The fold is generic over the chunk's columns (``d`` of them) and its
    target columns (``k``): a caller that wants an intercept may lane a
    ones column as the last of its own ``d`` and pay a column for it.
    ``border=True`` is the other way to the same normal equations: the
    chunk hands ``[Y, live]`` — k + 1 target columns, the last the 0/1
    mask of the rows it folds — and the ones column never enters the
    slab. The correlation the chunk step computes anyway then returns
    ``Xᵀ[Y, 1] = [XᵀY, s]`` (AtY is (d_pad, k + 1), its last column the
    column sums), a fourth carry piece takes ``liveᵀY = 1ᵀY`` (k,), and
    ``yty`` stays the sum over Y alone: with the fit's n these are the
    last row and column of the (d + 1)-wide Gramian, to the bit where
    the sums are exact — ``lbfgs._lbfgs_gram_core`` solves the bordered
    system from them. At a width that is a multiple of the column tile
    the appended column would cost a whole tile row (17 of 153 pairs at
    d = 16,384); the border costs one more of the 128 lanes the
    correlation's operand is padded to.

    Two chunk-loop structures (identical results — same chunk order, same
    per-chunk arithmetic):

    - ``pipeline=True`` (default): the scan carry holds the NEXT chunk's
      densified slab, so each step folds slab k while regenerating +
      densifying slab k+1 — the two are data-independent inside one step,
      which hands the scheduler the regen work (VPU) and the densify's
      small one-hot products to place around the accumulating syrk, the
      device-compute analog of ``data/prefetch.py``'s host-side double
      buffer. Costs one extra resident chunk slab (c × d_pad of
      ``val_dtype``).
    - ``pipeline=False``: the round-5 serial body (regen → densify →
      fold per step); one slab resident. Use when the extra slab busts
      HBM (resident-capacity probes).

    When ``use_pallas`` and the slab is tile-aligned
    (:func:`~keystone_tpu.ops.pallas_ops.gram_corr_acc_ok`), the chunk
    step is ONE accumulating Pallas kernel — syrk + correlation fused
    (:func:`~keystone_tpu.ops.pallas_ops.gram_corr_sym_acc`), so the
    separate AᵀY GEMM's full re-read of the slab from HBM disappears.
    """
    from keystone_tpu.ops import pallas_ops
    from keystone_tpu.ops.sparse_densify import densify_rows

    if carry is None:
        carry = sparse_gram_init(d, k, val_dtype, border)
    d_pad = carry[0].shape[0]

    @jax.named_scope("ks.sparse_densify")  # names the phase in a device profile
    def densify_chunk(cid):
        indices, values, Yc = chunk_fn(cid)
        return densify_rows(indices, values, d, d_pad, val_dtype, use_pallas), Yc

    # Fused-kernel eligibility is static (shapes only): probe the slab
    # shape abstractly so the decision never depends on a chunk id.
    slab_shape = jax.eval_shape(
        densify_chunk, jax.ShapeDtypeStruct((), jnp.asarray(cids).dtype)
    )[0]
    fused = use_pallas and pallas_ops.gram_corr_acc_ok(slab_shape)

    @jax.named_scope("ks.sparse_gram_acc")
    def fold_slab(carry, dense, Yc):
        G, AtY, yty, *ysum = carry
        if fused:
            G, AtY = pallas_ops.gram_corr_sym_acc(G, AtY, dense, Yc)
        else:
            if use_pallas and pallas_ops.gram_acc_ok(dense):
                G = pallas_ops.gram_sym_acc(G, dense)
            else:
                G = G + jax.lax.dot_general(
                    dense, dense, (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
            AtY = AtY + jax.lax.dot_general(
                dense, Yc.astype(dense.dtype), (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
        Yf = Yc.astype(jnp.float32)
        if border:
            # liveᵀY as the correlation takes XᵀY: targets in the slab's
            # type, float32 sums — the appended column's row of AtY.
            Yq = Yc.astype(dense.dtype)
            ysum = [ysum[0] + jax.lax.dot_general(
                Yq[:, k], Yq[:, :k], (((0,), (0,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32,
            )]
            Yf = Yf[:, :k]
        return (G, AtY, yty + jnp.sum(Yf * Yf), *ysum)

    cids = jnp.asarray(cids)
    num = int(cids.shape[0])
    if pipeline and num > 1:
        staged = densify_chunk(cids[0])

        def body(state, cid_next):
            carry, (dense, Yc) = state
            nxt = densify_chunk(cid_next)  # independent of the fold below
            return (fold_slab(carry, dense, Yc), nxt), None

        (carry, last), _ = jax.lax.scan(body, (carry, staged), cids[1:])
        return fold_slab(carry, *last)

    def body(carry, cid):
        return fold_slab(carry, *densify_chunk(cid)), None

    carry, _ = jax.lax.scan(body, carry, cids)
    return carry


@functools.partial(jax.jit, static_argnames=("d",))
def _scatter_dense(indices, values, d: int):
    """Padded-COO -> dense scatter-add (module-level jit: one executable per
    (shape, d), reused across batches)."""
    n, width = indices.shape
    dense = jnp.zeros((n, d), dtype=values.dtype)
    safe_idx = jnp.where(indices >= 0, indices, 0)
    mask = (indices >= 0).astype(values.dtype)
    rows = jnp.broadcast_to(jnp.arange(n)[:, None], (n, width))
    return dense.at[rows, safe_idx].add(values * mask)


@dataclass(frozen=True)
class Densify(Transformer):
    """Sparse batch -> dense batch (reference: Densify.scala:10-21)."""

    num_features: Optional[int] = None

    def apply(self, x):
        if isinstance(x, dict) and set(x.keys()) == {"indices", "values"}:
            d = self.num_features or int(np.max(x["indices"])) + 1
            out = np.zeros(d, dtype=np.float32)
            m = np.asarray(x["indices"]) >= 0
            out[np.asarray(x["indices"])[m]] = np.asarray(x["values"])[m]
            return jnp.asarray(out)
        return jnp.asarray(x)

    def batch_apply(self, data: Dataset) -> Dataset:
        return densify_dataset(data, self.num_features)


@dataclass(frozen=True)
class Sparsify(Transformer):
    """Dense batch -> padded-COO sparse batch (reference: Sparsify.scala:10-20)."""

    def apply(self, x):
        if isinstance(x, dict) and "indices" in x and "values" in x:
            return x  # already a sparse item: identity (mirrors Densify)
        x = np.asarray(x)
        idx = np.nonzero(x)[0]
        return {"indices": idx.astype(np.int32), "values": x[idx].astype(np.float32)}

    def batch_apply(self, data: Dataset) -> Dataset:
        if is_sparse_dataset(data):
            # Already padded-COO (e.g. the cost-model selector's
            # Sparsify->SparseLBFGS chain fitted on genuinely sparse
            # input): sparsifying is the identity.
            return data
        X = np.asarray(data.array)
        nnz_per_row = (X != 0).sum(axis=1)
        width = max(int(nnz_per_row.max()), 1)
        n = X.shape[0]
        indices = np.full((n, width), -1, dtype=np.int32)
        values = np.zeros((n, width), dtype=np.float32)
        for i in range(n):
            idx = np.nonzero(X[i])[0][:width]
            indices[i, : len(idx)] = idx
            values[i, : len(idx)] = X[i][idx]
        return Dataset({"indices": indices, "values": values}, n=data.n)


class SparseFeatureVectorizer(Transformer):
    """Map items to sparse vectors in a fixed feature space
    (reference: SparseFeatureVectorizer.scala:7-17)."""

    def __init__(self, feature_space: Dict[Any, int], max_nnz: Optional[int] = None):
        self.feature_space = feature_space
        self.num_features = len(feature_space)
        self.max_nnz = max_nnz

    @property
    def sparse_output_dim(self) -> int:
        """Declared output width — the cost-model sample collector threads
        this through as ``total_d`` so solver selection prices the true
        feature width instead of ``indices.max()+1`` over a tiny sample
        (which undershoots whenever the sample misses the top ids)."""
        space = self.feature_space.values()
        return (max(space) + 1) if space else 0

    def apply(self, item):
        pairs = sorted(
            (self.feature_space[f], v)
            for f, v in _item_pairs(item)
            if f in self.feature_space
        )
        idx = np.asarray([p[0] for p in pairs], dtype=np.int32)
        val = np.asarray([p[1] for p in pairs], dtype=np.float32)
        return {"indices": idx, "values": val}

    def batch_apply(self, data: Dataset) -> Dataset:
        return sparse_batch_from_items(
            data.to_list(), self.feature_space, self.max_nnz
        )

    def output_signature(self, sig):
        """Verifier declaration: weighted host items in, padded-COO
        sparse batch out (`sparse` kind — the dict pytree the sparse
        solvers consume)."""
        from keystone_tpu.workflow.verify import HostSig, expect_host

        sig = expect_host(sig, ("tf_dict", "ngram_counts"), self)
        return HostSig("sparse", n=sig.n, datum=sig.datum)


def _check_sparse_fit_input(est, input_sigs):
    """Shared fit-input contract for the sparse feature-space estimators:
    the DATA input must be weighted host items (a raw token stream here
    means the TermFrequency/weighting stage was dropped)."""
    from keystone_tpu.workflow.verify import HostSig, expect_host

    if input_sigs and isinstance(input_sigs[0], HostSig):
        expect_host(input_sigs[0], ("tf_dict", "ngram_counts"), est)


class CommonSparseFeatures(Estimator):
    """Keep the top-K features by document frequency, deterministic tie-break
    (reference: CommonSparseFeatures.scala:20-64)."""

    def __init__(self, num_features: int, max_nnz: Optional[int] = None):
        self.num_features = num_features
        self.max_nnz = max_nnz

    def fit(self, data: Dataset) -> SparseFeatureVectorizer:
        doc_freq: Counter = Counter()
        for i, item in enumerate(data.to_list()):
            for f, _ in _item_pairs(item):
                doc_freq[f] += 1
        # Deterministic: sort by (-count, repr) — the analog of the reference's
        # zipWithUniqueId tie-break.
        top = heapq.nsmallest(
            self.num_features, doc_freq.items(), key=lambda kv: (-kv[1], repr(kv[0]))
        )
        feature_space = {f: i for i, (f, _) in enumerate(top)}
        return SparseFeatureVectorizer(feature_space, self.max_nnz)

    def check_fit_signature(self, input_sigs):
        _check_sparse_fit_input(self, input_sigs)

    def fitted_signature(self, input_sigs):
        from keystone_tpu.workflow.verify import HostSig

        sig = input_sigs[0] if input_sigs else None
        n = getattr(sig, "n", None)
        datum = getattr(sig, "datum", False)
        return HostSig("sparse", n=n, datum=datum)


class AllSparseFeatures(Estimator):
    """Use every observed feature (reference: AllSparseFeatures.scala:15-27)."""

    def __init__(self, max_nnz: Optional[int] = None):
        self.max_nnz = max_nnz

    def fit(self, data: Dataset) -> SparseFeatureVectorizer:
        seen = {}
        for item in data.to_list():
            for f, _ in _item_pairs(item):
                if f not in seen:
                    seen[f] = len(seen)
        return SparseFeatureVectorizer(seen, self.max_nnz)

    def check_fit_signature(self, input_sigs):
        _check_sparse_fit_input(self, input_sigs)

    def fitted_signature(self, input_sigs):
        from keystone_tpu.workflow.verify import HostSig

        sig = input_sigs[0] if input_sigs else None
        n = getattr(sig, "n", None)
        datum = getattr(sig, "datum", False)
        return HostSig("sparse", n=n, datum=datum)
