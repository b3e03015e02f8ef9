"""Estimator API over the out-of-core streaming least-squares tier.

``StreamingFeaturizedLeastSquares`` is the pipeline-facing form of
``parallel.streaming``: the featurizer lives INSIDE the estimator, so the
fit generates features per row tile and folds them into the (d, d) normal
equations — the feature matrix never materializes (72 GB at the real
TIMIT geometry vs 16 GB of HBM). The fitted model applies the same
featurizer tile-wise. This is the user-facing handle on the BENCH_r04
headline path and on the reference's streaming-by-construction substrate
(CsvDataLoader.scala:10-31 lazy rows; per-partition Gramian accumulation,
BlockWeightedLeastSquares.scala:177-313).

Default semantics match ``BlockLeastSquaresEstimator``
(BlockLinearMapper.scala:224-243): features and labels are mean-centered
(the column sums accumulate in the same tile pass as the Gramian — a
rank-1 correction, not a second data pass) and the model carries the
intercept. ``center=False`` gives the raw-BCD semantics of
``linalg.bcd_least_squares`` instead.
"""

from __future__ import annotations

import logging
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from keystone_tpu import obs
from keystone_tpu.data import Dataset
from keystone_tpu.parallel import mesh as mesh_lib
from keystone_tpu.parallel import streaming
from keystone_tpu.workflow import LabelEstimator, Transformer

logger = logging.getLogger("keystone_tpu.streaming")


class StreamingFeaturizedLinearModel(Transformer):
    """Apply featurize + block weights tile-wise (features never resident).

    A centered fit supplies (fmean, ymean); predictions are then
    (F − fmean) @ W + ymean, which folds into the single affine offset
    ymean − fmean @ W_flat — BlockLinearMapper's model shape without a
    second pass over the features.

    ``d_in`` (when known) makes the model tolerant of graph position:
    fed RAW rows (width d_in) it featurizes tile-wise; fed
    ALREADY-FEATURIZED rows (width d_feat — e.g. a saved-state reuse in a
    later pipeline whose featurize nodes are intact) it applies the
    weights directly. The fused optimizer rewrite needs this because the
    same fitted transformer serves both rewired (raw-input) and original
    (featurized-input) apply sites.
    """

    def __init__(self, featurize, W_stack, tile_rows: int,
                 fmean=None, ymean=None, d_in: Optional[int] = None):
        self.featurize = featurize
        self.W_stack = jnp.asarray(W_stack)
        self.tile_rows = tile_rows
        self.d_in = d_in
        self.fmean = None if fmean is None else jnp.asarray(fmean)
        self.ymean = None if ymean is None else jnp.asarray(ymean)
        Wf = self.W_stack.reshape(-1, self.W_stack.shape[2])
        self.offset = (
            None if self.ymean is None
            else self.ymean - self.fmean.astype(jnp.float32) @ Wf
        )

    @property
    def d_feat(self) -> int:
        return self.W_stack.shape[0] * self.W_stack.shape[1]

    def _featurize_for(self, width: int):
        if self.d_in is None or width == self.d_in:
            return self.featurize
        if width == self.d_feat:
            return _identity_featurize
        raise ValueError(
            f"input width {width} matches neither raw d_in={self.d_in} "
            f"nor d_feat={self.d_feat}"
        )

    def apply(self, x):
        x = jnp.asarray(x)
        F = self._featurize_for(x.shape[-1])(x[None, :])
        Wf = self.W_stack.reshape(-1, self.W_stack.shape[2])
        out = (F.astype(jnp.float32) @ Wf)[0]
        return out if self.offset is None else out + self.offset

    def batch_apply(self, data: Dataset) -> Dataset:
        X = jnp.asarray(data.array)
        preds = streaming.streaming_predict(
            X, self.W_stack, self._featurize_for(X.shape[-1]),
            self.tile_rows,
        )
        if self.offset is not None:
            preds = preds + self.offset
        return Dataset(preds, n=data.n, mesh=data.mesh)._rezero_padding()


class StreamingFeaturizedLeastSquares(LabelEstimator):
    """Featurize-inside-the-fit block least squares (the streaming tier).

    ``featurize``: traceable ``(rows, d_in) -> (rows, d_feat)`` array
    function (e.g. a cosine random-feature bank). The fit is ONE compiled
    program per device (tile scan -> Gramian fold -> BCD epochs on the
    normal equations); sharded input runs the mesh form (per-device folds
    + one psum round, the solve replicated) — the same program keyed the
    same way, a bank's arrays its operands, so a sweep over new pipelines
    compiles once on a mesh as on one device. ``tile_rows=None`` sizes
    tiles to a ~2 GB feature slab. The fold adds the upper block-triangle
    of each tile's FᵀF alone, mirrored once a fit (``estimator.fit`` says
    ``gram="sym_dot"`` and in how many panels, ``gram_panels``).
    """

    def __init__(
        self,
        featurize: Callable,
        d_feat: int,
        block_size: int,
        num_iter: int = 1,
        lam: float = 0.0,
        tile_rows: Optional[int] = None,
        feat_itemsize: int = 4,
        center: bool = True,
    ):
        self.featurize = featurize
        self.d_feat = d_feat
        self.block_size = block_size
        self.num_iter = num_iter
        self.lam = lam
        self.tile_rows = tile_rows or streaming.pick_tile_rows(
            d_feat, feat_itemsize
        )
        self.center = center

    @property
    def weight(self) -> int:
        return self.num_iter + 1

    def device_fit_fn(self):
        """Fit-fusion contract (workflow/fusion.py): upstream transform +
        the internal tile-scanned featurize/Gramian/BCD program compile as
        ONE dispatch. F here is the estimator's INPUT (the upstream
        program's output, typically narrow raw-ish rows) — the internal
        cosine features still materialize only one tile slab at a time.
        The featurizer rides as TRACED DeviceFit operands (the
        BankFeaturize contract), so its arrays never embed as HLO
        constants and λ-sweeps over same-shape banks share one fused
        executable. A raw callable has no operand form — it could only
        ride inside the kept program, arrays and all — so its fit is not
        fused: it featurizes the materialized upstream rows."""
        from keystone_tpu.parallel.streaming import BankFeaturize, _fit_core
        from keystone_tpu.workflow.fusion import DeviceFit

        bank = self.featurize
        if not isinstance(bank, BankFeaturize):
            return None
        bank_type, bank_key = type(bank), bank.static_key()
        d_feat, block_size, num_iter = self.d_feat, self.block_size, self.num_iter
        tile_rows, center = self.tile_rows, self.center

        def fit_fn(F, Y, n_true: int, lam, *bank_params):
            W, _, _, fmean, ymean = _fit_core(
                F, Y,
                lambda X_t: bank_type.apply_bank(bank_key, bank_params, X_t),
                d_feat, min(tile_rows, F.shape[0]), block_size, lam, num_iter,
                False, n_true if n_true != F.shape[0] else None, None, center,
            )
            return W, fmean, ymean

        def build(params):
            W, fmean, ymean = params
            _note_gram(d_feat)
            return StreamingFeaturizedLinearModel(
                bank, W, tile_rows, fmean=fmean, ymean=ymean,
            )

        return DeviceFit(
            fit_fn, build,
            operands=(jnp.asarray(self.lam, jnp.float32),) + tuple(bank.params),
            program_key=(
                "StreamingFLS", d_feat, block_size, num_iter, tile_rows,
                center, bank_type, bank_key,
            ),
        )

    def fit(self, data: Dataset, labels: Dataset) -> StreamingFeaturizedLinearModel:
        X = jnp.asarray(data.array)
        Y = jnp.asarray(labels.array)
        mesh = data.mesh
        if mesh is None or not any(s > 1 for s in dict(mesh.shape).values()):
            mesh = None
        shards = 1 if mesh is None else mesh_lib.axis_size(
            mesh, mesh_lib.DATA_AXIS)
        rows_local = X.shape[0] // shards
        kw = dict(
            featurize=self.featurize, d_feat=self.d_feat,
            tile_rows=min(self.tile_rows, max(rows_local, 1)),
            block_size=self.block_size, lam=self.lam,
            num_iter=self.num_iter, mesh=mesh,
            valid=int(data.n) if data.n != X.shape[0] else None,
        )
        attrs = dict(rows=int(X.shape[0]), tile_rows=kw["tile_rows"])
        _note_gram(self.d_feat)
        if mesh is not None:
            attrs.update(mesh_shape=tuple(mesh.devices.shape),
                         rows_local=rows_local)
            psum_bytes = mesh_psum_bytes(self.d_feat, Y.shape[-1], self.center)
            obs.set_on_open("estimator.fit", engine="stream_mesh",
                            devices=shards, psum_bytes=psum_bytes)
            obs.counter_track("mesh.psum_bytes", psum_bytes)
        # The one program of a streamed fit (``_streaming_fit_bank``, on one
        # device or over the mesh the rows lie on): its dispatch, and
        # whatever tracing or compiling it causes.
        fmean = ymean = None
        with obs.span("solver.stream_fit", **attrs):
            if self.center:
                W, fmean, ymean, _ = streaming.streaming_bcd_fit_centered(
                    X, Y, **kw
                )
            else:
                W, _, _ = streaming.streaming_bcd_fit(X, Y, **kw)
        return StreamingFeaturizedLinearModel(
            self.featurize, W, self.tile_rows, fmean=fmean, ymean=ymean,
        )


def _note_gram(d_feat: int) -> None:
    """What ``estimator.fit`` says of a streamed fit's d x d Gramian: the
    fold adds a tile's upper block-triangle alone (``gram``, the block
    tier's constant) in ``gram_panels`` panels of columns
    (``streaming._tile_update``)."""
    obs.set_on_open("estimator.fit", gram=streaming.BLOCK_GRAM,
                    gram_panels=streaming.gram_panels(d_feat))


def mesh_psum_bytes(d_feat: int, k: int, center: bool = True) -> int:
    """Bytes one device hands the mesh fit's one all-reduce round: the
    float32 Gramian, FᵀY and Σy², and the column sums of a centred fit."""
    return 4 * (d_feat * d_feat + d_feat * k + 1
                + ((d_feat + k) if center else 0))


class CosineBankFeaturize(streaming.BankFeaturize):
    """Cosine random-feature bank as a :class:`BankFeaturize`: the bank
    arrays ride as jit operands, so every streamed fit over any bank of
    the same SHAPE shares one compiled program (λ-sweeps and pipeline
    re-optimizations never recompile the tile scan), and a TIMIT-scale
    bank never embeds as an HLO constant. Uses the fused Pallas cosine
    kernel when safely dispatchable (same recipe as the bench headline).
    """

    def __init__(self, Wrf_flat, brf_flat, feat_dtype=jnp.float32):
        from keystone_tpu.ops import pallas_ops

        self.Wrf = jnp.asarray(Wrf_flat)
        self.brf = jnp.asarray(brf_flat)
        self.feat_dtype = jnp.dtype(feat_dtype)
        self.use_pallas = bool(pallas_ops.pallas_direct_ok(self.Wrf))

    @property
    def params(self):
        return (self.Wrf, self.brf)

    def static_key(self) -> tuple:
        return (str(self.feat_dtype), self.use_pallas)

    @classmethod
    def apply_bank(cls, static_key, params, X_t):
        from keystone_tpu.ops import pallas_ops

        feat_dtype, use_pallas = jnp.dtype(static_key[0]), static_key[1]
        Wrf, brf = params
        if use_pallas:
            return pallas_ops.cosine_features(
                X_t, Wrf, brf,
                compute_dtype=feat_dtype, out_dtype=feat_dtype,
            )
        return jnp.cos(
            X_t.astype(jnp.float32) @ Wrf.T + brf
        ).astype(feat_dtype)


def cosine_bank_featurize(Wrf_flat, brf_flat, feat_dtype=jnp.float32):
    """Build a :class:`CosineBankFeaturize` (kept as the public factory)."""
    return CosineBankFeaturize(Wrf_flat, brf_flat, feat_dtype)


def _identity_featurize(X_t):
    """Module-level identity featurize: stable jit identity for the
    already-featurized (resident) fallback of the streaming choice."""
    return X_t


def _source_d_in(src) -> int:
    """Row width of a shard source's DATA field (view or paired form) —
    cheap metadata, no segment load or pairing construction. Raises the
    same TypeError as ``_paired_source`` for non-dense sources (e.g. a
    COOShardSource), so the deliberate guard is what callers hit."""
    width = getattr(src, "width", None)
    if width is None:
        width = getattr(src, "d_in", None)
    if width is None:
        raise TypeError(
            f"cannot stream a dense fit from shard source "
            f"{type(src).__name__}"
        )
    return int(width)


def _paired_source(data: Dataset, labels: Dataset):
    """Assemble the (X_seg, Y_seg, valid_rows) segment source a
    shard-backed fit folds over. The common spill-path case — data and
    labels are views over ONE set of disk shards — costs zero extra
    reads; resident labels (they usually fit host RAM even when rows
    don't) are sliced per segment."""
    from keystone_tpu.data.prefetch import (
        DenseShardSource,
        DenseShardView,
        PairedDenseSource,
        ResidentDenseSource,
    )

    def _same_provider(a, b):
        """Same segment provider: identical object, or disk-shard sources
        over the same directory (distinct DiskDenseShards handles on one
        shard set are equivalent)."""
        if a is b:
            return True
        sa, sb = getattr(a, "shards", None), getattr(b, "shards", None)
        if sa is None or sb is None:
            return False
        if sa is sb:
            return True
        da, db = getattr(sa, "directory", None), getattr(sb, "directory", None)
        return da is not None and da == db

    src = data.shard_source
    if isinstance(src, DenseShardView):
        if (
            labels is not None
            and labels.is_shard_backed
            and isinstance(labels.shard_source, DenseShardView)
            and labels.shard_source.field == "y"
            and _same_provider(labels.shard_source.paired, src.paired)
        ):
            # Field check rides in PairedDenseSource too: a swapped
            # (data, labels) pair must raise, never silently fit the
            # shards' stored labels against themselves.
            return PairedDenseSource(src)
        if (
            labels is not None
            and labels.is_shard_backed
            and isinstance(labels.shard_source, DenseShardView)
            and labels.shard_source.field == "x"
        ):
            raise ValueError(
                "labels is a rows ('x') shard view — pass the labels "
                "('y') view (a duplicated/swapped pair would silently "
                "fit rows against rows)"
            )
        if labels is None:
            raise ValueError("shard-backed fit needs labels")
        return PairedDenseSource(src, np.asarray(labels.array)[: labels.n])
    if isinstance(src, (DenseShardSource, PairedDenseSource,
                        ResidentDenseSource)):
        # The source already delivers (X_seg, Y_seg, valid) triples with
        # its own embedded labels. Silently fitting against those while
        # the caller passed DIFFERENT labels would train the wrong model
        # with no error — accept only labels that view the same source.
        if labels is not None:
            lsrc = (
                labels.shard_source if labels.is_shard_backed else None
            )
            lbase = (
                lsrc.paired if isinstance(lsrc, DenseShardView) else lsrc
            )
            base = getattr(src, "paired", src)
            same = lsrc is src or (
                lbase is not None and _same_provider(lbase, base)
            )
            if not same:
                raise ValueError(
                    "data's shard source embeds its own labels; pass the "
                    "matching labels view of the same shards (unrelated "
                    "labels would be silently ignored)"
                )
        return src
    raise TypeError(
        f"cannot stream a fit from shard source {type(src).__name__}"
    )


def _fit_paired_source(source, featurize, d_feat: int, block_size: int,
                       lam, num_iter: int, center: bool,
                       prefetch_depth: int = 2, checkpoint=None,
                       ) -> "StreamingFeaturizedLinearModel":
    """Shared disk-tier fit body: prefetched segment folds -> centered
    BCD on the normal equations -> the same affine model every streaming
    tier returns (existing streaming parity tolerances apply).
    ``checkpoint`` (a CheckpointSpec / directory; None consults
    ``KEYSTONE_CHECKPOINT_DIR``) makes the fold resumable — a killed fit
    re-run with the same spec continues from its last snapshot,
    bit-identically (docs/reliability.md)."""
    _note_gram(d_feat)
    W, fmean, ymean, _ = streaming.streaming_bcd_fit_segments(
        source, bank=streaming.as_bank(featurize), d_feat=d_feat,
        block_size=block_size, lam=lam, num_iter=num_iter, center=center,
        prefetch_depth=prefetch_depth, checkpoint=checkpoint,
    )
    return StreamingFeaturizedLinearModel(
        featurize, W, streaming.pick_tile_rows(d_feat, 4),
        fmean=fmean, ymean=ymean,
    )


def pick_block_size(d_feat: int, hint: int) -> int:
    """Largest divisor of d_feat that is <= hint (BCD needs d % bs == 0)."""
    for b in range(min(hint, d_feat), 0, -1):
        if d_feat % b == 0:
            return b
    return 1


class ComposedDeviceFeaturize(streaming.BankFeaturize):
    """Composition of device-fusable transformers as a bank featurize:
    the members' identities are its static key, their arrays its params
    (``Transformer.device_operands`` behind the streamed tier's contract),
    so streamed fits over new members of equal identities share one
    compiled program. Holds the members only (picklable — the save
    contract)."""

    def __init__(self, members):
        from keystone_tpu.workflow.fusion import chain_operands

        self.members = list(members)
        self._key, self._params = chain_operands(self.members)

    @property
    def params(self):
        return self._params

    def static_key(self) -> tuple:
        return self._key

    @classmethod
    def apply_bank(cls, static_key, params, X_t):
        from keystone_tpu.workflow.fusion import chain_apply

        return chain_apply(static_key, params, X_t)


def _extract_bank(members) -> Optional[CosineBankFeaturize]:
    """Recognize the cosine-featurizer shapes the optimizer produces and
    turn them into a :class:`CosineBankFeaturize` (the TIMIT composition —
    gather of CosineRandomFeatures branches + VectorCombiner — is exactly
    this after GatherFusionRule). Not for program keys (any members
    compose to a bank, :class:`ComposedDeviceFeaturize`): the Pallas tile
    kernel and BlockStreamedLeastSquares' per-block bank slices need the
    bank ITSELF (ROADMAP D12)."""
    from keystone_tpu.ops.stats import CosineRandomFeaturesModel
    from keystone_tpu.ops.util import VectorCombiner
    from keystone_tpu.workflow.fusion import FusedGatherTransformer

    if len(members) != 1:
        return None
    m = members[0]
    if isinstance(m, CosineRandomFeaturesModel):
        return CosineBankFeaturize(m.W, m.b)
    if isinstance(m, FusedGatherTransformer):
        if not isinstance(m.combiner, VectorCombiner):
            return None
        rfs = []
        for br in m.branches:
            if len(br) != 1 or not isinstance(br[0], CosineRandomFeaturesModel):
                return None
            rfs.append(br[0])
        return CosineBankFeaturize(
            jnp.concatenate([rf.W for rf in rfs]),
            jnp.concatenate([rf.b for rf in rfs]),
        )
    return None


class BlockStreamedLeastSquares(LabelEstimator):
    """The north-star tier as a pipeline estimator: per-block featurize →
    psum → solve → residual update (``parallel.streaming._block_sweep``),
    for geometries where even the (d, d) Gramian of the gram-streamed tier
    exceeds device memory (d ≳ 60k on a 16 GB chip). Requires a
    :class:`CosineBankFeaturize` (the residual sweep needs per-block bank
    slices). Centered by default — same BlockLeastSquares semantics as
    the other tiers (means fold into the block steps; NORTHSTAR.md).

    The fit is TWO dispatches: epoch 1, which builds the per-block
    Gramian/factor stash, and epochs 2+ in one more; the sweep's carry
    (residual, block weights, stash, block means) crosses between them as
    device arrays, the residual and the weights donated — so the host can
    put a span and a residual norm on each phase and the stash counts as
    the device memory it is. ``tile_rows`` bounds the feature slab a block
    step holds (None: a ~2 GB slab of ``block_size`` columns); ``stash`` is
    what is kept of a block's system between epochs
    (``streaming.BLOCK_STASHES``).

    Epoch 1's block Gramians F_bᵀF_b take the upper block-triangle alone,
    whatever the block and the rows: panels of columns (256 wide at a block
    of 4,096), each against the columns from its own on, plain
    ``dot_general``s at the slab's own precision, mirrored once a block
    (``streaming._gram_upper_panels``; ``estimator.fit`` says so as
    ``gram="sym_dot"``).
    """

    def __init__(
        self,
        bank: CosineBankFeaturize,
        d_feat: int,
        block_size: int,
        num_iter: int = 3,
        lam: float = 0.0,
        center: bool = True,
        tile_rows: Optional[int] = None,
        stash: str = "gram+factor",
    ):
        if not isinstance(bank, CosineBankFeaturize):
            raise TypeError(
                "BlockStreamedLeastSquares needs a CosineBankFeaturize "
                "(per-block bank slices drive the residual sweep)"
            )
        if bank.Wrf.shape[0] != d_feat:
            raise ValueError(
                f"bank rows {bank.Wrf.shape[0]} != d_feat {d_feat}"
            )
        if d_feat % block_size:
            raise ValueError(f"d_feat {d_feat} not divisible by {block_size}")
        self.bank = bank
        self.d_feat = d_feat
        self.block_size = block_size
        self.num_iter = num_iter
        self.lam = lam
        self.center = center
        self.tile_rows = tile_rows or streaming.pick_tile_rows(
            block_size, bank.feat_dtype.itemsize
        )
        self.stash = stash

    @property
    def label(self) -> str:
        return f"BlockStreamedLeastSquares({self.d_feat},{self.block_size})"

    @property
    def weight(self) -> int:
        return self.num_iter + 1

    @property
    def stash_bytes(self) -> int:
        """Device bytes of the per-block stash the fit carries."""
        return block_stash_bytes(self.d_feat, self.block_size, self.stash)

    def fit(self, data: Dataset, labels: Dataset) -> StreamingFeaturizedLinearModel:
        import jax as _jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        if data.is_shard_backed:
            # The block tier's residual sweep re-featurizes X every block
            # step — it NEEDS raw rows device-resident, so a ShardSource
            # materializes here (spilled datasets that still fit run;
            # genuinely over-RAM sets belong to the gram/disk tier, which
            # the capacity selector routes there).
            data = data.materialize()
        if labels.is_shard_backed:
            labels = labels.materialize()
        X = jnp.asarray(data.array)
        Y = jnp.asarray(labels.array)
        mesh = data.mesh
        if mesh is None or not any(
            s > 1 for s in dict(mesh.shape).values()
        ):
            # Single-device form: a 1-device mesh (psums are identities).
            mesh = mesh_lib.make_mesh(devices=_jax.devices()[:1])
            X = _jax.device_put(X, NamedSharding(mesh, P(mesh_lib.DATA_AXIS)))
            Y = _jax.device_put(Y, NamedSharding(mesh, P(mesh_lib.DATA_AXIS)))
        n_true = int(data.n) if data.n != X.shape[0] else None
        nb = self.d_feat // self.block_size
        local_rows = X.shape[0] // mesh_lib.axis_size(mesh, mesh_lib.DATA_AXIS)
        tile_rows = min(self.tile_rows, local_rows)
        # Over several tiles a step featurizes each tile twice (the sums,
        # then the update); one tile's slab serves both.
        passes = 1 if tile_rows == local_rows else 2
        obs.set_on_open(
            "estimator.fit", engine="block_stream", block_size=self.block_size,
            blocks=nb, stash=self.stash, stash_bytes=self.stash_bytes,
            gram=streaming.BLOCK_GRAM,
        )
        kw = dict(
            block_size=self.block_size, mesh=mesh, n_true=n_true,
            center=self.center, feat_dtype=self.bank.feat_dtype,
            tile_rows=tile_rows, use_pallas=self.bank.use_pallas,
            stash=self.stash,
        )

        def phase_done(residual_sq, epoch_from: int, epoch_to: int) -> None:
            """The host's one wait of a phase: the read of ‖R‖², a scalar
            the phase's program returns."""
            with obs.span("executor.drain", site="block_epoch",
                          epoch_from=epoch_from, epoch_to=epoch_to):
                residual_sq = float(residual_sq)
            steps = (epoch_to - epoch_from + 1) * nb
            obs.counter_track("block.steps", steps)
            obs.counter_track(
                "block.rows_featurized", steps * passes * int(data.n)
            )
            obs.counter_track("block.residual_fro", residual_sq ** 0.5)

        span = dict(blocks=nb, block_size=self.block_size, tile_rows=tile_rows)
        with obs.span("solver.block_epoch", epoch_from=1, epoch_to=1, **span):
            (R, W, *stashes), ymean, residual_sq = (
                streaming.block_bcd_first_epoch(
                    X, Y, self.bank.Wrf, self.bank.brf, self.lam, **kw)
            )
        phase_done(residual_sq, 1, 1)
        if self.num_iter > 1:
            with obs.span("solver.block_epoch", epoch_from=2,
                          epoch_to=self.num_iter, **span):
                R, W, residual_sq = streaming.block_bcd_later_epochs(
                    R, W, stashes, X, self.bank.Wrf, self.bank.brf,
                    self.lam, epochs=self.num_iter - 1, **kw)
            phase_done(residual_sq, 2, self.num_iter)
        fmean = stashes[2].reshape(self.d_feat) if self.center else None
        return StreamingFeaturizedLinearModel(
            self.bank, W,
            streaming.pick_tile_rows(self.d_feat, 4),
            fmean=fmean, ymean=ymean if self.center else None,
        )


def block_stash_bytes(d_feat: int, block_size: int, stash: str) -> int:
    """Bytes of the block tier's per-block stash: d/bs float32 (bs, bs)
    factors, and as many Gramians under ``"gram+factor"``."""
    return (8 if stash == "gram+factor" else 4) * d_feat * block_size


class StreamingLeastSquaresChoice(LabelEstimator):
    """The cost model's streaming-tier selection for
    :class:`~keystone_tpu.ops.learning.cost.LeastSquaresEstimator`.

    When the resident solvers' operands exceed device memory, ``optimize``
    returns this choice; the optimizer's StreamedFitFusionRule then binds
    the upstream featurize program INTO the fit (``fuse_with_members``),
    producing the out-of-core tier — featurize per row tile, Gramian
    fold, centered BCD (BlockLeastSquaresEstimator semantics). Fitting it
    DIRECTLY (no fusable upstream) tile-streams the already-resident
    features through the same solver: correct, but without the memory
    win, since the input had to materialize to reach it.

    Cost model: one streamed data pass building the normal equations
    (the Exact solver's n·d·(d+k) flops — LinearMapper.scala:100-115)
    plus ``num_iter`` Gramian-space epochs, at a streaming overhead
    factor, so resident solvers win whenever they fit.
    """

    streamed_fit_fusable = True
    # Streamed fits pay the full normal-equations syrk plus per-tile
    # featurize regeneration; measured on-chip (BENCH_r04) the resident
    # residual-BCD solver is several times faster at the same geometry
    # when its operands fit — bias selection toward resident solvers
    # whenever the analytic models land close.
    _STREAM_OVERHEAD = 2.0

    def __init__(
        self,
        num_iter: int = 3,
        lam: float = 0.0,
        block_size_hint: int = 4096,
        center: bool = True,
    ):
        self.num_iter = num_iter
        self.lam = lam
        self.block_size_hint = block_size_hint
        self.center = center
        # Set by the owning LeastSquaresEstimator before cost evaluation
        # (bytes per RAW input row — the streamed fit keeps raw rows, not
        # features, resident).
        self.raw_row_bytes: Optional[float] = None
        # Density of the raw input (set by the owner): decides how an
        # UNSET raw_row_bytes defaults in resident_bytes. None (no owner)
        # is treated as dense — the conservative direction for a
        # feasibility cut.
        self.input_is_sparse: Optional[bool] = None
        # Feature-slab budget for the tile scan; the owner shrinks it when
        # the device budget is small so the capacity model and the actual
        # fit agree on the working set.
        self.slab_bytes: int = 2 << 30
        # Device-memory budget (set by the owner): decides the TIER —
        # gram-streamed (one data pass, needs an 8d² Gramian+factor
        # stash) vs block-streamed (the north-star program: per-block
        # Gramians only, num_iter featurize passes) for d where 8d²
        # itself exceeds the budget (~60k dims on a 16 GB chip).
        self.budget_bytes: Optional[float] = None
        # DISK-tier knobs (set by the owner when the sampled input is
        # shard-backed): raw rows then stream from disk segments, so the
        # capacity model prices staged buffers instead of n·raw resident,
        # and the fit folds over a prefetched ShardSource.
        self.data_is_shard_backed: bool = False
        self.shard_segment_bytes: Optional[float] = None
        self.prefetch_depth: int = 2
        # Reliability knob: CheckpointSpec (or directory) the disk-tier
        # fold snapshots/resumes through; None defers to the
        # KEYSTONE_CHECKPOINT_DIR env (the run.py --checkpoint-dir
        # wiring), unset = no checkpointing.
        self.checkpoint = None

    @property
    def label(self) -> str:
        return f"StreamingLeastSquaresChoice({self.num_iter},{self.lam})"

    @property
    def weight(self) -> int:
        return self.num_iter + 1

    def _gram_tier_ok(self, d_feat: int) -> bool:
        """The d-only discriminator shared by the capacity model and
        build_estimator: the gram tier needs its (d, d) Gramian + factor
        stash resident."""
        if self.budget_bytes is None:
            return True
        slab = min(
            streaming.pick_tile_rows(d_feat, 4, slab_bytes=self.slab_bytes)
            * d_feat * 4.0,
            float(self.slab_bytes),
        )
        return 8.0 * d_feat * d_feat + slab <= self.budget_bytes

    def _block_tier_plan(self, d_feat: int, fixed_bytes: float = 0.0):
        """(block size, stash) the block-streamed tier runs with, given the
        ``fixed_bytes`` it holds whatever the plan (rows, targets, residual,
        bank, weights, one slab). The configured block size is KEPT
        wherever a stash of it fits the WHOLE budget: block Gauss-Seidel
        iterates depend on the block, so a shrunk block fits another
        model. Both stashes (8·d·bs bytes) first; where they do not fit the
        Gramian stash goes (4·d·bs: ``gram @ w`` is rebuilt from the
        factor); only then does the block shrink, to the largest divisor of
        d whose factor stash fits — and ``build_estimator`` says so aloud.
        Where not even a block of one would fit, the configured block
        stays (with the factor stash alone): shrinking cannot help."""
        bs = pick_block_size(d_feat, self.block_size_hint)
        if self.budget_bytes is None:
            return bs, "gram+factor"
        room = self.budget_bytes - fixed_bytes
        for stash in streaming.BLOCK_STASHES:
            if block_stash_bytes(d_feat, bs, stash) <= room:
                return bs, stash
        cap = int(room / (4.0 * d_feat))
        if cap < 1:
            # What the fit holds beside its stash is past the budget
            # already: no block is small enough, so none is tried.
            return bs, "factor"
        return pick_block_size(d_feat, min(bs, cap)), "factor"

    def _block_fixed_bytes(self, local_rows: float, d_feat: int, k: int,
                           raw: float, bank_bytes: float) -> float:
        """What a device holds in a block-streamed fit beside the stash:
        its rows, targets and residual, the bank (the branches' and the
        joined one), block weights and means, and one block step's slab —
        (tile, bs) float32, the tile never past ``slab_bytes``, with the
        matmul's result beside it."""
        bs = pick_block_size(d_feat, self.block_size_hint)
        return (
            local_rows * (raw + 8.0 * k)
            + 2.0 * bank_bytes
            + 4.0 * d_feat * (k + 1)
            + 2.0 * min(4.0 * local_rows * bs, float(self.slab_bytes))
        )

    def build_estimator(self, featurize, d_feat: int,
                        local_rows: int = 0, k: int = 0):
        """The tier's estimator for ``featurize``. ``local_rows`` (rows a
        device holds) and ``k`` (targets), where the caller knows them,
        let the block tier reckon what it holds beside its stash."""
        gram_ok = self._gram_tier_ok(d_feat)

        def emit(winner: str, reason: str, **context) -> None:
            # The streaming tier's own cost-model decision, audited like
            # the solver selection (obs plane, ISSUE 9).
            obs.record_cost_decision(obs.CostDecision(
                decision="streaming_tier",
                winner=winner,
                candidates=[
                    {"label": "gram", "feasible": gram_ok},
                    {"label": "block",
                     "feasible": isinstance(
                         featurize, CosineBankFeaturize)},
                ],
                reason=reason,
                context={
                    "d_feat": int(d_feat),
                    "budget_bytes": self.budget_bytes,
                    "featurize": type(featurize).__name__,
                    **context,
                },
            ))

        if gram_ok:
            emit("gram", "gramian_fits_budget")
            bs = pick_block_size(d_feat, self.block_size_hint)
            return StreamingFeaturizedLeastSquares(
                featurize, d_feat=d_feat, block_size=bs,
                num_iter=self.num_iter, lam=self.lam, center=self.center,
                tile_rows=streaming.pick_tile_rows(
                    d_feat, 4, slab_bytes=self.slab_bytes
                ),
            )
        if not isinstance(featurize, CosineBankFeaturize):
            # The capacity model assumed the block tier (no d² term), but
            # only bank featurizers can drive per-block slices. Best
            # effort: run the gram tier anyway (it may exceed the budget)
            # rather than crash a fit the selector already committed to.
            logger.warning(
                "d_feat=%d: (d, d) Gramian exceeds the device budget and "
                "the block-streamed tier needs a cosine bank featurizer "
                "(got %s); falling back to the gram tier — the fit may "
                "not fit device memory", d_feat, type(featurize).__name__,
            )
            emit("gram", "block_needs_bank_featurizer")
            return StreamingFeaturizedLeastSquares(
                featurize, d_feat=d_feat,
                block_size=pick_block_size(d_feat, self.block_size_hint),
                num_iter=self.num_iter, lam=self.lam, center=self.center,
                tile_rows=streaming.pick_tile_rows(
                    d_feat, 4, slab_bytes=self.slab_bytes
                ),
            )
        configured = pick_block_size(d_feat, self.block_size_hint)
        fixed = self._block_fixed_bytes(
            local_rows, d_feat, k,
            self.raw_row_bytes or 4.0 * featurize.Wrf.shape[1],
            float(featurize.Wrf.nbytes + featurize.brf.nbytes),
        )
        bs, stash = self._block_tier_plan(d_feat, fixed)
        reason = "gramian_exceeds_budget"
        if bs != configured:
            reason = "block_shrunk_to_fit_budget"
            logger.warning(
                "d_feat=%d: a block of %d with its factor stash (%.2f GB) does "
                "not fit the %.2f GB budget beside the %.2f GB the fit holds; "
                "fitting with blocks of %d — block Gauss-Seidel iterates "
                "depend on the block, so this is NOT the configured model",
                d_feat, configured,
                block_stash_bytes(d_feat, configured, "factor") / 1e9,
                self.budget_bytes / 1e9, fixed / 1e9, bs,
            )
        emit("block", reason, block_size=int(bs),
             configured_block_size=int(configured), stash=stash,
             stash_bytes=block_stash_bytes(d_feat, bs, stash),
             fixed_bytes=float(fixed), gram=streaming.BLOCK_GRAM)
        return BlockStreamedLeastSquares(
            featurize, d_feat=d_feat, block_size=bs,
            num_iter=self.num_iter, lam=self.lam, center=self.center,
            tile_rows=streaming.pick_tile_rows(
                bs, 4, slab_bytes=self.slab_bytes
            ),
            stash=stash,
        )

    def fuse_with_members(self, members) -> "StreamedFitEstimator":
        fused = StreamedFitEstimator(members, self)
        # A pending cost-decision back-annotation (cost.py optimize)
        # follows the fit wherever it actually runs: the fused streamed
        # estimator replaces this choice in the graph, so the executor
        # stamps the measured wall through IT, not through the choice.
        ref = getattr(self, "_pending_cost_outcome", None)
        if ref is not None:
            fused._pending_cost_outcome = ref
            self._pending_cost_outcome = None
        return fused

    def fit_source(self, data: Dataset, labels: Dataset, featurize,
                   d_feat: int):
        """The DISK tier: fold the normal equations over prefetched
        shard segments (featurize applied per tile inside the fold), so
        neither host RAM nor HBM ever holds the raw rows — the
        capacity-selected path for datasets past the host budget."""
        return _fit_paired_source(
            _paired_source(data, labels), featurize, d_feat,
            block_size=pick_block_size(d_feat, self.block_size_hint),
            lam=self.lam, num_iter=self.num_iter, center=self.center,
            prefetch_depth=self.prefetch_depth,
            checkpoint=getattr(self, "checkpoint", None),
        )

    def fit(self, data: Dataset, labels: Dataset):
        from keystone_tpu.ops.sparse import Densify, is_sparse_dataset

        if data.is_shard_backed:
            return self.fit_source(
                data, labels, _identity_featurize,
                _source_d_in(data.shard_source),
            )
        if is_sparse_dataset(data):
            data = Densify().batch_apply(data)
        d_feat = int(jnp.asarray(data.array).shape[-1])
        return self.build_estimator(_identity_featurize, d_feat).fit(
            data, labels
        )

    def cost(
        self, n, d, k, sparsity, num_machines, cpu_weight, mem_weight,
        network_weight,
    ) -> float:
        """The price of the TIER ``build_estimator`` would pick at this d
        (the shared ``_gram_tier_ok`` test). Gram tier: one data pass that
        builds the d × d normal equations, then epochs on them. Block tier:
        BlockLeastSquares' n·d·(bs + k) for the epoch that builds the
        per-block Gramians, n·d·k for every later one (the stash spares
        the Gramians), and the features made anew each epoch — a d/bs-th
        of the gram tier's leading term."""
        if self._gram_tier_ok(d):
            flops = (
                n * d * (d + k) + self.num_iter * d * d * k
            ) / num_machines
            bytes_scanned = n * d / num_machines + 2.0 * d * d
            network = d * (d + k)  # the single (G, FY) psum
        else:
            bs = pick_block_size(d, self.block_size_hint)
            d_in = (self.raw_row_bytes or 0.0) / 4.0
            flops = (
                n * d * (bs + k)
                + (self.num_iter - 1) * n * d * k
                + self.num_iter * n * d * d_in
            ) / num_machines
            bytes_scanned = self.num_iter * n * d / num_machines + 2.0 * d * bs
            # a (bs, bs) Gramian a block once, a (bs, k) correlation a step
            network = d * (bs + self.num_iter * k)
        return (
            self._STREAM_OVERHEAD
            * max(cpu_weight * flops, mem_weight * bytes_scanned)
            + network_weight * network
        )

    def resident_bytes(self, n, d, k, sparsity, num_machines) -> float:
        """Capacity model of whichever TIER ``build_estimator`` would pick
        at this d (the shared ``_gram_tier_ok`` discriminator keeps the
        two consistent). Gram tier: raw rows + labels (sharded) + the
        (d, d) Gramian/factor stash + one feature slab. Block tier (the
        north-star program): raw rows + labels + residual + per-BLOCK
        Gramian/factor stash + one block slab + the bank — no d² term."""
        raw = self.raw_row_bytes
        if self.input_is_sparse:
            # Resident SPARSE input: fit() densifies before the tile scan
            # (the streamed fold featurizes dense row tiles), so the
            # resident operand is the DENSIFIED matrix — 4d bytes/row —
            # whatever the COO row width was. Pricing the COO width here
            # let this tier look feasible at geometries where its own
            # densify would OOM (found by the round-6 replay test when
            # the TPU weights made it cost-competitive with the sparse
            # gram engine).
            raw = 4.0 * d
        elif not raw:
            # Unknown raw width, dense input: the raw operand IS the full
            # f32 row — 4d bytes (the old min(d, 512) cap underestimated
            # wide-dense rows ~32x at d=16384, letting this tier look
            # feasible when the raw operand alone exceeds HBM).
            raw = 4.0 * d
        bs = min(self.block_size_hint, d)
        slab = min(
            streaming.pick_tile_rows(d, 4, slab_bytes=self.slab_bytes)
            * d * 4.0,
            float(self.slab_bytes),
        )
        if self.data_is_shard_backed:
            # Disk tier: raw rows + labels live in the shard files and
            # stream through (prefetch_depth + 1) staged segment buffers,
            # so no term scales with n. The fit ALWAYS runs the gram fold
            # here (fit_source — the block tier needs resident raw rows),
            # so price the gram-tier stash unconditionally: if its 8d²
            # Gramian busts the device budget, the disk tier honestly
            # reports infeasible rather than OOMing mid-fold.
            seg = self.shard_segment_bytes or (8192.0 * (raw + 4.0 * k))
            return (
                (self.prefetch_depth + 1) * seg
                + 8.0 * d * d
                + 8.0 * d * bs
                + slab
            )
        common = n * raw / num_machines + 4.0 * n * k / num_machines
        if self._gram_tier_ok(d):
            return (
                common
                + 8.0 * d * d      # G + diagonal-block Cholesky stash
                + 8.0 * d * bs     # diag/chol block stacks in the solve
                + slab
            )
        # bank rows ~ raw row width
        fixed = self._block_fixed_bytes(
            n / num_machines, d, k, raw, d * (raw + 4.0)
        )
        bs_b, stash = self._block_tier_plan(d, fixed)
        return fixed + block_stash_bytes(d, bs_b, stash)


class StreamedFitEstimator(LabelEstimator):
    """A capacity-selected streaming fit bound to its upstream featurize
    program (the rewrite StreamedFitFusionRule performs).

    The members' composed ``device_apply`` becomes the tile featurizer of a
    :class:`StreamingFeaturizedLeastSquares` — featurize + Gramian fold +
    centered BCD compile as one scanned program and the feature matrix
    never materializes (the cost-model-driven form of the ``--streaming``
    flag this replaces; reference analog: LeastSquaresEstimator.scala:
    59-84 picking BlockLeastSquares, whose per-partition featurize+solve
    never materializes the global matrix either). Cosine featurizer
    shapes lower to the bank-as-operand program (stable compile keys).
    """

    def __init__(self, members, choice: StreamingLeastSquaresChoice):
        self.members = list(members)
        self.choice = choice
        self._featurize = _extract_bank(self.members) or ComposedDeviceFeaturize(
            self.members
        )

    @property
    def can_serve_raw_input(self) -> bool:
        """True when the fitted model can PROVABLY disambiguate raw vs
        featurized input by width — the gate StreamedFitFusionRule checks
        before rewiring apply sites to feed raw rows. Requires a bank
        featurizer (widths known statically) with d_in != d_feat."""
        Wrf = getattr(self._featurize, "Wrf", None)
        return Wrf is not None and Wrf.shape[0] != Wrf.shape[1]

    @property
    def label(self) -> str:
        inner = " > ".join(m.label for m in self.members)
        return f"StreamedFit[{inner} -> {self.choice.label}]"

    @property
    def weight(self) -> int:
        return self.choice.weight

    def _fallback(self, data: Dataset, labels: Dataset):
        raw_width = self._raw_width(data)
        for m in self.members:
            data = m.batch_apply(data)
        model = self.choice.fit(data, labels)
        # Apply sites may have been rewired to feed RAW rows (the rule
        # rewires only when can_serve_raw_input): make the fallback model
        # width-adaptive too, or those sites would crash on a raw batch.
        if (
            self.can_serve_raw_input
            and isinstance(model, StreamingFeaturizedLinearModel)
            and raw_width is not None
        ):
            model.featurize = self._featurize
            model.d_in = raw_width
        return model

    @staticmethod
    def _raw_width(data: Dataset):
        try:
            if data.is_host:
                items = data.to_list()
                return int(np.asarray(items[0]).shape[-1]) if items else None
            return int(jnp.asarray(data.array).shape[-1])
        except Exception:
            return None

    def fit(self, data: Dataset, labels: Dataset):
        if data.is_host or labels.is_host:
            return self._fallback(data, labels)
        if data.is_shard_backed:
            return self._fit_shard_backed(data, labels)
        X = jnp.asarray(data.array)
        d_feat = int(
            jax.eval_shape(
                self._featurize,
                jax.ShapeDtypeStruct((1,) + X.shape[1:], X.dtype),
            ).shape[-1]
        )
        d_in = int(X.shape[-1])
        shards = 1 if data.mesh is None else mesh_lib.axis_size(
            data.mesh, mesh_lib.DATA_AXIS)
        est = self.choice.build_estimator(
            self._featurize, d_feat, local_rows=X.shape[0] // shards,
            k=int(jnp.asarray(labels.array).shape[-1]),
        )
        model = est.fit(data, labels)
        if d_in == d_feat:
            # Width cannot disambiguate raw vs featurized input. The rule
            # never rewires apply sites in this case (can_serve_raw_input
            # is False), so every apply site featurizes upstream: the
            # model must always take the identity path.
            model.featurize = _identity_featurize
            model.d_in = None
        else:
            # d_in makes the model adaptive: rewired apply sites feed raw
            # rows (featurize-inside, tile-wise); saved-state reuse in
            # later pipelines with intact featurize nodes feeds
            # featurized rows.
            model.d_in = d_in
        return model

    def _fit_shard_backed(self, data: Dataset, labels: Dataset):
        """The out-of-core pipeline fit: raw rows stream from disk shards
        through the prefetcher, the bound featurize program runs per tile
        inside the fold, and the feature matrix never materializes at ANY
        tier — disk, host, or HBM."""
        d_in = _source_d_in(data.shard_source)
        d_feat = int(
            jax.eval_shape(
                self._featurize,
                jax.ShapeDtypeStruct((1, d_in), jnp.float32),
            ).shape[-1]
        )
        model = self.choice.fit_source(data, labels, self._featurize, d_feat)
        if d_in == d_feat:
            model.featurize = _identity_featurize
            model.d_in = None
        else:
            model.d_in = d_in
        return model
