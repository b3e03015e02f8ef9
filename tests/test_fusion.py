"""Stage fusion (workflow/fusion.py): chains of row-local device
transformers compile into ONE XLA program via the whole-pipeline optimizer's
final batch — the TPU-specific optimizer transform (one dispatch per chain,
XLA fusing across old node boundaries, vs the reference's one Spark stage
per node)."""

import jax.numpy as jnp
import numpy as np
import pytest

from keystone_tpu.data import Dataset
from keystone_tpu.ops.stats import (
    LinearRectifier,
    NormalizeRows,
    PaddedFFT,
    RandomSignNode,
    SignedHellingerMapper,
)
from keystone_tpu.ops.util import Cacher, MaxClassifier
from keystone_tpu.workflow import Pipeline
from keystone_tpu.workflow.fusion import (
    FusedBatchTransformer,
    StageFusionRule,
    fusable,
)

rng = np.random.default_rng(0)


def _chain_pipeline():
    return (
        RandomSignNode.create(64, seed=3)
        .to_pipeline()
        .and_then(PaddedFFT())
        .and_then(LinearRectifier(0.0))
    )


def _unfused_result(X):
    out = Dataset.of(X)
    for t in (
        RandomSignNode.create(64, seed=3),
        PaddedFFT(),
        LinearRectifier(0.0),
    ):
        out = t.batch_apply(out)
    return np.asarray(out.array)


class TestFusedBatchTransformer:
    def test_composed_matches_sequential(self):
        X = rng.normal(size=(16, 64)).astype(np.float32)
        members = [RandomSignNode.create(64, seed=3), PaddedFFT(), LinearRectifier(0.0)]
        fused = FusedBatchTransformer(members)
        out = np.asarray(fused.batch_apply(Dataset.of(X)).array)
        np.testing.assert_allclose(out, _unfused_result(X), atol=1e-5)

    def test_fitted_pipeline_with_fused_chain_pickles(self, tmp_path):
        # FittedPipeline.save() pickles the optimized transformer graph; the
        # fused node must survive the round trip and rebuild its jitted
        # composition on load (regression: the jitted local closure used to
        # make every fused fitted pipeline unpicklable).
        X = rng.normal(size=(12, 64)).astype(np.float32)
        fitted = _chain_pipeline().fit()
        before = np.asarray(fitted.apply(Dataset.of(X)).array)
        path = str(tmp_path / "fused.pkl")
        fitted.save(path)

        from keystone_tpu.workflow.pipeline import FittedPipeline

        loaded = FittedPipeline.load(path)
        after = np.asarray(loaded.apply(Dataset.of(X)).array)
        np.testing.assert_allclose(after, before, atol=1e-6)

    def test_single_datum_apply(self):
        x = rng.normal(size=(64,)).astype(np.float32)
        members = [RandomSignNode.create(64, seed=3), PaddedFFT(), LinearRectifier(0.0)]
        fused = FusedBatchTransformer(members)
        seq = x
        for m in members:
            seq = m.apply(seq)
        np.testing.assert_allclose(np.asarray(fused.apply(x)), np.asarray(seq), atol=1e-5)

    def test_rejects_non_fusable(self):
        from keystone_tpu.ops.nlp import Tokenizer

        with pytest.raises(ValueError):
            FusedBatchTransformer([NormalizeRows(), Tokenizer()])

    def test_padded_dataset_matches_unfused(self):
        """Mesh zero-padding: one trailing rezero (fused) must equal the
        per-stage rezeroing of the sequential chain — the row-local
        contract. Exercises a stage mapping 0 -> nonzero mid-chain
        (LinearRectifier with negative alpha)."""
        from keystone_tpu.parallel import mesh as mesh_lib

        mesh = mesh_lib.make_mesh()
        X = rng.normal(size=(13, 8)).astype(np.float32)  # pads
        members = [LinearRectifier(0.0, -0.5), NormalizeRows()]
        fused = FusedBatchTransformer(members)
        ds = Dataset.of(X).shard(mesh)
        out = fused.batch_apply(ds)
        seq = ds
        for m in members:
            seq = m.batch_apply(seq)
        np.testing.assert_allclose(
            np.asarray(out.array)[:13], np.asarray(seq.array)[:13], atol=1e-6
        )
        assert out.n == 13
        np.testing.assert_allclose(np.asarray(out.array)[13:], 0.0, atol=0)


class TestStageFusionRule:
    def test_pipeline_chain_fuses_to_one_node(self):
        pipe = _chain_pipeline()
        X = rng.normal(size=(12, 64)).astype(np.float32)
        handle = pipe.apply(Dataset.of(X))
        out = np.asarray(handle.get().array)
        np.testing.assert_allclose(out, _unfused_result(X), atol=1e-5)

        # The executed (optimized) graph is the applied data source plus
        # exactly one fused node — the three originals are gone.
        graph = handle.executor.optimized_graph
        labels = sorted(graph.get_operator(n).label for n in graph.nodes)
        assert sum(l.startswith("Fused[") for l in labels) == 1, labels
        assert len(labels) == 2, labels

    def test_cacher_is_a_fusion_barrier(self):
        # Cacher marks a prefix-published materialization point; chains must
        # not fuse across (or swallow) it.
        pipe = (
            SignedHellingerMapper()
            .to_pipeline()
            .and_then(Cacher())
            .and_then(NormalizeRows())
        )
        X = rng.normal(size=(10, 8)).astype(np.float32)
        handle = pipe.apply(Dataset.of(X))
        ref = NormalizeRows().batch_apply(
            SignedHellingerMapper().batch_apply(Dataset.of(X))
        )
        np.testing.assert_allclose(
            np.asarray(handle.get().array), np.asarray(ref.array), atol=1e-6
        )
        graph = handle.executor.optimized_graph
        labels = [graph.get_operator(n).label for n in graph.nodes]
        assert not any(l.startswith("Fused[") for l in labels), labels

    def test_branch_consumers_prevent_fusion(self):
        # A node consumed by two branches must stay materialized.
        from keystone_tpu.ops.util import VectorCombiner

        base = SignedHellingerMapper().to_pipeline()
        b1 = base.and_then(NormalizeRows())
        b2 = base.and_then(LinearRectifier(0.0))
        pipe = Pipeline.gather([b1, b2]).and_then(VectorCombiner())
        X = rng.normal(size=(6, 8)).astype(np.float32)
        out = np.asarray(pipe.apply(Dataset.of(X)).get().array)
        h = SignedHellingerMapper().batch_apply(Dataset.of(X))
        ref = np.concatenate(
            [
                np.asarray(NormalizeRows().batch_apply(h).array),
                np.asarray(LinearRectifier(0.0).batch_apply(h).array),
            ],
            axis=-1,
        )
        np.testing.assert_allclose(out, ref, atol=1e-6)

    def test_mnist_fft_branches_fuse(self):
        """The MnistRandomFFT featurizer's per-branch RandomSign -> PaddedFFT
        -> LinearRectifier chains first collapse into one fused node per
        branch (StageFusionRule), then the whole gather tree + combiner
        collapses into a single FusedGather program (GatherFusionRule) —
        the entire featurizer is ONE dispatch."""
        from keystone_tpu.pipelines.mnist_random_fft import (
            MnistRandomFFTConfig,
            build_featurizer,
        )

        cfg = MnistRandomFFTConfig(num_ffts=3, block_size=32, image_size=48)
        pipe = build_featurizer(cfg)
        X = rng.normal(size=(8, 48)).astype(np.float32)
        handle = pipe.apply(Dataset.of(X))
        out = np.asarray(handle.get().array)
        assert out.shape == (8, 3 * 32)  # 3 branches x (64-pad FFT)/2
        graph = handle.executor.optimized_graph
        labels = [graph.get_operator(n).label for n in graph.nodes]
        gathered = [l for l in labels if l.startswith("FusedGather[")]
        assert len(gathered) == 1, labels
        # Each branch's chain is visible inside the fused label.
        assert gathered[0].count(" | ") == 2, gathered

    def test_fusable_predicate(self):
        assert fusable(NormalizeRows())
        assert fusable(MaxClassifier())
        assert not fusable(Cacher())


class TestPackedFFTGather:
    """ISSUE 3: the packed-pair FFT lowering must be equality-tested
    against the per-branch composition it silently replaces, and its
    ENGAGEMENT on the MNIST shape must be pinned (the bench row states
    the packed program's flop/traffic model)."""

    def _branches(self, nb, d_in, alphas=None):
        from keystone_tpu.ops.stats import (
            LinearRectifier,
            PaddedFFT,
            RandomSignNode,
        )

        return [
            [
                RandomSignNode.create(d_in, seed=i),
                PaddedFFT(),
                LinearRectifier(0.0, alpha=(alphas[i] if alphas else 0.0)),
            ]
            for i in range(nb)
        ]

    @pytest.mark.parametrize("nb,d_in", [(2, 100), (3, 48), (4, 784)])
    def test_packed_matches_per_branch_composition(self, nb, d_in):
        from keystone_tpu.ops.stats import (
            packed_fft_gather_apply,
            packed_fft_gather_fn,
        )
        from keystone_tpu.ops.util import VectorCombiner

        branches = self._branches(nb, d_in, alphas=[0.1 * i for i in range(nb)])
        form = packed_fft_gather_fn(branches, VectorCombiner())
        assert form is not None
        hash(form[0])
        X = rng.normal(size=(16, d_in)).astype(np.float32)
        out = np.asarray(packed_fft_gather_apply(*form, jnp.asarray(X)))
        refs = []
        for br in branches:
            b = jnp.asarray(X)
            for m in br:
                b = m.device_fn()(b)
            refs.append(np.asarray(b))
        ref = np.concatenate(refs, axis=-1)
        assert out.shape == ref.shape
        np.testing.assert_allclose(out, ref, atol=1e-4)

    def test_fused_gather_engages_packed_path(self):
        from keystone_tpu.ops.util import VectorCombiner
        from keystone_tpu.workflow.fusion import FusedGatherTransformer

        fg = FusedGatherTransformer(
            self._branches(4, 64), VectorCombiner()
        )
        assert fg.uses_packed_fft
        # And the engaged program still matches the per-branch math
        # through the transformer's own batch path.
        X = rng.normal(size=(8, 64)).astype(np.float32)
        out = np.asarray(fg.batch_apply(Dataset.of(jnp.asarray(X))).array)
        refs = []
        for br in self._branches(4, 64):
            b = jnp.asarray(X)
            for m in br:
                b = m.device_fn()(b)
            refs.append(np.asarray(b))
        np.testing.assert_allclose(
            out, np.concatenate(refs, axis=-1), atol=1e-4
        )

    def test_non_matching_gather_falls_back(self):
        from keystone_tpu.ops.stats import packed_fft_gather_fn
        from keystone_tpu.ops.util import VectorCombiner
        from keystone_tpu.workflow.fusion import FusedGatherTransformer

        # Branch shape differs (no rectifier): recognizer must decline
        # and the generic composition must serve.
        branches = [
            [m for m in br[:2]] for br in self._branches(2, 32)
        ]
        assert packed_fft_gather_fn(branches, VectorCombiner()) is None
        fg = FusedGatherTransformer(branches, VectorCombiner())
        assert not fg.uses_packed_fft
        X = rng.normal(size=(4, 32)).astype(np.float32)
        out = np.asarray(fg.batch_apply(Dataset.of(jnp.asarray(X))).array)
        assert out.shape == (4, 2 * 16)  # two branches x (32-pad FFT)/2

    def test_mnist_pipeline_gather_is_packed(self):
        from keystone_tpu.pipelines.mnist_random_fft import (
            MnistRandomFFTConfig,
            build_featurizer,
        )
        from keystone_tpu.workflow.fusion import FusedGatherTransformer

        cfg = MnistRandomFFTConfig(num_ffts=4, block_size=32, image_size=48)
        pipe = build_featurizer(cfg)
        X = rng.normal(size=(8, 48)).astype(np.float32)
        handle = pipe.apply(Dataset.of(jnp.asarray(X)))
        handle.get()
        graph = handle.executor.optimized_graph
        fgs = [
            graph.get_operator(n) for n in graph.nodes
            if isinstance(graph.get_operator(n), FusedGatherTransformer)
        ]
        assert fgs and all(fg.uses_packed_fft for fg in fgs)


# ---------------------------------------------------------------------------
# Kept programs: members' arrays as operands, one program per logical pipeline
# ---------------------------------------------------------------------------


def _bank(seed, d_in=24, d_out=16):
    from keystone_tpu.ops.stats import CosineRandomFeaturesModel

    r = np.random.default_rng(seed)
    return CosineRandomFeaturesModel(
        r.normal(size=(d_out, d_in)).astype(np.float32),
        r.uniform(0, 6.28, size=d_out).astype(np.float32),
    )


def _fresh_fused(kind, seed, **bank_kw):
    """A fused transformer built afresh — new member objects, arrays whose
    VALUES depend on ``seed`` and whose shapes do not — and the function
    that computes what its own members compute, one after the other."""
    from keystone_tpu.ops.images.conv import Convolver, Pooler, SymmetricRectifier
    from keystone_tpu.ops.images.core import ImageVectorizer
    from keystone_tpu.ops.learning.linear import LinearMapper
    from keystone_tpu.ops.stats import StandardScalerModel
    from keystone_tpu.ops.util import VectorCombiner
    from keystone_tpu.workflow.fusion import FusedGatherTransformer

    r = np.random.default_rng(1000 + seed)
    if kind in ("gather", "packed_gather"):
        if kind == "gather":
            branches = [[_bank(10 * seed + i, **bank_kw)] for i in range(3)]
        else:  # the MnistRandomFFT shape
            branches = [
                [RandomSignNode.create(24, seed=10 * seed + i), PaddedFFT(),
                 LinearRectifier(0.0, alpha=0.1 * i)]
                for i in range(3)
            ]
        fused = FusedGatherTransformer(branches, VectorCombiner())
        assert fused.uses_packed_fft == (kind == "packed_gather")

        def members(X):
            outs = []
            for br in branches:
                b = X
                for m in br:
                    b = np.stack([np.asarray(m.apply(row)) for row in b])
                outs.append(b)
            return np.concatenate(outs, axis=-1)

        return fused, members
    if kind == "conv":  # images (n, 6, 4, 1) flattened to 24 on the way in
        conv = Convolver(
            r.normal(size=(5, 4)).astype(np.float32), 6, 4, 1,
            normalize_patches=False,
        )
        chain = [conv, SymmetricRectifier(alpha=0.1), Pooler(2, 2), ImageVectorizer()]
    else:
        bank = _bank(seed, **bank_kw)
        d = bank.W.shape[0]
        model = LinearMapper(
            r.normal(size=(d, 5)).astype(np.float32),
            r.normal(size=(5,)).astype(np.float32),
            StandardScalerModel(r.normal(size=(d,)).astype(np.float32)),
        )
        chain = [bank, model]
        if kind == "sign_chain":
            chain.insert(0, RandomSignNode.create(bank.W.shape[1], seed=seed))
    fused = FusedBatchTransformer(chain)

    def members(X):
        for m in chain:
            X = np.stack([np.asarray(m.apply(row)) for row in X])
        return X

    return fused, members


def _composed_compiles(tracer, under):
    """The ``jax.compile`` spans of a program named ``composed`` that
    ``under`` (a span) caused, by stage."""
    by_id = {s["span_id"]: s for s in tracer.spans()}

    def caused_by(s):
        while s is not None:
            if s["span_id"] == under.span_id:
                return True
            s = by_id.get(s["parent_id"])
        return False

    return sorted(
        s["args"]["stage"] for s in tracer.spans("jax.compile")
        if "composed" in str(s["args"]["fun"]) and caused_by(s)
    )


@pytest.fixture
def kept(monkeypatch):
    """The kept-program table and the process totals as a new process has
    them (other tests of this worker fused pipelines before)."""
    from keystone_tpu.workflow import fusion

    monkeypatch.setattr(fusion, "_KEPT_PROGRAMS", {})
    monkeypatch.setattr(fusion, "_PROGRAM_TOTALS", {"hit": 0, "miss": 0})
    return fusion


_KINDS = ["gather", "chain", "sign_chain", "packed_gather", "conv"]


class TestKeptPrograms:
    X = rng.normal(size=(8, 24)).astype(np.float32)

    def _input(self, kind):
        return self.X.reshape(8, 6, 4, 1) if kind == "conv" else self.X

    @pytest.mark.parametrize("kind", _KINDS)
    def test_second_build_runs_the_first_ones_program(self, kept, kind):
        """Any in-package member — a bank, ``RandomSignNode``, the packed
        FFT gather, ``Convolver`` — built twice from new arrays of the same
        shapes compiles its batch program once."""
        from keystone_tpu import obs

        X = self._input(kind)
        with obs.tracing() as t:
            with obs.span("first") as first:
                a, a_members = _fresh_fused(kind, 1)
                out_a = np.asarray(a.batch_apply(Dataset.of(X)).array)
            with obs.span("second") as second:
                b, b_members = _fresh_fused(kind, 2)
                out_b = np.asarray(b.batch_apply(Dataset.of(X)).array)
        assert (a.fused_program, b.fused_program) == ("miss", "hit")
        assert kept.fused_program_totals() == {"hit": 1, "miss": 1}
        assert _composed_compiles(t, first) == ["backend", "lower", "trace"]
        assert _composed_compiles(t, second) == []  # nothing traced, nothing compiled
        # Each computes with its OWN arrays: a program that kept the first
        # build's bank would give out_a twice.
        np.testing.assert_allclose(out_a, a_members(X), atol=1e-4)
        np.testing.assert_allclose(out_b, b_members(X), atol=1e-4)
        assert np.abs(out_a - out_b).max() > 1e-2

    def test_executor_node_span_says_miss_then_hit(self, kept):
        from keystone_tpu import obs
        from keystone_tpu.ops.util import VectorCombiner
        from keystone_tpu.workflow import PipelineEnv

        said = []
        with obs.tracing() as t:
            for seed in (1, 2):
                PipelineEnv.get_or_create().reset()
                pipe = Pipeline.gather(
                    [_bank(seed + i).to_pipeline() for i in range(2)]
                ).and_then(VectorCombiner())
                pipe.apply(Dataset.of(jnp.asarray(self.X))).get()
        for s in t.spans("executor.node"):
            if s["args"]["operator"] == "FusedGatherTransformer":
                said.append(s["args"]["fused_program"])
            else:
                assert "fused_program" not in s["args"]
        assert said == ["miss", "hit"]

    @pytest.mark.parametrize("kind", _KINDS)
    def test_pickle_round_trip_rebuilds(self, kept, kind):
        import cloudpickle

        fused, members = _fresh_fused(kind, 3)
        blob = cloudpickle.dumps(fused)
        assert not {"_composed", "_operands"} & set(fused.__getstate__())
        loaded = cloudpickle.loads(blob)
        # The table had the program when the copy was rebuilt.
        assert loaded.fused_program == "hit"
        X = self._input(kind)
        out = np.asarray(loaded.batch_apply(Dataset.of(X)).array)
        np.testing.assert_allclose(out, members(X), atol=1e-4)

    def test_plan_fingerprint_does_not_depend_on_hit_or_miss(self, kept):
        """A shipped plan is rebuilt (a ``hit``) from the pickle of one that
        took the ``miss``: how a node got its program is not its state."""
        import cloudpickle

        from keystone_tpu.serving.export import plan_fingerprint
        from tests._serving_util import fitted_from_transformer

        fused, _ = _fresh_fused("packed_gather", 1)
        again = cloudpickle.loads(cloudpickle.dumps(fused))
        assert (fused.fused_program, again.fused_program) == ("miss", "hit")
        prints = {
            plan_fingerprint(
                fitted_from_transformer(f).transformer_graph, (24,), "float32")
            for f in (fused, again)
        }
        assert len(prints) == 1

    def test_fused_node_nested_in_a_chain_keeps_its_program(self, kept):
        """The wrappers offer the operand form themselves: a fused gather
        inside a chain rides as (its members' identities, their arrays)."""
        from keystone_tpu.ops.learning.linear import LinearMapper

        outs = []
        for seed in (1, 2):
            gather, members = _fresh_fused("gather", seed)
            model = LinearMapper(
                rng.normal(size=(48, 3)).astype(np.float32))
            nested = FusedBatchTransformer([gather, model])
            outs.append(nested.fused_program)
            got = np.asarray(nested.batch_apply(Dataset.of(self.X)).array)
            np.testing.assert_allclose(
                got, members(self.X) @ np.asarray(model.x), rtol=1e-4, atol=1e-4)
        assert outs == ["miss", "hit"]

    def test_kept_program_carries_no_bank_as_a_constant(self, kept):
        import jax

        shape = dict(d_in=600, d_out=512)  # 1.2 MB a bank
        X = jnp.asarray(rng.normal(size=(4, 600)).astype(np.float32))
        fused, _ = _fresh_fused("gather", 1, **shape)
        assert fused.branches[0][0].W.nbytes > 1 << 20
        ((_, program),) = kept._KEPT_PROGRAMS.items()
        kept_text = program.lower(fused.device_operands()[1], X).as_text()
        assert len(kept_text) < 1 << 20  # a constant above 1 MB cannot be in it
        # The check can fail: bound to the instance, the banks are constants.
        assert len(jax.jit(fused.device_fn()).lower(X).as_text()) > 1 << 20

    def test_table_is_bounded_and_pins_no_array(self, kept):
        import gc
        import weakref

        from keystone_tpu.ops.util import VectorCombiner
        from keystone_tpu.workflow.fusion import FusedGatherTransformer

        arrays, keys = [], []
        for width in range(1, kept._KEPT_PROGRAMS_MAX + 5):  # a key per width
            fused = FusedGatherTransformer(
                [[_bank(width + i)] for i in range(width)], VectorCombiner()
            )
            assert fused.fused_program == "miss"
            fused.batch_apply(Dataset.of(self.X)).array.block_until_ready()
            arrays += [weakref.ref(a) for br in fused.branches for a in (br[0].W, br[0].b)]
            keys.append(next(reversed(kept._KEPT_PROGRAMS)))
        assert list(kept._KEPT_PROGRAMS) == keys[-kept._KEPT_PROGRAMS_MAX:]  # FIFO
        del fused
        gc.collect()
        assert all(ref() is None for ref in arrays)


def _contract_cases():
    """Every class of the package that offers the operand form, built at
    toy size except for ONE array of 1.2 MB where the node owns arrays
    (so a constant in its lowered program cannot hide): name -> (node,
    X)."""
    from keystone_tpu.ops.images.conv import (
        Convolver,
        PooledConvolution,
        Pooler,
        SymmetricRectifier,
    )
    from keystone_tpu.ops.images.core import GrayScaler, ImageVectorizer, PixelScaler
    from keystone_tpu.ops.learning.block import BlockLinearMapper
    from keystone_tpu.ops.learning.linear import LinearMapper
    from keystone_tpu.ops.learning.pca import ZCAWhitener
    from keystone_tpu.ops.stats import StandardScalerModel
    from keystone_tpu.ops.util import FloatToDouble, MatrixVectorizer, VectorCombiner
    from keystone_tpu.workflow.fusion import FusedGatherTransformer

    r = np.random.default_rng(9)

    def f32(*shape):
        return r.normal(size=shape).astype(np.float32)

    wide, images = f32(3, 600), np.abs(f32(3, 6, 5, 3))
    scalers = [StandardScalerModel(f32(300), np.abs(f32(300)) + 0.5),
               StandardScalerModel(f32(300))]
    big = dict(d_in=600, d_out=512)
    return {
        "cosine": (_bank(4, **big), wide),
        "padded_fft": (PaddedFFT(), f32(3, 45)),
        "random_sign": (RandomSignNode.create(1 << 19, seed=3), f32(2, 1 << 19)),
        "linear_rectifier": (LinearRectifier(0.1, alpha=0.2), wide),
        "signed_hellinger": (SignedHellingerMapper(), wide),
        "normalize_rows": (NormalizeRows(), wide),
        "gray_scaler": (GrayScaler(), images),
        "pixel_scaler": (PixelScaler(), images),
        "image_vectorizer": (ImageVectorizer(), images),
        "convolver": (
            Convolver(f32(6400, 48), 6, 5, 3,
                      whitener=ZCAWhitener(np.eye(48, dtype=np.float32), f32(48))),
            images),
        "pooled_convolution": (  # the Pallas kernel, interpreted here
            PooledConvolution(
                Convolver(f32(2800, 108), 8, 8, 3,
                          whitener=ZCAWhitener(np.eye(108, dtype=np.float32), f32(108))),
                SymmetricRectifier(alpha=0.1), Pooler(2, 2, pool_function="sum"),
                vectorize=True),
            np.abs(f32(2, 8, 8, 3))),
        "pooler": (Pooler(2, 2, pixel_function=jnp.abs, pool_function="max"), images),
        "symmetric_rectifier": (SymmetricRectifier(alpha=0.1), images),
        "matrix_vectorizer": (MatrixVectorizer(), f32(3, 4, 5)),
        "float_to_double": (FloatToDouble(strict=True), wide),
        "max_classifier": (MaxClassifier(), wide),
        "linear": (LinearMapper(f32(600, 512)), wide),
        "linear_intercept_scaler": (
            LinearMapper(f32(600, 512), f32(512),
                         StandardScalerModel(f32(600), np.abs(f32(600)) + 0.5)),
            wide),
        "block": (BlockLinearMapper([f32(300, 512), f32(300, 512)], 300), wide),
        "block_intercept_scalers": (
            BlockLinearMapper([f32(300, 512), f32(300, 512)], 300, f32(512), scalers),
            wide),
        "vector_combiner": (VectorCombiner(), (wide, f32(3, 7))),
        "fused_chain": (
            FusedBatchTransformer([_bank(5, **big), LinearRectifier(0.0)]), wide),
        "fused_gather": (
            FusedGatherTransformer(
                [[_bank(6, **big)], [_bank(7, **big), LinearRectifier(0.0)]],
                VectorCombiner()),
            wide),
        "fused_packed_gather": (
            FusedGatherTransformer(
                [[RandomSignNode.create(600, seed=i), PaddedFFT(), LinearRectifier(0.0)]
                 for i in range(3)],
                VectorCombiner()),
            wide),
    }


@pytest.mark.parametrize("name", sorted(_contract_cases()))
def test_operand_form_is_the_nodes_one_device_form(name):
    """The one contract: ``device_fn()(X)``, ``device_apply(static_key,
    params, X)`` and the stacked per-row ``apply`` agree, the key is
    hashable, and ``params`` holds every array the node owns — the
    lowered ``device_apply`` carries no constant above 1 MB."""
    import jax

    node, X = _contract_cases()[name]
    combiner = name == "vector_combiner"
    static_key, params = (
        node.device_combine_operands() if combiner else node.device_operands())
    hash(static_key)
    apply = type(node).device_combine_apply if combiner else type(node).device_apply
    bound = node.device_combine_fn() if combiner else node.device_fn()
    X = jax.tree_util.tree_map(jnp.asarray, X)
    got = np.asarray(apply(static_key, params, X))
    np.testing.assert_allclose(got, np.asarray(bound(X)), rtol=1e-6, atol=1e-6)
    rows = zip(*X) if combiner else X
    np.testing.assert_allclose(
        got, np.stack([np.asarray(node.apply(x)) for x in rows]),
        rtol=1e-4, atol=1e-4)
    owned = [a for a in jax.tree_util.tree_leaves(params) if a.nbytes > 1 << 20]
    assert bool(owned) == (name in {
        "cosine", "random_sign", "convolver", "pooled_convolution", "linear",
        "linear_intercept_scaler",
        "block", "block_intercept_scalers", "fused_chain", "fused_gather"})
    text = jax.jit(apply, static_argnums=0).lower(static_key, params, X).as_text()
    assert len(text) < 1 << 20


def test_device_fn_is_derived_and_cannot_be_overridden():
    """No class of the package overrides ``device_fn`` /
    ``device_combine_fn``, and outside code that still does fails where
    its class is created, with a message that names the operand form."""
    import importlib
    import pkgutil

    import keystone_tpu
    from keystone_tpu.workflow import Transformer

    for mod in pkgutil.walk_packages(keystone_tpu.__path__, "keystone_tpu."):
        try:
            importlib.import_module(mod.name)
        except ImportError:  # an optional dependency the container lacks
            continue

    def subclasses(cls):
        for sub in cls.__subclasses__():
            yield sub
            yield from subclasses(sub)

    found = [c for c in subclasses(Transformer)
             if c.__module__.startswith("keystone_tpu.")]
    assert len(found) > 60
    for cls in found:
        assert "device_fn" not in vars(cls), cls
        assert "device_combine_fn" not in vars(cls), cls
    # ... and the contract test above covers every class that offers the form.
    offering = {c for c in found
                if {"device_operands", "device_combine_operands"} & set(vars(c))}
    covered = {c for node, _ in _contract_cases().values() for c in type(node).__mro__}
    assert len(offering) == 20 and offering <= covered, offering - covered

    for name, form in [("device_fn", "device_operands"),
                       ("device_combine_fn", "device_combine_operands")]:
        with pytest.raises(TypeError, match=form):
            type("Old", (Transformer,), {name: lambda self: (lambda X: X)})


def test_non_scaler_feature_scaler_has_no_operand_form():
    from keystone_tpu.ops.learning.linear import LinearMapper

    model = LinearMapper(np.eye(4, dtype=np.float32), feature_scaler=LinearRectifier(0.0))
    assert model.device_operands() is None


class TestKeptProgramThroughTimitFits:
    """Two resident TIMIT fits at toy size, each a new pipeline with a new
    bank, as a λ sweep makes them."""

    ROWS, BLOCK = 256, 64

    def _fit(self, bank_seed, lam, fusion=True):
        from keystone_tpu.ops.learning.cost import LeastSquaresEstimator
        from keystone_tpu.pipelines import timit
        from keystone_tpu.workflow import PipelineEnv
        from keystone_tpu.workflow.optimizer import DefaultOptimizer

        env = PipelineEnv.get_or_create()
        env.reset()
        if not fusion:
            optimizer = DefaultOptimizer()
            optimizer.batches = [
                b for b in optimizer.batches if "Fusion" not in b.name
            ]
            env.set_optimizer(optimizer)
        r = np.random.default_rng(5)
        X = r.normal(size=(self.ROWS, timit.NUM_INPUT_FEATURES)).astype(np.float32)
        Y = 2.0 * np.eye(8, dtype=np.float32)[r.integers(0, 8, self.ROWS)] - 1.0
        cfg = timit.TimitConfig(num_cosines=2, block_size=self.BLOCK,
                                num_epochs=2, lam=lam, seed=bank_seed)
        estimator = LeastSquaresEstimator(
            lam=lam, block_size=self.BLOCK, block_iters=2)
        fitted = timit.build_featurizer(cfg).and_then(
            estimator, Dataset.of(jnp.asarray(X)), Dataset.of(jnp.asarray(Y))
        ).fit()
        probe = r.normal(size=(32, timit.NUM_INPUT_FEATURES)).astype(np.float32)
        return np.asarray(fitted.apply(Dataset.of(jnp.asarray(probe))).array)

    def test_second_fit_compiles_no_featurize_program(self, kept):
        from keystone_tpu import obs

        sweep = [(7, 1e-3), (11, 3e-3)]
        scores, featurize_compiles = [], []
        for bank_seed, lam in sweep:
            with obs.tracing() as t:
                scores.append(self._fit(bank_seed, lam))
            nodes = {s["span_id"] for s in t.spans("executor.node")
                     if s["args"]["operator"] == "FusedGatherTransformer"}
            featurize_compiles.append(sorted(
                c["args"]["stage"] for c in t.spans("jax.compile")
                if "composed" in str(c["args"]["fun"]) and c["parent_id"] in nodes
            ))
            if lam == sweep[-1][1]:  # nor for the probe rows, scored outside any node
                assert not [c for c in t.spans("jax.compile")
                            if "composed" in str(c["args"]["fun"])]
        assert featurize_compiles == [["backend", "lower", "trace"], []]
        for (bank_seed, lam), got in zip(sweep, scores):
            want = self._fit(bank_seed, lam, fusion=False)
            np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
        assert np.abs(scores[0] - scores[1]).max() > 1e-3
