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
        from keystone_tpu.ops.stats import packed_fft_gather_fn
        from keystone_tpu.ops.util import VectorCombiner

        branches = self._branches(nb, d_in, alphas=[0.1 * i for i in range(nb)])
        fn = packed_fft_gather_fn(branches, VectorCombiner())
        assert fn is not None
        X = rng.normal(size=(16, d_in)).astype(np.float32)
        out = np.asarray(fn(jnp.asarray(X)))
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
    from keystone_tpu.ops.learning.linear import LinearMapper
    from keystone_tpu.ops.stats import StandardScalerModel
    from keystone_tpu.ops.util import VectorCombiner
    from keystone_tpu.workflow.fusion import FusedGatherTransformer

    r = np.random.default_rng(1000 + seed)
    if kind == "gather":
        banks = [_bank(10 * seed + i, **bank_kw) for i in range(3)]
        fused = FusedGatherTransformer([[b] for b in banks], VectorCombiner())

        def members(X):
            return np.concatenate([np.asarray(b.apply(X)) for b in banks], axis=-1)

        return fused, members
    bank = _bank(seed, **bank_kw)
    d = bank.W.shape[0]
    model = LinearMapper(
        r.normal(size=(d, 5)).astype(np.float32),
        r.normal(size=(5,)).astype(np.float32),
        StandardScalerModel(r.normal(size=(d,)).astype(np.float32)),
    )
    if kind == "chain":
        chain = [bank, model]
    else:  # "closure": RandomSignNode has only the closure form
        chain = [RandomSignNode.create(bank.W.shape[1], seed=seed), bank, model]
    fused = FusedBatchTransformer(chain)

    def members(X):
        for m in chain:
            X = m.apply(X)
        return np.asarray(X)

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
    monkeypatch.setattr(
        fusion, "_PROGRAM_TOTALS", {"hit": 0, "miss": 0, "closure": 0}
    )
    return fusion


class TestKeptPrograms:
    X = rng.normal(size=(8, 24)).astype(np.float32)

    @pytest.mark.parametrize("kind", ["gather", "chain"])
    def test_second_build_runs_the_first_ones_program(self, kept, kind):
        from keystone_tpu import obs

        with obs.tracing() as t:
            with obs.span("first") as first:
                a, a_members = _fresh_fused(kind, 1)
                out_a = np.asarray(a.batch_apply(Dataset.of(self.X)).array)
            with obs.span("second") as second:
                b, b_members = _fresh_fused(kind, 2)
                out_b = np.asarray(b.batch_apply(Dataset.of(self.X)).array)
        assert (a.fused_program, b.fused_program) == ("miss", "hit")
        assert kept.fused_program_totals() == {"hit": 1, "miss": 1, "closure": 0}
        assert _composed_compiles(t, first) == ["backend", "lower", "trace"]
        assert _composed_compiles(t, second) == []  # nothing traced, nothing compiled
        # Each computes with its OWN arrays: a program that kept the first
        # build's bank would give out_a twice.
        np.testing.assert_allclose(out_a, a_members(self.X), atol=1e-5)
        np.testing.assert_allclose(out_b, b_members(self.X), atol=1e-5)
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

    def test_member_without_operand_form_keeps_the_closure_form(self, kept):
        from keystone_tpu import obs

        with obs.tracing() as t:
            with obs.span("both") as both:
                for seed in (1, 2):
                    fused, members = _fresh_fused("closure", seed)
                    assert fused.fused_program == "closure"
                    out = np.asarray(fused.batch_apply(Dataset.of(self.X)).array)
                    np.testing.assert_allclose(out, members(self.X), atol=1e-5)
        # Today's behaviour: one program per instance, none kept.
        assert _composed_compiles(t, both).count("backend") == 2
        assert kept._KEPT_PROGRAMS == {}
        assert kept.fused_program_totals() == {"hit": 0, "miss": 0, "closure": 2}

    @pytest.mark.parametrize("kind", ["gather", "chain", "closure"])
    def test_pickle_round_trip_rebuilds(self, kept, kind):
        import cloudpickle

        fused, members = _fresh_fused(kind, 3)
        blob = cloudpickle.dumps(fused)
        assert "_composed" not in fused.__getstate__()
        loaded = cloudpickle.loads(blob)
        # The table had the program when the copy was rebuilt.
        assert loaded.fused_program == ("closure" if kind == "closure" else "hit")
        out = np.asarray(loaded.batch_apply(Dataset.of(self.X)).array)
        np.testing.assert_allclose(out, members(self.X), atol=1e-5)

    def test_kept_program_carries_no_bank_as_a_constant(self, kept):
        import jax

        shape = dict(d_in=600, d_out=512)  # 1.2 MB a bank
        X = jnp.asarray(rng.normal(size=(4, 600)).astype(np.float32))
        fused, _ = _fresh_fused("gather", 1, **shape)
        assert fused.branches[0][0].W.nbytes > 1 << 20
        ((_, program),) = kept._KEPT_PROGRAMS.items()
        operands = (
            tuple(tuple(m.device_operands()[1] for m in br) for br in fused.branches),
            (),
        )
        kept_text = program.lower(operands, X).as_text()
        assert len(kept_text) < 1 << 20  # a constant above 1 MB cannot be in it
        closure, _ = _fresh_fused("closure", 1, **shape)
        assert len(jax.jit(closure.device_fn()).lower(X).as_text()) > 1 << 20

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


def _operand_form_holders():
    from keystone_tpu.ops.learning.block import BlockLinearMapper
    from keystone_tpu.ops.learning.linear import LinearMapper
    from keystone_tpu.ops.stats import StandardScalerModel

    r = np.random.default_rng(9)

    def f32(*shape):
        return r.normal(size=shape).astype(np.float32)

    scalers = [StandardScalerModel(f32(12), np.abs(f32(12)) + 0.5),
               StandardScalerModel(f32(12))]
    return {
        "cosine": _bank(4),
        "linear": LinearMapper(f32(24, 5)),
        "linear_intercept_scaler": LinearMapper(
            f32(24, 5), f32(5), StandardScalerModel(f32(24), np.abs(f32(24)) + 0.5)),
        "block": BlockLinearMapper([f32(12, 5), f32(12, 5)], 12),
        "block_intercept_scalers": BlockLinearMapper(
            [f32(12, 5), f32(12, 5)], 12, f32(5), scalers),
        "max_classifier": MaxClassifier(),
    }


@pytest.mark.parametrize("name", sorted(_operand_form_holders()))
def test_operand_form_equals_device_fn(name):
    """``device_apply(static_key, params, X)`` is ``device_fn()(X)``: the
    contract that lets a kept program stand in for a member's closure."""
    member = _operand_form_holders()[name]
    X = jnp.asarray(rng.normal(size=(6, 24)).astype(np.float32))
    static_key, params = member.device_operands()
    hash(static_key)
    got = type(member).device_apply(static_key, params, X)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(member.device_fn()(X)), rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(got),
        np.stack([np.asarray(member.apply(x)) for x in X]), rtol=1e-4, atol=1e-5)


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
