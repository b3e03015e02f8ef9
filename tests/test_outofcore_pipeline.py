"""Out-of-core ingestion wired into the typed Pipeline API (ISSUE 2):
loaders spill to disk shards instead of a resident array, a shard-backed
Dataset flows through ``Pipeline.fit``, and the capacity selector routes
past-host-RAM datasets through the disk tier with NO manual flag —
matching the resident path within existing streaming parity tolerances.
"""

import os

import numpy as np
import pytest

import jax.numpy as jnp

from keystone_tpu.data import Dataset, LabeledData
from keystone_tpu.data.loaders import csv_to_disk_shards
from keystone_tpu.data.shards import DiskDenseShards, DiskDenseShardWriter
from keystone_tpu.ops.learning.cost import LeastSquaresEstimator
from keystone_tpu.ops.learning.streaming_ls import (
    BlockStreamedLeastSquares,
    CosineBankFeaturize,
    StreamingLeastSquaresChoice,
)
from keystone_tpu.ops.stats import CosineRandomFeatures
from keystone_tpu.workflow.env import PipelineEnv


def _spilled_problem(tmp_path, n=1000, d=24, k=3, shard_rows=128, seed=0):
    """shard_rows does NOT divide n: ragged final shard by construction."""
    assert n % shard_rows != 0
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, d)).astype(np.float32)
    Y = rng.normal(size=(n, k)).astype(np.float32) + 0.3
    sld = LabeledData(X, Y).to_disk_shards(
        str(tmp_path / "shards"), shard_rows=shard_rows,
        tiles_per_segment=2,
    )
    return X, Y, sld


class TestSpillPath:
    def test_loader_spill_roundtrips_rows(self, tmp_path):
        X, Y, sld = _spilled_problem(tmp_path)
        assert sld.data.is_shard_backed and sld.labels.is_shard_backed
        assert sld.data.n == X.shape[0]
        np.testing.assert_array_equal(sld.data.to_numpy(), X)
        np.testing.assert_array_equal(sld.labels.to_numpy(), Y)

    def test_csv_dir_to_disk_shards_roundtrip_fit(self, tmp_path):
        # CSV directory -> disk shards ONE FILE AT A TIME -> streamed fit,
        # with a shard_rows that divides neither any file nor the total.
        rng = np.random.default_rng(1)
        n, d, num_classes = 541, 12, 4
        X = rng.normal(size=(n, d))
        labels = rng.integers(0, num_classes, size=n)
        csv_dir = tmp_path / "csv"
        csv_dir.mkdir()
        splits = [0, 200, 437, n]  # ragged files
        for i in range(3):
            lo, hi = splits[i], splits[i + 1]
            with open(csv_dir / f"part{i}.csv", "w") as f:
                for r in range(lo, hi):
                    f.write(
                        ",".join([str(labels[r])]
                                 + [f"{v:.6f}" for v in X[r]]) + "\n"
                    )
        (csv_dir / "part3_empty.csv").touch()  # _SUCCESS-marker semantics

        sld = csv_to_disk_shards(
            str(csv_dir), str(tmp_path / "spill"), shard_rows=128,
            tiles_per_segment=2, num_classes=num_classes,
        )
        assert sld.data.n == n
        X_back = sld.data.to_numpy()
        np.testing.assert_allclose(X_back, X.astype(np.float32), atol=1e-5)
        Y_expect = 2.0 * np.eye(num_classes, dtype=np.float32)[labels] - 1.0
        np.testing.assert_array_equal(sld.labels.to_numpy(), Y_expect)

        # Round trip THROUGH a fit: disk-tier solve equals resident solve.
        choice = StreamingLeastSquaresChoice(
            num_iter=2, lam=1e-2, block_size_hint=12
        )
        m_disk = choice.fit(sld.data, sld.labels)
        m_res = choice.fit(
            Dataset.of(X.astype(np.float32)), Dataset.of(Y_expect)
        )
        p_d = np.asarray(
            m_disk.batch_apply(Dataset.of(X.astype(np.float32))).array
        )
        p_r = np.asarray(
            m_res.batch_apply(Dataset.of(X.astype(np.float32))).array
        )
        np.testing.assert_allclose(p_d, p_r, atol=5e-4, rtol=5e-4)

    def test_csv_spill_preserves_float_labels(self, tmp_path):
        # num_classes=None: continuous targets must survive the spill as
        # floats (truncating to int would corrupt every downstream fit).
        rng = np.random.default_rng(5)
        n, d = 40, 3
        X = rng.normal(size=(n, d))
        y = rng.uniform(0.1, 2.0, size=n)
        csv = tmp_path / "reg.csv"
        with open(csv, "w") as f:
            for r in range(n):
                f.write(
                    ",".join([f"{y[r]:.6f}"] + [f"{v:.6f}" for v in X[r]])
                    + "\n"
                )
        sld = csv_to_disk_shards(
            str(csv), str(tmp_path / "regspill"), shard_rows=16
        )
        np.testing.assert_allclose(
            sld.labels.to_numpy().ravel(), y.astype(np.float32), atol=1e-5
        )

    def test_writer_overshoot_capacity_records_true_rows(self, tmp_path):
        w = DiskDenseShardWriter(
            str(tmp_path / "w"), capacity_rows=1000, d_in=4, k=1,
            tile_rows=64,
        )
        rng = np.random.default_rng(2)
        blocks = [rng.normal(size=(m, 4)).astype(np.float32)
                  for m in (100, 37, 240)]
        for b in blocks:
            w.append(b, np.ones((b.shape[0], 1), np.float32))
        shards = w.close()
        assert shards.n_true == 377
        assert shards.num_tiles == -(-377 // 64)
        np.testing.assert_allclose(
            shards.as_source().materialize()[0], np.concatenate(blocks)
        )


class TestCapacitySelection:
    def _sample(self, tmp_path, n=1000, d=24, k=3):
        X, Y, sld = _spilled_problem(tmp_path, n=n, d=d, k=k)
        return X, Y, sld

    def test_over_host_budget_routes_to_disk_tier(self, tmp_path):
        X, Y, sld = self._sample(tmp_path)
        # Host budget below the raw dataset: every resident candidate
        # (including non-shard streaming) is host-infeasible; only the
        # disk tier survives.
        est = LeastSquaresEstimator(lam=0.1, host_budget_bytes=16 << 10)
        from keystone_tpu.workflow.rules import _collect_samples
        from keystone_tpu.workflow.graph import Graph
        from keystone_tpu.workflow.operators import DatasetOperator

        g = Graph()
        g, dn = g.add_node(DatasetOperator(sld.data), [])
        g, ln = g.add_node(DatasetOperator(sld.labels), [])
        g, en = g.add_node(est, [dn, ln])
        g, _ = g.add_sink(en)
        samples = _collect_samples(g, [en], samples_per_shard=3)
        s, ls = samples[en]
        assert getattr(s, "shard_backed", False)
        assert s.total_n == X.shape[0]
        chosen = est.optimize(s, ls)
        assert isinstance(chosen, StreamingLeastSquaresChoice)
        assert chosen.data_is_shard_backed

    def test_under_host_budget_keeps_resident_solver(self, tmp_path):
        X, Y, sld = self._sample(tmp_path)
        est = LeastSquaresEstimator(lam=0.1, host_budget_bytes=1 << 30)
        from keystone_tpu.workflow.rules import _collect_samples
        from keystone_tpu.workflow.graph import Graph
        from keystone_tpu.workflow.operators import DatasetOperator

        g = Graph()
        g, dn = g.add_node(DatasetOperator(sld.data), [])
        g, ln = g.add_node(DatasetOperator(sld.labels), [])
        g, en = g.add_node(est, [dn, ln])
        g, _ = g.add_sink(en)
        samples = _collect_samples(g, [en], samples_per_shard=3)
        s, ls = samples[en]
        chosen = est.optimize(s, ls)
        assert not isinstance(chosen, StreamingLeastSquaresChoice)

    def test_shard_backed_pricing_matches_gram_fold_execution(self):
        # The shard-backed fit ALWAYS runs the gram fold (fit_source), so
        # its capacity model must carry the 8d^2 Gramian stash even where
        # _gram_tier_ok would pick the block tier — otherwise the
        # selector admits a fold that OOMs allocating G.
        choice = StreamingLeastSquaresChoice(num_iter=2, lam=1e-2)
        choice.data_is_shard_backed = True
        choice.shard_segment_bytes = 1 << 20
        choice.budget_bytes = 1 << 30  # 8d^2 at d=60k >> budget
        d = 60_000
        rb = choice.resident_bytes(10_000_000, d, 4, 1.0, 1)
        assert rb >= 8.0 * d * d
        # ...and no term scales with n: disk-tier residency is n-free.
        assert rb == choice.resident_bytes(10, d, 4, 1.0, 1)

    def test_host_cut_applies_to_plain_resident_data_too(self):
        # A NON-shard-backed dataset past the host budget has no disk
        # path: nothing is host-feasible and the selector falls back to
        # least-resident rather than pretending a resident solve fits.
        rng = np.random.default_rng(3)
        est = LeastSquaresEstimator(
            lam=0.1, hbm_bytes=8 << 30, host_budget_bytes=1 << 20
        )
        s = Dataset.of(rng.normal(size=(24, 512)).astype(np.float32))
        s.total_n = 10_000_000
        s.source_row_bytes = 2048.0
        ls = Dataset.of(rng.normal(size=(24, 4)).astype(np.float32))
        chosen = est.optimize(s, ls)  # warning path, still returns a plan
        assert chosen is not None


class TestOutOfCorePipelineFit:
    def test_pipeline_fit_over_host_budget_no_flag(self, tmp_path):
        """The acceptance path: Pipeline.fit on a shard-backed dataset
        whose resident size exceeds the (forced) host budget — the
        selector picks the streaming tier, the optimizer binds the
        featurizer, and the fit folds prefetched disk segments; result
        matches the explicit resident bank fit within streaming parity
        tolerances."""
        PipelineEnv.get_or_create().reset()
        rng = np.random.default_rng(0)
        n, d_in, d_feat, k = 4096, 16, 256, 4
        X = rng.normal(size=(n, d_in)).astype(np.float32)
        Y = rng.normal(size=(n, k)).astype(np.float32)
        sld = LabeledData(X, Y).to_disk_shards(
            str(tmp_path / "sh"), shard_rows=384, tiles_per_segment=2
        )

        crf = CosineRandomFeatures(d_in, d_feat, 0.2, seed=1)
        auto = LeastSquaresEstimator(lam=0.1, host_budget_bytes=64 << 10)
        p = crf.to_pipeline().and_then(auto, sld.data, sld.labels)
        res = p.apply(Dataset.of(X[:256]))
        preds = np.asarray(res.get().array)

        og = res.executor.optimized_graph
        labels_g = [
            str(getattr(op, "label", type(op).__name__))
            for op in og.operators.values()
        ]
        assert any("StreamedFit" in l for l in labels_g), labels_g

        choice = auto._streaming_choice
        assert choice.data_is_shard_backed
        ref = choice.build_estimator(
            CosineBankFeaturize(crf.W, crf.b), d_feat
        ).fit(Dataset.of(X), Dataset.of(Y))
        ref_preds = np.asarray(ref.batch_apply(Dataset.of(X[:256])).array)
        np.testing.assert_allclose(preds, ref_preds, atol=2e-3, rtol=2e-3)

        # fit() (the serializable-pipeline route) works on the same graph.
        fitted = p.fit()
        preds2 = np.asarray(fitted.apply(Dataset.of(X[:256])).array)
        np.testing.assert_allclose(preds2, ref_preds, atol=2e-3, rtol=2e-3)

    def test_direct_choice_fit_from_shards_matches_resident(self, tmp_path):
        X, Y, sld = _spilled_problem(tmp_path, n=900, d=32, k=3)
        choice = StreamingLeastSquaresChoice(
            num_iter=2, lam=1e-2, block_size_hint=16
        )
        m_disk = choice.fit(sld.data, sld.labels)
        m_res = choice.fit(Dataset.of(X), Dataset.of(Y))
        p_d = np.asarray(m_disk.batch_apply(Dataset.of(X)).array)
        p_r = np.asarray(m_res.batch_apply(Dataset.of(X)).array)
        np.testing.assert_allclose(p_d, p_r, atol=5e-4, rtol=5e-4)

    def test_mismatched_labels_against_paired_source_raise(self, tmp_path):
        # A triple-delivering source embeds its own labels: unrelated
        # labels must raise, not be silently ignored (the model would
        # otherwise train on the embedded Y with no error).
        from keystone_tpu.data.shards import DiskDenseShards

        X, Y, sld = _spilled_problem(tmp_path, n=500, d=8, k=2)
        paired = DiskDenseShards(
            str(tmp_path / "shards")
        ).as_source()
        data = Dataset.from_shards(paired)
        other = np.zeros((500, 2), np.float32)
        choice = StreamingLeastSquaresChoice(num_iter=1, lam=1e-2)
        with pytest.raises(ValueError, match="embeds its own labels"):
            choice.fit(data, Dataset.of(other))
        # The matching view of the same shards is accepted.
        m = choice.fit(data, sld.labels)
        assert m is not None

    def test_label_view_loads_only_labels(self, tmp_path, monkeypatch):
        # The cost-model sampler loads label segments: the label view
        # must never pay the (much wider) row read.
        X, Y, sld = _spilled_problem(tmp_path, n=500, d=8, k=2)
        view = sld.labels.shard_source
        monkeypatch.setattr(
            type(view.paired.shards), "segment_source_x",
            lambda self, s: (_ for _ in ()).throw(
                AssertionError("label view read the row file")
            ),
        )
        seg = view.load(0)
        assert seg.shape[-1] == 2
        np.testing.assert_array_equal(view.materialize(), Y)

    def test_resident_labels_pair_with_shard_backed_rows(self, tmp_path):
        # Labels usually fit host RAM even when rows don't: a resident
        # labels Dataset slices per segment against shard-backed rows.
        X, Y, sld = _spilled_problem(tmp_path, n=700, d=16, k=2)
        choice = StreamingLeastSquaresChoice(
            num_iter=2, lam=1e-2, block_size_hint=16
        )
        m_mix = choice.fit(sld.data, Dataset.of(Y))
        m_disk = choice.fit(sld.data, sld.labels)
        p_m = np.asarray(m_mix.batch_apply(Dataset.of(X)).array)
        p_d = np.asarray(m_disk.batch_apply(Dataset.of(X)).array)
        np.testing.assert_array_equal(p_m, p_d)

    def test_block_streamed_accepts_shard_backed(self, tmp_path, monkeypatch):
        # BlockStreamedLeastSquares accepts a ShardSource by materializing
        # (its residual sweep re-featurizes X every block step, so raw
        # rows must be device-resident). The mesh program itself is
        # exercised by the mesh suite; here we pin that the shard-backed
        # path hands it EXACTLY the rows the resident path gets.
        from keystone_tpu.ops.learning import streaming_ls
        from keystone_tpu.parallel import streaming as streaming_mod

        X, Y, sld = _spilled_problem(tmp_path, n=700, d=16, k=2)
        rng = np.random.default_rng(4)
        d_feat = 64
        bank = CosineBankFeaturize(
            rng.normal(size=(d_feat, 16)).astype(np.float32) * 0.3,
            rng.uniform(0, 6, d_feat).astype(np.float32),
        )
        est = BlockStreamedLeastSquares(
            bank, d_feat=d_feat, block_size=16, num_iter=2, lam=1e-2
        )
        seen = []

        def spy(X_in, Y_in, Wrf, brf, lam, **kw):
            seen.append((np.asarray(X_in), np.asarray(Y_in)))
            carry = (jnp.zeros_like(Y_in), jnp.zeros((4, 16, 2)), None,
                     jnp.zeros((4, 16, 16)), jnp.zeros((4, 16)))
            return carry, jnp.zeros(2), jnp.zeros(())

        # epoch 1's program takes the rows; epochs 2+ the carry it left
        monkeypatch.setattr(streaming_mod, "block_bcd_first_epoch", spy)
        monkeypatch.setattr(
            streaming_mod, "block_bcd_later_epochs",
            lambda R, W, stashes, *a, **kw: (R, W, jnp.zeros(())),
        )
        est.fit(sld.data, sld.labels)
        est.fit(Dataset.of(X), Dataset.of(Y))
        np.testing.assert_array_equal(seen[0][0], seen[1][0])
        np.testing.assert_array_equal(seen[0][1], seen[1][1])
