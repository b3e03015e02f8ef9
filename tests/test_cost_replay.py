"""Selector replay against recorded bench measurements (ISSUE 3 satellite).

The cost model's job is to rank candidates the way the hardware ranks them.
These tests replay geometries with MEASURED round-5 on-chip outcomes
(BENCH_r05.json and the rows ROADMAP Queue 1 quotes; provenance noted
per case) through
the ACTIVE selector weights and assert the selector picks the
measured-fastest feasible candidate:

  - TIMIT resident (n=262144, d=16384, k=147): resident block BCD measured
    0.327 s device; the streamed tier's per-row rate from the full-n
    headline (4.107 s at n=2.2e6) is ~0.49 s at this n — resident wins.
  - TIMIT full-n (n=2.2e6): resident candidates bust HBM; the streamed
    tier is the only feasible fit (measured 4.107 s — the headline).
  - Amazon sparse (n=500k, d=16384, nnz=82, k=2): gram engine measured
    1.805 s vs gather 7.903 s — gram wins while its Gramian fits.
  - dense LBFGS vs BCD at the TIMIT-resident geometry: 20 data passes vs
    3 block sweeps — the measured block row bounds LBFGS from below, so
    the model must rank block cheaper.

Weight-set plumbing (KEYSTONE_COST_WEIGHTS) is covered at the bottom.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from keystone_tpu import obs
from keystone_tpu.data import Dataset
from keystone_tpu.ops.learning.cost import (
    EC2_CPU_WEIGHT,
    EC2_MEM_WEIGHT,
    EC2_NETWORK_WEIGHT,
    LeastSquaresEstimator,
    TPU_CPU_WEIGHT,
    TPU_MEM_WEIGHT,
    TPU_NETWORK_WEIGHT,
    TransformerLabelEstimatorChain,
    active_weights,
    candidate_label,
    sparse_gather_overhead,
)
from keystone_tpu.ops.learning.block import BlockLeastSquaresEstimator
from keystone_tpu.ops.learning.lbfgs import DenseLBFGSwithL2, SparseLBFGSwithL2
from keystone_tpu.ops.learning.streaming_ls import StreamingLeastSquaresChoice


@pytest.fixture(autouse=True)
def _tpu_weight_family(monkeypatch):
    """The replay cases pin the TPU weight family: an ambient
    KEYSTONE_COST_WEIGHTS=ec2 (the documented A/B workflow) must not make
    them fail spuriously. TestWeightFamilySwitch sets the env itself."""
    monkeypatch.delenv("KEYSTONE_COST_WEIGHTS", raising=False)


def _dense_sample(n_total, d, k, seed=0):
    rng = np.random.default_rng(seed)
    s = Dataset.of(rng.normal(size=(24, d)).astype(np.float32))
    s.total_n = n_total
    s.source_row_bytes = 4.0 * 440  # raw TIMIT rows upstream of featurize
    ls = Dataset.of(rng.normal(size=(24, k)).astype(np.float32))
    return s, ls


def _cost_of(est, opt, n, d, k, sparsity=1.0, machines=1):
    return opt.cost(
        n, d, k, sparsity, machines,
        est.cpu_weight, est.mem_weight, est.network_weight,
    )


def _optimize_audited(est, s, ls):
    """Run the selection under tracing and return (chosen, the ONE
    ``least_squares_solver`` CostDecision event) — the trace-backed
    audit leg (ISSUE 9): every replay assertion below also asserts the
    recorded winner matches what the selector returned."""
    with obs.tracing() as t:
        chosen = est.optimize(s, ls)
    decisions = [
        e for e in t.events
        if e["type"] == "event" and e["name"] == "cost.decision"
        and e["args"]["decision"] == "least_squares_solver"
    ]
    assert len(decisions) == 1, decisions
    args = decisions[0]["args"]
    # The event is self-consistent evidence: every candidate priced,
    # the winner present in the candidate set, geometry recorded.
    labels = [c["label"] for c in args["candidates"]]
    assert args["winner"] in labels
    assert len(labels) == len(est.options)
    return chosen, args


def _audit_winner(args, expected_estimator) -> None:
    assert args["winner"] == candidate_label(expected_estimator), args


class TestReplayTimitResident:
    # BENCH_r05 timit_resident_262k: device 0.327 s, block BCD, bf16
    # features. The capacity models price conservative f32 (+ centered
    # copy), which busts a 16 GB budget at this n — the bench row's bf16 +
    # in-loop-block layout halves that. Budget set so the candidates the
    # row measured are feasible; what is under replay test is the RANKING
    # among them.
    N, D, K = 262_144, 16_384, 147

    def test_block_selected_over_streaming_and_lbfgs(self):
        # num_machines=1: the replayed rows are SINGLE-chip measurements
        # (the test env forces an 8-device CPU mesh, which would shard
        # capacity 8x and change feasibility).
        est = LeastSquaresEstimator(
            lam=1e-4, hbm_bytes=48 << 30, num_machines=1
        )
        s, ls = _dense_sample(self.N, self.D, self.K)
        chosen, audit = _optimize_audited(est, s, ls)
        assert isinstance(chosen, TransformerLabelEstimatorChain), chosen
        assert isinstance(chosen.estimator, BlockLeastSquaresEstimator), (
            type(chosen.estimator).__name__
        )
        _audit_winner(audit, chosen.estimator)
        assert audit["reason"] == "argmin"

    def test_measured_orderings_reproduced(self):
        est = LeastSquaresEstimator(
            lam=1e-4, hbm_bytes=48 << 30, num_machines=1
        )
        by_type = {type(o[0]).__name__ + getattr(o[0], "solver", ""): o[0]
                   for o in est.options}
        block = by_type["BlockLeastSquaresEstimator"]
        lbfgs = by_type["DenseLBFGSwithL2"]
        streaming = by_type["StreamingLeastSquaresChoice"]
        c_block = _cost_of(est, block, self.N, self.D, self.K)
        c_lbfgs = _cost_of(est, lbfgs, self.N, self.D, self.K)
        c_stream = _cost_of(est, streaming, self.N, self.D, self.K)
        # Measured: block 0.327 s device; streamed ~0.49 s (headline
        # per-row rate); 20-iteration LBFGS's 20 data passes bound it
        # above the 3-sweep block row.
        assert c_block < c_stream, (c_block, c_stream)
        assert c_block < c_lbfgs, (c_block, c_lbfgs)


class TestReplayTimitFullN:
    # BENCH_r05 headline: n=2.2e6 × d=16384, streamed 4.107 s device —
    # the ONLY tier that fits a 16 GB chip at this geometry.
    def test_streaming_selected_past_hbm(self):
        est = LeastSquaresEstimator(
            lam=1e-4, hbm_bytes=16 << 30, num_machines=1
        )
        s, ls = _dense_sample(2_200_000, 16_384, 147)
        chosen, audit = _optimize_audited(est, s, ls)
        assert isinstance(chosen, StreamingLeastSquaresChoice), chosen
        _audit_winner(audit, chosen)
        # The audit records WHY: every resident candidate priced
        # infeasible at this geometry, the streamed tier feasible.
        feas = {c["label"]: c["feasible"] for c in audit["candidates"]}
        assert feas[candidate_label(chosen)]
        assert not feas["DenseLBFGSwithL2"]
        assert not feas["BlockLeastSquaresEstimator"]


class TestReplayAmazonSparse:
    # BENCH_r05 amazon_sparse_lbfgs_d16384: gram 1.805 s vs gather
    # 7.903 s at n=500k, d=16384, nnz=82, k=2, 20 iterations.
    N, D, NNZ, K = 500_000, 16_384, 82, 2

    def _sample(self):
        rng = np.random.default_rng(4)
        idx = rng.integers(0, self.D, size=(24, self.NNZ)).astype(np.int32)
        idx[0, 0] = self.D - 1
        s = Dataset(
            {"indices": jnp.asarray(idx),
             "values": jnp.asarray(
                 rng.normal(size=(24, self.NNZ)).astype(np.float32))},
            n=24,
        )
        s.total_n = self.N
        s.source_row_bytes = self.NNZ * 4.0
        ls = Dataset.of(rng.normal(size=(24, self.K)).astype(np.float32))
        return s, ls

    def test_gram_selected_and_ranked_below_gather(self):
        est = LeastSquaresEstimator(
            lam=1e-3, hbm_bytes=16 << 30, num_machines=1
        )
        s, ls = self._sample()
        chosen, audit = _optimize_audited(est, s, ls)
        assert isinstance(chosen, TransformerLabelEstimatorChain), chosen
        inner = chosen.estimator
        assert isinstance(inner, SparseLBFGSwithL2) and inner.solver == "gram"
        _audit_winner(audit, inner)  # "SparseLBFGSwithL2[gram]"
        sparsity = self.NNZ / self.D
        gather = SparseLBFGSwithL2(
            lam=1e-3, num_iterations=20, solver="gather"
        )
        gram = SparseLBFGSwithL2(lam=1e-3, num_iterations=20, solver="gram")
        c_gather = _cost_of(est, gather, self.N, self.D, self.K, sparsity)
        c_gram = _cost_of(est, gram, self.N, self.D, self.K, sparsity)
        assert c_gram < c_gather, (c_gram, c_gather)

    def test_sketched_candidates_priced_but_gram_still_wins(self):
        """ISSUE 17 pin: once the sketched tier joins the candidate set
        (``allow_approximate=True``), the Amazon sparse decision is
        UNCHANGED — the gram engine still wins — while both sketched
        engines are priced and feasible, and the input-sparsity-time
        IHS undercuts the 20-iteration gather wall (the claim the
        amazon_sketched_frontier bench row measures)."""
        from keystone_tpu.ops.learning.sketch import (
            IterativeHessianSketch, SketchedLeastSquares,
        )

        est = LeastSquaresEstimator(
            lam=1e-3, hbm_bytes=16 << 30, num_machines=1,
            allow_approximate=True,
        )
        s, ls = self._sample()
        chosen, audit = _optimize_audited(est, s, ls)
        inner = chosen.estimator
        assert isinstance(inner, SparseLBFGSwithL2) and inner.solver == "gram"
        _audit_winner(audit, inner)
        by_label = {c["label"]: c for c in audit["candidates"]}
        for label in ("SketchedLeastSquares", "IterativeHessianSketch"):
            assert label in by_label, sorted(by_label)
            assert by_label[label]["feasible"] is True, by_label[label]
        sparsity = self.NNZ / self.D
        gather = SparseLBFGSwithL2(
            lam=1e-3, num_iterations=20, solver="gather"
        )
        c_gather = _cost_of(est, gather, self.N, self.D, self.K, sparsity)
        c_ihs = _cost_of(
            est, IterativeHessianSketch(lam=1e-3),
            self.N, self.D, self.K, sparsity,
        )
        c_srht = _cost_of(
            est, SketchedLeastSquares(lam=1e-3),
            self.N, self.D, self.K, sparsity,
        )
        assert c_ihs < c_gather, (c_ihs, c_gather)
        # SRHT's PCG data passes keep it under the gather engine too at
        # this geometry, but above IHS — the frontier row's ordering.
        assert c_ihs < c_srht < c_gather, (c_ihs, c_srht, c_gather)

    def test_tpu_weight_magnitudes_land_near_measured(self):
        """The TPU fit should PREDICT the two measured engine times within
        a small factor, not just rank them: gather 7.903 s, gram 1.805 s
        (n=500k row). Guards against weights that rank correctly by
        accident while being orders of magnitude off."""
        sparsity = self.NNZ / self.D
        gather = SparseLBFGSwithL2(
            lam=1e-3, num_iterations=20, solver="gather"
        )
        gram = SparseLBFGSwithL2(lam=1e-3, num_iterations=20, solver="gram")
        cpu, mem, net = TPU_CPU_WEIGHT, TPU_MEM_WEIGHT, TPU_NETWORK_WEIGHT
        c_gather = gather.cost(
            self.N, self.D, self.K, sparsity, 1, cpu, mem, net
        )
        c_gram = gram.cost(self.N, self.D, self.K, sparsity, 1, cpu, mem, net)
        assert 0.5 < c_gather / 7.903 < 2.0, c_gather
        assert 0.5 < c_gram / 1.805 < 2.0, c_gram


class TestReplayAmazonCompressedResident:
    # Round-5 resident probe, promoted to a tier (ISSUE 8): the
    # compressed int16+bf16 COO at n=30e6 is 9.8 GB measured on-chip
    # (fit-path folds ran from it in place), while the raw int32+f32
    # operand at the same n is 19.7 GB — past any 16 GB budget. The
    # selector must route this geometry CHIP-RESIDENT through the
    # compressed gram engine, not stream it.
    N, D, NNZ, K = 30_000_000, 16_384, 82, 2

    def _sample(self):
        rng = np.random.default_rng(8)
        idx = rng.integers(0, self.D, size=(24, self.NNZ)).astype(np.int32)
        idx[0, 0] = self.D - 1
        s = Dataset(
            {"indices": jnp.asarray(idx),
             "values": jnp.asarray(
                 rng.normal(size=(24, self.NNZ)).astype(np.float32))},
            n=24,
        )
        s.total_n = self.N
        s.source_row_bytes = self.NNZ * 4.0
        ls = Dataset.of(rng.normal(size=(24, self.K)).astype(np.float32))
        return s, ls

    def test_compressed_resident_selected_over_streamed(self):
        est = LeastSquaresEstimator(
            lam=1e-3, hbm_bytes=16 << 30, num_machines=1,
            host_budget_bytes=64 << 30,
        )
        s, ls = self._sample()
        chosen, audit = _optimize_audited(est, s, ls)
        assert isinstance(chosen, TransformerLabelEstimatorChain), chosen
        inner = chosen.estimator
        assert isinstance(inner, SparseLBFGSwithL2)
        assert inner.solver == "gram" and inner.compress == "int16_bf16"
        _audit_winner(audit, inner)  # "SparseLBFGSwithL2[gram,int16_bf16]"
        # The audit shows the capacity cut doing the work: the raw gram
        # engine priced infeasible, the compressed storage class feasible.
        feas = {c["label"]: c["feasible"] for c in audit["candidates"]}
        assert not feas["SparseLBFGSwithL2[gram]"]
        assert feas["SparseLBFGSwithL2[gram,int16_bf16]"]

    def test_feasibility_is_what_flips_the_choice(self):
        # The storage classes at this geometry, priced directly: raw COO
        # (8 B/nnz) busts the budget, compressed (4 B/nnz) fits — the
        # cost model is identical, so the capacity cut IS the decision.
        est = LeastSquaresEstimator(
            lam=1e-3, hbm_bytes=16 << 30, num_machines=1,
            host_budget_bytes=64 << 30,
        )
        budget = (16 << 30) * est.hbm_utilization
        sparsity = self.NNZ / self.D
        raw = SparseLBFGSwithL2(lam=1e-3, num_iterations=20, solver="gram")
        comp = SparseLBFGSwithL2(lam=1e-3, num_iterations=20,
                                 solver="gram", compress="int16_bf16")
        rb_raw = raw.resident_bytes(self.N, self.D, self.K, sparsity, 1)
        rb_comp = comp.resident_bytes(self.N, self.D, self.K, sparsity, 1)
        assert rb_raw > budget, (rb_raw, budget)
        assert rb_comp <= budget, (rb_comp, budget)
        c_raw = _cost_of(est, raw, self.N, self.D, self.K, sparsity)
        c_comp = _cost_of(est, comp, self.N, self.D, self.K, sparsity)
        assert c_raw == c_comp  # same engine, same model — capacity play

    def test_raw_still_wins_ties_when_both_fit(self):
        # At n=500k (the amazon_sparse row) both storage classes fit:
        # equal cost, and the selector keeps the raw engine (listed
        # first) — compression engages only when residency binds.
        est = LeastSquaresEstimator(
            lam=1e-3, hbm_bytes=16 << 30, num_machines=1
        )
        s, ls = TestReplayAmazonSparse()._sample()
        chosen, audit = _optimize_audited(est, s, ls)
        inner = chosen.estimator
        assert isinstance(inner, SparseLBFGSwithL2)
        assert inner.solver == "gram" and inner.compress is None
        _audit_winner(audit, inner)  # raw engine wins the tie on record


class TestReplayMeshLayout:
    """ISSUE 16: mesh layouts are first-class priced candidates whose
    ``mesh_layout`` CostDecision events flow through the calibration
    plane. The pin: at the amazon_fulln geometry (n=65e6, d=16384(+1),
    nnz=82(+1 intercept), k=2) on 8 devices the recorded winner is the
    full data-parallel layout — the one MULTICHIP_r05 dry-ran and the
    multichip_amazon_fulln row targets."""

    N, D1, W, K = 65_000_000, 16_385, 83, 2

    def _choose_traced(self, **kw):
        from keystone_tpu.ops.learning import cost as cost_mod

        with obs.tracing() as t:
            (p, q), ref = cost_mod.choose_mesh_layout(
                self.N, self.D1, self.K, nnz_per_row=self.W,
                num_devices=8, **kw,
            )
        decisions = [
            e for e in t.events
            if e["type"] == "event" and e["name"] == "cost.decision"
            and e["args"]["decision"] == "mesh_layout"
        ]
        assert len(decisions) == 1, decisions
        return (p, q), ref, decisions[0]["args"], t

    def test_recorded_layout_winner_pinned(self):
        from keystone_tpu.ops.learning import cost as cost_mod

        (p, q), ref, args, _ = self._choose_traced()
        assert (p, q) == (8, 1)
        assert args["winner"] == "mesh[data=8,model=1]"
        assert args["reason"] == "argmin"
        labels = [c["label"] for c in args["candidates"]]
        assert labels == [
            cost_mod.mesh_layout_label(*layout)
            for layout in cost_mod.MESH_LAYOUTS
        ]
        by_label = {c["label"]: c for c in args["candidates"]}
        # Every candidate feasible at 8 devices, each priced, and the
        # model-parallel replica tax makes 4x2 strictly costlier than
        # 4x1 (same data shards + an extra replica of every shard).
        assert all(c["feasible"] for c in args["candidates"])
        assert (by_label["mesh[data=4,model=2]"]["cost_s"]
                > by_label["mesh[data=4,model=1]"]["cost_s"])
        assert (by_label["mesh[data=8,model=1]"]["cost_s"]
                < by_label["mesh[data=4,model=1]"]["cost_s"])
        # Geometry + weight family ride in the event (refit provenance).
        assert args["n"] == self.N and args["d"] == self.D1
        assert args["weights"]["family"] == "tpu"

    def test_stamped_outcome_joins_through_calibration_plane(self):
        from keystone_tpu.obs import calibrate as cal

        _, ref, _, t = self._choose_traced()
        assert ref is not None
        ref.stamp(28.5, timing="wall")
        assert "mesh_layout" in cal.CALIBRATED_DECISIONS
        rows = cal.join_decisions(t.events)
        mesh_rows = [r for r in rows if r.decision == "mesh_layout"]
        assert len(mesh_rows) == 1, rows
        row = mesh_rows[0]
        assert row.winner == "mesh[data=8,model=1]"
        assert row.measured_s == pytest.approx(28.5)
        assert row.joined_via == "outcome"
        assert row.predicted_s > 0
        assert row.log_error() is not None

    def test_infeasible_layouts_cut_by_device_count(self):
        from keystone_tpu.ops.learning import cost as cost_mod

        with obs.tracing() as t:
            (p, q), _ = cost_mod.choose_mesh_layout(
                self.N, self.D1, self.K, nnz_per_row=self.W,
                num_devices=4,
            )
        assert (p, q) == (4, 1)
        args = [
            e for e in t.events
            if e["type"] == "event" and e["name"] == "cost.decision"
            and e["args"]["decision"] == "mesh_layout"
        ][0]["args"]
        feas = {c["label"]: c["feasible"] for c in args["candidates"]}
        assert not feas["mesh[data=8,model=1]"]
        assert not feas["mesh[data=4,model=2]"]
        assert feas["mesh[data=4,model=1]"]

    def test_compressed_bytes_constant_matches_resident_tier(self):
        # cost.py prices per-device residency with its own default so it
        # never imports the data plane; the constant must TRACK the
        # resident tier's real encoding (int16 idx + bf16 val = 4 B/nnz).
        from keystone_tpu.data import resident
        from keystone_tpu.ops.learning import cost as cost_mod

        assert (cost_mod.COMPRESSED_BYTES_PER_NNZ_DEFAULT
                == resident.COMPRESSED_BYTES_PER_NNZ)


class TestWeightFamilySwitch:
    def test_tpu_active_by_default(self, monkeypatch):
        monkeypatch.delenv("KEYSTONE_COST_WEIGHTS", raising=False)
        assert active_weights() == (
            TPU_CPU_WEIGHT, TPU_MEM_WEIGHT, TPU_NETWORK_WEIGHT
        )
        assert sparse_gather_overhead() == 500.0
        est = LeastSquaresEstimator(lam=0.1)
        assert est.cpu_weight == TPU_CPU_WEIGHT
        assert est.mem_weight == TPU_MEM_WEIGHT

    def test_ec2_env_restores_reference_constants(self, monkeypatch):
        monkeypatch.setenv("KEYSTONE_COST_WEIGHTS", "ec2")
        assert active_weights() == (
            EC2_CPU_WEIGHT, EC2_MEM_WEIGHT, EC2_NETWORK_WEIGHT
        )
        assert sparse_gather_overhead() == 8.0
        est = LeastSquaresEstimator(lam=0.1)
        assert est.cpu_weight == EC2_CPU_WEIGHT

    def test_explicit_weights_still_win(self, monkeypatch):
        monkeypatch.delenv("KEYSTONE_COST_WEIGHTS", raising=False)
        est = LeastSquaresEstimator(lam=0.1, cpu_weight=1.0, mem_weight=2.0)
        assert est.cpu_weight == 1.0 and est.mem_weight == 2.0

    def test_calibrated_artifact_family(self, monkeypatch, tmp_path):
        """The third family (ISSUE 13): a trace-refit artifact selected
        via KEYSTONE_COST_WEIGHTS=calibrated:<path> drives the selector
        exactly like the built-in constants. The refit round-trip
        against the golden trace fixture — loading the artifact
        reproduces the recorded winners at these replay geometries —
        lives in tests/test_calibrate.py::TestRefitRoundTrip."""
        from keystone_tpu.obs import calibrate as cal

        path = str(tmp_path / "cal.json")
        cal.write_calibration_artifact(
            path,
            {"cpu": 7e-15, "mem": 3e-11, "network": 2e-11,
             "sparse_gather_overhead": 321.0},
            {"run_ids": ["test"]},
        )
        monkeypatch.setenv("KEYSTONE_COST_WEIGHTS", f"calibrated:{path}")
        assert active_weights() == (7e-15, 3e-11, 2e-11)
        assert sparse_gather_overhead() == 321.0
        est = LeastSquaresEstimator(lam=0.1)
        assert est.cpu_weight == 7e-15 and est.mem_weight == 3e-11


def _placement_events(t, kind):
    return [
        e["args"] for e in t.events
        if e["type"] == "event" and e["name"] == "placement.decision"
        and e["args"]["decision"] == kind
    ]


class TestReplayUnifiedPlacement:
    """ISSUE 19 tentpole pin: every decision site routes through the ONE
    :class:`keystone_tpu.placement.engine.PlacementEngine`, mirrored
    into the unified ``placement.decision`` stream — and the unified
    engine reproduces every recorded winner bit for bit (ties keep the
    legacy first-minimum resolution)."""

    def test_solver_mirror_reproduces_timit_resident_winner(self):
        est = LeastSquaresEstimator(
            lam=1e-4, hbm_bytes=48 << 30, num_machines=1
        )
        s, ls = _dense_sample(262_144, 16_384, 147)
        with obs.tracing() as t:
            est.optimize(s, ls)
        legacy = [
            e["args"] for e in t.events
            if e["type"] == "event" and e["name"] == "cost.decision"
            and e["args"]["decision"] == "least_squares_solver"
        ]
        mirrors = _placement_events(t, "placement.solver")
        assert len(legacy) == 1 and len(mirrors) == 1
        assert mirrors[0]["winner"] == legacy[0]["winner"] \
            == "BlockLeastSquaresEstimator"
        assert mirrors[0]["reason"] == "argmin"
        assert mirrors[0]["weights_family"] == "tpu"
        assert len(mirrors[0]["candidates"]) == len(est.options)

    def test_solver_mirror_reproduces_fulln_streaming_winner(self):
        est = LeastSquaresEstimator(
            lam=1e-4, hbm_bytes=16 << 30, num_machines=1
        )
        s, ls = _dense_sample(2_200_000, 16_384, 147)
        with obs.tracing() as t:
            est.optimize(s, ls)
        (mirror,) = _placement_events(t, "placement.solver")
        assert mirror["winner"] == "StreamingLeastSquaresChoice"
        # Infeasible residents carry cost_s=None + feasible=False in the
        # normalized unified stream (inf never reaches JSON).
        by_label = {c["label"]: c for c in mirror["candidates"]}
        assert by_label["DenseLBFGSwithL2"]["feasible"] is False
        assert by_label["DenseLBFGSwithL2"]["cost_s"] is None

    def test_solver_mirror_reproduces_amazon_gram_variants(self):
        for n, hbm, host, expect in (
            (None, 16 << 30, None, "SparseLBFGSwithL2[gram]"),
            (30_000_000, 16 << 30, 64 << 30,
             "SparseLBFGSwithL2[gram,int16_bf16]"),
        ):
            kw = {"lam": 1e-3, "hbm_bytes": hbm, "num_machines": 1}
            if host is not None:
                kw["host_budget_bytes"] = host
            est = LeastSquaresEstimator(**kw)
            sampler = (
                TestReplayAmazonSparse() if n is None
                else TestReplayAmazonCompressedResident()
            )
            s, ls = sampler._sample()
            with obs.tracing() as t:
                est.optimize(s, ls)
            (mirror,) = _placement_events(t, "placement.solver")
            assert mirror["winner"] == expect, mirror

    def test_mesh_mirror_and_single_calibration_join(self):
        from keystone_tpu.obs import calibrate as cal
        from keystone_tpu.ops.learning import cost as cost_mod

        with obs.tracing() as t:
            cost_mod.choose_mesh_layout(
                65_000_000, 16_385, 2, nnz_per_row=83, num_devices=8
            )
        (mirror,) = _placement_events(t, "placement.mesh_layout")
        assert mirror["winner"] == "mesh[data=8,model=1]"
        assert mirror["weights_family"] == "tpu"
        # The namespaced placement kind must NOT double-join: extending
        # join_decisions to both event names still yields exactly one
        # mesh_layout row per decision.
        rows = cal.join_decisions(t.events)
        assert len([r for r in rows if r.decision == "mesh_layout"]) == 1

    def test_image_tier_mirror_reproduces_winner(self):
        from keystone_tpu.ops.learning import cost as cost_mod

        with obs.tracing() as t:
            tier, _ = cost_mod.choose_image_tier(
                50_000, 3072, 10, host_budget_bytes=4 << 30
            )
        (mirror,) = _placement_events(t, "placement.image_tier")
        assert mirror["winner"] == tier
        legacy = [
            e["args"] for e in t.events
            if e["type"] == "event" and e["name"] == "cost.decision"
            and e["args"]["decision"] == "image_tier"
        ]
        assert legacy[0]["winner"] == tier

    def test_all_six_streams_carry_weights_family(self):
        from keystone_tpu.serving.autoscale import AutoscaleDecision
        from keystone_tpu.serving.lifecycle import LifecycleDecision
        from keystone_tpu.serving.zoo import ZooDecision

        a = AutoscaleDecision(
            action="scale_up", reason="r", ok=True, t_s=0.0,
            inputs={}, thresholds={}, winner="replicas=2",
            candidates=({"label": "replicas=2"},), weights_family="tpu",
        ).to_args()
        z = ZooDecision(
            action="page_in", tenant="t", reason="r", ok=True, t_s=0.0,
            inputs={}, weights_family="tpu",
        ).to_args()
        lc = LifecycleDecision(
            action="publish", reason="r", fingerprint="f", ok=True,
            t_s=0.0, inputs={}, thresholds={}, weights_family="tpu",
        ).to_args()
        for args in (a, z, lc):
            assert args["weights_family"] == "tpu"
            assert "winner" in args and "candidates" in args
        # cost.decision + the placement stream (covered live above)
        # carry it via CostDecision.to_args / PlacementEngine._emit.
        dec = obs.CostDecision(
            decision="least_squares_solver", winner="w", candidates=[],
            reason="argmin", context={"weights": {"family": "ec2"}},
        )
        assert dec.to_args()["weights_family"] == "ec2"

    def test_engine_first_minimum_tie_and_fallback(self):
        from keystone_tpu.placement.engine import (
            KIND_SOLVER, PlacementEngine,
        )

        eng = PlacementEngine(weights_family="tpu")
        tie = eng.decide(KIND_SOLVER, [
            {"label": "a", "cost_s": 1.0, "feasible": True},
            {"label": "b", "cost_s": 1.0, "feasible": True},
        ])
        assert tie.winner == "a" and tie.index == 0  # first minimum
        fb = eng.decide(KIND_SOLVER, [
            {"label": "big", "cost_s": None, "feasible": False,
             "resident_bytes": 9e9},
            {"label": "small", "cost_s": None, "feasible": False,
             "resident_bytes": 1e9},
        ], fallback="least_resident")
        assert fb.winner == "small"
        assert fb.reason == "least_resident_fallback"
        with pytest.raises(ValueError):
            eng.decide(KIND_SOLVER, [
                {"label": "x", "cost_s": None, "feasible": False},
            ])


class TestCapacityPlannerGoldenTrace:
    """ISSUE 19 planner pin: replaying a recorded storm through
    :class:`keystone_tpu.placement.planner.CapacityPlanner` reproduces
    every recorded argmin winner, predicts the 1x p99 within the
    calibration plane's error bars, and degrades monotonically under
    2x traffic."""

    @pytest.fixture()
    def golden_dir(self, tmp_path):
        import time

        from keystone_tpu.placement.engine import (
            KIND_ZOO_PAGE_IN, PlacementEngine,
        )
        from keystone_tpu.ops.learning import cost as cost_mod

        td = str(tmp_path / "trace")
        rng = np.random.default_rng(0)
        s = Dataset.of(rng.normal(size=(24, 16_384)).astype(np.float32))
        s.total_n = 262_144
        s.source_row_bytes = 4.0 * 440
        ls = Dataset.of(rng.normal(size=(24, 147)).astype(np.float32))
        with obs.tracing(td) as tracer:
            est = LeastSquaresEstimator(
                lam=1e-4, hbm_bytes=48 << 30, num_machines=1
            )
            est.optimize(s, ls)
            cost_mod.choose_mesh_layout(
                65_000_000, 16_385, 2, nnz_per_row=83, num_devices=8
            )
            eng = PlacementEngine()
            priced = eng.price_page_in(1 << 28)
            ref = eng.audit(
                KIND_ZOO_PAGE_IN, "tenant-a",
                [{"label": "tenant-a", "cost_s": priced,
                  "feasible": True, "resident_bytes": float(1 << 28)}],
                reason="page_fault", context={},
            )
            ref.stamp(priced * 1.05, timing="single_run_cold")
            # The storm's occupancy snapshots: replicas ramp to 4 with
            # the backlog peaking at queue=6 / outstanding=6.
            for replicas, queue, outstanding in (
                (1, 2.0, 2.0), (2, 4.0, 4.0), (4, 6.0, 6.0),
            ):
                obs.event(
                    "autoscale.decision", action="scale_up",
                    reason="queue_pressure", ok=True,
                    winner=f"replicas={replicas}", candidates=[],
                    weights_family="tpu",
                    inputs={"replicas": replicas, "queue_depth": queue,
                            "outstanding": outstanding},
                )
            # Batch latencies: p50 = 10 ms service floor, measured tail
            # stretched to 35 ms by the storm.
            t0 = time.perf_counter()
            for i in range(100):
                dur = 0.010 if i < 98 else 0.035
                start = t0 + i * 0.05
                tracer.add_span("serving.batch", start, start + dur)
        return td

    def _planner(self, golden_dir):
        from keystone_tpu.obs.export import load_events
        from keystone_tpu.placement.planner import CapacityPlanner

        return CapacityPlanner(load_events(golden_dir))

    def test_one_x_replay_reproduces_and_stays_in_error_bars(
        self, golden_dir
    ):
        from keystone_tpu.obs.calibrate import DEFAULT_DRIFT_THRESHOLD

        planner = self._planner(golden_dir)
        fid = planner.fidelity()
        assert fid["num_replayed"] >= 4  # solver + mesh, both streams
        assert fid["num_reproduced"] == fid["num_replayed"], fid
        assert fid["num_outcomes"] >= 1  # the stamped page-in
        assert fid["max_abs_log_error"] < DEFAULT_DRIFT_THRESHOLD
        row = planner.whatif_traffic(1.0)
        assert row["abs_log_error_1x"] < DEFAULT_DRIFT_THRESHOLD, row

    def test_two_x_traffic_monotonically_degrades_p99(self, golden_dir):
        planner = self._planner(golden_dir)
        row = planner.whatif_traffic(2.0)
        assert row["predicted_p99_s"] > row["predicted_p99_1x_s"]
        assert row["predicted_p99_1x_s"] >= row["measured_p99_s"] * 0.5
        # Self-auditing shape (the bench _whatif_violations contract).
        assert row["num_decisions"] > 0
        assert isinstance(row["weights_family"], str)
        assert row["measured_p99_s"] is not None

    def test_half_hbm_flips_the_resident_winner(self, golden_dir):
        planner = self._planner(golden_dir)
        row = planner.whatif_hbm(0.5)
        assert row["whatif_changed_winners"] >= 1, row
        flipped = {c["kind"] for c in row["changed"]}
        assert "least_squares_solver" in flipped
        assert "placement.solver" in flipped  # both streams agree

    def test_added_tenant_priced_from_calibrated_family(self, golden_dir):
        planner = self._planner(golden_dir)
        row = planner.whatif_tenants(1)
        assert row["whatif_added_page_seconds"] > 0
        assert row["predicted_page_in_s"] == pytest.approx(
            row["whatif_added_page_seconds"]
        )
        # Predicted within the measured page-in's error bars (stamped
        # at 1.05x the priced seconds above).
        assert row["measured_page_in_p50_s"] == pytest.approx(
            row["predicted_page_in_s"] * 1.05
        )

    def test_mesh_whatif_prices_requested_vs_winner(self, golden_dir):
        planner = self._planner(golden_dir)
        row = planner.whatif_mesh("mesh[data=4,model=1]")
        assert row["recorded_winner"] == "mesh[data=8,model=1]"
        assert row["whatif_slowdown_x"] > 1.0

    def test_bin_plan_cli_runs_the_whatifs(self, golden_dir, capsys):
        from keystone_tpu.tools.plan import main as plan_main

        rc = plan_main([
            golden_dir, "--whatif", "traffic=2x", "--whatif", "hbm=0.5x",
            "--whatif", "tenants=+1", "--whatif", "mesh=8x1",
        ])
        out = capsys.readouterr().out
        assert rc == 0, out
        assert "1x fidelity" in out and "OK" in out
        assert "traffic=2x" in out and "hbm=0.5x" in out

    def test_cli_json_plan_is_machine_readable(self, golden_dir, capsys):
        import json

        from keystone_tpu.tools.plan import main as plan_main

        rc = plan_main([golden_dir, "--whatif", "traffic=2x", "--json"])
        assert rc == 0
        plan = json.loads(capsys.readouterr().out)
        assert plan["fidelity"]["num_reproduced"] \
            == plan["fidelity"]["num_replayed"]
        assert plan["whatifs"][0]["whatif"] == "traffic=2x"

    # ---- ROADMAP item 3's last loop: --apply -> serve --from-plan ----

    def test_apply_writes_gated_defaults_artifact(self, golden_dir,
                                                  tmp_path, capsys):
        import json

        from keystone_tpu.tools.plan import (
            PLAN_ARTIFACT_KIND, main as plan_main,
        )

        out_path = str(tmp_path / "defaults.json")
        rc = plan_main([golden_dir, "--apply", out_path])
        out = capsys.readouterr().out
        assert rc == 0, out
        assert f"apply: wrote {out_path}" in out
        with open(out_path) as f:
            doc = json.load(f)
        assert doc["artifact"] == PLAN_ARTIFACT_KIND
        # Every default is a function of the MEASURED baseline.
        d = doc["serve_defaults"]
        assert d["replicas"] == doc["baseline"]["replicas_peak"] == 4
        assert d["max_replicas"] == 8
        assert d["queue_depth"] >= 64  # 2x headroom over peak, floored
        assert d["slo_p99_ms"] == pytest.approx(
            3e3 * doc["baseline"]["measured_p99_s"], rel=1e-6
        )
        # Provenance: the artifact names its sources and the fidelity
        # verdict it was gated on.
        assert doc["source_traces"] and doc["fidelity"]["num_replayed"]

    def test_apply_refused_when_fidelity_gate_fails(self, golden_dir,
                                                    tmp_path, capsys):
        import os

        from keystone_tpu.tools.plan import main as plan_main

        out_path = str(tmp_path / "defaults.json")
        # An absurd drift threshold fails the gate: the planner must
        # REFUSE to configure the future it cannot reproduce.
        rc = plan_main([golden_dir, "--apply", out_path,
                        "--drift-threshold", "1e-12"])
        err = capsys.readouterr().err
        assert rc == 2
        assert "REFUSED" in err
        assert not os.path.exists(out_path)

    def test_serve_from_plan_fills_only_untouched_flags(
        self, golden_dir, tmp_path, capsys
    ):
        import argparse

        from keystone_tpu.run import _serve_apply_plan_defaults
        from keystone_tpu.tools.plan import main as plan_main

        out_path = str(tmp_path / "defaults.json")
        assert plan_main([golden_dir, "--apply", out_path]) == 0
        capsys.readouterr()

        parser = argparse.ArgumentParser()
        parser.add_argument("--replicas", type=int, default=1)
        parser.add_argument("--queue-depth", type=int, default=1024)
        parser.add_argument("--slo-p99-ms", type=float, default=0.0)
        parser.add_argument("--slo-target", type=float, default=0.99)
        parser.add_argument("--min-replicas", type=int, default=1)
        parser.add_argument("--max-replicas", type=int, default=8)
        parser.add_argument("--from-plan", default="")
        args = parser.parse_args(
            ["--from-plan", out_path, "--replicas", "7"]
        )
        stamp = _serve_apply_plan_defaults(args, parser)
        # The operator's explicit flag OUTRANKS the planner...
        assert args.replicas == 7
        assert "replicas" not in stamp["applied"]
        # ...while untouched flags fill from the measured baseline.
        assert args.slo_p99_ms > 0
        assert stamp["applied"]["slo_p99_ms"] == args.slo_p99_ms
        assert stamp["applied"]["queue_depth"] == args.queue_depth
        assert stamp["path"] == out_path
        assert stamp["source_traces"]

    def test_serve_from_plan_rejects_foreign_json(self, tmp_path):
        import argparse
        import json

        from keystone_tpu.run import _serve_apply_plan_defaults

        bogus = tmp_path / "notaplan.json"
        bogus.write_text(json.dumps({"hello": "world"}))
        parser = argparse.ArgumentParser()
        parser.add_argument("--from-plan", default="")
        args = parser.parse_args(["--from-plan", str(bogus)])
        with pytest.raises(ValueError, match="not a bin/plan"):
            _serve_apply_plan_defaults(args, parser)
