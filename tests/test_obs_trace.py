"""Trace correctness of the obs plane (ISSUE 9): a checkpointed
disk-streamed fit under tracing produces spans whose per-site busy
totals agree with ``PrefetchStats.site_busy_s``, span trees are
well-formed (no orphan/inverted spans) including under an injected
``prefetch.read`` fault, a traced ``Pipeline.fit`` yields ONE
Perfetto-loadable file correlating optimizer cost decisions, runtime
lane tasks, fold chunk spans, and checkpoint write-behind under one
``run_id`` — and ``bin/trace`` summarizes it."""

import json
import os

import numpy as np
import pytest

import jax.numpy as jnp

from keystone_tpu import obs
from keystone_tpu.data import Dataset, LabeledData
from keystone_tpu.data.durable import CheckpointSpec
from keystone_tpu.data.prefetch import PrefetchStats
from keystone_tpu.data.shards import DiskDenseShards
from keystone_tpu.obs import tracer as tracer_mod
from keystone_tpu.ops.learning.cost import LeastSquaresEstimator
from keystone_tpu.ops.learning.streaming_ls import CosineBankFeaturize
from keystone_tpu.ops.stats import CosineRandomFeatures
from keystone_tpu.parallel import streaming
from keystone_tpu.utils.faults import FaultPlan, FaultRule
from keystone_tpu.workflow.env import PipelineEnv


@pytest.fixture(autouse=True)
def _no_leaked_tracer():
    yield
    tracer_mod._ACTIVE = None


def _shard_problem(tmp_path, n=2000, d_in=12, k=3, shard_rows=64):
    rng = np.random.default_rng(0)
    X = rng.normal(size=(n, d_in)).astype(np.float32)
    Y = rng.normal(size=(n, k)).astype(np.float32)
    DiskDenseShards.write(
        str(tmp_path / "sh"), X, Y, tile_rows=shard_rows,
        tiles_per_segment=1,
    )
    source = DiskDenseShards(str(tmp_path / "sh")).as_source()
    rng2 = np.random.default_rng(1)
    d_feat = 64
    bank = CosineBankFeaturize(
        rng2.normal(size=(d_feat, d_in)).astype(np.float32) * 0.3,
        rng2.uniform(0, 6, d_feat).astype(np.float32),
    )

    def fit(stats=None, checkpoint=None):
        return streaming.streaming_bcd_fit_segments(
            source, bank=bank, d_feat=d_feat, block_size=16, lam=1e-3,
            num_iter=1, center=False, prefetch_depth=2,
            prefetch_stats=stats, checkpoint=checkpoint,
        )

    return source, fit


def _assert_well_formed(spans, run_id):
    """Every span's parent exists, opened before it, and closed after it
    (no orphans, no inverted nesting) — per thread, which is the only
    scope parent links are made in; and one run_id stamps everything."""
    by_id = {s["span_id"]: s for s in spans}
    assert spans, "trace recorded no spans"
    for s in spans:
        assert s["run_id"] == run_id
        pid = s.get("parent_id")
        if pid is None:
            continue
        parent = by_id.get(pid)
        assert parent is not None, f"orphan span {s['name']} -> {pid}"
        assert parent["thread"] == s["thread"]
        assert parent["ts_us"] <= s["ts_us"] + 1, (
            f"{parent['name']} opened after child {s['name']}"
        )
        assert (parent["ts_us"] + parent["dur_us"]
                >= s["ts_us"] + s["dur_us"] - 1), (
            f"{parent['name']} closed before child {s['name']}"
        )


def _span_sum_s(spans, name):
    return sum(s["dur_us"] for s in spans if s["name"] == name) / 1e6


def _assert_busy_agreement(spans, stats):
    """Per-site busy totals from PrefetchStats agree with the span sums
    over the spans instrumented at the SAME regions."""
    busy = stats.site_busy_s
    for site, span_name in (
        ("read", "prefetch.read"),
        ("compute", "fold.segment"),
        ("checkpoint", "checkpoint.write"),
    ):
        if site not in busy:
            continue
        span_s = _span_sum_s(spans, span_name)
        # The span and the counter bracket the same code region; allow
        # per-call bracketing skew + CI scheduling noise.
        tol = 0.35 * busy[site] + 0.06
        assert abs(span_s - busy[site]) <= tol, (
            site, span_s, busy[site]
        )


class TestTraceCorrectness:
    def test_checkpointed_streamed_fit_busy_totals_and_tree(
        self, tmp_path
    ):
        _, fit = _shard_problem(tmp_path)
        stats = PrefetchStats()
        ckpt = CheckpointSpec(str(tmp_path / "ck"), every_segments=4)
        with obs.tracing() as t:
            W, _, _, loss = fit(stats=stats, checkpoint=ckpt)
        assert np.isfinite(float(loss))
        spans = t.spans()
        _assert_well_formed(spans, t.run_id)
        _assert_busy_agreement(spans, stats)
        # The load-bearing seams all reported: read + wait + fold +
        # write-behind checkpoint + the runtime lane tasks hosting them.
        names = {s["name"] for s in spans}
        assert {"prefetch.read", "prefetch.wait", "fold.segment",
                "checkpoint.write", "checkpoint.submit",
                "runtime.task"} <= names
        # Write-behind: checkpoint.write ran on the checkpoint lane's
        # worker, nested under its runtime.task span.
        writes = [s for s in spans if s["name"] == "checkpoint.write"]
        assert writes and all(
            s["thread"] == "keystone-io-checkpoint" for s in writes
        )
        assert all(s["parent_id"] is not None for s in writes)
        # Reads ran on the read lane's worker.
        reads = [s for s in spans if s["name"] == "prefetch.read"]
        assert reads and all(
            s["thread"] == "keystone-io-read" for s in reads
        )

    def test_trace_well_formed_under_injected_prefetch_fault(
        self, tmp_path
    ):
        _, fit = _shard_problem(tmp_path)
        stats = PrefetchStats()
        flaky = FaultPlan([FaultRule("prefetch.read", "error",
                                     calls=[1, 3])])
        with obs.tracing() as t:
            with flaky:
                W, _, _, loss = fit(stats=stats)
        assert stats.retries == 2  # the retry layer absorbed both
        spans = t.spans()
        _assert_well_formed(spans, t.run_id)
        _assert_busy_agreement(spans, stats)

    def test_serial_leg_reads_same_span_name(self, tmp_path):
        source, _ = _shard_problem(tmp_path, n=500, shard_rows=128)
        rng = np.random.default_rng(1)
        bank = CosineBankFeaturize(
            rng.normal(size=(32, 12)).astype(np.float32) * 0.3,
            rng.uniform(0, 6, 32).astype(np.float32),
        )
        stats = PrefetchStats()
        with obs.tracing() as t:
            streaming.streaming_bcd_fit_segments(
                source, bank=bank, d_feat=32, block_size=16, lam=1e-3,
                num_iter=1, center=False, prefetch_depth=0,
                prefetch_stats=stats,
            )
        spans = t.spans("prefetch.read")
        assert spans and all(s["args"].get("serial") for s in spans)
        _assert_busy_agreement(t.spans(), stats)


class TestTracedPipelineFit:
    def test_single_traced_fit_produces_correlated_perfetto_trace(
        self, tmp_path, monkeypatch
    ):
        """The acceptance path: one traced fit through Pipeline.fit
        routed out-of-core with checkpointing — the written file is
        Chrome-trace-valid and contains optimizer cost-decision events,
        runtime lane tasks, fold chunk spans, and checkpoint
        write-behind spans sharing one run_id."""
        PipelineEnv.get_or_create().reset()
        monkeypatch.setenv("KEYSTONE_CHECKPOINT_DIR",
                           str(tmp_path / "ck"))
        monkeypatch.setenv("KEYSTONE_CHECKPOINT_EVERY", "8")
        rng = np.random.default_rng(0)
        n, d_in, d_feat, k = 4096, 16, 256, 4
        X = rng.normal(size=(n, d_in)).astype(np.float32)
        Y = rng.normal(size=(n, k)).astype(np.float32)
        sld = LabeledData(X, Y).to_disk_shards(
            str(tmp_path / "sh"), shard_rows=128, tiles_per_segment=1
        )
        crf = CosineRandomFeatures(d_in, d_feat, 0.2, seed=1)
        auto = LeastSquaresEstimator(lam=0.1, host_budget_bytes=64 << 10)
        trace_dir = str(tmp_path / "trace")
        with obs.tracing(trace_dir) as t:
            p = crf.to_pipeline().and_then(auto, sld.data, sld.labels)
            fitted = p.fit()
        assert fitted is not None

        events = obs.load_events(trace_dir)
        run_ids = {e["run_id"] for e in events if "run_id" in e}
        assert run_ids == {t.run_id}
        names = {e["name"] for e in events}
        # The four correlated record families the acceptance names,
        # plus the fit phases around them.
        assert "cost.decision" in names
        assert "runtime.task" in names
        assert "fold.segment" in names
        assert "checkpoint.write" in names
        assert "pipeline.fit" in names
        assert "verify.pre_pass" in names
        assert any(n.startswith("optimizer.rule.") for n in names)
        # The solver selection recorded the disk-tier winner.
        decisions = [
            e for e in events
            if e.get("type") == "event" and e["name"] == "cost.decision"
            and e["args"].get("decision") == "least_squares_solver"
        ]
        assert decisions
        assert decisions[-1]["args"]["winner"] == (
            "StreamingLeastSquaresChoice"
        )
        # Lane tasks cover both IO lanes of the fit.
        lanes = {
            (e.get("args") or {}).get("lane")
            for e in events if e["name"] == "runtime.task"
        }
        assert {"read", "checkpoint"} <= lanes
        # The written Chrome trace validates against the schema.
        doc = json.loads(
            open(os.path.join(trace_dir, "trace.json")).read()
        )
        assert obs.validate_chrome_trace(doc) == []
        spans = [e for e in events if e.get("type") == "span"]
        _assert_well_formed(spans, t.run_id)


class TestServingBridge:
    def test_traced_requests_emit_serving_spans(self):
        from keystone_tpu.serving.batcher import MicroBatchServer
        from keystone_tpu.serving.export import export_plan
        from keystone_tpu.workflow import Transformer
        from tests._serving_util import fitted_from_transformer

        class Scale2(Transformer):
            def apply(self, x):
                return jnp.asarray(x) * 2.0

            def device_operands(self):
                return (), ()

            @staticmethod
            def device_apply(static_key, params, X):
                return X * 2.0

        plan = export_plan(
            fitted_from_transformer(Scale2()), np.zeros(4, np.float32),
            max_batch=8,
        )
        with obs.tracing() as t:
            with MicroBatchServer(plan, max_wait_ms=1.0) as srv:
                futs = [srv.submit(np.full(4, float(i), np.float32))
                        for i in range(5)]
                outs = [f.result(timeout=10.0) for f in futs]
        np.testing.assert_allclose(
            np.asarray(outs[3]), np.full(4, 6.0), rtol=1e-6
        )
        reqs = t.spans("serving.request")
        assert len(reqs) == 5
        assert t.spans("serving.batch")
        counters = [e for e in t.events if e.get("type") == "counter"
                    and e["name"] == "serving.queue_depth"]
        assert counters  # the queue-depth counter track recorded


class TestTraceCLI:
    def _make_trace(self, tmp_path) -> str:
        _, fit = _shard_problem(tmp_path)
        stats = PrefetchStats()
        ckpt = CheckpointSpec(str(tmp_path / "ck"), every_segments=4)
        trace_dir = str(tmp_path / "trace")
        with obs.tracing(trace_dir):
            fit(stats=stats, checkpoint=ckpt)
            obs.record_cost_decision(obs.CostDecision(
                decision="least_squares_solver", winner="X",
                candidates=[{"label": "X", "feasible": True}],
            ))
        return trace_dir

    def test_cli_summarizes_and_emits_perfetto(self, tmp_path, capsys):
        from keystone_tpu.tools import trace as trace_cli

        trace_dir = self._make_trace(tmp_path)
        out_json = str(tmp_path / "out" / "perfetto.json")
        rc = trace_cli.main([trace_dir, "--perfetto", out_json])
        assert rc == 0
        printed = capsys.readouterr().out
        assert "top" in printed and "self-time" in printed
        assert "per-lane occupancy" in printed
        assert "cost decisions" in printed
        assert "winner=X" in printed
        doc = json.loads(open(out_json).read())
        assert obs.validate_chrome_trace(doc) == []

    def test_cli_errors_on_missing_dir(self, tmp_path, capsys):
        from keystone_tpu.tools import trace as trace_cli

        rc = trace_cli.main([str(tmp_path / "nope")])
        assert rc == 1

    def test_summarize_self_time_subtracts_children(self, tmp_path):
        from keystone_tpu.tools.trace import summarize

        with obs.tracing() as t:
            with obs.span("parent"):
                import time as _t

                with obs.span("child"):
                    _t.sleep(0.05)
        s = summarize(t.events)
        st = s["self_times"]
        assert st["child"]["self_s"] >= 0.045
        assert st["parent"]["self_s"] <= st["parent"]["total_s"] - 0.045


class TestPerDeviceTracks:
    """Mesh-run trace rendering (ISSUE 16): spans tagged ``device=`` and
    the per-device ``read.d<k>`` ingestion lanes surface as a
    per-device occupancy table in ``bin/trace`` and as one Perfetto
    track per device in the Chrome export."""

    def _mesh_trace(self):
        with obs.tracing() as t:
            # Two per-device ingestion lanes + one collective fold
            # dispatch covering the whole data axis — the span shapes
            # _run_lbfgs_gram_streamed_mesh and iter_mesh_segments emit.
            with obs.span("runtime.task", lane="read.d0", fn="load"):
                pass
            with obs.span("runtime.task", lane="read.d1", fn="load"):
                pass
            with obs.span("runtime.task", lane="read", fn="load"):
                pass  # the single-chip lane: NOT a device track
            with obs.span(
                "fold.segment", chunk0=0, device="data[0-1]", num_devices=2
            ):
                pass
        return t.events

    def test_summary_has_per_device_occupancy(self):
        from keystone_tpu.tools.trace import _render, summarize

        s = summarize(self._mesh_trace())
        assert set(s["devices"]) == {"0", "1", "data[0-1]"}
        assert s["devices"]["0"]["spans"] == 1
        assert s["devices"]["1"]["busy_s"] >= 0.0
        # the plain "read" lane stays in the lane table only
        assert "read" in s["lanes"]
        printed = _render(s, top=5)
        assert "per-device occupancy" in printed
        assert "device-0" in printed and "device-1" in printed

    def test_perfetto_export_puts_each_device_on_its_own_track(self):
        records = self._mesh_trace()
        doc = obs.to_chrome_trace(records)
        assert obs.validate_chrome_trace(doc) == []
        names = {
            e["args"]["name"]: e["tid"] for e in doc["traceEvents"]
            if e["ph"] == "M" and e["name"] == "thread_name"
        }
        assert "device-0" in names and "device-1" in names
        assert "device-data[0-1]" in names
        assert names["device-0"] != names["device-1"]
        by_dev_tid = {
            e["tid"]: e["name"] for e in doc["traceEvents"]
            if e["ph"] == "X"
        }
        # each device track actually carries its span
        assert by_dev_tid[names["device-0"]] == "runtime.task"
        assert by_dev_tid[names["device-data[0-1]"]] == "fold.segment"
