"""Fusion-aware autocache: cache placement on the post-fusion plan.

Round 5 measured the pre-fusion world model failing: whole-chain fusion
made recompute nearly free while inserted Cachers broke the fused program,
so greedy LOST to no-cache on the reuse bench. These tests pin the round-6
contract:

  - AutoCacheRule DECLINES to insert a Cacher inside a region the fusion
    rules would compile into one program (chain links, estimator featurize
    inputs), whatever the phase order;
  - it STILL caches fused-stage boundaries: multi-consumer intermediates
    and host-loader/decode stages;
  - AutoCachingOptimizer runs cache placement after fusion, so a fully
    device-fusable chain stays ONE fused program under the caching
    optimizer, and a cached host boundary is served from the prefix state
    table on later fits (the cross-fit reuse that makes caching win);
  - the executor records observed (full-scale, post-fusion) profiles that
    greedy prefers over sampled extrapolation.
"""

import numpy as np
import pytest

from keystone_tpu.data import Dataset
from keystone_tpu.ops.util import Cacher
from keystone_tpu.workflow import Estimator, PipelineEnv, Transformer
from keystone_tpu.workflow.autocache import (
    AggressiveCache,
    AutoCacheRule,
    GreedyCache,
    clear_observed_profiles,
    get_observed_profile,
    observed_profile_key,
)
from keystone_tpu.workflow.executor import GraphExecutor
from keystone_tpu.workflow.fusion import (
    cache_would_split_fusion,
    fused_members,
    fusion_splitting_nodes,
)
from keystone_tpu.workflow.graph import Graph
from keystone_tpu.workflow.operators import DatasetOperator
from keystone_tpu.workflow.optimizer import AutoCachingOptimizer


class DeviceScale(Transformer):
    """Row-local device-pure transformer (participates in stage fusion)."""

    def __init__(self, c: float, weight: int = 1):
        self.c = float(c)
        self.weight = weight

    def device_operands(self):
        return (self.c,), ()

    @staticmethod
    def device_apply(static_key, params, X):
        return X * static_key[0]

    def apply(self, x):
        return x * self.c


class HostDecode(Transformer):
    """Host-side stage: NOT device-fusable; counts batch executions."""

    def __init__(self, weight: int = 1):
        self.weight = weight
        self.batch_ns = []  # (n,) per batch_apply call

    def apply(self, x):
        return np.sqrt(np.abs(np.asarray(x))).astype(np.float32)

    def batch_apply(self, data: Dataset) -> Dataset:
        self.batch_ns.append(data.n)
        X = np.asarray(data.array)
        return Dataset.of(np.sqrt(np.abs(X)).astype(np.float32))


class WeightedSumEstimator(Estimator):
    """Plain (non-traceable) fit making ``weight`` passes over its input."""

    weight = 4

    def fit(self, data: Dataset) -> Transformer:
        total = float(np.sum(np.asarray(data.array)))
        return DeviceScale(1.0 + 0.0 * total)


def _cachers(graph: Graph):
    return [n for n in graph.nodes if isinstance(graph.get_operator(n), Cacher)]


class TestFusionPreservingPlacement:
    """AutoCacheRule never splits a fusable region, whatever the order."""

    def _chain_graph(self):
        ds = Dataset.of(np.arange(32.0, dtype=np.float32).reshape(8, 4))
        g = Graph()
        g, d = g.add_node(DatasetOperator(ds), [])
        g, a = g.add_node(DeviceScale(2.0), [d])
        g, b = g.add_node(DeviceScale(3.0, weight=4), [a])
        g, sink = g.add_sink(b)
        return g, d, a, b

    def test_aggressive_declines_cacher_inside_fusable_chain(self):
        # a's only consumer b is weight-4 (4 weighted accesses) — the
        # pre-fusion rule would cache a, severing the a->b chain edge
        # StageFusionRule compiles into one program.
        g, d, a, b = self._chain_graph()
        assert cache_would_split_fusion(g, a, {})
        new_graph, _ = AutoCacheRule(AggressiveCache()).apply(g, {})
        assert not _cachers(new_graph)

    def test_greedy_declines_and_skips_profiling_inside_chain(self, monkeypatch):
        from keystone_tpu.workflow import autocache

        calls = []
        monkeypatch.setattr(
            autocache,
            "profile_nodes",
            lambda *a, **k: calls.append(a) or {},
        )
        g, d, a, b = self._chain_graph()
        rule = AutoCacheRule(GreedyCache(max_mem_bytes=1 << 30))
        new_graph, _ = rule.apply(g, {})
        # No Cacher inside the fusable region (after a); the raw dataset
        # node d is a boundary and may legitimately be cached.
        for c in _cachers(new_graph):
            assert new_graph.get_dependencies(c) != (a,)
        # The chain-interior node is not even profiled: its recompute is
        # absorbed by the fused program, so sampling it would price a plan
        # that never runs.
        for (graph_arg, nodes, *_rest) in calls:
            assert a not in nodes

    def test_declines_cacher_on_estimator_featurize_input(self):
        # f's single consumer is a traceable fit: EstimatorFusionRule
        # would absorb f INTO the fit program — caching f splits it.
        class TraceableFit(Estimator):
            weight = 4
            streamed_fit_fusable = True

            def fit(self, data):
                return DeviceScale(1.0)

        ds = Dataset.of(np.ones((8, 4), np.float32))
        lab = Dataset.of(np.ones((8, 2), np.float32))
        g = Graph()
        g, d = g.add_node(DatasetOperator(ds), [])
        g, dl = g.add_node(DatasetOperator(lab), [])
        g, f = g.add_node(DeviceScale(2.0), [d])
        g, est = g.add_node(TraceableFit(), [f, dl])
        g, sink = g.add_sink(est)
        assert cache_would_split_fusion(g, f, {})
        new_graph, _ = AutoCacheRule(AggressiveCache()).apply(g, {})
        # No Cacher on the featurize input (the labels input dl is a
        # boundary the weight-4 fit legitimately caches).
        for c in _cachers(new_graph):
            assert new_graph.get_dependencies(c) != (f,)

    def test_still_caches_multi_consumer_boundary(self):
        # a feeds TWO branches: it is a materialization point of the fused
        # plan (chains never fuse across multi-consumer nodes), so the
        # cache lands.
        ds = Dataset.of(np.arange(32.0, dtype=np.float32).reshape(8, 4))
        g = Graph()
        g, d = g.add_node(DatasetOperator(ds), [])
        g, a = g.add_node(DeviceScale(2.0), [d])
        g, b = g.add_node(DeviceScale(3.0, weight=3), [a])
        g, c = g.add_node(DeviceScale(4.0, weight=3), [a])
        g, s1 = g.add_sink(b)
        g, s2 = g.add_sink(c)
        assert not cache_would_split_fusion(g, a, {})
        new_graph, _ = AutoCacheRule(AggressiveCache()).apply(g, {})
        cachers = _cachers(new_graph)
        assert len(cachers) == 1
        assert new_graph.get_dependencies(cachers[0]) == (a,)

    def test_still_caches_host_loader_boundary(self):
        # A host decode is not device-fusable: fusion cannot absorb it, so
        # its recompute cost is real and the cache lands right after it.
        ds = Dataset.of(np.arange(32.0, dtype=np.float32).reshape(8, 4))
        g = Graph()
        g, d = g.add_node(DatasetOperator(ds), [])
        g, h = g.add_node(HostDecode(), [d])
        g, b = g.add_node(DeviceScale(3.0, weight=4), [h])
        g, sink = g.add_sink(b)
        assert not cache_would_split_fusion(g, h, {})
        assert h not in fusion_splitting_nodes(g, {})
        new_graph, _ = AutoCacheRule(AggressiveCache()).apply(g, {})
        cachers = _cachers(new_graph)
        assert len(cachers) == 1
        assert new_graph.get_dependencies(cachers[0]) == (h,)


class TestPostFusionPhaseOrder:
    def test_greedy_keeps_whole_chain_fused(self):
        """Under AutoCachingOptimizer the device-pure chain compiles into
        ONE fused program — no Cacher lands inside it (round 5's measured
        defect: pre-fusion placement split the chain into per-stage
        dispatches)."""
        env = PipelineEnv.get_or_create()
        env.reset()
        clear_observed_profiles()
        env.set_optimizer(AutoCachingOptimizer(GreedyCache(max_mem_bytes=1 << 30)))
        try:
            f1, f2, f3 = DeviceScale(2.0), DeviceScale(0.5), DeviceScale(3.0)
            est = WeightedSumEstimator()
            X = np.arange(64.0, dtype=np.float32).reshape(16, 4)
            data = Dataset.of(X)
            pipe = (
                f1.to_pipeline().and_then(f2).and_then(f3).and_then(est, data)
            )
            res = pipe.apply(Dataset.of(X[:4]))
            out = np.asarray(res.get().to_numpy())
            g = res.executor.optimized_graph
            fused_ops = [
                g.get_operator(n)
                for n in g.nodes
                if str(getattr(g.get_operator(n), "label", "")).startswith("Fused[")
            ]
            # The full 3-stage chain fused as one program (train side and
            # apply side each collapse; membership query sees all stages).
            assert fused_ops, [
                getattr(g.get_operator(n), "label", "") for n in g.nodes
            ]
            assert any(len(fused_members(op)) == 3 for op in fused_ops)
            # Any Cacher sits at a boundary, never between fused members:
            # its dependency must not be a node the fusion rules would
            # chain through.
            for c in _cachers(g):
                (dep,) = g.get_dependencies(c)
                assert not cache_would_split_fusion(g, dep, {})
            np.testing.assert_allclose(out, X[:4] * 3.0, rtol=1e-5)
        finally:
            env.reset()

    def test_host_boundary_cached_and_reused_across_fits(self):
        """The cross-fit win caching still owns post-fusion: a host decode
        executes at FULL scale once; later fits load the published cache
        from the prefix state table instead of recomputing the stage."""
        env = PipelineEnv.get_or_create()
        env.reset()
        clear_observed_profiles()
        env.set_optimizer(AutoCachingOptimizer(GreedyCache(max_mem_bytes=1 << 30)))
        try:
            host = HostDecode()
            f = DeviceScale(2.0)
            n_full = 64
            X = np.abs(
                np.random.default_rng(0).normal(size=(n_full, 4))
            ).astype(np.float32)
            data = Dataset.of(X)
            for _ in range(3):  # a sweep refitting the same prefix
                est = WeightedSumEstimator()  # fresh fit per iteration
                pipe = host.to_pipeline().and_then(f).and_then(est, data)
                out = pipe.apply(Dataset.of(X[:4]))
                np.asarray(out.get().to_numpy())
            full_runs = [n for n in host.batch_ns if n == n_full]
            assert len(full_runs) == 1, host.batch_ns
        finally:
            env.reset()

    def test_pre_fusion_order_still_available_for_ab(self):
        post = AutoCachingOptimizer(GreedyCache())
        pre = AutoCachingOptimizer(GreedyCache(), cache_before_fusion=True)
        post_names = [b.name for b in post.batches]
        pre_names = [b.name for b in pre.batches]
        assert post_names.index("Auto Cache (post-fusion)") > post_names.index(
            "Tree & Fit Fusion"
        )
        assert pre_names.index("Auto Cache") < pre_names.index("Stage Fusion")


class TestObservedProfiles:
    def test_executor_records_full_scale_profiles(self):
        clear_observed_profiles()
        ds = Dataset.of(np.ones((8, 4), np.float32))
        g = Graph()
        g, d = g.add_node(DatasetOperator(ds), [])
        g, h = g.add_node(HostDecode(), [d])
        g, sink = g.add_sink(h)
        ex = GraphExecutor(g, optimize=False)
        ex.execute(sink).get()
        key = observed_profile_key(g, h)
        prof = get_observed_profile(key)
        assert prof is not None and prof.ns > 0
        assert prof.mem_bytes > 0

    def test_greedy_prefers_observed_over_sampling(self, monkeypatch):
        from keystone_tpu.workflow import autocache

        clear_observed_profiles()
        ds = Dataset.of(np.ones((8, 4), np.float32))
        g = Graph()
        g, d = g.add_node(DatasetOperator(ds), [])
        g, h = g.add_node(HostDecode(), [d])
        g, b = g.add_node(DeviceScale(1.0, weight=4), [h])
        g, sink = g.add_sink(b)
        # Real execution first: full-scale profiles land in the table.
        ex = GraphExecutor(g, optimize=False)
        ex.execute(sink).get()
        sampled = []
        monkeypatch.setattr(
            autocache,
            "profile_nodes",
            lambda graph, nodes, *a, **k: sampled.append(set(nodes)) or {},
        )
        rule = AutoCacheRule(GreedyCache(max_mem_bytes=1 << 30))
        rule.apply(g, {})
        # Every candidate (d and h) was observed by the executor — greedy
        # pays zero sampled profiling passes.
        assert not sampled or all(
            h not in nodes and d not in nodes for nodes in sampled
        )
