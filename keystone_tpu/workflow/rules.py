"""Standard whole-pipeline optimization rules.

Each rule mirrors its reference counterpart:
  - ExtractSaveablePrefixes  (reference: workflow/ExtractSaveablePrefixes.scala:9-22)
  - SavedStateLoadRule       (reference: workflow/SavedStateLoadRule.scala:7-20)
  - UnusedBranchRemovalRule  (reference: workflow/UnusedBranchRemovalRule.scala:7-24)
  - EquivalentNodeMergeRule  (reference: workflow/EquivalentNodeMergeRule.scala:13-47)
  - NodeOptimizationRule     (reference: workflow/NodeOptimizationRule.scala:143-198)
"""

from __future__ import annotations

from typing import Dict, Set

import numpy as np

from . import analysis
from .env import PipelineEnv, Prefix
from .graph import Graph, NodeId, SourceId
from .operators import EstimatorOperator, ExpressionOperator
from .optimizer import Plan, Rule


def _is_saveable(op) -> bool:
    from keystone_tpu.ops.util import Cacher

    return isinstance(op, (Cacher, EstimatorOperator))


class ExtractSaveablePrefixes(Rule):
    """Mark nodes whose results should be published to / loaded from the global
    prefix state table: Cacher nodes and estimator fits.

    Re-extraction MERGES: marks carried in from an earlier batch win, and
    only unmarked saveable nodes gain fresh prefixes. AutoCachingOptimizer
    runs this rule a second time after post-fusion cache placement, so the
    Cachers AutoCacheRule just inserted get published for cross-fit reuse
    without re-keying estimator marks the first extraction computed on the
    pre-fusion graph (whose keys earlier fits already published under).
    Marks for nodes no longer in the plan are dropped."""

    def apply(self, plan: Graph, prefixes: Dict[NodeId, Prefix]) -> Plan:
        new_prefixes: Dict[NodeId, Prefix] = {
            n: p for n, p in prefixes.items() if n in plan.operators
        }
        memo: Dict[NodeId, Prefix] = {}
        for node, op in plan.operators.items():
            if node in new_prefixes or not _is_saveable(op):
                continue
            # Prefixes are undefined for source-dependent nodes: skip them.
            ancestors = analysis.get_ancestors(plan, node)
            if any(isinstance(a, SourceId) for a in ancestors):
                continue
            new_prefixes[node] = Prefix.find(plan, node, memo)
        return plan, new_prefixes


class SavedStateLoadRule(Rule):
    """Replace marked nodes whose prefix exists in PipelineEnv.state with
    constant ExpressionOperators."""

    def apply(self, plan: Graph, prefixes: Dict[NodeId, Prefix]) -> Plan:
        state = PipelineEnv.get_or_create().state
        graph = plan
        for node, prefix in prefixes.items():
            expr = state.get(prefix)
            if expr is not None:
                graph = graph.set_operator(
                    node, ExpressionOperator(expr, label="SavedState")
                ).set_dependencies(node, [])
        return graph, prefixes


class UnusedBranchRemovalRule(Rule):
    """Dead-code elimination: drop nodes/sources that are not ancestors of any sink."""

    def apply(self, plan: Graph, prefixes: Dict[NodeId, Prefix]) -> Plan:
        ancestors_of_sinks: Set = set()
        for sink in plan.sinks:
            ancestors_of_sinks |= analysis.get_ancestors(plan, sink)

        live_nodes = {a for a in ancestors_of_sinks if isinstance(a, NodeId)}
        live_sources = {a for a in ancestors_of_sinks if isinstance(a, SourceId)}

        graph = plan
        for source in plan.sources - live_sources:
            graph = graph.remove_source(source)
        new_prefixes = dict(prefixes)
        for node in plan.nodes - live_nodes:
            graph = graph.remove_node(node)
            new_prefixes.pop(node, None)
        return graph, new_prefixes


class EquivalentNodeMergeRule(Rule):
    """Common-subexpression elimination: merge nodes with equal (operator, deps).

    Operator equality is Python ``==``/``hash``; node-library operators that are
    deterministic functions of their parameters define structural equality
    (dataclasses), everything else defaults to identity.
    """

    def apply(self, plan: Graph, prefixes: Dict[NodeId, Prefix]) -> Plan:
        groups: Dict = {}
        for node in plan.nodes:
            try:
                key = (plan.get_operator(node), plan.get_dependencies(node))
                groups.setdefault(key, []).append(node)
            except TypeError:
                # Unhashable operator: never mergeable.
                groups[(id(plan.get_operator(node)), node)] = [node]

        if all(len(g) == 1 for g in groups.values()):
            return plan, prefixes

        graph = plan
        new_prefixes = dict(prefixes)
        for group in groups.values():
            if len(group) <= 1:
                continue
            keep = min(group, key=lambda n: n.id)
            for node in group:
                if node == keep:
                    continue
                graph = graph.replace_dependency(node, keep).remove_node(node)
            merged_prefix = next(
                (new_prefixes[n] for n in group if n in new_prefixes), None
            )
            if merged_prefix is not None:
                for n in group:
                    new_prefixes.pop(n, None)
                new_prefixes[keep] = merged_prefix
        return graph, new_prefixes


class NodeOptimizationRule(Rule):
    """Node-level algorithm selection: run optimizable nodes' ``optimize`` hook
    on a sample of their input and swap in the chosen concrete operator.

    The reference executes the graph with a sampling executor
    (NodeOptimizationRule.scala:14-136) to obtain per-node input samples. Here
    the sample collector executes the graph with datasets truncated to
    ``samples_per_shard * num_shards`` rows before each optimizable node.
    """

    def __init__(self, samples_per_shard: int = 3):
        self.samples_per_shard = samples_per_shard

    def apply(self, plan: Graph, prefixes: Dict[NodeId, Prefix]) -> Plan:
        from .optimizable import (
            OptimizableEstimator,
            OptimizableLabelEstimator,
            OptimizableTransformer,
        )

        optimizable_nodes = [
            n
            for n, op in plan.operators.items()
            if isinstance(
                op, (OptimizableTransformer, OptimizableEstimator, OptimizableLabelEstimator)
            )
            # Nodes downstream of unbound sources can't be sampled.
            and not any(
                isinstance(a, SourceId) for a in analysis.get_ancestors(plan, n)
            )
        ]
        if not optimizable_nodes:
            return plan, prefixes

        samples = _collect_samples(plan, optimizable_nodes, self.samples_per_shard)

        graph = plan
        for node in optimizable_nodes:
            op = plan.get_operator(node)
            sample_inputs = samples.get(node)
            if sample_inputs is None:
                continue
            chosen = op.optimize(*sample_inputs)
            if chosen is not None:
                graph = graph.set_operator(node, chosen)
        return graph, prefixes


def _attach_sparse_width(op, value, dep_values) -> None:
    """Thread the TRUE feature width onto a derived sparse sample.

    ``optimize()`` measures d as ``indices.max()+1`` over the sampled rows,
    which undershoots whenever the handful of samples misses the top
    feature ids. The width is knowable without sampling in every real
    producer: a vectorizer declares it (``sparse_output_dim``) — whether
    chained directly or applied through a DelegatingOperator as a fitted
    transformer riding in the dep values — a Sparsify-style node's dense
    input carries it as the dense shape, and a width-preserving transform
    inherits its sparse input's. Attach it as ``total_d`` so the cost
    model prices resident_bytes at the true width.
    """
    from keystone_tpu.data import Dataset
    from keystone_tpu.ops.sparse import is_sparse_dataset

    if not is_sparse_dataset(value):
        return
    # The declaring operator is the node's own op, or (the fit-then-apply
    # route) a fitted transformer among the dep values.
    for declarer in [op] + [v for v in dep_values if not isinstance(v, Dataset)]:
        declared = getattr(declarer, "sparse_output_dim", None)
        if callable(declared):
            try:
                declared = declared()
            except Exception:
                declared = None
        if declared:
            value.total_d = int(declared)
            return
    dep_ds = [v for v in dep_values if isinstance(v, Dataset)]
    for v in dep_ds:
        if is_sparse_dataset(v):
            inherited = getattr(v, "total_d", None)
            if inherited:
                value.total_d = int(inherited)
                return
        else:
            try:
                import jax.tree_util as jtu

                leaves = jtu.tree_leaves(v.data)
                if len(leaves) == 1 and getattr(leaves[0], "ndim", 0) >= 2:
                    value.total_d = int(leaves[0].shape[-1])
                    return
            except Exception:
                pass


def _collect_samples(plan: Graph, nodes, samples_per_shard: int):
    """Execute ancestor chains of the target nodes with row-sampled datasets.

    Returns {node: tuple(sampled dep values)}.
    """
    from keystone_tpu.data import Dataset
    from .operators import DatasetOperator

    def _row_bytes(ds: Dataset):
        """Approximate bytes per row of the raw source (streaming-tier
        capacity models keep RAW rows resident, not features)."""
        try:
            if ds.is_host:
                items = ds.to_list()
                return float(np.asarray(items[0]).nbytes) if items else None
            import jax.tree_util as jtu

            return float(
                sum(
                    int(np.prod(x.shape[1:])) * x.dtype.itemsize
                    for x in jtu.tree_leaves(ds.data)
                )
            )
        except Exception:
            return None

    def sample_dataset(ds: Dataset) -> Dataset:
        from keystone_tpu.ops.sparse import is_sparse_dataset

        num_shards = 1
        if ds.mesh is not None:
            from keystone_tpu.parallel import mesh as mesh_lib

            num_shards = mesh_lib.axis_size(ds.mesh, mesh_lib.DATA_AXIS)
        k = min(ds.n, samples_per_shard * max(num_shards, 1))
        if getattr(ds, "is_shard_backed", False):
            # Out-of-core source: sample the FIRST segment only (never
            # materialize the dataset just to cost-model it) and carry
            # the disk-tier capacity facts the selector prices on.
            src = ds.shard_source
            first = src.load(0)
            arr = (
                first if isinstance(first, np.ndarray)
                else np.asarray(first[0]).reshape(
                    -1, np.asarray(first[0]).shape[-1]
                )
            )
            rows = min(k, arr.shape[0], ds.n)
            out = Dataset(np.asarray(arr[:rows]), n=rows)
            out.total_n = ds.n
            out.source_row_bytes = src.row_bytes or float(
                arr.shape[-1] * arr.dtype.itemsize
            )
            out.shard_backed = True
            out.shard_segment_bytes = src.segment_bytes
            return out
        if ds.is_host:
            out = Dataset.of(ds.to_list()[:k])
        else:
            import jax.tree_util as jtu

            data = jtu.tree_map(lambda x: x[:k], ds.data)
            out = Dataset(data, n=k)
        # Cost models need the FULL dataset size (the reference passes it via
        # numPerPartition, LeastSquaresEstimator.scala:60-64); the sample only
        # supplies d, k, and sparsity.
        out.total_n = ds.n
        out.source_row_bytes = _row_bytes(ds)
        if is_sparse_dataset(ds):
            # The TRUE feature width, measured over the FULL index array —
            # ``indices.max()+1`` over a handful of sampled rows can
            # undershoot it by orders of magnitude, mis-pricing every
            # sparse candidate's resident_bytes downstream (cost.py).
            # Reduced where the indices live: pulling a device-resident index
            # array to the host to take its max moves the whole dataset over
            # PCIe every fit (1.4 GB at the Amazon cell's 4.2M rows).
            try:
                out.total_d = int(ds.data["indices"].max()) + 1
            except Exception:
                pass
        return out

    # Execute with a private memo table, sampling at every DatasetOperator.
    memo: Dict[NodeId, object] = {}

    def evaluate(gid):
        if gid in memo:
            return memo[gid]
        op = plan.get_operator(gid)
        deps = [evaluate(d) for d in plan.get_dependencies(gid)]
        if isinstance(op, DatasetOperator):
            value = sample_dataset(Dataset.of(op.dataset))
        else:
            exprs = [_wrap(d) for d in deps]
            value = op.execute(exprs).get()
            # Operators derive NEW Datasets, losing the sample metadata —
            # without re-attaching it here a chained optimizable node would
            # see n = the handful of sampled rows and cost-select for a
            # tiny problem (the reference's numPerPartition reaches its
            # estimators whole, LeastSquaresEstimator.scala:60-64).
            if isinstance(value, Dataset):
                dep_ds = [v for v in deps if isinstance(v, Dataset)]
                totals = [
                    v.total_n for v in dep_ds
                    if getattr(v, "total_n", None) is not None
                ]
                if totals:
                    value.total_n = max(totals)
                raws = [
                    v.source_row_bytes for v in dep_ds
                    if getattr(v, "source_row_bytes", None) is not None
                ]
                if raws:
                    value.source_row_bytes = max(raws)
                # Disk-tier provenance: a derived sample whose SOURCE is
                # shard-backed keeps the flag ONLY through device-fusable
                # operators — exactly the chains StreamedFitFusionRule can
                # rewire to consume the raw shard source. Through a
                # non-fusable op the fit would receive a materialized
                # intermediate, so pricing the disk tier as feasible
                # there would admit the very host-RAM blowup the budget
                # cut exists to prevent.
                from .fusion import fusable

                if fusable(op) and any(
                    getattr(v, "shard_backed", False) for v in dep_ds
                ):
                    value.shard_backed = True
                    segs = [
                        v.shard_segment_bytes for v in dep_ds
                        if getattr(v, "shard_segment_bytes", None)
                        is not None
                    ]
                    if segs:
                        value.shard_segment_bytes = max(segs)
                _attach_sparse_width(op, value, deps)
        memo[gid] = value
        return value

    def _wrap(value):
        from .operators import DatasetExpression, DatumExpression, TransformerExpression
        from .operators import TransformerOperator

        if isinstance(value, Dataset):
            return DatasetExpression(lambda v=value: v)
        if isinstance(value, TransformerOperator):
            return TransformerExpression(lambda v=value: v)
        return DatumExpression(lambda v=value: v)

    out = {}
    for node in nodes:
        try:
            dep_values = tuple(evaluate(d) for d in plan.get_dependencies(node))
            # Optimization hooks take Dataset samples; datum-fed nodes keep
            # their default implementation (the reference's sampling executor
            # likewise only samples RDD inputs).
            if not all(isinstance(v, Dataset) for v in dep_values):
                out[node] = None
            else:
                out[node] = dep_values
        except Exception:
            out[node] = None
    return out
