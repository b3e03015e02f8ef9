"""Memoized pull-based graph execution (reference: workflow/GraphExecutor.scala:14-81).

On first demand the executor (optionally) runs the global whole-pipeline
optimizer, then recursively evaluates the requested id's dependency chain,
memoizing each node's Expression and publishing results for nodes whose prefix
was marked by the optimizer into the global PipelineEnv state table.

Profile collection: every source-free node's first force is timed and its
result size estimated, feeding the autocache observed-profile table. The
executor runs the OPTIMIZED graph, so what gets measured is the cost of the
post-fusion programs themselves — the full-scale ground truth AutoCacheRule
prefers over its sampled extrapolations when placing caches.
"""

from __future__ import annotations

import time
from typing import Dict, Mapping, Optional

from . import analysis
from .env import PipelineEnv, Prefix
from .graph import Graph, GraphId, NodeId, SinkId, SourceId
from .operators import Expression, ExpressionOperator


class GraphExecutor:
    """Executes parts of a graph, memoizing results. Not thread-safe."""

    def __init__(
        self,
        graph: Graph,
        optimize: bool = True,
        prefixes: Optional[Mapping[NodeId, Prefix]] = None,
    ):
        self.graph = graph
        self.optimize = optimize
        self._optimized_graph: Optional[Graph] = graph if not optimize else None
        self._prefixes: Optional[Mapping[NodeId, Prefix]] = prefixes
        self._execution_state: Dict[GraphId, Expression] = {}
        self._profile_key_memo: Dict[NodeId, Prefix] = {}

    def _ensure_optimized(self) -> Graph:
        if self._optimized_graph is None:
            if self.optimize:
                from keystone_tpu import obs

                # The lazy-path analog of Pipeline.fit's fit.optimize
                # span: pipelines driven through .get()/apply() optimize
                # HERE, and the optimizer.rule.* spans need this parent
                # to read as one phase in the trace.
                with obs.span("executor.optimize",
                              nodes=len(self.graph.operators)):
                    graph, prefixes = PipelineEnv.get_or_create().optimizer.execute(self.graph, {})
            else:
                graph, prefixes = self.graph, self._prefixes or {}
            self._optimized_graph = graph
            self._prefixes = prefixes
        return self._optimized_graph

    @property
    def optimized_graph(self) -> Graph:
        return self._ensure_optimized()

    def _source_dependants(self, graph: Graph) -> set:
        out = set()
        for source in graph.sources:
            out |= analysis.get_descendants(graph, source)
            out.add(source)
        return out

    def execute(self, graph_id: GraphId) -> Expression:
        graph = self._ensure_optimized()
        if graph_id in self._source_dependants(graph):
            raise ValueError("May not execute GraphIds that depend on unconnected sources.")
        return self._execute(graph, graph_id)

    def _execute(self, graph: Graph, graph_id: GraphId) -> Expression:
        if graph_id in self._execution_state:
            return self._execution_state[graph_id]

        if isinstance(graph_id, SourceId):
            raise ValueError("SourceIds may not be executed.")
        if isinstance(graph_id, SinkId):
            expression = self._execute(graph, graph.get_sink_dependency(graph_id))
        else:
            dep_exprs = [self._execute(graph, dep) for dep in graph.get_dependencies(graph_id)]
            operator = graph.get_operator(graph_id)
            expression = operator.execute(dep_exprs)
            self._observe(graph, graph_id, operator, dep_exprs, expression)
            self._annotate_failures(graph_id, operator, dep_exprs, expression)
            self._trace_node(graph_id, operator, expression)
            # Publish results the optimizer marked for prefix-state reuse.
            if self._prefixes and graph_id in self._prefixes:
                PipelineEnv.get_or_create().state[self._prefixes[graph_id]] = expression

        self._execution_state[graph_id] = expression
        return expression

    def _trace_node(self, graph_id, operator, expression) -> None:
        """Wrap the node's thunk in an ``executor.node`` span (obs
        plane): lazy pipelines do their real work at first force, on
        whatever thread demands the value, and deps force inside the
        thunk — so spans nest into the causal tree the executor actually
        ran. Wrapped OUTSIDE _observe/_annotate_failures so the span
        covers the node's full forced wall. One no-op branch per force
        when tracing is off; ExpressionOperator splices are skipped
        (their value was computed elsewhere — a span would misattribute
        it)."""
        if isinstance(operator, ExpressionOperator):
            return
        orig = getattr(expression, "_thunk", None)
        if orig is None:  # already computed (shared expression)
            return
        from keystone_tpu import obs

        # A fused node says how it got its batch program (fusion.py):
        # "hit" / "miss" of the kept-program table.
        how = getattr(operator, "fused_program", None)
        fused = {} if how is None else {"fused_program": how}

        def traced():
            with obs.span("executor.node", node=graph_id.id,
                          operator=type(operator).__name__, **fused):
                return orig()

        expression._thunk = traced

    def _annotate_failures(self, graph_id, operator, dep_exprs, expression) -> None:
        """Wrap the node's thunk so a runtime failure carries the same
        coordinates a static-verifier report would: the NodeId, the
        operator class, and the inferred signatures of its inputs. The
        exception TYPE is preserved (the context is appended in place,
        once, at the deepest failing node) so callers' except clauses
        and tests keep matching — see verify.annotate_node_error."""
        orig = getattr(expression, "_thunk", None)
        if orig is None:  # already computed (shared expression)
            return
        from .verify import annotate_node_error

        def annotated():
            try:
                return orig()
            except Exception as e:
                dep_values = [
                    d._value if d._computed else None for d in dep_exprs
                ]
                annotate_node_error(e, graph_id, operator, dep_values)
                raise

        expression._thunk = annotated

    def _observe(self, graph, graph_id, operator, dep_exprs, expression) -> None:
        """Arrange for the node's first force to record an observed profile.

        The expression's thunk is wrapped so that when (and only when) the
        value is actually demanded, the node's own wall time — deps forced
        first, which every core operator's thunk does anyway — and result
        bytes land in the autocache observed-profile table under the node's
        logical Prefix. ExpressionOperator nodes are skipped (their value
        was computed elsewhere; timing the splice says nothing about the
        operator's cost), as are source-dependent nodes (no Prefix).
        """
        if isinstance(operator, ExpressionOperator):
            return
        orig = getattr(expression, "_thunk", None)
        if orig is None:  # already computed (shared expression)
            return
        from . import autocache

        key = autocache.observed_profile_key(
            graph, graph_id, self._profile_key_memo
        )
        if key is None:
            return

        from keystone_tpu import obs

        def drain(value):
            """Wait out async JAX dispatch on a value's device arrays —
            time the host waits for the device, on the record as an
            ``executor.drain`` span."""
            try:
                import jax

                leaves = [x for x in jax.tree_util.tree_leaves(
                    getattr(value, "data", value)
                ) if hasattr(x, "block_until_ready")]
                if leaves:
                    with obs.span("executor.drain", site="observe",
                                  node=graph_id.id):
                        jax.block_until_ready(leaves)
            except Exception:
                pass

        def timed():
            # Force AND drain deps BEFORE the clock starts: an upstream
            # fused program's in-flight device compute would otherwise
            # block inside this node's timed region and be double-counted
            # against it.
            for d in dep_exprs:
                drain(d.get())
            t0 = time.perf_counter()
            value = orig()
            # Drain the node's own dispatch INSIDE the timed region (the
            # same guard the sampled profiler applies): a jitted program
            # returns un-materialized arrays, and without the sync its
            # compute would be mis-attributed to whichever downstream
            # stage first blocks.
            drain(value)
            ns = (time.perf_counter() - t0) * 1e9
            try:
                autocache.record_observed_profile(
                    key, ns, autocache._estimate_bytes(value)
                )
            except Exception:
                pass
            return value

        expression._thunk = timed
