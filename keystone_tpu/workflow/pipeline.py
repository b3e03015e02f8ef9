"""Typed ML API: Transformer / Estimator / LabelEstimator / Pipeline / gather.

Behavioral contract from the reference's typed layer (reference:
workflow/Transformer.scala:18-70, Estimator.scala:10-62,
LabelEstimator.scala:13-100, Chainable.scala:13-126, Pipeline.scala:22-155,
FittedPipeline.scala:18-48, PipelineResult.scala:14-21): composition is pure
graph surgery; applying a pipeline returns lazy handles; estimator insertion
adds the estimator node plus a delegating node that applies the *fitted*
transformer to the pipeline's source; ``fit()`` executes all estimators and
yields a serializable transformer-only pipeline.
"""

from __future__ import annotations

import functools
import types

import cloudpickle as pickle
from typing import Any, Callable, Dict, Generic, List, Optional, Sequence, Tuple, TypeVar, Union

import jax
import jax.numpy as jnp
import numpy as np

from keystone_tpu import obs
from keystone_tpu.data import Dataset
from keystone_tpu.utils.profiling import follow_profiler

from .executor import GraphExecutor
from .graph import Graph, GraphId, NodeId, SinkId, SourceId
from .operators import (
    DatasetOperator,
    DatumOperator,
    DelegatingOperator,
    EstimatorOperator,
    GatherTransformerOperator,
    TransformerOperator,
)

A = TypeVar("A")
B = TypeVar("B")
C = TypeVar("C")
L = TypeVar("L")


# ---------------------------------------------------------------------------
# Lazy result handles
# ---------------------------------------------------------------------------


class PipelineResult(Generic[B]):
    """Lazy wrapper around a scheduled execution; ``.get()`` memoizes."""

    def __init__(self, executor: GraphExecutor, sink: SinkId):
        self.executor = executor
        self.sink = sink
        self._result: Any = None
        self._computed = False

    def get(self) -> B:
        if not self._computed:
            self._result = self.executor.execute(self.sink).get()
            self._computed = True
        return self._result


class PipelineDataset(PipelineResult[B]):
    """Lazy handle on a dataset flowing out of a pipeline."""

    @staticmethod
    def of(dataset: Dataset) -> "PipelineDataset":
        graph, node = Graph().add_node(DatasetOperator(dataset), [])
        graph, sink = graph.add_sink(node)
        return PipelineDataset(GraphExecutor(graph), sink)


class PipelineDatum(PipelineResult[B]):
    """Lazy handle on a single datum flowing out of a pipeline."""

    @staticmethod
    def of(datum: Any) -> "PipelineDatum":
        graph, node = Graph().add_node(DatumOperator(datum), [])
        graph, sink = graph.add_sink(node)
        return PipelineDatum(GraphExecutor(graph), sink)


def _as_pipeline_dataset(data: Any) -> "PipelineDataset":
    if isinstance(data, PipelineDataset):
        return data
    if not isinstance(data, Dataset):
        data = Dataset.of(data)
    return PipelineDataset.of(data)


# ---------------------------------------------------------------------------
# Chainable mixin
# ---------------------------------------------------------------------------


class Chainable(Generic[A, B]):
    """Provides ``and_then`` composition; implementors supply ``to_pipeline``."""

    def to_pipeline(self) -> "Pipeline[A, B]":
        raise NotImplementedError

    def and_then(
        self,
        nxt: Union["Chainable[B, C]", "Estimator", "LabelEstimator"],
        data: Any = None,
        labels: Any = None,
    ) -> "Pipeline[A, C]":
        """Chain a transformer/pipeline, or fit-and-chain an estimator.

        ``and_then(est, data)`` fits ``est`` on this pipeline applied to
        ``data``; ``and_then(label_est, data, labels)`` additionally passes
        labels (Chainable.scala:26-126).
        """
        if isinstance(nxt, LabelEstimator):
            if data is None or labels is None:
                raise ValueError("LabelEstimator chaining requires data and labels")
            me = self.to_pipeline()
            return me.and_then(nxt.with_data(me.apply(data), labels))
        if isinstance(nxt, Estimator):
            if data is None:
                raise ValueError("Estimator chaining requires data")
            me = self.to_pipeline()
            return me.and_then(nxt.with_data(me.apply(data)))
        if data is not None or labels is not None:
            raise ValueError("data/labels only apply when chaining estimators")

        me = self.to_pipeline()
        next_pipe = nxt.to_pipeline()
        new_graph, _, _, sink_mapping = me.executor.graph.connect_graph(
            next_pipe.executor.graph, {next_pipe.source: me.sink}
        )
        return Pipeline(GraphExecutor(new_graph), me.source, sink_mapping[next_pipe.sink])

    # `p | next` sugar for and_then
    def __or__(self, nxt: "Chainable[B, C]") -> "Pipeline[A, C]":
        return self.and_then(nxt)


# ---------------------------------------------------------------------------
# Pipeline
# ---------------------------------------------------------------------------


class Pipeline(Chainable[A, B]):
    """Typed facade over (executor, source, sink). Not thread-safe."""

    def __init__(self, executor: GraphExecutor, source: SourceId, sink: SinkId):
        self.executor = executor
        self.source = source
        self.sink = sink

    def to_pipeline(self) -> "Pipeline[A, B]":
        return self

    def apply(self, data: Any) -> PipelineResult[B]:
        """Lazily apply this pipeline to a datum, Dataset, or lazy handle."""
        if isinstance(data, Dataset):
            return self.apply(PipelineDataset.of(data))
        if isinstance(data, PipelineDataset):
            new_graph, _, _, sink_mapping = data.executor.graph.connect_graph(
                self.executor.graph, {self.source: data.sink}
            )
            return PipelineDataset(
                GraphExecutor(new_graph, self.executor.optimize), sink_mapping[self.sink]
            )
        if isinstance(data, PipelineDatum):
            new_graph, _, _, sink_mapping = data.executor.graph.connect_graph(
                self.executor.graph, {self.source: data.sink}
            )
            return PipelineDatum(
                GraphExecutor(new_graph, self.executor.optimize), sink_mapping[self.sink]
            )
        return self.apply(PipelineDatum.of(data))

    __call__ = apply

    def fit(self) -> "FittedPipeline[A, B]":
        """Fit all estimators, returning a transformer-only serializable pipeline
        (Pipeline.scala:38-65).

        Runs the static plan verifier first (workflow/verify.py): a
        malformed plan — the compile-time error KeystoneML's typed Scala
        API would have raised — fails HERE with node-level coordinates,
        not deep inside an estimator fit. ``KEYSTONE_VERIFY=off``
        disables the pre-pass."""
        from .env import PipelineEnv
        from .rules import UnusedBranchRemovalRule
        from .verify import verify_fit_graph

        follow_profiler()
        with obs.span("pipeline.fit",
                      nodes=len(self.executor.graph.operators)):
            with obs.span("fit.verify"):
                verify_fit_graph(
                    self.executor.graph, context="Pipeline.fit plan"
                )
            with obs.span("fit.optimize"):
                optimized, prefixes = (
                    PipelineEnv.get_or_create().optimizer.execute(
                        self.executor.graph, {}
                    )
                )

            # Publish fitted state into the prefix table so later
            # pipelines reuse it.
            fitting_executor = GraphExecutor(
                optimized, optimize=False, prefixes=prefixes
            )
            delegating_nodes = [
                n for n, op in optimized.operators.items()
                if isinstance(op, DelegatingOperator)
            ]

            graph = optimized
            for node in delegating_nodes:
                deps = optimized.get_dependencies(node)
                estimator_dep = deps[0]
                est_op = optimized.get_operator(estimator_dep)
                with obs.span("fit.estimator", node=estimator_dep.id,
                              operator=type(est_op).__name__):
                    transformer = (
                        fitting_executor.execute(estimator_dep).get()
                    )
                if not isinstance(transformer, TransformerOperator):
                    raise TypeError(
                        "Estimator fit did not produce a TransformerOperator"
                    )
                graph = graph.set_operator(node, transformer) \
                    .set_dependencies(node, deps[1:])

            graph, _ = UnusedBranchRemovalRule().apply(graph, {})
            return FittedPipeline(
                TransformerGraph.from_graph(graph), self.source, self.sink
            )

    @staticmethod
    def gather(branches: Sequence["Pipeline[A, B]"]) -> "Pipeline[A, List[B]]":
        """Combine the outputs of branches applied to one input (Pipeline.scala:119-154)."""
        source = SourceId(0)
        graph = Graph(sources=frozenset({source}))

        branch_sinks: List[GraphId] = []
        for branch in branches:
            graph, source_mapping, _, sink_mapping = graph.add_graph(branch.executor.graph)
            branch_source = source_mapping[branch.source]
            branch_sink = sink_mapping[branch.sink]
            branch_sink_dep = graph.get_sink_dependency(branch_sink)
            graph = (
                graph.replace_dependency(branch_source, source)
                .remove_source(branch_source)
                .remove_sink(branch_sink)
            )
            branch_sinks.append(branch_sink_dep)

        graph, gather_node = graph.add_node(GatherTransformerOperator(), branch_sinks)
        graph, sink = graph.add_sink(gather_node)
        return Pipeline(GraphExecutor(graph), source, sink)


# ---------------------------------------------------------------------------
# TransformerGraph + FittedPipeline
# ---------------------------------------------------------------------------


def compose_apply_fn(
    graph: Graph, source: SourceId, sink: SinkId
) -> Optional[Callable]:
    """Compose a transformer graph into ONE pure batched array function
    ``X -> Y``, or None when the graph is not expressible as one.

    Requirements: every node on the sink's ancestry declares a
    ``device_fn`` and takes exactly one input, and ``source`` is the only
    unbound source. After the fusion rules have run, linear pipelines —
    including gather trees, which GatherFusionRule collapses to a single
    node — satisfy this; anything host-side or multi-input does not and
    the caller keeps the per-node execution path.

    Shared by the per-datum apply fast path (one compiled executable per
    input shape instead of an eager op-by-op walk) and by
    :mod:`keystone_tpu.serving.export`'s bucketed plan compiler.
    """
    from . import analysis

    steps = []
    for gid in analysis.linearize(graph, sink):
        if gid == source or isinstance(gid, SinkId):
            continue
        if isinstance(gid, SourceId):
            return None  # a second unbound source — not a pure X -> Y map
        op = graph.get_operator(gid)
        fn_getter = getattr(op, "device_fn", None)
        fn = fn_getter() if callable(fn_getter) else None
        deps = graph.get_dependencies(gid)
        if fn is None or len(deps) != 1:
            return None
        steps.append((gid, fn, deps[0]))
    final = graph.get_sink_dependency(sink)

    def composed(X):
        values = {source: X}
        for gid, fn, dep in steps:
            values[gid] = fn(values[dep])
        return values[final]

    return composed


class TransformerGraph(Graph):
    """A Graph whose every operator is a TransformerOperator — the
    serializable transformer-only restriction backing FittedPipeline
    (reference: TransformerGraph.scala:12-29)."""

    @staticmethod
    def from_graph(graph: Graph) -> "TransformerGraph":
        for _, op in graph.operators.items():
            if not isinstance(op, TransformerOperator):
                raise TypeError(
                    f"Non-transformer operator {op.label} in TransformerGraph"
                )
        return TransformerGraph(
            sources=graph.sources,
            operators=graph.operators,
            dependencies=graph.dependencies,
            sink_dependencies=graph.sink_dependencies,
        )


class FittedPipeline(Generic[A, B]):
    """Transformer-only pipeline: eager, no optimization or fitting on apply.

    Serializable via pickle (``save``/``load``), the analog of the reference's
    Java-serializable FittedPipeline (FittedPipeline.scala:12-48).
    """

    # Per-process cap on cached per-shape datum executables: a client
    # sweeping many input shapes must not retain one program per shape.
    _DATUM_PROGRAM_CACHE_MAX = 16

    def __init__(self, graph: Graph, source: SourceId, sink: SinkId):
        self.transformer_graph = graph
        self.source = source
        self.sink = sink
        self._init_datum_cache()

    def _init_datum_cache(self) -> None:
        # (shape, dtype) -> jitted single-datum program; _batched_fn is
        # the graph's composed batch function (False = "checked, not
        # composable" so the walk only ever happens once). The lock makes
        # concurrent apply(datum) callers safe: cache insertion/eviction
        # would otherwise race (dict pop during iteration) exactly in the
        # threaded-serving setting this PR exists for.
        import threading

        self._datum_programs: Dict[tuple, Any] = {}
        self._batched_fn: Any = None
        self._datum_lock = threading.Lock()

    # Jitted closures are not picklable; FittedPipeline.save() pickles the
    # whole object, so the compile caches rebuild lazily after load (same
    # contract as the fused transformers' __getstate__).
    def __getstate__(self):
        state = self.__dict__.copy()
        state.pop("_datum_programs", None)
        state.pop("_batched_fn", None)
        state.pop("_datum_lock", None)
        return state

    def __setstate__(self, state):
        self.__dict__.update(state)
        self._init_datum_cache()

    def _datum_program(self, x) -> Optional[Callable]:
        """One compiled executable per input (shape, dtype) for the
        single-datum serve path.

        Repeated ``apply(datum)`` calls previously walked the graph
        op-by-op, dispatching each node's eager ops every call; now the
        first call with a given shape traces ONE program (the composed
        batched function at batch 1) and later calls reuse the compiled
        executable — no re-trace, no per-node dispatch waves. Returns
        None (caller keeps the per-node path) for pipelines that don't
        compose to a pure array function.
        """
        if not hasattr(x, "shape") or not hasattr(x, "dtype"):
            return None
        with self._datum_lock:
            if self._batched_fn is None:
                self._batched_fn = (
                    compose_apply_fn(
                        self.transformer_graph, self.source, self.sink
                    )
                    or False
                )
            if self._batched_fn is False:
                return None
            key = (tuple(x.shape), str(x.dtype))
            program = self._datum_programs.get(key)
            if program is None:
                batched = self._batched_fn
                program = jax.jit(lambda v: batched(v[None])[0])
                if len(self._datum_programs) >= self._DATUM_PROGRAM_CACHE_MAX:
                    self._datum_programs.pop(next(iter(self._datum_programs)))
                self._datum_programs[key] = program
            return program

    def apply(self, data: Any) -> Any:
        """Score ``data`` eagerly. The entry also lets the program's
        tracing follow a jax profile (two reads when nothing is on): a
        profiled apply is a root ``pipeline.apply`` span, and the first
        apply after a profile has stopped ends the session that followed
        it, which then takes its device account."""
        follow_profiler()
        with obs.span("pipeline.apply"):
            return self._apply(data)

    def _apply(self, data: Any) -> Any:
        from . import analysis

        is_dataset = isinstance(data, (Dataset, PipelineDataset))
        if isinstance(data, (PipelineDataset, PipelineDatum)):
            data = data.get()

        if not is_dataset and not isinstance(data, Dataset):
            program = self._datum_program(data)
            if program is not None:
                return program(data)

        values: Dict[GraphId, Any] = {self.source: data}
        for gid in analysis.linearize(self.transformer_graph, self.sink):
            if gid in values:
                continue
            if isinstance(gid, SinkId):
                values[gid] = values[self.transformer_graph.get_sink_dependency(gid)]
            elif isinstance(gid, NodeId):
                op = self.transformer_graph.get_operator(gid)
                inputs = [values[d] for d in self.transformer_graph.get_dependencies(gid)]
                try:
                    if is_dataset:
                        values[gid] = op.batch_transform(inputs)
                    else:
                        values[gid] = op.single_transform(inputs)
                except Exception as e:
                    # Runtime failures cite the same coordinates as
                    # static-verifier reports (NodeId + operator +
                    # inferred input signatures), appended in place so
                    # the exception type survives.
                    from .verify import annotate_node_error

                    annotate_node_error(e, gid, op, inputs)
                    raise
            else:
                raise ValueError(f"Unbound source {gid} in FittedPipeline")
        return values[self.sink]

    __call__ = apply

    def save(self, path: str) -> None:
        with open(path, "wb") as f:
            pickle.dump(self, f)

    @staticmethod
    def load(path: str) -> "FittedPipeline":
        with open(path, "rb") as f:
            return pickle.load(f)


# ---------------------------------------------------------------------------
# Transformer
# ---------------------------------------------------------------------------


class Transformer(TransformerOperator, Chainable[A, B]):
    """A function on single items, batchable over datasets.

    Subclasses implement ``apply`` (single item) or the operand form, from
    which it is derived. ``batch_apply`` defaults to
    the node's operand form (``device_operands`` + ``device_apply``) via
    ``map_batch`` when one is declared (so a device-pure node writes ONE
    batched function, not three methods kept in sync), else to mapping
    ``apply`` over the dataset (vmap for device
    arrays, Python map for host collections); override it only for batch
    semantics neither default expresses (Transformer.scala:18-70).
    """

    def apply(self, x: A) -> B:
        """Default for a node with the operand form: its one row-local
        program on a batch of one (the arithmetic is written once)."""
        fn = self.device_fn()
        if fn is None:
            raise NotImplementedError
        return fn(jnp.asarray(x)[None])[0]

    def batch_apply(self, data: Dataset) -> Dataset:
        fn = self.device_fn()
        if fn is not None:
            if not data.is_host:
                return data.map_batch(fn)
            # Rectangular host collections stack to one array and take the
            # batched path too (one dispatch instead of one per item — the
            # SIFT→FV pipelines' post-encoding chains live here); ragged
            # items (variable image sizes) fall through to per-item apply,
            # mirroring Dataset.map's vmap-or-loop policy.
            try:
                batch = data.array
            except (ValueError, TypeError):
                # Ragged items cannot stack (the expected case). Any other
                # exception class is a genuine stacking bug and propagates —
                # swallowing it would silently degrade the pipeline to the
                # per-item path with no visible cause.
                return data.map(self.apply)
            try:
                out = fn(jnp.asarray(batch))
                # Sync inside the try: dispatch is async, so runtime
                # failures (batch too large for one dispatch) would
                # otherwise surface downstream, past this fallback.
                jax.block_until_ready(out)
                return Dataset(out, n=data.n)
            except Exception:
                # The items DID stack, so device_fn itself failed (axis bug,
                # batch too large for one dispatch, ...). The per-item path
                # may still work, but say so — a silently-degraded pipeline
                # runs orders of magnitude slower with no visible cause.
                import logging

                logging.getLogger("keystone_tpu.pipeline").warning(
                    "%s.device_fn failed on a stacked (%d, ...) host batch; "
                    "falling back to per-item apply",
                    type(self).__name__, data.n, exc_info=True,
                )
                return data.map(self.apply)
        return data.map(self.apply)

    # THE device contract: a node that is one row-local array program says
    # so in the operand form below. ``device_fn`` / ``device_combine_fn``
    # are derived from it here and are not extension points — outside code
    # that still overrides one is told so where its class is created.
    def __init_subclass__(cls, **kwargs):
        super().__init_subclass__(**kwargs)
        for derived, form in (
            ("device_fn", "device_operands() + device_apply"),
            ("device_combine_fn",
             "device_combine_operands() + device_combine_apply"),
        ):
            if derived in cls.__dict__:
                raise TypeError(
                    f"{cls.__qualname__} overrides {derived}(), which is "
                    f"derived from the operand form: implement {form}"
                    "(static_key, params, X) instead (docs/MIGRATING.md)"
                )

    def device_operands(self) -> Optional[Tuple[Any, tuple]]:
        """The node as one row-local array program: ``(static_key,
        params)`` — a hashable key holding every non-array setting the
        computation depends on, and a tuple of EVERY array the node owns
        (absent ones as None) — or None (default) when the node is not
        expressible as one. Offering it opts the node into whole-pipeline
        stage fusion (workflow/fusion.py): chains of such nodes compile
        into ONE XLA program that takes the arrays as arguments and is
        kept across pipelines, so a new node with new arrays of the same
        shapes runs the program already compiled. Contract of
        ``type(self).device_apply(static_key, params, X)``: row-local
        (output row i depends only on input row i), side-effect free, and
        equal to ``batch_apply`` on array-form datasets."""
        return None

    @staticmethod
    def device_apply(static_key, params, X):
        """The computation of :meth:`device_operands`: a pure function of
        its arguments, resolved through the CLASS, that captures no array
        — so one traced program serves every instance with an equal
        key."""
        raise NotImplementedError

    def device_fn(self) -> Optional[Callable]:
        """``device_apply`` bound to this instance's key and arrays — the
        X-only batched function (plan verifier, datum programs, serving
        export, the default ``batch_apply``) — or None when the node has
        no operand form. Derived; subclasses implement
        :meth:`device_operands` and :meth:`device_apply`."""
        form = self.device_operands()
        return form and functools.partial(type(self).device_apply, *form)

    def device_combine_operands(self) -> Optional[Tuple[Any, tuple]]:
        """A gather's combiner as one array program over the LIST of its
        branch outputs: the combiner's side of :meth:`device_operands`
        (workflow/fusion.py::GatherFusionRule). None (default): not one."""
        return None

    @staticmethod
    def device_combine_apply(static_key, params, arrays):
        """The computation of :meth:`device_combine_operands`."""
        raise NotImplementedError

    def device_combine_fn(self) -> Optional[Callable]:
        """``device_combine_apply`` bound to this instance (derived)."""
        form = self.device_combine_operands()
        return form and functools.partial(type(self).device_combine_apply, *form)

    def __call__(self, x: Any) -> Any:
        """Eager application to a datum or Dataset; lazy on pipeline handles."""
        if isinstance(x, Dataset):
            return self.batch_apply(x)
        if isinstance(x, (PipelineDataset, PipelineDatum)):
            return self.to_pipeline().apply(x)
        return self.apply(x)

    def to_pipeline(self) -> Pipeline[A, B]:
        graph = Graph(
            sources=frozenset({SourceId(0)}),
            sink_dependencies={SinkId(0): NodeId(0)},
            operators={NodeId(0): self},
            dependencies={NodeId(0): (SourceId(0),)},
        )
        return Pipeline(GraphExecutor(graph), SourceId(0), SinkId(0))

    # Untyped operator plumbing
    def single_transform(self, inputs: Sequence[Any]) -> Any:
        return self.apply(inputs[0])

    def batch_transform(self, inputs: Sequence[Any]) -> Any:
        return self.batch_apply(inputs[0])


class LambdaTransformer(Transformer):
    """``Transformer(f)`` literal constructor (Transformer.scala:58-70)."""

    def __init__(self, f: Callable[[A], B], batch_f: Optional[Callable] = None, name: str = None):
        self.f = f
        self.batch_f = batch_f
        self.name = name or getattr(f, "__name__", "lambda")

    @property
    def label(self) -> str:
        return f"Lambda[{self.name}]"

    def apply(self, x: A) -> B:
        return self.f(x)

    def batch_apply(self, data: Dataset) -> Dataset:
        if self.batch_f is not None:
            return self.batch_f(data)
        return data.map(self.f)


def transformer(f: Callable[[A], B]) -> Transformer[A, B]:
    """Decorator/factory: lift a plain function to a Transformer."""
    return LambdaTransformer(f)


class Identity(Transformer[A, A]):
    """Passes input through unchanged (workflow/Identity.scala:12)."""

    def apply(self, x: A) -> A:
        return x

    def batch_apply(self, data: Dataset) -> Dataset:
        return data

    def __eq__(self, other: object) -> bool:
        return type(other) is Identity

    def __hash__(self) -> int:
        return hash(Identity)


# ---------------------------------------------------------------------------
# Estimators
# ---------------------------------------------------------------------------


def _held_arrays(fitted) -> list:
    """Every device array a fitted transformer reaches: through containers
    and registered pytrees (``jax.tree_util``), through attributes, and
    through what a closure, a partial or a bound method has captured — a
    class made inside a fit (``cost.py``'s ``Chained``) holds its model in
    its methods' closure cells. Modules and ordinary classes are not
    entered."""
    found: list = []
    seen: set = set()

    def walk(obj, depth: int) -> None:
        if id(obj) in seen or depth > 10:
            return
        seen.add(id(obj))
        if isinstance(obj, jax.Array):
            found.append(obj)
            return
        if obj is None or isinstance(obj, (str, bytes, int, float, np.ndarray,
                                           types.ModuleType)):
            return
        leaves = jax.tree_util.tree_leaves(obj)
        if len(leaves) != 1 or leaves[0] is not obj:  # a container: its leaves
            held = leaves
        elif isinstance(obj, type):
            held = list(vars(obj).values()) if "<locals>" in obj.__qualname__ else []
        else:
            held = list(getattr(obj, "__dict__", {}).values())
            if "<locals>" in type(obj).__qualname__:
                held.append(type(obj))
            for cell in getattr(obj, "__closure__", None) or ():
                try:
                    held.append(cell.cell_contents)
                except ValueError:  # an empty cell
                    pass
            if isinstance(obj, functools.partial):
                held += [obj.func, obj.args, obj.keywords]
            elif isinstance(obj, types.MethodType):
                held.append(obj.__self__)
        for child in held:
            walk(child, depth + 1)

    walk(fitted, 0)
    return found


def _sync_fitted(fitted) -> None:
    """Execution barrier for the measured-outcome stamp: jax dispatch is
    async, so a fit-call wall can close before the device work it priced
    has run. Blocks on every device array the fitted transformer reaches
    (:func:`_held_arrays`: a chained transformer's arrays hide in
    closures); a device error surfaces here, inside the fit that caused
    it. Only a traced fit holds this barrier; the wait is its own
    ``executor.drain`` span (the ``executor.node`` above it names the
    node)."""
    with obs.span("executor.drain", site="estimator_sync"):
        jax.block_until_ready(_held_arrays(fitted))


def _stamped_fit(est, thunk):
    """Run one estimator fit under an ``estimator.fit`` span (the shared
    no-op when tracing is off), back-annotating a pending cost decision.

    When the cost model selected ``est`` (``LeastSquaresEstimator.
    optimize`` left a ``CostOutcomeRef`` on it), the executor is the one
    place that observes the priced work actually run — so it stamps the
    winner's measured wall + ``estimator.fit`` span id onto the decision
    record (obs/calibrate.py joins predicted-vs-measured from that).
    The ref is consumed BEFORE the fit so a failed fit never stamps a
    bogus measurement and a re-fit never double-stamps. Estimators with
    no pending decision get the span alone — no barrier, no stamp."""
    import time as _time

    ref = getattr(est, "_pending_cost_outcome", None)
    if ref is not None:
        est._pending_cost_outcome = None
    t0 = _time.perf_counter()
    with obs.span("estimator.fit", estimator=type(est).__name__) as sp:
        fitted = thunk()
        if ref is not None:
            _sync_fitted(fitted)
    if ref is not None:
        # timing="single_run_cold": a pipeline fits each estimator once,
        # so this wall INCLUDES XLA compile — the calibrator surfaces the
        # mix (calibration_report "timings") and the refit discipline
        # prefers warm rows (docs/observability.md calibration section);
        # the sweep harness stamps min_of_N_warm on its
        # dispatch-subtracted points.
        ref.stamp(
            _time.perf_counter() - t0,
            span_id=getattr(sp, "span_id", None),
            timing="single_run_cold",
        )
    return fitted


class Estimator(EstimatorOperator, Generic[A, B]):
    """Fits a Transformer from a dataset (Estimator.scala:10-62)."""

    def fit(self, data: Dataset) -> Transformer[A, B]:
        raise NotImplementedError

    def fit_datasets(self, inputs: Sequence[Any]) -> TransformerOperator:
        return _stamped_fit(self, lambda: self.fit(inputs[0]))

    def with_data(self, data: Any) -> Pipeline[A, B]:
        """Pipeline that fits this estimator on `data`, then applies the fitted
        transformer to the pipeline input (Estimator.scala:29-46)."""
        data = _as_pipeline_dataset(data)
        cur_sink_dep = data.executor.graph.get_sink_dependency(data.sink)
        graph, est_id = data.executor.graph.remove_sink(data.sink).add_node(self, [cur_sink_dep])
        graph, source_id = graph.add_source()
        graph, delegating_id = graph.add_node(DelegatingOperator(), [est_id, source_id])
        graph, sink_id = graph.add_sink(delegating_id)
        return Pipeline(GraphExecutor(graph), source_id, sink_id)


class LabelEstimator(EstimatorOperator, Generic[A, B, L]):
    """Fits a Transformer from a dataset plus labels (LabelEstimator.scala:13-100)."""

    def device_fit_fn(self):
        """Fit-fusion contract: return a ``workflow.fusion.DeviceFit``
        (traceable fit + host model builder + geometry gate) to let the
        optimizer compile upstream featurization INTO this fit as one
        program, or None (default) to keep the materialized-features
        path."""
        return None

    def fit(self, data: Dataset, labels: Dataset) -> Transformer[A, B]:
        raise NotImplementedError

    def fit_datasets(self, inputs: Sequence[Any]) -> TransformerOperator:
        return _stamped_fit(self, lambda: self.fit(inputs[0], inputs[1]))

    def with_data(self, data: Any, labels: Any) -> Pipeline[A, B]:
        data = _as_pipeline_dataset(data)
        labels = _as_pipeline_dataset(labels)

        graph, _, _, label_sink_mapping = data.executor.graph.add_graph(labels.executor.graph)
        data_sink_dep = graph.get_sink_dependency(data.sink)
        labels_sink_dep = graph.get_sink_dependency(label_sink_mapping[labels.sink])
        graph, est_id = (
            graph.remove_sink(data.sink)
            .remove_sink(label_sink_mapping[labels.sink])
            .add_node(self, [data_sink_dep, labels_sink_dep])
        )
        graph, source_id = graph.add_source()
        graph, delegating_id = graph.add_node(DelegatingOperator(), [est_id, source_id])
        graph, sink_id = graph.add_sink(delegating_id)
        return Pipeline(GraphExecutor(graph), source_id, sink_id)
