"""Stage fusion: compile chains of device-pure transformers into ONE XLA
program.

The reference executes one Spark stage per node; its per-node overhead is a
job wave. The TPU analog of that overhead is one XLA dispatch per node — and
one missed fusion opportunity per node boundary, because elementwise work
(rectifiers, scalers, sign flips) that XLA would fuse straight into a
neighboring matmul/FFT instead round-trips HBM between programs. This module
is the whole-pipeline optimizer's TPU-specific answer (SURVEY §3's optimizer
layer doing a transform Spark has no analog of):

  - Transformers that are *row-local pure array functions* declare it in
    the operand form: ``Transformer.device_operands()`` -> ``(static_key,
    params)`` — every non-array setting, every array — computed by the
    class-level ``device_apply(static_key, params, X)``, which captures
    no array (a gather's combiner: ``device_combine_operands`` /
    ``device_combine_apply``). ``device_fn()`` is derived from it on the
    base class and is not an extension point.
  - :class:`StageFusionRule` rewrites maximal linear chains of such nodes
    into one :class:`FusedBatchTransformer` whose batch path is a single
    ``jax.jit`` of the composed functions: one dispatch, full XLA fusion
    across the old node boundaries.

Chains never fuse across: estimator fits, multi-input nodes (gather/
combiner), sinks, prefix-published nodes (their intermediate result must
stay materializable for the state table — e.g. everything a Cacher marks),
or nodes whose results another branch consumes.

Row-local contract for ``device_apply``: output row i depends only on
input row i (elementwise over the leading axis), so mesh zero-padding rows
cannot leak into valid rows and a single trailing ``_rezero_padding`` is
equivalent to per-stage rezeroing.

Where a fused program lives. Every fused program — a chain's or a
gather's batch program, a fused featurize+fit — takes its members' arrays
as traced operands and is kept in ONE table of this module
(:func:`_kept_program`) by its LOGICAL identity: each member's ``(type,
static_key)`` in order, and for a fit the estimator's
``DeviceFit.program_key`` and the geometry. A pipeline built afresh (a λ
sweep builds one per fit, each with new bank arrays) calls the program the
first one compiled: no trace, no lowering, no constants baked into a new
executable. Shapes and dtypes are ``jax.jit``'s own cache key under that.
The table holds callables only, never an array. A fused transformer says
how it got its program in ``fused_program`` (``"hit"`` or ``"miss"``; the
node's ``executor.node`` span carries it) and
:func:`fused_program_totals` counts them for the process. The fused
wrappers offer the operand form themselves (key: their members'
identities; params: their members' params), so a fused node nested in
another program keeps its program too.
"""

from __future__ import annotations

import functools
import threading
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax

from .env import Prefix
from .graph import Graph, NodeId, SinkId
from .operators import DelegatingOperator, GatherTransformerOperator
from .optimizer import Plan, Rule
from .pipeline import LabelEstimator, Transformer

__all__ = [
    "FusedBatchTransformer",
    "FusedGatherTransformer",
    "FusedFitEstimator",
    "StageFusionRule",
    "GatherFusionRule",
    "EstimatorFusionRule",
    "StreamedFitFusionRule",
    "fusable",
    "fused_members",
    "fused_program_totals",
    "cache_would_split_fusion",
    "fusion_splitting_nodes",
]


def fusable(op) -> bool:
    """True when the operator participates in stage fusion."""
    offer = getattr(op, "device_operands", None)
    return callable(offer) and offer() is not None


def _combines(op) -> bool:
    """True when the operator can merge a gather's branches inside a fused
    program."""
    offer = getattr(op, "device_combine_operands", None)
    return callable(offer) and offer() is not None


def fused_members(op) -> list:
    """Fused-stage membership query: the original operators a fused program
    absorbed, or ``[op]`` for an unfused node. Lets graph-level passes
    (cache placement, cost attribution) reason about what a post-fusion
    node *contains* without knowing each fused wrapper class."""
    if isinstance(op, FusedBatchTransformer):
        return list(op.members)
    if isinstance(op, FusedGatherTransformer):
        return [m for br in op.branches for m in br] + [op.combiner]
    if isinstance(op, FusedFitEstimator):
        return list(op.members) + [op.est]
    # StreamedFitEstimator and future fused wrappers share the duck shape:
    # a ``members`` list plus the operator the members feed.
    members = getattr(op, "members", None)
    if isinstance(members, list) and members:
        tail = getattr(op, "est", None) or getattr(op, "choice", None)
        return list(members) + ([tail] if tail is not None else [])
    return [op]


def _device_fit_capable(op) -> bool:
    """True when an estimator operator would be absorbed by
    EstimatorFusionRule / StreamedFitFusionRule (a traceable fit)."""
    if getattr(op, "streamed_fit_fusable", False):
        return True
    if getattr(op, "device_fit_fn", None) is None:
        return False
    try:
        return op.device_fit_fn() is not None
    except Exception:
        return False


def cache_would_split_fusion(plan, node, prefixes, consumers=None) -> bool:
    """Boundary query for cache placement: True when splicing a ``Cacher``
    after ``node`` would sever an edge the fusion rules would otherwise
    compile into one program (a chain link, an estimator's featurize
    input, or a gather branch feeding a device combiner).

    A node for which this returns False sits on a fused-stage *boundary*:
    a Cacher there materializes a result the fused plan had to materialize
    anyway (host stages, multi-consumer intermediates, inputs of
    non-traceable fits), so insertion never splits a fusable region.
    """
    if consumers is None:
        consumers = _consumers(plan)
    op = plan.get_operator(node)
    if not fusable(op) or node in prefixes:
        return False
    outs = consumers.get(node, [])
    if len(outs) != 1 or not isinstance(outs[0], NodeId):
        # Multi-consumer nodes and sink feeds are materialization points
        # in the fused plan already.
        return False
    consumer = outs[0]
    if consumer in prefixes:
        return False
    cop = plan.get_operator(consumer)
    cdeps = plan.get_dependencies(consumer)
    single_dep = len(plan.get_dependencies(node)) == 1
    # StageFusionRule chain edge: node -> consumer fuse into one program.
    if single_dep and fusable(cop) and len(cdeps) == 1:
        return True
    # Estimator / streamed-fit fusion: the fit absorbs its DATA input.
    if len(cdeps) == 2 and cdeps[0] == node and _device_fit_capable(cop):
        return True
    # Gather branch: node feeds a gather whose output a device combiner
    # consumes (GatherFusionRule would inline the branch).
    if single_dep and isinstance(cop, GatherTransformerOperator):
        gouts = consumers.get(consumer, [])
        if len(gouts) == 1 and isinstance(gouts[0], NodeId):
            comb = plan.get_operator(gouts[0])
            if _combines(comb):
                return True
    return False


def fusion_splitting_nodes(plan, prefixes) -> set:
    """All nodes where a spliced Cacher would break a fusable region —
    the exclusion set AutoCacheRule applies before selecting candidates."""
    consumers = _consumers(plan)
    return {
        n
        for n in plan.nodes
        if cache_would_split_fusion(plan, n, prefixes, consumers)
    }


# A handful of logical pipelines per process is the normal case; FIFO keeps
# a process that fuses many distinct shapes of pipeline from retaining one
# program (and its executables) per shape for ever.
_KEPT_PROGRAMS_MAX = 16

# logical key -> jitted program taking the members' arrays as operands.
# Callables only: nothing here may pin a dead pipeline's arrays in device
# memory.
_KEPT_PROGRAMS: Dict[tuple, Callable] = {}
_PROGRAM_TOTALS = {"hit": 0, "miss": 0}
_KEPT_LOCK = threading.Lock()


def fused_program_totals() -> Dict[str, int]:
    """How the process's fused nodes got their program so far: ``hit``
    (the kept table had it) or ``miss`` (built and kept now)."""
    with _KEPT_LOCK:
        return dict(_PROGRAM_TOTALS)


def _kept_program(key: tuple, build: Callable[[], Callable]) -> Tuple[Callable, str]:
    """THE table of fused programs: the program kept under ``key`` —
    ``build()`` makes it on a miss — and which of the two it was."""
    with _KEPT_LOCK:
        program = _KEPT_PROGRAMS.get(key)
        how = "miss" if program is None else "hit"
        if program is None:
            program = build()
            if len(_KEPT_PROGRAMS) >= _KEPT_PROGRAMS_MAX:
                _KEPT_PROGRAMS.pop(next(iter(_KEPT_PROGRAMS)))
            _KEPT_PROGRAMS[key] = program
        _PROGRAM_TOTALS[how] += 1
    return program, how


def _member_form(member, combine: bool = False) -> Tuple[tuple, tuple]:
    """One member of a fused program as ``(identity, params)``: its
    ``(type, static_key)`` and its arrays. ``combine``: the member is a
    gather's combiner."""
    offer = getattr(
        member, "device_combine_operands" if combine else "device_operands",
        None,
    )
    form = offer() if callable(offer) else None
    if form is None:
        raise ValueError(f"{member!r} is not device-fusable")
    static_key, params = form
    return (type(member), static_key), tuple(params)


def absorbed(members) -> list:
    """A chain as its program runs it: where a member offers to take the
    members after it (``device_absorb(successors)`` -> ``(how many, the
    member that runs them all with it)``, or None), the offer stands in
    for them — the convolution's Pallas kernel
    (``ops/images/conv.PooledConvolution``). A chain with no such offer
    is returned as it is, so its program and kept key do not change."""
    runs, i = [], 0
    while i < len(members):
        offer = getattr(members[i], "device_absorb", None)
        taken = offer(members[i + 1:]) if callable(offer) else None
        if taken is None:
            runs.append(members[i])
            i += 1
        else:
            count, member = taken
            runs.append(member)
            i += 1 + count
    return runs


def chain_operands(members) -> Tuple[tuple, tuple]:
    """A chain of members as ``(identities, params)``, the arguments of
    :func:`chain_apply`: of the chain as it runs (:func:`absorbed`)."""
    forms = [_member_form(m) for m in absorbed(members)]
    return tuple(i for i, _ in forms), tuple(p for _, p in forms)


def chain_apply(identities, params, X):
    """Run a chain: a pure function of its arguments (the identities are
    static, the params traced), so one trace serves every chain of equal
    identities."""
    for (cls, static_key), p in zip(identities, params):
        X = cls.device_apply(static_key, p, X)
    return X


def _compose(static_key, params, X):
    """THE composition routine of the fused transformers: ``static_key``
    is (the identities of each branch over one input, the combiner's
    identity or None); a plain chain is one branch and no combiner."""
    branch_identities, combiner = static_key
    branch_params, combine_params = params
    outs = [
        chain_apply(identities, ps, X)
        for identities, ps in zip(branch_identities, branch_params)
    ]
    if combiner is None:
        return outs[0]
    cls, combine_key = combiner
    return cls.device_combine_apply(combine_key, combine_params, outs)


def _compose_form(branches, combiner=None) -> Tuple[Callable, tuple, tuple]:
    """``(apply, static_key, params)`` of :func:`_compose` for these
    members."""
    chains = [chain_operands(br) for br in branches]
    combine = (None, ()) if combiner is None else _member_form(combiner, True)
    return (
        _compose,
        (tuple(i for i, _ in chains), combine[0]),
        (tuple(p for _, p in chains), combine[1]),
    )


def _row_batch(members) -> Optional[int]:
    """The fewest rows a member asks a fused program to take at a time
    (``device_row_batch()``: a featurizer whose intermediates are many times
    its output, the convolution's map), or None: all rows at once."""
    asks = [m.device_row_batch() for m in members
            if callable(getattr(m, "device_row_batch", None))]
    asks = [b for b in asks if b]
    return min(asks) if asks else None


def _counters(members) -> list:
    """The counter tracks the members count their rows on
    (``rows_counter``), each once."""
    return list(dict.fromkeys(m.rows_counter for m in members
                              if getattr(m, "rows_counter", None)))


def _scope(members) -> str:
    """The name scope of a fused program: the first member's
    ``device_scope`` where one names it, else ``ks.featurize``."""
    return next((m.device_scope for m in members
                 if getattr(m, "device_scope", None)), "ks.featurize")


def in_row_batches(fn: Callable, X, rows: Optional[int]):
    """``fn(X)`` for a row-local ``fn``, ``rows`` rows at a time inside the
    calling program: one loop whose every step writes its rows into the
    output in place, so no intermediate of ``fn`` exists for more than
    ``rows`` rows. The last step starts at ``n - rows`` and writes again,
    with the same values, rows an earlier step wrote (row-local: a row's
    output does not depend on its neighbours)."""
    n = X.shape[0]
    if rows is None or n <= rows:
        return fn(X)
    import jax.numpy as jnp

    out = jax.eval_shape(fn, jax.ShapeDtypeStruct((rows,) + X.shape[1:], X.dtype))

    def step(i, acc):
        start = jnp.minimum(i * rows, n - rows)
        block = jax.lax.dynamic_slice_in_dim(X, start, rows)
        return jax.lax.dynamic_update_slice_in_dim(acc, fn(block), start, axis=0)

    return jax.lax.fori_loop(0, -(-n // rows), step,
                             jnp.zeros((n,) + out.shape[1:], out.dtype))


class _FusedTransformer(Transformer):
    """What the two fused transformers share: the fused program in operand
    form — ``_program_form()`` -> ``(apply, static_key, params)`` with
    ``apply(static_key, params, X)`` a module-level function — kept in the
    table by ``(apply, static_key)`` and offered as this node's own
    operand form. Members may ask for the program's name scope
    (``device_scope``) and for the rows it takes at a time
    (``device_row_batch``, :func:`in_row_batches`)."""

    def _program_form(self) -> Tuple[Callable, tuple, tuple]:
        raise NotImplementedError

    def _runs(self) -> list:
        """The members as the program runs them (:func:`absorbed`)."""
        raise NotImplementedError

    def _build_composed(self) -> None:
        apply, static_key, params = self._program_form()
        self._operands = ((apply, static_key), params)
        runs = self._runs()
        rows, scope = _row_batch(runs), _scope(runs)

        def build():
            def composed(params, X):
                # names the phase in a device profile (trace-time only)
                with jax.named_scope(scope):
                    return in_row_batches(
                        functools.partial(apply, static_key, params), X, rows)

            return jax.jit(composed)

        program, self._how = _kept_program((apply, static_key, rows, scope), build)
        # a member may count the rows its program takes (``rows_counter``),
        # and so may one that runs members absorbed into it
        self._counted = _counters(fused_members(self) + runs)
        self._program = program  # jitted ``program(params, X)``: lower it to read it
        self._composed = lambda X: program(params, X)

    @property
    def fused_program(self) -> str:
        """How this node got its program: ``"hit"`` or ``"miss"`` of the
        kept table. Not state: a plan's fingerprint (serving/export.py)
        must not depend on which build came first in the process."""
        return self._how

    # The jitted program is not picklable; FittedPipeline.save() pickles the
    # whole transformer graph (the serializable-pipeline contract,
    # Pipeline.scala:38-65 / FittedPipeline.scala:12-22), so persist only the
    # members and rebuild the composition on load.
    def __getstate__(self):
        state = self.__dict__.copy()
        for derived in ("_composed", "_operands", "_how", "_program", "_counted"):
            state.pop(derived, None)
        return state

    def __setstate__(self, state):
        self.__dict__.update(state)
        self._build_composed()

    def device_operands(self):
        return self._operands

    @staticmethod
    def device_apply(static_key, params, X):
        apply, inner_key = static_key
        return apply(inner_key, params, X)


class FusedBatchTransformer(_FusedTransformer):
    """A chain of row-local transformers compiled as one program.

    Single-datum ``apply`` keeps exact per-node semantics (composition of
    the members' ``apply``); the batch path runs the kept composition of
    the members' ``device_apply`` functions. Host-form datasets fall back
    to the sequential member chain.
    """

    def __init__(self, members: Sequence[Transformer]):
        if len(members) < 2:
            raise ValueError("fusion needs at least two members")
        self.members = list(members)
        self._build_composed()

    def _program_form(self):
        return _compose_form([self.members])

    def _runs(self) -> list:
        return absorbed(self.members)

    @property
    def label(self) -> str:
        return "Fused[" + " > ".join(m.label for m in self.members) + "]"

    def apply(self, x):
        for m in self.members:
            x = m.apply(x)
        return x

    def batch_apply(self, data):
        if data.is_host:
            for m in self.members:
                data = m.batch_apply(data)
            return data
        if self._counted:
            from keystone_tpu import obs

            for name in self._counted:
                obs.counter_track(name, data.n)
        return data.map_batch(self._composed)


class DeviceFit:
    """The traceable-fit contract estimators opt into for fit fusion.

    ``fit(F, Y, n_true, *operands) -> params`` must be traceable
    (jittable) on the featurized array; ``build(params) -> Transformer``
    runs on host with the concrete params; ``supports(d_feat)`` gates
    geometry (e.g. block divisibility) before any tracing happens.
    ``operands``: arrays the fit needs as TRACED inputs (e.g. a random-
    feature bank, the ridge λ) — a fit that closes over concrete arrays
    embeds them as HLO constants, which recompiles per instance and
    bakes a TIMIT-size bank (~360 MB) into every executable.

    ``program_key`` (required): hashable logical identity of the TRACE
    (estimator family + every static config the fit function closes
    over). Fused programs are kept ACROSS FusedFitEstimator instances by
    the members' identities and this key — a λ-sweep building a fresh
    pipeline per λ then compiles ONE program (λ rides in ``operands``).
    The contract: two DeviceFits with equal program_key must trace
    identically; anything value-affecting that is not in the key MUST be
    an operand. ``fit`` outlives its estimator inside the kept program,
    so it captures settings, never an array.
    """

    def __init__(self, fit, build, supports=lambda d: True, operands=(),
                 *, program_key):
        self.fit = fit
        self.build = build
        self.supports = supports
        self.operands = tuple(operands)
        self.program_key = program_key


def masked_center(F, Y, n_true: int):
    """Mean-center (F, Y) over the first ``n_true`` rows, masking padding
    BEFORE the means: inside a fused program padding rows hold
    featurize(0), which is nonzero in general (cos(b), rectifier caps,
    intercepts), so an unmasked sum would bias every scaler. Returns
    (Fc, Yc, fmean, ymean) with padding rows re-zeroed — the solvers'
    zero-padding contract. Shared by every ``device_fit_fn``.
    """
    import jax.numpy as jnp

    valid = (jnp.arange(F.shape[0]) < n_true).astype(F.dtype)[:, None]
    F = F * valid
    fmean = jnp.sum(F, axis=0) / n_true
    Fc = (F - fmean) * valid
    yvalid = valid.astype(Y.dtype)
    ymean = jnp.sum(Y * yvalid, axis=0) / n_true
    Yc = (Y - ymean) * yvalid
    return Fc, Yc, fmean, ymean


class FusedGatherTransformer(_FusedTransformer):
    """A gather-of-branches + combiner compiled as one program.

    Each branch is a (possibly empty — identity) list of row-local
    device-fusable transformers applied to the SAME input; the combiner's
    ``device_combine_apply`` merges the branch outputs (e.g.
    VectorCombiner's concat). The batch path is one jit: branch
    intermediates never round-trip HBM between programs, and XLA schedules
    the branches inside one computation (the gather's per-branch dispatch
    waves disappear — the tree analog of :class:`FusedBatchTransformer`'s
    chains).
    """

    def __init__(self, branches: Sequence[Sequence[Transformer]], combiner):
        if not branches:
            raise ValueError("gather fusion needs at least one branch")
        self.branches = [list(b) for b in branches]
        self.combiner = combiner
        self._build_composed()

    def _runs(self) -> list:
        return [m for br in self.branches for m in absorbed(br)] + [self.combiner]

    def _program_form(self):
        # Shape-specialized lowering first: a gather of
        # [RandomSign → PaddedFFT → LinearRectifier] branches packs branch
        # pairs into complex FFTs and reads X once for all branches
        # (stats.packed_fft_gather_apply) — the generic composition
        # reads X per branch and runs one real FFT each.
        from keystone_tpu.ops.stats import (
            packed_fft_gather_apply,
            packed_fft_gather_fn,
        )

        packed = packed_fft_gather_fn(self.branches, self.combiner)
        # Observable engagement: tests pin that the MNIST-shaped gather
        # actually lowers to the packed program (whose flop/traffic model
        # the bench row states), not the generic composition.
        self.uses_packed_fft = packed is not None
        if packed is not None:
            return (packed_fft_gather_apply, *packed)
        return _compose_form(self.branches, self.combiner)

    @property
    def label(self) -> str:
        inner = " | ".join(
            " > ".join(m.label for m in br) or "id" for br in self.branches
        )
        return f"FusedGather[{inner} -> {self.combiner.label}]"

    def apply(self, x):
        outs = []
        for br in self.branches:
            b = x
            for m in br:
                b = m.apply(b)
            outs.append(b)
        return self.combiner.apply(tuple(outs))

    def batch_apply(self, data):
        if data.is_host:
            branch_out = []
            for br in self.branches:
                d = data
                for m in br:
                    d = m.batch_apply(d)
                branch_out.append(d)
            gathered = GatherTransformerOperator().batch_transform(branch_out)
            return self.combiner.batch_apply(gathered)
        return data.map_batch(self._composed)


class FusedFitEstimator(LabelEstimator):
    """An estimator fit fused with its upstream featurize program.

    Wraps a LabelEstimator exposing ``device_fit_fn()`` (a ``DeviceFit``
    with traceable ``fit(F, Y, n_true) -> params``, host ``build(params)
    -> Transformer`` and ``supports(d_feat) -> bool``) together with the
    device-fusable transformer(s) feeding it. ``fit`` then compiles
    featurize + solve into ONE program — the feature matrix never
    materializes between them (the pipeline form of the bench's hand-fused
    featurize+BCD region) — kept in the table like every fused program, so
    a λ-sweep that builds a new pipeline per fit pays the multi-second
    featurize+solve compile once. Falls back to the sequential path for
    host datasets, multi-device meshes, or unsupported geometry.
    """

    def __init__(self, members: Sequence[Transformer], est):
        self.members = list(members)
        self.est = est

    @property
    def label(self) -> str:
        inner = " > ".join(m.label for m in self.members)
        return f"FusedFit[{inner} -> {self.est.label}]"

    @property
    def weight(self) -> int:
        return getattr(self.est, "weight", 1)

    def _fallback(self, data, labels):
        for m in self.members:
            data = m.batch_apply(data)
        return self.est.fit(data, labels)

    def fit(self, data, labels):
        dev = self.est.device_fit_fn()
        multi = data.mesh is not None and any(
            s > 1 for s in dict(data.mesh.shape).values()
        )
        if dev is None or data.is_host or labels.is_host or multi:
            return self._fallback(data, labels)
        identities, member_params = chain_operands(self.members)
        X = data.array
        d_feat = int(
            jax.eval_shape(
                functools.partial(chain_apply, identities), member_params, X
            ).shape[-1]
        )
        if not dev.supports(d_feat):
            return self._fallback(data, labels)
        n_true = int(data.n)
        # The kept program holds the fit FUNCTION alone: ``dev`` itself
        # holds this estimator's operand arrays.
        fit = dev.fit

        def build():
            def fused(X, Y, member_params, operands):
                F = chain_apply(identities, member_params, X)
                return fit(F, Y, n_true, *operands)

            return jax.jit(fused)

        fused, _ = _kept_program(
            ("fit", identities, dev.program_key, n_true, X.shape, str(X.dtype)),
            build,
        )
        params = fused(X, labels.array, member_params, dev.operands)
        return dev.build(params)


class _IdentityMemo:
    """Bounded memo keyed by the object identities of its constituents.

    Shared by every fusion rule: re-optimizing a graph built from the same
    node objects (the normal case — pipelines are re-applied with the same
    operators) must return the SAME fused wrapper. Not for the compiled
    program (the kept table has that whatever object asks): for PREFIX
    identity. ``Prefix.__eq__`` (env.py) compares operators with ``==``,
    which for a fused wrapper is object identity, so saved state and
    autocache profiles are found again across re-optimizations only if
    the rules hand back the wrapper they made before (ROADMAP D10: value
    equality on the wrappers would retire this). id() keys alone are
    unsafe — an evicted entry's ids can be recycled by the allocator — so
    hits re-verify every constituent with `is` against the live objects
    the cached value holds.
    """

    def __init__(self, max_entries: int = 64):
        self._cache: Dict[tuple, object] = {}
        self._max = max_entries

    def get(self, key_objs, verify, build):
        key = tuple(id(o) for o in key_objs)
        hit = self._cache.get(key)
        if hit is not None and verify(hit):
            return hit
        value = build()
        if key not in self._cache and len(self._cache) >= self._max:
            # Only evict for genuinely NEW keys: a verify-failed overwrite
            # replaces its own slot and must not drop an unrelated entry.
            self._cache.pop(next(iter(self._cache)))
        self._cache[key] = value
        return value


def _consumers(plan: Graph) -> Dict[NodeId, List]:
    out: Dict[NodeId, List] = {}
    for node, deps in plan.dependencies.items():
        for d in deps:
            out.setdefault(d, []).append(node)
    for sink in plan.sinks:
        out.setdefault(plan.get_sink_dependency(sink), []).append(sink)
    return out


class StageFusionRule(Rule):
    """Fuse maximal linear chains of device-fusable transformer nodes.

    A node chains onto its single dependency when BOTH are fusable, the
    dependency has exactly one consumer (this node), and neither is
    prefix-published (prefix results must materialize for the state table).

    Fused transformers are memoized by member identity (:class:`_IdentityMemo`):
    re-optimizing a graph that contains the same transformer instances
    (the normal case — pipelines are re-applied with the same node
    objects) hands back the same wrapper, so prefix state published under
    it is found again.
    """

    def __init__(self) -> None:
        self._memo = _IdentityMemo()

    def _fused(self, ops) -> FusedBatchTransformer:
        return self._memo.get(
            ops,
            lambda hit: len(hit.members) == len(ops)
            and all(a is b for a, b in zip(hit.members, ops)),
            lambda: FusedBatchTransformer(ops),
        )

    def apply(self, plan: Graph, prefixes: Dict[NodeId, Prefix]) -> Plan:
        consumers = _consumers(plan)

        def chainable(node) -> bool:
            return (
                isinstance(node, NodeId)
                and node not in prefixes
                and fusable(plan.get_operator(node))
                and len(plan.get_dependencies(node)) == 1
            )

        # Walk heads: a chain head is chainable but its dependency link
        # upward is not extendable.
        chains: List[List[NodeId]] = []
        seen = set()
        for node in sorted(plan.nodes, key=lambda n: n.id):
            if node in seen or not chainable(node):
                continue
            # extend upward
            head = node
            while True:
                dep = plan.get_dependencies(head)[0]
                if (
                    chainable(dep)
                    and len(consumers.get(dep, [])) == 1
                ):
                    head = dep
                else:
                    break
            # collect downward from head
            chain = [head]
            cur = head
            while True:
                nexts = consumers.get(cur, [])
                if len(nexts) != 1 or isinstance(nexts[0], SinkId):
                    break
                nxt = nexts[0]
                if not chainable(nxt) or plan.get_dependencies(nxt)[0] != cur:
                    break
                chain.append(nxt)
                cur = nxt
            seen.update(chain)
            if len(chain) >= 2:
                chains.append(chain)

        for chain in chains:
            ops = [plan.get_operator(n) for n in chain]
            fused = self._fused(ops)
            head_deps = plan.get_dependencies(chain[0])
            tail = chain[-1]
            # Reuse the tail node id so downstream consumers stay wired.
            plan = plan.set_operator(tail, fused)
            plan = plan.set_dependencies(tail, head_deps)
            for n in chain[:-1]:
                plan = plan.remove_node(n)

        return plan, prefixes


class GatherFusionRule(Rule):
    """Fuse gather(branch...) -> combiner trees into one program.

    Applies when: a :class:`GatherTransformerOperator` node's single
    consumer is a combiner offering ``device_combine_operands``; every branch
    feeding the gather is the common input itself (identity branch) or a
    device-fusable node consumed only by the gather; and all branches hang
    off ONE common dependency. Runs after :class:`StageFusionRule`, so
    multi-node branches have already collapsed to single fused nodes.

    Fused gathers are memoized by (branch members, combiner) identity —
    same policy, same reason as the other fusion rules
    (:class:`_IdentityMemo`).
    """

    def __init__(self) -> None:
        self._memo = _IdentityMemo()

    def _fused(self, branches, comb) -> FusedGatherTransformer:
        flat = [m for br in branches for m in br] + [comb]

        def verify(hit):
            return (
                hit.combiner is comb
                and len(hit.branches) == len(branches)
                and all(
                    len(ha) == len(ba)
                    and all(a is b for a, b in zip(ha, ba))
                    for ha, ba in zip(hit.branches, branches)
                )
            )

        return self._memo.get(
            flat, verify, lambda: FusedGatherTransformer(branches, comb)
        )

    def apply(self, plan: Graph, prefixes: Dict[NodeId, Prefix]) -> Plan:
        consumers = _consumers(plan)
        for node in sorted(plan.nodes, key=lambda n: n.id):
            if node not in plan.nodes:  # removed by an earlier rewrite
                continue
            op = plan.get_operator(node)
            if not isinstance(op, GatherTransformerOperator):
                continue
            outs = consumers.get(node, [])
            if len(outs) != 1 or isinstance(outs[0], SinkId):
                continue
            comb_node = outs[0]
            comb = plan.get_operator(comb_node)
            if not _combines(comb) or comb_node in prefixes or node in prefixes:
                continue
            tails = plan.get_dependencies(node)
            if not tails:
                continue
            branches, common = [], None
            ok = True
            for t in tails:
                if isinstance(t, NodeId):
                    top = plan.get_operator(t)
                    if (
                        not fusable(top)
                        or t in prefixes
                        or len(plan.get_dependencies(t)) != 1
                        or consumers.get(t, []) != [node]
                    ):
                        ok = False
                        break
                    dep = plan.get_dependencies(t)[0]
                    members = (
                        top.members
                        if isinstance(top, FusedBatchTransformer)
                        else [top]
                    )
                else:
                    dep, members = t, []  # identity branch off the source
                if common is None:
                    common = dep
                elif dep != common:
                    ok = False
                    break
                branches.append(members)
            if not ok or common is None:
                continue
            fused = self._fused(branches, comb)
            plan = plan.set_operator(comb_node, fused)
            plan = plan.set_dependencies(comb_node, [common])
            plan = plan.remove_node(node)
            for t in tails:
                if isinstance(t, NodeId):
                    plan = plan.remove_node(t)
            consumers = _consumers(plan)
        return plan, prefixes


class StreamedFitFusionRule(Rule):
    """Bind the upstream featurize program INTO a capacity-selected
    streaming estimator.

    Applies when a node's operator declares ``streamed_fit_fusable``
    (the cost model's StreamingLeastSquaresChoice) and its DATA input is
    a fusable transformer consumed only by it. The rewrite calls the
    choice's ``fuse_with_members(members)``, whose fit generates features
    per row tile inside the solver — the feature matrix never
    materializes, which is the entire point of the selection: the cost
    model picked this tier BECAUSE the featurized operand cannot fit.
    Runs after Stage/Gather fusion (upstream is one node) and after
    NodeOptimizationRule (the choice has been swapped in).
    """

    def __init__(self) -> None:
        self._memo = _IdentityMemo()

    def _fused(self, members, choice):
        return self._memo.get(
            list(members) + [choice],
            lambda hit: hit.choice is choice
            and len(hit.members) == len(members)
            and all(a is b for a, b in zip(hit.members, members)),
            lambda: choice.fuse_with_members(members),
        )

    def apply(self, plan: Graph, prefixes: Dict[NodeId, Prefix]) -> Plan:
        consumers = _consumers(plan)
        for node in sorted(plan.nodes, key=lambda n: n.id):
            if node not in plan.nodes:
                continue
            op = plan.get_operator(node)
            if not getattr(op, "streamed_fit_fusable", False):
                continue
            deps = plan.get_dependencies(node)
            if len(deps) != 2:
                continue
            dnode = deps[0]
            unbindable = None
            dop = None
            if not isinstance(dnode, NodeId) or dnode in prefixes:
                unbindable = "its data input is a source/prefix-published node"
            else:
                dop = plan.get_operator(dnode)
                if not fusable(dop) or len(plan.get_dependencies(dnode)) != 1:
                    unbindable = "its upstream transformer is not device-fusable"
            if unbindable:
                _logger().warning(
                    "capacity-selected streaming fit at %s cannot bind its "
                    "featurizer (%s): the fit will tile-stream MATERIALIZED "
                    "features — the memory-wall selection may not hold",
                    getattr(op, "label", op), unbindable,
                )
                continue
            # The featurize node may have other consumers ONLY when they
            # are this estimator's own apply sites (delegating nodes fed
            # by the same featurizer — CSE merges the train and apply
            # chains when the pipeline is applied to its training data).
            # Those get rewired to RAW input below; any other consumer
            # means the featurized result is genuinely needed elsewhere
            # and fusing would force recomputation — bail.
            def _is_own_delegate(c):
                return (
                    isinstance(c, NodeId)
                    and isinstance(plan.get_operator(c), DelegatingOperator)
                    and list(plan.get_dependencies(c)) == [node, dnode]
                )

            shared_delegates = [
                c for c in consumers.get(dnode, []) if c != node
            ]
            if not all(_is_own_delegate(c) for c in shared_delegates):
                _logger().warning(
                    "capacity-selected streaming fit at %s cannot bind its "
                    "featurizer (featurized result has other consumers): "
                    "the fit will tile-stream MATERIALIZED features — the "
                    "memory-wall selection may not hold",
                    getattr(op, "label", op),
                )
                continue
            members = (
                dop.members
                if isinstance(dop, FusedBatchTransformer)
                else [dop]
            )
            fused = self._fused(members, op)
            # Rewiring apply sites to feed RAW rows requires the fitted
            # model to disambiguate raw vs featurized input by width —
            # only provable for bank featurizers with d_in != d_feat.
            can_rewire = getattr(fused, "can_serve_raw_input", False)
            raw_in = plan.get_dependencies(dnode)[0]
            plan = plan.set_operator(node, fused)
            plan = plan.set_dependencies(node, [raw_in, deps[1]])
            if can_rewire:
                for c in shared_delegates:
                    plan = plan.set_dependencies(c, [node, raw_in])
            if can_rewire or not shared_delegates:
                plan = plan.remove_node(dnode)
            # else: dnode stays — the shared delegates keep featurizing
            # upstream and the width-adaptive model takes the identity
            # path on their featurized input.

            # Remaining apply sites (delegating nodes) may featurize via a
            # TWIN node holding the SAME operator (the fusion memos
            # guarantee object identity for train/apply twins — the
            # non-merged case, e.g. applying to held-out data). Rewire
            # them to feed RAW input too: the fitted model then carries
            # the featurizer and applies it tile-wise, so inference never
            # materializes the feature matrix either. Sites that keep
            # their featurizer still work — the fitted model is
            # width-adaptive (StreamingFeaturizedLinearModel.d_in).
            consumers = _consumers(plan)
            if can_rewire:
                delegates = [
                    c for c in consumers.get(node, [])
                    if isinstance(c, NodeId)
                    and isinstance(plan.get_operator(c), DelegatingOperator)
                ]
                for c in delegates:
                    cdeps = plan.get_dependencies(c)
                    ain = cdeps[1] if len(cdeps) == 2 else None
                    if ain == raw_in:
                        continue  # rewired above (merged case)
                    if (
                        isinstance(ain, NodeId)
                        and plan.get_operator(ain) is dop
                        and len(plan.get_dependencies(ain)) == 1
                    ):
                        plan = plan.set_dependencies(
                            c, [cdeps[0], plan.get_dependencies(ain)[0]]
                        )
                        if consumers.get(ain, []) == [c]:
                            plan = plan.remove_node(ain)
                consumers = _consumers(plan)
        return plan, prefixes


def _logger():
    import logging

    return logging.getLogger("keystone_tpu.fusion")


class EstimatorFusionRule(Rule):
    """Fuse an estimator fit with the device-fusable node feeding it.

    Applies when a LabelEstimator node exposing ``device_fit_fn()`` takes
    its DATA input from a fusable transformer whose only consumer is this
    estimator (and which is not prefix-published). The featurize + solve
    then compile as one program (:class:`FusedFitEstimator`) — the
    pipeline-level form of the manually fused featurize+BCD bench region.
    Runs after Stage/Gather fusion so the upstream is a single node.

    Fused estimators are memoized by (member, estimator) identity — the
    same policy as StageFusionRule (:class:`_IdentityMemo`). The compiled
    featurize+fit program is kept by logical identity whatever object
    asks (:func:`_kept_program`).
    """

    def __init__(self) -> None:
        self._memo = _IdentityMemo()

    def _fused(self, members, est) -> FusedFitEstimator:
        return self._memo.get(
            list(members) + [est],
            lambda hit: hit.est is est
            and len(hit.members) == len(members)
            and all(a is b for a, b in zip(hit.members, members)),
            lambda: FusedFitEstimator(members, est),
        )

    def apply(self, plan: Graph, prefixes: Dict[NodeId, Prefix]) -> Plan:
        consumers = _consumers(plan)
        for node in sorted(plan.nodes, key=lambda n: n.id):
            if node not in plan.nodes:  # removed by an earlier rewrite
                continue
            op = plan.get_operator(node)
            if getattr(op, "device_fit_fn", None) is None:
                continue
            try:
                if op.device_fit_fn() is None:
                    continue
            except Exception:
                continue
            deps = plan.get_dependencies(node)
            if len(deps) != 2:
                continue
            dnode = deps[0]
            if not isinstance(dnode, NodeId) or dnode in prefixes:
                continue
            dop = plan.get_operator(dnode)
            if not fusable(dop) or len(plan.get_dependencies(dnode)) != 1:
                continue
            if consumers.get(dnode, []) != [node]:
                continue
            members = (
                dop.members
                if isinstance(dop, FusedBatchTransformer)
                else [dop]
            )
            fused = self._fused(members, op)
            plan = plan.set_operator(node, fused)
            plan = plan.set_dependencies(
                node, [plan.get_dependencies(dnode)[0], deps[1]]
            )
            plan = plan.remove_node(dnode)
            consumers = _consumers(plan)
        return plan, prefixes
