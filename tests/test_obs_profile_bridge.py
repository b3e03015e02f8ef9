"""The bridge between a jax profile and the program's tracer (PR 26):
``utils.profiling.follow_profiler`` at the fit entries, spans that are also
``ks.*`` annotations in the profile, and the compile ledger's ``jax.compile``
spans. CPU only; the profiles are taken with the Python tracer off, as the
benchmark takes them."""

import ast
import contextlib
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import chip_smoke
from keystone_tpu import obs
from keystone_tpu.data import Dataset
from keystone_tpu.obs import tracer as tracer_mod
from keystone_tpu.ops.learning.block import BlockLeastSquaresEstimator
from keystone_tpu.ops.learning.cost import LeastSquaresEstimator
from keystone_tpu.pipelines import timit
from keystone_tpu.utils import profiling
from keystone_tpu.workflow import PipelineEnv

ROWS, BLOCK, BRANCHES = 256, 64, 2


@pytest.fixture(autouse=True)
def no_session_left_over(monkeypatch):
    """Each test starts as a process that never traced would: no tracer
    active, no earlier session to be read."""
    monkeypatch.setattr(tracer_mod, "_ACTIVE", None)
    monkeypatch.setattr(tracer_mod, "_SESSION", None)
    yield
    profiling.follow_profiler()  # no profile runs now: a session still open ends


@contextlib.contextmanager
def profile(directory):
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(directory), profiler_options=options)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def toy_rows(seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(ROWS, timit.NUM_INPUT_FEATURES)).astype(np.float32)
    labels = rng.integers(0, 8, ROWS)
    Y = (2.0 * np.eye(8, dtype=np.float32)[labels] - 1.0)
    return Dataset.of(jnp.asarray(X)), Dataset.of(jnp.asarray(Y))


def toy_fit(entry, lam=1e-3):
    """One whole new fit through a public entry, as the benchmark makes it."""
    PipelineEnv.get_or_create().reset()
    cfg = timit.TimitConfig(num_cosines=BRANCHES, block_size=BLOCK, num_epochs=2,
                            lam=lam, seed=7)
    X, Y = toy_rows()
    if entry == "streaming":
        pipe = timit.streaming_estimator(cfg).with_data(X, Y)
    elif entry == "fused":  # the optimizer compiles the featurizer into this fit
        pipe = timit.build_featurizer(cfg).and_then(
            BlockLeastSquaresEstimator(BLOCK, 2, lam), X, Y)
    else:
        pipe = timit.build_featurizer(cfg).and_then(
            LeastSquaresEstimator(lam=lam, block_size=BLOCK, block_iters=2), X, Y)
    return pipe.fit()


def duration_listeners():
    from jax._src import monitoring

    return list(monitoring.get_event_duration_listeners())


def annotations(directory):
    """{line: [(name, start_ns, end_ns)]} of the ``ks.`` and ``outer.``
    events in the one profile under ``directory``."""
    (path,) = glob.glob(os.path.join(str(directory), "plugins", "profile", "*", "*.xplane.pb"))
    found = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(("ks.", "outer.")):
                    found.setdefault((plane.name, line.name), []).append(
                        (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns))
    return found


REACHED = {
    "auto": {"pipeline.build", "pipeline.fit", "fit.verify", "fit.optimize", "fit.estimator",
             "executor.node", "executor.drain", "estimator.fit", "cost.select", "jax.compile"},
    "fused": {"pipeline.build", "pipeline.fit", "fit.estimator", "executor.node",
              "executor.drain", "estimator.fit", "jax.compile"},
    "streaming": {"pipeline.build", "pipeline.fit", "executor.node", "estimator.fit",
                  "solver.stream_fit"},
}


@pytest.mark.parametrize("entry", sorted(REACHED))
def test_without_a_profile_a_fit_traces_nothing_and_registers_nothing(entry):
    before = duration_listeners()
    ledger = profiling._LEDGER
    toy_fit(entry)
    assert not obs.enabled() and obs.last_session() is None
    assert duration_listeners() == before and profiling._LEDGER is ledger


@pytest.mark.parametrize("entry", sorted(REACHED))
def test_under_a_profile_the_fit_is_spanned_and_linked(tmp_path, entry):
    toy_fit(entry)  # warm: the second fit shows what every new fit pays
    with profile(tmp_path):
        toy_fit(entry, lam=2e-3)
    session = obs.last_session()
    assert session is not None and session.annotate is profiling.TraceAnnotation
    spans = session.spans()
    names = {s["name"] for s in spans}
    assert REACHED[entry] <= names, REACHED[entry] - names
    ids = {s["span_id"] for s in spans}
    roots = [s for s in spans if s["parent_id"] is None]
    assert sorted(s["name"] for s in roots) == ["pipeline.build", "pipeline.fit"]
    assert all(s["parent_id"] in ids for s in spans if s["parent_id"] is not None)
    if entry != "streaming":  # the barrier only a traced run has, and the observe drains
        sites = {s["args"]["site"] for s in spans if s["name"] == "executor.drain"}
        assert "observe" in sites and ("estimator_sync" in sites) == (entry == "auto")


def test_the_block_solvers_phases_are_spanned_with_their_compiles_under_them():
    """``fit_blocks`` as the resident cell reaches it (there the cost model
    selects the estimator, and its ``fit`` is called on resident features)."""
    rng = np.random.default_rng(3)
    F = Dataset.of(jnp.asarray(rng.normal(size=(ROWS, 4 * BLOCK)).astype(np.float32)))
    _, Y = toy_rows()
    with obs.tracing() as t:
        with obs.span("estimator.fit") as fit:
            BlockLeastSquaresEstimator(BLOCK, 2, 1e-3).fit(F, Y)
    phases = [s for s in t.spans() if s["name"].startswith("solver.")]
    assert [s["name"] for s in sorted(phases, key=lambda s: s["ts_us"])] == [
        "solver.scale", "solver.stack", "solver.bcd"]
    assert all(s["parent_id"] == fit.span_id for s in phases)
    owners = {s["parent_id"] for s in t.spans("jax.compile")}
    assert owners <= {fit.span_id} | {s["span_id"] for s in phases}
    (bcd,) = t.spans("solver.bcd")
    assert any(s["parent_id"] == bcd["span_id"] and "_bcd_fused_kernel" in str(s["args"]["fun"])
               for s in t.spans("jax.compile"))


def test_spans_ride_the_profile_under_the_callers_annotation(tmp_path):
    toy_fit("streaming")
    with profile(tmp_path):
        with jax.profiler.TraceAnnotation("outer.fit"):
            toy_fit("streaming", lam=2e-3)
    lines = annotations(tmp_path)
    (line,) = [k for k, events in lines.items() if any(e[0] == "ks.pipeline.fit" for e in events)]
    events = {name: (t0, t1) for name, t0, t1 in lines[line]}
    outer, fit = events["outer.fit"], events["ks.pipeline.fit"]
    assert outer[0] <= fit[0] <= fit[1] <= outer[1]  # same line, same clock, nested
    assert outer[0] <= events["ks.pipeline.build"][0] <= fit[0]
    assert fit[0] <= events["ks.solver.stream_fit"][0] <= fit[1]


def test_a_first_call_inside_a_span_puts_its_compiles_under_it():
    @jax.jit
    def fresh(x):
        return jnp.sin(x) * 3 + 1

    with obs.tracing() as t:
        with obs.span("outer"):
            with obs.span("inner") as inner:
                fresh(jnp.ones(17)).block_until_ready()
            with obs.span("again"):
                fresh(jnp.ones(17)).block_until_ready()  # cached: nothing to record
    compiles = t.spans("jax.compile")
    mine = [s for s in compiles if "fresh" in str(s["args"]["fun"])]
    assert {s["args"]["stage"] for s in mine} == {"trace", "lower", "backend"}
    assert all(s["parent_id"] == inner.span_id for s in compiles)
    (inner_rec,) = t.spans("inner")
    for s in mine:  # from now - duration to now, inside the span that caused it
        assert s["ts_us"] + s["dur_us"] <= inner_rec["ts_us"] + inner_rec["dur_us"] + 1000


def test_add_span_takes_the_innermost_open_span_as_parent():
    with obs.tracing() as t:
        alone = t.add_span("late", 1.0, 2.0)
        with obs.span("outer"):
            with obs.span("inner") as inner:
                under = t.add_span("late", 1.0, 2.0)
    by_id = {s["span_id"]: s for s in t.spans("late")}
    assert by_id[alone]["parent_id"] is None
    assert by_id[under]["parent_id"] == inner.span_id


def test_a_tracer_of_obs_tracing_is_left_alone(tmp_path):
    with obs.tracing() as t:
        with profile(tmp_path):
            toy_fit("streaming")
            assert obs.active_tracer() is t
        toy_fit("streaming")  # no profile now: still not the bridge's to end
        assert obs.active_tracer() is t
    assert obs.last_session() is None and t.annotate is None
    assert len(t.spans("pipeline.fit")) == 2


def test_the_session_ends_at_the_first_fit_entry_after_the_profile(tmp_path):
    with profile(tmp_path):
        toy_fit("streaming")
    session = obs.last_session()
    assert obs.active_tracer() is session  # nothing has looked since
    toy_fit("streaming")
    assert not obs.enabled() and obs.last_session() is session
    assert len(session.spans("pipeline.fit")) == 1
    with profile(tmp_path / "second"):
        toy_fit("streaming")
    assert obs.last_session() is not session  # a new profile, a new session


def test_xla_profile_puts_span_trace_and_profile_on_one_clock(tmp_path):
    with obs.tracing(str(tmp_path), xla_profile=True) as t:
        assert t.annotate is profiling.TraceAnnotation
        with obs.span("phase.outer", step=1):
            with obs.span("phase.inner"):
                jnp.ones(8).sum().block_until_ready()
    events = {name: (t0, t1) for evs in annotations(tmp_path / "xla").values()
              for name, t0, t1 in evs}
    outer, inner = events["ks.phase.outer"], events["ks.phase.inner"]
    assert outer[0] <= inner[0] <= inner[1] <= outer[1]
    assert os.path.exists(tmp_path / "events.jsonl")


def test_one_compile_listener_class_and_the_ledger_is_one_per_process():
    assert chip_smoke.CompileClock is profiling.CompileClock
    assert profiling.compile_ledger() is profiling.compile_ledger()
    assert profiling.compile_ledger().record_spans
    clock = profiling.CompileClock()  # a caller's own clock counts and records no span
    with obs.tracing() as t, clock.measure() as timing:
        jax.jit(lambda x: x * 5 - 2)(jnp.ones(11)).block_until_ready()
    assert timing["programs_compiled"] >= 1
    stages = sorted(s["args"]["stage"] for s in t.spans("jax.compile")
                    if "<lambda>" in str(s["args"]["fun"]))
    assert stages == ["backend", "lower", "trace"]  # the ledger's, once each


@pytest.mark.parametrize("module", sorted(
    f for f in os.listdir(os.path.dirname(tracer_mod.__file__)) if f.endswith(".py")))
def test_obs_imports_no_jax_at_module_level(module):
    path = os.path.join(os.path.dirname(tracer_mod.__file__), module)
    tree = ast.parse(open(path).read())
    for node in tree.body:
        names = ([a.name for a in node.names] if isinstance(node, ast.Import) else
                 [node.module or ""] if isinstance(node, ast.ImportFrom) else [])
        assert not [n for n in names if n.split(".")[0] in ("jax", "jaxlib")], (module, names)
