"""The five per-layer metrics that read the program's spans (PR 26), each
against a hand-written span list: what the account gives to which layer,
that the four times partition the spanned total to the microsecond, and
that a program with no session to read gives nothing and does not raise."""

import itertools

import pytest

from benchmarks import run
from benchmarks.layer_metrics import span_account

READERS = ("fit_retrace_ms", "retraces_per_fit", "device_wait_ms", "executor_self_ms",
           "solver_host_ms")
T0 = 1_700_000_000_000_000  # epoch microseconds, as the tracer writes them


def span(ids, name, start, dur, parent=None, **args):
    return {"type": "span", "name": name, "ts_us": T0 + start, "dur_us": dur,
            "span_id": next(ids), "parent_id": parent, "args": args}


def one_fit(ids, at, scale=1):
    """A fit of 1,000 us x ``scale`` after a build of 100 x ``scale``: a node
    that retraces (a trace with another nested in it, a lowering, a fetch) and
    drains, then the solver with one compile and the traced run's barrier."""
    k = scale
    build = span(ids, "pipeline.build", at, 100 * k)
    fit = span(ids, "pipeline.fit", at + 200 * k, 1000 * k, nodes=3)
    f = fit["ts_us"] - T0
    verify = span(ids, "fit.verify", f + 10 * k, 40 * k, fit["span_id"])
    pre = span(ids, "verify.pre_pass", f + 15 * k, 30 * k, verify["span_id"])
    node = span(ids, "executor.node", f + 100 * k, 800 * k, fit["span_id"],
                node=9, operator="FusedGatherTransformer")
    n = node["ts_us"] - T0
    compiles = [
        span(ids, "jax.compile", n + 10 * k, 100 * k, node["span_id"], stage="trace", fun="composed"),
        span(ids, "jax.compile", n + 30 * k, 20 * k, node["span_id"], stage="trace", fun="cos"),
        span(ids, "jax.compile", n + 110 * k, 50 * k, node["span_id"], stage="lower", fun="jit(composed)"),
        span(ids, "jax.compile", n + 160 * k, 40 * k, node["span_id"], stage="backend", fun="jit(composed)"),
    ]
    drain = span(ids, "executor.drain", n + 250 * k, 150 * k, node["span_id"], site="observe", node=9)
    est = span(ids, "estimator.fit", n + 450 * k, 300 * k, node["span_id"],
               estimator="BlockLeastSquaresEstimator")
    e = est["ts_us"] - T0
    bcd = span(ids, "solver.bcd", e + 20 * k, 200 * k, est["span_id"])
    bcd_trace = span(ids, "jax.compile", e + 30 * k, 60 * k, bcd["span_id"], stage="trace",
                     fun="_bcd_fused_kernel")
    sync = span(ids, "executor.drain", e + 240 * k, 50 * k, est["span_id"], site="estimator_sync")
    return [build, fit, verify, pre, node, *compiles, drain, est, bcd, bcd_trace, sync]


# per unit of ``scale``: united compiles 190 + 60; drains 150 + 50; estimator.fit
# 300 - 200 - 50 = 50 and solver.bcd 200 - 60 = 140; the rest of 1,100 is the executor's
WANT = {"retrace": 250, "wait": 200, "solver": 190, "executor": 460}


def ctx_for(fits, window_s=1.0):
    return {"trace": {"window_s": window_s}, "window": {"fits": fits, "window_s": window_s},
            "notes": [], "config": {}, "traffic": {}, "counters": {}, "device_kind": "TPU v5 lite"}


@pytest.fixture
def session(monkeypatch):
    """Hands the readers a span list in the place of the program's session."""
    def install(spans):
        monkeypatch.setattr(span_account, "session_spans", lambda: spans)
    return install


def test_the_account_of_one_fit_by_hand():
    found = span_account.account(one_fit(itertools.count(1), at=0))
    assert found["fits"] == 1 and found["layers_us"] == WANT
    assert found["spanned_us"] == {"pipeline.build": 100, "pipeline.fit": 1000}
    assert found["wait_sites_us"] == {"observe": 150, "estimator_sync": 50}
    # the trace of ``cos`` ran inside the trace of ``composed``: one retrace, not two
    assert found["nested_traces"] == 1 and found["traces"] == {
        "executor.node[FusedGatherTransformer] composed": 1,
        "solver.bcd _bcd_fused_kernel": 1}


@pytest.mark.parametrize("scales", [(1,), (1, 3), (2, 5, 7)])
def test_the_four_times_partition_the_spanned_total_to_the_microsecond(scales):
    ids, spans, at = itertools.count(1), [], 0
    for k in scales:
        spans += one_fit(ids, at, scale=k)
        at += 2000 * k
    found = span_account.account(spans)
    assert found["fits"] == len(scales)
    assert sum(found["layers_us"].values()) == sum(found["spanned_us"].values()) == 1100 * sum(scales)
    assert found["layers_us"] == {k: v * sum(scales) for k, v in WANT.items()}


def test_overlapping_and_overhanging_children_still_partition():
    ids = itertools.count(1)
    fit = span(ids, "pipeline.fit", 0, 1000)
    spans = [fit,
             span(ids, "executor.node", 100, 500, fit["span_id"]),
             span(ids, "executor.drain", 550, 300, fit["span_id"], site="observe"),  # overlaps 50
             span(ids, "jax.compile", 900, 400, fit["span_id"], stage="trace", fun="f"),  # overhangs
             span(ids, "jax.compile", -20, 60, fit["span_id"], stage="lower", fun="f")]  # starts early
    found = span_account.account(spans)
    assert sum(found["layers_us"].values()) == 1000
    assert found["layers_us"] == {"retrace": 40 + 100, "wait": 250, "executor": 60 + 500 + 50,
                                  "solver": 0}


@pytest.mark.parametrize("reader,want", [
    ("fit_retrace_ms", 0.5), ("retraces_per_fit", 2), ("device_wait_ms", 0.4),
    ("executor_self_ms", 0.92), ("solver_host_ms", 0.38)])
def test_each_reader_gives_the_mean_of_two_fits(session, reader, want):
    ids = itertools.count(1)
    session(one_fit(ids, 0, scale=1) + one_fit(ids, 5000, scale=3))
    ctx = ctx_for(fits=2, window_s=0.005)
    value = run.load_reader(reader).read(ctx)
    assert value == pytest.approx(want) and not any("root pipeline.fit" in n for n in ctx["notes"])
    if reader == "retraces_per_fit":
        assert isinstance(value, int) and "solver.bcd _bcd_fused_kernel" in ctx["notes"][0]
    if reader == "device_wait_ms":
        assert "'estimator_sync': 0.1, 'observe': 0.3" in ctx["notes"][0]
    if reader == "executor_self_ms":  # the whole account, and what the spans do not cover
        assert "= 2.200; spanned 2.200" in ctx["notes"][0] and "so 0.300 is" in ctx["notes"][0]


def test_a_count_of_fits_that_differs_from_the_windows_is_noted_once(session):
    session(one_fit(itertools.count(1), 0))
    ctx = ctx_for(fits=2)
    values = [run.load_reader(r).read(ctx) for r in READERS]
    assert None not in values
    assert sum("1 root pipeline.fit spans in the session, 2 fits" in n for n in ctx["notes"]) == 1


@pytest.mark.parametrize("reader", READERS)
@pytest.mark.parametrize("why", ["no_session", "no_profile", "program_before_pr26", "no_fit_spans"])
def test_nothing_to_read_gives_none_and_does_not_raise(monkeypatch, reader, why):
    from keystone_tpu import obs
    from keystone_tpu.obs import tracer

    ctx = ctx_for(fits=2)
    if why == "no_session":
        monkeypatch.setattr(tracer, "_SESSION", None)
    elif why == "no_profile":  # a rehearsal, whatever an earlier test left behind
        ctx["trace"] = None
        monkeypatch.setattr(tracer, "_SESSION", tracer.Tracer())
    elif why == "program_before_pr26":
        monkeypatch.delattr(obs, "last_session")
    else:
        monkeypatch.setattr(tracer, "_SESSION", tracer.Tracer())
    assert run.load_reader(reader).read(ctx) is None and ctx["notes"] == []


def test_the_manifest_lists_the_five_for_both_cells():
    for cell in ("timit_stream_fit_1m", "timit_resident_fit_40k"):
        names = [m["name"] for m in run.load_cell(cell)["per_layer"]]
        assert names[-5:] == list(READERS)
