"""``obs.device``: the device's time by ``ks.*`` phase and its idle gaps by
program span (PR 37). The arithmetic on hand-written tuples; the reader on
a trimmed recording of a real chip profile (``tests/fixtures/``); and the
session's own use of it end to end on the CPU, where a profile holds no
device plane and the account is None."""

import json
import os
import sys

import jax
import pytest

from keystone_tpu import obs
from keystone_tpu.obs import device
from keystone_tpu.obs import tracer as tracer_mod
from keystone_tpu.utils import profiling

from test_obs_profile_bridge import profile, toy_fit, toy_rows

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")
PROFILE = os.path.join(FIXTURES, "chip_profile.xplane.pb")
RECORDED = os.path.join(FIXTURES, "chip_profile_ops.json")
US = 1_000.0  # the tuples are in nanoseconds; the cases below think in microseconds
EPOCH_US = 1_791_158_000_000_000  # what a tracer's ``ts_us`` looks like


def op(name, start_us, dur_us, path="", program="jit_f"):
    return (name, start_us * US, dur_us * US, path, program)


def mark(name, start_us, dur_us):
    return (name, start_us * US, dur_us * US)


@pytest.fixture(autouse=True)
def no_session_left_over(monkeypatch):
    monkeypatch.setattr(tracer_mod, "_ACTIVE", None)
    monkeypatch.setattr(tracer_mod, "_SESSION", None)
    yield
    profiling.follow_profiler()


@pytest.mark.parametrize("path, scope", [
    ("jit(_streaming_fit_bank)/jit(main)/while/body/ks.gram_fold/dot_general", "ks.gram_fold"),
    ("jit(f)/ks.block_update/ks.block_gram/dot_general", "ks.block_gram"),  # the innermost
    ("jit(f)/ks.gram_fold/while/body/closed_call/ks.gram_psum/psum", "ks.gram_psum"),
    ("jit(f)/vmap(ks.featurize)/cos", "ks.featurize"),  # under a transformation
    ("jit(f)/jit(main)/transpose(jvp(ks.bcd))/mul", "ks.bcd"),
    ("jit(f)/jit(main)/while/body/add", "unscoped"),
    ("jit(tasks.helper)/mul", "unscoped"),  # ``ks.`` has to start a name
    ("", "unscoped"),
])
def test_the_innermost_scope_of_a_path(path, scope):
    assert device.scope_of(path) == scope


def test_self_time_under_a_while_and_the_unscoped_rest():
    """A ``while`` of 100 us holds two panels and a featurize; what they
    leave (25 us) is the loop's own, filed where ITS path says."""
    ops = [
        op("while.1", 0, 100, "jit(f)/while"),
        op("fusion.7", 5, 30, "jit(f)/while/body/ks.gram_fold/dot_general"),
        op("fusion.8", 40, 20, "jit(f)/while/body/ks.gram_fold/dot_general"),
        op("cosine_features.4", 65, 25, "jit(f)/while/body/ks.featurize/pallas_call"),
        op("copy.3", 110, 10, "", "jit_g"),
    ]
    found = device.account([("/device:TPU:0", ops)], [])
    (plane,) = found["planes"]
    assert plane["by_scope_ns"] == {"ks.gram_fold": 50 * US, "ks.featurize": 25 * US,
                                    "unscoped": 35 * US}
    assert plane["by_program_ns"] == {"jit_f": 100 * US, "jit_g": 10 * US}
    assert plane["unscoped_ops_ns"] == {"jit_f/while.1": 25 * US, "jit_g/copy.3": 10 * US}
    assert plane["busy_ns"] == 110 * US and plane["idle_ns"] == 10 * US
    assert sum(plane["by_scope_ns"].values()) == plane["busy_ns"]
    assert found["extent_ns"] == 120 * US


def test_planes_are_kept_apart():
    fold = "jit(f)/ks.gram_fold/dot_general"
    psum = "jit(f)/ks.gram_psum/psum"
    planes = [
        ("/device:TPU:0", [op("fusion.1", 0, 100, fold), op("psum.35", 100, 30, psum)]),
        ("/device:TPU:1", [op("fusion.1", 0, 120, fold), op("psum.35", 120, 10, psum)]),
        ("/device:TPU:2", []),  # a plane that ran nothing is no plane of the account
    ]
    found = device.account(planes, [])
    assert [p["device"] for p in found["planes"]] == ["/device:TPU:0", "/device:TPU:1"]
    assert [p["by_scope_ns"]["ks.gram_psum"] for p in found["planes"]] == [30 * US, 10 * US]
    assert [p["by_scope_ns"]["ks.gram_fold"] for p in found["planes"]] == [100 * US, 120 * US]


def test_a_gap_is_split_over_three_spans_and_outside():
    """One gap of 100 us: 30 under the optimizer's rule, 25 under
    ``solver.stack``, 15 under ``pipeline.fit`` itself and 30 under no span of
    the program — each part to the span open over it, not the whole to the
    midpoint's."""
    ops = [op("a", 0, 10, "jit(f)/ks.bcd/x"), op("b", 110, 10, "jit(f)/ks.bcd/x")]
    marks = [
        mark("ks.pipeline.fit", 20, 70),                 # 20..90
        mark("ks.optimizer.rule.NodeOptimizationRule", 30, 30),  # 30..60
        mark("ks.solver.stack", 65, 25),                 # 65..90
    ]
    found = device.account([("/device:TPU:0", ops)], marks)
    (plane,) = found["planes"]
    assert plane["idle_ns"] == 100 * US
    assert plane["idle_ns_by_span"] == {
        "ks.optimizer.rule.NodeOptimizationRule": 30 * US,
        "outside": 30 * US,          # 10..20 and 90..110
        "ks.solver.stack": 25 * US,
        "ks.pipeline.fit": 15 * US,  # 20..30 and 60..65
    }
    (gap,) = found["longest_gaps"]
    assert gap["gap_ns"] == 100 * US and gap["start_ns"] == 10 * US
    assert gap["chain"] == ["ks.pipeline.fit"]  # over the midpoint, 60 us
    assert gap["by_span_ns"] == plane["idle_ns_by_span"]


def test_the_ten_longest_gaps_with_their_chains():
    ops = [op(f"o{i}", i * 100, 100 - (i + 1), "jit(f)/ks.bcd/x") for i in range(14)]
    marks = [mark("ks.pipeline.fit", 0, 1400), mark("ks.estimator.fit", 1290, 20)]
    found = device.account([("/device:TPU:0", ops)], marks)
    gaps = found["longest_gaps"]
    assert len(gaps) == device.LONGEST_GAPS
    assert [g["gap_ns"] for g in gaps] == [(14 - i) * US for i in range(10)]
    assert gaps[0]["chain"] == ["ks.pipeline.fit"]
    assert gaps[1]["chain"] == ["ks.pipeline.fit", "ks.estimator.fit"]
    assert gaps[1]["by_span_ns"] == {"ks.estimator.fit": 10 * US, "ks.pipeline.fit": 3 * US}


def session_spans(offset_us_of_root):
    """Two fits' roots and a compile that only the ledger knows of; each
    root's annotation stands ``offset_us_of_root[i]`` later than the first's."""
    spans, marks = [], []
    for i, (name, at, dur) in enumerate([("pipeline.build", 100, 50), ("pipeline.fit", 200, 600),
                                         ("pipeline.build", 900, 50), ("pipeline.fit", 1000, 600)]):
        spans.append({"name": name, "ts_us": EPOCH_US + at, "dur_us": dur, "span_id": i + 1,
                      "parent_id": None, "args": {}})
        marks.append(mark("ks." + name, at + offset_us_of_root[i], dur))
    spans.append({"name": "jax.compile", "ts_us": EPOCH_US + 300, "dur_us": 100, "span_id": 9,
                  "parent_id": 2, "args": {"stage": "lower", "fun": "wrapped"}})
    spans.append({"name": "fit.verify", "ts_us": EPOCH_US + 210, "dur_us": 20, "span_id": 10,
                  "parent_id": 2, "args": {}})
    marks.append(mark("ks.fit.verify", 210, 20))
    return spans, marks


def test_after_the_fact_spans_are_laid_on_the_profiles_clock():
    spans, marks = session_spans([0, 0, 0, 0])
    laid, clock = device.after_the_fact(spans, marks)
    # only what has no annotation of its own: the compile, under its stage
    assert laid == [("ks.jax.compile[lower]", 300 * US, 100 * US)]
    assert clock == {"roots": 4, "offset_ns": -EPOCH_US * 1000, "spread_ns": 0}
    ops = [op("a", 100, 150, "jit(f)/ks.bcd/x"), op("b", 450, 1150, "jit(f)/ks.bcd/x")]
    found = device.account([("/device:TPU:0", ops)], marks, spans)
    (plane,) = found["planes"]
    # the gap 250..450: 50 under pipeline.fit, 100 under the compile, 50 under the fit again
    assert plane["idle_ns_by_span"] == {"ks.jax.compile[lower]": 100 * US,
                                        "ks.pipeline.fit": 100 * US}
    assert found["longest_gaps"][0]["chain"] == ["ks.pipeline.fit", "ks.jax.compile[lower]"]
    assert found["clock"]["spread_ns"] == 0


def test_the_clocks_offset_is_the_median_and_its_spread_is_kept():
    spans, marks = session_spans([0, 2, 5, 3])
    laid, clock = device.after_the_fact(spans, marks)
    assert clock["roots"] == 4 and clock["spread_ns"] == 5 * US
    assert clock["offset_ns"] == -EPOCH_US * 1000 + 2500  # the median of 0, 2, 3, 5 us
    assert laid[0][1] == 300 * US + 2500


def test_without_roots_nothing_is_laid():
    spans, marks = session_spans([0, 0, 0, 0])
    assert device.after_the_fact(spans, [m for m in marks if "pipeline" not in m[0]]) == ([], None)
    assert device.account([("/device:TPU:0", [op("a", 0, 1)])], [], [])["clock"] is None


@pytest.mark.parametrize("planes", [[], [("/device:TPU:0", [])]])
def test_no_operation_on_a_device_is_no_account(planes):
    assert device.account(planes, [mark("ks.pipeline.fit", 0, 10)]) is None
    assert "no account" in device.render(None)


def test_the_account_renders_as_text():
    ops = [op("fusion.7", 0, 30, "jit(f)/ks.gram_fold/dot_general"), op("copy.1", 50, 10)]
    text = device.render(device.account([("/device:TPU:0", ops)], [mark("ks.pipeline.fit", 25, 30)]))
    for piece in ("/device:TPU:0", "ks.gram_fold", "unscoped", "jit_f/copy.1",
                  "ks.pipeline.fit", "longest idle gaps"):
        assert piece in text, piece


def test_the_reader_finds_the_scope_of_every_event_of_a_chip_profile():
    """A real profile (cell ``timit_stream_fit_1m``, TPU v5 lite, jax 0.9.0),
    trimmed to a few hundred events, read by this module's own wire-format
    reader — against what protobuf's generated code read off the same bytes,
    and beside each event the scope a person read off its ``tf_op``."""
    with open(RECORDED) as f:
        recorded = json.load(f)
    planes, marks = device.read_profile(PROFILE)
    (name, ops), (want,) = planes[0], recorded["planes"]
    assert len(planes) == 1 and name == want["device"] and len(ops) == len(want["events"]) >= 300
    seen = set()
    for got, e in zip(ops, want["events"]):
        assert got[0] == e["name"] and got[3] == e["tf_op"] and got[4] == e["program"]
        assert got[1] == pytest.approx(e["start_ns"], abs=1e-3)
        assert got[2] == pytest.approx(e["duration_ns"], abs=1e-3)
        assert device.scope_of(got[3]) == e["scope"], e
        seen.add(e["scope"])
    assert seen == {"ks.featurize", "ks.gram_fold", "ks.bcd", "unscoped"}
    assert sorted(marks) == sorted(tuple(m) for m in recorded["annotations"])
    assert {"ks.pipeline.build", "ks.pipeline.fit", "ks.solver.stream_fit"} <= {m[0] for m in marks}
    found = device.account(planes, marks)
    (plane,) = found["planes"]
    assert plane["by_program_ns"]["jit__streaming_fit_bank"] > 0.99 * plane["busy_ns"]
    assert set(plane["by_scope_ns"]) == seen


def test_the_newest_profile_under_a_directory_is_found(tmp_path):
    for stamp in ("2026_10_04_00_00_00", "2026_10_05_00_00_00"):
        run = tmp_path / "plugins" / "profile" / stamp
        run.mkdir(parents=True)
        (run / "host.xplane.pb").write_bytes(open(PROFILE, "rb").read())
        os.utime(run / "host.xplane.pb", (1, 1) if stamp < "2026_10_05" else None)
    assert "2026_10_05" in device.newest_xplane(str(tmp_path))
    assert device.newest_xplane(PROFILE) == PROFILE
    with pytest.raises(FileNotFoundError):
        device.newest_xplane(str(tmp_path / "plugins"))
    assert device.device_account(str(tmp_path))["planes"][0]["device"] == "/device:TPU:0"


def test_what_is_no_profile_is_refused():
    with pytest.raises(ValueError, match="not an .xplane.pb"):
        list(device._fields(b"\x0b\x00", 0, 2))  # a group's wire type


def test_under_a_profile_the_session_ends_at_the_first_apply_and_takes_the_directory(tmp_path):
    toy_fit("auto")
    with profile(tmp_path):
        fitted = toy_fit("auto", lam=2e-3)
    session = obs.last_session()
    assert obs.active_tracer() is session and session.profile_dir == str(tmp_path)
    assert session.device_account is None
    X, _ = toy_rows()
    fitted.apply(X)  # the profile is over: the session ends here, and looks at it
    assert not obs.enabled() and obs.last_session() is session
    assert session.device_account is None  # the CPU's profile holds no device plane
    assert "keystone_tpu.obs.device" in sys.modules
    # no span of the scoring is in the session: it ended before the apply opened one
    assert not session.spans("pipeline.apply")
    planes, marks = device.read_profile(device.newest_xplane(str(tmp_path)))
    assert planes == [] and {"ks.pipeline.fit", "ks.pipeline.build"} <= {m[0] for m in marks}
    laid, clock = device.after_the_fact(session.spans(), marks)
    assert clock["roots"] == 2 and clock["spread_ns"] < 20_000
    assert laid and all(m[0].startswith("ks.jax.compile[") for m in laid)


def test_a_profiled_apply_is_a_root_span(tmp_path):
    fitted = toy_fit("streaming")
    X, _ = toy_rows()
    with profile(tmp_path):
        fitted.apply(X)
    session = obs.last_session()
    (root,) = session.spans("pipeline.apply")
    assert root["parent_id"] is None


def test_a_profile_that_cannot_be_read_is_a_note_not_an_error(tmp_path, caplog):
    with profile(tmp_path):
        fitted = toy_fit("streaming")
    session = obs.last_session()
    session.profile_dir = str(tmp_path / "gone")
    X, _ = toy_rows()
    with caplog.at_level("WARNING", logger="keystone_tpu.profiling"):
        fitted.apply(X)
    assert not obs.enabled() and session.device_account is None
    assert any("no device account" in r.message for r in caplog.records)


def test_with_no_profile_and_no_tracer_an_apply_starts_nothing():
    fitted = toy_fit("streaming")
    X, _ = toy_rows()
    assert not obs.enabled() and obs.last_session() is None
    fitted.apply(X)
    assert not obs.enabled() and obs.last_session() is None


def test_the_sync_barrier_reaches_a_chained_transformers_arrays():
    """``cost.py``'s ``Chained`` is a class made inside a fit: its instance
    holds nothing, its methods' closures hold the model."""
    import jax.numpy as jnp

    from keystone_tpu.workflow import pipeline

    inner_weights = jnp.ones((3, 2))
    scale = jnp.full((3,), 2.0)

    class Inner:
        def __init__(self):
            self.W = {"blocks": [inner_weights]}

    def make():
        inner, s = Inner(), scale

        class Chained:
            def apply(self, x):
                return inner.W["blocks"][0] * s

        return Chained()

    found = pipeline._held_arrays(make())
    assert {id(a) for a in found} == {id(inner_weights), id(scale)}
    assert pipeline._held_arrays(jax) == []  # a module is not entered


def test_the_cli_prints_the_account_of_a_kept_profile_and_refuses_what_is_none(tmp_path, capsys):
    from keystone_tpu.tools import trace as trace_cli

    assert trace_cli.main(["--device", PROFILE]) == 0
    out = capsys.readouterr().out
    assert "/device:TPU:0" in out and "ks.gram_fold" in out and "longest idle gaps" in out
    broken = tmp_path / "broken.xplane.pb"
    broken.write_bytes(open(PROFILE, "rb").read()[:1000])
    assert trace_cli.main(["--device", str(broken)]) == 1
    assert trace_cli.main(["--device", str(tmp_path / "nothing_here")]) == 1
    assert "cannot read a profile" in capsys.readouterr().err
