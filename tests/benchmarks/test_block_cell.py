"""The block cell's own tests (tier-1, CPU): the configuration file, the
residual-form reference against the Gramian-form one, the program through
the public entry against the reference at toy size with the selector
facing the gram-or-block choice, the one-bf16-pass control, the faults the
comparison has to catch, and the three new readers.

Nothing here describes a TPU topology or touches a chip.
"""

import itertools
import json
import os

import numpy as np
import pytest

from benchmarks import arith, run
from benchmarks.drivers import block_fit_loop as driver
from benchmarks.drivers import fit_loop
from benchmarks.layer_metrics import span_account
from benchmarks.reference import timit as gram_reference
from benchmarks.reference import timit_block as reference

CELL = "timit_block_fit_131k"
MANIFEST = json.load(open(os.path.join(run.ROOT, "BENCHMARK.json")))
SEED = 2_147_500_123


@pytest.fixture(autouse=True)
def float32_mode():
    """The suite's conftest turns 64-bit mode on; the benchmark runs as its
    users do, without it."""
    import jax

    with jax.enable_x64(False):
        yield


@pytest.fixture(scope="module")
def toy():
    """The rehearsal's cell, one seed's rows, and the reference's scores and
    the one-pass control's for one ridge value."""
    import jax

    with jax.enable_x64(False):
        cell = run.load_cell(CELL, rehearse=True)
        _, X, Y, probe = fit_loop.make_problem(cell, SEED)
        lam, shared = 1e-5, fit_loop.reference_args(cell["config_data"])
        want = reference.fit_and_score(X, Y, probe, [lam], **shared)[lam]
        lowered = reference.fit_and_score(X, Y, probe, [lam], precision="bf16", **shared)[lam]
        return {"cell": cell, "X": X, "Y": Y, "probe": probe, "lam": lam, "want": want,
                "shared": shared, "control": reference.score_gaps(lowered, want)}


def rehearse(capsys, trace_flag=0, seed=SEED):
    rc = run.main(["--workload", CELL, "--seed", str(seed), "--seconds", "0.2",
                   "--trace", str(trace_flag), "--rehearse"])
    out = capsys.readouterr()
    return rc, json.loads(out.out.strip().splitlines()[-1]), out.err


def test_configuration_states_the_deployment_and_cuts_no_width():
    cell = run.load_cell(CELL)
    config, traffic = cell["config_data"], cell["traffic_data"]
    entry = next(c for c in MANIFEST["configs"] if c["name"] == cell["config"])
    assert config["source"] == entry["source"] and len(entry["source"]) <= 200
    assert config["architecture"] is None
    assert "sixteen devices" in config["deployment"] and "131,072" in config["deployment"]
    for key in ("num_cosines", "num_epochs", "block_size", "d_in", "num_classes", "gamma"):
        assert config[key] == config["published"][key]  # no width differs from the source
    assert (config["num_cosines"], config["block_size"], config["num_epochs"],
            config["d_in"], config["num_classes"]) == (50, 4096, 5, 440, 147)
    assert config["reduced"] == ["rows"] == entry["reduced"]
    assert config["rows"] == traffic["rows"] == 4 * 32768
    assert 0.9 < config["rows"] / (config["published"]["rows"] / 16) < 1.0
    assert config["entry"] == "auto" and config["selector"] == {}
    assert config["fit_flops"] == "block_bcd" and {"lam", "rows", "bank"} <= set(config["assumed"])
    assert traffic["compare_fits"] == 1 and traffic["driver"] == "block_fit_loop"
    assert cell["chips"] == 1
    assert {m["name"] for m in cell["per_layer"]} >= {
        "window_compiles", "fit_mfu_pct", "device_idle_pct", "block_first_epoch_ms",
        "block_later_epoch_ms", "block_fit_host_ms", "block_featurize_roofline"}
    # 2.15e14 operations a fit by the yardstick: 5.5% of the peak at 20 s a fit
    flops = arith.FIT_FLOPS["block_bcd"](config["rows"], 440, 204800, 147, 4096, 5)
    assert flops == pytest.approx(2.15e14, rel=0.02)


def test_residual_form_reference_equals_the_gramian_form(toy):
    """The two references are the same iterates: one eliminates the residual
    through the d x d Gramian, the other keeps it and makes every block's
    features again. Where both can run they agree to 1e-5."""
    import jax

    lams = [1e-5, 1e-3]
    keys = jax.random.split(jax.random.key(0), 3)
    X, Y = fit_loop.make_rows(keys[0], keys[1], 2048, 440, 147)  # more rows than features:
    probe, _ = fit_loop.make_rows(keys[0], keys[2], 256, 440, 147)  # both forms well-posed
    shared = dict(toy["shared"], num_cosines=6)
    ours = reference.fit_and_score(X, Y, probe, lams, rows_per_block=512, **shared)
    theirs = gram_reference.fit_and_score(X, Y, probe, lams, **shared)
    for lam in lams:
        assert max(reference.score_gaps(ours[lam], theirs[lam])) < 1e-5
    # no sweep: W = 0, and the scores are the label mean
    none = reference.fit_and_score(toy["X"], toy["Y"], toy["probe"], [1e-4],
                                   **{**toy["shared"], "epochs": 0})[1e-4]
    assert np.allclose(np.asarray(none), np.asarray(toy["Y"]).mean(axis=0), atol=1e-6)


def test_reference_imports_nothing_of_the_program_and_forms_no_d_by_d_gramian():
    import inspect

    source = inspect.getsource(reference)
    assert "keystone_tpu" not in source.split('"""', 2)[2]
    assert "centred_stats" not in source and "block_gauss_seidel" not in source


def test_program_within_the_limits_and_the_control_over_them(toy):
    """Through the cell's own entry, no engine named: the rehearsal's budget
    makes the block tier the cost model's own choice, at the configured
    block size, and the fit's span says so."""
    from keystone_tpu import obs

    cell = toy["cell"]
    config = cell["config_data"]
    with obs.tracing() as tracer:
        fitted = driver.fit_once(config, toy["lam"], toy["X"], toy["Y"])
    assert driver.block_weight_shapes(fitted) == [(16, 128, 147)] == [driver.configured_shape(config)]
    attrs = next(s["args"] for s in tracer.spans("estimator.fit") if "engine" in s["args"])
    assert attrs["engine"] == "block_stream" and attrs["block_size"] == 128
    assert attrs["blocks"] == 16 and attrs["stash"] == "gram+factor"
    assert attrs["stash_bytes"] == 8 * 2048 * 128
    phases = [(s["args"]["epoch_from"], s["args"]["epoch_to"])
              for s in tracer.spans("solver.block_epoch")]
    assert phases == [(1, 1), (2, 5)]
    drains = [s["args"] for s in tracer.spans("executor.drain") if s["args"]["site"] == "block_epoch"]
    assert [(d["epoch_from"], d["epoch_to"]) for d in drains] == phases
    program = reference.score_gaps(fit_loop.probe_scores(fitted, toy["probe"]), toy["want"])
    for i, name in enumerate(("score_rel_fro", "score_widest")):
        assert program[i] < cell["limits"][name]["limit"] < toy["control"][i]
        assert toy["control"][i] > 5 * program[i]


def _later_fits(monkeypatch, make_faulty):
    """Plant a fault in every fit AFTER the warm-up (which the driver checks
    on its own): ``make_faulty()`` is entered once the first fit is built."""
    sound, calls = driver.build_pipeline, []

    def build(*args):
        calls.append(1)
        if len(calls) == 2:
            make_faulty()
        return sound(*args)

    monkeypatch.setattr(driver, "build_pipeline", build)


@pytest.mark.parametrize("fault", ["block_size_halved", "one_epoch_fewer", "stash_of_next_block",
                                   "centring_left_out"])
def test_a_broken_timed_path_comes_out_not_correct(capsys, monkeypatch, fault):
    """The rest of a run with the block tier broken underneath."""
    from keystone_tpu.ops.learning import streaming_ls
    from keystone_tpu.parallel import streaming

    if fault == "block_size_halved":  # the tier shrinks the block it was given (another model)
        sound_plan = streaming_ls.StreamingLeastSquaresChoice._block_tier_plan

        def halved(self, d_feat, fixed_bytes=0.0):
            bs, stash = sound_plan(self, d_feat, fixed_bytes)
            return bs // 2, stash

        _later_fits(monkeypatch, lambda: monkeypatch.setattr(
            streaming_ls.StreamingLeastSquaresChoice, "_block_tier_plan", halved))
    elif fault == "one_epoch_fewer":
        sound = driver.build_pipeline
        monkeypatch.setattr(driver, "build_pipeline", lambda config, *rest: sound(
            dict(config, num_epochs=config["num_epochs"] - 1), *rest))
    elif fault == "stash_of_next_block":  # epochs 2+ read block b + 1's Gramian and factor
        import jax.numpy as jnp

        sound_first = streaming.block_bcd_first_epoch

        def rolled(*args, **kw):
            (R, W, G, C, M), ymean, res = sound_first(*args, **kw)
            return (R, W, jnp.roll(G, -1, axis=0), jnp.roll(C, -1, axis=0), M), ymean, res

        monkeypatch.setattr(streaming, "block_bcd_first_epoch", rolled)
    else:  # the model fitted without the means
        sound_init = streaming_ls.BlockStreamedLeastSquares.__init__

        def uncentred(self, *args, **kw):
            sound_init(self, *args, **{**kw, "center": False})

        monkeypatch.setattr(streaming_ls.BlockStreamedLeastSquares, "__init__", uncentred)
    rc, line, err = rehearse(capsys)
    assert rc == 0 and line["correct"] is False, err
    over = {name for name, p in line["compared"].items() if p["value"] > p["limit"]}
    assert over & {"score_rel_fro", "score_widest"}, line["compared"]
    if fault == "block_size_halved":
        assert line["compared"]["block_size_gap"] == {"value": 64, "limit": 0}


def test_a_program_that_cannot_fit_the_configuration_gets_no_result(capsys, monkeypatch):
    """The warm-up fit at another block size: the run ends there, aloud,
    with another exit code than 0 — it is not timed as if it were the model."""
    from keystone_tpu.ops.learning import streaming_ls

    sound_plan = streaming_ls.StreamingLeastSquaresChoice._block_tier_plan
    monkeypatch.setattr(streaming_ls.StreamingLeastSquaresChoice, "_block_tier_plan",
                        lambda self, d, fixed=0.0: (sound_plan(self, d, fixed)[0] // 2, "factor"))
    with pytest.raises(SystemExit) as exit_:
        run.main(["--workload", CELL, "--seed", str(SEED), "--seconds", "0.2", "--trace", "0",
                  "--rehearse"])
    assert "(32, 64, 147)" in str(exit_.value) and "another model" in str(exit_.value)
    assert exit_.value.code not in (0, None) and capsys.readouterr().out == ""


def test_sound_rehearsal_is_correct_and_compiles_nothing_in_the_window(capsys):
    rc, line, err = rehearse(capsys, trace_flag=1)
    assert rc == 0 and line["correct"] is True and line["failed"] == 0, err
    assert line["compared"]["block_size_gap"] == {"value": 0, "limit": 0}
    assert line["metrics"]["window_compiles"] == {"value": 0, "unit": "count"}  # lam is an operand
    assert "block weights [(16, 128, 147)]" in err


T0 = 1_700_000_000_000_000


def _session(fits=2):
    """Hand-written spans of ``fits`` block-streamed fits: a build of 300 us,
    a fit of 20,000 with the sample (400), the first dispatch (100) and its
    drain (9,000), the second (50) and its drain (8,000: four epochs)."""
    ids, spans = itertools.count(1), []

    def span(name, start, dur, parent=None, **args):
        spans.append({"type": "span", "name": name, "ts_us": T0 + start, "dur_us": dur,
                      "span_id": next(ids), "parent_id": parent, "args": args})
        return spans[-1]["span_id"]

    for i in range(fits):
        at = i * 30_000
        span("pipeline.build", at, 300, entry="featurizer", branches=50)
        fit = span("pipeline.fit", at + 1_000, 20_000)
        span("optimizer.rule.NodeOptimizationRule", at + 1_100, 400, fit)
        est = span("estimator.fit", at + 2_000, 18_000, fit, estimator="StreamedFitEstimator",
                   engine="block_stream", block_size=4096, blocks=50, stash="gram+factor")
        span("solver.block_epoch", at + 2_100, 100, est, epoch_from=1, epoch_to=1, blocks=50)
        span("executor.drain", at + 2_200, 9_000, est, site="block_epoch", epoch_from=1, epoch_to=1)
        span("solver.block_epoch", at + 11_300, 50, est, epoch_from=2, epoch_to=5, blocks=50)
        span("executor.drain", at + 11_400, 8_000, est, site="block_epoch", epoch_from=2, epoch_to=5)
    return spans


def _ctx(fits=2, trace=True):
    return {"trace": {"window_s": 1.0} if trace else None, "notes": [], "config": {},
            "traffic": {}, "counters": {}, "device_kind": "TPU v5 lite",
            "window": {"fits": fits, "window_s": 1.0}}


def test_phase_and_host_readers_on_a_small_session_and_with_nothing_to_read(monkeypatch):
    first, later, host = (run.load_reader(name) for name in (
        "block_first_epoch_ms", "block_later_epoch_ms", "block_fit_host_ms"))
    monkeypatch.setattr(span_account, "session_spans", lambda: _session())
    ctx = _ctx()
    assert first.read(ctx) == pytest.approx(9.0)  # the first drain, a fit
    assert later.read(ctx) == pytest.approx(8.0 / 4)  # the second over its four epochs
    assert "'engine': 'block_stream'" in ctx["notes"][-1]
    # the host's own: build 300 + fit 20,000 less the two waits
    assert host.read(ctx) == pytest.approx((300 + 20_000 - 17_000) / 1e3)
    assert "NodeOptimizationRule, whole duration) 0.4 ms" in ctx["notes"][-1]
    # a program whose spans name no block phase (the parent's): nothing, and no raise
    other = [s for s in _session() if s["args"].get("site") != "block_epoch"]
    monkeypatch.setattr(span_account, "session_spans", lambda: other)
    assert first.read(_ctx()) is None and later.read(_ctx()) is None
    monkeypatch.setattr(span_account, "session_spans", lambda: None)  # no session at all
    for reader in (first, later, host):
        assert reader.read(_ctx()) is None and reader.read(_ctx(trace=False)) is None


def test_featurize_roofline_on_a_small_trace_and_with_nothing_to_read():
    """250 calls a fit, each all rows x one block: bound by the slab's bytes."""
    reader = run.load_reader("block_featurize_roofline")
    config = run.load_cell(CELL)["config_data"]

    def ctx(op_seconds, fits=2):
        trace = None if op_seconds is None else {"op_seconds": op_seconds}
        return {"trace": trace, "config": config, "notes": [], "device_kind": "TPU v5 lite",
                "window": {"fits": fits, "window_s": 30.0, "rows": 131072}}

    found = ctx({"cosine_features.3": 9.0, "select_add_fusion.3": 14.0, "cosine_features_x.1": 5.0})
    call_bytes = 4 * (131072 * 440 + 4096 * 440 + 4096 + 131072 * 4096)
    assert reader.read(found) == pytest.approx(100 * 500 * call_bytes / 819e9 / 9.0)
    assert "500 calls" in found["notes"][-1] and "bound by memory" in found["notes"][-1]
    assert reader.read(found) < 100
    assert reader.read(ctx({"fusion.7": 9.0})) is None  # the tier on XLA: no such kernel
    assert reader.read(ctx(None)) is None and reader.read(ctx({"cosine_features.3": 1.0}, 0)) is None


def test_control_readings_part_program_from_control_at_toy_size():
    """``benchmarks.control_block`` (the readings the limits are set from):
    one seed, the grid's smallest ridge value among its two, the program
    under the one-pass control on both numbers."""
    from benchmarks import control_block

    cell = run.load_cell(CELL, rehearse=True)
    line = control_block.readings(cell, seed=2_147_500_000, control=True, precisions=("bf16",))
    assert line["lams"][0] == cell["traffic_data"]["lam_grid"]["low"] and len(line["lams"]) == 2
    assert line["block_weights"] == [[(16, 128, 147)]] * 2
    for program, control in zip(line["program"], line["bf16"]):
        assert program[0] < control[0] / 5 and program[1] < control[1] / 5
