"""The sparse cell's own tests (tier-1, CPU): the configuration file, the row
generator, the program through the public entry against the plain reference
for each sparse candidate, the one-bf16-pass control, the faults the
comparison has to catch, the sparse arithmetic and the three new readers.

Nothing here describes a TPU topology or touches a chip.
"""

import json
import os

import numpy as np
import pytest

from benchmarks import arith, arith_sparse, run
from benchmarks.drivers import sparse_fit_loop as driver
from benchmarks.reference import amazon as reference

CELL = "amazon_lbfgs_fit_4m"
MANIFEST = json.load(open(os.path.join(run.ROOT, "BENCHMARK.json")))


@pytest.fixture(autouse=True)
def float32_mode():
    """The suite's conftest turns 64-bit mode on; the benchmark runs as its
    users do, without it."""
    import jax

    with jax.enable_x64(False):
        yield


@pytest.fixture(scope="module")
def toy():
    """The rehearsal's cell, one seed's rows, the reference's scores and the
    control's, for one ridge value."""
    import jax

    with jax.enable_x64(False):
        cell = run.load_cell(CELL, rehearse=True)
        problem = driver.make_problem(cell, seed=2_147_500_123)
        lam, shared = 1e-4, driver.reference_args(cell["config_data"])
        want, its = reference.fit_and_score(*problem[1:], [lam], **shared)
        lowered, _ = reference.fit_and_score(*problem[1:], [lam], precision="bf16", **shared)
        return {"cell": cell, "problem": problem, "lam": lam, "want": want[lam],
                "iterations": its[lam],
                "control": reference.score_gaps(lowered[lam], want[lam])}


def rehearse(capsys, trace_flag=0, seed=2_147_500_123):
    rc = run.main(["--workload", CELL, "--seed", str(seed), "--seconds", "0.2",
                   "--trace", str(trace_flag), "--rehearse"])
    out = capsys.readouterr()
    return rc, json.loads(out.out.strip().splitlines()[-1]), out.err


def test_configuration_states_the_deployment():
    cell = run.load_cell(CELL)
    config, traffic = cell["config_data"], cell["traffic_data"]
    entry = next(c for c in MANIFEST["configs"] if c["name"] == cell["config"])
    assert config["source"] == entry["source"] and len(entry["source"]) <= 200
    assert config["architecture"] is None and "16 nodes" in config["deployment"]
    # no width differs from the source; the scale alone is cut
    for key in ("num_features", "num_targets", "iterations"):
        assert config[key] == config["published"][key]
    assert config["lanes"] == round(config["published"]["sparsity"] * config["num_features"])
    assert config["reduced"] == ["rows"] == entry["reduced"]
    assert config["rows"] == traffic["rows"] == 64 * 65536
    assert abs(config["rows"] - config["published"]["rows"] / 16) / config["rows"] < 0.04
    assert {"lam", "distinct_ids", "popularity", "labels", "storage"} <= set(config["assumed"])
    assert "highest" in config["precision"] and "0/1" in config["precision"]
    assert config["selector"] == {}  # no budget, no engine, no cluster size named
    assert traffic["compare_fits"] == 2 and cell["chips"] == 1
    assert {m["name"] for m in cell["per_layer"]} == {
        "window_compiles", "fit_mfu_pct", "device_idle_pct", "gram_acc_roofline",
        "sparse_fold_other_pct", "sparse_fit_host_ms"}


def test_rows_are_distinct_zipf_ids_from_the_seed(toy):
    config = toy["cell"]["config_data"]
    _, idx, val, Y, probe_idx, _ = toy["problem"]
    idx = np.asarray(idx)
    assert idx.shape == (4096, config["lanes"]) and idx.dtype == np.int32
    assert (np.diff(idx, axis=1) > 0).all()  # ascending, so distinct
    assert idx.min() == 0 and idx.max() < config["num_features"]
    assert np.asarray(val).min() == np.asarray(val).max() == 1.0
    share = [(idx == j).any(axis=1).mean() for j in (0, 1, 500)]
    assert share[0] > share[1] > 0.3 and share[2] < 0.02  # a head and a tail
    Y = np.asarray(Y)
    assert set(np.unique(Y)) == {-1.0, 1.0} and (Y[:, 0] == -Y[:, 1]).all()
    again = driver.make_problem(toy["cell"], seed=2_147_500_123)
    assert (np.asarray(again[1]) == idx).all() and (np.asarray(again[3]) == Y).all()
    other = driver.make_problem(toy["cell"], seed=2_147_500_124)  # past 32 signed bits
    assert (np.asarray(other[1]) != idx).any()
    assert (np.asarray(probe_idx) != idx[:256]).any()


def test_a_row_short_of_distinct_ids_is_an_error(toy):
    import jax

    config = dict(toy["cell"]["config_data"], draws=12)
    with pytest.raises(RuntimeError, match="distinct ids"):
        driver.make_rows(jax.random.key(0), 256, config)


def sparse_candidates(cell, rows):
    """Device budgets under which the selector's argmin is each sparse
    candidate in turn: dense ones infeasible, the sparse ones cut one by one."""
    from keystone_tpu.ops.learning.cost import DEFAULT_HBM_UTILIZATION, LeastSquaresEstimator

    config = cell["config_data"]
    d, k = config["num_features"], config["num_targets"]
    sparsity = config["lanes"] / d
    need = {}
    for model, _ in LeastSquaresEstimator(lam=1e-4).options:
        if type(model).__name__ == "SparseLBFGSwithL2":
            need[(model.solver, model.compress)] = model.resident_bytes(rows, d, k, sparsity, 1)
    gather, gram, packed = need[("gather", None)], need[("gram", None)], need[("gram", "int16_bf16")]
    assert gather < packed < gram
    just_over = lambda nbytes: 1.001 * nbytes / DEFAULT_HBM_UTILIZATION
    return {"gather": just_over(gather), "gram,int16_bf16": just_over(packed),
            "gram": just_over(gram)}


@pytest.mark.parametrize("candidate", ["gather", "gram", "gram,int16_bf16"])
def test_program_through_the_public_entry_matches_the_reference(toy, candidate):
    """No engine is named: the budget makes the candidate the cost model's
    own choice, and the fit's span says which engine ran."""
    from keystone_tpu import obs
    from keystone_tpu.data import Dataset
    from keystone_tpu.ops.learning.cost import LeastSquaresEstimator
    from keystone_tpu.workflow import PipelineEnv

    _, idx, val, Y, probe_idx, probe_val = toy["problem"]
    budget = sparse_candidates(toy["cell"], idx.shape[0])[candidate]
    with obs.tracing() as tracer:
        PipelineEnv.get_or_create().reset()
        fitted = LeastSquaresEstimator(lam=toy["lam"], hbm_bytes=budget, num_machines=1).with_data(
            driver.sparse_dataset(idx, val), Dataset.of(Y)).fit()
    attrs = next(s["args"] for s in tracer.spans("estimator.fit"))
    engine, _, compress = candidate.partition(",")
    assert attrs["engine"] == engine and attrs.get("compress") == (compress or None)
    program = reference.score_gaps(driver.probe_scores(fitted, probe_idx, probe_val), toy["want"])
    assert driver.iterations_run(fitted) == [toy["iterations"]] == [20]
    assert max(program) < 2e-3, program
    assert toy["control"][0] > 5 * program[0] and toy["control"][1] > 5 * program[1]


def test_control_reads_over_the_limit_and_well_above_the_program(toy):
    """The reference in the program's place with its two products in one
    bf16 pass: on the other side of the chip's limits, and >= 5 x what the
    program reads through the cell's own entry."""
    cell, (_, idx, val, Y, probe_idx, probe_val) = toy["cell"], toy["problem"]
    fitted = driver.fit_once(cell["config_data"], toy["lam"], idx, val, Y)
    program = reference.score_gaps(driver.probe_scores(fitted, probe_idx, probe_val), toy["want"])
    for i, name in enumerate(("score_rel_fro", "score_widest")):
        assert toy["control"][i] > cell["limits"][name]["limit"]
        assert toy["control"][i] > 5 * program[i]


def test_reference_forms_no_gramian_and_imports_nothing_of_the_program():
    import inspect

    source = inspect.getsource(reference)
    assert "keystone_tpu" not in source.split('"""', 2)[2]
    # the (d + 1)^2 matrix never exists: every product has k columns
    import jax
    import jax.numpy as jnp

    idx = jnp.zeros((16, 3), jnp.int32)
    text = str(jax.make_jaxpr(lambda P: reference.xt_x_p(
        idx, jnp.ones((16, 3)), P, d=40, precision="highest", rows_per_block=8))(jnp.ones((41, 2))))
    assert "f32[41,41]" not in text and "f32[41,2]" in text


@pytest.mark.parametrize("fault", ["half_the_rows", "score_altered", "iteration_cap_zero"])
def test_a_broken_timed_path_comes_out_not_correct(capsys, monkeypatch, fault):
    if fault == "half_the_rows":  # the fit made on half of the batch
        sound = driver.build_pipeline
        monkeypatch.setattr(driver, "build_pipeline", lambda config, lam, idx, val, Y: sound(
            config, lam, idx[: idx.shape[0] // 2], val[: idx.shape[0] // 2], Y[: idx.shape[0] // 2]))
    elif fault == "score_altered":  # one target's score nudged where it is produced
        sound = driver.probe_scores

        def altered(fitted, probe_idx, probe_val):
            scores = np.array(sound(fitted, probe_idx, probe_val))
            scores[:, 1] *= np.float32(1.02)
            return scores

        monkeypatch.setattr(driver, "probe_scores", altered)
    else:  # the solver returns its state (W = 0) unchanged: the iteration cap cut to 0
        from keystone_tpu.ops.learning import lbfgs

        sound = lbfgs._solve_operands
        monkeypatch.setattr(lbfgs, "_solve_operands",
                            lambda lam, its, tol, n: sound(lam, 0, tol, n))
    rc, line, err = rehearse(capsys)
    assert rc == 0 and line["correct"] is False, err
    assert any(p["value"] > p["limit"] for p in line["compared"].values())


def test_sound_rehearsal_is_correct_and_reports_the_iterations(capsys):
    rc, line, err = rehearse(capsys, trace_flag=1)
    assert rc == 0 and line["correct"] is True and line["failed"] == 0, err
    assert line["compared"]["iterations_gap"] == {"value": 0, "limit": 1}
    assert line["metrics"]["window_compiles"] == {"value": 0, "unit": "count"}  # lam is an operand


def test_arith_sparse_against_hand_worked_counts():
    # 8 rows of 3 features (+ intercept = 4), 2 lanes (+1), 5 targets, chunks of 4
    flops, nbytes = arith_sparse.gram_fold_cost(8, 3, 2, 5, 4)
    assert flops == 8 * 4 * 4 + 2 * 8 * 4 * 5 == 448
    assert nbytes == 4 * 8 * 4 + 8 * 8 * 3 + 4 * 8 * 5 + 2 * 2 * 4 * (16 + 20) == 1056
    assert arith_sparse.gram_fold_cost(8, 3, 2, 5, 4, slab_itemsize=2)[1] == 1056 - 2 * 8 * 4
    assert arith_sparse.gram_lbfgs_fit_flops(8, 3, 2, 5, 2) == 8 * 16 + 2 * 8 * 3 * 5 + 3 * 2 * 16 * 5


def test_yardstick_the_accepted_reader_takes_is_within_a_twentieth_of_a_percent():
    config = run.load_cell(CELL)["config_data"]
    theirs = arith.FIT_FLOPS[config["fit_flops"]](
        config["rows"], config["d_in"], config["num_cosines"] * config["block_size"],
        config["num_classes"], config["block_size"], config["num_epochs"])
    ours = arith_sparse.gram_lbfgs_fit_flops(
        config["rows"], config["num_features"], config["lanes"], config["num_targets"],
        config["iterations"])
    assert config["num_cosines"] * config["block_size"] == config["num_features"]
    assert ours == pytest.approx(1.12606e15, rel=1e-5)
    assert 0 < theirs / ours - 1 < 5e-4
    # at 197 TFLOP/s that is 5.7 s: fit_mfu_pct cannot pass 100% above it
    assert theirs / arith.peaks("TPU v5 lite")["flops_per_s"] == pytest.approx(5.72, abs=0.01)


def reader_ctx(op_seconds, fits=2, rows=4 * 65536):
    config = run.load_cell(CELL)["config_data"]
    trace = None if op_seconds is None else {
        "op_seconds": op_seconds, "busy_s": sum(op_seconds.values()), "window_s": 10.0}
    return {"trace": trace, "config": config, "traffic": {}, "counters": {},
            "window": {"fits": fits, "window_s": 10.0, "rows": rows},
            "device_kind": "TPU v5 lite", "notes": []}


def test_device_readers_on_a_small_trace_and_with_nothing_to_read():
    roofline, other = run.load_reader("gram_acc_roofline"), run.load_reader("sparse_fold_other_pct")
    ops = {"gram_corr_sym_acc.3": 6.0, "gram_sym_acc.1": 2.0, "scatter.9": 1.5, "fusion.2": 0.5,
           "gram_corr_sym.6": 0.0}  # the resident kernel's name is another kernel
    ctx = reader_ctx(ops)
    flops, nbytes = arith_sparse.gram_fold_cost(2 * 4 * 65536, 16384, 82, 2, 65536)
    least = max(flops / 197e12, nbytes / 819e9)
    assert roofline.read(ctx) == pytest.approx(100 * least / 8.0)
    assert "bound by compute" in ctx["notes"][-1]
    assert other.read(ctx) == pytest.approx(100 * 2.0 / 10.0)
    xla = reader_ctx({"convolution.4": 9.0, "scatter.9": 1.0})  # the fold on XLA's dot
    assert roofline.read(xla) is None and "nothing to read" in xla["notes"][-1]
    assert other.read(xla) == 100.0
    for reader in (roofline, other):
        assert reader.read(reader_ctx(None)) is None  # no trace
    assert roofline.read(reader_ctx(ops, fits=0)) is None


def test_host_reader_on_a_small_session_and_with_nothing_to_read(monkeypatch):
    from benchmarks.layer_metrics import span_account

    reader = run.load_reader("sparse_fit_host_ms")
    ids = iter(range(1, 100))

    def span(name, start, dur, parent=None, **args):
        return {"type": "span", "name": name, "ts_us": start, "dur_us": dur,
                "span_id": next(ids), "parent_id": parent, "args": args}

    fit = span("pipeline.fit", 0, 10_000)
    est = span("estimator.fit", 1_000, 8_000, fit["span_id"], engine="gram", pallas=True)
    fold = span("solver.gram_fold", 1_500, 500, est["span_id"])
    solve = span("solver.lbfgs", 2_000, 6_500, est["span_id"], stage="read")
    drain = span("executor.drain", 2_100, 6_300, solve["span_id"], site="solver_loss")
    compile_ = span("jax.compile", 200, 300, fit["span_id"], stage="trace", fun="f")
    monkeypatch.setattr(span_account, "session_spans",
                        lambda: [fit, est, fold, solve, drain, compile_])
    ctx = reader_ctx({"x": 1.0}, fits=1)
    assert reader.read(ctx) == pytest.approx((10_000 - 6_300) / 1e3)  # the wait is not the host's
    note = ctx["notes"][-1]
    assert "'wait': 6.3" in note and "'solver.lbfgs': 6.5" in note and "'engine': 'gram'" in note
    monkeypatch.setattr(span_account, "session_spans", lambda: None)
    assert reader.read(reader_ctx({"x": 1.0}, fits=1)) is None  # a program with no session
    assert reader.read(reader_ctx(None)) is None


def test_control_readings_part_program_from_control_at_toy_size():
    """``benchmarks.control_sparse`` (the readings the limits are set from):
    one seed, both numbers, program under control on each of its two lams."""
    from benchmarks import control_sparse

    line = control_sparse.readings(run.load_cell(CELL, rehearse=True), seed=2_147_500_000,
                                   control=True)
    assert len(line["lams"]) == 2 and line["iterations"] == [[[20], 20], [[20], 20]]
    for program, control in zip(line["program"], line["bf16"]):
        assert program[0] < control[0] / 5 and program[1] < control[1] / 5
