"""The benchmark's own tests (tier-1, CPU): the manifest and the files it
names, the arithmetic, the trace reduction on a small hand-built trace, a
toy-size rehearsal of each cell, and the comparison that decides
``correct`` — its control and the faults it has to catch.

Nothing here describes a TPU topology or touches a chip.
"""

import importlib
import json
import os

import numpy as np
import pytest

from benchmarks import arith, run, trace
from benchmarks.drivers import fit_loop
from benchmarks.reference import timit as reference

ROOT = run.ROOT
MANIFEST = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELLS = [w["name"] for w in MANIFEST["workloads"]]
STREAM = "timit_stream_fit_1m"


@pytest.fixture(autouse=True)
def float32_mode():
    """The suite's conftest turns 64-bit mode on; the benchmark runs as its
    users do, without it (in 64-bit mode the program draws its bank in
    float64, which is another bank)."""
    import jax

    with jax.enable_x64(False):
        yield


def rehearse(capsys, cell, trace_flag=0, seed=2_147_500_123):
    rc = run.main(["--workload", cell, "--seed", str(seed), "--seconds", "0.2",
                   "--trace", str(trace_flag), "--rehearse"])
    out = capsys.readouterr()
    return rc, json.loads(out.out.strip().splitlines()[-1]), out.err


@pytest.mark.parametrize("cell", CELLS)
def test_cell_names_files_that_exist_and_agree(cell):
    loaded = run.load_cell(cell)
    entry = next(c for c in MANIFEST["configs"] if c["name"] == loaded["config"])
    assert entry["file"].startswith(tuple(p + "/" for p in MANIFEST["paths"]))
    assert loaded["config_data"]["name"] == entry["name"]
    assert sorted(loaded["config_data"]["reduced"]) == sorted(entry["reduced"])
    for key in entry["reduced"]:  # a reduced key differs from the published value
        assert loaded["config_data"][key] != loaded["config_data"]["published"][key]
    assert loaded["config_data"]["fit_flops"] in arith.FIT_FLOPS
    importlib.import_module("benchmarks.drivers." + loaded["traffic_data"]["driver"])
    end_to_end = {m["name"] for m in loaded["end_to_end"]}
    assert {"setup_s", "fit_s"} <= end_to_end
    assert loaded["per_layer"], "every cell reports a per-layer metric"
    for metric in loaded["per_layer"]:
        assert callable(run.load_reader(metric["name"]).read)
        assert metric["moves"] in end_to_end
    for number in (v for v in loaded["limits"].values() if isinstance(v, dict)):
        assert number["lower"] < number["limit"] < number["upper"]
        assert number["upper"] >= 3 * number["lower"]


def test_arith_against_hand_worked_counts():
    # 8 rows, 3 inputs, 4 features in blocks of 2, 5 classes, 2 epochs
    flops, nbytes = arith.cosine_features_cost(8, 3, 4)
    assert flops == 2 * 8 * 3 * 4 == 192
    assert nbytes == 4 * (8 * 3 + 4 * 3 + 4 + 8 * 4) == 288
    solves = 2 * 2 ** 3 / 3 + 2 * 2 * 2 * 2 * 2 * 5  # factor once, two solves a step
    gram = 192 + 8 * 4 * 4 + 2 * 8 * 4 * 5 + 2 * 2 * 2 * 4 * 2 * 5 + solves
    assert arith.gram_bcd_fit_flops(8, 3, 4, 5, 2, 2) == pytest.approx(gram)
    block = 192 + 2 * 8 * 2 * 2 + 2 * 2 * 4 * 8 * 2 * 5 + solves
    assert arith.block_bcd_fit_flops(8, 3, 4, 5, 2, 2) == pytest.approx(block)
    peak = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert arith.least_seconds(1000.0, 50.0, peak) == (10.0, "compute")
    assert arith.least_seconds(100.0, 50.0, peak) == (5.0, "memory")


def test_unknown_device_kind_is_an_error():
    assert arith.peaks("TPU v5 lite")["flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="no peaks for device kind"):
        arith.peaks("TPU v99")


def test_trace_reduction_on_the_small_trace():
    fx = json.load(open(os.path.join(run.HERE, "fixtures", "small_trace.json")))
    chips = [[tuple(e) for e in chip] for chip in fx["device_events"]]
    spans = [tuple(s) for s in fx["spans"]]
    out, want = trace.reduce_events(chips, spans), fx["expect"]
    assert out["window_s"] * 1e9 == pytest.approx(want["window_ns"])
    assert out["busy_s"] * 1e9 == pytest.approx(want["busy_ns"])  # nested ops counted once
    got_self = {k: v * 1e9 for k, v in out["op_seconds"].items()}
    assert got_self == pytest.approx(want["self_ns"])
    gaps = [[label, round(s * 1e9)] for label, s in out["idle_gaps"]]
    assert gaps == want["idle_gaps"]  # each under the host span that covers it
    assert trace.short_name("%cosine_features.4 = f32[8,4]{1,0} custom-call(x)") == "cosine_features.4"


@pytest.mark.parametrize("cell,trace_flag", [(c, 0) for c in CELLS] + [(STREAM, 1)])
def test_rehearsal_prints_a_wellformed_line_and_no_device_metric(capsys, cell, trace_flag):
    rc, line, _ = rehearse(capsys, cell, trace_flag)
    assert rc == 0
    assert list(line)[-1] == "compared" and {"correct", "attempted", "failed",
                                             "metrics", "device"} <= set(line)
    assert line["device"]["rehearsal"] is True and line["attempted"] >= 1
    device_metrics = {m["name"] for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]
                      if m["source"] in run.DEVICE_SOURCES}
    assert not device_metrics & set(line["metrics"])
    if trace_flag:
        assert line["metrics"]["window_compiles"] == {"value": 0, "unit": "count"}
    if cell == STREAM:  # at toy size ``auto`` picks another solver than the cell's
        assert line["correct"] is True and line["failed"] == 0, line


def test_without_an_accelerator_there_is_no_result(capsys):
    rc = run.main(["--workload", STREAM, "--seed", "1", "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc == 2 and out.out == ""


def test_control_reads_above_the_program():
    """The reference in the program's place, in one bf16 pass, reads well
    above what the program reads, and on the other side of the limit."""
    cell = run.load_cell(STREAM, rehearse=True)
    _, X, Y, probe = fit_loop.make_problem(cell, seed=5)
    shared, lam = fit_loop.reference_args(cell["config_data"]), 1e-4
    want = reference.fit_and_score(X, Y, probe, [lam], **shared)[lam]
    fitted = fit_loop.fit_once(cell["config_data"], lam, X, Y)
    program = reference.score_gaps(fit_loop.probe_scores(fitted, probe), want)
    lowered = reference.fit_and_score(X, Y, probe, [lam], precision="bf16", **shared)[lam]
    control = reference.score_gaps(lowered, want)
    assert control[0] > 5 * program[0] and control[1] > 5 * program[1]
    for i, name in enumerate(("score_rel_fro", "score_widest")):  # the chip's limits part them
        assert program[i] < cell["limits"][name]["limit"] < control[i]


@pytest.mark.parametrize("fault", ["half_the_rows", "answer_altered", "control_precision",
                                   "state_unchanged", "fit_never_comes"])
def test_a_broken_timed_path_comes_out_not_correct(capsys, monkeypatch, fault):
    """The rest of a run with the timed path broken underneath."""
    if fault == "half_the_rows":  # half of the batch left out, the fit made on the rest
        sound = fit_loop.build_pipeline
        monkeypatch.setattr(fit_loop, "build_pipeline", lambda config, lam, X, Y:
                            sound(config, lam, X[: X.shape[0] // 2], Y[: Y.shape[0] // 2]))
    elif fault == "answer_altered":  # one class's score nudged where it is produced
        sound = fit_loop.probe_scores

        def altered(fitted, probe):
            scores = np.array(sound(fitted, probe))
            scores[:, 3] *= np.float32(1.01)
            return scores

        monkeypatch.setattr(fit_loop, "probe_scores", altered)
    elif fault == "fit_never_comes":  # every fit after the warm-up raises
        sound, calls = fit_loop.build_pipeline, []

        def raises(*args):
            calls.append(1)
            if len(calls) > 1:
                raise RuntimeError("the fit was lost")
            return sound(*args)

        monkeypatch.setattr(fit_loop, "build_pipeline", raises)
    else:  # the reference put in the program's place: as the control, in one bf16
        # pass, or with a solve that returns its state (W = 0) unchanged
        changed = {"precision": "bf16"} if fault == "control_precision" else {"epochs": 0}

        def lowered(fitted, probe):
            config, lam, X, Y = fitted
            return np.asarray(reference.fit_and_score(
                X, Y, probe, [lam], **{**fit_loop.reference_args(config), **changed})[lam])

        monkeypatch.setattr(fit_loop, "fit_once", lambda *args: args)
        monkeypatch.setattr(fit_loop, "probe_scores", lowered)
        monkeypatch.setattr(fit_loop, "program_classes", lambda fitted: ["control"])
    rc, line, err = rehearse(capsys, STREAM)
    assert rc == 0 and line["correct"] is False, err
    assert any(p["value"] > p["limit"] for p in line["compared"].values())
