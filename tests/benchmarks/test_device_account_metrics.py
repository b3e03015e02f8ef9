"""The five per-layer metrics that read the program's device account (PR 37),
each against a hand-written account: values, notes, the cross-check with the
harness's own total, and that a program whose session holds no account gives
nothing and does not raise."""

import json
import os

import pytest

from benchmarks import run
from benchmarks.layer_metrics import device_account

READERS = ("featurize_device_ms", "gram_device_ms", "solve_device_ms", "device_unscoped_pct",
           "idle_in_program_ms")
MS = 1e6  # the account is in nanoseconds
CELLS = ("timit_stream_fit_1m", "timit_resident_fit_40k", "amazon_lbfgs_fit_4m",
         "timit_block_fit_131k", "timit_stream_fit_4chip")


def plane(device, scopes_ms, idle_ms, unscoped_ops_ms=None):
    return {"device": device, "busy_ns": sum(scopes_ms.values()) * MS,
            "idle_ns": sum(idle_ms.values()) * MS,
            "by_scope_ns": {k: v * MS for k, v in scopes_ms.items()},
            "by_program_ns": {"jit__streaming_fit_bank": sum(scopes_ms.values()) * MS},
            "unscoped_ops_ns": {k: v * MS for k, v in (unscoped_ops_ms or {}).items()},
            "idle_ns_by_span": {k: v * MS for k, v in idle_ms.items()}}


def mesh_account():
    """Two fits on two device planes: the second folds a little longer and
    waits less in the all-reduce."""
    return {
        "extent_ns": 2200 * MS, "longest_gaps": [],
        "clock": {"roots": 4, "offset_ns": -1, "spread_ns": 3000},
        "planes": [
            plane("/device:TPU:0",
                  {"ks.gram_fold": 1800, "ks.featurize": 120, "ks.bcd": 40, "ks.gram_psum": 30,
                   "unscoped": 10},
                  {"outside": 90, "ks.pipeline.fit": 6, "ks.solver.stream_fit": 4},
                  {"jit__streaming_fit_bank/copy.7": 6, "jit__fence/add.1": 4}),
            plane("/device:TPU:1",
                  {"ks.gram_fold": 1820, "ks.featurize": 120, "ks.bcd": 40, "ks.gram_psum": 10,
                   "unscoped": 10},
                  {"outside": 92, "ks.pipeline.fit": 8},
                  {"jit__streaming_fit_bank/copy.7": 10}),
        ],
    }


def ctx_for(fits=2, op_seconds=None):
    return {"trace": {"window_s": 2.2, "op_seconds": op_seconds or {"all": 2.0}},
            "window": {"fits": fits, "window_s": 2.2}, "notes": [], "config": {},
            "traffic": {}, "counters": {}, "device_kind": "TPU v5 lite"}


@pytest.fixture
def session(monkeypatch):
    """Hands the readers an account in the place of the program's session's."""
    def install(found):
        monkeypatch.setattr(device_account, "session_account", lambda: found)
    return install


def read(name, ctx):
    return run.load_reader(name).read(ctx)


def test_the_phase_metrics_are_per_fit_and_the_mean_over_the_planes(session):
    session(mesh_account())
    ctx = ctx_for()
    assert read("featurize_device_ms", ctx) == pytest.approx(60.0)
    assert read("gram_device_ms", ctx) == pytest.approx(905.0)  # (900 + 910) / 2; the psum not in it
    assert read("solve_device_ms", ctx) == pytest.approx(20.0)
    notes = "\n".join(ctx["notes"])
    assert "gram_device_ms: 905.000 (planes 900.000 – 910.000) ms a fit and device" in notes
    assert "ks.gram_fold 905.000 (planes 900.000 – 910.000)" in notes
    # the collective's time a plane apart: the skew between the devices
    assert "not in the sum: ks.gram_psum 10.000 (planes 5.000 – 15.000)" in notes


def test_the_sparse_and_block_scopes_add_up_and_stand_apart_in_the_note(session):
    session({"extent_ns": 9000 * MS, "longest_gaps": [], "clock": None, "planes": [plane(
        "/device:TPU:0", {"ks.sparse_gram_acc": 7860, "ks.sparse_densify": 227,
                          "ks.lbfgs_gram": 100, "unscoped": 20}, {"outside": 50})]})
    ctx = ctx_for(fits=1, op_seconds={"all": 8.207})
    assert read("gram_device_ms", ctx) == pytest.approx(8087.0)
    assert read("solve_device_ms", ctx) == pytest.approx(100.0)
    assert read("featurize_device_ms", ctx) is None  # the sparse cell featurizes nothing
    notes = "\n".join(ctx["notes"])
    assert "ks.sparse_densify 227.000" in notes and "ks.sparse_gram_acc 7860.000" in notes
    assert "featurize_device_ms: the account holds none of" in notes


def test_the_resident_solvers_data_movement_stands_beside_the_solve(session):
    session({"extent_ns": 400 * MS, "longest_gaps": [], "clock": None, "planes": [plane(
        "/device:TPU:0", {"ks.gram_corr_fold": 124, "ks.bcd_step": 52, "ks.featurize": 53,
                          "ks.center": 12, "ks.split": 8, "ks.stack": 8, "unscoped": 12},
        {"outside": 50})]})
    ctx = ctx_for(fits=1, op_seconds={"all": 0.269})
    assert read("solve_device_ms", ctx) == pytest.approx(52.0)
    (note,) = [n for n in ctx["notes"] if n.startswith("solve_device_ms")]
    assert "not in the sum: ks.center 12.000, ks.split 8.000, ks.stack 8.000" in note


def test_the_block_tiers_factor_is_the_solves_and_not_the_gramians(session):
    """``ks.block_gram`` is the panels alone: centring, mirror, factor and the
    stash's writes (``ks.block_factor``) move the solve's metric."""
    session({"extent_ns": 12000 * MS, "longest_gaps": [], "clock": None, "planes": [plane(
        "/device:TPU:0", {"ks.block_featurize": 4584, "ks.block_gram": 3788,
                          "ks.block_factor": 142, "ks.block_update": 3030, "unscoped": 63},
        {"outside": 37})]})
    ctx = ctx_for(fits=1, op_seconds={"all": 11.607})
    assert read("gram_device_ms", ctx) == pytest.approx(3788.0)
    assert read("solve_device_ms", ctx) == pytest.approx(3172.0)
    (note,) = [n for n in ctx["notes"] if n.startswith("solve_device_ms")]
    assert "ks.block_factor 142.000, ks.block_update 3030.000" in note


def test_the_unscoped_share_and_its_largest_operations(session):
    session(mesh_account())
    ctx = ctx_for()
    assert read("device_unscoped_pct", ctx) == pytest.approx(100.0 * 20 / 4000)
    (note,) = [n for n in ctx["notes"] if n.startswith("device_unscoped_pct")]
    # per fit and device: copy.7 (6 + 10) / 2 planes / 2 fits, the fence's add 4 / 2 / 2
    assert "jit__streaming_fit_bank/copy.7 4.000, jit__fence/add.1 1.000" in note


def test_idle_in_program_is_what_a_program_span_covers(session):
    session(mesh_account())
    ctx = ctx_for()
    assert read("idle_in_program_ms", ctx) == pytest.approx((10 + 8) / 2 / 2)
    (note,) = [n for n in ctx["notes"] if n.startswith("idle_in_program_ms")]
    assert "4.500 (planes 4.000 – 5.000) ms a fit and device idle under a program span" in note
    assert "45.500 (planes 45.000 – 46.000) under none" in note
    assert "ks.pipeline.fit 3.500, ks.solver.stream_fit 1.000" in note


def test_the_totals_agree_or_a_note_says_by_how_much(session):
    session(mesh_account())
    ctx = ctx_for(op_seconds={"fusion.1": 1.9, "cosine_features.4": 0.1})  # 2.000 s a plane
    read("gram_device_ms", ctx)
    (note,) = [n for n in ctx["notes"] if n.startswith("device account")]
    assert "2 plane(s), 2.0000 s of self time a plane against the harness's 2.0000" in note
    assert "PART" not in note and "spread over 4 roots is 3.0 us" in note
    ctx = ctx_for(op_seconds={"fusion.1": 1.9})  # 5% short of the account's
    read("gram_device_ms", ctx)
    (note,) = [n for n in ctx["notes"] if n.startswith("device account")]
    assert "the totals PART by +5.26%" in note


@pytest.mark.parametrize("name", READERS)
def test_without_an_account_a_reader_gives_none_and_a_note(session, name):
    session(None)
    ctx = ctx_for()
    assert read(name, ctx) is None
    assert ctx["notes"] == ["device account: the program's session holds none"]
    assert read(name, dict(ctx_for(), trace=None)) is None  # an untraced run


@pytest.mark.parametrize("name", READERS)
def test_a_program_without_the_attribute_is_read_as_no_account(monkeypatch, name):
    """The parent of PR 37: a session, and no ``device_account`` on it."""
    from keystone_tpu import obs

    class OldSession:
        pass

    monkeypatch.setattr(obs, "last_session", lambda: OldSession())
    assert read(name, ctx_for()) is None
    monkeypatch.delattr(obs, "last_session")
    assert read(name, ctx_for()) is None


def test_the_manifest_lists_the_five_with_their_cells():
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entries = {m["name"]: m for m in manifest["per_layer"] if m["name"] in READERS}
    assert sorted(entries) == sorted(READERS)
    for name, m in entries.items():
        assert m["source"] == "device_trace" and m["moves"] == "fit_s"
        assert os.path.exists(os.path.join(run.HERE, "layer_metrics", name + ".py"))
        want = [c for c in CELLS if not (name == "featurize_device_ms" and c == "amazon_lbfgs_fit_4m")]
        assert m["workloads"] == want
    assert entries["device_unscoped_pct"]["better"] == "lower"
    order = [m["name"] for m in manifest["per_layer"] if m["name"] in READERS]
    assert order == list(READERS)  # among themselves; whatever a later PR adds around them


def _in_order(wanted, names):
    """``wanted`` are all among ``names``, in the order they have there."""
    return [n for n in names if n in wanted] == list(wanted)


@pytest.mark.parametrize("cell", CELLS)
def test_a_cell_keeps_the_metrics_it_had_in_the_order_they_had(cell):
    """Every per-layer metric a cell had before PR 37 is still its own and
    in its old order, and the five come after them in theirs. A subset and a
    relative order: a later PR's metrics may stand anywhere among them."""
    had = {
        "timit_stream_fit_1m": ["window_compiles", "fit_mfu_pct", "cosine_features_roofline",
                                "device_idle_pct", "fit_retrace_ms", "retraces_per_fit",
                                "device_wait_ms", "executor_self_ms", "solver_host_ms"],
        "timit_resident_fit_40k": ["window_compiles", "fit_mfu_pct", "device_idle_pct",
                                   "fit_retrace_ms", "retraces_per_fit", "device_wait_ms",
                                   "executor_self_ms", "solver_host_ms"],
        "amazon_lbfgs_fit_4m": ["window_compiles", "fit_mfu_pct", "device_idle_pct",
                                "gram_acc_roofline", "sparse_fold_other_pct",
                                "sparse_fit_host_ms"],
        "timit_block_fit_131k": ["window_compiles", "fit_mfu_pct", "device_idle_pct",
                                 "block_first_epoch_ms", "block_later_epoch_ms",
                                 "block_fit_host_ms", "block_featurize_roofline"],
        "timit_stream_fit_4chip": ["window_compiles", "fit_mfu_pct", "device_idle_pct",
                                   "allreduce_ms", "mesh_fit_host_ms"],
    }[cell]
    names = [m["name"] for m in run.load_cell(cell)["per_layer"]]
    new = [r for r in READERS
           if not (r == "featurize_device_ms" and cell == "amazon_lbfgs_fit_4m")]
    assert _in_order(had + new, names)
