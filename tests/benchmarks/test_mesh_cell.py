"""The four-chip cell's own tests (tier-1, CPU, 4 of the suite's 8 devices):
the configuration file, the shard-by-shard reference against
``reference/timit.py``, the cell through ``--rehearse``, the program through
the public entry against the reference with the one-bf16-pass control over
the limits, the faults the comparison has to catch, the two new readers,
and the mesh program compiled at the cell's own size for the described
``v5e:2x2`` (nothing runs; the one test here that describes a topology, in
a module fixture).
"""

import itertools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import run, trace
from benchmarks.drivers import fit_loop
from benchmarks.drivers import fit_loop_mesh as driver
from benchmarks.layer_metrics import span_account
from benchmarks.reference import timit as one_device_reference
from benchmarks.reference import timit_mesh as reference

CELL = "timit_stream_fit_4chip"
STREAM = "timit_stream_fit_1m"
MANIFEST = json.load(open(os.path.join(run.ROOT, "BENCHMARK.json")))
SEED = 2_147_500_123


@pytest.fixture(autouse=True)
def float32_mode():
    """The suite's conftest turns 64-bit mode on; the benchmark runs as its
    users do, without it."""
    with jax.enable_x64(False):
        yield


@pytest.fixture(scope="module")
def toy():
    """The rehearsal's cell on a mesh of four CPU devices, one seed's rows
    (sharded, and placed as the user's set-up places them), and the
    reference's scores and the one-pass control's for one ridge value."""
    with jax.enable_x64(False):
        cell = run.load_cell(CELL, rehearse=True)
        mesh = driver.make_mesh(cell["config_data"], jax.devices()[:4])
        _, X, Y, probe = driver.make_problem(cell, SEED, mesh)
        data, labels = driver.shard_once(X, Y, mesh)
        lam, shared = 1e-5, fit_loop.reference_args(cell["config_data"])
        want = reference.fit_and_score(X, Y, probe, [lam], **shared)[lam]
        lowered = reference.fit_and_score(X, Y, probe, [lam], precision="bf16", **shared)[lam]
        return {"cell": cell, "mesh": mesh, "X": X, "Y": Y, "probe": probe, "lam": lam,
                "data": data, "labels": labels, "want": want, "shared": shared,
                "control": reference.score_gaps(lowered, want)}


def rehearse(capsys, trace_flag=0, seed=SEED):
    rc = run.main(["--workload", CELL, "--seed", str(seed), "--seconds", "0.2",
                   "--trace", str(trace_flag), "--rehearse"])
    out = capsys.readouterr()
    return rc, json.loads(out.out.strip().splitlines()[-1]), out.err


def test_configuration_states_the_mesh_and_keeps_cell_ones_widths():
    cell, stream = run.load_cell(CELL), run.load_cell(STREAM)
    config, traffic = cell["config_data"], cell["traffic_data"]
    entry = next(c for c in MANIFEST["configs"] if c["name"] == cell["config"])
    assert config["source"] == entry["source"] and len(entry["source"]) <= 200
    assert "TimitPipeline.scala:37-130" in entry["source"] and "treeReduce" in entry["source"]
    assert config["architecture"] is None
    assert config["mesh"] == {"axes": ["data"], "shape": [4]} and cell["chips"] == 4
    assert "four-chip v5e host" in config["deployment"] and "1,048,576 a device" in config["deployment"]
    for key in ("d_in", "num_classes", "num_cosines", "block_size", "gamma", "rf_type",
                "num_epochs", "entry", "fit_flops", "bank_seed", "precision", "model", "reduced"):
        assert config[key] == stream["config_data"][key], key  # every width as cell 1
    assert {"lam", "rows", "bank"} <= set(config["assumed"]) and "1.86" in config["assumed"]["rows"]
    assert traffic["rows_per_device"] == stream["traffic_data"]["rows"] == 1 << 20
    assert traffic["lam_grid"] == stream["traffic_data"]["lam_grid"]
    assert traffic["compare_fits"] == 2 and traffic["driver"] == "fit_loop_mesh"
    assert traffic["probe_rows"] == 4096
    for name in ("score_rel_fro", "score_widest"):  # cell 1's limits
        assert cell["limits"][name]["limit"] == stream["limits"][name]["limit"]
    assert {m["name"] for m in cell["per_layer"]} >= {
        "window_compiles", "fit_mfu_pct", "device_idle_pct", "allreduce_ms", "mesh_fit_host_ms"}
    four_chip = [w["name"] for w in MANIFEST["workloads"] if w["chips"] == 4]
    assert four_chip == [CELL] and len(four_chip) <= max(1, len(MANIFEST["workloads"]) // 4)


def test_every_device_makes_its_own_rows_and_the_set_up_moves_none(toy):
    from keystone_tpu import obs

    X, Y, mesh = toy["X"], toy["Y"], toy["mesh"]
    rows_local = toy["cell"]["traffic_data"]["rows_per_device"]
    assert X.shape == (4 * rows_local, 440) and Y.shape == (4 * rows_local, 147)
    assert [s.device for s in X.addressable_shards] == list(mesh.devices.flat)
    blocks = [np.asarray(s.data) for s in X.addressable_shards]
    assert all(b.shape == (rows_local, 440) for b in blocks)
    assert not np.array_equal(blocks[0], blocks[1])  # a draw of its own on every device
    with obs.tracing() as tracer, jax.transfer_guard("disallow"):
        data, labels = driver.shard_once(X, Y, mesh)
    assert data.array is X and labels.array is Y
    assert [s["args"]["moved"] for s in tracer.spans("data.shard")] == ["none", "none"]


def test_reference_over_the_shards_equals_the_one_device_reference(toy):
    """The same algorithm told where the rows lie: equal to
    ``reference/timit.py`` on the same rows to 1e-5, over four devices and
    where the rows lie on one; it imports nothing of the program."""
    import inspect

    lams = [1e-5, 1e-3]
    X, Y = jnp.asarray(np.asarray(toy["X"])), jnp.asarray(np.asarray(toy["Y"]))  # on one device
    theirs = one_device_reference.fit_and_score(X, Y, toy["probe"], lams, **toy["shared"])
    ours = reference.fit_and_score(toy["X"], toy["Y"], toy["probe"], lams,
                                   rows_per_block=128, **toy["shared"])
    alone = reference.fit_and_score(X, Y, toy["probe"], lams, **toy["shared"])
    for lam in lams:
        assert max(reference.score_gaps(ours[lam], theirs[lam])) < 1e-5
        assert max(reference.score_gaps(alone[lam], theirs[lam])) < 1e-5
    assert "keystone_tpu" not in inspect.getsource(reference).split('"""', 2)[2]
    with pytest.raises(ValueError, match="same devices"):
        reference.row_shards(toy["X"], Y[: toy["X"].shape[0]])


def test_program_within_the_limits_and_the_control_over_them(toy):
    """Through the cell's own entry over the sharded datasets: the mesh fit
    says so on its spans, and the one-bf16-pass control reads five times
    the program or more, on the other side of cell 1's limits."""
    from keystone_tpu import obs

    cell = toy["cell"]
    with obs.tracing() as tracer:
        fitted = driver.fit_once(cell["config_data"], toy["lam"], toy["data"], toy["labels"],
                                 driver.fence_token(toy["mesh"]))
    attrs = next(s["args"] for s in tracer.spans("estimator.fit"))
    assert attrs["engine"] == "stream_mesh" and attrs["devices"] == 4
    assert next(s["args"] for s in tracer.spans("solver.stream_fit"))["mesh_shape"] == (4,)
    program = reference.score_gaps(fit_loop.probe_scores(fitted, toy["probe"]), toy["want"])
    for i, name in enumerate(("score_rel_fro", "score_widest")):
        assert program[i] < cell["limits"][name]["limit"] < toy["control"][i]
        assert toy["control"][i] > 5 * program[i]


def _new_program(monkeypatch, fault):
    """Key the fit program anew, so that a fault planted in what it traces is
    traced — and so that no later test finds the faulty program in the cache."""
    from keystone_tpu.ops.learning import streaming_ls

    sound_key = streaming_ls.CosineBankFeaturize.static_key
    monkeypatch.setattr(streaming_ls.CosineBankFeaturize, "static_key",
                        lambda self: sound_key(self) + (fault,))


@pytest.mark.parametrize("fault", ["one_shard_left_out", "a_devices_own_mean", "one_epoch_fewer"])
def test_a_broken_timed_path_comes_out_not_correct(capsys, monkeypatch, fault):
    """The rest of a run with the mesh fit broken underneath."""
    from keystone_tpu.parallel import streaming

    if fault == "one_shard_left_out":  # the last device's rows never reach the fold
        sound = streaming.streaming_bcd_fit_centered

        def short(X, Y, **kw):
            shards = kw["mesh"].devices.size
            return sound(X, Y, **{**kw, "valid": X.shape[0] - X.shape[0] // shards})

        monkeypatch.setattr(streaming, "streaming_bcd_fit_centered", short)
    elif fault == "a_devices_own_mean":  # centred with the first device's column sums,
        sound_stats = streaming.gram_stats_mesh  # as if no all-reduce had added the others'

        def own_sums(X, Y, featurize, d_feat, tile_rows, mesh, **kw):
            G, FY, yty, _, _ = sound_stats(X, Y, featurize, d_feat, tile_rows, mesh, **kw)
            shards = mesh.devices.size
            local = X.shape[0] // shards
            *_, fsum, ysum = streaming.gram_stats(X[:local], Y[:local], featurize, d_feat,
                                                  tile_rows, moments=True)
            return G, FY, yty, shards * fsum, shards * ysum

        monkeypatch.setattr(streaming, "gram_stats_mesh", own_sums)
        _new_program(monkeypatch, fault)
    else:
        sound_build = fit_loop.build_pipeline
        monkeypatch.setattr(fit_loop, "build_pipeline", lambda config, *rest: sound_build(
            dict(config, num_epochs=config["num_epochs"] - 1), *rest))
    rc, line, err = rehearse(capsys)
    assert rc == 0 and line["correct"] is False, err
    over = {name for name, p in line["compared"].items() if p["value"] > p["limit"]}
    assert over & {"score_rel_fro", "score_widest"}, line["compared"]


def test_sound_rehearsal_is_correct_and_compiles_nothing_in_the_window(capsys):
    rc, line, err = rehearse(capsys, trace_flag=1)
    assert rc == 0 and line["correct"] is True and line["failed"] == 0, err
    assert list(line)[-1] == "compared" and line["device"]["rehearsal"] is True
    assert line["metrics"] == {"window_compiles": {"value": 0, "unit": "count"}}  # no device metric
    assert "on 4 devices" in err and "share of the peak of all 4 chips" in err


def test_allreduce_ms_on_a_small_trace_and_with_nothing_to_read():
    """Two all-reduce operations a fit on each of two device planes: the
    Gramian's, which keeps ``psum``'s name, inside a ``while`` whose self time
    is its own, and the small sums'."""
    reader = run.load_reader("allreduce_ms")
    config = run.load_cell(CELL)["config_data"]
    chip = [("while.1", 0.0, 10e6), ("convolution_add_fusion.3", 1e6, 8e6),
            ("psum.35", 20e6, 3e6), ("all-reduce", 23e6, 1e6), ("fusion.9", 25e6, 1e6)]
    late = [(name, start + 2e6, dur) for name, start, dur in chip]
    summary = trace.reduce_events([chip, late], [("bench.fit", 0.0, 40e6)])

    def ctx(summary, fits=2):
        return {"trace": summary, "config": config, "notes": [], "device_kind": "TPU v5 lite",
                "window": {"fits": fits, "window_s": 0.04, "rows": 1 << 20}}

    found = ctx(summary)
    assert reader.read(found) == pytest.approx((3.0 + 1.0) / 2)  # ms a fit, the planes' mean
    nbytes = 4 * (16384 * 16384 + 16384 * 147 + 16384 + 147 + 1)
    assert reader.round_bytes(config) == nbytes == 1_083_441_744
    assert f"{nbytes} bytes" in found["notes"][-1] and "['all-reduce', 'psum.35']" in found["notes"][-1]
    gbps = 2 * 3 / 4 * nbytes / 2e-3 / 1e9
    assert f"{gbps:.1f} GB/s" in found["notes"][-1]
    none = trace.reduce_events([[e for e in chip if e[0] in ("while.1", "fusion.9")]], [])
    assert reader.read(ctx(none)) is None and reader.read(ctx(None)) is None
    assert reader.read(ctx(summary, fits=0)) is None


T0 = 1_700_000_000_000_000


def _session(fits=2, sound=True):
    """Hand-written spans of ``fits`` mesh fits: a build of 300 us, a fit of
    2,000 with the estimator's span (1,500), the one dispatch (200) and the
    traced run's barrier (1,000). Unsound: each fit also shards its rows
    (400) and traces its program again (100 inside the dispatch)."""
    ids, spans = itertools.count(1), []

    def span(name, start, dur, parent=None, **args):
        spans.append({"type": "span", "name": name, "ts_us": T0 + start, "dur_us": dur,
                      "span_id": next(ids), "parent_id": parent, "args": args})
        return spans[-1]["span_id"]

    for i in range(fits):
        at = i * 30_000
        span("pipeline.build", at, 300, entry="streaming", branches=4)
        fit = span("pipeline.fit", at + 1_000, 2_000)
        if not sound:
            span("data.shard", at + 1_050, 400, fit, devices=4, bytes=10**9, moved="host")
        est = span("estimator.fit", at + 1_500, 1_500, fit, engine="stream_mesh", devices=4,
                   estimator="StreamingFeaturizedLeastSquares", psum_bytes=1_083_441_744)
        stream = span("solver.stream_fit", at + 1_550, 200, est, rows=1 << 22, tile_rows=32768,
                      mesh_shape=(4,), rows_local=1 << 20)
        if not sound:
            span("jax.compile", at + 1_600, 100, stream, stage="trace", fun="_streaming_fit_bank")
        span("executor.drain", at + 1_800, 1_000, est, site="estimator_sync")
    # what the benchmark traces after the window, in the same session: under no fit
    span("jax.compile", fits * 30_000, 500, None, stage="trace", fun="streaming_predict")
    return spans


def test_mesh_fit_host_ms_on_a_small_session_and_with_nothing_to_read(monkeypatch):
    reader = run.load_reader("mesh_fit_host_ms")

    def ctx(traced=True):
        return {"trace": {"window_s": 1.0} if traced else None, "notes": [], "config": {},
                "traffic": {}, "counters": {}, "device_kind": "TPU v5 lite",
                "window": {"fits": 2, "window_s": 1.0}}

    monkeypatch.setattr(span_account, "session_spans", lambda: _session())
    found = ctx()
    assert reader.read(found) == pytest.approx((300 + 2_000 - 1_000) / 1e3)  # the wait left out
    note = found["notes"][-1]
    assert "traces a fit 0.0" in note and "by stage none" in note and "data.shard spans under the fits none" in note
    assert "'engine': 'stream_mesh'" in note and "'rows_local': 1048576" in note
    monkeypatch.setattr(span_account, "session_spans", lambda: _session(sound=False))
    found = ctx()
    assert reader.read(found) == pytest.approx(1.3)  # the same host time, spent otherwise
    note = found["notes"][-1]
    assert "traces a fit 1.0" in note and "{'trace': 2}" in note and "'moved': 'host'" in note
    monkeypatch.setattr(span_account, "session_spans", lambda: None)  # a program with no session
    assert reader.read(ctx()) is None and reader.read(ctx(traced=False)) is None


@pytest.fixture(scope="module")
def v5e_2x2():
    """The four chips of a described v5e host, to compile for (nothing runs)."""
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return jax.sharding.Mesh(np.array(topo.devices).reshape(4), ("data",))


def test_the_mesh_program_compiles_for_the_four_chip_host_at_the_cells_size(
        v5e_2x2, compile_for_chip, monkeypatch):
    """``_streaming_fit_bank``'s body at 4 x 1,048,576 rows over the described
    ``v5e:2x2``: the Mosaic featurize kernel inside the ``shard_map``, the
    psum round as all-reduces, and what a device holds beside its rows —
    the one-device program of cell 1 reads the same 7.57 GB of temporaries
    (PERF.md section 6, PR 35), so the mesh form adds no buffer of the
    Gramian's size to it."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from keystone_tpu.ops import pallas_ops
    from keystone_tpu.ops.learning.streaming_ls import CosineBankFeaturize
    from keystone_tpu.parallel import streaming

    monkeypatch.setattr(pallas_ops, "_interpret", lambda: False)  # else the kernel is interpreted
    config, traffic = run.load_cell(CELL)["config_data"], run.load_cell(CELL)["traffic_data"]
    d, k, d_in = config["num_cosines"] * config["block_size"], config["num_classes"], config["d_in"]
    n = 4 * traffic["rows_per_device"]
    rows, everywhere = NamedSharding(v5e_2x2, P("data")), NamedSharding(v5e_2x2, P())

    def fit(X, Y, Wrf, brf, lam):
        return streaming._fit_core(
            X, Y, lambda X_t: CosineBankFeaturize.apply_bank(("float32", True), (Wrf, brf), X_t),
            d, streaming.pick_tile_rows(d, 4), config["block_size"], lam, config["num_epochs"],
            False, None, None, True, v5e_2x2)

    shape = jax.ShapeDtypeStruct
    compiled = compile_for_chip(
        fit, shape((n, d_in), jnp.float32, sharding=rows), shape((n, k), jnp.float32, sharding=rows),
        shape((d, d_in), jnp.float32, sharding=everywhere), shape((d,), jnp.float32, sharding=everywhere),
        shape((), jnp.float32, sharding=everywhere))
    text, memory = compiled.as_text(), compiled.memory_analysis()
    assert "tpu_custom_call" in text and "ks.gram_psum" in text and "all-reduce(" in text
    assert memory.argument_size_in_bytes < 2.6e9  # a device's own rows, targets and the bank
    assert memory.temp_size_in_bytes < 8e9
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 11e9  # of 16.9
