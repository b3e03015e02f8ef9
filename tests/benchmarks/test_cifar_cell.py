"""The image cell's own tests (tier-1, CPU): the configuration file and its
yardstick, the reference against the program at rehearsal size, the
one-bf16-pass control, the faults the comparison has to catch, the
rehearsal of both cells this configuration's PR added, the three readers,
and the featurize and solve programs compiled for a described v5e at the
cell's own shape (nothing runs; the topology is described in a module
fixture)."""

import itertools
import json
import os

import numpy as np
import pytest

from benchmarks import arith, arith_conv, run
from benchmarks.drivers import image_fit_loop as driver
from benchmarks.layer_metrics import device_account, span_account
from benchmarks.reference import cifar_patch as reference

CELL = "cifar_patch_fit_50k"
MANIFEST = json.load(open(os.path.join(run.ROOT, "BENCHMARK.json")))
SEED = 2_147_600_321
MS = 1e6


@pytest.fixture(autouse=True)
def float32_mode():
    """The suite's conftest turns 64-bit mode on; the benchmark runs as its
    users do, without it."""
    import jax

    with jax.enable_x64(False):
        yield


def rehearse(capsys, cell=CELL, trace_flag=0, seed=SEED):
    rc = run.main(["--workload", cell, "--seed", str(seed), "--seconds", "0.2",
                   "--trace", str(trace_flag), "--rehearse"])
    out = capsys.readouterr()
    return rc, json.loads(out.out.strip().splitlines()[-1]), out.err


def test_configuration_states_the_deployment_and_its_yardstick():
    cell = run.load_cell(CELL)
    config, traffic = cell["config_data"], cell["traffic_data"]
    entry = next(c for c in MANIFEST["configs"] if c["name"] == cell["config"])
    assert len(entry["source"]) <= 200 and "RandomPatchCifar.scala" in entry["source"]
    assert "Coates" in entry["source"] and "Coates" in config["source"]
    assert config["architecture"] is None and config["reduced"] == [] == entry["reduced"]
    assert "50,000 images" in config["deployment"] and "one-chip" in config["deployment"]
    assert (config["num_filters"], config["patch_size"], config["whitener_size"],
            config["pool_size"], config["pool_stride"], config["block_size"],
            config["num_epochs"]) == (1600, 6, 100000, 14, 13, 4096, 1)
    for key in ("whitener_size", "patch_size", "pool_size", "pool_stride", "alpha"):
        assert config[key] == config["published"][key]
    assert arith_conv.pools_a_side(config) == 2  # 2 x 2 pools of the 27 x 27 map
    assert arith_conv.pooled_features(config) == config["features"] == 12800
    assert {"images", "lam", "filter_draw", "filter_rows"} <= set(config["assumed"])
    assert "highest" in config["precision"] and "pool" in config
    assert traffic["images"] == 50000 and traffic["probe_images"] == 4096
    assert traffic["lam_grid"] == {"low": 1.0, "high": 1000.0, "points": 16}
    assert traffic["compare_fits"] == 2 and cell["chips"] == 1
    assert {m["name"] for m in cell["per_layer"]} == {
        "window_compiles", "fit_mfu_pct", "device_idle_pct", "conv_featurize_device_ms",
        "conv_featurize_roofline", "image_fit_host_ms"}
    # the accepted reader's yardstick reckons the fit's own operations within 0.5%
    need = arith_conv.fit_flops(traffic["images"], config)
    said = arith.FIT_FLOPS[config["fit_flops"]](
        traffic["images"], config["d_in"], config["num_cosines"] * config["block_size"],
        config["num_classes"], config["block_size"], config["num_epochs"])
    assert need == pytest.approx(1.5222e13, rel=1e-3) and said == pytest.approx(need, rel=0.005)


def test_the_owed_cell_is_data_alone():
    cell = run.load_cell("timit_auto_fit_1m")
    assert cell["config"] == "timit-resident-d16384" and cell["traffic"] == "fit_loop_1m"
    assert cell["config_data"]["entry"] == "auto" and cell["chips"] == 1
    assert cell["traffic_data"]["rows"] == 2 ** 20 and cell["traffic_data"]["compare_fits"] == 3
    assert cell["limits"] == run.load_cell("timit_stream_fit_1m")["limits"] | {
        k: v for k, v in cell["limits"].items() if k == "set_from"}


def test_arith_against_hand_worked_counts():
    config = {"image_size": 8, "channels": 2, "patch_size": 3, "num_filters": 5,
              "pool_size": 4, "pool_stride": 3, "block_size": 16, "num_classes": 3,
              "num_epochs": 2}
    assert arith_conv.conv_map_side(config) == 6 and arith_conv.pools_a_side(config) == 2
    assert arith_conv.pooled_features(config) == 5 * 2 * 4 == 40
    flops, nbytes = arith_conv.conv_featurize_cost(7, config)
    assert flops == 2 * 7 * 36 * 18 * 5
    assert nbytes == 4 * (7 * 64 * 2 + 5 * 18 + 7 * 40)
    assert arith_conv.feature_blocks(40, 16) == [16, 16, 8]
    solve = sum(7 * b * b + b ** 3 / 3 for b in (16, 16, 8)) + 2 * sum(
        4 * 7 * b * 3 + 2 * b * b * 3 for b in (16, 16, 8))
    assert arith_conv.block_solve_flops(7, 40, 16, 3, 2) == pytest.approx(solve)
    assert arith_conv.fit_flops(7, config) == pytest.approx(flops + solve)


def test_reference_imports_nothing_of_the_program():
    import inspect

    source = inspect.getsource(reference)
    assert "keystone_tpu" not in source.split('"""', 2)[2]


@pytest.fixture(scope="module")
def toy():
    import jax

    with jax.enable_x64(False):
        cell = run.load_cell(CELL, rehearse=True)
        lams, images, Y, probe = driver.make_problem(cell, SEED)
        lam = cell["traffic_data"]["lam_grid"]["low"]
        want = reference.fit_and_score(images, Y, probe, [lam], config=cell["config_data"])[lam]
        lowered = reference.fit_and_score(images, Y, probe, [lam], config=cell["config_data"],
                                          precision="bf16")[lam]
        return {"cell": cell, "images": images, "Y": Y, "probe": probe, "lam": lam,
                "want": want, "control": reference.score_gaps(lowered, want)}


def test_program_within_the_limits_and_the_control_over_them(toy):
    from benchmarks.drivers import fit_loop

    cell = toy["cell"]
    fitted = driver.fit_at(cell["config_data"], toy["lam"], toy["images"], toy["Y"])
    program = reference.score_gaps(fit_loop.probe_scores(fitted, toy["probe"]), toy["want"])
    for i, name in enumerate(("score_rel_fro", "score_widest")):
        assert program[i] < driver.SCORE_GUARDS[name] < toy["control"][i]
        assert toy["control"][i] > 5 * program[i]


@pytest.mark.parametrize("fault", ["filters_of_another_draw", "scaler_left_out",
                                   "half_the_images"])
def test_a_broken_timed_path_comes_out_not_correct(capsys, monkeypatch, fault):
    """The rest of a run with the timed path broken underneath, after the
    warm-up fit."""
    sound, calls = driver.build_pipeline, []

    def broken(config, lam, images, Y):
        calls.append(1)
        if len(calls) == 1:
            return sound(config, lam, images, Y)
        if fault == "filters_of_another_draw":
            return sound(dict(config, filter_seed=config["filter_seed"] + 1), lam, images, Y)
        if fault == "half_the_images":
            half = images.shape[0] // 2
            return sound(config, lam, images[:half], Y[:half])
        from keystone_tpu.ops.stats import StandardScaler
        from keystone_tpu.pipelines import cifar

        monkeypatch.setattr(cifar, "StandardScaler", lambda: StandardScaler(normalize_std_dev=False))
        return sound(config, lam, images, Y)

    monkeypatch.setattr(driver, "build_pipeline", broken)
    rc, line, err = rehearse(capsys)
    assert rc == 0 and line["correct"] is False, err
    assert any(line["compared"][n]["value"] > line["compared"][n]["limit"]
               for n in ("score_rel_fro", "score_widest")), line["compared"]


def test_sound_rehearsal_is_correct_guarded_and_compiles_nothing_in_the_window(capsys):
    rc, line, err = rehearse(capsys, trace_flag=1)
    assert rc == 0 and line["correct"] is True and line["failed"] == 0, err
    assert line["metrics"]["window_compiles"] == {"value": 0, "unit": "count"}
    assert "transfer guard over the warm-up fit: held" in err
    assert "images made on the device" in err and "before the driver" in err


def test_rehearsal_of_the_owed_cell(capsys):
    """At toy size ``auto`` picks another solver than at the cell's size (as
    in cell 2's rehearsal: exact normal equations, a program a ridge value);
    the line is whole and every fit comes."""
    rc, line, err = rehearse(capsys, cell="timit_auto_fit_1m", trace_flag=1)
    assert rc == 0 and line["failed"] == 0 and line["attempted"] >= 1, err
    assert "window_compiles" in line["metrics"] and "fit_mfu_pct" not in line["metrics"]
    assert list(line)[-1] == "compared" and line["device"]["rehearsal"] is True


def test_control_readings_part_program_from_control_at_toy_size():
    from benchmarks import control_cifar

    cell = run.load_cell(CELL, rehearse=True)
    line = control_cifar.readings(cell, seed=2_147_600_000, control=True)
    assert line["lams"][0] == cell["traffic_data"]["lam_grid"]["low"] and len(line["lams"]) == 2
    for program, control in zip(line["program"], line["bf16"]):
        assert program[0] < control[0] / 5 and program[1] < control[1] / 5


def _account(fits=2):
    planes = [{"device": "/device:TPU:0", "busy_ns": 0, "idle_ns": 0,
               "by_scope_ns": {k: v * MS * fits for k, v in {
                   "ks.conv_featurize": 1600.0, "ks.patch_whiten": 20.0, "ks.center": 30.0,
                   "ks.split": 10.0, "ks.stack": 12.0, "ks.gram_corr_fold": 240.0,
                   "ks.bcd_step": 60.0, "unscoped": 28.0}.items()},
               "by_program_ns": {}, "unscoped_ops_ns": {}, "idle_ns_by_span": {}}]
    return {"extent_ns": 0, "longest_gaps": [], "clock": {}, "planes": planes}


def _ctx(fits=2, trace=True):
    config = run.load_cell(CELL)["config_data"]
    return {"trace": {"window_s": 30.0, "op_seconds": {"all": 4.0}} if trace else None,
            "notes": [], "config": config, "traffic": {}, "counters": {},
            "device_kind": "TPU v5 lite",
            "window": {"fits": fits, "window_s": 30.0, "rows": 50000}}


def test_device_readers_on_a_hand_written_account_and_with_nothing_to_read(monkeypatch):
    device, roofline = run.load_reader("conv_featurize_device_ms"), run.load_reader(
        "conv_featurize_roofline")
    monkeypatch.setattr(device_account, "session_account", lambda: _account())
    ctx = _ctx()
    assert device.read(ctx) == pytest.approx(1600.0)
    notes = "\n".join(ctx["notes"])
    assert "ks.patch_whiten 20.000" in notes and "ks.gram_corr_fold 240.000" in notes
    assert "1.400% of the device's self time under no ks.* scope" in notes
    # 2 fits x 50,000 images: 2 x 1.2597e13 operations at 197e12 against 3.2 s
    least = 2 * 2.0 * 50000 * 729 * 108 * 1600 / 197e12
    assert roofline.read(ctx) == pytest.approx(100 * least / 3.2)
    assert "bound by compute" in ctx["notes"][-1] and roofline.read(_ctx()) < 100
    monkeypatch.setattr(device_account, "session_account", lambda: None)  # a program that keeps no account
    for reader in (device, roofline):
        assert reader.read(_ctx()) is None and reader.read(_ctx(trace=False)) is None
    other = _account()
    del other["planes"][0]["by_scope_ns"]["ks.conv_featurize"]  # no such phase ran
    monkeypatch.setattr(device_account, "session_account", lambda: other)
    assert device.read(_ctx()) is None and roofline.read(_ctx()) is None


T0 = 1_700_000_000_000_000


def _session(fits=2):
    """Hand-written spans of ``fits`` fits: a build of 3,000 us, a fit of
    40,000 with the optimizer (500), the solver's stack (200) and bcd
    (300), and a drain of 30,000."""
    ids, spans = itertools.count(1), []

    def span(name, start, dur, parent=None, **args):
        spans.append({"type": "span", "name": name, "ts_us": T0 + start, "dur_us": dur,
                      "span_id": next(ids), "parent_id": parent, "args": args})
        return spans[-1]["span_id"]

    for i in range(fits):
        at = i * 50_000
        span("pipeline.build", at, 3_000, entry="random_patch", filters=1600,
             patches_sampled=100000, features=12800, image_batch=152)
        fit = span("pipeline.fit", at + 4_000, 40_000)
        span("optimizer.rule.StageFusionRule", at + 4_100, 500, fit)
        est = span("estimator.fit", at + 5_000, 38_000, fit, estimator="BlockLeastSquaresEstimator")
        span("solver.stack", at + 5_100, 200, est)
        span("solver.bcd", at + 5_400, 300, est, epochs=1)
        span("executor.drain", at + 6_000, 30_000, est, site="estimator_sync")
    return spans


def test_host_reader_on_hand_written_spans_and_with_nothing_to_read(monkeypatch):
    host = run.load_reader("image_fit_host_ms")
    monkeypatch.setattr(span_account, "session_spans", lambda: _session())
    ctx = _ctx()
    assert host.read(ctx) == pytest.approx((3_000 + 40_000 - 30_000) / 1e3)
    assert "traces a fit 0.0" in ctx["notes"][-1] and "'image_batch': 152" in ctx["notes"][-1]
    monkeypatch.setattr(span_account, "session_spans", lambda: None)
    assert host.read(_ctx()) is None and host.read(_ctx(trace=False)) is None


@pytest.fixture(scope="module")
def v5e():
    """One chip of a described v5e host, to compile for (nothing runs)."""
    import jax
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e topology can be described here: {e}")
    return jax.sharding.SingleDeviceSharding(topo.devices[0])


def test_featurize_and_solve_compile_for_the_chip_at_the_cells_shape(v5e, compile_for_chip,
                                                                     monkeypatch):
    """The fused featurize program over 50,000 images in batches of 152 and
    the fused block solve over three blocks of 4,096 and a tail of 512, with
    the Mosaic Gramian kernel: their temporaries beside the 0.6 GB of images
    and the 2.56 GB of features they take."""
    import functools

    import jax
    import jax.numpy as jnp

    from keystone_tpu.ops import pallas_ops
    from keystone_tpu.ops.images.conv import Convolver, Pooler, SymmetricRectifier
    from keystone_tpu.ops.images.core import ImageVectorizer
    from keystone_tpu.ops.learning.pca import ZCAWhitener
    from keystone_tpu.parallel import linalg
    from keystone_tpu.workflow import fusion

    monkeypatch.setattr(pallas_ops, "_interpret", lambda: False)  # else the kernel is interpreted
    config = run.load_cell(CELL)["config_data"]
    n, K = run.load_cell(CELL)["traffic_data"]["images"], config["num_filters"]
    shape = functools.partial(jax.ShapeDtypeStruct, sharding=v5e)
    members = [Convolver(jnp.zeros((K, 108)), 32, 32, 3,
                         whitener=ZCAWhitener(jnp.eye(108), jnp.zeros(108))),
               SymmetricRectifier(alpha=config["alpha"]),
               Pooler(config["pool_stride"], config["pool_size"], pool_function="sum"),
               ImageVectorizer()]
    fused = fusion.FusedBatchTransformer(members)
    params = jax.tree_util.tree_map(lambda a: shape(a.shape, a.dtype), fused._operands[1])
    featurize = compile_for_chip(lambda p, X: fused._program(p, X), params,
                                 shape((n, 32, 32, 3), jnp.float32))
    memory = featurize.memory_analysis()
    assert featurize.as_text().count("while") >= 1  # one loop over the batches of 152
    assert memory.output_size_in_bytes == n * 12800 * 4
    assert memory.temp_size_in_bytes < 2e9  # no (50,000, 27, 27, .) map: 233 GB for the conv map
    solve = compile_for_chip(
        lambda A, B, W, lam, tail: linalg._bcd_fused_kernel(
            A, B, W, lam, num_iter=1, use_pallas=True, sym=True, cache_stash=False, tail=tail),
        shape((3, n, 4096), jnp.float32), shape((n, 10), jnp.float32),
        shape((3, 4096, 10), jnp.float32), shape((), jnp.float32), shape((n, 512), jnp.float32))
    assert "tpu_custom_call" in solve.as_text()
    assert solve.memory_analysis().temp_size_in_bytes < 2.5e9
