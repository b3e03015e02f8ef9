"""Traffic driver ``fit_loop_mesh``: ``fit_loop``'s closed loop of whole fits
— a ridge sweep by one caller — over rows that lie sharded over the
configuration's mesh, as a user with a four-chip host writes it:

    data, labels = Dataset.of(X).shard(mesh), Dataset.of(Y).shard(mesh)   # once
    timit.streaming_estimator(cfg).with_data(data, labels).fit()          # every lambda

The grid, the clocks, the window, the walk over a fitted pipeline, the
composition of the pipeline and the compile counter are ``fit_loop``'s own,
imported. What differs: each device's rows are made on that device (no
device ever holds another's rows and nothing is made on the host), a fit
ends with a fence on EVERY device of the mesh, and the comparison is
against ``benchmarks/reference/timit_mesh.py``, which folds each shard
where it lies.

``window["rows"]`` is what ONE device folds: ``fit_mfu_pct`` divides by one
chip's peak, so with a device's rows it reads the share of the mesh's whole
peak that the fit reaches — comparable with the one-chip cell's.
"""

from __future__ import annotations

import gc
import time
from typing import Any, Dict

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from benchmarks.drivers import fit_loop
from benchmarks.reference import timit_mesh as reference


def make_mesh(config: Dict[str, Any], devices) -> Mesh:
    """The configuration's mesh over the devices the run was given (a
    rehearsal's ``shape: null``: over however many those are)."""
    shape = config["mesh"]["shape"] or [len(devices)]
    count = int(np.prod(shape))
    if count > len(devices):
        raise SystemExit(f"fit_loop_mesh: a mesh of {shape} needs {count} devices, "
                         f"the run was given {len(devices)}. No result.")
    return Mesh(np.array(devices[:count]).reshape(shape), tuple(config["mesh"]["axes"]))


def make_sharded_rows(mesh: Mesh, key_centres, key_rows, rows_per_device: int,
                      d_in: int, classes: int):
    """(X, Y) over the mesh's first axis, shard *i* made on device *i* by
    ``fit_loop.make_rows`` from ``fold_in(key_rows, i)`` about the shared
    class centres — one SPMD program with no collective (compiled once,
    where a program a device compiles once a device): every device draws
    its own rows, which never leave it."""
    axis = mesh.axis_names[0]
    keys = jax.vmap(lambda i: jax.random.fold_in(key_rows, i))(np.arange(mesh.devices.size))

    def local(kc, kr):
        return fit_loop.make_rows(kc, kr[0], rows_per_device, d_in, classes)

    make = jax.shard_map(local, mesh=mesh, in_specs=(P(), P(axis)),
                         out_specs=(P(axis), P(axis)), check_vma=False)
    return jax.jit(make)(key_centres, keys)


def make_problem(cell: Dict[str, Any], seed: int, mesh: Mesh):
    """(lams, X, Y, probe): ``fit_loop.make_problem`` with the rows made
    shard by shard; the probe rows lie on the first device."""
    config, traffic = cell["config_data"], cell["traffic_data"]
    key_centres, key_rows, key_probe = jax.random.split(fit_loop.seed_key(seed), 3)
    shape = (config["d_in"], config["num_classes"])
    X, Y = make_sharded_rows(mesh, key_centres, key_rows, traffic["rows_per_device"], *shape)
    probe, _ = fit_loop.make_rows(key_centres, key_probe, traffic["probe_rows"], *shape)
    return fit_loop.lam_order(seed, traffic["lam_grid"]), X, Y, probe


def shard_once(X, Y, mesh: Mesh):
    """What the user's set-up does: the rows placed over the mesh, once."""
    from keystone_tpu.data import Dataset

    return Dataset.of(X).shard(mesh), Dataset.of(Y).shard(mesh)


def fence_token(mesh: Mesh):
    """A float on every device of the mesh, for ``fit_once``'s fence program."""
    return jax.device_put(np.zeros(mesh.devices.size, np.float32),
                          NamedSharding(mesh, P(mesh.axis_names)))


def fit_once(config, lam: float, data, labels, fence_on):
    """One whole new fit over the sharded datasets: no saved state reused,
    weights ready and every device of the mesh drained on return."""
    from keystone_tpu.workflow import PipelineEnv

    PipelineEnv.get_or_create().reset()
    # ``Dataset.of`` hands a Dataset back as it is: fit_loop's composition
    fitted = fit_loop.build_pipeline(config, lam, data, labels).fit()
    jax.block_until_ready(fit_loop.device_arrays(fitted))
    # one more program on every device, behind every program of the fit
    jax.block_until_ready(fit_loop._fence(fence_on))
    return fitted


def compare(kept_scores, failed: int, X, Y, probe, config, limits) -> Dict[str, Any]:
    """``fit_loop.compare`` against the reference that folds each shard of
    (X, Y) where it lies: each kept fit's probe scores beside the
    reference's for the same lambda, the worst of each gap beside its
    limit; none may fail."""
    compared = {"fits_failed": {"value": failed, "limit": 0}}
    if not kept_scores:
        return compared
    want = reference.fit_and_score(
        X, Y, probe, [lam for _, lam, _ in kept_scores], **fit_loop.reference_args(config))
    gaps = np.array([reference.score_gaps(got, want[lam]) for _, lam, got in kept_scores])
    big = float(np.finfo(np.float32).max)  # JSON has no inf
    worst = np.nan_to_num(gaps, nan=big, posinf=big).max(axis=0)
    for name, value in zip(("score_rel_fro", "score_widest"), worst):
        compared[name] = {"value": float(value), "limit": limits[name]["limit"]}
    return compared


def run(cell: Dict[str, Any], *, seed: int, seconds: float, trace: bool,
        devices) -> Dict[str, Any]:
    config, traffic = cell["config_data"], cell["traffic_data"]
    counter = fit_loop.CompileCounter()
    t_start = time.perf_counter()
    mesh = make_mesh(config, devices)
    devices = list(mesh.devices.flat)
    lams, X, Y, probe = make_problem(cell, seed, mesh)
    jax.block_until_ready((X, Y, probe))
    t_rows = time.perf_counter()
    data, labels = shard_once(X, Y, mesh)
    X, Y = data.array, labels.array  # the placed rows alone stay (the same buffers
    jax.block_until_ready((X, Y))    # where the program moved nothing)
    t_sharded = time.perf_counter()
    fence_on = fence_token(mesh)

    def fit(lam: float):
        return fit_once(config, lam, data, labels, fence_on)

    notes = [f"fitted model classes: {fit_loop.program_classes(fit(lams[-1]))}"]  # warm-up
    gc.collect()
    notes.append(f"set-up: rows {t_rows - t_start:.2f} s on {len(devices)} devices, "
                 f"Dataset.shard {t_sharded - t_rows:.2f} s, warm-up fit "
                 f"{time.perf_counter() - t_sharded:.2f} s, {counter.programs} programs "
                 f"compiled or fetched in {counter.seconds:.2f} s, persistent cache "
                 f"{counter.cache}")

    compiles_before = counter.programs
    with fit_loop.maybe_trace(trace) as tracing:
        window = fit_loop.measure_window(fit, lams, seconds, traffic["compare_fits"],
                                         np.random.default_rng(seed))
    window_compiles = counter.programs - compiles_before
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices]
    peak = max(peaks)

    kept_scores = [(i, lam, fit_loop.probe_scores(f, probe)) for i, lam, f in window.pop("kept")]
    from keystone_tpu.workflow import PipelineEnv

    PipelineEnv.get_or_create().reset()  # the program's state goes before the reference runs
    del data, labels
    gc.collect()
    fits = window["attempted"] - window["failed"]
    rows_local = traffic["rows_per_device"]
    notes.append(f"window: {fits} fits in {window['window_s']:.3f} s; compared fits "
                 f"{[i for i, _, _ in kept_scores]}; seconds of each fit (the time "
                 f"between fits apart): {[round(s, 3) for s in window['fit_seconds']]}; "
                 f"peak bytes by device {peaks}")
    notes.append(f"fit_mfu_pct here is the fit's share of the peak of all {len(devices)} "
                 f"chips: its operations are counted for the {rows_local} rows ONE device "
                 f"folds and divided by one chip's peak (of {rows_local * len(devices)} rows "
                 f"in all)")
    t_compare = time.perf_counter()
    compared = compare(kept_scores, window["failed"], X, Y, probe, config, cell["limits"])
    notes.append(f"comparison: {time.perf_counter() - t_compare:.2f} s")
    correct = all(p["value"] <= p["limit"] for p in compared.values())
    return {
        "correct": correct, "attempted": window["attempted"], "failed": window["failed"],
        "compared": compared, "notes": notes, "memory_peak_bytes": peak,
        "window_started_at": window["started"], "trace_dir": tracing.get("dir"),
        "window": {"fits": fits, "window_s": window["window_s"],
                   "rows": rows_local, "rows_total": rows_local * len(devices)},
        "counters": {"window_compiles": window_compiles},
        "end_to_end": {"fit_s": window["window_s"] / max(fits, 1),
                       "peak_hbm_gb": peak / 1e9},
    }
