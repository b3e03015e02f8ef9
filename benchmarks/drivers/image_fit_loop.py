"""Traffic driver ``image_fit_loop``: ``fit_loop``'s closed loop of whole
fits — a ridge sweep by one caller — over the RandomPatchCifar pipeline,
through the function that ``run_random_patch_cifar`` fits through

    cifar.build_random_patch(CifarConfig(...), Dataset(images), Dataset(Y), lam).fit()

Every fit is a new pipeline that draws its filters anew from the images.
The images, the grid, the comparison and the clocks are the benchmark's
own; the window, the walk over a fitted pipeline and the compile counter
are ``fit_loop``'s, imported. The images and their targets are made on the
device by one program from the seed, and each fit's lambda is sent to the
device before the fit starts, so that nothing crosses the host inside a
fit: the warm-up fit runs under ``jax.transfer_guard("disallow")`` and the
set-up note says whether it held.
"""

from __future__ import annotations

import gc
import sys
import time
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

# The set-up note splits the time before the driver: what the harness did
# before importing it (Python, jax, the TPU's start-up) and the program's
# imports below.
_T_IMPORT = time.perf_counter()
from benchmarks.drivers import fit_loop  # noqa: E402
from benchmarks.reference import cifar_patch as reference  # noqa: E402
# A program without ``build_random_patch`` cannot run this cell: it stops here.
from keystone_tpu.pipelines.cifar import CifarConfig, build_random_patch  # noqa: E402

_T_IMPORTED = time.perf_counter()

F32 = jnp.float32

# The probe scores cannot carry a precision limit in this cell: at the grid's
# smallest ridge a one-epoch solve of standardised, strongly correlated
# features rounds forward in float32 about as far as one bfloat16 pass adds
# (program 1.2e-4 to 1.5e-4, the control 3.8e-4 to 4.4e-4 at lambda = 1;
# PERF.md section 4), short of the 3x a limit's two readings must part by.
# They are guards of the solve, fixed here, above every sound reading with
# room; the probe features carry the precision limit (limits/<cell>.json).
SCORE_GUARDS = {"score_rel_fro": 3e-4, "score_widest": 1e-3}


def make_images(key_patterns, keys, sizes, config: Dict[str, Any]):
    """[(images, +-1 targets)] a key and a size, made on the device in ONE
    program: a low-frequency pattern a class (frequencies U[0.2, 1.2], a
    phase a channel U[0, 2 pi), from ``key_patterns``, the same for every
    set) at 127.5 +- 90, N(0, 25^2) noise, clipped to [0, 255] —
    ``synthetic_cifar``'s images."""
    classes, side, channels = config["num_classes"], config["image_size"], config["channels"]

    def one(freqs, phases, key, n):
        k_label, k_noise = jax.random.split(key)
        labels = jax.random.randint(k_label, (n,), 0, classes)
        yy, xx = jnp.meshgrid(jnp.arange(side, dtype=F32), jnp.arange(side, dtype=F32),
                              indexing="ij")
        f = freqs[labels]
        wave = (f[:, 0, None, None] * xx + f[:, 1, None, None] * yy)[..., None]
        images = 127.5 + 90.0 * jnp.sin(wave + phases[labels][:, None, None, :])
        images = images + 25.0 * jax.random.normal(k_noise, images.shape, F32)
        return (jnp.clip(images, 0.0, 255.0),
                F32(2) * jax.nn.one_hot(labels, classes, dtype=F32) - F32(1))

    @jax.jit
    def make(kp, keys):
        k_freq, k_phase = jax.random.split(kp)
        freqs = jax.random.uniform(k_freq, (classes, 2), F32, 0.2, 1.2)
        phases = jax.random.uniform(k_phase, (classes, channels), F32, 0.0, 2 * np.pi)
        return [one(freqs, phases, key, n) for key, n in zip(keys, sizes)]

    return make(key_patterns, list(keys))


def make_problem(cell: Dict[str, Any], seed: int):
    """(lams, images, Y, probe) of ``cell`` for ``seed``."""
    config, traffic = cell["config_data"], cell["traffic_data"]
    key_patterns, key_images, key_probe = jax.random.split(fit_loop.seed_key(seed), 3)
    (images, Y), (probe, _) = make_images(
        key_patterns, [key_images, key_probe],
        [traffic["images"], traffic["probe_images"]], config)
    return fit_loop.lam_order(seed, traffic["lam_grid"]), images, Y, probe


def cifar_config(config: Dict[str, Any], lam) -> CifarConfig:
    return CifarConfig(
        num_filters=config["num_filters"], whitener_size=config["whitener_size"],
        patch_size=config["patch_size"], pool_size=config["pool_size"],
        pool_stride=config["pool_stride"], alpha=config["alpha"], lam=lam,
        block_size=config["block_size"], num_epochs=config["num_epochs"],
        seed=config["filter_seed"])


def build_pipeline(config: Dict[str, Any], lam, images, Y):
    from keystone_tpu.data import Dataset

    return build_random_patch(cifar_config(config, lam), Dataset(images), Dataset(Y), lam)


@jax.jit
def _fence(images):
    return images[0, 0, 0, 0] + 1  # indexed inside a program: no index sent from the host


def fit_once(config, lam, images, Y):
    """One whole new fit: no saved state reused, weights ready on return."""
    from keystone_tpu.workflow import PipelineEnv

    PipelineEnv.get_or_create().reset()
    fitted = build_pipeline(config, lam, images, Y).fit()
    jax.block_until_ready(fit_loop.device_arrays(fitted))
    jax.block_until_ready(_fence(images))  # after every program of the fit
    return fitted


def fit_at(config, lam: float, images, Y):
    """:func:`fit_once` with ``lam`` sent to the device first."""
    return fit_once(config, jax.device_put(np.float32(lam)), images, Y)


def guarded_warm_up(config, lam: float, images, Y):
    """The warm-up fit under ``jax.transfer_guard("disallow")``, and what
    the guard said; a fit the guard stops is made again without it."""
    lam_on_device = jax.device_put(np.float32(lam))
    try:
        with jax.transfer_guard("disallow"):
            return (fit_once(config, lam_on_device, images, Y),
                    "held (nothing crossed between host and device)")
    except Exception as e:  # the guard's refusal names the transfer
        said = f"REFUSED a transfer ({type(e).__name__}: {str(e)[:200]}); fitted again without it"
        return fit_once(config, lam_on_device, images, Y), said


def probe_features(fitted, probe):
    """The probe images' pooled features through the fitted pipeline's own
    featurizer — its fused convolution chain — or None where it holds none."""
    from keystone_tpu.data import Dataset

    for node in fit_loop.walk(fitted):
        members = getattr(node, "members", None)
        if isinstance(members, list) and any(type(m).__name__ == "Convolver" for m in members):
            return np.asarray(node.batch_apply(Dataset(probe)).array, dtype=np.float32)
    return None


def feature_gap(got, want) -> float:
    """Relative Frobenius gap of the probe features; a program whose fitted
    pipeline holds no convolution chain reads the largest float32."""
    big = float(np.finfo(np.float32).max)
    if got is None or got.shape != tuple(want.shape):
        return big
    return float(np.nan_to_num(reference.score_gaps(got, want)[0], nan=big, posinf=big))


def compare(kept, failed: int, images, Y, probe, config, limits) -> Dict[str, Any]:
    """Each kept fit's probe scores against the plain reference's for the
    same lambda, and its probe features against the reference's; the worst
    of each gap stands beside its limit."""
    compared = {"fits_failed": {"value": failed, "limit": 0}}
    if not kept:
        return compared
    want, features = reference.fit_score_and_features(
        images, Y, probe, [lam for _, lam, _, _ in kept], config=config)
    gaps = np.array([reference.score_gaps(got, want[lam]) for _, lam, got, _ in kept])
    big = float(np.finfo(np.float32).max)
    worst = np.nan_to_num(gaps, nan=big, posinf=big).max(axis=0)
    for name, value in zip(("score_rel_fro", "score_widest"), worst):
        compared[name] = {"value": float(value), "limit": SCORE_GUARDS[name]}
    compared["feature_rel_fro"] = {
        "value": max(feature_gap(f, features) for _, _, _, f in kept),
        "limit": limits["feature_rel_fro"]["limit"]}
    return compared


def run(cell: Dict[str, Any], *, seed: int, seconds: float, trace: bool,
        devices) -> Dict[str, Any]:
    config, traffic = cell["config_data"], cell["traffic_data"]
    counter = fit_loop.CompileCounter()
    t_start = time.perf_counter()
    lams, images, Y, probe = make_problem(cell, seed)
    jax.block_until_ready((images, Y, probe))
    t_rows = time.perf_counter()
    warm, guard = guarded_warm_up(config, lams[-1], images, Y)
    notes = [f"fitted model classes: {fit_loop.program_classes(warm)}"]
    del warm
    gc.collect()
    t_warm = time.perf_counter()
    t0 = getattr(sys.modules.get("__main__"), "_T0", t_start)
    before, to_import = t_start - t0, max(_T_IMPORT - t0, 0.0)
    notes.append(
        f"set-up: rows {t_rows - t_start:.2f} s ({traffic['images']} + "
        f"{traffic['probe_images']} images made on the device), warm-up fit "
        f"{t_warm - t_rows:.2f} s, {counter.programs} programs compiled or fetched in "
        f"{counter.seconds:.2f} s, persistent cache {counter.cache}; before the driver "
        f"(imports, start-up) {before:.2f} s = {to_import:.2f} s to its import (Python, jax, "
        f"the TPU's start-up, the harness) + {_T_IMPORTED - _T_IMPORT:.2f} s of its imports "
        f"(the program) + the rest; transfer guard over the warm-up fit: {guard}")

    compiles_before = counter.programs
    with fit_loop.maybe_trace(trace) as tracing:
        window = fit_loop.measure_window(
            lambda lam: fit_at(config, lam, images, Y), lams, seconds,
            traffic["compare_fits"], np.random.default_rng(seed))
    window_compiles = counter.programs - compiles_before
    stats = [d.memory_stats() for d in devices]
    peak = max((s or {}).get("peak_bytes_in_use", 0) for s in stats)

    kept = [(i, lam, fit_loop.probe_scores(f, probe), probe_features(f, probe))
            for i, lam, f in window.pop("kept")]
    from keystone_tpu.workflow import PipelineEnv

    PipelineEnv.get_or_create().reset()  # the program's state goes before the reference runs
    gc.collect()
    fits = window["attempted"] - window["failed"]
    notes.append(f"window: {fits} fits in {window['window_s']:.3f} s; compared fits "
                 f"{[i for i, _, _, _ in kept]}; seconds of each fit (the time "
                 f"between fits apart): {[round(s, 3) for s in window['fit_seconds']]}")
    compared = compare(kept, window["failed"], images, Y, probe, config, cell["limits"])
    correct = all(p["value"] <= p["limit"] for p in compared.values())
    return {
        "correct": correct, "attempted": window["attempted"], "failed": window["failed"],
        "compared": compared, "notes": notes, "memory_peak_bytes": peak,
        "window_started_at": window["started"], "trace_dir": tracing.get("dir"),
        "window": {"fits": fits, "window_s": window["window_s"],
                   "rows": traffic["images"]},
        "counters": {"window_compiles": window_compiles},
        "end_to_end": {"fit_s": window["window_s"] / max(fits, 1),
                       "peak_hbm_gb": peak / 1e9},
    }
