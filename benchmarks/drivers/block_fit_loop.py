"""Traffic driver ``block_fit_loop``: ``fit_loop``'s closed loop of whole
fits — a ridge sweep by one caller — over the TIMIT pipeline AT ITS
PUBLISHED WIDTH, through cell 2's entry

    timit.build_featurizer(cfg).and_then(
        LeastSquaresEstimator(lam, block_size=..., block_iters=...), X, Y).fit()

with the solver left to the cost model, which at 204,800 features can only
take the block-streamed tier. The rows, the grid, the clocks, the window,
the walk over a fitted pipeline and the compile counter are ``fit_loop``'s
own, imported; what differs is the comparison — against
``benchmarks/reference/timit_block.py``, the residual-form reference that
never forms a d x d Gramian — and ``selector``, the keyword arguments a
REHEARSAL hands the selector so that it faces the cell's choice at toy size
(empty at the cell's own size).
"""

from __future__ import annotations

import gc
import time
from typing import Any, Dict, List

import jax
import numpy as np

from benchmarks.drivers import fit_loop
from benchmarks.reference import timit_block as reference


def build_pipeline(config: Dict[str, Any], lam: float, X, Y):
    """``fit_loop.build_pipeline``'s ``auto`` composition, with the
    configuration's ``selector`` (empty outside a rehearsal)."""
    from keystone_tpu.data import Dataset
    from keystone_tpu.ops.learning.cost import LeastSquaresEstimator
    from keystone_tpu.pipelines import timit

    if config["entry"] != "auto":
        raise ValueError(f"block_fit_loop knows no entry {config['entry']!r}")
    cfg = timit.TimitConfig(
        num_cosines=config["num_cosines"], gamma=config["gamma"],
        rf_type=config["rf_type"], block_size=config["block_size"],
        num_epochs=config["num_epochs"], lam=lam, seed=config["bank_seed"],
        solver="auto",
    )
    estimator = LeastSquaresEstimator(
        lam=cfg.lam, block_size=cfg.block_size, block_iters=cfg.num_epochs,
        **config["selector"])
    return timit.build_featurizer(cfg).and_then(estimator, Dataset.of(X), Dataset.of(Y))


def fit_once(config, lam: float, X, Y):
    """One whole new fit: no saved state reused, weights ready on return."""
    from keystone_tpu.workflow import PipelineEnv

    PipelineEnv.get_or_create().reset()
    fitted = build_pipeline(config, lam, X, Y).fit()
    jax.block_until_ready(fit_loop.device_arrays(fitted))
    jax.block_until_ready(fit_loop._fence(X[0, 0]))  # after every program of the fit
    return fitted


def block_weight_shapes(fitted) -> List[tuple]:
    """Shapes of the block weights a fitted pipeline holds, where the
    program keeps them stacked by block (``W_stack``): they say at which
    block size the model was really fitted."""
    return [tuple(o.W_stack.shape) for o in fit_loop.walk(fitted)
            if getattr(o, "W_stack", None) is not None
            and not isinstance(o, (jax.Array, np.ndarray))]


def configured_shape(config: Dict[str, Any]) -> tuple:
    return (config["num_cosines"], config["block_size"], config["num_classes"])


def block_size_gap(shapes: List[tuple], config: Dict[str, Any]) -> int:
    """How far the block size of the fitted weights is from the configured
    one (0: fitted at it). Block Gauss-Seidel iterates depend on the block,
    so a fit at another block size is another model, however close its
    scores come."""
    want = configured_shape(config)
    if not shapes:
        return want[1]
    return max(abs(shape[1] - want[1]) if len(shape) == 3 else want[1] for shape in shapes)


def compare(kept_scores, failed: int, X, Y, probe, config, limits) -> Dict[str, Any]:
    """Each kept fit's probe scores against the residual-form reference's
    for the same lambda; the worst of each gap stands beside its limit,
    and the fitted block size beside the configured one."""
    compared = {"fits_failed": {"value": failed, "limit": 0}}
    if not kept_scores:
        return compared
    compared["block_size_gap"] = {
        "value": max(block_size_gap(shapes, config) for *_, shapes in kept_scores), "limit": 0}
    kept_scores = [kept[:3] for kept in kept_scores]
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
    lams, X, Y, probe = fit_loop.make_problem(cell, seed)

    def fit(lam: float):
        return fit_once(config, lam, X, Y)

    jax.block_until_ready((X, Y, probe))
    t_rows = time.perf_counter()
    warm = fit(lams[-1])  # warm-up
    shapes = block_weight_shapes(warm)
    notes = [f"fitted model classes: {fit_loop.program_classes(warm)}; block weights {shapes}"]
    del warm
    if block_size_gap(shapes, config):
        # A program that cannot fit this configuration is told so once,
        # here, and not timed: its window would time another model.
        raise SystemExit(
            f"block_fit_loop: the warm-up fit's block weights are {shapes}, not "
            f"{configured_shape(config)}: this program fits another model than the "
            f"configuration's (block Gauss-Seidel iterates depend on the block). No result.")
    gc.collect()
    notes.append(f"set-up: rows {t_rows - t_start:.2f} s, warm-up fit "
                 f"{time.perf_counter() - t_rows:.2f} s, {counter.programs} programs "
                 f"compiled or fetched in {counter.seconds:.2f} s, persistent cache "
                 f"{counter.cache}")

    compiles_before = counter.programs
    with fit_loop.maybe_trace(trace) as tracing:
        window = fit_loop.measure_window(fit, lams, seconds, traffic["compare_fits"],
                                         np.random.default_rng(seed))
    window_compiles = counter.programs - compiles_before
    stats = [d.memory_stats() for d in devices]
    peak = max((s or {}).get("peak_bytes_in_use", 0) for s in stats)

    kept_scores = [(i, lam, fit_loop.probe_scores(f, probe), block_weight_shapes(f))
                   for i, lam, f in window.pop("kept")]
    from keystone_tpu.workflow import PipelineEnv

    PipelineEnv.get_or_create().reset()  # the program's state goes before the reference runs
    gc.collect()
    fits = window["attempted"] - window["failed"]
    notes.append(f"window: {fits} fits in {window['window_s']:.3f} s; compared fits "
                 f"{[i for i, *_ in kept_scores]}; seconds of each fit (the time "
                 f"between fits apart): {[round(s, 3) for s in window['fit_seconds']]}")
    compared = compare(kept_scores, window["failed"], X, Y, probe, config, cell["limits"])
    correct = all(p["value"] <= p["limit"] for p in compared.values())
    return {
        "correct": correct, "attempted": window["attempted"], "failed": window["failed"],
        "compared": compared, "notes": notes, "memory_peak_bytes": peak,
        "window_started_at": window["started"], "trace_dir": tracing.get("dir"),
        "window": {"fits": fits, "window_s": window["window_s"],
                   "rows": traffic["rows"]},
        "counters": {"window_compiles": window_compiles},
        "end_to_end": {"fit_s": window["window_s"] / max(fits, 1),
                       "peak_hbm_gb": peak / 1e9},
    }
