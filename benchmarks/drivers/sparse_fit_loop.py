"""Traffic driver ``sparse_fit_loop``: ``fit_loop``'s closed loop of whole
fits — a ridge sweep by one caller — over SPARSE rows: binary n-gram
features as padded COO, made on the device from the seed, fitted through

    LeastSquaresEstimator(lam=lam).with_data(Dataset({"indices", "values"}, n=rows),
                                             Dataset.of(Y)).fit()

with the solver left to the cost model. The clocks, the window, the walk
over a fitted pipeline and the compile counter are ``fit_loop``'s own,
imported; the rows, the entry and the comparison (against
``benchmarks/reference/amazon.py``) are this file's.
"""

from __future__ import annotations

import functools
import gc
import sys
import time
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.drivers import fit_loop
from benchmarks.reference import amazon as reference

F32 = jnp.float32
ROWS_PER_DRAW = 65536  # rows made by one step of the generator
MAX_ITERATION_GAP = 1  # a stop at the tolerance may fall one step apart


@functools.partial(jax.jit, static_argnames=("rows", "d", "lanes", "draws"))
def _draw_ids(key, rows: int, d: int, lanes: int, draws: int):
    """``lanes`` distinct ids a row under a Zipf(1.0) popularity, ascending:
    the first ``lanes`` distinct values of ``draws`` i.i.d. draws
    ``floor((d + 1)^u) - 1`` — drawing without replacement, one id after the
    other. Also the least count of distinct values any row's draws held."""
    step = min(rows, ROWS_PER_DRAW)
    slot = jnp.broadcast_to(jnp.arange(draws, dtype=jnp.int32), (step, draws))

    def some(k):
        u = jax.random.uniform(k, (step, draws), F32)
        ids = jnp.minimum(jnp.exp(u * np.log(d + 1.0)).astype(jnp.int32) - 1, d - 1)
        ids, at = jax.lax.sort((ids, slot), dimension=1, num_keys=2)
        first = jnp.concatenate(
            [jnp.ones((step, 1), bool), ids[:, 1:] != ids[:, :-1]], axis=1)
        # a value's first draw keeps its slot; later draws of it go last
        order, ids = jax.lax.sort((jnp.where(first, at, draws), ids), dimension=1, num_keys=1)
        return jnp.sort(ids[:, :lanes], axis=1), jnp.sum(order < draws, axis=1).min()

    ids, distinct = jax.lax.map(some, jax.random.split(key, rows // step))
    return ids.reshape(rows, lanes), distinct.min()


@functools.partial(jax.jit, static_argnames=("d",))
def _targets(key_w, key_noise, idx, d: int, noise_scale):
    """The two +-1 indicators of a planted linear score's sign. The scores
    are gathered ``ROWS_PER_DRAW`` rows at a step."""
    rows, lanes = idx.shape
    step = min(rows, ROWS_PER_DRAW)
    w = jax.random.normal(key_w, (d,), F32)
    noise = jax.random.normal(key_noise, (rows,), F32)
    planted = jax.lax.map(lambda some: jnp.take(w, some).sum(axis=1),
                          idx.reshape(rows // step, step, lanes)).reshape(rows)
    score = planted + noise_scale * np.sqrt(lanes) * noise
    # (a stack of y and -y along either axis does not come out of it either)
    return jnp.where(score[:, None] > 0, jnp.asarray([1, -1], F32), jnp.asarray([-1, 1], F32))


def say(what: str) -> None:
    """Progress on standard error, as it happens: a run that is cut shows
    how far it came."""
    print(f"[{time.perf_counter():.1f}] {what}", file=sys.stderr, flush=True)


def make_rows(key, rows: int, config: Dict[str, Any]):
    d, lanes, draws = config["num_features"], config["lanes"], config["draws"]
    if rows % min(rows, ROWS_PER_DRAW):
        raise ValueError(f"{rows} rows are no whole number of {ROWS_PER_DRAW}")
    idx, distinct = _draw_ids(key, rows, d, lanes, draws)
    if int(distinct) < lanes:
        raise RuntimeError(f"a row's {draws} draws held {int(distinct)} distinct ids, "
                           f"under the {lanes} a row takes: raise 'draws'")
    return idx, jnp.full(idx.shape, config["value"], F32)


def make_problem(cell: Dict[str, Any], seed: int):
    """(lams, idx, val, Y, probe_idx, probe_val) of ``cell`` for ``seed``."""
    config, traffic = cell["config_data"], cell["traffic_data"]
    key_rows, key_w, key_noise, key_probe = jax.random.split(fit_loop.seed_key(seed), 4)
    idx, val = make_rows(key_rows, traffic["rows"], config)
    Y = _targets(key_w, key_noise, idx, config["num_features"], F32(config["noise_scale"]))
    probe_idx, probe_val = make_rows(key_probe, traffic["probe_rows"], config)
    return (fit_loop.lam_order(seed, traffic["lam_grid"]), idx, val, Y, probe_idx, probe_val)


def reference_args(config: Dict[str, Any]) -> Dict[str, Any]:
    """What the plain reference takes from a configuration's file."""
    return dict(d=config["num_features"], iterations=config["iterations"],
                history=config["history"], tol=config["tolerance"])


def sparse_dataset(idx, val):
    from keystone_tpu.data import Dataset

    return Dataset({"indices": idx, "values": val}, n=int(idx.shape[0]))


def build_pipeline(config: Dict[str, Any], lam: float, idx, val, Y):
    """The configuration's entry: the solver is the cost model's choice
    (``selector`` is empty at the cell's size; a rehearsal alone fills it)."""
    from keystone_tpu.data import Dataset
    from keystone_tpu.ops.learning.cost import LeastSquaresEstimator

    return LeastSquaresEstimator(lam=lam, **config["selector"]).with_data(
        sparse_dataset(idx, val), Dataset.of(Y))


def fit_once(config, lam: float, idx, val, Y):
    """One whole new fit: no saved state reused, weights ready on return."""
    from keystone_tpu.workflow import PipelineEnv

    PipelineEnv.get_or_create().reset()
    fitted = build_pipeline(config, lam, idx, val, Y).fit()
    jax.block_until_ready(fit_loop.device_arrays(fitted))
    jax.block_until_ready(fit_loop._fence(Y[0, 0]))  # after every program of the fit
    return fitted


def probe_scores(fitted, probe_idx, probe_val) -> np.ndarray:
    return np.asarray(fitted.apply(sparse_dataset(probe_idx, probe_val)).array,
                      dtype=np.float32)


def iterations_run(fitted) -> List[int]:
    """The L-BFGS iterations the fitted pipeline says it ran, where the
    program exposes them (``lbfgs_iterations`` on the fitted mapper)."""
    return [int(o.lbfgs_iterations) for o in fit_loop.walk(fitted)
            if getattr(o, "lbfgs_iterations", None) is not None
            and not isinstance(o, (jax.Array, np.ndarray))]


def compare(kept, failed: int, problem, config, limits) -> Dict[str, Any]:
    """Each kept fit's probe scores against the plain reference's for the
    same lambda; the worst of each gap stands beside its limit."""
    _, idx, val, Y, probe_idx, probe_val = problem
    compared = {"fits_failed": {"value": failed, "limit": 0}}
    if not kept:
        return compared
    want, want_its = reference.fit_and_score(
        idx, val, Y, probe_idx, probe_val, [lam for _, lam, _, _ in kept],
        **reference_args(config))
    gaps = np.array([reference.score_gaps(got, want[lam]) for _, lam, got, _ in kept])
    big = float(np.finfo(np.float32).max)  # JSON has no inf
    worst = np.nan_to_num(gaps, nan=big, posinf=big).max(axis=0)
    for name, value in zip(("score_rel_fro", "score_widest"), worst):
        compared[name] = {"value": float(value), "limit": limits[name]["limit"]}
    its = [abs(got - want_its[lam]) for _, lam, _, ran in kept for got in ran]
    if its:
        compared["iterations_gap"] = {"value": int(max(its)), "limit": MAX_ITERATION_GAP}
    return compared


def run(cell: Dict[str, Any], *, seed: int, seconds: float, trace: bool,
        devices) -> Dict[str, Any]:
    config, traffic = cell["config_data"], cell["traffic_data"]
    counter = fit_loop.CompileCounter()
    t_start = time.perf_counter()
    problem = make_problem(cell, seed)
    lams, idx, val, Y = problem[:4]

    def fit(lam: float):
        return fit_once(config, lam, idx, val, Y)

    jax.block_until_ready(problem[1:])
    say("rows made")
    t_rows = time.perf_counter()
    rows_bytes = (devices[0].memory_stats() or {}).get("bytes_in_use", 0)
    notes = [f"fitted model classes: {fit_loop.program_classes(fit(lams[-1]))}"]  # warm-up
    gc.collect()
    say("warm-up fit done")
    notes.append(f"set-up: rows {t_rows - t_start:.2f} s, warm-up fit "
                 f"{time.perf_counter() - t_rows:.2f} s (device bytes in use with the rows "
                 f"made: {rows_bytes}), {counter.programs} programs "
                 f"compiled or fetched in {counter.seconds:.2f} s, persistent cache "
                 f"{counter.cache}")

    compiles_before = counter.programs
    with fit_loop.maybe_trace(trace) as tracing:
        window = fit_loop.measure_window(fit, lams, seconds, traffic["compare_fits"],
                                         np.random.default_rng(seed))
    window_compiles = counter.programs - compiles_before
    say(f"window closed: {window['attempted']} fits in {window['window_s']:.1f} s")
    stats = [d.memory_stats() for d in devices]
    peak = max((s or {}).get("peak_bytes_in_use", 0) for s in stats)

    kept = [(i, lam, probe_scores(f, *problem[4:]), iterations_run(f))
            for i, lam, f in window.pop("kept")]
    from keystone_tpu.workflow import PipelineEnv

    PipelineEnv.get_or_create().reset()  # the program's state goes before the reference runs
    gc.collect()
    fits = window["attempted"] - window["failed"]
    notes.append(f"window: {fits} fits in {window['window_s']:.3f} s; compared fits "
                 f"{[i for i, *_ in kept]}, iterations {[ran for *_, ran in kept]}; seconds "
                 f"of each fit (the time between fits apart): "
                 f"{[round(s, 3) for s in window['fit_seconds']]}")
    compared = compare(kept, window["failed"], problem, config, cell["limits"])
    correct = all(p["value"] <= p["limit"] for p in compared.values())
    return {
        "correct": correct, "attempted": window["attempted"], "failed": window["failed"],
        "compared": compared, "notes": notes, "memory_peak_bytes": peak,
        "window_started_at": window["started"], "trace_dir": tracing.get("dir"),
        "window": {"fits": fits, "window_s": window["window_s"], "rows": traffic["rows"]},
        "counters": {"window_compiles": window_compiles},
        "end_to_end": {"fit_s": window["window_s"] / max(fits, 1),
                       "peak_hbm_gb": peak / 1e9},
    }
