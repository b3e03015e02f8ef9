"""Traffic driver ``fit_loop``: a closed loop of whole fits by one caller —
a ridge sweep. Fit *i* takes its lambda from a fixed log-grid (the same set
for every seed, in an order drawn from the seed), builds a NEW pipeline
through the public entry the configuration names, fits it, and waits for
the fitted weights. The window closes at the first fit boundary at or
after ``seconds``.

What is taken from the program: the entry points of
``keystone_tpu.pipelines.timit`` and the fitted pipeline they return.
Everything else — the rows, the grid, the clocks, the comparison — is the
benchmark's own.
"""

from __future__ import annotations

import contextlib
import gc
import sys
import tempfile
import time
import traceback
from typing import Any, Callable, Dict, Iterator, List

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference import timit as reference

F32 = jnp.float32
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "hits",
                 "/jax/compilation_cache/cache_misses": "misses"}
MAX_FAILED_FITS = 3


class CompileCounter:
    """Programs JAX handed to the backend compiler (persistent-cache
    look-ups included), from ``jax.monitoring`` — a copy of
    ``chip_smoke.CompileClock``'s count."""

    def __init__(self) -> None:
        self.programs = 0
        self.seconds = 0.0
        self.cache = {"hits": 0, "misses": 0}  # of the persistent cache
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **_: Any) -> None:
        if event == _COMPILE_EVENT:
            self.programs += 1
            self.seconds += duration

    def _on_event(self, event: str, **_: Any) -> None:
        if event in _CACHE_EVENTS:
            self.cache[_CACHE_EVENTS[event]] += 1


def seed_key(seed: int):
    """A key from any whole number up to 2**62: the low 31 bits seed it and
    the rest are folded in (a 32-bit key cannot take the number whole)."""
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def make_rows(key_centres, key_rows, rows: int, d_in: int, classes: int):
    """Class-separated rows and their +-1 indicator targets, made on the
    device in one program (the shape of ``synthetic_timit``: centres
    0.6 * N(0, I), unit noise, uniform labels)."""

    @jax.jit
    def make(kc, kr):
        k_label, k_noise = jax.random.split(kr)
        centres = F32(0.6) * jax.random.normal(kc, (classes, d_in), F32)
        labels = jax.random.randint(k_label, (rows,), 0, classes)
        X = centres[labels] + jax.random.normal(k_noise, (rows, d_in), F32)
        return X, F32(2) * jax.nn.one_hot(labels, classes, dtype=F32) - F32(1)

    return make(key_centres, key_rows)


def lam_order(seed: int, grid: Dict[str, Any]) -> List[float]:
    lams = np.logspace(np.log10(grid["low"]), np.log10(grid["high"]), grid["points"])
    return [float(x) for x in np.random.default_rng(seed).permutation(lams)]


def make_problem(cell: Dict[str, Any], seed: int):
    """(lams, X, Y, probe) of ``cell`` for ``seed``: the same grid of lams in
    an order from the seed, the fit's rows, and probe rows about the same
    class centres."""
    config, traffic = cell["config_data"], cell["traffic_data"]
    key_centres, key_rows, key_probe = jax.random.split(seed_key(seed), 3)
    shape = (config["d_in"], config["num_classes"])
    X, Y = make_rows(key_centres, key_rows, traffic["rows"], *shape)
    probe, _ = make_rows(key_centres, key_probe, traffic["probe_rows"], *shape)
    return lam_order(seed, traffic["lam_grid"]), X, Y, probe


def reference_args(config: Dict[str, Any]) -> Dict[str, Any]:
    """What the plain reference takes from a configuration's file."""
    return dict(bank_seed=config["bank_seed"], num_cosines=config["num_cosines"],
                block=config["block_size"], gamma=config["gamma"],
                epochs=config["num_epochs"])


def _children(obj) -> List[Any]:
    """What an object of the program holds: containers, attributes, and
    what a closure or a partial has captured (``auto`` ends in a closure)."""
    if isinstance(obj, dict):
        return list(obj.values())
    if isinstance(obj, (list, tuple, set)):
        return list(obj)
    found: List[Any] = []
    if hasattr(obj, "__dict__"):
        found += list(vars(obj).values())
    if "<locals>" in type(obj).__qualname__:  # a class made inside a fit:
        found += list(vars(type(obj)).values())  # its methods hold the model
    for cell in getattr(obj, "__closure__", None) or ():
        with contextlib.suppress(ValueError):  # an empty cell
            found.append(cell.cell_contents)
    for name in ("__self__", "func", "args", "keywords"):
        if hasattr(obj, name):
            found.append(getattr(obj, name))
    return found


def walk(obj, _seen=None, _depth=0) -> Iterator[Any]:
    """Every object reachable from a fitted pipeline, once."""
    seen = set() if _seen is None else _seen
    if id(obj) in seen or _depth > 12:
        return
    seen.add(id(obj))
    yield obj
    if isinstance(obj, (jax.Array, np.ndarray, str, bytes, int, float)):
        return
    for child in _children(obj):
        yield from walk(child, seen, _depth + 1)


def device_arrays(fitted) -> List[jax.Array]:
    """Every jax array the fitted pipeline holds — its weights, however
    the program nests them."""
    return [o for o in walk(fitted) if isinstance(o, jax.Array)]


def program_classes(fitted) -> List[str]:
    """Names of the program's classes inside a fitted pipeline: they say
    which solver ``auto`` selected."""
    return sorted({type(o).__name__ for o in walk(fitted)
                   if type(o).__module__.startswith("keystone_tpu")})


def build_pipeline(config: Dict[str, Any], lam: float, X, Y):
    """The compositions ``timit.run`` builds, without its host-side data
    synthesis and evaluation."""
    from keystone_tpu.data import Dataset
    from keystone_tpu.pipelines import timit

    cfg = timit.TimitConfig(
        num_cosines=config["num_cosines"], gamma=config["gamma"],
        rf_type=config["rf_type"], block_size=config["block_size"],
        num_epochs=config["num_epochs"], lam=lam, seed=config["bank_seed"],
        solver=config["entry"],
    )
    data, labels = Dataset.of(X), Dataset.of(Y)
    if config["entry"] == "streaming":
        return timit.streaming_estimator(cfg).with_data(data, labels)
    if config["entry"] == "auto":
        from keystone_tpu.ops.learning.cost import LeastSquaresEstimator

        estimator = LeastSquaresEstimator(
            lam=cfg.lam, block_size=cfg.block_size, block_iters=cfg.num_epochs)
        return timit.build_featurizer(cfg).and_then(estimator, data, labels)
    raise ValueError(f"fit_loop knows no entry {config['entry']!r}")


@jax.jit
def _fence(x):
    return x + 1


def fit_once(config, lam: float, X, Y):
    """One whole new fit: no saved state reused, weights ready on return."""
    from keystone_tpu.workflow import PipelineEnv

    PipelineEnv.get_or_create().reset()
    fitted = build_pipeline(config, lam, X, Y).fit()
    jax.block_until_ready(device_arrays(fitted))
    # A chip runs its programs in order: one more, enqueued now, is ready
    # only after every program of the fit — also one whose result the walk
    # above did not reach.
    jax.block_until_ready(_fence(X[0, 0]))
    return fitted


def probe_scores(fitted, probe) -> np.ndarray:
    from keystone_tpu.data import Dataset

    return np.asarray(fitted.apply(Dataset.of(probe)).array, dtype=np.float32)


@contextlib.contextmanager
def maybe_trace(on: bool) -> Iterator[Dict[str, str]]:
    """Profile the window into a directory under ``TMPDIR``."""
    holder: Dict[str, str] = {}
    if not on:
        yield holder
        return
    holder["dir"] = tempfile.mkdtemp(prefix="bench_trace_")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0  # 700k Python frames a window otherwise
    jax.profiler.start_trace(holder["dir"], profiler_options=options)
    try:
        yield holder
    finally:
        jax.profiler.stop_trace()


def measure_window(fit: Callable[[float], Any], lams: List[float], seconds: float,
                   keep: int, rng: np.random.Generator) -> Dict[str, Any]:
    """Whole fits until ``seconds`` have passed. Keeps the first and the
    last fit and a seeded sample of those between (``keep`` in all) for the
    comparison — a fixed number, so that the memory held does not grow with
    the count of fits."""
    kept: List[Any] = []  # (index, lam, fitted)
    fit_seconds: List[float] = []
    attempted = failed = 0
    started = time.perf_counter()
    closed = started
    while True:
        fit_started = time.perf_counter()
        lam = lams[attempted % len(lams)]
        with jax.profiler.TraceAnnotation("bench.fit"):
            try:
                fitted = fit(lam)
            except Exception:  # a fit that fails is counted, and the loop goes on
                traceback.print_exc(file=sys.stderr)
                fitted = None
                failed += 1
        attempted += 1
        closed = time.perf_counter()
        fit_seconds.append(closed - fit_started)
        done = closed - started >= seconds or failed >= MAX_FAILED_FITS
        with jax.profiler.TraceAnnotation("bench.between_fits"):
            if fitted is not None:
                if len(kept) < keep:
                    kept.append((attempted - 1, lam, fitted))
                else:
                    if keep > 2 and rng.random() < 0.5:
                        kept[-2] = kept[-1]  # the last fit joins the sample between
                    kept[-1] = (attempted - 1, lam, fitted)
            del fitted
            gc.collect()
        if done:
            break
    return {"attempted": attempted, "failed": failed, "kept": kept,
            "started": started, "window_s": closed - started, "fit_seconds": fit_seconds}


def compare(kept_scores, failed: int, X, Y, probe, config, limits) -> Dict[str, Any]:
    """Each kept fit's probe scores against the plain reference's for the
    same lambda; the worst of each gap stands beside its limit. A fit that
    never came is compared exactly: none may fail."""
    compared = {"fits_failed": {"value": failed, "limit": 0}}
    if not kept_scores:
        return compared
    want = reference.fit_and_score(
        X, Y, probe, [lam for _, lam, _ in kept_scores], **reference_args(config))
    gaps = np.array([reference.score_gaps(got, want[lam]) for _, lam, got in kept_scores])
    # a score that is not a number reads as the largest float32 (JSON has no inf)
    big = float(np.finfo(np.float32).max)
    worst = np.nan_to_num(gaps, nan=big, posinf=big).max(axis=0)
    for name, value in zip(("score_rel_fro", "score_widest"), worst):
        compared[name] = {"value": float(value), "limit": limits[name]["limit"]}
    return compared


def run(cell: Dict[str, Any], *, seed: int, seconds: float, trace: bool,
        devices) -> Dict[str, Any]:
    config, traffic = cell["config_data"], cell["traffic_data"]
    counter = CompileCounter()
    t_start = time.perf_counter()
    lams, X, Y, probe = make_problem(cell, seed)

    def fit(lam: float):
        return fit_once(config, lam, X, Y)

    jax.block_until_ready((X, Y, probe))
    t_rows = time.perf_counter()
    notes = [f"fitted model classes: {program_classes(fit(lams[-1]))}"]  # warm-up
    gc.collect()
    notes.append(f"set-up: rows {t_rows - t_start:.2f} s, warm-up fit "
                 f"{time.perf_counter() - t_rows:.2f} s, {counter.programs} programs "
                 f"compiled or fetched in {counter.seconds:.2f} s, persistent cache "
                 f"{counter.cache}")

    compiles_before = counter.programs
    with maybe_trace(trace) as tracing:
        window = measure_window(fit, lams, seconds, traffic["compare_fits"],
                                np.random.default_rng(seed))
    window_compiles = counter.programs - compiles_before
    stats = [d.memory_stats() for d in devices]
    peak = max((s or {}).get("peak_bytes_in_use", 0) for s in stats)

    kept_scores = [(i, lam, probe_scores(f, probe)) for i, lam, f in window.pop("kept")]
    from keystone_tpu.workflow import PipelineEnv

    PipelineEnv.get_or_create().reset()  # the program's state goes before the reference runs
    gc.collect()
    fits = window["attempted"] - window["failed"]
    notes.append(f"window: {fits} fits in {window['window_s']:.3f} s; compared fits "
                 f"{[i for i, _, _ in kept_scores]}; seconds of each fit (the time "
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
