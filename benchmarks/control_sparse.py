"""Readings for the limits of ``correct`` in the sparse cell: not part of a
benchmark run. ``benchmarks.control`` for ``sparse_fit_loop``'s cells.

    python3 -m benchmarks.control_sparse --workload amazon_lbfgs_fit_4m --seeds 6 --control-seeds 2

In ONE process (set-up is long), at the cell's own size, for each seed:

* the LOWER reading — the program's fit through the cell's own entry
  against the plain reference at ``highest`` (what a sound run reads), with
  the iterations each ran;
* on the first ``--control-seeds`` seeds the CONTROL — the reference put in
  the program's place with its two products in one bf16 pass — against the
  reference at ``highest``. Its smallest reading is the upper one.

One JSON line per seed on standard output; ``limits/<cell>.json`` records
what the limits were set from.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from typing import List, Optional

import jax

from benchmarks import run as bench_run
from benchmarks.drivers import sparse_fit_loop as driver
from benchmarks.reference import amazon as reference

CONTROL_PRECISION = "bf16"


def readings(cell, seed: int, control: bool, lams_per_seed: int = 2):
    config = cell["config_data"]
    problem = driver.make_problem(cell, seed)
    lams, (idx, val, Y, probe_idx, probe_val) = problem[0][:lams_per_seed], problem[1:]
    got, ran, seconds = {}, {}, {}
    for lam in lams:
        started = time.perf_counter()
        fitted = driver.fit_once(config, lam, idx, val, Y)
        seconds[lam] = time.perf_counter() - started
        got[lam] = driver.probe_scores(fitted, probe_idx, probe_val)
        ran[lam] = driver.iterations_run(fitted)
        del fitted
    from keystone_tpu.workflow import PipelineEnv

    PipelineEnv.get_or_create().reset()
    gc.collect()
    shared = driver.reference_args(config)
    want, want_its = reference.fit_and_score(idx, val, Y, probe_idx, probe_val, lams, **shared)
    line = {"seed": seed, "lams": lams,
            "program": [reference.score_gaps(got[lam], want[lam]) for lam in lams],
            "iterations": [[ran[lam], want_its[lam]] for lam in lams],
            "fit_seconds": [seconds[lam] for lam in lams]}
    if control:
        lowered, _ = reference.fit_and_score(idx, val, Y, probe_idx, probe_val, lams,
                                             precision=CONTROL_PRECISION, **shared)
        line[CONTROL_PRECISION] = [reference.score_gaps(lowered[lam], want[lam]) for lam in lams]
    return line


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(prog="benchmarks.control_sparse")
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", type=int, default=6)
    parser.add_argument("--control-seeds", type=int, default=2)
    parser.add_argument("--first-seed", type=int, default=2_147_500_000)
    parser.add_argument("--rehearse", action="store_true")
    args = parser.parse_args(argv)
    cell = bench_run.load_cell(args.workload, rehearse=args.rehearse)
    if not args.rehearse and jax.devices()[0].platform == "cpu":
        print("benchmarks.control_sparse: no accelerator", file=sys.stderr)
        return 2
    if not args.rehearse:
        bench_run.keep_compile_cache(jax)
    for i in range(args.seeds):
        started = time.perf_counter()
        line = readings(cell, args.first_seed + 7919 * i, control=i < args.control_seeds)
        line["seconds"] = time.perf_counter() - started
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
