"""Readings for the limits of ``correct`` in the block cell: not part of a
benchmark run.

    python3 -m benchmarks.control_block --workload timit_block_fit_131k --seeds 6 --control-seeds 2

``benchmarks.control``'s readings with the cell's own driver and reference
(``drivers/block_fit_loop``, ``reference/timit_block``): in ONE process, at
the cell's own size, for each seed the LOWER reading — the program's fit
through the cell's own entry against the residual-form reference at
``highest``, on two ridge values of which one is the grid's smallest (where
five sweeps carry the most rounding forward) — and on the first
``--control-seeds`` seeds the CONTROLS: the reference put in the program's
place at ``high`` (three bf16 passes) and at ``default`` (one), against the
reference at ``highest``. One JSON line per seed on standard output;
``limits/<cell>.json`` records what the limits were set from.
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
from benchmarks.drivers import block_fit_loop, fit_loop
from benchmarks.reference import timit_block as reference


def readings(cell, seed: int, control: bool, precisions=("high", "default")):
    config, grid = cell["config_data"], cell["traffic_data"]["lam_grid"]
    lams, X, Y, probe = fit_loop.make_problem(cell, seed)
    lams = [grid["low"], next(lam for lam in lams if lam != grid["low"])]
    got, shapes = {}, []
    for lam in lams:
        fitted = block_fit_loop.fit_once(config, lam, X, Y)
        got[lam] = fit_loop.probe_scores(fitted, probe)
        shapes.append(block_fit_loop.block_weight_shapes(fitted))
        del fitted
    from keystone_tpu.workflow import PipelineEnv

    PipelineEnv.get_or_create().reset()
    gc.collect()
    shared = fit_loop.reference_args(config)
    want = reference.fit_and_score(X, Y, probe, lams, **shared)
    line = {"seed": seed, "lams": lams, "block_weights": shapes,
            "program": [reference.score_gaps(got[lam], want[lam]) for lam in lams]}
    if control:
        for precision in precisions:  # a CPU ignores these two: its tests pass "bf16"
            lowered = reference.fit_and_score(X, Y, probe, lams, precision=precision, **shared)
            line[precision] = [reference.score_gaps(lowered[lam], want[lam]) for lam in lams]
    return line


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(prog="benchmarks.control_block")
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", type=int, default=6)
    parser.add_argument("--control-seeds", type=int, default=2)
    parser.add_argument("--first-seed", type=int, default=2_147_500_000)
    parser.add_argument("--rehearse", action="store_true")
    args = parser.parse_args(argv)
    cell = bench_run.load_cell(args.workload, rehearse=args.rehearse)
    if not args.rehearse and jax.devices()[0].platform == "cpu":
        print("benchmarks.control_block: no accelerator", file=sys.stderr)
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
