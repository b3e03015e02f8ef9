"""Readings for the limits of ``correct`` in the image cell: not part of a
benchmark run.

    python3 -m benchmarks.control_cifar --workload cifar_patch_fit_50k --seeds 6 --control-seeds 2

In ONE process, at the cell's own size, for each seed the LOWER reading —
the program's fit through the cell's own entry (``drivers/image_fit_loop``)
against ``reference/cifar_patch.py`` at ``highest``, on two ridge values of
which one is the grid's smallest — and on the first ``--control-seeds``
seeds the CONTROL: the reference in one bf16 pass (``bf16``: every
contraction's operands rounded to bfloat16) in the program's place, against
the reference at ``highest`` — the probe scores at both ridge values and
the probe images' pooled features. One JSON line per seed on standard output;
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
import numpy as np

from benchmarks import run as bench_run
from benchmarks.drivers import fit_loop
from benchmarks.drivers import image_fit_loop as driver
from benchmarks.reference import cifar_patch as reference


def readings(cell, seed: int, control: bool, precisions=("bf16",)):
    config, grid = cell["config_data"], cell["traffic_data"]["lam_grid"]
    lams, images, Y, probe = driver.make_problem(cell, seed)
    lams = [grid["low"], next(lam for lam in lams if lam != grid["low"])]
    got, features = {}, None
    for lam in lams:
        fitted = driver.fit_at(config, lam, images, Y)
        got[lam] = fit_loop.probe_scores(fitted, probe)
        features = driver.probe_features(fitted, probe)
        del fitted
    from keystone_tpu.workflow import PipelineEnv

    PipelineEnv.get_or_create().reset()
    gc.collect()
    want, want_features = reference.fit_score_and_features(images, Y, probe, lams, config=config)
    line = {"seed": seed, "lams": lams,
            "program": [reference.score_gaps(got[lam], want[lam]) for lam in lams],
            "program_features": driver.feature_gap(features, want_features)}
    if control:
        for precision in precisions:
            lowered, lowered_features = reference.fit_score_and_features(
                images, Y, probe, lams, config=config, precision=precision)
            line[precision] = [reference.score_gaps(lowered[lam], want[lam]) for lam in lams]
            line[precision + "_features"] = driver.feature_gap(
                np.asarray(lowered_features), want_features)
    return line


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(prog="benchmarks.control_cifar")
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", type=int, default=6)
    parser.add_argument("--control-seeds", type=int, default=2)
    parser.add_argument("--first-seed", type=int, default=2_147_600_000)
    parser.add_argument("--rehearse", action="store_true")
    args = parser.parse_args(argv)
    cell = bench_run.load_cell(args.workload, rehearse=args.rehearse)
    if not args.rehearse and jax.devices()[0].platform == "cpu":
        print("benchmarks.control_cifar: no accelerator", file=sys.stderr)
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
