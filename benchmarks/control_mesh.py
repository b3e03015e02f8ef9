"""Readings for the limits of ``correct`` in the mesh cell: not part of a
benchmark run.

    python3 -m benchmarks.control_mesh --workload timit_stream_fit_4chip --seeds 1 --control-seeds 1

``benchmarks.control`` over the configuration's mesh: in ONE process, at the
cell's own size, for each seed the LOWER reading — the program's fit over the
sharded rows through the cell's own entry against
``reference/timit_mesh.py`` at ``highest`` — and on the first
``--control-seeds`` seeds the CONTROLS — that reference in the program's
place at ``high`` (three bf16 passes) and at ``default`` (one), against
itself at ``highest``. One JSON line per seed on standard output;
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
from benchmarks.drivers import fit_loop
from benchmarks.drivers import fit_loop_mesh as driver
from benchmarks.reference import timit_mesh as reference


def readings(cell, seed: int, control: bool, devices, lams_per_seed: int = 2,
             precisions=("high", "default")):
    config = cell["config_data"]
    mesh = driver.make_mesh(config, devices)
    lams, X, Y, probe = driver.make_problem(cell, seed, mesh)
    lams = lams[:lams_per_seed]
    data, labels = driver.shard_once(X, Y, mesh)
    X, Y = data.array, labels.array
    fence_on = driver.fence_token(mesh)
    got = {}
    for lam in lams:
        fitted = driver.fit_once(config, lam, data, labels, fence_on)
        got[lam] = fit_loop.probe_scores(fitted, probe)
        del fitted
    from keystone_tpu.workflow import PipelineEnv

    PipelineEnv.get_or_create().reset()
    del data, labels
    gc.collect()
    shared = fit_loop.reference_args(config)
    want = reference.fit_and_score(X, Y, probe, lams, **shared)
    line = {"seed": seed, "lams": lams, "devices": int(mesh.devices.size),
            "program": [reference.score_gaps(got[lam], want[lam]) for lam in lams]}
    if control:
        for precision in precisions:
            lowered = reference.fit_and_score(X, Y, probe, lams, precision=precision, **shared)
            line[precision] = [reference.score_gaps(lowered[lam], want[lam]) for lam in lams]
    return line


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(prog="benchmarks.control_mesh")
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", type=int, default=3)
    parser.add_argument("--control-seeds", type=int, default=1)
    parser.add_argument("--first-seed", type=int, default=2_147_500_000)
    parser.add_argument("--rehearse", action="store_true")
    args = parser.parse_args(argv)
    cell = bench_run.load_cell(args.workload, rehearse=args.rehearse)
    devices = jax.devices()
    if not args.rehearse and (devices[0].platform == "cpu" or len(devices) < cell["chips"]):
        print(f"benchmarks.control_mesh: needs {cell['chips']} accelerator chips", file=sys.stderr)
        return 2
    if not args.rehearse:
        bench_run.keep_compile_cache(jax)
    for i in range(args.seeds):
        started = time.perf_counter()
        line = readings(cell, args.first_seed + 7919 * i, control=i < args.control_seeds,
                        devices=devices[:cell["chips"]])
        line["seconds"] = time.perf_counter() - started
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
