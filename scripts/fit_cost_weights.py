"""Fit the solver cost-model weights from measured TPU DEVICE time.

The reference derives its cpu/mem/network weights by regressing measured
solver times on a 16-node cluster (scripts/constantEstimator.R, consumed
by LeastSquaresEstimator.scala:28-31). This is the TPU edition, round-13
form: the script is now ONLY the measurement harness — every timed
(engine, geometry) point is recorded as a ``calibration_sweep``
cost-decision event with its measured outcome stamped on, and the
fitting itself is the calibration plane's trace-driven refit
(``keystone_tpu/obs/calibrate.py`` — the SAME join → fit path
``bin/calibrate --refit`` runs on production traces, so there is
exactly one weight-fitting implementation).

Measurement discipline (kept from round 6):

  - DEVICE time, not wall: every point is min-of-N warm wall minus a
    calibrated null-dispatch round trip (the round-5 fit regressed on
    walls that were mostly per-dispatch host overhead and produced
    weights off by five orders of magnitude).
  - bench-adjacent geometries: the grid runs up to the largest shapes
    the attached chip fits (OOM points are skipped and reported), so
    the rates come from the regime the selector actually discriminates
    in, not from sub-millisecond toys.
  - the max() form the selector evaluates: time ≈ max(cpu·flops,
    mem·bytes) + net·network, with each solver's own cost() extractor
    providing the features (calibrate.fit_weights).
  - the sparse gather engine's random-access multiplier is refit from
    the gather rows GIVEN the dense (cpu, mem).
  - the network weight is PINNED (cost.TPU_NETWORK_WEIGHT): a
    single-chip fit cannot observe it.

Output: the refit constants (paste into cost.py's TPU_* block, or —
the preferred round-13 route — activate the written artifact directly
with ``KEYSTONE_COST_WEIGHTS=calibrated:<out>``), per-engine residuals,
and the measured pairwise orderings the replay test pins. With
``--from-trace DIR`` the sweep is skipped entirely and the refit runs
on an existing traced run (what ``bin/calibrate --refit`` wraps).

Usage: python scripts/fit_cost_weights.py [--quick] [--out ART.json]
                                          [--trace-dir DIR]
       python scripts/fit_cost_weights.py --from-trace DIR [--out ...]
"""

import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))


def dispatch_overhead(reps: int = 5) -> float:
    """Calibrate the per-dispatch round-trip cost with a null program."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def null(x):
        return x + 1.0

    x = jnp.zeros(())
    float(null(x))  # compile
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        float(null(x))
        best = min(best, time.perf_counter() - t0)
    return best


def time_solver(est, data, labels, overhead: float, reps: int = 2) -> float:
    """Min-of-N warm fit wall minus the calibrated dispatch overhead —
    the device-time estimate for one (solver, geometry) point."""
    import jax.numpy as jnp

    def run():
        m = est.fit(data, labels)
        # The scalar's host transfer is the execution barrier.
        x = getattr(m, "x", None)
        probe = x if x is not None else next(
            v for v in vars(m).values() if isinstance(v, jnp.ndarray)
        )
        return float(jnp.sum(jnp.abs(jnp.asarray(probe))))

    run()  # warmup/compile
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        run()
        best = min(best, time.perf_counter() - t0)
    return max(best - overhead, 1e-6)


def record_point(est, context, measured_s: float) -> None:
    """Record one timed (engine, geometry) point as a single-candidate
    ``calibration_sweep`` decision with its measured outcome stamped on
    — the row shape the trace-driven refit joins, identical to a
    production decision the executor back-annotated."""
    from keystone_tpu import obs
    from keystone_tpu.ops.learning import cost as cost_mod

    label = cost_mod.candidate_label(est)
    cpu, mem, net = cost_mod.active_weights()
    try:
        predicted = est.cost(
            context["n"], context["d"], context["k"],
            context["sparsity"], context["machines"], cpu, mem, net,
        )
    except TypeError:  # estimators without a cost extractor
        predicted = None
    ref = obs.record_cost_decision(obs.CostDecision(
        decision="calibration_sweep",
        winner=label,
        candidates=[{
            "label": label,
            "cost_s": (None if predicted is None else float(predicted)),
            "feasible": True,
        }],
        reason="sweep",
        context={
            **context,
            "weights": {
                "cpu": cpu, "mem": mem, "network": net,
                "family": cost_mod.weights_family_name(),
            },
        },
    ))
    if ref is not None:
        # min_of_N_warm: time_solver warms/compiles first and subtracts
        # the calibrated dispatch round trip — device time, the row
        # family the refit trusts most.
        ref.stamp(measured_s, timing="min_of_N_warm")


def run_sweep(quick: bool) -> None:
    """Time the solver grid, recording every point into the active
    tracer as a stamped ``calibration_sweep`` decision."""
    import jax
    import jax.numpy as jnp

    from keystone_tpu.data import Dataset
    from keystone_tpu.ops.learning.block import BlockLeastSquaresEstimator
    from keystone_tpu.ops.learning.lbfgs import (
        DenseLBFGSwithL2,
        SparseLBFGSwithL2,
    )
    from keystone_tpu.ops.learning.linear import LinearMapEstimator

    machines = max(len(jax.devices()), 1)
    overhead = dispatch_overhead()
    print(f"null-dispatch overhead: {overhead * 1e3:.1f} ms (subtracted)")

    dense_shapes = (
        [(16384, 1024, 16), (65536, 2048, 32)]
        if quick
        else [
            (16384, 1024, 16),
            (65536, 2048, 32),
            (131072, 4096, 64),
            (65536, 8192, 32),
            (262144, 4096, 147),  # bench-adjacent: TIMIT-block-shaped
        ]
    )
    rng = np.random.default_rng(0)
    for n, d, k in dense_shapes:
        X = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
        Y = jnp.asarray(rng.normal(size=(n, k)).astype(np.float32))
        data, labels = Dataset.of(X), Dataset.of(Y)
        solvers = [
            ("exact", LinearMapEstimator(1e-3)),
            ("lbfgs", DenseLBFGSwithL2(lam=1e-3, num_iterations=20)),
            ("block", BlockLeastSquaresEstimator(min(1000, d), 3, lam=1e-3)),
        ]
        for name, est in solvers:
            try:
                secs = time_solver(est, data, labels, overhead)
            except Exception as e:  # OOM etc: skip the point, say so
                print(f"skip {name} n={n} d={d} k={k}: {type(e).__name__}")
                continue
            record_point(est, {
                "n": n, "d": d, "k": k, "sparsity": 1.0,
                "machines": machines,
            }, secs)
            print(f"{name:7s} n={n:7d} d={d:5d} k={k:3d}: {secs:7.3f}s device")

    # Sparse gather/gram points at the amazon-row geometry family.
    for n, d, nnz, k in [(250_000, 16384, 82, 2), (500_000, 16384, 82, 2)]:
        if quick and n > 250_000:
            continue
        idx = rng.integers(0, d, size=(n, nnz)).astype(np.int32)
        idx.sort(axis=1)
        vals = rng.normal(size=(n, nnz)).astype(np.float32)
        sp = Dataset(
            {"indices": jnp.asarray(idx), "values": jnp.asarray(vals)}, n=n
        )
        Y = Dataset.of(
            jnp.asarray(rng.normal(size=(n, k)).astype(np.float32))
        )
        s = nnz / d
        for solver in ("gather", "gram"):
            est = SparseLBFGSwithL2(
                lam=1e-3, num_iterations=20, num_features=d, solver=solver,
                gram_dtype="bf16" if solver == "gram" else None,
            )
            try:
                secs = time_solver(est, sp, Y, overhead)
            except Exception as e:
                print(f"skip sparse-{solver} n={n}: {type(e).__name__}")
                continue
            record_point(est, {
                "n": n, "d": d, "k": k, "sparsity": s,
                "machines": machines,
            }, secs)
            print(f"sparse-{solver:6s} n={n:7d}: {secs:7.3f}s device")


def print_refit(result) -> None:
    w = result["weights"]
    print("\nPaste into keystone_tpu/ops/learning/cost.py (or activate "
          "the artifact directly):")
    print(f"TPU_CPU_WEIGHT = {w['cpu']:.3e}")
    print(f"TPU_MEM_WEIGHT = {w['mem']:.3e}")
    print(f"TPU_NETWORK_WEIGHT = {w['network']:.3e}"
          "  # pinned: single-chip fit cannot observe the network term")
    if w["sparse_gather_overhead"] is not None:
        print("TPU_SPARSE_GATHER_OVERHEAD = "
              f"{w['sparse_gather_overhead']:.0f}.0")
    after = result["after"]
    before = result["before"]
    fmt = lambda v: "?" if v is None else f"{v:.3f}"  # noqa: E731
    print(f"\nresiduals (median |log error|): "
          f"{fmt(before['median_abs_log_error'])} under the base family "
          f"-> {fmt(after['median_abs_log_error'])} refit")
    for label, eng in sorted(after["per_engine"].items()):
        print(f"  {label:<40} n={eng['count']:<3} "
              f"med|err|={fmt(eng['median_abs_log_error'])}")

    # Measured pairwise orderings the replay test pins: per geometry,
    # engines ranked by their measured seconds.
    outcomes = [
        o for o in result["outcomes"] if o.measured_s is not None
    ]
    by_geom = {}
    for o in outcomes:
        n, d, k = (o.context.get("n"), o.context.get("d"),
                   o.context.get("k"))
        by_geom.setdefault((n, d, k), []).append(o)
    print("\nmeasured orderings (feed tests/test_cost_replay.py):")
    for geom, rows in sorted(by_geom.items()):
        if len(rows) < 2:
            continue
        rows.sort(key=lambda o: o.measured_s)
        print(f"  n,d,k={geom}: "
              + " < ".join(o.winner for o in rows))
    if result["artifact_path"]:
        print(f"\nartifact: {result['artifact_path']}")
        print("activate: KEYSTONE_COST_WEIGHTS=calibrated:"
              f"{result['artifact_path']}")


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--quick", action="store_true")
    parser.add_argument(
        "--out", default="", metavar="ART.json",
        help="write the calibration artifact here "
             "(KEYSTONE_COST_WEIGHTS=calibrated:ART.json)",
    )
    parser.add_argument(
        "--trace-dir", default="", metavar="DIR",
        help="also persist the sweep's trace (decisions + outcomes) "
             "for later re-analysis with bin/calibrate",
    )
    parser.add_argument(
        "--from-trace", default="", metavar="DIR",
        help="skip the sweep: refit from an existing traced run "
             "(the bin/calibrate --refit path)",
    )
    args = parser.parse_args()

    from keystone_tpu import obs
    from keystone_tpu.obs import calibrate as cal

    if args.from_trace:
        records = obs.load_events(args.from_trace)
    else:
        with obs.tracing(args.trace_dir or None) as t:
            run_sweep(args.quick)
            records = t.events

    result = cal.refit(records, out_path=args.out or None)
    print_refit(result)


if __name__ == "__main__":
    main()
