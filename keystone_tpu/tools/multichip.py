"""Multichip mesh runner: ``python -m keystone_tpu.tools.multichip``
(wrapped by ``bin/multichip``).

Runs one synthetic padded-COO streamed gram fit TWICE — on a single
device and on a data-parallel mesh (``run_lbfgs_gram_streamed``'s
``mesh=`` path: per-device local folds, ONE psum tree-reduction per
fit) — and reports parity and walls. Two deployment forms:

- **Forced host devices** (``--force-host-devices 8``): the tier-1-safe
  leg — XLA splits the host CPU into N devices, so the mesh *program*
  (sharding, liveness masking, the psum) is exercised with no chips.
  Walls measured this way are NOT device evidence (N ways of one CPU);
  the runner says so rather than printing a fake speedup.
- **Real chips** (no ``--force-host-devices``: the run uses whatever
  backend JAX finds, and prints it): the measurement leg — the
  walls are real, the layout decision (``cost.choose_mesh_layout``) is
  recorded as a ``mesh_layout`` CostDecision and stamped with the
  measured mesh wall, so ``bin/calibrate`` joins predicted-vs-measured
  for layouts exactly like solver decisions.

Exit code: 0 when the mesh fit matches the single-device fit within
``--tol``, 1 otherwise (or on setup errors).
"""

from __future__ import annotations

import argparse
import os
import sys
import time
from typing import List, Optional, Sequence

__all__ = ["main", "run", "run_scaling"]

# Max |dW| between the 1-device and mesh fits. The forced-host leg is
# the SAME arithmetic scheduled differently (per-device partial folds +
# one tree reduction), so the bound is float-reassociation noise — the
# MULTICHIP_r05 dry-run pinned 3.43e-07 for the streaming leg; the
# default keeps headroom over it for bigger geometries.
DEFAULT_TOL = 5e-5


def _parse_layout(spec: str):
    try:
        p, q = spec.lower().split("x")
        return max(int(p), 1), max(int(q), 1)
    except ValueError:
        raise SystemExit(
            f"--layout {spec!r}: expected '<data>x<model>', e.g. 8x1"
        )


def _synth_coo(args):
    """The runner's synthetic padded-COO problem (ragged rows via dead
    lanes) chunked for the streamed fold."""
    import numpy as np

    n, d, w, k, c = args.n, args.d, args.nnz, args.k, args.chunk
    rng = np.random.default_rng(args.seed)
    idx = rng.integers(0, d, size=(n, w)).astype(np.int32)
    idx[rng.random((n, w)) < 0.2] = -1  # ragged rows: dead lanes
    val = rng.normal(size=(n, w)).astype(np.float32)
    Y = rng.normal(size=(n, k)).astype(np.float32)
    nchunks = -(-n // c)
    pad = nchunks * c - n
    idx_t = np.pad(idx, ((0, pad), (0, 0)), constant_values=-1)
    val_t = np.pad(val, ((0, pad), (0, 0)))
    y_t = np.pad(Y, ((0, pad), (0, 0)))
    return nchunks, (
        idx_t.reshape(nchunks, c, w),
        val_t.reshape(nchunks, c, w),
        y_t.reshape(nchunks, c, k),
    )


def run(args) -> int:
    import jax
    import jax.numpy as jnp

    from keystone_tpu import obs
    from keystone_tpu.ops.learning import cost as cost_mod
    from keystone_tpu.ops.learning.lbfgs import (
        _resident_chunk_fn,
        run_lbfgs_gram_streamed,
    )
    from keystone_tpu.parallel import mesh as mesh_lib
    from keystone_tpu.utils.startup import device_summary

    device = device_summary()
    backend, avail = device["backend"], device["device_count"]
    n, d, w, k, c = args.n, args.d, args.nnz, args.k, args.chunk

    if args.layout == "auto":
        (p, q), ref = cost_mod.choose_mesh_layout(
            n, d, k, nnz_per_row=w, num_devices=avail,
        )
        layout_src = "cost.choose_mesh_layout"
    else:
        p, q = _parse_layout(args.layout)
        ref = None
        layout_src = "forced"
    if p * q > avail:
        print(
            f"multichip: layout {p}x{q} needs {p * q} devices, "
            f"{avail} available ({backend})", file=sys.stderr,
        )
        return 1

    nchunks, operands = _synth_coo(args)

    kw = dict(
        lam=args.lam, num_iterations=args.iters, convergence_tol=1e-8,
        n=n, val_dtype=jnp.float32,
    )

    t0 = time.perf_counter()
    W1, loss1 = run_lbfgs_gram_streamed(
        _resident_chunk_fn, nchunks, d, k, operands=operands,
        max_chunks_per_dispatch=args.seg, **kw,
    )
    W1.block_until_ready()
    single_s = time.perf_counter() - t0

    if q > 1:
        mesh = mesh_lib.make_mesh(
            (p, q), (mesh_lib.DATA_AXIS, mesh_lib.MODEL_AXIS),
            devices=jax.devices()[: p * q],
        )
    else:
        mesh = mesh_lib.make_mesh(
            (p,), (mesh_lib.DATA_AXIS,), devices=jax.devices()[:p],
        )
    t0 = time.perf_counter()
    Wm, lossm = run_lbfgs_gram_streamed(
        _resident_chunk_fn, nchunks, d, k, operands=operands,
        max_chunks_per_dispatch=args.seg, mesh=mesh, **kw,
    )
    Wm.block_until_ready()
    mesh_s = time.perf_counter() - t0
    if ref is not None:
        ref.stamp(mesh_s, timing="wall")

    parity = float(jnp.max(jnp.abs(W1 - Wm)))
    ok = parity <= args.tol
    print(f"backend={backend} device_kind={device['device_kind']!r} "
          f"devices={avail} layout={p}x{q} ({layout_src})")
    print(f"geometry: n={n} d={d} nnz/row={w} k={k} chunk={c} "
          f"seg={args.seg} iters={args.iters}")
    print(f"single-device wall: {single_s:.3f}s (loss {float(loss1):.6f})")
    print(f"mesh wall:          {mesh_s:.3f}s (loss {float(lossm):.6f})")
    if backend == "cpu":
        # N forced host devices share ONE CPU's cycles: the mesh wall is
        # program-correctness evidence, never a speedup claim.
        print("note: cpu backend — walls are not device evidence "
              "(forced host devices share one CPU); parity is the "
              "result here")
    else:
        print(f"speedup: {single_s / mesh_s:.2f}x "
              f"(num_devices={p * q}, "
              f"single_device_baseline_s={single_s:.3f})")
    print(f"parity max|dW|: {parity:.3e} "
          f"({'OK' if ok else 'FAIL'}, tol {args.tol:.1e})")
    if obs.enabled():
        print("trace: mesh_layout decision + fold.segment device spans "
              "recorded")
    return 0 if ok else 1


def run_scaling(args) -> int:
    """``--scaling``: the same fit at 1/2/4/8 devices (data-parallel
    meshes over device prefixes), each leg warmed then min-of-``--reps``.
    Per-leg walls are split into the fold phase (sum of ``fold.segment``
    span time — the parallel part) and the solve remainder (the ONE psum
    + the replicated L-BFGS-on-G solve — the Amdahl term that bends the
    scaling curve), so the bend is ATTRIBUTED, not guessed. Emits one
    machine-readable ``scaling: {json}`` line (bench.py's
    multichip_timit_scaling row parses it); exit code is the parity
    verdict of every leg against the 1-device fit."""
    import json as _json

    import jax
    import jax.numpy as jnp

    from keystone_tpu import obs
    from keystone_tpu.ops.learning.lbfgs import (
        _resident_chunk_fn,
        run_lbfgs_gram_streamed,
    )
    from keystone_tpu.parallel import mesh as mesh_lib
    from keystone_tpu.utils.startup import device_summary

    device = device_summary()
    backend, avail = device["backend"], device["device_count"]
    legs_m = [m for m in (1, 2, 4, 8) if m <= avail]
    nchunks, operands = _synth_coo(args)
    n, d, k = args.n, args.d, args.k
    kw = dict(
        lam=args.lam, num_iterations=args.iters, convergence_tol=1e-8,
        n=n, val_dtype=jnp.float32,
    )
    print(f"backend={backend} device_kind={device['device_kind']!r} "
          f"devices={avail} scaling legs={legs_m}")
    print(f"geometry: n={n} d={d} nnz/row={args.nnz} k={k} "
          f"chunk={args.chunk} seg={args.seg} iters={args.iters}")

    legs = []
    W_ref = None
    worst_parity = 0.0
    for m in legs_m:
        mesh = None
        if m > 1:
            mesh = mesh_lib.make_mesh(
                (m,), (mesh_lib.DATA_AXIS,), devices=jax.devices()[:m],
            )

        def fit():
            return run_lbfgs_gram_streamed(
                _resident_chunk_fn, nchunks, d, k, operands=operands,
                max_chunks_per_dispatch=args.seg, mesh=mesh, **kw,
            )

        W, _ = fit()  # warm: compile + first execute, untimed
        W.block_until_ready()
        wall = float("inf")
        fold_s = None
        for _ in range(max(args.reps, 1)):
            # In-memory trace per rep (only when the caller isn't already
            # tracing) splits the wall into fold vs solve phases.
            tr = None if obs.enabled() else obs.tracing()
            t0 = time.perf_counter()
            if tr is not None:
                with tr as t:
                    W, _ = fit()
                    W.block_until_ready()
            else:
                W, _ = fit()
                W.block_until_ready()
            rep_wall = time.perf_counter() - t0
            if rep_wall < wall:
                wall = rep_wall
                if tr is not None:
                    fold_s = sum(
                        e.get("dur_us", 0) for e in t.events
                        if e.get("type") == "span"
                        and e.get("name") == "fold.segment"
                    ) / 1e6
        if W_ref is None:
            W_ref = W
        parity = float(jnp.max(jnp.abs(W - W_ref)))
        worst_parity = max(worst_parity, parity)
        leg = {"num_devices": m, "wall_s": round(wall, 4),
               "parity_max_dw": parity}
        if fold_s is not None:
            leg["fold_s"] = round(min(fold_s, wall), 4)
            leg["solve_s"] = round(max(wall - fold_s, 0.0), 4)
        legs.append(leg)
        print(f"  m={m}: wall {wall:.3f}s"
              + (f" (fold {leg['fold_s']:.3f}s, solve+psum "
                 f"{leg['solve_s']:.3f}s)" if fold_s is not None else ""))

    t1 = legs[0]["wall_s"]
    for leg in legs:
        # The scaling-claim audit rule (bench.py _scaling_violations):
        # every speedup/scaling_efficiency claim carries its numeric
        # num_devices and single_device_baseline_s in the SAME dict.
        leg["speedup_vs_single_device"] = round(t1 / leg["wall_s"], 4)
        leg["scaling_efficiency"] = round(
            t1 / leg["wall_s"] / leg["num_devices"], 4,
        )
        leg["single_device_baseline_s"] = t1

    have_phases = all("fold_s" in leg for leg in legs)
    if have_phases:
        bend = {
            "phase": "gram_solve+psum",
            "note": (
                "the fold phase shards across devices; the one psum and "
                "the replicated L-BFGS-on-G solve do not — their share "
                f"grows from {legs[0]['solve_s'] / max(t1, 1e-9):.0%} of "
                f"the 1-device wall to "
                f"{legs[-1]['solve_s'] / max(legs[-1]['wall_s'], 1e-9):.0%}"
                f" at {legs[-1]['num_devices']} devices (Amdahl term)"
            ),
        }
    else:
        bend = {"phase": "unattributed",
                "note": "phase split unavailable (outer tracing active)"}

    device_evidence = backend != "cpu"
    if not device_evidence:
        print("note: cpu backend — walls are not device evidence "
              "(forced host devices share one CPU); parity and the "
              "phase decomposition are the result here")
    ok = worst_parity <= args.tol
    print(f"parity max|dW| (worst leg): {worst_parity:.3e} "
          f"({'OK' if ok else 'FAIL'}, tol {args.tol:.1e})")
    print("scaling: " + _json.dumps({
        **device, "device_evidence": device_evidence,
        "legs": legs, "bend": bend,
        "geometry": {"n": n, "d": d, "nnz_per_row": args.nnz, "k": k,
                     "chunk": args.chunk, "seg": args.seg,
                     "iters": args.iters},
        "parity_worst_max_dw": worst_parity, "parity_tol": args.tol,
    }))
    return 0 if ok else 1


def main(argv: Optional[Sequence[str]] = None) -> int:
    parser = argparse.ArgumentParser(
        "keystone-multichip", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    parser.add_argument("--layout", default="auto",
                        help="'<data>x<model>' mesh shape, or 'auto' "
                             "(cost.choose_mesh_layout picks and the "
                             "decision is recorded)")
    parser.add_argument("--force-host-devices", type=int, default=0,
                        help="split the host CPU into N XLA devices "
                             "(must run before jax initializes; the "
                             "tier-1-safe parity leg)")
    parser.add_argument("--scaling", action="store_true",
                        help="run the 1/2/4/8-device scaling legs and "
                             "emit a machine-readable 'scaling:' JSON "
                             "line (bench multichip_timit_scaling row)")
    parser.add_argument("--reps", type=int, default=2,
                        help="warm reps per scaling leg (min taken)")
    parser.add_argument("--n", type=int, default=20_000)
    parser.add_argument("--d", type=int, default=256)
    parser.add_argument("--nnz", type=int, default=16,
                        help="active lanes per padded-COO row")
    parser.add_argument("--k", type=int, default=2)
    parser.add_argument("--chunk", type=int, default=512,
                        help="rows per fold chunk")
    parser.add_argument("--seg", type=int, default=4,
                        help="chunks per dispatched fold segment")
    parser.add_argument("--iters", type=int, default=30)
    parser.add_argument("--lam", type=float, default=0.1)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--tol", type=float, default=DEFAULT_TOL)
    parser.add_argument("--trace", default="",
                        help="write a trace directory (mesh_layout "
                             "decision, per-device spans)")
    args = parser.parse_args(list(argv) if argv is not None else None)

    if args.force_host_devices:
        # XLA reads the flag at BACKEND initialization, not at module
        # import — setting it here works as long as nothing has queried
        # jax.devices() yet; the count check below catches the too-late
        # case (an already-initialized single-device backend).
        if "--xla_force_host_platform_device_count" not in \
                os.environ.get("XLA_FLAGS", ""):
            os.environ["XLA_FLAGS"] = (
                f"--xla_force_host_platform_device_count="
                f"{args.force_host_devices} "
                + os.environ.get("XLA_FLAGS", "")
            )
        # Forced host devices ARE the CPU platform: the flag is the
        # request, whatever JAX_PLATFORMS the environment carries.
        os.environ["JAX_PLATFORMS"] = "cpu"
        import jax

        if len(jax.devices()) < args.force_host_devices:
            print(
                f"multichip: wanted {args.force_host_devices} forced "
                f"host devices but the backend initialized with "
                f"{len(jax.devices())} — set XLA_FLAGS="
                "--xla_force_host_platform_device_count=N before any "
                "jax.devices() call (bin/multichip does)",
                file=sys.stderr,
            )
            return 1

    from keystone_tpu.utils.startup import enable_compile_cache

    enable_compile_cache()
    entry = run_scaling if args.scaling else run
    if args.trace:
        from keystone_tpu import obs

        with obs.tracing(args.trace):
            rc = entry(args)
        print(f"trace written: {args.trace}")
        return rc
    return entry(args)


if __name__ == "__main__":
    sys.exit(main())
