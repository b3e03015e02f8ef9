#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

    python3 chip_smoke.py

drives the main path once, through the entry points a user calls, at the
full width of the widest configuration this code has run on a chip:

  device    refuses to run unless ``jax.devices()[0].platform == "tpu"``;
            prints platform, device kind and count, the jax / jaxlib /
            libtpu versions, the compile-cache directory in use and which
            host data plane (native C++ or NumPy) is serving.
  kernels   the roll-call: each Pallas kernel the package dispatches (12)
            compiled by Mosaic
            (``interpret=False`` passed explicitly) at the tile shape its
            production caller uses, against the plain ``jax.numpy``
            expression beside it.
  timit     ``keystone_tpu.pipelines.timit.run`` — the function
            ``python -m keystone_tpu.run TimitPipeline`` calls — at
            d = 16,384 (440 inputs -> 4 cosine branches x 4,096 -> 147
            classes, blockSize 4,096, 3 epochs, lambda 1e-4) on 65,536
            synthetic rows, once with ``solver="auto"`` and once with
            ``solver="streaming"``; checks the train error, that the two
            fits predict the same classes, which kernels each fit
            dispatched and that they are Mosaic custom calls in the
            lowered program text; exports both fits and prints
            ``plan_compiled``.
  serve     ``keystone_tpu.run.main(["serve", ...])`` with the serve CLI's
            own defaults, then a handful of rows through the same calls it
            makes (fit -> export_plan -> MicroBatchServer.submit) against
            ``fitted.apply``.
  mesh      with four or more devices: the streaming fit with rows sharded
            over a four-device data mesh against the one-device fit.

One process: the smoke holds the chip itself and starts no child that
needs it. Every time it prints is a SMOKE READING — one cold or cached
run, compilation counted apart from the rest — never a performance
number. It exits non-zero if any phase raised, any check failed, or any
phase ran somewhere other than the TPU; the last line of standard output
is ``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.

The phases are plain functions of their sizes, so tests/test_chip_smoke.py
calls them at toy size on the CPU with interpret mode requested explicitly
— a rehearsal of control flow, never a pass.
"""

from __future__ import annotations

import argparse
import collections
import contextlib
import dataclasses
import gc
import importlib.metadata
import io
import json
import os
import re
import shutil
import sys
import tempfile
import time
import traceback
from typing import Any, Callable, Dict, Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from keystone_tpu.utils.profiling import CompileClock, compile_ledger

PHASES = ("kernels", "timit", "serve", "mesh")
TIMING_NOTE = (
    "every *_s_smoke value is a smoke reading (one run, cold or cached as "
    "stated), not a performance number"
)

# TIMIT train error on the separable synthetic frames: the r5 parity row and
# a full-size CPU run of this configuration both fit them exactly (0.00%);
# 1% is the bound past which the solve, not the data, is wrong.
TIMIT_TRAIN_ERROR_BOUND = 0.01
# The two fits solve the SAME centered ridge system with the same three
# Gauss-Seidel sweeps (residual form vs normal-equations form — identical
# iterates in exact arithmetic). In f32 they differ by reassociation of
# 16,384-wide contractions, which can only flip the argmax of a near-tie:
# at most 1 row in 1,000 may disagree.
TIMIT_MIN_AGREEMENT = 0.999
# Served vs offline: the same fitted program at another batch shape (padded
# bucket vs the exact row count) — f32 FFT + GEMM reassociation only.
SERVE_MAX_REL_SCORE_ERR = 1e-4
# Mesh vs one device: the same fold with G summed as four partials and one
# psum. Scores move by f32 reassociation only; weights are compared in the
# metric the model is used in (probe-row scores), relative to their scale.
MESH_MAX_REL_SCORE_ERR = 1e-3


@dataclasses.dataclass(frozen=True)
class TimitSizes:
    """The smoke's TIMIT configuration (width is never cut; rows are)."""

    rows: int = 65_536
    cosines: int = 4
    block: int = 4_096
    epochs: int = 3
    lam: float = 1e-4
    seed: int = 123
    probe_rows: int = 4_096
    export_max_batch: int = 8


@dataclasses.dataclass(frozen=True)
class KernelSizes:
    """Operand sizes for the roll-call. The defaults give every kernel the
    TILE its production caller gives it (the grid is cut, tiles are not):
    KRR at d=2048 (256x256x512 tiles), TIMIT blocks of 4,096 (512-wide f32
    and 1,024-wide bf16 column tiles, 512-row k tiles — the 48/64 MB
    ``vmem_limit_bytes`` requests), 147 classes (lane-padded to 256),
    the Amazon sketch chunk (256-row x 82-nnz tiles into 512x256
    output tiles), and one grid step of the image featurize (128 CIFAR
    images, 6x6 patches, 1,600 filters, 2x2 pools of 14 every 13)."""

    rows: int = 2_048
    krr_dim: int = 2_048
    krr_block: int = 1_024
    krr_classes: int = 10
    timit_in: int = 440
    block: int = 4_096
    classes: int = 147
    sketch_rows: int = 512
    sketch_nnz: int = 82
    sketch_m: int = 1_024
    sketch_d1: int = 641
    conv_filters: int = 1_600


class CheckFailed(AssertionError):
    """A smoke check did not hold."""


def check(cond: bool, message: str) -> None:
    if not cond:
        raise CheckFailed(message)


# ---------------------------------------------------------------------------
# Clocks and program text
# ---------------------------------------------------------------------------


@contextlib.contextmanager
def lowered_text() -> Iterator[Callable[[], Dict[str, int]]]:
    """While open, JAX writes the StableHLO of every program it hands to
    the compiler to a scratch directory (``jax_dump_ir_to`` — it dumps
    before the persistent-cache look-up, so cached runs dump too). Yields
    a function returning ``{kernel_name: count}`` over the Mosaic custom
    calls (``@tpu_custom_call``) in that text."""
    dump_dir = tempfile.mkdtemp(prefix="chip_smoke_ir_")
    previous = jax.config.read("jax_dump_ir_to")
    jax.config.update("jax_dump_ir_to", dump_dir)

    def mosaic_calls() -> Dict[str, int]:
        found: collections.Counter = collections.Counter()
        for name in sorted(os.listdir(dump_dir)):
            with open(os.path.join(dump_dir, name), errors="replace") as f:
                for line in f:
                    if "@tpu_custom_call" not in line:
                        continue
                    m = re.search(r'kernel_name = "([^"]+)"', line)
                    found[m.group(1) if m else "<unnamed>"] += 1
        return dict(found)

    try:
        yield mosaic_calls
    finally:
        jax.config.update("jax_dump_ir_to", previous)
        shutil.rmtree(dump_dir, ignore_errors=True)


def platforms_of(x) -> List[str]:
    """Platforms of the devices holding a jax array."""
    return sorted({d.platform for d in x.devices()})


def peak_bytes() -> Optional[List[int]]:
    """``peak_bytes_in_use`` per local device (None where the backend
    reports no memory statistics)."""
    stats = [d.memory_stats() for d in jax.local_devices()]
    if any(s is None for s in stats):
        return None
    return [int(s["peak_bytes_in_use"]) for s in stats]


# ---------------------------------------------------------------------------
# Phase: device
# ---------------------------------------------------------------------------


def _version(dist: str) -> str:
    try:
        return importlib.metadata.version(dist)
    except importlib.metadata.PackageNotFoundError:
        return "not installed"


def phase_device() -> Dict[str, Any]:
    """Name the device and refuse any platform but the TPU."""
    devices = jax.devices()
    info = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    if info["platform"] != "tpu":
        print(
            f"chip_smoke: jax.devices()[0].platform is {info['platform']!r}, "
            "not 'tpu' — this smoke proves the system on the chip and does "
            "not run anywhere else",
            file=sys.stderr,
        )
        raise SystemExit(2)

    from keystone_tpu import native
    from keystone_tpu.utils.startup import enable_compile_cache

    cache_dir = enable_compile_cache()
    report = {
        "device": info,
        "jax": jax.__version__,
        "jaxlib": _version("jaxlib"),
        "libtpu": _version("libtpu"),
        "compile_cache_dir": cache_dir,
        "compile_cache_from_env": bool(
            os.environ.get("JAX_COMPILATION_CACHE_DIR")
        ),
        "compile_cache_entries_at_start": (
            len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
        ),
        "host_data_plane": native.status(),
        "memory_stats_bytes_limit": (
            (devices[0].memory_stats() or {}).get("bytes_limit")
        ),
    }
    for key, value in report.items():
        print(f"device: {key} = {value}")
    return report


# ---------------------------------------------------------------------------
# Phase: kernel roll-call
# ---------------------------------------------------------------------------

_HI = jax.lax.Precision.HIGHEST

def _mirror(G):
    """Both triangles from a buffer whose upper triangle is the valid one
    (the ``*_acc`` kernels' contract)."""
    return jnp.triu(G) + jnp.triu(G, 1).T


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def _tn(a, b):
    """aᵀ b in exact f32."""
    return jnp.einsum("ni,nj->ij", _f32(a), _f32(b), precision=_HI)


def kernel_cases(sz: KernelSizes) -> List[Tuple[str, Callable[[bool], Tuple]]]:
    """``(name, case)`` per kernel variant; ``case(interpret)`` returns
    ``(got, want, rel_tol)`` with ``got`` from the kernel and ``want`` from
    the plain jax.numpy expression. Tolerances are relative to max|want|:
    1e-4 for f32 kernels (6-pass MXU vs XLA's HIGHEST — reassociation
    only), 2e-3 for bf16-operand kernels against the exact-f32 product of
    the SAME bf16-rounded operands (f32 accumulation over ``rows`` terms)."""
    from keystone_tpu.ops import pallas_ops as po

    rng = np.random.default_rng(0)

    def normal(*shape, scale=1.0, dtype=jnp.float32):
        return jnp.asarray(
            (scale * rng.normal(size=shape)).astype(np.float32), dtype=dtype
        )

    n, k = sz.rows, sz.classes
    cases: List[Tuple[str, Callable[[bool], Tuple]]] = []

    # -- KRR pair -----------------------------------------------------------
    gamma = 5e-4
    Xk = normal(n, sz.krr_dim)
    Yk = normal(sz.krr_block, sz.krr_dim)
    Wk = normal(n, sz.krr_classes)
    xn, yn = jnp.sum(Xk * Xk, axis=1), jnp.sum(Yk * Yk, axis=1)

    def k_ref():
        sq = xn[:, None] + yn[None, :] - 2.0 * jnp.einsum(
            "md,nd->mn", Xk, Yk, precision=_HI
        )
        return jnp.exp(-gamma * jnp.maximum(sq, 0.0))

    cases.append(("gaussian_kernel_block", lambda interpret: (
        po.gaussian_kernel_block(Xk, Yk, xn, yn, gamma, interpret=interpret),
        k_ref(), 1e-4,
    )))
    cases.append(("gaussian_resid_block", lambda interpret: (
        po.gaussian_resid_block(Xk, Yk, xn, yn, Wk, gamma,
                                interpret=interpret),
        jnp.einsum("mn,mk->nk", k_ref(), Wk, precision=_HI), 1e-4,
    )))

    # -- cosine features (the TIMIT bank: 440 inputs, one 4,096 branch) ------
    Xc = normal(n, sz.timit_in)
    Wc = normal(sz.block, sz.timit_in, scale=0.05)
    bc = jnp.asarray(
        rng.uniform(0, 2 * np.pi, size=sz.block).astype(np.float32)
    )
    cases.append(("cosine_features", lambda interpret: (
        po.cosine_features(Xc, Wc, bc, interpret=interpret),
        jnp.cos(jnp.einsum("md,nd->mn", Xc, Wc, precision=_HI) + bc),
        1e-4,
    )))

    # -- one-pass Gramian + correlation, f32 (resident BlockLS step) ---------
    A = normal(n, sz.block)
    R = normal(n, k)
    for name, fn in (("gram_corr", po.gram_corr),
                     ("gram_corr_sym", po.gram_corr_sym)):
        cases.append((name, lambda interpret, fn=fn: (
            fn(A, R, interpret=interpret), (_tn(A, A), _tn(A, R)), 1e-4,
        )))

    # -- strided column-window trio (fused-flat BCD): f32 as the library's
    # fit-fused BlockLS runs it (512-wide tiles), bf16 as the bench does
    # (1,024-wide tiles) ----------------------------------------------------
    for dtype, tag, tol in ((jnp.float32, "f32", 1e-4),
                            (jnp.bfloat16, "bf16", 2e-3)):
        F = normal(n, 2 * sz.block, dtype=dtype)
        win = F[:, sz.block:]
        dW = normal(sz.block, k, scale=0.01)
        dW_c = dW.astype(dtype)
        cases.append((f"block_gram_sym[{tag}]", lambda interpret, F=F,
                      win=win, tol=tol: (
            po.block_gram_sym(F, sz.block, sz.block, interpret=interpret),
            _tn(win, win), tol,
        )))
        cases.append((f"block_corr[{tag}]", lambda interpret, F=F, win=win,
                      dtype=dtype, tol=tol: (
            po.block_corr(F, sz.block, sz.block, R, interpret=interpret),
            _tn(win, R.astype(dtype)), tol,
        )))
        cases.append((f"block_residual_update[{tag}]", lambda interpret, F=F,
                      win=win, dW_c=dW_c, tol=tol: (
            po.block_residual_update(F, sz.block, sz.block, dW_c, R,
                                     interpret=interpret),
            R - jnp.einsum("nb,bk->nk", _f32(win), _f32(dW_c),
                           precision=_HI),
            tol,
        )))

    # -- accumulating syrk pair, bf16 1,024-wide tiles (the streamed folds:
    # the 48 MB / 64 MB vmem_limit_bytes requests) --------------------------
    Fb = normal(n, sz.block, dtype=jnp.bfloat16)
    G0 = _tn(A[: sz.block // 4], A[: sz.block // 4])  # symmetric, nonzero
    C0 = normal(sz.block, k)
    cases.append(("gram_sym_acc[bf16]", lambda interpret: (
        _mirror(po.gram_sym_acc(G0, Fb, interpret=interpret)),
        G0 + _tn(Fb, Fb), 2e-3,
    )))

    def corr_acc(interpret):
        gout, cout = po.gram_corr_sym_acc(G0, C0, Fb, R, interpret=interpret)
        return (
            (_mirror(gout), cout),
            (G0 + _tn(Fb, Fb), C0 + _tn(Fb, R.astype(jnp.bfloat16))),
            2e-3,
        )

    cases.append(("gram_corr_sym_acc[bf16]", corr_acc))

    # -- CountSketch scatter at the Amazon chunk's tile geometry -------------
    c, s, m, d1 = sz.sketch_rows, sz.sketch_nnz, sz.sketch_m, sz.sketch_d1
    idx = rng.integers(0, d1, size=(c, s)).astype(np.int32)
    val = rng.normal(size=(c, s)).astype(np.float32)
    dead = rng.random(size=(c, s)) < 0.2
    idx[dead], val[dead] = -1, 0.0
    bucket = rng.integers(0, m, size=c).astype(np.int32)
    sign = rng.choice([-1.0, 1.0], size=c).astype(np.float32)

    def sketch_ref():
        flat = jnp.where(idx >= 0, bucket[:, None] * d1 + idx, m * d1)
        out = jnp.zeros((m * d1 + 1,), jnp.float32).at[flat.reshape(-1)].add(
            (sign[:, None] * val).reshape(-1)
        )
        return out[:-1].reshape(m, d1)

    cases.append(("countsketch_scatter", lambda interpret: (
        po.countsketch_scatter(idx, val, bucket, sign, m, d1,
                               interpret=interpret),
        sketch_ref(), 1e-4,
    )))

    # -- the image featurize (RandomPatchCifar: one grid step of 128 images) -
    from keystone_tpu.ops.images.conv import Convolver, Pooler, SymmetricRectifier
    from keystone_tpu.ops.pallas_images import LANES, conv_pool_features

    images = jnp.asarray(rng.uniform(0, 255, size=(LANES, 32, 32, 3)), jnp.float32)
    filters = normal(sz.conv_filters, 108)
    means = normal(108)

    def conv_pool_ref():
        out = Convolver.device_apply((6, True, 10.0), (filters, means), images)
        out = SymmetricRectifier.device_apply((0.0, 0.25), (), out)
        out = Pooler.device_apply((13, 14, None, "sum"), (), out)
        return out.reshape(LANES, -1)

    cases.append(("conv_pool", lambda interpret: (
        conv_pool_features(images, filters, means, patch_size=6, stride=13,
                           pool_size=14, alpha=0.25, interpret=interpret),
        conv_pool_ref(), 1e-4,
    )))

    return cases


def _rel_err(got, want) -> float:
    got = got if isinstance(got, tuple) else (got,)
    want = want if isinstance(want, tuple) else (want,)
    check(len(got) == len(want), "kernel/reference output counts differ")
    worst = 0.0
    for g, w in zip(got, want):
        check(g.shape == w.shape, f"shape {g.shape} != reference {w.shape}")
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        check(bool(np.isfinite(g).all()), "kernel output is not finite")
        worst = max(worst, float(np.max(np.abs(g - w))
                                 / max(float(np.max(np.abs(w))), 1e-30)))
    return worst


def phase_kernels(
    clock: CompileClock,
    sizes: KernelSizes = KernelSizes(),
    interpret: bool = False,
    platform: str = "tpu",
) -> Dict[str, Any]:
    """Compile and check every kernel. A kernel that fails is recorded
    (with the compiler's message) and the roll-call goes on, so one run
    names every kernel that needs work; any failure fails the phase."""
    results: Dict[str, Any] = {}
    for name, case in kernel_cases(sizes):
        entry: Dict[str, Any] = {"interpret": interpret}
        with clock.measure() as timing:
            try:
                got, want, tol = case(interpret)
                got = jax.block_until_ready(got)
                first = got[0] if isinstance(got, tuple) else got
                entry.update({"rel_err": _rel_err(got, want), "tol": tol,
                              "platforms": platforms_of(first)})
                check(entry["rel_err"] <= tol,
                      f"rel err {entry['rel_err']:.3e} > tol {tol:.1e}")
                check(entry["platforms"] == [platform],
                      f"ran on {entry['platforms']}, not {platform}")
                entry["ok"] = True
            except Exception as e:  # noqa: BLE001 — recorded; fails the phase
                entry["ok"] = False
                entry["error"] = f"{type(e).__name__}: {e}"[-4000:]
        entry.update(timing)
        results[name] = entry
        status = "ok" if entry["ok"] else "FAILED"
        print(f"kernels: {name}: {status} "
              + json.dumps({k: v for k, v in entry.items() if k != "error"}),
              flush=True)
        if not entry["ok"]:
            print(f"kernels: {name}: {entry['error']}")
    failed = [n for n, r in results.items() if not r["ok"]]
    return {"ok": not failed, "failed": failed, "kernels": results}


# ---------------------------------------------------------------------------
# Phase: TIMIT fit + apply, auto and streaming
# ---------------------------------------------------------------------------


def _timit_config(sz: TimitSizes, solver: str):
    from keystone_tpu.pipelines.timit import TimitConfig

    return TimitConfig(
        num_cosines=sz.cosines, block_size=sz.block, num_epochs=sz.epochs,
        lam=sz.lam, seed=sz.seed, synthetic_n=sz.rows, solver=solver,
    )


def phase_timit(
    clock: CompileClock,
    sizes: TimitSizes = TimitSizes(),
    platform: str = "tpu",
    min_agreement: float = TIMIT_MIN_AGREEMENT,
) -> Dict[str, Any]:
    from keystone_tpu.data.loaders import synthetic_timit
    from keystone_tpu.ops import pallas_ops
    from keystone_tpu.pipelines import timit
    from keystone_tpu.serving import export_plan
    from keystone_tpu.workflow import PipelineEnv

    probe = synthetic_timit(sizes.probe_rows, seed=sizes.seed + 2)
    # Where pallas_direct_ok says yes on the TPU backend, the fits below
    # must carry Mosaic custom calls; off it (the CPU rehearsal, where a
    # forced-on kernel is interpreted) none is expected.
    kernels_expected = jax.default_backend() == "tpu" and bool(
        pallas_ops.pallas_direct_ok(probe.data.array)
    )
    report: Dict[str, Any] = {"kernels_expected": kernels_expected}
    predictions: Dict[str, np.ndarray] = {}

    for solver in ("auto", "streaming"):
        PipelineEnv.get_or_create().reset()
        gc.collect()
        with clock.measure() as timing, lowered_text() as mosaic_calls, \
                pallas_ops.record_dispatches() as dispatches:
            pipeline, train_eval, test_eval = timit.run(
                _timit_config(sizes, solver)
            )
            out = pipeline.apply(probe.data).get()
            preds = out.to_numpy()
            in_text = mosaic_calls()
        compiled = sorted({n for n, interp in dispatches if not interp})
        interpreted = sorted({n for n, interp in dispatches if interp})
        entry: Dict[str, Any] = {
            "train_error": float(train_eval.total_error),
            "test_error": float(test_eval.total_error),
            "kernels_dispatched": compiled,
            "kernels_interpreted": interpreted,
            "mosaic_custom_calls_in_lowered_text": in_text,
            "gram_sym_acc_dispatched": "gram_sym_acc" in compiled,
            "platforms": platforms_of(out.array),
            "peak_bytes_in_use": peak_bytes(),
        }
        entry.update(timing)
        with clock.measure() as export_timing:
            plan = export_plan(
                pipeline.fit(), np.zeros(timit.NUM_INPUT_FEATURES, np.float32),
                max_batch=sizes.export_max_batch,
            )
        entry["plan_compiled"] = bool(plan.compiled)
        entry["export"] = export_timing
        report[solver] = entry
        predictions[solver] = preds
        print(f"timit[{solver}]: " + json.dumps(entry))

        check(preds.shape == (sizes.probe_rows,),
              f"{solver}: predictions shape {preds.shape}")
        check(bool(((preds >= 0) & (preds < timit.NUM_CLASSES)).all()),
              f"{solver}: predicted class ids out of range")
        check(entry["train_error"] <= TIMIT_TRAIN_ERROR_BOUND,
              f"{solver}: train error {entry['train_error']:.4f} > "
              f"{TIMIT_TRAIN_ERROR_BOUND}")
        check(entry["platforms"] == [platform],
              f"{solver}: predictions live on {entry['platforms']}, "
              f"not {platform}")
        missing = [n for n in compiled if n not in in_text]
        check(not missing,
              f"{solver}: kernels {missing} were dispatched as compiled but "
              "are not Mosaic custom calls in the lowered text")
        if kernels_expected:
            check(bool(in_text),
                  f"{solver}: pallas_direct_ok said yes but the lowered "
                  "text holds no Mosaic custom call")
        del pipeline, plan, out

    agreement = float(np.mean(predictions["auto"] == predictions["streaming"]))
    report["auto_vs_streaming_agreement"] = agreement
    report["min_agreement"] = min_agreement
    print(f"timit: auto vs streaming agree on {agreement:.5f} of "
          f"{sizes.probe_rows} probe rows (bound {min_agreement})")
    check(agreement >= min_agreement,
          f"auto and streaming fits agree on only {agreement:.5f} of the "
          f"probe rows (< {min_agreement})")
    report["ok"] = True
    return report


# ---------------------------------------------------------------------------
# Phase: serve
# ---------------------------------------------------------------------------

SERVE_ARGV = ("serve", "--pipeline", "MnistRandomFFT", "--rate", "200",
              "--duration-s", "3")


def phase_serve(
    clock: CompileClock,
    argv: Sequence[str] = SERVE_ARGV,
    platform: str = "tpu",
    probe_rows: int = 8,
) -> Dict[str, Any]:
    from keystone_tpu import run as run_mod
    from keystone_tpu.data import Dataset
    from keystone_tpu.serving import MicroBatchServer, export_plan

    # 1. The CLI itself. Its summary is the last line it prints.
    captured = io.StringIO()
    with clock.measure() as timing, contextlib.redirect_stdout(captured):
        rc = run_mod.main(list(argv))
    sys.stdout.write(captured.getvalue())
    check(rc == 0, f"run.main({list(argv)}) returned {rc}")
    summary = json.loads(captured.getvalue().strip().splitlines()[-1])
    report: Dict[str, Any] = {"cli": dict(timing), "summary": summary}
    check(summary["plan_compiled"] is True, "serve: plan_compiled is not true")
    check(summary["num_samples"] > 0, "serve: completed nothing")
    check(summary["failed"] == 0, f"serve: failed = {summary['failed']}")
    check(summary["rejected"] == 0, f"serve: rejected = {summary['rejected']}")
    check(summary["backend"] == platform,
          f"serve: ran on backend {summary['backend']!r}, not {platform!r}")

    # 2. The CLI does not hand back the model: send a handful of rows
    # through the same calls it makes and compare with fitted.apply. The
    # plan ends in the solver's 10 class scores (no classifier node): the
    # served and offline programs differ in batch shape only, so scores
    # must agree to f32 reassociation (SERVE_MAX_REL_SCORE_ERR of their
    # scale) and name the same class. Whether they are bit-identical is
    # recorded for ROADMAP D1, not required.
    ns = argparse.Namespace(
        model="", pipeline="MnistRandomFFT", input_dim=784, numFFTs=4,
        blockSize=2048, fit_n=4096, seed=0,
    )
    with clock.measure() as timing:
        fitted, d_in = run_mod._serve_build_fitted(ns)
        plan = export_plan(fitted, np.zeros(d_in, np.float32), max_batch=256)
        rows = np.random.default_rng(7).normal(
            size=(probe_rows, d_in)
        ).astype(np.float32)
        server = MicroBatchServer(plan, max_batch=256, max_wait_ms=5.0,
                                  max_queue_depth=1024)
        try:
            futures = [server.submit(row) for row in rows]
            served = np.stack([np.asarray(f.result(timeout=120.0))
                               for f in futures])
        finally:
            server.close()
        offline = fitted.apply(Dataset.of(jnp.asarray(rows)))
    want = offline.to_numpy()
    check(served.shape == want.shape,
          f"served shape {served.shape} != fitted.apply {want.shape}")
    rel_err = float(np.max(np.abs(served - want)) / np.max(np.abs(want)))
    report["rows"] = dict(timing)
    report["rows"].update({
        "plan_compiled": bool(plan.compiled),
        "rows": probe_rows,
        "rel_score_err": rel_err,
        "rel_score_tol": SERVE_MAX_REL_SCORE_ERR,
        "bit_identical": bool((served == want).all()),
        "served_classes": served.argmax(axis=1).tolist(),
        "offline_classes": want.argmax(axis=1).tolist(),
        "platforms": platforms_of(offline.array),
    })
    print("serve: rows " + json.dumps(report["rows"]))
    check(plan.compiled, "serve rows: plan did not compose")
    check(bool(np.isfinite(served).all()), "served scores are not finite")
    check(rel_err <= SERVE_MAX_REL_SCORE_ERR,
          f"served vs fitted.apply scores differ by {rel_err:.3e} of their "
          f"scale (> {SERVE_MAX_REL_SCORE_ERR:.0e})")
    check(report["rows"]["served_classes"] == report["rows"]["offline_classes"],
          "served and offline scores name different classes")
    check(report["rows"]["platforms"] == [platform],
          f"serve rows: ran on {report['rows']['platforms']}")
    report["ok"] = True
    return report


# ---------------------------------------------------------------------------
# Phase: four-device mesh leg
# ---------------------------------------------------------------------------


def phase_mesh(
    clock: CompileClock,
    sizes: TimitSizes = TimitSizes(),
    platform: str = "tpu",
    num_devices: int = 4,
) -> Dict[str, Any]:
    devices = jax.devices()
    if len(devices) < num_devices:
        print(f"mesh_leg: skipped ({len(devices)} devices)")
        return {"ok": True, "skipped": True, "devices": len(devices)}

    from keystone_tpu.data.loaders import synthetic_timit
    from keystone_tpu.ops.util import ClassLabelIndicatorsFromIntLabels
    from keystone_tpu.parallel import mesh as mesh_lib
    from keystone_tpu.pipelines import timit

    mesh = mesh_lib.make_mesh(
        (num_devices,), (mesh_lib.DATA_AXIS,), devices=devices[:num_devices]
    )
    train = synthetic_timit(sizes.rows, seed=sizes.seed)
    probe = synthetic_timit(sizes.probe_rows, seed=sizes.seed + 2)
    labels = ClassLabelIndicatorsFromIntLabels(timit.NUM_CLASSES)(train.labels)
    est = timit.streaming_estimator(_timit_config(sizes, "streaming"))

    with clock.measure() as one_timing:
        one = est.fit(train.data, labels)
        scores_one = np.asarray(one.batch_apply(probe.data).array)
    data_sh, labels_sh = train.data.shard(mesh), labels.shard(mesh)
    shard_devices = sorted(
        str(s.device) for s in data_sh.array.addressable_shards
    )
    with clock.measure() as mesh_timing:
        meshed = est.fit(data_sh, labels_sh)
        scores_mesh = np.asarray(meshed.batch_apply(probe.data).array)

    scale = float(np.max(np.abs(scores_one)))
    rel_score_err = float(np.max(np.abs(scores_mesh - scores_one))) / scale
    w_one, w_mesh = np.asarray(one.W_stack), np.asarray(meshed.W_stack)
    rel_w_err = float(np.linalg.norm(w_mesh - w_one) / np.linalg.norm(w_one))
    agreement = float(np.mean(
        scores_one.argmax(axis=1) == scores_mesh.argmax(axis=1)
    ))
    peaks = peak_bytes()
    report = {
        "devices": len(devices),
        "mesh": dict(mesh.shape),
        "shard_devices": shard_devices,
        "one_device": dict(one_timing),
        "mesh_fit": dict(mesh_timing),
        "rel_score_err": rel_score_err,
        "rel_score_tol": MESH_MAX_REL_SCORE_ERR,
        "rel_weight_err_fro": rel_w_err,
        "argmax_agreement": agreement,
        "peak_bytes_in_use": peaks,
    }
    print("mesh_leg: " + json.dumps(report))
    check(len(set(shard_devices)) == num_devices,
          f"rows sit on {shard_devices}, not {num_devices} distinct devices")
    check(bool(np.isfinite(w_mesh).all()), "mesh weights are not finite")
    check(rel_score_err <= MESH_MAX_REL_SCORE_ERR,
          f"mesh vs one-device scores differ by {rel_score_err:.3e} "
          f"(> {MESH_MAX_REL_SCORE_ERR:.0e} of their scale)")
    check(agreement >= TIMIT_MIN_AGREEMENT,
          f"mesh vs one-device argmax agreement {agreement:.5f}")
    if platform == "tpu":
        # Every device of the mesh has allocated something. (The parity
        # above is the stronger evidence that each did its share: a device
        # that skipped its fold would leave its rows out of G. The stats
        # do not count a program's internal buffers — on the chip a device
        # that folded a 1.07 GB Gramian reports a 0.5 GB peak.)
        check(peaks is not None, "no memory statistics on the TPU backend")
        idle = [i for i, p in enumerate(peaks[:num_devices]) if p <= 0]
        check(not idle,
              f"devices {idle} report peak_bytes_in_use = 0: {peaks}")
    report["ok"] = True
    return report


# ---------------------------------------------------------------------------
# Driver
# ---------------------------------------------------------------------------


def run_phases(
    phases: Sequence[str],
    clock: CompileClock,
) -> Tuple[bool, Dict[str, Any]]:
    """Run the named phases in order. A phase that raises is recorded as
    failed, with its traceback printed, and the later phases still run —
    the run as a whole has failed either way."""
    # Looked up at call time, so a test can substitute a phase.
    table = {name: globals()[f"phase_{name}"] for name in PHASES}
    results: Dict[str, Any] = {}
    for name in phases:
        print(f"=== phase {name} ===", flush=True)
        with clock.measure() as timing:
            try:
                result = table[name](clock)
            except Exception as e:  # noqa: BLE001 — recorded; fails the run
                traceback.print_exc(file=sys.stdout)
                result = {"ok": False, "error": f"{type(e).__name__}: {e}"}
        result["phase"] = dict(timing)
        results[name] = result
        print(f"=== phase {name}: {'ok' if result['ok'] else 'FAILED'} "
              + json.dumps(result["phase"]), flush=True)
    return all(r["ok"] for r in results.values()), results


def main(argv: Optional[Sequence[str]] = None) -> int:
    parser = argparse.ArgumentParser(
        "chip_smoke", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    parser.add_argument(
        "--phases", default=",".join(PHASES),
        help="comma-separated subset of %(default)s, for debugging on the "
             "chip; anything but the full list prints \"partial\": true",
    )
    args = parser.parse_args(list(argv) if argv is not None else None)
    phases = [p for p in args.phases.split(",") if p]
    unknown = [p for p in phases if p not in PHASES]
    if unknown:
        parser.error(f"unknown phases {unknown}; choose from {PHASES}")

    device_report = phase_device()
    clock = compile_ledger()
    t0 = time.perf_counter()
    ok, results = run_phases(phases, clock)
    summary = {
        "ok": ok,
        "timing_note": TIMING_NOTE,
        "device": device_report,
        "total_wall_s_smoke": round(time.perf_counter() - t0, 3),
        "total_compile_s_smoke": round(clock.compile_s, 3),
        "persistent_cache_hits": clock.cache_hits,
        "persistent_cache_misses": clock.cache_misses,
        "phases": results,
    }
    out_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "chip_smoke_summary.json"), "w") as f:
        json.dump(summary, f, indent=1, default=str)

    print("summary: " + json.dumps({
        "ok": ok,
        "timing_note": TIMING_NOTE,
        "total_wall_s_smoke": summary["total_wall_s_smoke"],
        "total_compile_s_smoke": summary["total_compile_s_smoke"],
        "persistent_cache_hits": clock.cache_hits,
        "persistent_cache_misses": clock.cache_misses,
        "phases": {
            name: {"ok": r["ok"], **r["phase"]} for name, r in results.items()
        },
        "failed_kernels": results.get("kernels", {}).get("failed"),
        "timit_plan_compiled": {
            s: results["timit"][s]["plan_compiled"]
            for s in ("auto", "streaming")
            if s in results.get("timit", {})
        },
        "host_data_plane": device_report["host_data_plane"],
    }))
    last: Dict[str, Any] = {"ok": ok, "device": device_report["device"]}
    if list(phases) != list(PHASES):
        last["partial"] = True
    if not ok:
        last["failed"] = [n for n, r in results.items() if not r["ok"]]
    print(json.dumps(last), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
