"""Cost-model-driven solver selection.

Reference: nodes/learning/CostModel.scala:6-16, LeastSquaresEstimator.scala:26-87,
ChainUtils.scala (TransformerLabelEstimatorChain).

The analytic cost(n, d, k, sparsity, numMachines, cpuW, memW, netW) models
keep the reference's feature extractors verbatim; `numMachines` maps to mesh
device count. The ACTIVE weights are TPU-derived (fit from measured on-chip
DEVICE time at the bench geometries — see the derivation at TPU_CPU_WEIGHT
below and ``scripts/fit_cost_weights.py``), matching the reference's defining
discipline of weights fit on the machine they steer
(LeastSquaresEstimator.scala:17,28-31). ``KEYSTONE_COST_WEIGHTS=ec2``
restores the reference's cluster constants (cpu=3.8e-4, mem=2.9e-1,
net=1.32 — 16-node r3.4xlarge).
"""

from __future__ import annotations

import logging
from typing import Optional, Sequence, Tuple

import jax
import numpy as np

from keystone_tpu import obs
from keystone_tpu.data import Dataset
from keystone_tpu.ops.sparse import Densify, Sparsify, is_sparse_dataset
from keystone_tpu.placement.engine import (
    KIND_IMAGE_TIER,
    KIND_MESH,
    KIND_SOLVER,
    PlacementEngine,
)
from keystone_tpu.utils.startup import device_memory_limit
from keystone_tpu.workflow import LabelEstimator, Transformer
from keystone_tpu.workflow.optimizable import OptimizableLabelEstimator

logger = logging.getLogger("keystone_tpu.cost")

# Reference cluster cost weights (LeastSquaresEstimator.scala:28-31; fit on
# a 2015 16-node r3.4xlarge cluster). Selectable via KEYSTONE_COST_WEIGHTS=
# ec2 — for A/B against the reference's selection behavior, and for tests
# that pin the reference weight set.
EC2_CPU_WEIGHT = 3.8e-4
EC2_MEM_WEIGHT = 2.9e-1
EC2_NETWORK_WEIGHT = 1.32
EC2_SPARSE_GATHER_OVERHEAD = 8.0
# Pre-round-6 aliases (these were the active defaults then).
DEFAULT_CPU_WEIGHT = EC2_CPU_WEIGHT
DEFAULT_MEM_WEIGHT = EC2_MEM_WEIGHT
DEFAULT_NETWORK_WEIGHT = EC2_NETWORK_WEIGHT

# Fallback device-memory budget when the backend reports no memory stats
# (CPU test meshes); real chips report bytes_limit (v5e: ~15.75 GB).
DEFAULT_HBM_BYTES = 16 << 30
# Fraction of device memory a solver's resident operands may claim: the
# rest covers XLA scratch, fusion temporaries and transfer buffers.
DEFAULT_HBM_UTILIZATION = 0.85
# Fallback HOST-memory budget when the OS reports nothing. The host tier
# sits between HBM and disk: candidates needing the dataset host-resident
# are infeasible past it, and the shard-backed streaming (disk) tier —
# which stages only prefetch-depth segments — becomes the only door.
DEFAULT_HOST_BYTES = 64 << 30
# Fraction of host RAM the dataset may claim (the rest covers the
# process, staging buffers, page cache churn).
DEFAULT_HOST_UTILIZATION = 0.8


def device_memory_bytes() -> int:
    """Per-device memory budget: the backend's reported limit. Only a
    backend that reports no memory statistics at all (the CPU test mesh)
    gets ``DEFAULT_HBM_BYTES``; on a TPU a missing limit raises
    (``utils.startup.device_memory_limit``)."""
    limit = device_memory_limit()
    return DEFAULT_HBM_BYTES if limit is None else limit


def host_memory_bytes() -> int:
    """Host-RAM budget for resident datasets: the
    ``KEYSTONE_HOST_BUDGET_BYTES`` env override (the ops knob — and the
    test hook forcing the disk tier), else the OS-reported physical
    memory, else the conservative default."""
    import os

    env = os.environ.get("KEYSTONE_HOST_BUDGET_BYTES")
    if env:
        return int(float(env))
    try:
        pages = os.sysconf("SC_PHYS_PAGES")
        page = os.sysconf("SC_PAGE_SIZE")
        if pages > 0 and page > 0:
            return int(pages * page)
    except (ValueError, OSError, AttributeError):
        pass
    return DEFAULT_HOST_BYTES

# TPU weights — ACTIVE by default. Fit from round-5 on-chip DEVICE time
# (marginal in-program time, so per-dispatch host overhead and host
# transfer are excluded) at the BENCH_r05 geometries, under the
# max(cpu·flops, mem·bytes) form the selector evaluates:
#
#   cpu = 3.8e-15 s per model-flop unit. The two MXU-bound rows bracket it:
#     the resident block row (0.327 s device = 3 sweeps of n·d·(bs+k) at
#     n=262144, d=16384 → 5.98e-15) and the streamed full-n headline
#     (4.107 s device = 2.0 × n·d·(d+k) at n=2.2e6 → 3.45e-15); the
#     geometric middle reproduces both within ~30%.
#   mem = 1.9e-11 s per sequentially-scanned f32 cell (≈ 210 GB/s achieved
#     streaming — below the 819 GB/s pin-rate peak because the models count
#     one scan of n·d while the folds re-read tiles). Chosen jointly with
#     cpu so that every MEASURED pairwise ordering reproduces: resident
#     block < streamed at in-budget geometries, block < 20-iteration dense
#     LBFGS, sparse gram < sparse gather (tests/test_cost_replay.py).
#   net = 1.0e-11 s per float (~100 G f32/s over ICI) — PINNED, not fit: a
#     single-chip measurement cannot observe the network term; refit on a
#     real multi-chip mesh before trusting cross-mesh rankings.
#
# The sparse gather path's random-access rate (measured 2.1e8 cells/s on
# the amazon row — 7.903 s / 20 iters / 2 passes / 4.15e7 active cells) is
# ~900x the sequential mem rate; it lives in the SparseLBFGS model's
# sparse_overhead factor, refit to 500 from the same row (the gram engine's
# prediction then lands at 1.78 s vs 1.805 measured). Re-derive all of
# these with ``python scripts/fit_cost_weights.py`` on-chip.
TPU_CPU_WEIGHT = 3.8e-15
TPU_MEM_WEIGHT = 1.9e-11
TPU_NETWORK_WEIGHT = 1.0e-11  # pinned (single-chip unobservable), not fit
TPU_SPARSE_GATHER_OVERHEAD = 500.0

# Sketched-engine weight families (ISSUE 17). Like the gather overhead,
# each is a random-access multiplier on the sequential mem rate for the
# engine's signature pass, refit from traces by ``bin/calibrate --refit``:
#
#   srht_sketch_overhead — the SRHT engine's densify scatter (writing
#     n·d·s active cells into chunk slabs before the FFT mixing). Seeded
#     slightly above the gather overhead: a scatter WRITE pays
#     read-modify-write per cell where the gather pass's read does not.
#   countsketch_overhead — the IHS engine's O(nnz) CountSketch
#     scatter-add into the flattened (m·d) accumulator. Cheaper than the
#     densify: one add per stored cell, no slab zero-fill, bucket
#     locality within a chunk.
#
# The EC2 values keep the reference-cluster convention (mem already at
# cluster rates, so the factors stay single-digit).
TPU_SRHT_SKETCH_OVERHEAD = 650.0
TPU_COUNTSKETCH_OVERHEAD = 250.0
EC2_SRHT_SKETCH_OVERHEAD = 10.0
EC2_COUNTSKETCH_OVERHEAD = 6.0

# Image-tier decode multiplier (ISSUE 18): host-side decompression of
# one encoded image into f32 cells, as a multiplier on the sequential
# mem rate per DECODED cell. Seeded from the native PNM decoder's
# ~1 GB/s single-thread throughput (≈ 4e-9 s per f32 cell against the
# 1.9e-11 s sequential rate); the EC2 value keeps the reference
# cluster's convention of single-digit factors. Refit from traces like
# the other per-engine overheads.
TPU_IMAGE_DECODE_OVERHEAD = 200.0
EC2_IMAGE_DECODE_OVERHEAD = 4.0

# Zoo page-in multiplier (ISSUE 19): host-side decode + CRC + pytree
# rebuild of one evicted tenant's spill, as a multiplier on the
# sequential mem rate per resident BYTE. Seeded from the spill codec's
# ~1 GB/s single-thread restore (1/(1.9e-11 x 50) ≈ 1 GB/s); the EC2
# value keeps the cluster convention of single-digit factors. This is
# the weight family behind ``PlacementEngine.price_page_in`` — the
# ModelZoo seeds its page-in EMA from it instead of a hardcoded
# constant, so ``bin/calibrate --refit`` covers zoo paging like every
# other engine overhead.
TPU_ZOO_PAGE_OVERHEAD = 50.0
EC2_ZOO_PAGE_OVERHEAD = 2.0


# Weight-family spec for trace-calibrated constants:
# KEYSTONE_COST_WEIGHTS=calibrated:<path> points at a refit artifact
# written by the calibration plane (obs/calibrate.py — trace-driven
# refit with provenance: source run_ids, span counts, residuals).
CALIBRATED_PREFIX = "calibrated:"

# Loaded-artifact cache keyed by path -> (mtime, weights dict): a
# selector consulting the env per construction must not re-read and
# re-validate the JSON every time, but a refreshed artifact (refit in
# place) must be picked up.
_CALIBRATED_CACHE: dict = {}


def _calibrated_weights(path: str) -> dict:
    import os

    try:
        mtime = os.stat(path).st_mtime_ns
    except OSError as e:
        raise ValueError(
            f"KEYSTONE_COST_WEIGHTS={CALIBRATED_PREFIX}{path}: artifact "
            f"is unreadable: {e}"
        ) from e
    cached = _CALIBRATED_CACHE.get(path)
    if cached is not None and cached[0] == mtime:
        return cached[1]
    from keystone_tpu.obs.calibrate import load_calibration_artifact

    weights = dict(load_calibration_artifact(path)["weights"])
    _CALIBRATED_CACHE[path] = (mtime, weights)
    return weights


def _parse_weights_env() -> Tuple[str, Optional[str]]:
    """Parse ``KEYSTONE_COST_WEIGHTS`` into (family, artifact_path).

    Accepted (family part case-insensitive; artifact paths keep their
    case): unset/empty or ``tpu`` -> the TPU constants, ``ec2`` -> the
    reference cluster set, ``calibrated:<path>`` -> a refit artifact.
    Anything else raises naming the variable — a typo'd family must not
    silently select the default and mis-price every decision (the exact
    failure mode the calibration plane exists to catch)."""
    import os

    raw = os.environ.get("KEYSTONE_COST_WEIGHTS", "").strip()
    low = raw.lower()
    if not raw or low == "tpu":
        return "tpu", None
    if low == "ec2":
        return "ec2", None
    if low.startswith(CALIBRATED_PREFIX):
        return "calibrated", raw[len(CALIBRATED_PREFIX):]
    raise ValueError(
        f"KEYSTONE_COST_WEIGHTS={raw!r}: expected 'tpu', 'ec2' or "
        f"'calibrated:<artifact.json>'"
    )


def weights_family_name() -> str:
    """The active weight family's name: ``tpu`` (default), ``ec2``, or
    ``calibrated`` — what decision audits and calibration reports record
    as provenance."""
    return _parse_weights_env()[0]


def active_weights() -> Tuple[float, float, float]:
    """The selector's (cpu, mem, network) weights: TPU-derived by
    default; ``KEYSTONE_COST_WEIGHTS=ec2`` restores the reference's
    cluster constants; ``KEYSTONE_COST_WEIGHTS=calibrated:<path>`` loads
    a trace-refit artifact (obs/calibrate.py) — malformed or missing
    artifacts, and unknown family names, raise naming the variable
    rather than mis-pricing silently."""
    family, path = _parse_weights_env()
    if family == "ec2":
        return EC2_CPU_WEIGHT, EC2_MEM_WEIGHT, EC2_NETWORK_WEIGHT
    if family == "calibrated":
        w = _calibrated_weights(path)
        return float(w["cpu"]), float(w["mem"]), float(w["network"])
    return TPU_CPU_WEIGHT, TPU_MEM_WEIGHT, TPU_NETWORK_WEIGHT


def sparse_gather_overhead() -> float:
    """Random-access multiplier for the sparse gather engine's mem term,
    matching the active weight family (the EC2 mem weight already prices
    bytes at cluster rates, so its historical factor stays 8). A
    calibrated artifact fit from traces with no gather rows records
    null — the TPU constant stands in, since the artifact's (cpu, mem)
    are TPU-fit refinements."""
    family, path = _parse_weights_env()
    if family == "ec2":
        return EC2_SPARSE_GATHER_OVERHEAD
    if family == "calibrated":
        so = _calibrated_weights(path).get("sparse_gather_overhead")
        return float(so) if so is not None else TPU_SPARSE_GATHER_OVERHEAD
    return TPU_SPARSE_GATHER_OVERHEAD


def srht_sketch_overhead() -> float:
    """Random-access multiplier for the SRHT engine's densify-scatter
    sketch pass, per the active weight family. Same null convention as
    :func:`sparse_gather_overhead`: a calibrated artifact fit from
    traces with no SRHT rows records null and the TPU constant stands
    in."""
    family, path = _parse_weights_env()
    if family == "ec2":
        return EC2_SRHT_SKETCH_OVERHEAD
    if family == "calibrated":
        so = _calibrated_weights(path).get("srht_sketch_overhead")
        return float(so) if so is not None else TPU_SRHT_SKETCH_OVERHEAD
    return TPU_SRHT_SKETCH_OVERHEAD


def countsketch_overhead() -> float:
    """Random-access multiplier for the IHS engine's CountSketch
    scatter-add pass, per the active weight family (null-in-artifact
    falls back to the TPU constant, as above)."""
    family, path = _parse_weights_env()
    if family == "ec2":
        return EC2_COUNTSKETCH_OVERHEAD
    if family == "calibrated":
        so = _calibrated_weights(path).get("countsketch_overhead")
        return float(so) if so is not None else TPU_COUNTSKETCH_OVERHEAD
    return TPU_COUNTSKETCH_OVERHEAD


def image_decode_overhead() -> float:
    """Random-access multiplier for the image tier's host decode pass,
    per the active weight family (null-in-artifact falls back to the TPU
    constant, as above)."""
    family, path = _parse_weights_env()
    if family == "ec2":
        return EC2_IMAGE_DECODE_OVERHEAD
    if family == "calibrated":
        so = _calibrated_weights(path).get("image_decode_overhead")
        return float(so) if so is not None else TPU_IMAGE_DECODE_OVERHEAD
    return TPU_IMAGE_DECODE_OVERHEAD


def zoo_page_overhead() -> float:
    """Random-access multiplier for the zoo's tenant page-in pass
    (spill decode + CRC + pytree rebuild), per the active weight family
    (null-in-artifact falls back to the TPU constant, as above)."""
    family, path = _parse_weights_env()
    if family == "ec2":
        return EC2_ZOO_PAGE_OVERHEAD
    if family == "calibrated":
        so = _calibrated_weights(path).get("zoo_page_overhead")
        return float(so) if so is not None else TPU_ZOO_PAGE_OVERHEAD
    return TPU_ZOO_PAGE_OVERHEAD


def candidate_label(est) -> str:
    """Stable human-readable label of one solver candidate — the name a
    :class:`~keystone_tpu.obs.tracer.CostDecision` event records and the
    replay tests assert against. Disambiguates the engine/storage-class
    variants of one estimator type (``solver=``/``compress=``)."""
    name = type(est).__name__
    qual = [
        str(v) for v in (
            getattr(est, "solver", None), getattr(est, "compress", None)
        ) if v
    ]
    return name + (f"[{','.join(qual)}]" if qual else "")


# ---------------------------------------------------------------------------
# Mesh-layout pricing (ISSUE 16): layouts are first-class candidates
# ---------------------------------------------------------------------------

# Candidate (data, model) mesh shapes the layout selector prices for a
# data-parallel streamed gram fold. 1x1 is the single-chip baseline the
# BENCH rows measured; 8x1 puts every device on the fold's row axis; 4x2
# spends half the pod replicating along the model axis (which the gram
# fold cannot use — it prices as a 4-way fold plus replica broadcast).
MESH_LAYOUTS: Tuple[Tuple[int, int], ...] = ((1, 1), (4, 1), (4, 2), (8, 1))


def mesh_layout_label(data: int, model: int) -> str:
    """Stable candidate label of one mesh layout — what the
    ``mesh_layout`` CostDecision records and the replay test pins."""
    return f"mesh[data={int(data)},model={int(model)}]"


def price_mesh_layout(
    n: int, d: int, k: int, data: int, model: int,
    *,
    nnz_per_row: Optional[int] = None,
    cpu_weight: Optional[float] = None,
    mem_weight: Optional[float] = None,
    network_weight: Optional[float] = None,
) -> float:
    """Predicted seconds for ONE streamed gram fit on a (data × model)
    mesh.

    The model mirrors the fold's actual program shape
    (ops/learning/lbfgs.py ``_run_lbfgs_gram_streamed_mesh``):

    - each of the ``data`` devices folds its contiguous row shard locally
      (compute and scan terms divide by ``data`` and by nothing else —
      the gram fold has no model-axis parallelism);
    - ONE ring all-reduce of (G upper-tri, AtY, yty) crosses the ICI per
      fit: ``2·(p-1)/p`` of the reduced floats move per device;
    - model-axis replicas fold identical shards, so ``model > 1`` buys
      nothing and pays the operand broadcast to each extra replica.
    """
    if cpu_weight is None or mem_weight is None or network_weight is None:
        aw = active_weights()
        cpu_weight = cpu_weight if cpu_weight is not None else aw[0]
        mem_weight = mem_weight if mem_weight is not None else aw[1]
        network_weight = network_weight if network_weight is not None else aw[2]
    p, q = int(data), int(model)
    active = float(nnz_per_row) if nnz_per_row else float(d)
    # Per-fit work: gram outer products (active² MACs/row) + AtY + labels.
    flops = 2.0 * n * active * (active + k)
    cells = float(n) * (2.0 * active + k)  # idx+val lanes and the labels
    fold_s = max(cpu_weight * flops, mem_weight * cells) / p
    # The single psum tree-reduction per fit (upper-tri G + AtY + yty).
    reduce_floats = d * (d + 1) / 2.0 + d * k + 1.0
    net_s = (
        network_weight * reduce_floats * 2.0 * (p - 1) / p if p > 1 else 0.0
    )
    # Replica tax: the fold operands reach each model-axis replica over
    # the same interconnect the psum rides.
    net_s += network_weight * (cells / p) * (q - 1)
    return fold_s + net_s


def mesh_layout_resident_bytes(
    n: int, d: int, k: int, data: int,
    nnz_per_row: Optional[int] = None,
) -> float:
    """Per-device HBM claim of a chip-resident row shard under a layout:
    compressed-COO lanes (int16 idx + bf16 val = 4 B/nnz) when the input
    is sparse, f32 rows otherwise, plus the f32 label shard."""
    row = (
        COMPRESSED_BYTES_PER_NNZ_DEFAULT * float(nnz_per_row)
        if nnz_per_row else 4.0 * d
    )
    return (n / max(int(data), 1)) * (row + 4.0 * k)


# Kept here (not imported from data/resident.py) so pricing has no
# data-plane import cycle; tests/test_cost_replay.py asserts the two
# constants agree.
COMPRESSED_BYTES_PER_NNZ_DEFAULT = 4.0


def choose_mesh_layout(
    n: int, d: int, k: int,
    *,
    nnz_per_row: Optional[int] = None,
    layouts: Sequence[Tuple[int, int]] = MESH_LAYOUTS,
    num_devices: Optional[int] = None,
    hbm_bytes: Optional[int] = None,
    hbm_utilization: float = DEFAULT_HBM_UTILIZATION,
):
    """Select a mesh layout for a streamed gram fit, with the decision
    recorded as first-class ``cost.decision`` evidence.

    Prices every candidate layout in ``layouts`` (default
    :data:`MESH_LAYOUTS`), marks infeasible the ones needing more chips
    than ``num_devices`` (default: the runtime's device count), and
    emits a ``decision="mesh_layout"`` CostDecision whose
    :class:`~keystone_tpu.obs.tracer.CostOutcomeRef` the runner stamps
    with the measured fit wall — ``bin/calibrate`` joins these records
    exactly like solver decisions (obs/calibrate.py
    ``CALIBRATED_DECISIONS``).

    Returns ``((data, model), outcome_ref)``; ``outcome_ref`` is None
    when no tracer is active.
    """
    devices = int(num_devices) if num_devices else max(len(jax.devices()), 1)
    budget = (
        hbm_bytes if hbm_bytes is not None else device_memory_bytes()
    ) * hbm_utilization
    cpu_w, mem_w, net_w = active_weights()
    try:
        family = weights_family_name()
    except ValueError:
        family = "custom"

    def feasible(p: int, q: int) -> bool:
        return p * q <= devices

    costs = [
        price_mesh_layout(
            n, d, k, p, q, nnz_per_row=nnz_per_row,
            cpu_weight=cpu_w, mem_weight=mem_w, network_weight=net_w,
        ) if feasible(p, q) else float("inf")
        for p, q in layouts
    ]
    if all(c == float("inf") for c in costs):
        raise ValueError(
            f"no candidate mesh layout fits {devices} device(s): "
            f"{[mesh_layout_label(p, q) for p, q in layouts]}"
        )
    candidates = [
        {
            "label": mesh_layout_label(p, q),
            "cost_s": (None if c == float("inf") else float(c)),
            "feasible": c != float("inf"),
            "resident_bytes": float(
                mesh_layout_resident_bytes(n, d, k, p, nnz_per_row)
            ),
            "chip_resident": (
                mesh_layout_resident_bytes(n, d, k, p, nnz_per_row)
                <= budget
            ),
            "host_ok": True,
        }
        for (p, q), c in zip(layouts, costs)
    ]
    # The unified placement stream rides alongside the legacy
    # cost.decision record; the engine's first-minimum argmin IS
    # np.argmin, so the recorded winner is unchanged by construction.
    choice = PlacementEngine(weights_family=family).decide(
        KIND_MESH, candidates,
        context={
            "n": int(n), "d": int(d), "k": int(k),
            "machines": devices,
            "hbm_budget_bytes": float(budget),
        },
    )
    winner = layouts[choice.index]
    ref = obs.record_cost_decision(obs.CostDecision(
        decision="mesh_layout",
        winner=mesh_layout_label(*winner),
        candidates=candidates,
        reason="argmin",
        context={
            "n": int(n), "d": int(d), "k": int(k),
            "sparsity": (
                float(nnz_per_row) / d if nnz_per_row else 1.0
            ),
            "machines": devices,
            "hbm_budget_bytes": float(budget),
            "nnz_per_row": (
                int(nnz_per_row) if nnz_per_row else None
            ),
            "weights": {
                "cpu": cpu_w, "mem": mem_w, "network": net_w,
                "family": family,
            },
        },
    ))
    return winner, ref


IMAGE_TIERS = ("resident", "resident_u8", "disk_shards")


def choose_image_tier(
    n_images: int, d: int, k: int,
    *,
    images_per_segment: int = 256,
    prefetch_depth: int = 2,
    host_budget_bytes: Optional[float] = None,
    host_utilization: float = 0.8,
):
    """Select the storage tier for a decoded image set, recorded as
    first-class ``cost.decision`` evidence — this is what lets
    ``Pipeline.fit`` route a past-host-RAM image set through disk shards
    with NO flag: the loader prices the tiers and the infeasible ones
    price to inf.

    ``d`` is decoded floats per image (x·y·c after augmentation), ``k``
    the label width. Candidates:

      - ``resident``: decoded f32 rows held in host RAM — one decode
        pass, cheapest reads, infeasible past the host budget.
      - ``resident_u8``: the compressed-resident tier — uint8 pixel rows
        (exact for 8-bit sources), 4× smaller residency, a cast per
        epoch on the way to the device.
      - ``disk_shards``: spill through ``DiskDenseShardWriter`` — host
        residency is ``(prefetch_depth + 1)`` staged segments only,
        always feasible; pays the spill write + re-read traffic.

    Returns ``(tier_name, outcome_ref)``; ``outcome_ref`` is None when
    no tracer is active.
    """
    cpu_w, mem_w, net_w = active_weights()
    try:
        family = weights_family_name()
    except ValueError:
        family = "custom"
    if host_budget_bytes is not None:
        budget = float(host_budget_bytes)
    else:
        budget = host_memory_bytes() * host_utilization

    n = int(n_images)
    cells = float(n) * (d + k)
    decode_s = mem_w * image_decode_overhead() * float(n) * d
    seg_bytes = float(images_per_segment) * (4.0 * d + 4.0 * k)
    resident_bytes = {
        "resident": cells * 4.0,
        "resident_u8": float(n) * (d + 4.0 * k),
        "disk_shards": (prefetch_depth + 1) * seg_bytes,
    }
    tier_cost = {
        # One decode pass each; reads price the per-epoch traffic.
        "resident": decode_s + mem_w * cells,
        # u8 rows read 1/4 the bytes but pay a widening cast per epoch.
        "resident_u8": decode_s + mem_w * cells * 1.25,
        # Spill write + shard re-read (checksummed), both full passes.
        "disk_shards": decode_s + mem_w * cells * 3.0,
    }
    costs = {
        t: (tier_cost[t] if resident_bytes[t] <= budget else float("inf"))
        for t in IMAGE_TIERS
    }
    if all(c == float("inf") for c in costs.values()):
        raise ValueError(
            f"no image tier fits the host budget {budget:.3g} B "
            f"(even {prefetch_depth + 1} staged segments of "
            f"{seg_bytes:.3g} B); shrink images_per_segment"
        )
    candidates = [
        {
            "label": t,
            "cost_s": (None if costs[t] == float("inf") else float(costs[t])),
            "feasible": costs[t] != float("inf"),
            "resident_bytes": float(resident_bytes[t]),
            "chip_resident": False,  # the image tier is host-side
            "host_ok": resident_bytes[t] <= budget,
        }
        for t in IMAGE_TIERS
    ]
    # Placement mirror: min-over-tuple-order equals the engine's
    # first-minimum over the candidate list (both first on ties).
    choice = PlacementEngine(weights_family=family).decide(
        KIND_IMAGE_TIER, candidates,
        context={
            "n": n, "d": int(d), "k": int(k),
            "images_per_segment": int(images_per_segment),
            "prefetch_depth": int(prefetch_depth),
            "host_budget_bytes": float(budget),
        },
    )
    winner = IMAGE_TIERS[choice.index]
    ref = obs.record_cost_decision(obs.CostDecision(
        decision="image_tier",
        winner=winner,
        candidates=candidates,
        reason="argmin",
        context={
            "n": n, "d": int(d), "k": int(k),
            "images_per_segment": int(images_per_segment),
            "prefetch_depth": int(prefetch_depth),
            "host_budget_bytes": float(budget),
            "weights": {
                "cpu": cpu_w, "mem": mem_w, "network": net_w,
                "family": family,
            },
        },
    ))
    return winner, ref


class CostModel:
    """Analytic per-solver performance model (CostModel.scala:6-16)."""

    def cost(
        self,
        n: int,
        d: int,
        k: int,
        sparsity: float,
        num_machines: int,
        cpu_weight: float,
        mem_weight: float,
        network_weight: float,
    ) -> float:
        raise NotImplementedError


class TransformerLabelEstimatorChain(LabelEstimator):
    """Fuse a Transformer with a LabelEstimator into one LabelEstimator
    (reference: ChainUtils.scala)."""

    def __init__(self, transformer: Transformer, estimator: LabelEstimator):
        self.transformer = transformer
        self.estimator = estimator

    def fit(self, data: Dataset, labels: Dataset):
        transformed = self.transformer.batch_apply(data)
        inner = self.estimator.fit(transformed, labels)

        chain_transformer = self.transformer

        class Chained(Transformer):
            def apply(self, x):
                return inner.apply(chain_transformer.apply(x))

            def batch_apply(self, ds: Dataset) -> Dataset:
                return inner.batch_apply(chain_transformer.batch_apply(ds))

        return Chained()

    @property
    def weight(self) -> int:
        return getattr(self.estimator, "weight", 1)


class LeastSquaresEstimator(OptimizableLabelEstimator):
    """Auto-selecting least-squares solver (LeastSquaresEstimator.scala:26-87).

    Candidates: DenseLBFGS, Sparsify->SparseLBFGS (gather, gram, and
    compressed-resident gram — the int16+bf16 4 B/nnz storage class of
    ``data/resident.py``), Densify->BlockLS(1000, 3),
    Densify->Exact normal equations, the STREAMING tier
    (StreamingLeastSquaresChoice — featurize-inside-the-fit, bound to the
    upstream featurizer by the optimizer's StreamedFitFusionRule), and
    (only when ``allow_approximate``) the randomized tier:
    Densify->SketchedLeastSquaresEstimator (dense CountSketch +
    Hessian-sketch refinement), Sparsify->SketchedLeastSquares (SRHT
    sketch-and-precondition — exact up to CG tolerance) and
    Sparsify->IterativeHessianSketch (input-sparsity-time CountSketch
    folds, ``ops/learning/sketch.py``). ``optimize`` measures
    (n, d, k, sparsity, num devices) from
    the sample and picks the cost-model argmin among candidates whose
    RESIDENT operands fit the device-memory budget — a capacity term the
    reference's cluster cost model (CostModel.scala:6-16) folds into its
    memory weight, and which on a fixed-HBM chip must instead be a hard
    feasibility cut: past it, the streaming tier is the only candidate
    that can run at all.

    The cut prices THREE tiers separately: HBM (per-candidate
    resident_bytes vs the device budget), host RAM (the raw dataset +
    labels vs ``host_budget_bytes`` — every candidate except the disk
    tier needs the dataset host-resident to begin), and DISK (a
    shard-backed input lets the streaming choice stage only
    prefetch-depth segments, so datasets past the host budget route
    through disk shards with no flag — docs/data.md).
    """

    def __init__(
        self,
        lam: float = 0.0,
        num_machines: Optional[int] = None,
        cpu_weight: Optional[float] = None,
        mem_weight: Optional[float] = None,
        network_weight: Optional[float] = None,
        allow_approximate: bool = False,
        hbm_bytes: Optional[float] = None,
        hbm_utilization: float = DEFAULT_HBM_UTILIZATION,
        host_budget_bytes: Optional[float] = None,
        host_utilization: float = DEFAULT_HOST_UTILIZATION,
        block_size: int = 1000,
        block_iters: int = 3,
    ):
        from keystone_tpu.ops.learning.block import BlockLeastSquaresEstimator
        from keystone_tpu.ops.learning.lbfgs import DenseLBFGSwithL2, SparseLBFGSwithL2
        from keystone_tpu.ops.learning.linear import (
            LinearMapEstimator,
            SketchedLeastSquaresEstimator,
        )
        from keystone_tpu.ops.learning.streaming_ls import (
            StreamingLeastSquaresChoice,
        )

        self.lam = lam
        self.num_machines = num_machines
        # None -> the active weight family (TPU-derived by default;
        # KEYSTONE_COST_WEIGHTS=ec2 restores the reference constants).
        # Resolved at construction so one estimator's ranking is stable
        # even if the env flag changes mid-process.
        a_cpu, a_mem, a_net = active_weights()
        self.cpu_weight = a_cpu if cpu_weight is None else cpu_weight
        self.mem_weight = a_mem if mem_weight is None else mem_weight
        self.network_weight = a_net if network_weight is None else network_weight
        self.hbm_bytes = hbm_bytes
        self.hbm_utilization = hbm_utilization
        self.host_budget_bytes = host_budget_bytes
        self.host_utilization = host_utilization

        dense_lbfgs = DenseLBFGSwithL2(lam=lam, num_iterations=20)
        sparse_lbfgs = SparseLBFGSwithL2(lam=lam, num_iterations=20)
        # The gram engine: fold G once on the MXU, iterate data-free —
        # cheaper than gather past ~5 iterations whenever its (d_pad)^2
        # Gramian fits the budget (its resident_bytes carries that term).
        sparse_gram = SparseLBFGSwithL2(
            lam=lam, num_iterations=20, solver="gram"
        )
        # The compressed-resident storage class (data/resident.py,
        # ISSUE 8): the SAME gram iterates over int16+bf16 operands at
        # 4 B/nnz — half the raw COO's residency, feasible only while
        # every index fits int16. Priced as a
        # third tier between HBM-raw and disk: identical cost model
        # (the fold runs the same bf16 slabs), so selection is driven
        # by the capacity cut — raw-infeasible, compressed-feasible
        # working sets stay chip-resident instead of streaming, with no
        # flag (tests/test_cost_replay.py replays the Amazon n=30e6
        # geometry).
        sparse_gram_compressed = SparseLBFGSwithL2(
            lam=lam, num_iterations=20, solver="gram",
            compress="int16_bf16",
        )
        block = BlockLeastSquaresEstimator(block_size, block_iters, lam=lam)
        exact = LinearMapEstimator(lam)
        streaming = StreamingLeastSquaresChoice(
            num_iter=block_iters, lam=lam,
            block_size_hint=block_size,
        )
        self._streaming_choice = streaming

        self.options: Sequence[Tuple[object, LabelEstimator]] = [
            (dense_lbfgs, dense_lbfgs),
            (sparse_lbfgs, TransformerLabelEstimatorChain(Sparsify(), sparse_lbfgs)),
            (sparse_gram, TransformerLabelEstimatorChain(Sparsify(), sparse_gram)),
            # Listed AFTER the raw gram engine: equal cost when both fit
            # (argmin takes the first), so compression only engages when
            # raw residency is the binding constraint.
            (sparse_gram_compressed,
             TransformerLabelEstimatorChain(Sparsify(), sparse_gram_compressed)),
            (block, TransformerLabelEstimatorChain(Densify(), block)),
            (exact, TransformerLabelEstimatorChain(Densify(), exact)),
            # The streaming choice is its own graph operator (no Densify
            # chain): StreamedFitFusionRule must see it directly to bind
            # the upstream featurizer; its fit densifies sparse input
            # itself on the resident fallback path.
            (streaming, streaming),
        ]
        if allow_approximate:
            # Beyond the reference's candidate set: randomized sketch-and-
            # solve with Hessian-sketch refinement — cheapest in the tall-
            # and-wide dense regime, but its answer is approximate, so users
            # must opt in.
            sketched = SketchedLeastSquaresEstimator(lam=lam)
            # The streamed sketched tier (ISSUE 17): SRHT sketch-and-
            # precondition and input-sparsity-time IHS over the SAME
            # padded-COO chunk stream the gram fold consumes. Each has its
            # own calibrated weight family (srht_sketch_overhead /
            # countsketch_overhead), so a refit can re-rank them without
            # touching the exact engines' weights.
            from keystone_tpu.ops.learning.sketch import (
                IterativeHessianSketch, SketchedLeastSquares,
            )

            srht = SketchedLeastSquares(lam=lam)
            ihs = IterativeHessianSketch(lam=lam)
            self.options = list(self.options) + [
                (sketched, TransformerLabelEstimatorChain(Densify(), sketched)),
                (srht, TransformerLabelEstimatorChain(Sparsify(), srht)),
                (ihs, TransformerLabelEstimatorChain(Sparsify(), ihs)),
            ]
        self._default = dense_lbfgs

    @property
    def default(self) -> LabelEstimator:
        return self._default

    @property
    def weight(self) -> int:
        return self._default.weight

    def optimize(self, sample: Dataset, labels_sample: Dataset):
        with obs.span("cost.select"):
            return self._select(sample, labels_sample)

    def _select(self, sample: Dataset, labels_sample: Dataset):
        # total_n: the full dataset size attached by the sample collector;
        # sample.n is just the handful of sampled rows.
        n = getattr(sample, "total_n", sample.n)
        if is_sparse_dataset(sample):
            indices = np.asarray(sample.data["indices"])
            # Feature width: prefer the TRUE width threaded through by the
            # sample collector (``total_d`` — declared by the vectorizer or
            # measured over the full index array); ``indices.max()+1`` over
            # a 24-row sample undershoots whenever the sample misses the
            # top ids, mis-pricing every sparse candidate's resident_bytes.
            measured_d = int(indices.max()) + 1
            d = max(int(getattr(sample, "total_d", 0) or 0), measured_d)
            # Active fraction measured over the SAMPLE's valid rows
            # (dividing by the full n would collapse sparsity toward zero
            # whenever the collector attaches total_n; padded-COO rows
            # hold -1 lanes, which the >= 0 mask already excludes).
            sparsity = float(
                (indices >= 0).sum() / (max(sample.n, 1) * d)
            )
        elif sample.is_host:
            first = sample.to_list()[0]
            d = int(np.asarray(first).shape[-1])
            X = np.stack([np.asarray(x) for x in sample.to_list()])
            sparsity = float((X != 0).mean())
        else:
            d = int(np.asarray(sample.array).shape[-1])
            # Slice by the sample's VALID rows, matching the sparse branch:
            # n here is the full-dataset size, so ``[: n]`` would keep any
            # zero-padded tail rows and deflate the measured sparsity.
            sparsity = float(
                np.mean(np.asarray(sample.array[: sample.n]) != 0)
            )
        k = int(np.asarray(labels_sample.array).shape[-1])
        machines = self.num_machines or max(len(jax.devices()), 1)

        # Raw-source row bytes (attached by the sample collector): the
        # streaming tier keeps RAW rows resident, not features. The
        # density flag lets its capacity model default an UNSET raw width
        # honestly — a dense row is the full 4d bytes, not a capped guess.
        raw_row_bytes = getattr(sample, "source_row_bytes", None)
        self._streaming_choice.raw_row_bytes = raw_row_bytes
        self._streaming_choice.input_is_sparse = is_sparse_dataset(sample)
        # DISK tier: a shard-backed source streams raw rows from disk
        # segments — the streaming choice's resident operand stops
        # scaling with n, and host-RAM feasibility is priced per
        # candidate below.
        shard_backed = bool(getattr(sample, "shard_backed", False))
        self._streaming_choice.data_is_shard_backed = shard_backed
        self._streaming_choice.shard_segment_bytes = getattr(
            sample, "shard_segment_bytes", None
        )
        import os as _os

        budget = (
            self.hbm_bytes if self.hbm_bytes is not None
            else device_memory_bytes()
        ) * self.hbm_utilization
        # An EXPLICIT host budget (constructor knob or env flag) is the
        # operator's chosen cap and is honored as-is; the utilization
        # derate applies only to autodetected physical RAM, where the
        # process/staging/page-cache headroom is unaccounted.
        env_budget = _os.environ.get("KEYSTONE_HOST_BUDGET_BYTES")
        if self.host_budget_bytes is not None:
            host_budget = float(self.host_budget_bytes)
        elif env_budget:
            host_budget = float(env_budget)
        else:
            host_budget = host_memory_bytes() * self.host_utilization
        # The streaming tier's feature slab scales down with the budget so
        # its capacity model and its actual tile sizing agree; the budget
        # itself drives its gram-vs-block tier decision.
        self._streaming_choice.slab_bytes = int(min(2 << 30, budget // 4))
        self._streaming_choice.budget_bytes = budget

        # What every NON-disk candidate needs host-side before any device
        # placement: the raw dataset plus labels, resident once.
        host_resident = (
            n * (raw_row_bytes if raw_row_bytes else 4.0 * d) + 4.0 * n * k
        )

        def resident(opt) -> float:
            rb = getattr(opt[0], "resident_bytes", None)
            if rb is None:
                return 0.0
            return rb(n, d, k, sparsity, machines)

        def host_ok(opt) -> bool:
            # The disk tier (shard-backed streaming choice) stages only
            # prefetch-depth segments host-side; everything else needs
            # the full dataset in host RAM to even begin.
            if shard_backed and opt[0] is self._streaming_choice:
                return True
            return host_resident <= host_budget

        def total_cost(opt) -> float:
            # Infeasible candidates — resident operands past the device
            # budget, or a dataset past the host-RAM budget with no disk
            # path — cost infinity: they would OOM, whatever their model
            # time says.
            if not host_ok(opt) or resident(opt) > budget:
                return float("inf")
            return opt[0].cost(
                n, d, k, sparsity, machines,
                self.cpu_weight, self.mem_weight, self.network_weight,
            )

        costs = [total_cost(opt) for opt in self.options]
        logger.debug(
            "LeastSquaresEstimator optimize: n=%d d=%d k=%d sparsity=%.4f "
            "machines=%d budget=%.2e costs=%s",
            n, d, k, sparsity, machines, budget,
            [f"{type(o[0]).__name__}={c:.3g}" for o, c in
             zip(self.options, costs)],
        )

        my_weights = (self.cpu_weight, self.mem_weight, self.network_weight)
        try:
            family = (
                weights_family_name()
                if my_weights == active_weights() else "custom"
            )
        except ValueError:  # broken calibrated artifact mid-process
            family = "custom"

        candidates = [
            {
                "label": candidate_label(o[0]),
                "cost_s": (None if c == float("inf") else float(c)),
                "feasible": c != float("inf"),
                "resident_bytes": float(resident(o)),
                "host_ok": host_ok(o),
            }
            for o, c in zip(self.options, costs)
        ]

        def emit_decision(winner, reason: str):
            # The structured audit event (obs plane, ISSUE 9): candidate
            # set, predicted costs, feasibility verdicts, winner —
            # tests/test_cost_replay.py's trace-backed audit leg asserts
            # the recorded winner matches every replay assertion.
            # Returns the CostOutcomeRef the executor later stamps the
            # winner's measured wall onto (obs/calibrate.py).
            return obs.record_cost_decision(obs.CostDecision(
                decision="least_squares_solver",
                winner=candidate_label(winner),
                candidates=candidates,
                reason=reason,
                context={
                    "n": int(n), "d": int(d), "k": int(k),
                    "sparsity": float(sparsity), "machines": int(machines),
                    "hbm_budget_bytes": float(budget),
                    "host_budget_bytes": float(host_budget),
                    "shard_backed": shard_backed,
                    "weights": {
                        "cpu": self.cpu_weight, "mem": self.mem_weight,
                        "network": self.network_weight,
                        "family": family,
                    },
                },
            ))

        # The global placement engine resolves the argmin (first minimum
        # — exactly int(np.argmin)) and, all-infeasible, the
        # least-resident fallback (exactly min(options, key=resident)):
        # the recorded winner is unchanged by construction, and the
        # unified placement.decision stream gets its mirror row.
        choice = PlacementEngine(weights_family=family).decide(
            KIND_SOLVER, candidates,
            context={
                "n": int(n), "d": int(d), "k": int(k),
                "sparsity": float(sparsity), "machines": int(machines),
                "hbm_budget_bytes": float(budget),
                "host_budget_bytes": float(host_budget),
                "shard_backed": shard_backed,
            },
            fallback="least_resident",
        )
        chosen = self.options[choice.index]
        if choice.reason == "least_resident_fallback":
            # Nothing fits the budget model: the least-resident
            # candidate (in practice the streaming tier) beats a
            # guaranteed OOM.
            logger.warning(
                "no solver candidate fits the %.2f GB budget at n=%d d=%d; "
                "selecting least-resident %s",
                budget / 2**30, n, d, type(chosen[0]).__name__,
            )
        # The pending back-annotation: whoever fits the winner (the
        # executor's fit_datasets, or a fused streamed fit that inherits
        # the ref) stamps the measured wall + span id onto the decision
        # record, closing the predicted-vs-measured loop per decision.
        chosen[1]._pending_cost_outcome = emit_decision(
            chosen[0], choice.reason
        )
        return chosen[1]
