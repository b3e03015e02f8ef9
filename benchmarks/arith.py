"""Operations and bytes the algorithms NEED, from shapes alone, and the
chip's peaks. The yardstick for ``fit_mfu_pct`` and every
``*_roofline_pct``: kept with the benchmark so that no PR which claims a
gain can move it.

Conventions (restated from ``docs/performance.md``): a multiply-add is two
operations; a symmetric rank-k update (F^T F) is counted ``n d^2`` — what
its upper triangle needs — not the ``2 n d^2`` a full GEMM executes; work
that is recomputed, padded or done twice is not counted; a float32 matmul
counts its float32 operations once, however many bf16 passes carry it.
Peaks are the chip's published bf16 figures: a share of them is a share
of what the silicon can do, not of what float32 can reach.
"""

from __future__ import annotations

import json
import os
from typing import Dict, Tuple

_PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks(device_kind: str) -> Dict[str, float]:
    """The peaks of ``device_kind``; a device that is not in the table is
    an error, never a default."""
    with open(_PEAKS_FILE) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(
            f"no peaks for device kind {device_kind!r} in {_PEAKS_FILE} "
            f"(known: {sorted(table)})"
        )
    return table[device_kind]


def least_seconds(flops: float, nbytes: float, peak: Dict[str, float]) -> Tuple[float, str]:
    """The least time the chip could take, and which peak sets it."""
    t_compute = flops / peak["flops_per_s"]
    t_memory = nbytes / peak["hbm_bytes_per_s"]
    return (t_compute, "compute") if t_compute >= t_memory else (t_memory, "memory")


def cosine_features_cost(rows: int, d_in: int, d_feat: int, itemsize: int = 4) -> Tuple[float, float]:
    """cos(X W^T + b) over ``rows``: one GEMM; X and the bank read once,
    the features written once. The cosine itself is not counted."""
    flops = 2.0 * rows * d_in * d_feat
    nbytes = float(itemsize) * (rows * d_in + d_feat * d_in + d_feat + rows * d_feat)
    return flops, nbytes


def _cholesky_solves(d_feat: int, block: int, classes: int, epochs: int) -> float:
    """One factorization per block (kept over the epochs), two triangular
    solves per block step."""
    nb = d_feat // block
    return nb * block ** 3 / 3.0 + epochs * nb * 2.0 * block * block * classes


def gram_bcd_fit_flops(rows: int, d_in: int, d_feat: int, classes: int,
                       block: int, epochs: int) -> float:
    """Streamed fit on the normal equations: featurize, G = F^T F (syrk),
    F^T Y, then per block step one (d, block) x (block, k) update of G W."""
    nb = d_feat // block
    return (
        cosine_features_cost(rows, d_in, d_feat)[0]
        + float(rows) * d_feat * d_feat
        + 2.0 * rows * d_feat * classes
        + epochs * nb * 2.0 * d_feat * block * classes
        + _cholesky_solves(d_feat, block, classes, epochs)
    )


def block_bcd_fit_flops(rows: int, d_in: int, d_feat: int, classes: int,
                        block: int, epochs: int) -> float:
    """Resident residual-form fit: featurize, one (block x block) Gramian
    per block (syrk), then per block step A_b^T R and the residual update."""
    nb = d_feat // block
    return (
        cosine_features_cost(rows, d_in, d_feat)[0]
        + nb * float(rows) * block * block
        + epochs * nb * 4.0 * rows * block * classes
        + _cholesky_solves(d_feat, block, classes, epochs)
    )


FIT_FLOPS = {"gram_bcd": gram_bcd_fit_flops, "block_bcd": block_bcd_fit_flops}
