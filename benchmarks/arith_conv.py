"""Operations and bytes of the RandomPatchCifar fit, from shapes alone, in
``benchmarks/arith.py``'s conventions: a multiply-add is two operations, a
block Gramian is counted ``n d_b^2`` (its upper triangle), float32 work is
counted once however many bf16 passes carry it, and nothing done twice or
padded is counted.
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple


def conv_map_side(config: Dict[str, Any]) -> int:
    return config["image_size"] - config["patch_size"] + 1


def pools_a_side(config: Dict[str, Any]) -> int:
    """Pooler.scala's pools along one axis of the conv map."""
    side, half = conv_map_side(config), config["pool_size"] // 2
    return len(range(half, side, config["pool_stride"]))


def pooled_features(config: Dict[str, Any]) -> int:
    """filters x 2 (the two-sided rectifier) x the pools."""
    return config["num_filters"] * 2 * pools_a_side(config) ** 2


def conv_featurize_cost(images: int, config: Dict[str, Any],
                        itemsize: int = 4) -> Tuple[float, float]:
    """The featurize of ``images``: one filter product a window (2 · windows
    · patch² · channels · filters); the images read once and the pooled
    features written once — the same count whatever implements it (the conv
    map, the rectified copy and the windows are not counted)."""
    windows = conv_map_side(config) ** 2
    patch = config["patch_size"] ** 2 * config["channels"]
    flops = 2.0 * images * windows * patch * config["num_filters"]
    nbytes = float(itemsize) * (
        images * config["image_size"] ** 2 * config["channels"]
        + config["num_filters"] * patch
        + images * pooled_features(config))
    return flops, nbytes


def feature_blocks(d: int, block: int) -> List[int]:
    """Block widths of ``d`` features: whole blocks, then the rest."""
    return [block] * (d // block) + ([d % block] if d % block else [])


def block_solve_flops(rows: int, d: int, block: int, classes: int, epochs: int) -> float:
    """Block Gauss-Seidel in residual form (``arith.block_bcd_fit_flops``
    without its featurize): one Gramian and one factor a block, then a
    block step's A_b^T R, residual update and two triangular solves."""
    blocks = feature_blocks(d, block)
    return (sum(float(rows) * b * b + b ** 3 / 3.0 for b in blocks)
            + epochs * sum(4.0 * rows * b * classes + 2.0 * b * b * classes for b in blocks))


def fit_flops(rows: int, config: Dict[str, Any]) -> float:
    """What one fit needs: the featurize of ``rows`` images and the solve
    (the filter draw, the scaler and the centring are under 0.1% of it)."""
    return (conv_featurize_cost(rows, config)[0]
            + block_solve_flops(rows, pooled_features(config), config["block_size"],
                                config["num_classes"], config["num_epochs"]))
