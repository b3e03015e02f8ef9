"""Operations and bytes the sparse gram-engine fit NEEDS, from shapes alone
— the sparse sibling of ``arith.py``, under the same conventions: a
multiply-add is two operations, a symmetric rank-k update is counted
``n d^2`` (its upper triangle), nothing padded, recomputed or done twice is
counted, and a float32 product counts once however many passes carry it.

``d`` is the feature width WITHOUT the intercept; the ones column the
solver appends makes every width here ``d + 1``.
"""

from __future__ import annotations

from typing import Tuple


def gram_fold_cost(rows: int, d: int, lanes: int, classes: int, chunk_rows: int,
                   slab_itemsize: int = 4) -> Tuple[float, float]:
    """Folding ``rows`` padded-COO rows into G = X^T X and X^T Y through
    dense slabs of ``chunk_rows``: (operations, bytes). Operations: the
    syrk ``n (d+1)^2`` and the correlation ``2 n (d+1) k``, as a dense fold
    needs them — the same count whatever implements the fold. Bytes: each
    slab read once, the COO (int32 + float32 a lane, the intercept lane
    included) and the targets read once, and the Gramian read and written
    once a chunk."""
    d1, chunks = d + 1, -(-rows // chunk_rows)
    flops = float(rows) * d1 * d1 + 2.0 * rows * d1 * classes
    nbytes = (float(slab_itemsize) * rows * d1
              + 8.0 * rows * (lanes + 1) + 4.0 * rows * classes
              + chunks * 2.0 * 4.0 * (d1 * d1 + d1 * classes))
    return flops, nbytes


def gram_lbfgs_fit_flops(rows: int, d: int, lanes: int, classes: int,
                         iterations: int) -> float:
    """One whole gram-engine fit: the syrk, X^T Y over the active lanes
    alone, and ``iterations`` Hessian products on the Gramian plus the one
    of the final loss."""
    d1 = d + 1
    return (float(rows) * d1 * d1
            + 2.0 * rows * (lanes + 1) * classes
            + (iterations + 1) * 2.0 * d1 * d1 * classes)
