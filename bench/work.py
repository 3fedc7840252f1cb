"""The least work of a sparse product, from the matrix's shapes alone.

One call multiplies an ``n_rows x n_cols`` matrix with ``nnz`` stored
entries by ``k`` right-hand sides.  Whatever format or kernel serves it,
the call has to read each value (4 B) and each column index (4 B) once,
the row pointer (``n_rows + 1`` entries of 4 B) once, and each
right-hand side and result once (4 B an entry), and it does one multiply
and one add per entry and right-hand side.  Padding, gathered panels and
copies are not work, so a format that needs fewer of them moves the
roofline share honestly and never past 100%.
"""
from __future__ import annotations

from typing import Dict

WORD = 4


def least_bytes(n_rows: int, n_cols: int, nnz: int, k: int = 1) -> int:
    return (WORD * 2 * nnz + WORD * (n_rows + 1)
            + WORD * k * (n_cols + n_rows))


def least_flops(nnz: int, k: int = 1) -> int:
    return 2 * nnz * k


def least_seconds(n_rows: int, n_cols: int, nnz: int, k: int,
                  peaks: Dict[str, object]) -> float:
    """The larger of bytes over HBM bandwidth and operations over peak."""
    return max(least_bytes(n_rows, n_cols, nnz, k)
               / float(peaks["hbm_bytes_per_s"]),
               least_flops(nnz, k) / float(peaks["flops_per_s"]))
