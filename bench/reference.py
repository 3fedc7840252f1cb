"""The plain reference and the number that decides ``correct``.

The reference is a float64 CSR product on the host in numpy.  It shares
no code with the program and takes nothing the program made: it reads
the host matrix the benchmark synthesized and the client's vectors.

For one product ``y = A @ x`` the compared number is the worst row error
relative to the row's absolute sum::

    row_err = max_i |y_i - yref_i| / sum_j |a_ij * x_j|

float32 arithmetic reads about 1e-7 here and bfloat16 about 1e-3; the
limit between them, with the readings it was set from, is in PERF.md and
in ``bench/limits.json``.
"""
from __future__ import annotations

from typing import Tuple

import numpy as np

from bench.table1 import HostCSR


class HostReference:
    """float64 ``A @ x`` for one host matrix, column by column."""

    def __init__(self, csr: HostCSR):
        nnz = csr.nnz
        self.n_rows = csr.n_rows
        self.rows = np.repeat(np.arange(csr.n_rows),
                              np.diff(csr.indptr.astype(np.int64)))
        self.a = csr.data[:nnz].astype(np.float64)
        self.c = csr.cols[:nnz]

    def product(self, x: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        """``(A @ x, |A| @ |x|)`` in float64 for one vector ``x``."""
        prod = self.a * np.asarray(x, np.float64)[self.c]
        y = np.bincount(self.rows, prod, minlength=self.n_rows)
        scale = np.bincount(self.rows, np.abs(prod), minlength=self.n_rows)
        return y, scale

    def row_err(self, y: np.ndarray, x: np.ndarray) -> float:
        """The compared number for one served product ``y`` of ``x``; a
        shape mismatch or a non-finite entry reads as infinity."""
        y = np.asarray(y, np.float64)
        if y.shape != (self.n_rows,) or not np.isfinite(y).all():
            return float("inf")
        ref, scale = self.product(x)
        return float((np.abs(y - ref) / np.maximum(scale, 1e-300)).max())


class Control:
    """The control: the reference put in the program's place on the
    device and computed one precision down from the configuration's
    float32.  Values and vector are rounded to bfloat16 and multiplied in
    bfloat16; the row sums run in float32."""

    def __init__(self, csr: HostCSR):
        import jax
        import jax.numpy as jnp
        nnz, n_rows = csr.nnz, csr.n_rows
        rows = np.repeat(np.arange(n_rows, dtype=np.int32),
                         np.diff(csr.indptr.astype(np.int64)))
        self.args = jax.device_put((csr.data[:nnz], csr.cols[:nnz], rows))

        def product(a, c, r, x):
            prod = a.astype(jnp.bfloat16) * x.astype(jnp.bfloat16)[c]
            return jax.ops.segment_sum(prod.astype(jnp.float32), r,
                                       num_segments=n_rows)
        self._product = jax.jit(product)

    def product(self, x) -> np.ndarray:
        return np.asarray(self._product(*self.args, x))
