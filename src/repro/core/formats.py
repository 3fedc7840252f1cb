"""Sparse-matrix storage formats as JAX pytrees.

The paper (Katagiri & Sato) studies run-time transformation between CRS
(a.k.a. CSR), COO (row- and column-ordered) and ELL.  We represent each
format as a registered-dataclass pytree whose array leaves may be numpy
(host) or jax.Array (device), with all *structural* metadata (shape, true
nnz, storage order, pad width) static so the objects cross ``jit``
boundaries with static shapes — the TPU adaptation of the paper's
call-time transformation model (§2 of DESIGN.md).

Padding conventions (needed because XLA requires static shapes):
  * CSR/COO: nnz padded up to ``pad_to`` with (row=0, col=0, val=0) entries —
    harmless for SpMV since the value is zero.
  * ELL: ``data``/``cols`` are dense ``(n_rows, width)`` (row order) or
    ``(width, n_rows)`` (column order, the paper's "ELL-Col" storage);
    missing band entries hold (col=0, val=0) exactly as the paper describes
    ("the value of zero is inserted in the position of missing band parts").
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Tuple, Union

import jax
import numpy as np

Array = Any  # np.ndarray | jax.Array


class MatrixValidationError(ValueError):
    """A sparse container's structural invariants do not hold (malformed
    indptr, out-of-range indices, wrong dtypes).  Raised at the trust
    boundaries — ``SpMVService.register`` and ``plan.bind`` — so corrupt
    input fails loudly there instead of as NaN/garbage deep inside a
    kernel (see docs/robustness.md)."""


def _register(cls, data_fields, meta_fields):
    jax.tree_util.register_dataclass(cls, data_fields=list(data_fields),
                                     meta_fields=list(meta_fields))
    return cls


def _np(x) -> np.ndarray:
    return np.asarray(x)


# ---------------------------------------------------------------------------
# CSR — the paper's CRS: VAL(1:nnz), ICOL(1:nnz), IRP(1:n+1)
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class CSR:
    data: Array      # (nnz_pad,)  = VAL
    cols: Array      # (nnz_pad,)  = ICOL
    indptr: Array    # (n_rows+1,) = IRP
    shape: Tuple[int, int]
    nnz: int         # true nnz (<= nnz_pad)

    @property
    def n_rows(self) -> int:
        return self.shape[0]

    @property
    def n_cols(self) -> int:
        return self.shape[1]

    @property
    def nnz_pad(self) -> int:
        return int(self.data.shape[0])

    def row_lengths(self) -> np.ndarray:
        ip = _np(self.indptr)
        return ip[1:] - ip[:-1]

    def todense(self) -> np.ndarray:
        out = np.zeros(self.shape, dtype=_np(self.data).dtype)
        ip, cols, data = _np(self.indptr), _np(self.cols), _np(self.data)
        for i in range(self.n_rows):
            s, e = ip[i], ip[i + 1]
            # duplicate (i, j) entries accumulate, matching SpMV semantics
            np.add.at(out[i], cols[s:e], data[s:e])
        return out

    def validate(self) -> "CSR":
        """Check the CSR structural invariants; raises
        :class:`MatrixValidationError` on the first violation, returns
        ``self`` for chaining.  One O(n + nnz) numpy pass — cheap at the
        register/bind boundary relative to the transform it gates."""
        ip = _np(self.indptr)
        cols = _np(self.cols)
        data = _np(self.data)
        if ip.ndim != 1 or ip.shape[0] != self.n_rows + 1:
            raise MatrixValidationError(
                f"indptr must have shape ({self.n_rows + 1},); "
                f"got {ip.shape}")
        if not np.issubdtype(ip.dtype, np.integer):
            raise MatrixValidationError(
                f"indptr must be an integer array; got dtype {ip.dtype}")
        if not np.issubdtype(cols.dtype, np.integer):
            raise MatrixValidationError(
                f"cols must be an integer array; got dtype {cols.dtype}")
        if int(ip[0]) != 0:
            raise MatrixValidationError(
                f"indptr[0] must be 0; got {int(ip[0])}")
        if np.any(ip[1:] < ip[:-1]):
            i = int(np.argmax(ip[1:] < ip[:-1]))
            raise MatrixValidationError(
                f"indptr must be monotone non-decreasing; "
                f"indptr[{i + 1}]={int(ip[i + 1])} < "
                f"indptr[{i}]={int(ip[i])}")
        if int(ip[-1]) != self.nnz:
            raise MatrixValidationError(
                f"indptr[-1] must equal nnz={self.nnz}; "
                f"got {int(ip[-1])}")
        if self.nnz > self.nnz_pad:
            raise MatrixValidationError(
                f"nnz={self.nnz} exceeds storage nnz_pad={self.nnz_pad}")
        if cols.shape != data.shape:
            raise MatrixValidationError(
                f"cols and data must share a shape; "
                f"got {cols.shape} vs {data.shape}")
        if self.nnz > 0:
            live = cols[: self.nnz]
            lo, hi = int(live.min()), int(live.max())
            if lo < 0 or hi >= self.n_cols:
                raise MatrixValidationError(
                    f"column indices must lie in [0, {self.n_cols}); "
                    f"found range [{lo}, {hi}]")
        return self


_register(CSR, ("data", "cols", "indptr"), ("shape", "nnz"))


# ---------------------------------------------------------------------------
# CCS — compressed column storage (paper's Phase-I target)
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class CCS:
    data: Array      # (nnz_pad,)
    rows: Array      # (nnz_pad,) row index of each stored value
    indptr: Array    # (n_cols+1,)
    shape: Tuple[int, int]
    nnz: int

    @property
    def n_rows(self) -> int:
        return self.shape[0]

    @property
    def n_cols(self) -> int:
        return self.shape[1]

    @property
    def nnz_pad(self) -> int:
        return int(self.data.shape[0])

    def todense(self) -> np.ndarray:
        out = np.zeros(self.shape, dtype=_np(self.data).dtype)
        ip, rows, data = _np(self.indptr), _np(self.rows), _np(self.data)
        for j in range(self.n_cols):
            s, e = ip[j], ip[j + 1]
            np.add.at(out[:, j], rows[s:e], data[s:e])
        return out

    def validate(self) -> "CCS":
        """CSR's invariants mirrored over columns: ``indptr`` segments the
        column axis and ``rows`` must stay inside the row space."""
        ip = _np(self.indptr)
        rows = _np(self.rows)
        data = _np(self.data)
        if ip.ndim != 1 or ip.shape[0] != self.n_cols + 1:
            raise MatrixValidationError(
                f"indptr must have shape ({self.n_cols + 1},); "
                f"got {ip.shape}")
        if not np.issubdtype(ip.dtype, np.integer):
            raise MatrixValidationError(
                f"indptr must be an integer array; got dtype {ip.dtype}")
        if not np.issubdtype(rows.dtype, np.integer):
            raise MatrixValidationError(
                f"rows must be an integer array; got dtype {rows.dtype}")
        if int(ip[0]) != 0:
            raise MatrixValidationError(
                f"indptr[0] must be 0; got {int(ip[0])}")
        if np.any(ip[1:] < ip[:-1]):
            j = int(np.argmax(ip[1:] < ip[:-1]))
            raise MatrixValidationError(
                f"indptr must be monotone non-decreasing; "
                f"indptr[{j + 1}]={int(ip[j + 1])} < "
                f"indptr[{j}]={int(ip[j])}")
        if int(ip[-1]) != self.nnz:
            raise MatrixValidationError(
                f"indptr[-1] must equal nnz={self.nnz}; got {int(ip[-1])}")
        if self.nnz > self.nnz_pad:
            raise MatrixValidationError(
                f"nnz={self.nnz} exceeds storage nnz_pad={self.nnz_pad}")
        if rows.shape != data.shape:
            raise MatrixValidationError(
                f"rows and data must share a shape; "
                f"got {rows.shape} vs {data.shape}")
        if self.nnz > 0:
            live = rows[: self.nnz]
            lo, hi = int(live.min()), int(live.max())
            if lo < 0 or hi >= self.n_rows:
                raise MatrixValidationError(
                    f"row indices must lie in [0, {self.n_rows}); "
                    f"found range [{lo}, {hi}]")
        return self


_register(CCS, ("data", "rows", "indptr"), ("shape", "nnz"))


# ---------------------------------------------------------------------------
# COO — VAL, ICOL, IROW; `order` records sortedness ("row" | "col" | None)
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class COO:
    data: Array      # (nnz_pad,)
    rows: Array      # (nnz_pad,)
    cols: Array      # (nnz_pad,)
    shape: Tuple[int, int]
    nnz: int
    order: Union[str, None] = "row"

    @property
    def n_rows(self) -> int:
        return self.shape[0]

    @property
    def n_cols(self) -> int:
        return self.shape[1]

    @property
    def nnz_pad(self) -> int:
        return int(self.data.shape[0])

    def todense(self) -> np.ndarray:
        out = np.zeros(self.shape, dtype=_np(self.data).dtype)
        np.add.at(out, (_np(self.rows), _np(self.cols)), _np(self.data))
        return out

    def validate(self) -> "COO":
        """Bounds, dtypes, and the sortedness the ``order`` tag promises
        (the segmented COO kernels rely on it for run detection)."""
        data = _np(self.data)
        rows = _np(self.rows)
        cols = _np(self.cols)
        if self.order not in ("row", "col", None):
            raise MatrixValidationError(
                f"order must be 'row', 'col', or None; got {self.order!r}")
        if not (data.ndim == rows.ndim == cols.ndim == 1):
            raise MatrixValidationError(
                "data/rows/cols must be 1-D arrays")
        if not (data.shape == rows.shape == cols.shape):
            raise MatrixValidationError(
                f"data/rows/cols must share a shape; got {data.shape}, "
                f"{rows.shape}, {cols.shape}")
        for name, arr in (("rows", rows), ("cols", cols)):
            if not np.issubdtype(arr.dtype, np.integer):
                raise MatrixValidationError(
                    f"{name} must be an integer array; got dtype "
                    f"{arr.dtype}")
        if self.nnz > self.nnz_pad:
            raise MatrixValidationError(
                f"nnz={self.nnz} exceeds storage nnz_pad={self.nnz_pad}")
        if self.nnz > 0:
            for name, arr, bound in (("rows", rows, self.n_rows),
                                     ("cols", cols, self.n_cols)):
                live = arr[: self.nnz]
                lo, hi = int(live.min()), int(live.max())
                if lo < 0 or hi >= bound:
                    raise MatrixValidationError(
                        f"{name} indices must lie in [0, {bound}); "
                        f"found range [{lo}, {hi}]")
            key = rows if self.order == "row" else \
                cols if self.order == "col" else None
            if key is not None:
                live = key[: self.nnz]
                if np.any(live[1:] < live[:-1]):
                    i = int(np.argmax(live[1:] < live[:-1]))
                    raise MatrixValidationError(
                        f"order={self.order!r} promises sorted "
                        f"{self.order} indices; violated at entry "
                        f"{i + 1} ({int(live[i + 1])} < {int(live[i])})")
        return self


_register(COO, ("data", "rows", "cols"), ("shape", "nnz", "order"))


# ---------------------------------------------------------------------------
# ELL — VAL(1:n, 1:nz): dense padded band storage.
#   order == "row": data[r, k] is the k-th stored entry of row r
#                   (paper's ELL-Row; TPU-friendly: row-major, width minor).
#   order == "col": data[k, r] — the paper's ELL-Col / inner-parallel layout.
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class ELL:
    data: Array      # (n_rows, width) or (width, n_rows)
    cols: Array      # same shape as data; padded entries point at column 0
    shape: Tuple[int, int]
    nnz: int
    order: str = "row"

    @property
    def n_rows(self) -> int:
        return self.shape[0]

    @property
    def n_cols(self) -> int:
        return self.shape[1]

    @property
    def width(self) -> int:
        return int(self.data.shape[1] if self.order == "row" else self.data.shape[0])

    def todense(self) -> np.ndarray:
        data = _np(self.data)
        cols = _np(self.cols)
        if self.order == "col":
            data, cols = data.T, cols.T
        out = np.zeros(self.shape, dtype=data.dtype)
        rows = np.broadcast_to(np.arange(self.n_rows)[:, None], data.shape)
        np.add.at(out, (rows.ravel(), cols.ravel()), data.ravel())
        return out

    def validate(self) -> "ELL":
        """Band-storage invariants.  Note the band ``width`` may exceed
        ``n_cols``: the transform quantum-pads it (multiples of 8), so
        only the *index* range is bounded, not the width."""
        data = _np(self.data)
        cols = _np(self.cols)
        if self.order not in ("row", "col"):
            raise MatrixValidationError(
                f"order must be 'row' or 'col'; got {self.order!r}")
        if data.ndim != 2 or data.shape != cols.shape:
            raise MatrixValidationError(
                f"data and cols must be 2-D with one shape; got "
                f"{data.shape} vs {cols.shape}")
        if not np.issubdtype(cols.dtype, np.integer):
            raise MatrixValidationError(
                f"cols must be an integer array; got dtype {cols.dtype}")
        row_axis = data.shape[0] if self.order == "row" else data.shape[1]
        if row_axis != self.n_rows:
            raise MatrixValidationError(
                f"{self.order}-order storage must span n_rows="
                f"{self.n_rows} on its row axis; got {row_axis}")
        if self.nnz > self.n_rows * max(self.width, 0):
            raise MatrixValidationError(
                f"nnz={self.nnz} cannot fit n_rows={self.n_rows} x "
                f"width={self.width} band storage")
        if cols.size and self.n_cols > 0:
            # padded entries point at column 0, so every slot is bounded
            lo, hi = int(cols.min()), int(cols.max())
            if lo < 0 or hi >= self.n_cols:
                raise MatrixValidationError(
                    f"column indices must lie in [0, {self.n_cols}); "
                    f"found range [{lo}, {hi}]")
        return self


_register(ELL, ("data", "cols"), ("shape", "nnz", "order"))


# ---------------------------------------------------------------------------
# BucketedELL — beyond-paper SELL-C-σ adaptation (DESIGN.md §2).
# Rows are sorted by length (σ-sort over the whole matrix), grouped into
# width buckets; each bucket is a dense ELL block over a contiguous slice of
# the *permuted* row space.  `perm[i]` = original row of permuted row i.
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class BucketedELL:
    perm: Array                 # (n_rows,) permuted -> original row index
    buckets: Tuple[ELL, ...]    # each over (bucket_rows, n_cols)
    row_offsets: Tuple[int, ...]  # static: start row (permuted) of each bucket
    shape: Tuple[int, int]
    nnz: int

    @property
    def n_rows(self) -> int:
        return self.shape[0]

    @property
    def n_cols(self) -> int:
        return self.shape[1]

    @property
    def widths(self) -> Tuple[int, ...]:
        return tuple(b.width for b in self.buckets)

    def padded_nnz(self) -> int:
        return sum(int(np.prod(b.data.shape)) for b in self.buckets)

    def todense(self) -> np.ndarray:
        out = np.zeros(self.shape, dtype=_np(self.buckets[0].data).dtype)
        perm = _np(self.perm)
        for off, b in zip(self.row_offsets, self.buckets):
            dense_b = b.todense()  # (bucket_rows, n_cols)
            rows = perm[off:off + dense_b.shape[0]]
            out[rows] += dense_b
        return out

    def validate(self) -> "BucketedELL":
        """SELL invariants: ``perm`` is a permutation, buckets tile the
        permuted row space contiguously, widths are distinct and
        monotone non-increasing (widest bucket first — the sort order
        the transform emits and the per-bucket tuner keys on), and the
        bucket nnz sums to the whole."""
        perm = _np(self.perm)
        if perm.ndim != 1 or perm.shape[0] != self.n_rows:
            raise MatrixValidationError(
                f"perm must have shape ({self.n_rows},); got {perm.shape}")
        if not np.issubdtype(perm.dtype, np.integer):
            raise MatrixValidationError(
                f"perm must be an integer array; got dtype {perm.dtype}")
        if not np.array_equal(np.sort(perm),
                              np.arange(self.n_rows, dtype=perm.dtype)):
            raise MatrixValidationError(
                "perm is not a permutation of the row indices")
        if len(self.row_offsets) != len(self.buckets):
            raise MatrixValidationError(
                f"{len(self.buckets)} buckets but "
                f"{len(self.row_offsets)} row offsets")
        if not self.buckets:
            raise MatrixValidationError("SELL container has no buckets")
        if self.row_offsets[0] != 0:
            raise MatrixValidationError(
                f"row_offsets must start at 0; got {self.row_offsets[0]}")
        end = 0
        for i, (off, b) in enumerate(zip(self.row_offsets, self.buckets)):
            if off != end:
                raise MatrixValidationError(
                    f"bucket {i} starts at permuted row {off}, expected "
                    f"{end} (buckets must tile contiguously)")
            if b.shape[1] != self.n_cols:
                raise MatrixValidationError(
                    f"bucket {i} spans {b.shape[1]} columns, expected "
                    f"{self.n_cols}")
            end = off + b.n_rows
            b.validate()
        if end != self.n_rows:
            raise MatrixValidationError(
                f"buckets cover {end} permuted rows, expected "
                f"{self.n_rows}")
        widths = self.widths
        for a, b_ in zip(widths, widths[1:]):
            if b_ >= a:
                raise MatrixValidationError(
                    f"bucket widths must be distinct and strictly "
                    f"decreasing (widest first); got {widths}")
        if sum(b.nnz for b in self.buckets) != self.nnz:
            raise MatrixValidationError(
                f"bucket nnz sums to "
                f"{sum(b.nnz for b in self.buckets)}, expected {self.nnz}")
        return self


_register(BucketedELL, ("perm", "buckets"), ("row_offsets", "shape", "nnz"))


# ---------------------------------------------------------------------------
# Statistics — the paper's D_mat = sigma / mu (eq. 4)
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class MatrixStats:
    n: int
    nnz: int
    mu: float        # mean nnz per row
    sigma: float     # stddev nnz per row (population, as in the paper)
    d_mat: float     # sigma / mu
    max_row: int
    min_row: int

    @staticmethod
    def of(mat: "CSR") -> "MatrixStats":
        lens = mat.row_lengths().astype(np.float64)
        mu = float(lens.mean())
        sigma = float(lens.std())
        return MatrixStats(
            n=mat.n_rows, nnz=mat.nnz, mu=mu, sigma=sigma,
            d_mat=sigma / mu if mu > 0 else float("inf"),
            max_row=int(lens.max()), min_row=int(lens.min()),
        )


def memory_bytes(fmt) -> int:
    """Storage footprint of a format instance (index + value arrays)."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(fmt):
        total += int(np.prod(leaf.shape)) * np.dtype(leaf.dtype).itemsize
    return total


def validate_container(obj):
    """Run a container's :meth:`validate` when it has one (every format
    in this module does; the hybrid container validates per block at its
    own boundary).  Returns ``obj`` for chaining — the shared entry point
    ``plan.bind`` uses after each transform."""
    check = getattr(obj, "validate", None)
    if callable(check):
        check()
    return obj


# FORMAT_NAMES is derived from the dispatch registry (module __getattr__
# below) so it can never again go stale against the registered formats —
# it used to be a hand-maintained literal that silently omitted bcsr/ccs.
def __getattr__(name: str):
    if name == "FORMAT_NAMES":
        from . import dispatch
        return tuple(dispatch.registered_formats("spmv"))
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


__all__ = [
    "CSR", "CCS", "COO", "ELL", "BucketedELL", "MatrixStats",
    "MatrixValidationError", "memory_bytes", "validate_container",
    "FORMAT_NAMES",
]


# ---------------------------------------------------------------------------
# BCSR — the paper's named future work ("evaluating the transformation to
# other formats, such as BCSR, which enables cache blocking").  b x b dense
# blocks in CSR order: on TPU each block is an MXU-shaped tile, so BCSR
# SpMV becomes a stream of tiny dense matmuls — the cache-blocking the
# paper anticipates, mapped to VMEM tiles.
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class BCSR:
    data: Array        # (nblocks_pad, b, b)
    block_cols: Array  # (nblocks_pad,) block-column indices
    indptr: Array      # (n_block_rows + 1,)
    shape: Tuple[int, int]
    nnz: int           # true scalar nnz represented
    block: int         # b

    @property
    def n_rows(self) -> int:
        return self.shape[0]

    @property
    def n_cols(self) -> int:
        return self.shape[1]

    @property
    def n_block_rows(self) -> int:
        return int(self.indptr.shape[0]) - 1

    @property
    def nblocks_pad(self) -> int:
        return int(self.data.shape[0])

    def todense(self) -> np.ndarray:
        b = self.block
        nbr = self.n_block_rows
        out = np.zeros((nbr * b, self.n_cols + (-self.n_cols) % b),
                       dtype=_np(self.data).dtype)
        ip = _np(self.indptr)
        bc = _np(self.block_cols)
        dat = _np(self.data)
        for i in range(nbr):
            for p in range(ip[i], ip[i + 1]):
                j = bc[p]
                out[i * b:(i + 1) * b, j * b:(j + 1) * b] += dat[p]
        return out[: self.n_rows, : self.n_cols]

    def validate(self) -> "BCSR":
        """CSR invariants lifted to the block grid: ``indptr`` segments
        ``ceil(n_rows / b)`` block rows, stored tiles are dense ``b x b``,
        and block columns stay inside ``ceil(n_cols / b)``."""
        b = self.block
        if not isinstance(b, int) or b < 1:
            raise MatrixValidationError(
                f"block size must be a positive int; got {b!r}")
        ip = _np(self.indptr)
        bc = _np(self.block_cols)
        data = _np(self.data)
        nbr = -(-self.n_rows // b) if self.n_rows else 0
        if data.ndim != 3 or data.shape[1:] != (b, b):
            raise MatrixValidationError(
                f"data must be (nblocks_pad, {b}, {b}) dense tiles; "
                f"got {data.shape}")
        if ip.ndim != 1 or ip.shape[0] != nbr + 1:
            raise MatrixValidationError(
                f"indptr must have shape ({nbr + 1},) for n_rows="
                f"{self.n_rows}, block={b}; got {ip.shape}")
        for name, arr in (("indptr", ip), ("block_cols", bc)):
            if not np.issubdtype(arr.dtype, np.integer):
                raise MatrixValidationError(
                    f"{name} must be an integer array; got dtype "
                    f"{arr.dtype}")
        if int(ip[0]) != 0:
            raise MatrixValidationError(
                f"indptr[0] must be 0; got {int(ip[0])}")
        if np.any(ip[1:] < ip[:-1]):
            i = int(np.argmax(ip[1:] < ip[:-1]))
            raise MatrixValidationError(
                f"indptr must be monotone non-decreasing; "
                f"indptr[{i + 1}]={int(ip[i + 1])} < "
                f"indptr[{i}]={int(ip[i])}")
        nblocks = int(ip[-1]) if ip.size else 0
        if nblocks > self.nblocks_pad:
            raise MatrixValidationError(
                f"indptr stores {nblocks} blocks but only "
                f"{self.nblocks_pad} are allocated")
        if bc.shape != (self.nblocks_pad,):
            raise MatrixValidationError(
                f"block_cols must have shape ({self.nblocks_pad},); "
                f"got {bc.shape}")
        if self.nnz > nblocks * b * b:
            raise MatrixValidationError(
                f"nnz={self.nnz} cannot fit {nblocks} dense {b}x{b} "
                f"blocks")
        if nblocks > 0:
            nbc = -(-self.n_cols // b)
            live = bc[:nblocks]
            lo, hi = int(live.min()), int(live.max())
            if lo < 0 or hi >= nbc:
                raise MatrixValidationError(
                    f"block-column indices must lie in [0, {nbc}); "
                    f"found range [{lo}, {hi}]")
        return self


_register(BCSR, ("data", "block_cols", "indptr"), ("shape", "nnz", "block"))


def bcsr_fill_ratio(m: "BCSR") -> float:
    """nnz / stored scalars — the density of the chosen blocks (the BCSR
    analogue of ELL's padding ratio; drives the same AT cost algebra)."""
    stored = m.nblocks_pad * m.block * m.block
    return m.nnz / stored if stored else 0.0
