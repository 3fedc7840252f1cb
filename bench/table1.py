"""Table-1 matrix generator, kept with the benchmark.

The paper (arXiv:2407.00019) publishes each matrix's N, NNZ, mean and
standard deviation of the row length, and D_mat, but not the matrix
files' column structure.  A configuration file names those statistics
and the models below, and this module builds a CSR matrix from them:

  * ``normal`` rows: lengths ``round(N(mu, sigma))`` clipped to ``[1, n]``
    (the low-variation FEM matrices);
  * ``two_point`` rows: a deterministic mixture of short rows and a few
    very long ones whose two lengths are solved from ``(mu, sigma)`` (the
    heavy-tailed matrices, torso1 among them);
  * row totals are then moved by +/-1 on single rows until they sum to
    NNZ exactly;
  * ``band`` columns: a contiguous band centred on the diagonal;
    ``scatter`` columns: ``i + k*h (mod n)`` with ``gcd(h, n) = 1``;
  * values ``1 + 0.01 * (k mod 7)`` over the stored entries in order.

It builds the same arrays, bit for bit, as the program's own Table-1
synthesizer did when this benchmark was written (``bench/tests`` checks
it), without a loop over rows.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict

import numpy as np

#: the CSR arrays are padded to a multiple of this many entries
PAD = 8
_PRIMES = (1000003, 411451, 611953)


@dataclass(frozen=True)
class HostCSR:
    """A CSR matrix on the host: ``data`` and ``cols`` are padded to
    ``nnz_pad`` with zeros; ``indptr`` has ``n_rows + 1`` entries."""
    data: np.ndarray      # float32 (nnz_pad,)
    cols: np.ndarray      # int32 (nnz_pad,)
    indptr: np.ndarray    # int32 (n_rows + 1,)
    n_rows: int
    n_cols: int
    nnz: int


def _lengths_normal(rng: np.random.Generator, n: int, mu: float,
                    sigma: float) -> np.ndarray:
    lens = np.rint(rng.normal(mu, sigma, size=n)).astype(np.int64)
    return np.clip(lens, 1, n)


def _lengths_two_point(n: int, mu: float, sigma: float) -> np.ndarray:
    """Two row lengths S and B, a share f of rows at B:
    ``f*B + (1-f)*S = mu`` and ``f*B^2 + (1-f)*S^2 = sigma^2 + mu^2``."""
    s = max(1, int(round(mu / 2)))
    m2 = sigma * sigma + mu * mu
    big = (m2 - s * s) / max(mu - s, 1e-9)
    f = (mu - s) / max(big - s, 1e-9)
    big = int(min(round(big), n))
    n_big = max(1, int(round(f * n)))
    lens = np.full(n, s, dtype=np.int64)
    lens[np.linspace(0, n - 1, n_big).astype(np.int64)] = big
    return lens


def _adjust_total(lens: np.ndarray, target_nnz: int, n: int) -> np.ndarray:
    """+/-1 on single rows, shortest (or longest) first, until the lengths
    sum to ``target_nnz``."""
    lens = lens.copy()
    diff = int(target_nnz - lens.sum())
    if diff == 0:
        return lens
    step = 1 if diff > 0 else -1
    k = abs(diff)
    order = np.argsort(lens) if step > 0 else np.argsort(-lens)
    i = 0
    while k > 0:
        r = order[i % n]
        new = lens[r] + step
        if 1 <= new <= n:
            lens[r] = new
            k -= 1
        i += 1
    return lens


def row_lengths(spec: Dict[str, Any], seed: int) -> np.ndarray:
    """The row lengths of ``spec`` (a configuration's ``matrix`` entry)."""
    n, nnz = int(spec["n"]), int(spec["nnz"])
    rng = np.random.default_rng(seed + int(spec["table1_no"]))
    model = spec["row_model"]
    if model == "two_point":
        lens = _lengths_two_point(n, spec["mu"], spec["sigma"])
    elif model == "normal":
        lens = _lengths_normal(rng, n, spec["mu"], spec["sigma"])
    else:
        raise ValueError(f"unknown row model {model!r}")
    return np.minimum(_adjust_total(lens, nnz, n), n)


def synthesize(spec: Dict[str, Any], seed: int) -> HostCSR:
    """Build the matrix that ``spec`` describes from ``seed``."""
    n = int(spec["n"])
    lens = row_lengths(spec, seed)
    nnz = int(lens.sum())
    indptr = np.zeros(n + 1, dtype=np.int32)
    np.cumsum(lens, out=indptr[1:])
    rows = np.repeat(np.arange(n, dtype=np.int64), lens)
    k = np.arange(nnz, dtype=np.int64) - np.repeat(
        indptr[:-1].astype(np.int64), lens)          # slot within its row
    pattern = spec["columns"]
    if pattern == "band":
        start = np.minimum(np.maximum(np.arange(n) - lens // 2, 0), n - lens)
        cols = np.repeat(start, lens) + k
    elif pattern == "scatter":
        h = _PRIMES[int(spec["table1_no"]) % len(_PRIMES)]
        while np.gcd(h, n) != 1:
            h += 2
        cols = (rows + k * h) % n
    else:
        raise ValueError(f"unknown column pattern {pattern!r}")
    nnz_pad = max(-(-nnz // PAD) * PAD, PAD)
    data = np.zeros(nnz_pad, np.float32)
    data[:nnz] = 1.0 + 0.01 * (np.arange(nnz) % 7)
    cols_p = np.zeros(nnz_pad, np.int32)
    cols_p[:nnz] = cols
    return HostCSR(data=data, cols=cols_p, indptr=indptr, n_rows=n,
                   n_cols=n, nnz=nnz)
