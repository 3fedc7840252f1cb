"""jit'd public wrappers around the Pallas kernels.

Responsibilities:
  * gather ``x[ICOL]`` in XLA and lay VAL and the gathered panel out the
    way the kernels stream them (band-major for ELL, lane-dense rows for
    CSR), padded to legal TPU blocks (val=0/col=0 padding — the paper's
    own ELL zero-fill convention, so padding never changes results); the
    gather runs under the ``gather_x`` named scope and the SELL row
    scatter under ``reassemble``, so that a device trace names them;
  * gather x for an ELL block's SpMV by column runs where the block
    allows it: when every run of 8 band slots of a row (the band tile's 8
    sublanes) reads at most 8 consecutive columns, one 8-wide column of a
    window panel ``xw[j, c] = x[c + j]`` per run, and a select puts each
    slot's value in place (``_gather_band``; an eighth of the indices,
    and a padding slot reads 0.0).  The block's own columns decide, on
    the device; any other block, the SpMM path and CSR gather one element
    per index, as ``_gather_x`` always did;
  * accept the ``repro.core.formats`` pytree classes;
  * provide a custom VJP so the ELL kernel is trainable (y = A@x  =>
    dx = A^T dy via a scatter; dA = dy_r * x_c at the stored positions);
  * choose compiled mode on a TPU and interpret mode elsewhere — one
    kernel body for both, so the CPU tests check the code the chip runs;
  * accept a per-call launch geometry (``tuning=`` — a
    ``core.kernel_tune.TileGeometry``); ``None`` fields fall back to the
    built-in defaults below, and every tile is rounded to a shape the TPU
    compiler accepts (lane tiles: a multiple of 128 or the whole axis);
  * register every format-level wrapper in the ``repro.core.dispatch``
    registry under the ``"kernel"`` tier — ``KERNEL_SPMV_IMPLS`` /
    ``KERNEL_SPMM_IMPLS`` below are views of that registry, kept for
    callers that want a plain dict.

The kernel tier covers CSR (``kernels/csr_spmv.py``), ELL in both storage
orders and SELL (``kernels/ell_spmv.py``, one launch per bucket), and the
hybrid container (each block through its format's kernel).  COO, CCS and
BCSR are served by the reference tier.
"""
from __future__ import annotations

import functools
from typing import Dict, Mapping, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import dispatch as _dispatch
from repro.core.formats import CSR, ELL, BucketedELL
from repro.core.kernel_tune import MAX_TILE, TileGeometry
from . import csr_spmv as _csr
from . import ell_spmv as _ell


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _interpret(flag: Optional[bool]) -> bool:
    """Compiled on a TPU, interpreted elsewhere.  Asking for the
    interpreter on a TPU is an error: it would serve results at a small
    fraction of the chip's speed without saying so."""
    on_tpu = _on_tpu()
    if flag and on_tpu:
        raise ValueError("Pallas interpret mode requested on a TPU backend")
    return (not on_tpu) if flag is None else flag


def _pad_to(x: jax.Array, axis: int, mult: int, value=0) -> jax.Array:
    size = x.shape[axis]
    target = ((size + mult - 1) // mult) * mult
    if target == size:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, target - size)
    return jnp.pad(x, pads, constant_values=value)


def _align8(n: int) -> int:
    return max(8, 8 * ((int(n) + 7) // 8))


def _lane_tile(v: int, n: int) -> int:
    """A tile along a lane (minor) axis of extent ``n`` that the TPU
    compiler accepts: a multiple of 128, or the whole 8-aligned axis."""
    full = _align8(n)
    t = 128 * -(-int(v) // 128)
    return full if t >= full else t


def _geom(tuning: Optional[TileGeometry], name: str, default: int,
          cap: Optional[int] = None) -> int:
    v = getattr(tuning, name, None) if tuning is not None else None
    v = default if v is None else _align8(v)
    return min(v, cap) if cap is not None else v


def _block_sizes(n_rows: int, width: int) -> Tuple[int, int]:
    """Default ELL tile: 256 rows along the lanes (or every row of a
    smaller matrix); the band tile is the smallest 8-aligned cover of the
    band, capped at 128 — a 40-slot band gets a 40-slot tile, never a
    128-slot one."""
    return _lane_tile(256, n_rows), min(128, _align8(width))


def _block_k(k: int) -> int:
    return min(128, _align8(k))


def _gather_x(x: jax.Array, idx: jax.Array) -> jax.Array:
    """``x[ICOL]``, under the ``gather_x`` scope so that a device trace
    names it.  A vector gathers as ``x[idx]``; a panel ``(n_cols, k)``
    gathers every right-hand side in one gather from ``x.T``:
    ``(k, *idx.shape)``, lane-dense along ``idx``, indices clamped like
    ``x[idx]`` (no out-of-range fill mask)."""
    with jax.named_scope("gather_x"):
        if x.ndim == 1:
            return x[idx]
        return jnp.take(x.T, idx, axis=1, mode="clip")


# ---------------------------------------------------------------------------
# x gathered by column runs (the SpMV path of ELL blocks)
# ---------------------------------------------------------------------------
#: band slots per run: the ELL band tile's 8 sublanes (``block_w % 8 == 0``)
RUN = 8


def band_runs(data_t, cols_t, xp=jnp):
    """Cut a band-major ELL block ``(W, n_rows)`` into runs of ``RUN``
    consecutive band slots of one row: ``(cols, zero, base, fits)``.

    ``cols`` is the block's columns as ``(W/RUN, RUN, n_rows)``, the band
    padded to a multiple of ``RUN`` slots; ``zero`` marks the padding
    slots, those whose value and column are both 0 (the ELL zero-fill);
    ``base`` ``(W/RUN, n_rows)`` is the least column among a run's real
    slots (0 for a run of padding); ``fits`` is whether every real slot
    lies within ``RUN`` columns of its run's base, so that one ``RUN``-wide
    slice of x per run holds every value the run reads (a padding slot's
    column 0 never raises a run's greatest column).  ``xp`` is ``numpy``
    for host arrays, ``jax.numpy`` under trace."""
    pad = (0, -cols_t.shape[0] % RUN), (0, 0)
    data_t, cols_t = xp.pad(data_t, pad), xp.pad(cols_t, pad)
    cols = cols_t.reshape(-1, RUN, cols_t.shape[1])
    zero = (data_t.reshape(cols.shape) == 0) & (cols == 0)
    top = np.iinfo(cols.dtype).max
    base = xp.where(zero, top, cols).min(axis=1)
    fits = (cols.max(axis=1) - base < RUN).all()
    return cols, zero, xp.where(base == top, 0, base), fits


def _gather_runs(xw: jax.Array, cols: jax.Array, zero: jax.Array,
                 base: jax.Array) -> jax.Array:
    """``x[ICOL]`` of a block whose runs fit (:func:`band_runs`): one
    column of the window panel ``xw[j, c] = x[c + j]`` per run, then each
    slot picks its column's value out of its run's ``RUN`` values by an
    ``RUN``-way select.  Values are exact copies of x, and a padding slot
    reads 0.0, not ``x[0]``.  Returns the ``(W, n_rows)`` panel, laid out
    like VAL."""
    g = jnp.take(xw, base, axis=1, mode="clip")   # (RUN, W/RUN, n_rows)
    off = jnp.where(zero, -1, cols - base[:, None, :])
    xg = jnp.zeros(off.shape, xw.dtype)
    for j in range(RUN):
        xg = jnp.where(off == j, g[j][:, None, :], xg)
    return xg.reshape(-1, off.shape[2])


def _gather_band(x: jax.Array, data_t: jax.Array,
                 cols_t: jax.Array) -> jax.Array:
    """``x[ICOL]`` of a band-major block ``(W, n_rows)``, ``W`` a multiple
    of ``RUN``: by runs when every run of the block fits, else one
    element per slot (:func:`_gather_x`).  The block's own traced columns
    decide, on the device, so a streaming swap or a traced operator needs
    no table kept beside it.  The window panel ``(RUN, n_cols)`` is built
    outside the branch, so that the blocks of one operator share it."""
    with jax.named_scope("gather_x"):
        n = x.shape[0]
        xp = jnp.pad(x, (0, RUN - 1))
        xw = jnp.stack([xp[j:j + n] for j in range(RUN)])
        cols, zero, base, fits = band_runs(data_t, cols_t)
        return jax.lax.cond(fits,
                            lambda: _gather_runs(xw, cols, zero, base),
                            lambda: _gather_x(x, cols_t))


def _bands(m):
    """The ELL blocks of an ELL, SELL or hybrid operator."""
    if isinstance(m, ELL):
        yield m
    elif isinstance(m, BucketedELL):
        yield from m.buckets
    else:
        for b in getattr(m, "blocks", ()):
            yield from _bands(b)


def run_share(m) -> float:
    """Of the ELL band slots of ``m`` (ELL, SELL or hybrid, on the host),
    the share in blocks whose runs fit: the slots whose x the kernel
    tier's SpMV gathers by runs.  0.0 when ``m`` has no ELL slots."""
    served = total = 0
    for b in _bands(m):
        data, cols = np.asarray(b.data), np.asarray(b.cols)
        if b.order == "row":
            data, cols = data.T, cols.T
        total += data.size
        if data.size and band_runs(data, cols, np)[3]:
            served += data.size
    return served / total if total else 0.0


# ---------------------------------------------------------------------------
# ELL: band-major (width, n_rows) arrays
# ---------------------------------------------------------------------------
def _ell_geometry(n_rows: int, width: int,
                  tuning: Optional[TileGeometry]) -> Tuple[int, int]:
    br0, bw0 = _block_sizes(n_rows, width)
    br = _lane_tile(_geom(tuning, "block_rows", br0), n_rows)
    bw = _geom(tuning, "block_w", bw0, cap=_align8(width))
    return br, bw


def _ell_t_spmv(data_t: jax.Array, cols_t: jax.Array, x: jax.Array,
                interpret: Optional[bool],
                tuning: Optional[TileGeometry]) -> jax.Array:
    width, n_rows = data_t.shape
    br, bw = _ell_geometry(n_rows, width, tuning)
    data_t = _pad_to(_pad_to(data_t, 1, br), 0, bw)
    cols_t = _pad_to(_pad_to(cols_t, 1, br), 0, bw)
    y = _ell.ell_spmv(data_t, _gather_band(x, data_t, cols_t),
                      block_rows=br, block_w=bw,
                      interpret=_interpret(interpret))
    return y[:n_rows].astype(jnp.result_type(data_t.dtype, x.dtype))


def _ell_t_spmm(data_t: jax.Array, cols_t: jax.Array, x: jax.Array,
                interpret: Optional[bool],
                tuning: Optional[TileGeometry]) -> jax.Array:
    width, n_rows = data_t.shape
    k = x.shape[1]
    br, bw = _ell_geometry(n_rows, width, tuning)
    bk = _geom(tuning, "block_k", _block_k(k), cap=_align8(k))
    data_t = _pad_to(_pad_to(data_t, 1, br), 0, bw)
    cols_t = _pad_to(_pad_to(cols_t, 1, br), 0, bw)
    xg_t = _gather_x(_pad_to(x, 1, bk), cols_t)     # (k, W, n)
    y_t = _ell.ell_spmm(data_t, xg_t, block_rows=br, block_w=bw,
                        block_k=bk, interpret=_interpret(interpret))
    return y_t[:k, :n_rows].T.astype(jnp.result_type(data_t.dtype, x.dtype))


def ell_spmv_raw(data: jax.Array, cols: jax.Array, x: jax.Array,
                 interpret: Optional[bool] = None,
                 tuning: Optional[TileGeometry] = None) -> jax.Array:
    """ELL-Row arrays ``(n_rows, width)``."""
    return _ell_t_spmv(data.T, cols.T, x, interpret, tuning)


def ell_spmm_raw(data: jax.Array, cols: jax.Array, x: jax.Array,
                 interpret: Optional[bool] = None,
                 tuning: Optional[TileGeometry] = None) -> jax.Array:
    return _ell_t_spmm(data.T, cols.T, x, interpret, tuning)


# ---------------------------------------------------------------------------
# differentiable ELL SpMV
# ---------------------------------------------------------------------------
@jax.custom_vjp
def ell_spmv_ad(data: jax.Array, cols: jax.Array, x: jax.Array) -> jax.Array:
    return ell_spmv_raw(data, cols, x)


def _ell_fwd(data, cols, x):
    return ell_spmv_ad(data, cols, x), (data, cols, x)


def _ell_bwd(res, dy):
    data, cols, x = res
    # dx[c] = sum_{r,k: cols[r,k]=c} data[r,k] * dy[r]   (A^T dy, scatter)
    dx = jnp.zeros_like(x).at[cols.reshape(-1)].add(
        (data * dy[:, None]).reshape(-1).astype(x.dtype))
    # dA[r,k] = dy[r] * x[cols[r,k]]
    ddata = (dy[:, None] * x[cols]).astype(data.dtype)
    return ddata, None, dx


ell_spmv_ad.defvjp(_ell_fwd, _ell_bwd)


# ---------------------------------------------------------------------------
# format-level entry points (what the auto-tuner plugs in)
# ---------------------------------------------------------------------------
def _ell_arrays_t(m: ELL):
    """VAL/ICOL band-major, ``(width, n_rows)``: ELL-Col as stored,
    ELL-Row transposed."""
    data, cols = jnp.asarray(m.data), jnp.asarray(m.cols)
    if m.order == "row":
        data, cols = data.T, cols.T
    return data, cols


def spmv_ell(m: ELL, x: jax.Array, interpret: Optional[bool] = None,
             tuning: Optional[TileGeometry] = None) -> jax.Array:
    return _ell_t_spmv(*_ell_arrays_t(m), x, interpret, tuning)


def spmm_ell(m: ELL, x: jax.Array, interpret: Optional[bool] = None,
             tuning: Optional[TileGeometry] = None) -> jax.Array:
    return _ell_t_spmm(*_ell_arrays_t(m), x, interpret, tuning)


# ---------------------------------------------------------------------------
# CSR — native row-segmented kernel (kernels/csr_spmv.py)
# ---------------------------------------------------------------------------
def _csr_geometry(m: CSR, tuning: Optional[TileGeometry]) -> Tuple[int, int]:
    """Effective (block_rows, block_nnz): both are lane tiles, and their
    product (the one-hot row-recovery tile) stays within ``MAX_TILE``."""
    br = _lane_tile(_geom(tuning, "block_rows", 256), m.n_rows)
    bn = _lane_tile(_geom(tuning, "block_nnz", 2048), m.nnz_pad)
    cap = max(128, (MAX_TILE // br) // 128 * 128)
    return br, (bn if bn <= cap else cap)


def exact_slab_bound(m: CSR, tuning: Optional[TileGeometry] = None) -> int:
    """Concrete slab-coverage bound for a CSR instance at the wrapper's own
    *effective* launch geometry (tile knobs get clamped to the instance,
    so the bound must be derived post-clamp).  For baking one bound into a
    geometry shared by sibling blocks, take the max over the blocks — a
    larger bound only adds masked slabs, never drops entries."""
    if not isinstance(m, CSR):
        raise TypeError(f"no slab-coverage bound for {type(m)}")
    t = tuning.without_slab_bound() if tuning is not None else None
    br, bn = _csr_geometry(m, t)
    return _csr.slabs_needed(np.asarray(m.indptr), br, bn)


def _csr_slab_bound(m: CSR, br: int, bn: int,
                    tuning: Optional[TileGeometry]) -> int:
    """Static slab-coverage bound: exact when the index structure is
    concrete; from the tuned geometry under trace; 0 (always-correct full
    sweep) otherwise."""
    ip = m.indptr
    if not isinstance(ip, jax.core.Tracer):
        return _csr.slabs_needed(np.asarray(ip), br, bn)
    if tuning is not None and tuning.slabs_per_block is not None:
        return int(tuning.slabs_per_block)
    return 0


def _csr_launch(m: CSR, xg_t: jax.Array, bk: int,
                interpret: Optional[bool],
                tuning: Optional[TileGeometry]) -> jax.Array:
    br, bn = _csr_geometry(m, tuning)
    spb = _csr_slab_bound(m, br, bn, tuning)
    data = _pad_to(jnp.asarray(m.data), 0, bn)
    return _csr.csr_spmm_t(data, _pad_to(xg_t, 1, bn),
                           jnp.asarray(m.indptr), block_rows=br,
                           block_nnz=bn, block_k=bk, slabs_per_block=spb,
                           interpret=_interpret(interpret))


def spmv_csr(m: CSR, x: jax.Array, interpret: Optional[bool] = None,
             tuning: Optional[TileGeometry] = None) -> jax.Array:
    """CSR through the native row-segmented kernel (SpMV is its k=1 case)."""
    xg_t = _gather_x(x, jnp.asarray(m.cols))[None, :]
    y_t = _csr_launch(m, xg_t, 1, interpret, tuning)
    return y_t[0, :m.n_rows].astype(jnp.result_type(m.data.dtype, x.dtype))


def spmm_csr(m: CSR, x: jax.Array, interpret: Optional[bool] = None,
             tuning: Optional[TileGeometry] = None) -> jax.Array:
    k = x.shape[1]
    bk = _geom(tuning, "block_k", _block_k(k), cap=_align8(k))
    xg_t = _gather_x(_pad_to(x, 1, bk), jnp.asarray(m.cols))
    y_t = _csr_launch(m, xg_t, bk, interpret, tuning)
    return y_t[:k, :m.n_rows].T.astype(
        jnp.result_type(m.data.dtype, x.dtype))


# ---------------------------------------------------------------------------
# SELL / hybrid containers
# ---------------------------------------------------------------------------
SellTuning = Union[TileGeometry, Sequence[Optional[TileGeometry]],
                   Mapping[int, TileGeometry]]


def _sell_tunings(m: BucketedELL, tuning: Optional[SellTuning]
                  ) -> Tuple[Optional[TileGeometry], ...]:
    """Resolve the per-bucket launch geometry for a SELL container.

    ``tuning`` may be: ``None`` (defaults everywhere); one
    :class:`TileGeometry` — broadcast, unless it carries a ``buckets``
    table, in which case each bucket looks up its *width* and falls back
    to the table-less top-level knobs; a ``{width: TileGeometry}`` mapping;
    or a positional sequence (one entry per bucket, ``None`` allowed)."""
    n = len(m.buckets)
    if tuning is None:
        return (None,) * n
    if isinstance(tuning, Mapping):
        return tuple(tuning.get(b.width) for b in m.buckets)
    if isinstance(tuning, (list, tuple)):
        if len(tuning) != n:
            raise ValueError(f"per-bucket tuning sequence has {len(tuning)} "
                             f"entries for {n} buckets")
        return tuple(tuning)
    if tuning.buckets:
        table = dict(tuning.buckets)
        base = tuning.broadcast()
        return tuple(table.get(b.width, base) for b in m.buckets)
    return (tuning,) * n


def spmv_sell(m: BucketedELL, x: jax.Array,
              interpret: Optional[bool] = None,
              tuning: Optional[SellTuning] = None) -> jax.Array:
    # an all-zero matrix may carry an empty bucket list — the product is
    # exactly zeros of (n_rows,) in x's dtype, not None
    perm = jnp.asarray(m.perm)
    y = jnp.zeros((m.n_rows,), x.dtype)
    for off, b, g in zip(m.row_offsets, m.buckets, _sell_tunings(m, tuning)):
        yb = spmv_ell(b, x, interpret, g)
        with jax.named_scope("reassemble"):
            y = y.at[perm[off:off + b.n_rows]].set(yb.astype(y.dtype))
    return y


def spmm_sell(m: BucketedELL, x: jax.Array,
              interpret: Optional[bool] = None,
              tuning: Optional[SellTuning] = None) -> jax.Array:
    perm = jnp.asarray(m.perm)
    y = jnp.zeros((m.n_rows, x.shape[1]), x.dtype)
    for off, b, g in zip(m.row_offsets, m.buckets, _sell_tunings(m, tuning)):
        yb = spmm_ell(b, x, interpret, g)
        with jax.named_scope("reassemble"):
            y = y.at[perm[off:off + b.n_rows]].set(yb.astype(y.dtype))
    return y


def _kernel_block_impls(op: str, interpret: Optional[bool],
                        tuning: Optional[Dict[str, TileGeometry]] = None):
    """Per-block overrides for the hybrid container: every kernel-tier impl
    except hybrid itself, with ``interpret`` (and any per-format tuned
    geometry) bound."""
    out = {}
    for f, impl in _dispatch.impl_table(op, "kernel",
                                        exclude=("hybrid",)).items():
        g = (tuning or {}).get(f)
        out[f] = functools.partial(impl, interpret=interpret, tuning=g)
    return out


def spmv_hybrid(m, x: jax.Array,
                interpret: Optional[bool] = None,
                tuning: Optional[Dict[str, TileGeometry]] = None
                ) -> jax.Array:
    """Partitioned hybrid matrix: each row block through its own format's
    Pallas kernel (reassembly lives in the partition subsystem).  ``tuning``
    maps format name -> TileGeometry for the per-block kernels."""
    from repro.partition import spmv_hybrid as _hyb
    return _hyb(m, x, impls=_kernel_block_impls("spmv", interpret, tuning))


def spmm_hybrid(m, x: jax.Array,
                interpret: Optional[bool] = None,
                tuning: Optional[Dict[str, TileGeometry]] = None
                ) -> jax.Array:
    from repro.partition import spmm_hybrid as _hyb
    return _hyb(m, x, impls=_kernel_block_impls("spmm", interpret, tuning))


# ---------------------------------------------------------------------------
# registry: the kernel tier of repro.core.dispatch
# ---------------------------------------------------------------------------
for _fmt, _spmv_fn, _spmm_fn in (
    ("csr", spmv_csr, spmm_csr),
    ("ell_row", spmv_ell, spmm_ell),
    ("ell_col", spmv_ell, spmm_ell),
    ("sell", spmv_sell, spmm_sell),
    ("hybrid", spmv_hybrid, spmm_hybrid),
):
    _dispatch.register_impl(_fmt, "spmv", _spmv_fn, tier="kernel")
    _dispatch.register_impl(_fmt, "spmm", _spmm_fn, tier="kernel")

# read-only dict views of the registry, recomputed on access so later
# registrations are never missed — the single source of truth stays in
# core/dispatch.  Mutating the returned dict has no effect; add or override
# implementations with
# ``repro.core.dispatch.register_impl(fmt, op, fn, tier="kernel")``.
def __getattr__(name: str):
    if name == "KERNEL_SPMV_IMPLS":
        return _dispatch.impl_table("spmv", "kernel")
    if name == "KERNEL_SPMM_IMPLS":
        return _dispatch.impl_table("spmm", "kernel")
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")

__all__ = ["ell_spmv_raw", "ell_spmm_raw", "ell_spmv_ad",
           "spmv_ell", "spmm_ell", "spmv_csr", "spmm_csr",
           "exact_slab_bound", "spmv_sell", "spmm_sell",
           "spmv_hybrid", "spmm_hybrid", "KERNEL_SPMV_IMPLS",
           "KERNEL_SPMM_IMPLS"]
