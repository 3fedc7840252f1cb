"""Kernel launch-geometry auto-tuning: extend AT from format choice down to
the hot loop.

The paper's auto-tuner stops at *format selection*; the Pallas tier then
used to launch every kernel with one hard-coded tile shape.  AlphaSparse
(arXiv:2212.10432) shows per-matrix design-space search over launch
parameters dominates any single fixed schedule, and SELL-C-sigma
(arXiv:1307.6209) shows tile/chunk geometry is the decisive knob on
wide-SIMD hardware.  This module is the launch-parameter half of that
argument for our stack:

  * :class:`TileGeometry` — the knobs every kernel wrapper in
    ``kernels/ops.py`` accepts per call (``tuning=``): ``block_rows`` /
    ``block_w`` (ELL band tiles), ``block_k`` (SpMM RHS tile),
    ``block_nnz`` (CSR nnz slab) and ``slabs_per_block`` (the CSR static
    slab-coverage bound — data-dependent, so only the tuner, holding the
    concrete matrix, can supply it to traced callers);
  * :func:`candidate_geometries` — the bounded per-(format, op) search
    grid, holding only tiles the TPU compiler accepts
    (``tests/test_chip_compile.py`` compiles every candidate for v5e);
  * :class:`KernelTuner` — times real launches per candidate, memoizes the
    winner per ``(format, op, batch, matrix profile)``, records into the
    existing :class:`~repro.core.autotune.TuningDB` (persisted next to the
    ``OfflineRecord``\\s), and answers unseen matrices with a
    D_mat-keyed nearest-neighbour fallback.

The timing loop is injectable (``timer=``) so tests tune deterministically
without a clock.
"""
from __future__ import annotations

import time
from dataclasses import asdict, dataclass, replace
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

import repro.obs as _obs

from . import dispatch as _dispatch
from .formats import CSR, MatrixStats

__all__ = [
    "TileGeometry", "GeometryRecord", "GRID_FORMATS", "MAX_TILE",
    "candidate_geometries", "nearest_geometry", "KernelTuner",
]


# ---------------------------------------------------------------------------
# the geometry pytree-of-knobs
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class TileGeometry:
    """Per-call launch geometry; ``None`` fields fall back to the wrapper's
    built-in default.  Hashable so it can ride through static closures.

    ``block_rows`` is the row tile of the ELL and CSR kernels.

    ``buckets`` is the SELL per-bucket table: ``((width, TileGeometry),
    ...)`` pairs keyed by bucket *width*, so one persisted geometry carries
    a different tile shape for every bucket of the container (SELL-C-σ's
    point: chunk geometry is per-chunk).  Bucket widths absent from the
    table fall back to the top-level knobs."""
    block_rows: Optional[int] = None   # ELL/CSR row (lane) tile
    block_w: Optional[int] = None      # ELL band (sublane) tile
    block_k: Optional[int] = None      # SpMM right-hand-side tile
    block_nnz: Optional[int] = None    # CSR nnz slab
    slabs_per_block: Optional[int] = None  # CSR static coverage bound
    buckets: Optional[Tuple[Tuple[int, "TileGeometry"], ...]] = None  # SELL

    _KNOBS = ("block_rows", "block_w", "block_k", "block_nnz",
              "slabs_per_block")

    def to_dict(self) -> Dict[str, Any]:
        d = {k: getattr(self, k) for k in self._KNOBS
             if getattr(self, k) is not None}
        if self.buckets is not None:
            d["buckets"] = [[w, g.to_dict()] for w, g in self.buckets]
        return d

    @staticmethod
    def from_dict(d: Dict[str, Any]) -> "TileGeometry":
        d = dict(d)
        buckets = d.pop("buckets", None)
        g = TileGeometry(**d)
        if buckets is not None:
            g = replace(g, buckets=tuple(
                (int(w), TileGeometry.from_dict(gd)) for w, gd in buckets))
        return g

    def broadcast(self) -> "TileGeometry":
        """The top-level knobs alone (per-bucket table stripped) — what a
        bucket whose width is missing from the table launches with."""
        return replace(self, buckets=None)

    def without_slab_bound(self) -> "TileGeometry":
        """Strip the data-dependent coverage bound — required when a
        geometry learned on one matrix is applied to another under trace
        (the bound would silently drop entries; without it the CSR kernel
        falls back to the always-correct full sweep, and concrete
        callers recompute the exact bound anyway).  Applies through the
        per-bucket table too."""
        buckets = self.buckets
        if buckets is not None:
            buckets = tuple((w, g.without_slab_bound()) for w, g in buckets)
        return replace(self, slabs_per_block=None, buckets=buckets)


@dataclass
class GeometryRecord:
    """One tuning outcome: the winning geometry for (format, op, batch) on
    a matrix profile, plus the measured win over the default launch.

    ``sig`` fingerprints the index structure (CRC of the pointer array)
    when it was concrete at tune time: two same-sized matrices must not
    share a memoized record, because the winning geometry can carry a
    matrix-specific slab-coverage bound.

    ``bucket_w`` marks a SELL per-bucket component record (the winner for
    the bucket of that width); ``None`` is a whole-matrix record — for
    SELL that aggregate's geometry carries the composed per-bucket table,
    and only aggregates feed the nearest-neighbour fallback."""
    fmt: str
    op: str
    batch: int
    n: int
    nnz: int
    d_mat: float
    geometry: TileGeometry
    t_best: float
    t_default: float
    sig: int = 0
    bucket_w: Optional[int] = None

    @property
    def speedup(self) -> float:
        return self.t_default / self.t_best if self.t_best > 0 else 1.0

    def to_dict(self) -> Dict[str, Any]:
        d = asdict(self)
        d["geometry"] = self.geometry.to_dict()
        if self.bucket_w is None:
            d.pop("bucket_w")
        return d

    @staticmethod
    def from_dict(d: Dict[str, Any]) -> "GeometryRecord":
        d = dict(d)
        d["geometry"] = TileGeometry.from_dict(d["geometry"])
        return GeometryRecord(**d)


# ---------------------------------------------------------------------------
# the bounded search grid
# ---------------------------------------------------------------------------
# Every tile below is one the TPU compiler accepts for its axis: row
# tiles lie along the 128 vector lanes (a multiple of 128, or the whole
# axis once clamped to a smaller matrix); band and right-hand-side tiles
# lie along the sublanes (multiples of 8).
ROW_TILES = (128, 256, 512)
W_TILES = (8, 32, 128)
K_TILES = (8, 32, 128)
CSR_ROW_TILES = (128, 256, 512)
CSR_NNZ_TILES = (512, 1024, 2048, 4096)
#: cap on the largest per-step tile, in elements: the CSR kernel's
#: (block_nnz, block_rows) one-hot row-recovery tile, and the ELL SpMM
#: kernel's (block_k, block_w, block_rows) gathered-x panel.  It keeps
#: every candidate inside the compiler's default scoped VMEM.
MAX_TILE = 1 << 20

#: every format with a bounded candidate grid below — the kernel tier's
#: tunable surface.  Kept as a plain literal tuple so the static registry
#: audit (``repro.analyze``) can read it without importing jax; the
#: ``candidate_geometries`` gate uses it, so a kernel registered without a
#: grid entry is caught both here and by the audit.
GRID_FORMATS = ("ell_row", "ell_col", "sell", "csr")


def _align8(n: int) -> int:
    return max(8, 8 * ((int(n) + 7) // 8))


def candidate_geometries(fmt: str, op: str = "spmv", *, n_rows: int = 0,
                         width: int = 0, nnz_pad: int = 0,
                         batch: int = 1) -> List[TileGeometry]:
    """The bounded launch-geometry grid for one (format, op).

    Candidates are pre-clamped to the matrix profile (a 512-row tile on a
    100-row matrix is the same launch as a 128-row one) and de-duplicated,
    so the tuner never times the same effective launch twice."""
    if fmt not in GRID_FORMATS:
        return []
    ks = tuple(sorted({min(k, _align8(batch)) for k in K_TILES})) \
        if op == "spmm" else (None,)
    geoms: List[TileGeometry] = []
    if fmt.startswith("ell") or fmt == "sell":
        rows = {min(r, _align8(n_rows)) for r in ROW_TILES} if n_rows \
            else set(ROW_TILES)
        ws = {min(w, _align8(width)) for w in W_TILES} if width \
            else set(W_TILES)
        for r in sorted(rows):
            for w in sorted(ws):
                for k in ks:
                    if r * w * (k or 1) > MAX_TILE:
                        continue
                    geoms.append(TileGeometry(block_rows=r, block_w=w,
                                              block_k=k))
    else:
        # CSR: both tiles lie along lanes; a whole-axis tile (a matrix
        # smaller than the tile) is clamped to the 8-aligned extent
        rows = {min(r, _align8(n_rows)) for r in CSR_ROW_TILES} if n_rows \
            else set(CSR_ROW_TILES)
        bns = {min(bn, _align8(nnz_pad)) for bn in CSR_NNZ_TILES} \
            if nnz_pad else set(CSR_NNZ_TILES)
        for r in sorted(rows):
            for bn in sorted(bns):
                if r * bn > MAX_TILE:
                    continue
                for k in ks:
                    geoms.append(TileGeometry(block_rows=r, block_nnz=bn,
                                              block_k=k))
    seen, out = set(), []
    for g in geoms:
        key = (g.block_rows, g.block_w, g.block_k, g.block_nnz)
        if key not in seen:
            seen.add(key)
            out.append(g)
    return out


# ---------------------------------------------------------------------------
# nearest-neighbour fallback over recorded geometries
# ---------------------------------------------------------------------------
def nearest_geometry(records: Sequence[GeometryRecord], fmt: str,
                     op: str = "spmv", d_mat: float = 0.0,
                     batch: Optional[int] = None) -> Optional[TileGeometry]:
    """D_mat-keyed (log-space) nearest neighbour among recorded winners.

    The returned geometry is stripped of its slab-coverage bound — that
    bound is only valid for the matrix it was measured on.  SELL
    per-bucket component records (``bucket_w`` set) are skipped: the
    whole-matrix aggregate already carries the composed bucket table."""
    recs = [r for r in records if r.fmt == fmt and r.op == op
            and getattr(r, "bucket_w", None) is None]
    if batch is not None:
        exact = [r for r in recs if r.batch == batch]
        recs = exact or recs
    if not recs:
        return None
    q = np.log(max(d_mat, 1e-9))
    best = min(recs, key=lambda r: abs(np.log(max(r.d_mat, 1e-9)) - q))
    return best.geometry.without_slab_bound()


# ---------------------------------------------------------------------------
# matrix profiling (best effort per format)
# ---------------------------------------------------------------------------
def _structure_sig(obj: Any) -> int:
    """CRC fingerprint of the concrete index-pointer structure (0 when the
    object has none, or it is abstract).  Part of the memo identity: the
    winning geometry's slab-coverage bound is only valid for the exact
    structure it was measured on."""
    ip = getattr(obj, "indptr", None)
    if ip is None or isinstance(ip, jax.core.Tracer):
        return 0
    import zlib
    return zlib.crc32(np.ascontiguousarray(np.asarray(ip)).tobytes()) or 1


def _profile_of(obj: Any, stats: Optional[MatrixStats] = None
                ) -> Tuple[int, int, float, int]:
    sig = _structure_sig(obj)
    if stats is not None:
        return int(stats.n), int(stats.nnz), float(stats.d_mat), sig
    n = int(getattr(obj, "n_rows", 0))
    nnz = int(getattr(obj, "nnz", 0))
    d_mat = 0.0
    if isinstance(obj, CSR) and not isinstance(obj.indptr,
                                               jax.core.Tracer):
        d_mat = float(MatrixStats.of(obj).d_mat)
    return n, nnz, d_mat, sig


def _width_of(obj: Any) -> int:
    w = getattr(obj, "width", None)
    if w is not None:
        return int(w)
    widths = getattr(obj, "widths", None)   # BucketedELL
    if widths:
        return int(max(widths))
    return 0


def _slab_bound_for(obj: Any, g: TileGeometry) -> Optional[int]:
    """Exact slab coverage bound for a CSR candidate at the launch the
    kernel wrapper will actually make, computable only with the concrete
    index structure in hand."""
    if isinstance(getattr(obj, "indptr", None), jax.core.Tracer):
        return None
    from repro.kernels.ops import exact_slab_bound
    return exact_slab_bound(obj, g)


# ---------------------------------------------------------------------------
# the tuner
# ---------------------------------------------------------------------------
def _real_timer(iters: int, warmup: int) -> Callable:
    def timer(thunk: Callable[[], Any], geometry: Optional[TileGeometry]
              ) -> float:
        for _ in range(warmup):
            thunk()
        best = float("inf")
        for _ in range(iters):
            t0 = time.perf_counter()
            thunk()
            best = min(best, time.perf_counter() - t0)
        return best
    return timer


class KernelTuner:
    """Searches :func:`candidate_geometries` by timing real launches.

    ``db``: an :class:`~repro.core.autotune.TuningDB` to read/record
    geometry winners in (its ``geometries`` list is shared, so saving the
    db persists the tuner's work).  ``timer(thunk, geometry) -> seconds``
    is injectable for deterministic tests.
    """

    def __init__(self, db: Optional[Any] = None,
                 interpret: Optional[bool] = None,
                 iters: int = 3, warmup: int = 1,
                 timer: Optional[Callable] = None,
                 max_candidates: Optional[int] = None):
        self.db = db
        self.interpret = interpret
        self.records: List[GeometryRecord] = (
            db.geometries if db is not None
            and getattr(db, "geometries", None) is not None else [])
        if db is not None and getattr(db, "geometries", None) is None:
            db.geometries = self.records
        self._timer = timer or _real_timer(iters, warmup)
        self.max_candidates = max_candidates
        # memo maps key -> *index* into self.records, so a forced re-tune
        # replaces the superseded record in place instead of accumulating
        # duplicates in the shared (persisted) list
        self._memo: Dict[Tuple, int] = self._build_memo()

    def _build_memo(self) -> Dict[Tuple, int]:
        memo = {
            self._key(r.fmt, r.op, r.batch, (r.n, r.nnz, r.d_mat, r.sig),
                      getattr(r, "bucket_w", None)): i
            for i, r in enumerate(self.records)}
        if len(memo) != len(self.records):
            # a db persisted before re-tunes replaced in place can carry
            # stale duplicates; keep the last record per key (the freshest
            # winner) so nearest_geometry can't resurrect a superseded one
            # — compact through the slice so the db's list alias heals too
            self.records[:] = [self.records[i] for i in sorted(memo.values())]
            return self._build_memo()
        return memo

    @staticmethod
    def _key(fmt: str, op: str, batch: int,
             profile: Tuple[int, int, float, int],
             bucket_w: Optional[int] = None):
        return (fmt, op, batch, profile[0], profile[1],
                round(profile[2], 6), profile[3], bucket_w)

    def _record(self, key: Tuple, rec: GeometryRecord) -> GeometryRecord:
        """Memoize ``rec`` under ``key``, replacing any superseded record
        in place (keeps one record per key across forced re-tunes, and
        keeps ``self.records`` aliased with the db's list)."""
        idx = self._memo.get(key)
        if idx is None:
            self._memo[key] = len(self.records)
            self.records.append(rec)
        else:
            self.records[idx] = rec
        tel = _obs.get()
        if tel.enabled:
            attrs = dict(fmt=rec.fmt, op=rec.op, batch=rec.batch,
                         t_best=rec.t_best, t_default=rec.t_default,
                         speedup=rec.speedup,
                         geometry=rec.geometry.to_dict())
            if rec.bucket_w is not None:
                attrs["bucket_w"] = rec.bucket_w
            tel.event("tune.winner", **attrs)
        return rec

    # -- search --------------------------------------------------------------
    def tune(self, obj: Any, op: str = "spmv", batch: int = 1,
             impl: Optional[Callable] = None, x: Optional[jax.Array] = None,
             stats: Optional[MatrixStats] = None,
             force: bool = False) -> GeometryRecord:
        """Time every candidate launch of ``obj``'s kernel and return (and
        memoize) the winner.  The default launch is always a candidate, so
        ``t_best <= t_default`` by construction.

        SELL containers are tuned *per bucket*: each bucket width gets its
        own candidate sweep (timed on that bucket's ELL launch alone), the
        per-width winners are memoized as component records, and the
        returned aggregate's geometry composes them into a
        ``TileGeometry.buckets`` table."""
        import jax.numpy as jnp

        fmt = _dispatch.format_of(obj)
        profile = _profile_of(obj, stats)
        batch = max(batch, 1)
        key = self._key(fmt, op, batch, profile)
        idx = self._memo.get(key)
        if not force and idx is not None:
            tel = _obs.get()
            if tel.enabled:
                tel.counter("tune.memo_hit", fmt=fmt, op=op).inc()
            return self.records[idx]

        if impl is None:
            impl = _dispatch.get_impl(fmt, op, tier="kernel", fallback=False)
        if x is None:
            shape = (obj.n_cols,) if op == "spmv" else (obj.n_cols, batch)
            x = jnp.ones(shape, jnp.float32)

        if fmt == "sell":
            with _obs.span("tune.sweep", fmt=fmt, op=op, batch=batch,
                           d_mat=profile[2]):
                return self._tune_sell(obj, op, batch, impl, x, profile,
                                       key, force)

        cands: List[Optional[TileGeometry]] = [None]
        grid = candidate_geometries(
            fmt, op, n_rows=profile[0], width=_width_of(obj),
            nnz_pad=int(getattr(obj, "nnz_pad", 0) or 0), batch=batch)
        if self.max_candidates is not None:
            grid = grid[: self.max_candidates]
        cands.extend(grid)

        with _obs.span("tune.sweep", fmt=fmt, op=op, batch=batch,
                       d_mat=profile[2]) as sweep:
            times: List[Tuple[float, Optional[TileGeometry]]] = []
            for g in cands:
                gg = g
                if g is not None and fmt == "csr":
                    spb = _slab_bound_for(obj, g)
                    if spb is not None:
                        gg = replace(g, slabs_per_block=spb)
                times.append((self._time_launch(impl, obj, x, gg,
                                                fmt=fmt, op=op), gg))

            t_default = times[0][0]
            t_best, best_g = min(times, key=lambda tg: tg[0])
            sweep.set(candidates=len(cands), t_best=t_best,
                      t_default=t_default)
        rec = GeometryRecord(
            fmt=fmt, op=op, batch=batch, n=profile[0],
            nnz=profile[1], d_mat=profile[2], sig=profile[3],
            geometry=best_g if best_g is not None else TileGeometry(),
            t_best=t_best, t_default=t_default)
        return self._record(key, rec)

    def _time_launch(self, impl: Callable, obj: Any, x: jax.Array,
                     g: Optional[TileGeometry], **span_attrs: Any) -> float:
        fn = jax.jit(lambda m, v, _f=impl, _g=g:
                     _f(m, v, interpret=self.interpret, tuning=_g))
        thunk = lambda _fn=fn: jax.block_until_ready(_fn(obj, x))
        with _obs.span("tune.candidate",
                       geometry=g.to_dict() if g is not None else {},
                       **span_attrs) as sp:
            t = float(self._timer(thunk, g))
            sp.set(t=t)
        return t

    def _tune_sell(self, obj: Any, op: str, batch: int, impl: Callable,
                   x: jax.Array, profile: Tuple[int, int, float, int],
                   key: Tuple, force: bool) -> GeometryRecord:
        """Per-bucket SELL search (SELL-C-sigma's per-chunk geometry).

        Bucket widths are distinct by construction (equal-width neighbours
        merge at transform time), so each width is searched once on its own
        bucket — an ELL launch over (bucket_rows, width) — and memoized as
        a component record keyed by ``bucket_w``.  The aggregate then times
        the composed per-bucket table against the all-defaults launch, so
        its ``t_best <= t_default`` stays true by construction."""
        ell_impl = _dispatch.get_impl("ell_row", op, tier="kernel",
                                      fallback=False)
        table: List[Tuple[int, TileGeometry]] = []
        for b in obj.buckets:
            bkey = self._key("sell", op, batch, profile,
                             bucket_w=int(b.width))
            bidx = self._memo.get(bkey)
            if not force and bidx is not None:
                table.append((int(b.width), self.records[bidx].geometry))
                continue
            grid = candidate_geometries("sell", op, n_rows=b.n_rows,
                                        width=b.width, batch=batch)
            if self.max_candidates is not None:
                grid = grid[: self.max_candidates]
            times = [(self._time_launch(ell_impl, b, x, g, fmt="sell",
                                        op=op, bucket_w=int(b.width)), g)
                     for g in [None] + grid]
            t_default = times[0][0]
            t_best, best_g = min(times, key=lambda tg: tg[0])
            brec = GeometryRecord(
                fmt="sell", op=op, batch=batch, n=profile[0],
                nnz=profile[1], d_mat=profile[2], sig=profile[3],
                bucket_w=int(b.width),
                geometry=best_g if best_g is not None else TileGeometry(),
                t_best=t_best, t_default=t_default)
            self._record(bkey, brec)
            table.append((int(b.width), brec.geometry))

        cands: List[Optional[TileGeometry]] = [None]
        if table:
            cands.append(TileGeometry(buckets=tuple(table)))
        times = [(self._time_launch(impl, obj, x, g, fmt="sell", op=op), g)
                 for g in cands]
        t_default = times[0][0]
        t_best, best_g = min(times, key=lambda tg: tg[0])
        rec = GeometryRecord(
            fmt="sell", op=op, batch=batch, n=profile[0], nnz=profile[1],
            d_mat=profile[2], sig=profile[3],
            geometry=best_g if best_g is not None else TileGeometry(),
            t_best=t_best, t_default=t_default)
        return self._record(key, rec)

    # -- lookup --------------------------------------------------------------
    def best(self, obj: Any = None, op: str = "spmv", batch: int = 1,
             fmt: Optional[str] = None, d_mat: Optional[float] = None,
             stats: Optional[MatrixStats] = None
             ) -> Optional[TileGeometry]:
        """Memoized winner for this exact profile, else the D_mat-keyed
        nearest-neighbour among recorded winners (slab bound stripped),
        else ``None`` (caller uses the default launch)."""
        if obj is not None:
            fmt = fmt or _dispatch.format_of(obj)
            profile = _profile_of(obj, stats)
            idx = self._memo.get(self._key(fmt, op, max(batch, 1), profile))
            if idx is not None:
                return self.records[idx].geometry
            if d_mat is None:
                d_mat = profile[2]
        if fmt is None:
            raise ValueError("best() needs a matrix object or a format name")
        return nearest_geometry(self.records, fmt, op,
                                d_mat=d_mat or 0.0, batch=max(batch, 1))

    # -- binding helpers -----------------------------------------------------
    def bind(self, impls: Dict[str, Callable],
             tunings: Dict[str, TileGeometry]) -> Dict[str, Callable]:
        """``{fmt: impl}`` with each format's tuned geometry partially
        applied (formats without a tuned geometry — or whose impl doesn't
        accept ``tuning=`` — pass through).  Delegates to the shared
        :func:`repro.core.plan.bind_tunings`."""
        from .plan import bind_tunings
        return bind_tunings(impls, tunings)
