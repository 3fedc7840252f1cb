"""Partitioned hybrid-format SpMV: per-row-block auto-tuning.

The whole-matrix auto-tuner (core/autotune.py) answers "which single format
for this matrix"; one heavy row forces the answer to CRS.  This module
answers the finer question per row block: partition the (optionally
length-sorted) row space, compute per-block ``MatrixStats``, run the same
D_mat–R decision machinery *per block* under the same ``MemoryPolicy``
budget, and materialize a ``HybridMatrix`` — a pytree of per-block format
objects plus the row permutation.  SpMV dispatches each block to the
existing per-format implementations and reassembles the output.

Transformation time is accounted per block (``HybridReport``) and, because
``host_csr_to_hybrid`` is registered in ``core.transform.TRANSFORMS_HOST``,
the whole-pipeline cost is measured by ``offline_phase`` exactly like any
other format — R_hybrid feeds back into the D_mat–R graph.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core import dispatch as _dispatch
from repro.core.autotune import (MachineModel, TuningDB, decide_cost_model,
                                 decide_generalized, decide_paper)
from repro.core.formats import CSR, MatrixStats, memory_bytes
from repro.core.policy import MemoryPolicy
from repro.core.transform import TRANSFORMS_HOST, pad_to_multiple

from .strategies import PARTITIONERS

# formats a block may land in (csr = stay; no nested hybrid)
BLOCK_FORMATS = ("ell_row", "ell_col", "coo_row", "coo_col", "sell")


# ---------------------------------------------------------------------------
# the hybrid container
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class HybridMatrix:
    """Per-row-block storage: ``blocks[i]`` covers permuted rows
    ``row_offsets[i] : row_offsets[i] + blocks[i].n_rows`` and holds the
    format named by ``formats[i]``.  ``perm[i]`` = original row of permuted
    row i (identity when the partitioner did not sort)."""
    perm: Any                       # (n_rows,) permuted -> original row
    blocks: Tuple[Any, ...]         # CSR | COO | ELL | BucketedELL per block
    row_offsets: Tuple[int, ...]    # static: start (permuted) row per block
    formats: Tuple[str, ...]        # static: format name per block
    shape: Tuple[int, int]
    nnz: int
    identity_perm: bool = False     # static: True -> outputs just concatenate

    @property
    def n_rows(self) -> int:
        return self.shape[0]

    @property
    def n_cols(self) -> int:
        return self.shape[1]

    @property
    def n_blocks(self) -> int:
        return len(self.blocks)

    def block_rows(self, i: int) -> int:
        return int(self.blocks[i].n_rows)

    def format_counts(self) -> Dict[str, int]:
        out: Dict[str, int] = {}
        for f in self.formats:
            out[f] = out.get(f, 0) + 1
        return out

    def todense(self) -> np.ndarray:
        dense_blocks = [b.todense() for b in self.blocks]
        out = np.zeros(self.shape, dtype=dense_blocks[0].dtype)
        perm = np.asarray(self.perm)
        for off, dense_b in zip(self.row_offsets, dense_blocks):
            out[perm[off:off + dense_b.shape[0]]] += dense_b
        return out


jax.tree_util.register_dataclass(
    HybridMatrix, data_fields=["perm", "blocks"],
    meta_fields=["row_offsets", "formats", "shape", "nnz", "identity_perm"])


# ---------------------------------------------------------------------------
# CSR row-slicing (host)
# ---------------------------------------------------------------------------
def take_rows_csr(m: CSR, rows: np.ndarray, pad: int = 8) -> CSR:
    """Sub-CSR over an arbitrary (ordered) row subset; full column space."""
    ip = np.asarray(m.indptr)
    lens = (ip[1:] - ip[:-1])[rows]
    nnz = int(lens.sum())
    indptr = np.zeros(len(rows) + 1, dtype=np.int32)
    np.cumsum(lens, out=indptr[1:])
    src_d, src_c = np.asarray(m.data), np.asarray(m.cols)
    data = np.zeros(max(pad_to_multiple(nnz, pad), pad), dtype=src_d.dtype)
    cols = np.zeros_like(data, dtype=np.int32)
    # gather each row's [start, start+len) span into the packed layout
    if nnz:
        starts = ip[rows]
        idx = np.concatenate([np.arange(s, s + l)
                              for s, l in zip(starts, lens)]) if len(rows) \
            else np.zeros(0, np.int64)
        data[:nnz] = src_d[idx]
        cols[:nnz] = src_c[idx]
    return CSR(data=data, cols=cols, indptr=indptr,
               shape=(len(rows), m.n_cols), nnz=nnz)


def slice_csr_cols(m: CSR, c0: int, c1: int, pad: int = 8) -> CSR:
    """Column slab [c0, c1): keep entries whose column falls in the slab,
    rebased to column 0 — the column-sharding analogue of ``slice_csr``.
    Full row space (every shard of a column-sharded matrix owns all rows
    and contributes a partial y that is psum-reduced)."""
    ip = np.asarray(m.indptr)
    data = np.asarray(m.data)[:m.nnz]
    cols = np.asarray(m.cols)[:m.nnz]
    lens = (ip[1:] - ip[:-1]).astype(np.int64)
    rows = np.repeat(np.arange(m.n_rows, dtype=np.int64), lens)
    sel = (cols >= c0) & (cols < c1)
    d, c, r = data[sel], cols[sel] - c0, rows[sel]  # stays row-major sorted
    nnz = int(d.size)
    new_lens = np.bincount(r, minlength=m.n_rows)
    indptr = np.zeros(m.n_rows + 1, dtype=np.int32)
    np.cumsum(new_lens, out=indptr[1:])
    nnz_pad = max(pad_to_multiple(nnz, pad), pad)
    dd = np.zeros(nnz_pad, dtype=data.dtype)
    cc = np.zeros(nnz_pad, dtype=np.int32)
    dd[:nnz], cc[:nnz] = d, c
    return CSR(data=dd, cols=cc, indptr=indptr,
               shape=(m.n_rows, c1 - c0), nnz=nnz)


def slice_csr(m: CSR, r0: int, r1: int, pad: int = 8) -> CSR:
    """Contiguous row slice [r0, r1) — O(block nnz) views + one copy."""
    ip = np.asarray(m.indptr)
    s, e = int(ip[r0]), int(ip[r1])
    nnz = e - s
    data = np.asarray(m.data)[s:e]
    cols = np.asarray(m.cols)[s:e]
    nnz_pad = max(pad_to_multiple(nnz, pad), pad)
    d = np.zeros(nnz_pad, dtype=data.dtype)
    c = np.zeros(nnz_pad, dtype=np.int32)
    d[:nnz], c[:nnz] = data, cols
    return CSR(data=d, cols=c,
               indptr=(ip[r0:r1 + 1] - s).astype(np.int32),
               shape=(r1 - r0, m.n_cols), nnz=nnz)


# ---------------------------------------------------------------------------
# per-block decision (reuses core/autotune + core/policy)
# ---------------------------------------------------------------------------
def choose_block_format(stats: MatrixStats,
                        db: Optional[TuningDB] = None,
                        rule: str = "auto",
                        model: Optional[MachineModel] = None,
                        policy: Optional[MemoryPolicy] = None,
                        expected_iterations: int = 100,
                        formats: Sequence[str] = BLOCK_FORMATS,
                        batch: int = 1) -> str:
    """One block's format via the same machinery as the whole-matrix tuner.

    Candidates are first filtered by the memory policy (estimate vs the
    block's own CSR estimate), then ranked by the paper rule, the
    generalized DB prediction, or the roofline cost model.  ``batch`` is
    the expected RHS count per call — amortization runs over
    ``expected_iterations * batch`` products."""
    policy = policy or MemoryPolicy()
    csr_bytes = max(policy.estimate_bytes("csr", stats), 1)

    def fits(f: str) -> bool:
        b = policy.estimate_bytes(f, stats)
        ok = b <= policy.budget_ratio * csr_bytes
        if policy.hard_bytes:
            ok = ok and b <= policy.hard_bytes
        return ok

    cand = [f for f in formats if fits(f)]
    if not cand:
        return "csr"
    if db is not None and rule == "paper":
        return decide_paper(db, stats).fmt if "ell_row" in cand else "csr"
    if db is not None:
        return decide_generalized(db, stats, expected_iterations,
                                  formats=cand,
                                  memory_budget_ratio=policy.budget_ratio,
                                  batch=batch).fmt
    return decide_cost_model(model or MachineModel(), stats,
                             expected_iterations, formats=cand,
                             batch=batch).fmt


# ---------------------------------------------------------------------------
# build
# ---------------------------------------------------------------------------
@dataclass
class BlockDecision:
    """One row block's outcome.  ``plan`` is the leaf
    :class:`~repro.core.plan.ExecutionPlan` for the block — the portable
    decision artifact (format + transform recipe + fingerprint) that the
    Planner and the serving layer compose into whole-matrix hybrid plans;
    ``fmt`` is kept as the flat view of ``plan.fmt``."""
    fmt: str
    rows: Tuple[int, int]       # [start, end) in the permuted row space
    d_mat: float
    nnz: int
    bytes: int
    t_transform: float
    plan: Optional[Any] = None  # core.plan.ExecutionPlan (leaf)


@dataclass
class HybridReport:
    strategy: str
    n_blocks: int
    t_partition: float
    t_transform: float          # total per-block materialization seconds
    decisions: List[BlockDecision] = field(default_factory=list)

    def format_counts(self) -> Dict[str, int]:
        out: Dict[str, int] = {}
        for d in self.decisions:
            out[d.fmt] = out.get(d.fmt, 0) + 1
        return out


def build_hybrid(m: CSR,
                 strategy: str = "variance",
                 db: Optional[TuningDB] = None,
                 rule: str = "auto",
                 model: Optional[MachineModel] = None,
                 policy: Optional[MemoryPolicy] = None,
                 expected_iterations: int = 100,
                 sort_rows: Optional[bool] = None,
                 formats: Sequence[str] = BLOCK_FORMATS,
                 batch: int = 1,
                 **strategy_kw) -> Tuple[HybridMatrix, HybridReport]:
    """Partition -> per-block stats -> per-block decision -> materialize.

    ``sort_rows`` (default: True for the variance strategy) length-sorts the
    row space first so contiguous blocks are homogeneous — the sigma-sort of
    SELL-C-sigma lifted to the whole decision problem."""
    if strategy not in PARTITIONERS:
        raise KeyError(f"unknown strategy {strategy!r}; "
                       f"one of {sorted(PARTITIONERS)}")
    if sort_rows is None:
        sort_rows = strategy == "variance"
    lens = m.row_lengths().astype(np.int64)

    t0 = time.perf_counter()
    if sort_rows:
        perm = np.argsort(-lens, kind="stable").astype(np.int32)
    else:
        perm = np.arange(m.n_rows, dtype=np.int32)
    boundaries = PARTITIONERS[strategy](lens[perm], **strategy_kw)
    t_partition = time.perf_counter() - t0

    # per-block decisions ship as leaf ExecutionPlans (portable artifacts
    # the Planner / serving layer compose into whole-matrix hybrid plans)
    from repro.core.plan import leaf_plan
    rule_used = ("paper" if db is not None and rule == "paper"
                 else "generalized" if db is not None else "cost_model")

    blocks: List[Any] = []
    fmts: List[str] = []
    offsets: List[int] = []
    decisions: List[BlockDecision] = []
    t_transform = 0.0
    for s, e in zip(boundaries[:-1], boundaries[1:]):
        s, e = int(s), int(e)
        sub = (slice_csr(m, s, e) if not sort_rows
               else take_rows_csr(m, perm[s:e]))
        stats = MatrixStats.of(sub)
        fmt = choose_block_format(stats, db=db, rule=rule, model=model,
                                  policy=policy,
                                  expected_iterations=expected_iterations,
                                  formats=formats, batch=batch)
        t1 = time.perf_counter()
        obj = TRANSFORMS_HOST[fmt](sub)
        dt = time.perf_counter() - t1
        t_transform += dt
        blocks.append(obj)
        fmts.append(fmt)
        offsets.append(s)
        decisions.append(BlockDecision(
            fmt=fmt, rows=(s, e), d_mat=stats.d_mat, nnz=stats.nnz,
            bytes=memory_bytes(obj), t_transform=dt,
            plan=leaf_plan(sub, stats, fmt, rule_used, batch=batch,
                           expected_iterations=expected_iterations,
                           machine=db.machine if db is not None else "")))

    hyb = HybridMatrix(perm=perm, blocks=tuple(blocks),
                       row_offsets=tuple(offsets), formats=tuple(fmts),
                       shape=m.shape, nnz=m.nnz,
                       identity_perm=not sort_rows)
    report = HybridReport(strategy=strategy, n_blocks=len(blocks),
                          t_partition=t_partition, t_transform=t_transform,
                          decisions=decisions)
    return hyb, report


def host_csr_to_hybrid(m: CSR, strategy: str = "variance",
                       **kw) -> HybridMatrix:
    """``TRANSFORMS_HOST``-compatible entry point (cost-model decisions when
    no TuningDB is supplied).  ``offline_phase`` times this call as a whole,
    so R_hybrid lands on the D_mat–R graph like any other transformation."""
    hyb, _ = build_hybrid(m, strategy=strategy, **kw)
    return hyb


# ---------------------------------------------------------------------------
# execution — per-block implementations resolved through core/dispatch
# ---------------------------------------------------------------------------
def _block_impl(fmt: str, op: str,
                impls: Optional[Dict[str, Callable]]) -> Callable:
    fn = (impls or {}).get(fmt)
    return fn if fn is not None else _dispatch.get_impl(fmt, op)


def reassembly(m: Any) -> str:
    """How :func:`spmv_hybrid` puts the block outputs of ``m`` back in row
    order: ``"sort"`` (one key-value sort of ``(perm, y)`` on ``perm``)
    for a hybrid whose partition sorted the rows, ``"none"`` when its
    outputs just concatenate: a hybrid that kept row order, or an
    operator that is not a hybrid (a sharded one)."""
    return ("sort" if isinstance(m, HybridMatrix) and not m.identity_perm
            else "none")


def spmv_hybrid(m: HybridMatrix, x: jax.Array,
                impls: Optional[Dict[str, Callable]] = None) -> jax.Array:
    """y = A @ x: each block through its format's SpMV, then reassemble.

    ``impls`` maps format name -> callable(block, x) (e.g. the Pallas
    wrappers in ``kernels/ops.py``); formats not overridden resolve to the
    reference tier of the ``core/dispatch`` registry.  ``perm`` is a
    permutation, so sorting ``(perm, y)`` on ``perm`` puts ``y[i]`` at row
    ``perm[i]`` exactly as the scatter ``zeros.at[perm].set(y)`` does, and
    on the TPU at a fraction of its cost: the scatter pays per index."""
    outs = [_block_impl(fmt, "spmv", impls)(b, x)
            for fmt, b in zip(m.formats, m.blocks)]
    y = jnp.concatenate(outs) if len(outs) > 1 else outs[0]
    if reassembly(m) == "none":
        return y
    with jax.named_scope("reassemble"):
        return lax.sort((jnp.asarray(m.perm), y), num_keys=1)[1]


def spmm_hybrid(m: HybridMatrix, x: jax.Array,
                impls: Optional[Dict[str, Callable]] = None) -> jax.Array:
    """Multi-vector RHS: x (n_cols, B) -> (n_rows, B) — each block's own
    SpMM, reassembling the (rows, B) panels through the row permutation."""
    outs = [_block_impl(fmt, "spmm", impls)(b, x)
            for fmt, b in zip(m.formats, m.blocks)]
    y = jnp.concatenate(outs, axis=0) if len(outs) > 1 else outs[0]
    if m.identity_perm:
        return y
    with jax.named_scope("reassemble"):
        return jnp.zeros((m.n_rows, x.shape[1]),
                         y.dtype).at[jnp.asarray(m.perm)].set(y)


# the hybrid container is a first-class format: one registration here is
# the only place it is wired into the dispatch stack
_dispatch.register_format("hybrid", HybridMatrix)
_dispatch.register_impl("hybrid", "spmv", spmv_hybrid)
_dispatch.register_impl("hybrid", "spmm", spmm_hybrid)


__all__ = ["BLOCK_FORMATS", "HybridMatrix", "BlockDecision", "HybridReport",
           "take_rows_csr", "slice_csr", "slice_csr_cols",
           "choose_block_format", "build_hybrid", "host_csr_to_hybrid",
           "reassembly", "spmv_hybrid", "spmm_hybrid"]
