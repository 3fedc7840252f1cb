"""Multi-device sharded SpMV/SpMM: per-shard ExecutionPlans on a mesh.

The distributed execution tier.  A host CSR is cut into contiguous slabs
along one axis by the partition strategies lifted to device-count
granularity (``partition_for_devices``), the :class:`~repro.core.plan.Planner`
runs independently per slab so every device gets its own format + launch
geometry, and the resulting :class:`ShardedPlannedMatrix` serves
``P @ x`` / ``P @ X`` across the mesh.

Collective structure (see docs/sharding.md for the cost table):

  * ``axis="row"``   — x is replicated, each device multiplies its row slab
    locally, and the outputs reassemble by *concatenation alone* (the
    partitioner never sorts rows, so slabs stay contiguous in the original
    row order and no scatter collective is needed).
  * ``axis="col"``   — x is replicated then each device slices its column
    window (the gather step), multiplies its column slab locally into a
    full-length partial y, and a single ``psum`` reduces the partials.

Execution modes — the resolution of a real tension: per-shard plans are
*heterogeneous* (that is the point), but ``jax.shard_map`` wants one SPMD
program with uniform shapes:

  * ``"shard_map"`` — the collective-scaled path.  Slab CSRs are padded to
    a common (rows_pad, nnz_pad) envelope, stacked with a leading device
    axis sharded ``P("shards")``, and one program runs the reference CSR
    op per device (pad entries are val=0/col=0, so they contribute
    nothing).  Uniform by construction; per-shard format choices are
    recorded in the plan but not applied here.
  * ``"dispatch"``  — the format-faithful path.  Each shard binds its own
    :class:`~repro.core.plan.PlannedMatrix` (own format, tier, geometry),
    placed round-robin across devices; JAX's async dispatch overlaps the
    per-shard launches.  Works with more shards than devices (and on a
    single device, which is how the in-process tests run).
  * ``"auto"``      — ``shard_map`` when the mesh has at least one device
    per shard, else ``dispatch``.  A 1-shard plan degenerates to the
    single-plan path of PR 5 (mode ``"single"``).
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

import repro.obs as _obs
from repro.core.formats import CSR, memory_bytes
from repro.core.plan import (PlanError, Planner, ShardedPlan,
                             shard_boundaries, slice_shard)
from repro.core.spmv import spmm_csr, spmv_csr


# ---------------------------------------------------------------------------
# partitioning the host matrix
# ---------------------------------------------------------------------------
def shard_csr(csr: CSR, n_shards: int, axis: str = "row",
              strategy: str = "balanced_nnz",
              **strategy_kw) -> Tuple[np.ndarray, List[CSR]]:
    """Cut ``csr`` into ``n_shards`` slabs along ``axis``; returns
    ``(boundaries, [slab CSRs])``.  Row slabs keep the full column space;
    column slabs keep the full row space with columns rebased to 0."""
    b = shard_boundaries(csr, n_shards, axis=axis, strategy=strategy,
                         **strategy_kw)
    subs = [slice_shard(csr, int(s), int(e), axis=axis)
            for s, e in zip(b[:-1], b[1:])]
    return b, subs


def _slice_for(csr: CSR, boundaries: np.ndarray, axis: str) -> List[CSR]:
    return [slice_shard(csr, int(s), int(e), axis=axis)
            for s, e in zip(boundaries[:-1], boundaries[1:])]


def _imbalance(subs: Sequence[CSR]) -> float:
    nnzs = np.array([m.nnz for m in subs], dtype=np.float64)
    return float(nnzs.max() / max(nnzs.mean(), 1.0))


# ---------------------------------------------------------------------------
# the SPMD envelope (shard_map mode)
# ---------------------------------------------------------------------------
def _stack_shards(subs: Sequence[CSR]):
    """Pad every slab to a common (rows_pad, nnz_pad) envelope and stack
    with a leading device axis.  Pad entries are val=0/col=0 (harmless
    for SpMV) and indptr extends flat, so padded rows produce zeros."""
    rows_pad = max(m.n_rows for m in subs)
    nnz_pad = max(m.nnz_pad for m in subs)
    width_pad = max(m.n_cols for m in subs)
    datas, colss, ips = [], [], []
    for m in subs:
        d = np.zeros(nnz_pad, dtype=np.asarray(m.data).dtype)
        c = np.zeros(nnz_pad, dtype=np.int32)
        d[:m.nnz_pad] = np.asarray(m.data)
        c[:m.nnz_pad] = np.asarray(m.cols)
        ip = np.asarray(m.indptr, dtype=np.int32)
        ipp = np.full(rows_pad + 1, ip[-1], dtype=np.int32)
        ipp[:ip.shape[0]] = ip
        datas.append(d)
        colss.append(c)
        ips.append(ipp)
    return (np.stack(datas), np.stack(colss), np.stack(ips),
            rows_pad, nnz_pad, width_pad)


def _mesh_for(n_shards: int, axis_name: str,
              devices: Optional[Sequence[Any]] = None,
              mesh: Optional[Any] = None):
    """A 1-D mesh of exactly ``n_shards`` devices named ``axis_name`` —
    the caller's mesh when it already fits, else the first ``n_shards``
    of the given (or all) devices."""
    if mesh is not None:
        if axis_name in mesh.axis_names \
                and dict(mesh.shape)[axis_name] == n_shards:
            return mesh
        devices = list(np.asarray(mesh.devices).flatten())
    devs = list(devices if devices is not None else jax.devices())
    if len(devs) < n_shards:
        raise PlanError(
            f"shard_map mode needs >= {n_shards} devices for {n_shards} "
            f"shards; have {len(devs)} (use mode='dispatch', or set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=N)")
    return jax.sharding.Mesh(np.asarray(devs[:n_shards]), (axis_name,))


def _make_shard_map_fns(stacked, axis: str, mesh, axis_name: str,
                        shape: Tuple[int, int], boundaries: np.ndarray):
    """jit-compiled SpMV/SpMM dispatchers over the stacked envelope.

    Row axis: local products, outputs laid out shard-major (out_specs
    ``P(axis_name)``), unpadded outside by static slices — zero
    collectives.  Column axis: replicated x, per-device offset +
    ``dynamic_slice`` (the gather), full-length partials, one psum."""
    data_s, cols_s, ip_s, rows_pad, nnz_pad, width_pad = stacked
    n_rows, n_cols = shape
    sharded = jax.sharding.NamedSharding(mesh, P(axis_name))
    data_s = jax.device_put(jnp.asarray(data_s), sharded)
    cols_s = jax.device_put(jnp.asarray(cols_s), sharded)
    ip_s = jax.device_put(jnp.asarray(ip_s), sharded)
    if axis == "row":
        rows_per = np.diff(boundaries)

        def _exec(op, x):
            def body(d, c, ip, xx):
                local = CSR(data=d[0], cols=c[0], indptr=ip[0],
                            shape=(rows_pad, n_cols), nnz=nnz_pad)
                return op(local, xx)

            out = jax.shard_map(
                body, mesh=mesh,
                in_specs=(P(axis_name), P(axis_name), P(axis_name), P()),
                out_specs=P(axis_name))(data_s, cols_s, ip_s, x)
            # each device owns rows_pad output rows; keep the valid head
            # of every slab and concatenate — static slices, no collective
            return jnp.concatenate(
                [out[i * rows_pad: i * rows_pad + int(r)]
                 for i, r in enumerate(rows_per)])
    else:
        offs = jax.device_put(
            jnp.asarray(boundaries[:-1], dtype=jnp.int32), sharded)

        def _exec(op, x):
            pads = ((0, width_pad),) + ((0, 0),) * (x.ndim - 1)
            xp = jnp.pad(x, pads)  # slices never clamp

            def body(d, c, ip, off, xx):
                start = (off[0],) + (0,) * (xx.ndim - 1)
                size = (width_pad,) + xx.shape[1:]
                xl = jax.lax.dynamic_slice(xx, start, size)  # the gather
                local = CSR(data=d[0], cols=c[0], indptr=ip[0],
                            shape=(n_rows, width_pad), nnz=nnz_pad)
                return jax.lax.psum(op(local, xl), axis_name)

            return jax.shard_map(
                body, mesh=mesh,
                in_specs=(P(axis_name), P(axis_name), P(axis_name),
                          P(axis_name), P()),
                out_specs=P())(data_s, cols_s, ip_s, offs, xp)

    fns = {"spmv": jax.jit(lambda x: _exec(spmv_csr, x)),
           "spmm": jax.jit(lambda x: _exec(spmm_csr, x))}
    nbytes = int(data_s.nbytes + cols_s.nbytes + ip_s.nbytes)
    return fns, nbytes


# ---------------------------------------------------------------------------
# the bound sharded operator
# ---------------------------------------------------------------------------
class ShardedPlannedMatrix:
    """A :class:`~repro.core.plan.ShardedPlan` applied to a concrete
    matrix.  ``y = P @ x`` dispatches on x's rank exactly like
    :class:`~repro.core.plan.PlannedMatrix` — 1-D serves SpMV,
    ``(n_cols, B)`` serves SpMM — executed across the mesh per the
    resolved mode (see the module docstring)."""

    def __init__(self, plan: ShardedPlan, source: CSR, mode: str,
                 boundaries: np.ndarray, fingerprint_matched: bool,
                 planned: Optional[List[Any]] = None,
                 exec_fns: Optional[Dict[str, Any]] = None,
                 mesh: Optional[Any] = None, nbytes: int = 0,
                 shard_nnz: Optional[List[int]] = None):
        self.plan = plan
        self.source = source
        self.mode = mode
        self.boundaries = np.asarray(boundaries, dtype=np.int64)
        self.fingerprint_matched = fingerprint_matched
        self.planned = planned          # dispatch/single: per-shard bound
        self.mesh = mesh
        self.shard_nnz = list(shard_nnz or [])
        self._exec_fns = exec_fns       # shard_map: jitted dispatchers
        self._nbytes = nbytes
        self._devices = []
        self.shard_guards: List[Dict[str, Any]] = []
        if planned is not None and mode == "dispatch":
            devs = jax.devices()
            self._devices = [devs[i % len(devs)]
                             for i in range(len(planned))]
            for pm, dev in zip(planned, self._devices):
                pm.matrix = jax.device_put(pm.matrix, dev)
            self.shard_guards = self._build_shard_guards()

    def _build_shard_guards(self) -> List[Dict[str, Any]]:
        """Dispatch mode serves shards one by one on the host, so each
        shard gets its own degradation ladder: the bound per-shard impl
        backed by reference-CSR on that shard's source slice.  Exception
        faults demote a single shard instead of failing the whole product;
        finiteness is *not* probed per shard (that would add one device
        sync per shard per call) — the service-level guard already probes
        the assembled output end-to-end."""
        # lazy: sharding must stay importable without the serve package
        from repro.core.spmv import spmv as _spmv_ref
        from repro.core import dispatch as _dispatch
        from repro.serve.guard import guard_ladder
        ref_mv = jax.jit(_spmv_ref)
        ref_mm = jax.jit(_dispatch.get_impl("csr", "spmm", "reference"))
        guards = []
        for i, pm in enumerate(self.planned):
            src = pm.source
            guards.append({
                "spmv": guard_ladder(
                    f"shard{i}", "spmv",
                    [("tuned", lambda xi, _pm=pm: _pm.spmv(xi)),
                     ("csr", lambda xi, _s=src: ref_mv(_s, xi))],
                    fmt=pm.fmt, probe_finite=False),
                "spmm": guard_ladder(
                    f"shard{i}", "spmm",
                    [("tuned", lambda xi, _pm=pm: _pm.spmm(xi)),
                     ("csr", lambda xi, _s=src: ref_mm(_s, xi))],
                    fmt=pm.fmt, probe_finite=False),
            })
        return guards

    def guard_report(self) -> List[Dict[str, Any]]:
        """Per-shard ladder snapshots (dispatch mode; empty otherwise)."""
        return [{op: g.snapshot() for op, g in shard.items()}
                for shard in self.shard_guards]

    # -- views ---------------------------------------------------------------
    fmt = "sharded"

    @property
    def shape(self) -> Tuple[int, int]:
        return self.source.shape

    @property
    def n_rows(self) -> int:
        return self.source.shape[0]

    @property
    def n_cols(self) -> int:
        return self.source.shape[1]

    @property
    def n_shards(self) -> int:
        return self.plan.n_shards

    @property
    def n_blocks(self) -> int:
        # the serving layer's block-count view: one block per shard
        return self.plan.n_shards

    @property
    def axis(self) -> str:
        return self.plan.axis

    def nbytes(self) -> int:
        if self.planned is not None:
            return sum(memory_bytes(pm.matrix) for pm in self.planned)
        return self._nbytes

    def report(self) -> List[Dict[str, Any]]:
        """Per-shard decision summary: slab extent, format, tier, nnz."""
        out = []
        b = self.boundaries
        for i, bp in enumerate(self.plan.shards):
            out.append({"shard": i, "rows": (int(b[i]), int(b[i + 1])),
                        "fmt": bp.plan.fmt, "tier": bp.plan.tier,
                        "nnz": (self.shard_nnz[i]
                                if i < len(self.shard_nnz)
                                else bp.plan.fingerprint.nnz
                                if bp.plan.fingerprint else -1)})
        return out

    # -- execution -----------------------------------------------------------
    def _check(self, x: jnp.ndarray, op: str) -> jnp.ndarray:
        x = jnp.asarray(x)
        want = 1 if op == "spmv" else 2
        if x.ndim != want or x.shape[0] != self.n_cols:
            shape = (f"({self.n_cols},)" if op == "spmv"
                     else f"({self.n_cols}, B)")
            raise ValueError(f"{op} expects x of shape {shape}; "
                             f"got {x.shape}")
        return x

    def _run_dispatch(self, op: str, x: jnp.ndarray,
                      tel) -> jnp.ndarray:
        b = self.boundaries
        parts = []
        for i, pm in enumerate(self.planned):
            with tel.span("shard.spmv", shard=i, fmt=pm.fmt,
                          mode="dispatch"):
                if self.axis == "row":
                    xi = x
                else:
                    with tel.span("shard.gather", shard=i):
                        xi = x[int(b[i]): int(b[i + 1])]
                if self.shard_guards:
                    parts.append(self.shard_guards[i][op](xi))
                else:
                    parts.append(getattr(pm, op)(xi))
        if self._devices:
            # partials live where their shards ran; reassembly needs them
            # on one device (concatenate/add refuse cross-device args)
            home = self._devices[0]
            parts = [jax.device_put(p, home) for p in parts]
        if self.axis == "row":
            return jnp.concatenate(parts)
        total = parts[0]
        for p in parts[1:]:
            total = total + p
        return total

    def _apply(self, op: str, x: jnp.ndarray) -> jnp.ndarray:
        x = self._check(x, op)
        tel = _obs.get()
        with tel.span("sharded.spmv", op=op, mode=self.mode,
                      axis=self.axis, n_shards=self.n_shards):
            if self.mode == "single":
                return getattr(self.planned[0], op)(x)
            if self.mode == "dispatch":
                return self._run_dispatch(op, x, tel)
            if self.axis == "col":
                with tel.span("shard.gather", mode="shard_map",
                              n_shards=self.n_shards):
                    x = jnp.asarray(x)   # replicate once, sliced in-body
            return self._exec_fns[op](x)

    def spmv(self, x) -> jnp.ndarray:
        return self._apply("spmv", x)

    def spmm(self, x) -> jnp.ndarray:
        return self._apply("spmm", x)

    def __matmul__(self, x) -> jnp.ndarray:
        x = jnp.asarray(x)
        return self.spmv(x) if x.ndim == 1 else self.spmm(x)

    def __call__(self, x) -> jnp.ndarray:
        return self @ x

    def __repr__(self) -> str:
        return (f"ShardedPlannedMatrix(n_shards={self.n_shards}, "
                f"axis={self.axis!r}, mode={self.mode!r}, "
                f"shape={self.shape}, formats={self.plan.shard_formats()}, "
                f"fingerprint_matched={self.fingerprint_matched})")


# ---------------------------------------------------------------------------
# binding
# ---------------------------------------------------------------------------
def _resolve_mode(mode: str, n_shards: int,
                  devices: Optional[Sequence[Any]],
                  mesh: Optional[Any]) -> str:
    if n_shards == 1:
        return "single"
    if mode == "auto":
        n_avail = (int(np.asarray(mesh.devices).size) if mesh is not None
                   else len(devices if devices is not None
                            else jax.devices()))
        return "shard_map" if n_avail >= n_shards else "dispatch"
    if mode not in ("shard_map", "dispatch", "single"):
        raise PlanError(f"unknown mode {mode!r}; one of "
                        "('auto', 'shard_map', 'dispatch', 'single')")
    return mode


def build_sharded(csr: CSR, *, plan: Optional[ShardedPlan] = None,
                  planner: Optional[Planner] = None, db: Optional[Any] = None,
                  n_shards: Optional[int] = None, axis: str = "row",
                  strategy: str = "balanced_nnz", mode: str = "auto",
                  devices: Optional[Sequence[Any]] = None,
                  mesh: Optional[Any] = None, batch: int = 1,
                  strategy_kw: Optional[Dict[str, Any]] = None,
                  **plan_kw) -> ShardedPlannedMatrix:
    """Partition + per-shard plan + mesh execution in one call.

    Without ``plan``, a :class:`Planner` (the given one, or a fresh one
    over ``db``) mints a :class:`ShardedPlan` for ``csr`` first.  With
    ``plan``, the recorded decisions replay with zero re-tuning; a
    fingerprint mismatch keeps the recipe — axis, strategy, shard count,
    per-shard formats — but re-partitions on the new matrix (per-shard
    geometry re-resolves exactly like PR 5 single plans)."""
    tel = _obs.get()
    if plan is None:
        planner = planner or Planner(db=db)
        if n_shards is None:
            n_shards = (int(np.asarray(mesh.devices).size)
                        if mesh is not None
                        else len(devices if devices is not None
                                 else jax.devices()))
        plan = planner.plan_sharded(csr, n_shards=n_shards, axis=axis,
                                    strategy=strategy, batch=batch,
                                    strategy_kw=strategy_kw, **plan_kw)
        if db is None:
            db = planner.db
    matched = plan.matches(csr)

    with tel.span("sharded.bind", n_shards=plan.n_shards, axis=plan.axis,
                  matched=matched) as sp:
        if matched:
            boundaries = plan.boundaries()
        else:
            boundaries = shard_boundaries(csr, plan.n_shards,
                                          axis=plan.axis,
                                          strategy=plan.strategy,
                                          **plan.params)
        subs = _slice_for(csr, boundaries, plan.axis)
        imb = _imbalance(subs)
        tel.gauge("sharded.load_imbalance").set(imb)
        shard_nnz = [m.nnz for m in subs]
        resolved = _resolve_mode(mode, plan.n_shards, devices, mesh)
        sp.set(mode=resolved, imbalance=imb)

        if resolved == "shard_map":
            m = _mesh_for(plan.n_shards, plan.mesh_axis, devices, mesh)
            fns, nbytes = _make_shard_map_fns(
                _stack_shards(subs), plan.axis, m, plan.mesh_axis,
                csr.shape, boundaries)
            return ShardedPlannedMatrix(
                plan, csr, "shard_map", boundaries, matched,
                exec_fns=fns, mesh=m, nbytes=nbytes, shard_nnz=shard_nnz)

        planned = [bp.plan.bind(sub, db=db)
                   for bp, sub in zip(plan.shards, subs)]
        return ShardedPlannedMatrix(
            plan, csr, resolved, boundaries, matched, planned=planned,
            shard_nnz=shard_nnz)


__all__ = ["ShardedPlannedMatrix", "build_sharded", "shard_csr"]
