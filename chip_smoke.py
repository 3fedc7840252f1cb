#!/usr/bin/env python3
"""Chip smoke test: the plan -> serve path at Table-1 size on a TPU.

    python chip_smoke.py               # one chip: torso1 and xenon2
    python chip_smoke.py --chips 4     # the sharded path on four chips

First the paper's off-line phase runs on this device: the Table-1 suite
(torso1 left out, as in the paper) is synthesized at a small scale from
``--seed``, each matrix is timed in CRS and in ELL through the kernel
tier, and the D_mat threshold D* is learned into a ``TuningDB``.

One chip.  ``torso1`` (116,158 rows, 8.5M nonzeros, D_mat 5.72) and
``xenon2`` (157,464 rows, 3.87M nonzeros, D_mat 0.16) are synthesized at
scale 1.0 from ``--seed`` (``repro.core.suite``).  Each is

  * planned by ``Planner`` with the paper's rule (transform to ELL iff
    D_mat < D*) on the kernel tier with a ``KernelTuner``, bound, and
    multiplied: ``P @ x`` and ``P @ X`` with B = 32.  torso1's D_mat is
    above every suite matrix's, so it stays in CSR; xenon2 goes to ELL
    when the measured D* is above 0.16;
  * registered in ``SpMVService(tuner=KernelTuner(...))`` and served: a
    few ``spmv`` calls, one ``spmm`` at B = 32 and one ``submit``/``flush``
    batch.  The service builds a hybrid matrix whose row blocks take
    their formats from its cost model.

Four chips (``--chips 4``).  torso1 only: ``Planner.plan_sharded`` with 4
shards on the row and the column axis under the same rule, bound in
``shard_map`` and ``dispatch`` modes, and served by ``SpMVService`` from a
sharded plan.  Each result is compared with the one-chip result as well.

Every result is checked against an independent float64 CSR product on
the host, row by row:

    |y_i - yref_i| <= 1e-4 * sum_j |a_ij * x_j|

The run also fails when anything falls back: a registration that
degrades, a guard that serves below its tuned rung (the service's, or a
shard's own in dispatch mode), a plan whose ops do not resolve to the
kernel tier, a block format without a kernel, or Pallas kernels that
would run in interpret mode.  The last line of stdout
is ``{"ok": true, "device": {...}}``; on any failure, or on a platform
other than ``tpu``, the script exits non-zero and prints no such line.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import Any, Dict, Optional, Sequence, Tuple

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)

REL_TOL = 1e-4
BATCH = 32
MATRICES = ("torso1", "xenon2")
#: the paper's decision space: keep CRS, or transform to ELL
PLAN_FORMATS = ("ell_row",)
#: the off-line phase's suite scale: N and NNZ shrink, D_mat does not
OFFLINE_SCALE = 0.02
EXPECTED_ITERATIONS = 1000
#: tuner candidates timed per (format, op) beyond the default launch:
#: enough to exercise the sweep, few enough for a cold run of minutes
MAX_CANDIDATES = 2


class SmokeFailure(RuntimeError):
    """A phase produced a wrong result or served through a fallback."""


def require(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# ---------------------------------------------------------------------------
# inputs and the host reference
# ---------------------------------------------------------------------------
def synthesize(name: str, scale: float, seed: int):
    from repro.core.suite import TABLE1, synthesize as _synth
    spec = next(s for s in TABLE1 if s.name == name)
    return _synth(spec, scale=scale, seed=seed)


def make_inputs(csr, seed: int):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal(csr.n_cols).astype(np.float32)
    X = rng.standard_normal((csr.n_cols, BATCH)).astype(np.float32)
    return x, X


def host_reference(csr, x: np.ndarray):
    """float64 ``A @ x`` and the per-row error bound
    ``REL_TOL * sum_j |a_ij * x_j|``, column by column for a panel."""
    ip = np.asarray(csr.indptr)
    rows = np.repeat(np.arange(csr.n_rows), np.diff(ip))
    a = np.asarray(csr.data)[:csr.nnz].astype(np.float64)
    c = np.asarray(csr.cols)[:csr.nnz]
    xs = np.asarray(x, np.float64)
    cols = xs[:, None] if xs.ndim == 1 else xs
    y = np.empty((csr.n_rows, cols.shape[1]))
    bound = np.empty_like(y)
    for j in range(cols.shape[1]):
        prod = a * cols[c, j]
        y[:, j] = np.bincount(rows, prod, minlength=csr.n_rows)
        bound[:, j] = REL_TOL * np.bincount(rows, np.abs(prod),
                                            minlength=csr.n_rows)
    if xs.ndim == 1:
        return y[:, 0], bound[:, 0]
    return y, bound


def check(what: str, y, ref: np.ndarray, bound: np.ndarray) -> float:
    """Worst ``|y - yref| / bound`` over all entries (must be <= 1)."""
    y = np.asarray(y, np.float64)
    require(y.shape == ref.shape,
            f"{what}: shape {y.shape}, expected {ref.shape}")
    require(bool(np.isfinite(y).all()), f"{what}: non-finite output")
    err = np.abs(y - ref)
    over = err > bound
    if over.any():
        i = np.unravel_index(int(np.argmax(err - bound)), err.shape)
        raise SmokeFailure(
            f"{what}: {int(over.sum())} entries outside the bound; worst "
            f"at {tuple(int(v) for v in i)}: |y - yref| = {err[i]:.3e} > "
            f"{bound[i]:.3e}")
    pos = bound > 0
    return float((err[pos] / bound[pos]).max()) if pos.any() else 0.0


def _timed(fn, *args):
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t0


def emit(phase: str, **fields: Any) -> None:
    parts = [f"phase={phase}"]
    for k, v in fields.items():
        if isinstance(v, float):
            v = f"{v:.4g}"
        elif isinstance(v, dict):
            v = ",".join(f"{a}:{b}" for a, b in v.items())
        parts.append(f"{k}={v}")
    print(" ".join(parts), flush=True)


def new_tuner(max_candidates: Optional[int]):
    from repro.core.autotune import TuningDB
    from repro.core.kernel_tune import KernelTuner
    db = TuningDB(machine="chip_smoke", c=1.0, records=[], d_star={})
    return KernelTuner(db=db, max_candidates=max_candidates)


def offline_db(seed: int, scale: float = OFFLINE_SCALE,
               include: Optional[Sequence[str]] = None):
    """The paper's off-line phase on this device: the Table-1 suite
    without torso1 (or the ``include`` subset of it), CRS and ELL timed
    through the kernel tier, D* learned at c = 1.  Each launch is tuned
    before it is timed (the default and one candidate), so the timed
    launches are those the planner would bind."""
    from repro.core.autotune import offline_phase
    from repro.core.suite import paper_suite
    from repro.kernels import ops
    t0 = time.perf_counter()
    suite = paper_suite(scale=scale, seed=seed, include=include,
                        skip_ell_overflow=True)
    db = offline_phase(suite, formats=PLAN_FORMATS, machine="chip_smoke",
                       spmv_impls={"csr": ops.spmv_csr,
                                   "ell_row": ops.spmv_ell},
                       tuner=new_tuner(1))
    emit("offline", matrices=len(suite), scale=scale,
         d_star=db.d_star, offline_s=time.perf_counter() - t0)
    return db


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------
def plan_phase(name: str, csr, x, X, refs, db, max_candidates) -> Dict:
    """D_mat -> the paper's rule -> ExecutionPlan -> transform -> Pallas
    kernel."""
    from repro.core.plan import Planner
    planner = Planner(db=db, tuner=new_tuner(max_candidates), tier="kernel",
                      rule="paper")
    t0 = time.perf_counter()
    plan = planner.plan(csr, batch=BATCH,
                        expected_iterations=EXPECTED_ITERATIONS,
                        formats=PLAN_FORMATS)
    plan_s = time.perf_counter() - t0
    P = plan.bind(csr)
    require(P.tiers == {"spmv": "kernel", "spmm": "kernel"},
            f"plan {name}: ops resolved to tiers {P.tiers}")
    _, t_first_v = _timed(P.__matmul__, x)
    y, t_v = _timed(P.__matmul__, x)
    _, t_first_m = _timed(P.__matmul__, X)
    Y, t_m = _timed(P.__matmul__, X)
    worst = max(check(f"plan {name} spmv", y, *refs["x"]),
                check(f"plan {name} spmm", Y, *refs["X"]))
    out = dict(matrix=name, d_mat=plan.d_mat, d_star=plan.d_star,
               fmt=plan.fmt, tiers=P.tiers, served_by="plan",
               plan_s=plan_s,
               compile_s=(t_first_v - t_v) + (t_first_m - t_m),
               spmv_ms=t_v * 1e3, spmm_ms=t_m * 1e3,
               worst_err_over_bound=worst)
    emit("plan", **out)
    return {"planned": P, "y": y, "Y": Y, **out}


def _tuned_calls(what: str, snap: Dict[str, Any]) -> Tuple[int, int]:
    """A guard ladder's snapshot, required to have served every call on
    its tuned rung; returns ``(tuned, calls)``."""
    require(snap["calls"] > 0, f"{what}: never called")
    require(snap["fallback_calls"] == 0
            and snap["served_by"].get("tuned", 0) == snap["calls"],
            f"{what}: {snap['fallback_calls']} of {snap['calls']} calls "
            f"fell back: {snap['served_by']} failures {snap['failures']}")
    return snap["served_by"]["tuned"], snap["calls"]


def _shard_checks(what: str, spm) -> Dict[str, str]:
    """Dispatch mode: every shard's planned matrix resolves to the kernel
    tier, and every shard's own ladder (its kernel, then reference CSR)
    served every call on the kernel.  Returns ``tuned/calls`` per op,
    summed over the shards."""
    require(spm.mode == "dispatch", f"{what}: bound as {spm.mode}")
    for i, pm in enumerate(spm.planned):
        require(pm.tiers == {"spmv": "kernel", "spmm": "kernel"},
                f"{what} shard {i}: ops resolved to tiers {pm.tiers}")
    report = spm.guard_report()
    require(len(report) == spm.n_shards,
            f"{what}: {len(report)} shard guards for {spm.n_shards} shards")
    served = {}
    for op in ("spmv", "spmm"):
        counts = [_tuned_calls(f"{what} shard {i} {op}", shard[op])
                  for i, shard in enumerate(report)]
        served[op] = (f"{sum(t for t, _ in counts)}/"
                      f"{sum(c for _, c in counts)}")
    return served


def _service_checks(name: str, svc, entry, sink) -> Dict[str, Any]:
    """Everything that would mean the service did not serve ``name`` on
    the kernel tier."""
    from repro.core import dispatch
    degraded = [e for e in sink.named("service.register_degraded")
                if e.get("attrs", {}).get("key") == name]
    require(not degraded, f"serve {name}: registration degraded: "
                          f"{degraded[0].get('attrs') if degraded else ''}")
    plan = entry.plan
    require(plan is not None and getattr(plan, "rule", "") != "degraded",
            f"serve {name}: registration degraded to a reference plan")
    # a ShardedPlan carries its tier on the per-shard plans
    tiers = ({bp.plan.tier for bp in plan.shards} if hasattr(plan, "shards")
             else {plan.tier})
    require(tiers == {"kernel"},
            f"serve {name}: plan tiers {sorted(tiers)}, not 'kernel'")
    require(set(entry.guards) == {"spmv", "spmm"},
            f"serve {name}: guarded ops {sorted(entry.guards)}")
    served_by = {}
    for op, g in entry.guards.items():
        tuned, calls = _tuned_calls(f"serve {name} {op}", g.snapshot())
        served_by[op] = f"{tuned}/{calls}"
    if hasattr(entry.matrix, "guard_report"):
        # a sharded entry's tuned rung runs each shard through its own
        # ladder, which would absorb a failing shard kernel
        for op, s in _shard_checks(f"serve {name}", entry.matrix).items():
            served_by[f"shards_{op}"] = s
    if getattr(plan, "fmt", None) == "hybrid":
        for fmt in entry.formats():
            for op in ("spmv", "spmm"):
                require(dispatch.has_impl(fmt, op, tier="kernel"),
                        f"serve {name}: block format {fmt!r} has no "
                        f"{op} kernel")
                require(fmt in entry.tunings.get(op, {}),
                        f"serve {name}: no tuned {op} geometry for "
                        f"{fmt!r} blocks")
    return served_by


def serve_phase(name: str, csr, x, X, refs, max_candidates,
                plan=None, **register_kw) -> Dict:
    """SpMVService with a KernelTuner: spmv, spmm at B = 32, and one
    submit/flush batch, all on the tuned rung."""
    import repro.obs as obs
    from repro.serve.spmv_service import SpMVService
    sink = obs.InMemorySink()
    was_enabled = obs.enabled()
    tel = obs.enable(sink=sink)
    try:
        svc = SpMVService(tuner=new_tuner(max_candidates), max_batch=BATCH)
        t0 = time.perf_counter()
        entry = svc.register(name, csr, batch=BATCH, plan=plan,
                             expected_iterations=EXPECTED_ITERATIONS,
                             measure_baseline=False, **register_kw)
        register_s = time.perf_counter() - t0
        worst = 0.0
        y_ref, y_bound = refs["X"]
        t_v = []
        for j in range(3):
            y, dt = _timed(svc.spmv, name, X[:, j])
            t_v.append(dt)
            worst = max(worst, check(f"serve {name} spmv[{j}]", y,
                                     y_ref[:, j], y_bound[:, j]))
        t_m = []
        for _ in range(2):
            Y, dt = _timed(svc.spmm, name, X)
            t_m.append(dt)
            worst = max(worst, check(f"serve {name} spmm", Y, y_ref,
                                     y_bound))
        n_sub = BATCH - 4           # a ragged panel, padded by the service
        futs = [svc.submit(name, X[:, j]) for j in range(n_sub)]
        served = svc.flush(name)
        require(served == n_sub, f"serve {name}: flush served {served} "
                                 f"of {n_sub} submitted")
        for j, f in enumerate(futs):
            worst = max(worst, check(f"serve {name} submit[{j}]",
                                     f.result(), y_ref[:, j],
                                     y_bound[:, j]))
        served_by = _service_checks(name, svc, entry, sink)
        out = dict(matrix=name, fmt=getattr(entry.plan, "fmt", "sharded"),
                   blocks=entry.formats(),
                   tiers={op: "kernel" for op in entry.guards},
                   served_by=served_by, register_s=register_s,
                   compile_s=(t_v[0] - t_v[1]) + (t_m[0] - t_m[1]),
                   spmv_ms=t_v[1] * 1e3, spmm_ms=t_m[1] * 1e3,
                   worst_err_over_bound=worst)
        emit("serve", **out)
        return out
    finally:
        tel.sinks.remove(sink)
        if not was_enabled:
            obs.disable()


def sharded_phase(name: str, csr, x, X, refs, db, one_chip, n_chips: int):
    """Row and column sharding in shard_map and dispatch modes, and a
    sharded plan served by SpMVService — against the one-chip result and
    the host reference."""
    import jax
    from repro.core.plan import Planner
    planner = Planner(db=db, tier="kernel", rule="paper")
    for axis in ("row", "col"):
        splan = planner.plan_sharded(csr, n_shards=n_chips, axis=axis,
                                     batch=BATCH, formats=PLAN_FORMATS,
                                     expected_iterations=EXPECTED_ITERATIONS)
        for mode in ("shard_map", "dispatch"):
            spm = splan.bind(csr, mode=mode)
            require(spm.mode == mode, f"{axis}/{mode}: bound as {spm.mode}")
            if mode == "shard_map":
                devs = list(np.asarray(spm.mesh.devices).flatten())
            else:       # where each shard's matrix was placed
                devs = [next(iter(jax.tree.leaves(pm.matrix)[0].devices()))
                        for pm in spm.planned]
            require(len({d.id for d in devs}) == n_chips,
                    f"{axis}/{mode}: {len({d.id for d in devs})} distinct "
                    f"devices, expected {n_chips}")
            _, t_first_v = _timed(spm.spmv, x)
            y, t_v = _timed(spm.spmv, x)
            _, t_first_m = _timed(spm.spmm, X)
            Y, t_m = _timed(spm.spmm, X)
            what = f"sharded {name} {axis}/{mode}"
            worst = max(check(what + " spmv", y, *refs["x"]),
                        check(what + " spmm", Y, *refs["X"]))
            # two results inside the bound agree within twice the bound
            check(what + " spmv vs one chip", y,
                  np.asarray(one_chip["y"], np.float64),
                  2 * refs["x"][1])
            check(what + " spmm vs one chip", Y,
                  np.asarray(one_chip["Y"], np.float64),
                  2 * refs["X"][1])
            if mode == "shard_map":
                # one SPMD reference-CSR body on every device, by design
                body, served_by = "reference_csr", "spmd"
            else:
                served_by = _shard_checks(what, spm)
                body = "kernel"
            emit("sharded", matrix=name, axis=axis, mode=mode,
                 shards=",".join(splan.shard_formats()), body=body,
                 served_by=served_by, devices=len(devs),
                 compile_s=(t_first_v - t_v) + (t_first_m - t_m),
                 spmv_ms=t_v * 1e3, spmm_ms=t_m * 1e3,
                 worst_err_over_bound=worst)
        if axis == "row":
            out = serve_phase(f"{name}_sharded", csr, x, X, refs, None,
                              plan=splan, mode="dispatch")
            require("shards_spmv" in out["served_by"],
                    "sharded serve: shard guards were not read")
            require(out["fmt"] == "sharded",
                    f"sharded serve bound a {out['fmt']!r} plan")


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 2
    if len(jax.devices()) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(jax.devices())} device(s)", file=sys.stderr)
        return 2
    from benchmarks.common import use_compile_cache
    from repro.kernels import ops
    cache = use_compile_cache()
    print(f"# device {dev.device_kind} x{len(jax.devices())}; compile "
          f"cache {cache}", flush=True)

    try:
        require(not ops._interpret(None),
                "Pallas kernels would run in interpret mode on this TPU")
        names = MATRICES if args.chips == 1 else ("torso1",)
        db = offline_db(args.seed)
        for name in names:
            t0 = time.perf_counter()
            csr = synthesize(name, 1.0, args.seed)
            x, X = make_inputs(csr, args.seed)
            refs = {"x": host_reference(csr, x),
                    "X": host_reference(csr, X)}
            emit("setup", matrix=name, n=csr.n_rows, nnz=csr.nnz,
                 setup_s=time.perf_counter() - t0)
            if args.chips == 1:
                plan_phase(name, csr, x, X, refs, db, MAX_CANDIDATES)
                serve_phase(name, csr, x, X, refs, MAX_CANDIDATES)
            else:
                one = plan_phase(name, csr, x, X, refs, db, 0)
                sharded_phase(name, csr, x, X, refs, db, one, args.chips)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
