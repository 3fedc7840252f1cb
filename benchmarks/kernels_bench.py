"""Kernel-level benchmark: tuned vs default launch geometry per
(format, op).

Matrices are chosen per format the way the paper's auto-tuner would route
them: CSR is benched on torso1 — the suite's flagship heavy-tail matrix
(D_mat 5.72), exactly the kind the D_mat–R rule keeps in CRS (the paper
removed torso1's ELL run for memory overflow) — while the regular,
transform-friendly chem_master1 carries the ELL/SELL rows.

Every (format, op) pair runs through ``core.kernel_tune.KernelTuner`` —
the default launch is always one of the timed candidates, so the reported
``tuned_speedup = t_default / t_best`` is >= 1.0 by construction (equality
means the default was already the winner).

The kernels run compiled on a TPU and in the Pallas interpreter elsewhere
(``kernels.ops`` chooses by backend); off the chip the times describe the
interpreter, not a device.

    PYTHONPATH=src python -m benchmarks.kernels_bench [--quick]
        [--scale S] [--iters N] [--json OUT.json]
"""
from __future__ import annotations

import argparse
import json
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import MatrixStats, dispatch, host_csr_to_ell, spmv, time_fn
from repro.core.kernel_tune import KernelTuner
from repro.core.suite import paper_suite
from repro.core.transform import TRANSFORMS_HOST
from repro.kernels import ops, ref

from .common import Row

# matrix -> formats benched on it (formats where the D_mat–R rule would
# actually land that matrix; see module docstring)
BENCH_PLAN: Tuple[Tuple[str, Tuple[str, ...]], ...] = (
    ("torso1", ("csr",)),
    ("chem_master1", ("ell_row", "sell")),
)


def run(scale: float = 0.01, iters: int = 3, batch: int = 8,
        plan: Optional[Tuple] = None) -> List[Row]:
    plan = plan or BENCH_PLAN
    suite = dict(paper_suite(scale=scale,
                             include=[name for name, _ in plan]))
    tuner = KernelTuner(iters=iters, warmup=1)
    rows: List[Row] = []
    for mat_name, formats in plan:
        csr = suite[mat_name]
        stats = MatrixStats.of(csr)
        for op in ("spmv", "spmm"):
            if op == "spmv":
                x = jnp.ones((csr.n_cols,), jnp.float32)
            else:
                x = jnp.ones((csr.n_cols, batch), jnp.float32)
            for fmt in formats:
                obj = TRANSFORMS_HOST[fmt](csr)
                impl = dispatch.get_impl(fmt, op, tier="kernel",
                                         fallback=False)
                rec = tuner.tune(obj, op=op, batch=(1 if op == "spmv"
                                                    else batch),
                                 impl=impl, stats=stats)
                derived = {
                    "d_mat": f"{stats.d_mat:.3f}",
                    "t_default_us": f"{rec.t_default * 1e6:.1f}",
                    "tuned_speedup": f"{rec.speedup:.3f}",
                    "geometry": json.dumps(rec.geometry.to_dict()),
                }
                if op == "spmm":
                    derived["batch"] = batch
                rows.append(Row(name=f"kernels/{fmt}_{op}/{mat_name}",
                                us_per_call=rec.t_best * 1e6,
                                derived=derived))
        # numerical sanity against the pure-jnp oracle (ELL), kept from the
        # original benchmark so the section still guards kernel parity
        if "ell_row" in formats:
            ell = host_csr_to_ell(csr)
            x1 = jnp.ones((csr.n_cols,), jnp.float32)
            d, c = jnp.asarray(ell.data), jnp.asarray(ell.cols)
            err = float(jnp.max(jnp.abs(
                ops.ell_spmv_raw(d, c, x1) -
                ref.ell_spmv_ref(d, c, x1))))
            t_ref = time_fn(jax.jit(spmv), ell, x1, iters=iters)
            rows.append(Row(name=f"kernels/ell_ref/{mat_name}",
                            us_per_call=t_ref * 1e6,
                            derived={"kernel_vs_ref_maxerr": f"{err:.2e}",
                                     "d_mat": f"{stats.d_mat:.3f}"}))
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="reduced plan / few iters (CI smoke)")
    ap.add_argument("--scale", type=float, default=None)
    ap.add_argument("--iters", type=int, default=None)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--json", default=None, help="also dump rows as JSON")
    args = ap.parse_args()
    scale = args.scale if args.scale is not None else 0.01
    iters = args.iters if args.iters is not None else (1 if args.quick else 3)
    plan = (("torso1", ("csr",)),
            ("chem_master1", ("ell_row",))) if args.quick else None
    rows = run(scale=scale, iters=iters, batch=args.batch, plan=plan)
    from .common import print_rows
    print_rows(rows)
    if args.json:
        with open(args.json, "w") as f:
            json.dump([{"name": r.name, "us_per_call": r.us_per_call,
                        **r.derived} for r in rows], f, indent=1)
    bad = [r.name for r in rows
           if float(r.derived.get("tuned_speedup", 1)) < 1.0]
    assert not bad, f"tuned geometry slower than default: {bad}"


if __name__ == "__main__":
    main()
