"""Multi-device sharded SpMV: tuned sharded vs single-device tuned vs CSR.

Times a torso1-class (heavy power-law tail) matrix three ways: whole-matrix
CSR, the single-device tuned path (``Planner().build``), and the sharded
tier at 2/4/8 shards — per-shard tuned formats (dispatch mode) and the
shard_map SPMD path over every device the process sees.

The benchmark runs in the calling process on the devices JAX already
has: on a TPU host that is its chips, and it never starts a child
process (a parent that has touched JAX holds the chip).  On a CPU, ask
for simulated devices before JAX starts:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        PYTHONPATH=src python -m benchmarks.sharded_spmv [--quick]

Simulated host devices share the machine's cores and add no parallel
hardware, so each ``row_nd*`` row reports two numbers: measured wall time
(``wall_us``), and the per-shard critical path (``us_per_call`` of the
``*_critical`` rows — the max per-shard SpMV time, i.e. what the mesh's
wall-clock becomes when every shard owns a device and the reassembly
collective is free).
"""
from __future__ import annotations

import sys
from typing import List

import jax
import jax.numpy as jnp

from repro.core.autotune import time_fn
from repro.core.plan import Planner
from repro.core.spmv import spmv
from repro.core.suite import synthesize_power_law
from repro.sharding import build_sharded

from .common import ITERS, Row

N_FULL = 16384
N_QUICK = 4096


def run(scale: float = None, iters: int = ITERS) -> List[Row]:
    n = N_FULL if scale is None else max(1024, int(N_FULL * scale / 0.08))
    rows = []
    csr = synthesize_power_law(n=n, mu=16.0, alpha=1.5, seed=0)
    x = jnp.ones((csr.n_cols,), jnp.float32)

    t_csr = time_fn(jax.jit(spmv), csr, x, iters=iters)
    rows.append(["csr_whole", t_csr * 1e6,
                 {"n": csr.n_rows, "nnz": csr.nnz}])

    P = Planner().build(csr)
    t_single = time_fn(lambda v: P.spmv(v), x, iters=iters)
    rows.append(["tuned_single", t_single * 1e6,
                 {"fmt": P.fmt,
                  "speedup_vs_csr": round(t_csr / t_single, 2)}])

    for nd in (2, 4, 8):
        spm = build_sharded(csr, n_shards=nd, axis="row", mode="dispatch")
        t_wall = time_fn(lambda v: spm.spmv(v), x, iters=iters)
        t_shards = [time_fn(lambda v, pm=pm: pm.spmv(v), x, iters=iters)
                    for pm in spm.planned]
        t_crit = max(t_shards)
        nnzs = list(spm.shard_nnz)
        rows.append([f"row_nd{nd}_critical", t_crit * 1e6,
                     {"metric": "max_shard_spmv",
                      "wall_us": round(t_wall * 1e6, 2),
                      "formats": ";".join(
                          sorted(set(spm.plan.shard_formats()))),
                      "imbalance_nnz": round(max(nnzs) / (sum(nnzs) / nd),
                                             3),
                      "speedup_vs_single": round(t_single / t_crit, 2),
                      "speedup_vs_csr": round(t_csr / t_crit, 2)}])

    nd = len(jax.devices())
    for axis in ("row", "col"):
        spm = build_sharded(csr, n_shards=nd, axis=axis)
        t_wall = time_fn(lambda v: spm.spmv(v), x, iters=iters)
        rows.append([f"{axis}_nd{nd}_shard_map", t_wall * 1e6,
                     {"mode": spm.mode, "metric": "wall", "devices": nd,
                      "speedup_vs_csr": round(t_csr / t_wall, 2)}])
    return [Row(name=f"sharded/powerlaw/{name}", us_per_call=us,
                derived=derived) for name, us, derived in rows]


def main() -> None:
    import argparse
    from .common import print_rows
    from .run import write_snapshot
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help=f"n={N_QUICK} smoke run (CI / snapshot refresh)")
    ap.add_argument("--json", default=None, metavar="DIR",
                    help="write BENCH_sharded.json into DIR")
    args = ap.parse_args()
    import time
    scale = 0.08 * N_QUICK / N_FULL if args.quick else None
    t0 = time.time()
    rows = run(scale=scale)
    print_rows(rows)
    if args.json:
        path = write_snapshot(args.json, "sharded", rows, time.time() - t0,
                              scale, args.quick)
        print(f"# wrote {path}", file=sys.stderr)


if __name__ == "__main__":
    main()
