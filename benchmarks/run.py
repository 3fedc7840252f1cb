"""Benchmark driver — one section per paper table/figure plus the
integration, kernel, and observability suites.  Prints
``name,us_per_call,derived`` CSV; ``--json DIR`` additionally writes one
``BENCH_<section>.json`` snapshot per section (the machine-readable form
CI archives and ``benchmarks/snapshots/`` pins).

    PYTHONPATH=src python -m benchmarks.run [--only table1,fig8]
        [--scale S] [--quick] [--json DIR]

``--quick`` runs the scale-aware sections at a smoke scale — seconds,
not minutes — for CI and for refreshing committed snapshots.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

from .common import print_rows, use_compile_cache


SECTIONS = ("table1", "fig56", "fig7", "fig8", "hybrid", "spmm_batch",
            "dstar", "kernels", "obs", "guard", "sharded", "stream")

QUICK_SCALE = 0.02


def snapshot_path(json_dir: str, section: str) -> str:
    return os.path.join(json_dir, f"BENCH_{section}.json")


def write_snapshot(json_dir: str, section: str, rows, wall_s: float,
                   scale, quick: bool) -> str:
    """One section's rows as a JSON snapshot (sorted keys, trailing
    newline — byte-stable for committed copies)."""
    os.makedirs(json_dir, exist_ok=True)
    path = snapshot_path(json_dir, section)
    doc = {
        "section": section,
        "generated_by": "benchmarks.run",
        "quick": bool(quick),
        "scale": scale,
        "wall_s": round(wall_s, 2),
        "rows": [{"name": r.name, "us_per_call": round(r.us_per_call, 2),
                  "derived": r.derived} for r in rows],
    }
    with open(path, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True, default=str)
        f.write("\n")
    return path


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help=f"comma list of {SECTIONS}")
    ap.add_argument("--scale", type=float, default=None,
                    help="suite scale override (default per-section)")
    ap.add_argument("--quick", action="store_true",
                    help=f"smoke scale ({QUICK_SCALE}) for scale-aware "
                         "sections; the CI/snapshot path")
    ap.add_argument("--json", default=None, metavar="DIR",
                    help="also write BENCH_<section>.json per section")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else set(SECTIONS)
    unknown = only - set(SECTIONS)
    if unknown:
        ap.error(f"unknown sections {sorted(unknown)}; "
                 f"choose from {SECTIONS}")
    scale = args.scale if args.scale is not None \
        else (QUICK_SCALE if args.quick else None)

    use_compile_cache()
    rows = []
    t0 = time.time()

    def section(name, fn, **kw):
        if name not in only:
            return
        t = time.time()
        out = list(fn(**kw))
        rows.extend(out)
        dt = time.time() - t
        print(f"# {name}: {dt:.1f}s", file=sys.stderr)
        if args.json:
            path = write_snapshot(args.json, name, out, dt,
                                  kw.get("scale"), args.quick)
            print(f"# wrote {path}", file=sys.stderr)

    from . import (fig56_speedup, fig7_overhead, fig8_graph, hybrid_blocks,
                   kernels_bench, obs_overhead, sharded_spmv, spmm_batch,
                   stream_updates, table1)
    scale_kw = {"scale": scale} if scale is not None else {}
    section("table1", table1.run, **scale_kw)
    section("fig56", fig56_speedup.run, **scale_kw)
    section("fig7", fig7_overhead.run, **scale_kw)
    section("fig8", fig8_graph.run, **scale_kw)
    section("hybrid", hybrid_blocks.run, **scale_kw)
    section("spmm_batch", spmm_batch.run, **scale_kw)
    section("dstar", spmm_batch.dstar_sweep, **scale_kw)
    section("kernels", kernels_bench.run)
    section("obs", obs_overhead.run, **scale_kw)
    section("stream", stream_updates.run, **scale_kw)
    section("guard", obs_overhead.run_guard, **scale_kw)
    # runs on the devices this process already has (see its docstring)
    section("sharded", sharded_spmv.run, **scale_kw)

    print_rows(rows)
    print(f"# total: {time.time()-t0:.1f}s", file=sys.stderr)


if __name__ == "__main__":
    main()
