#!/usr/bin/env python3
"""The readings a cell's ``row_err`` limit is set from, in one process.

    python bench/readings.py --workload <name> --seeds 1001-1012 \\
        --control-seeds 3 --seconds 2

One set-up, then for each seed: the client's vectors drawn from it, the
cell's own traffic for ``--seconds`` through the same registered
service, and the worst row error of the sampled products against the
float64 reference (the program's reading).  For the first
``--control-seeds`` seeds the same sampled vectors also go through the
control (``reference.Control``: the reference in bfloat16 on the device),
whose row error is the upper reading.  One JSON line per seed, then a
summary line.  The benchmark's own runs do not run this.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[0:1] = [ROOT, os.path.join(ROOT, "src")]
# libtpu would otherwise keep its logs at a fixed path under /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def seed_list(spec: str):
    lo, _, hi = spec.partition("-")
    return list(range(int(lo), int(hi or lo) + 1))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="first-last")
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args(argv)

    import numpy as np
    from bench.harness import Cell, drive, prepare, row_err, \
        use_compile_cache
    from bench.reference import Control, HostReference
    import jax
    use_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"readings: JAX found no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 2
    cell = Cell.load(args.workload)
    seeds = seed_list(args.seeds)
    p = prepare(cell, seeds[0])
    print(f"# set-up {time.perf_counter() - T_PROCESS:.1f} s", flush=True)
    ref = HostReference(p.host)
    control = Control(p.host)
    program, ctrl = [], []
    for n, seed in enumerate(seeds):
        if n:
            p.new_vectors(seed)
        w = drive(p, args.seconds)
        line = {"seed": seed, "requests": w.requests,
                "compared": len(w.samples), "raised": w.raised,
                "failed": w.failed, "row_err": row_err(p, w, ref)}
        program.append(line["row_err"])
        if n < args.control_seeds:
            line["control_row_err"] = max(
                ref.row_err(control.product(p.sess.xs[j]),
                            np.asarray(p.sess.xs[j]))
                for _, j in w.samples)
            ctrl.append(line["control_row_err"])
        print(json.dumps(line), flush=True)
    print(json.dumps({
        "workload": args.workload, "seeds": len(seeds),
        "lower": max(program), "upper": min(ctrl) if ctrl else None,
        "fallbacks": p.guard.snapshot()["fallback_calls"],
        "degraded": p.degraded(), "device": dev.device_kind}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
