#!/usr/bin/env python3
"""Run one cell of the benchmark on the chips of this machine.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json`` at the
checkout's root.  The last line of standard output is the result as one
JSON object; the numbers compared to decide ``correct`` are the last
lines of standard error.  With no TPU, or fewer chips than the cell asks
for, the run exits with code 2 and prints no result.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[0:1] = [ROOT, os.path.join(ROOT, "src")]   # not bench/ itself
# libtpu would otherwise keep its logs at a fixed path under /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench.harness import Cell, print_result, read_json, run, \
        use_compile_cache
    bm = read_json(ROOT, "BENCHMARK.json")
    cell = Cell.load(args.workload, bm)
    chips = next(w["chips"] for w in bm["workloads"]
                 if w["name"] == args.workload)

    import jax
    use_compile_cache()
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"bench: JAX found no TPU (platform {devs[0].platform!r})",
              file=sys.stderr)
        return 2
    if len(devs) < chips:
        print(f"bench: {args.workload} needs {chips} chips, JAX sees "
              f"{len(devs)}", file=sys.stderr)
        return 2
    print_result(run(cell, args.seed, args.seconds, bool(args.trace),
                     T_PROCESS))
    return 0


if __name__ == "__main__":
    sys.exit(main())
