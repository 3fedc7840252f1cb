"""One client in a closed loop of bursts: ``submit`` a burst of vectors,
wait on every future, send the next burst.

Traffic parameters: ``burst`` = ``[min, max]`` vectors per burst.  A
burst of ``max_batch`` vectors is flushed by the service itself when the
last one is submitted (one SpMM); a shorter burst ends with an explicit
``flush``.  Burst sizes cycle through every size of the range, in an order
drawn from the seed, so every seed sends the same sizes.  The latency of
a vector runs from its ``submit`` to its result being ready.
"""
import time

import jax
from jax.profiler import TraceAnnotation

OP = "spmm"


def _sizes(s):
    if "sizes" not in s.state:
        lo, hi = s.traffic["burst"]
        s.state["sizes"] = [int(v) for v in
                            s.rng.permutation(range(lo, hi + 1))]
    return s.state["sizes"]


def size(s, i: int) -> int:
    sizes = _sizes(s)
    return sizes[i % len(sizes)]


def per_call(s) -> int:
    return int(s.traffic["max_batch"])


def request(s, i: int) -> None:
    b = size(s, i)
    start = i * int(s.traffic["burst"][1])
    sent, futs = [], []
    for v in range(b):
        j = (start + v) % len(s.xs)
        sent.append((time.perf_counter(), j))
        with TraceAnnotation("client.submit"):
            futs.append(s.svc.submit(s.key, s.xs[j]))
    if b < int(s.traffic["max_batch"]):
        with TraceAnnotation("client.flush"):
            s.svc.flush(s.key)
    with TraceAnnotation("client.wait"):
        ys = jax.block_until_ready([f.result() for f in futs])
    t_ready = time.perf_counter()
    for (t0, j), y in zip(sent, ys):
        s.done(t0, t_ready, y, j)
