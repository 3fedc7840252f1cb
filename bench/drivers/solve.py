"""One client in a closed loop: ``SpMVService.spmv``, wait, again.

The vectors cycle through the session's pool.  This is an iterative
solver's or a PageRank iteration's traffic: every step waits on the
product before it.
"""
import time

from jax.profiler import TraceAnnotation

OP = "spmv"


def size(s, i: int) -> int:
    return 1


def per_call(s) -> int:
    return 1


def request(s, i: int) -> None:
    j = i % len(s.xs)
    t0 = time.perf_counter()
    with TraceAnnotation("client.spmv"):
        y = s.svc.spmv(s.key, s.xs[j])      # returns when y is ready
    s.done(t0, time.perf_counter(), y, j)
