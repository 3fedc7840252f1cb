"""Share of device busy time spent outside the Pallas kernels: the
``x[ICOL]`` gathers, pads, transposes, SELL and hybrid scatters and panel
stacks of the kernel wrappers and the hybrid reassembly."""
from bench.devtrace import KERNELS


def read(ctx):
    tr = ctx["trace"]
    busy = tr.busy_s() if tr is not None else 0.0
    kernels = tr.busy_s(KERNELS) if busy > 0 else 0.0
    if kernels <= 0:
        return None
    return 100.0 * (busy - kernels) / busy
