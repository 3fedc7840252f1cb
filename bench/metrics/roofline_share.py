"""The least time of the products served in the traced window, over the
device's busy time in it.  The least time counts each value, column index
and row-pointer entry once per call and each vector once
(``bench/work.py``), against the chip's published peaks."""


def read(ctx):
    tr = ctx["trace"]
    busy = tr.busy_s() if tr is not None else 0.0
    if busy <= 0 or ctx["least_s"] <= 0:
        return None
    return 100.0 * ctx["least_s"] / busy
