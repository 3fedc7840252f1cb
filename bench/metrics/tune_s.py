"""Seconds of the kernel tuner's launch-geometry sweeps during
registration: the program's ``tune.sweep`` spans."""


def read(ctx):
    durs = [s["dur"] for s in ctx["spans"] if s["name"] == "tune.sweep"]
    return sum(durs) if durs else None
