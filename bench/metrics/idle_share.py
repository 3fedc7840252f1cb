"""Share of the traced window in which no operation ran on the device."""


def read(ctx):
    tr = ctx["trace"]
    window = tr.window_s() if tr is not None else 0.0
    if window <= 0:
        return None
    return 100.0 * (1.0 - tr.busy_s() / window)
