"""Seconds of host format conversion during registration: the program's
``transform`` spans, a conversion nested inside another counted once."""


def read(ctx):
    parent = {s["span_id"]: s["parent_id"] for s in ctx["spans"]}
    name = {s["span_id"]: s["name"] for s in ctx["spans"]}

    def nested(sid):
        p = parent.get(sid)
        while p is not None:
            if name.get(p) == "transform":
                return True
            p = parent.get(p)
        return False

    durs = [s["dur"] for s in ctx["spans"]
            if s["name"] == "transform" and not nested(s["span_id"])]
    return sum(durs) if durs else None
