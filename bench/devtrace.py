"""Device traces: capture, a plain form, and the reductions to metrics.

``capture`` runs JAX's profiler around a block.  ``load`` turns the
``.xplane.pb`` it wrote into a :class:`Trace`: device operations (the
``XLA Ops`` line of each ``/device:`` plane, named by their HLO text) and
the host events of the client's thread (the ``/host:CPU`` line that holds
the client's annotations), each with its start and end in nanoseconds.
``Trace.from_json`` reads that form back from a file of two lists
(``device`` and ``host``), which is what ``bench/tests`` keeps.

The window is the span of the client's own annotations (``client.*``,
written by the drivers): from the start of the first to the end of the
last.  Busy time is the union of device-operation intervals inside it,
averaged over the devices that ran anything.
"""
from __future__ import annotations

import contextlib
import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

#: the Pallas kernels, by the ``name=`` they are launched with
KERNELS = ("ell_spmv", "ell_spmm", "csr_spmm")
OPS_LINE = "XLA Ops"
CLIENT = "client."

Interval = Tuple[int, int]


@contextlib.contextmanager
def capture(log_dir: str) -> Iterator[None]:
    """Profile the block into ``log_dir``, without the Python tracer."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


@dataclass
class Trace:
    #: (device plane, op name, start ns, end ns)
    device: List[Tuple[str, str, int, int]] = field(default_factory=list)
    #: (host line, event name, start ns, end ns)
    host: List[Tuple[str, str, int, int]] = field(default_factory=list)

    @staticmethod
    def from_json(d: Dict[str, list]) -> "Trace":
        return Trace(device=[tuple(e) for e in d["device"]],
                     host=[tuple(e) for e in d["host"]])

    # -- the window and busy time -------------------------------------------
    def window(self) -> Optional[Interval]:
        spans = [(s, e) for _, n, s, e in self.host if n.startswith(CLIENT)]
        if not spans:
            return None
        return min(s for s, _ in spans), max(e for _, e in spans)

    def window_s(self) -> float:
        w = self.window()
        return (w[1] - w[0]) * 1e-9 if w else 0.0

    def _ops(self, plane: Optional[str] = None,
             names: Optional[Sequence[str]] = None) -> List[Interval]:
        w = self.window()
        if w is None:
            return []
        out = []
        for p, n, s, e in self.device:
            if plane is not None and p != plane:
                continue
            if names is not None and not is_kernel(n, names):
                continue
            s, e = max(s, w[0]), min(e, w[1])
            if e > s:
                out.append((s, e))
        return out

    def planes(self) -> List[str]:
        return sorted({p for p, _, _, _ in self.device})

    def busy_s(self, names: Optional[Sequence[str]] = None) -> float:
        """Union of device-operation time in the window (of the ``names``
        kernels alone, when given), averaged over the devices that ran."""
        planes = [p for p in self.planes() if self._ops(p)]
        if not planes:
            return 0.0
        return sum(_union(self._ops(p, names)) for p in planes) \
            * 1e-9 / len(planes)

    # -- the breakdown ------------------------------------------------------
    def top_ops(self, n: int = 10) -> List[List]:
        """Device time in the window by operation name, largest first."""
        w = self.window()
        tot: Dict[str, int] = defaultdict(int)
        if w is not None:
            for _, name, s, e in self.device:
                s, e = max(s, w[0]), min(e, w[1])
                if e > s:
                    tot[op_label(name)] += e - s
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v * 1e-9] for k, v in top]

    def idle_gaps(self, n: int = 10) -> List[List]:
        """Idle device time in the window, by what the client's thread was
        doing at the middle of each gap: the client annotation and the
        innermost host event under it."""
        w = self.window()
        planes = self.planes()
        if w is None or not planes:
            return []
        busy = _merge(self._ops(planes[0]))
        gaps, t = [], w[0]
        for s, e in busy:
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if w[1] > t:
            gaps.append((t, w[1]))
        lines = {ln for ln, name, _, _ in self.host
                 if name.startswith(CLIENT)}
        host = sorted(((s, e, name) for ln, name, s, e in self.host
                       if ln in lines), key=lambda h: (h[0], -h[1]))
        mids = sorted(((s + e) // 2, e - s) for s, e in gaps)
        tot: Dict[str, int] = defaultdict(int)
        for label, dur in zip(_doing(host, [t for t, _ in mids]),
                              (d for _, d in mids)):
            tot[label] += dur
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v * 1e-9] for k, v in top]


def is_kernel(op: str, names: Sequence[str] = KERNELS) -> bool:
    return op_family(op) in names


def op_family(op: str) -> str:
    """An operation's short name without the numeric suffix XLA gives each
    instance.  The TPU trace names an operation by its HLO text
    (``%fusion.12 = f32[...]{...} fusion(...)``): its family is
    ``fusion``; a Pallas kernel's is its ``name=`` (``ell_spmv``)."""
    head = op.split(" = ", 1)[0].strip().lstrip("%")
    return re.sub(r"(\.\d+)+$", "", head)


def op_label(op: str) -> str:
    """An operation's name and result type, without operands or layouts:
    ``fusion.12 = f32[495616] fusion``."""
    op = re.sub(r"\{[^}]*\}", "", op)
    m = re.match(r"%?(\S+) = (\(.*?\)|\S+) ([\w.-]+)\(", op)
    return " ".join((m[1], "=", m[2], m[3])) if m else op.lstrip("%")


def _merge(iv: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def _union(iv: List[Interval]) -> int:
    return sum(e - s for s, e in _merge(iv))


def _doing(host: List[Tuple[int, int, str]], times: List[int]) -> List[str]:
    """For each of the sorted ``times``: the outermost client annotation
    covering it and the innermost host event under that
    (``client.spmv > PjitFunction(fn)``), or ``between calls``.  The host
    events of one thread nest, so one sweep with a stack finds both."""
    out, stack, i = [], [], 0
    for t in times:
        while i < len(host) and host[i][0] <= t:
            while stack and stack[-1][1] <= host[i][0]:
                stack.pop()
            stack.append(host[i])
            i += 1
        while stack and stack[-1][1] <= t:
            stack.pop()
        client = next((h for h in stack if h[2].startswith(CLIENT)), None)
        if client is None:
            out.append("between calls")
        elif stack[-1] is client:
            out.append(client[2])
        else:
            out.append(f"{client[2]} > {stack[-1][2]}")
    return out


def load(log_dir: str) -> Trace:
    """Read the one ``.xplane.pb`` under ``log_dir`` into a :class:`Trace`."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(paths) != 1:
        raise RuntimeError(f"expected one profile under {log_dir}, found "
                           f"{len(paths)}")
    tr = Trace()
    for plane in ProfileData.from_file(paths[0]).planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    tr.device.append((plane.name, ev.name, int(ev.start_ns),
                                      int(ev.end_ns)))
        elif plane.name == "/host:CPU":
            # only the client's thread: the runtime's threads hold millions
            # of events that no reduction reads
            for line in plane.lines:
                evs = [(line.name, ev.name, int(ev.start_ns), int(ev.end_ns))
                       for ev in line.events]
                if any(e[1].startswith(CLIENT) for e in evs):
                    tr.host.extend(evs)
    return tr
