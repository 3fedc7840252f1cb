"""A trace recorded on a TPU v5e with the program's telemetry on
(``data/trace_v5e_xenon2_batch32_spans.json``: two bursts of
``xenon2.batch32``).  Beside the device's ``XLA Ops`` and the client
thread's host events it keeps ``scopes``: each device op's ``tf_op``
stat, the op metadata that carries its named scope, which the XPlane
keeps in the op's event metadata and not in the event's own stats."""
import json
import os

import pytest

from bench.devtrace import Trace, op_family
from bench.harness import load_module

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(DATA, "trace_v5e_xenon2_batch32_spans.json")) as f:
        d = json.load(f)
    return Trace.from_json(d), d["scopes"]


def _within(inner, outer):
    return outer[2] <= inner[2] and inner[3] <= outer[3]


def test_the_reductions_read_it_as_before(recorded):
    tr, _ = recorded
    ctx = {"trace": tr, "least_s": 0.0, "spans": [], "peaks": None}
    idle = load_module("metrics", "idle_share").read(ctx)
    glue = load_module("metrics", "glue_share").read(ctx)
    assert 0 < idle < 100 and 0 < glue < 100
    assert sum(v for _, v in tr.idle_gaps(n=100)) == pytest.approx(
        tr.window_s() - tr.busy_s(), rel=1e-6)


def test_program_spans_nest_on_the_client_line(recorded):
    tr, _ = recorded
    line = {ln for ln, n, _, _ in tr.host if n.startswith("client.")}
    assert len(line) == 1
    ev = {n: [e for e in tr.host if e[1] == n] for n in
          ("client.submit", "service.submit", "service.flush",
           "service.panel", "service.scatter", "guard.dispatch",
           "guard.probe")}
    assert len(ev["service.submit"]) == len(ev["client.submit"]) == 64
    for s in ev["service.submit"]:
        assert any(_within(s, c) for c in ev["client.submit"])
    assert len(ev["service.flush"]) == 2
    for f in ev["service.flush"]:
        assert any(_within(f, s) for s in ev["service.submit"])
    for name in ("service.panel", "service.scatter", "guard.dispatch",
                 "guard.probe"):
        assert len(ev[name]) == 2
        for e in ev[name]:
            assert any(_within(e, f) for f in ev["service.flush"]), name


def test_device_ops_carry_their_scope(recorded):
    tr, scopes = recorded
    gathers = {n for n in scopes if "/gather_x/" in scopes[n]}
    scatters = {n for n in scopes if "/reassemble/" in scopes[n]}
    assert gathers and scatters and not gathers & scatters
    longest = max(tr.device, key=lambda d: d[3] - d[2])[1]
    assert longest in scatters and op_family(longest) == "fusion"
    w0, w1 = tr.window()
    busy = tr.busy_s()
    gather_s = sum(min(e, w1) - max(s, w0) for _, n, s, e in tr.device
                   if n in gathers and min(e, w1) > max(s, w0)) * 1e-9
    glue = load_module("metrics", "glue_share").read(
        {"trace": tr, "least_s": 0.0, "spans": [], "peaks": None})
    assert 0 < 100 * gather_s / busy <= glue
