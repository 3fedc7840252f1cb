"""The reduction from a device trace to idle, glue and roofline shares and
the breakdown: on a hand-made trace with known answers, and on a small
trace recorded on a TPU v5e (``data/trace_v5e.json``)."""
import json
import os

import pytest

from bench.devtrace import Trace, op_family, op_label
from bench.harness import load_module

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
DEV = "/device:TPU:0"
MS = 1_000_000
GATHER = ("%fusion.1 = f32[48]{0:T(1024)S(1)} fusion(f32[16]{0:T(1024)S(1)} "
          "%copy-done, s32[48]{0:T(1024)S(1)} %broadcast_clamp_fusion), "
          "kind=kCustom, calls=%fused_computation")
KERNEL = ("%%ell_spmv.%d = f32[1,8]{1,0:T(1,128)S(1)} custom-call(f32[8,8]"
          "{1,0:T(8,128)S(1)} %%pad.3, f32[8,8]{1,0:T(8,128)S(1)} "
          "%%reshape.2), custom_call_target=\"tpu_custom_call\"")


def hand_made():
    # client window 0..10 ms; device: gather 1..3, kernel 3..5 and 4..6
    # (overlapping), pad 8..9, and an op outside the window 12..13
    # operations are named by their HLO text, as in a TPU trace
    return Trace(
        device=[(DEV, GATHER, 1 * MS, 3 * MS),
                (DEV, KERNEL % 2, 3 * MS, 5 * MS),
                (DEV, KERNEL % 7, 4 * MS, 6 * MS),
                (DEV, "%pad = f32[8]{0:T(1024)} pad(f32[6]{0} %y, f32[] "
                      "%c), padding=0_2", 8 * MS, 9 * MS),
                (DEV, "%fusion.3 = f32[8]{0} fusion(f32[8]{0} %p)",
                 12 * MS, 13 * MS)],
        host=[("main", "client.spmv", 0, 7 * MS),
              ("main", "PjitFunction(fn)", 5 * MS + 500_000, 7 * MS),
              ("main", "client.spmv", 7 * MS + 100_000, 10 * MS),
              ("other", "unrelated", 0, 10 * MS)])


def ctx(tr, least_s=0.0, spans=()):
    return {"trace": tr, "least_s": least_s, "spans": list(spans),
            "peaks": None}


def read(metric, c):
    return load_module("metrics", metric).read(c)


def test_window_busy_and_idle():
    tr = hand_made()
    assert tr.window() == (0, 10 * MS)
    assert tr.window_s() == pytest.approx(0.010)
    assert tr.busy_s() == pytest.approx(0.006)          # 1..6 and 8..9
    assert read("idle_share", ctx(tr)) == pytest.approx(40.0)


def test_glue_is_busy_time_outside_the_kernels():
    tr = hand_made()
    assert tr.busy_s(("ell_spmv",)) == pytest.approx(0.003)
    assert read("glue_share", ctx(tr)) == pytest.approx(50.0)


def test_roofline_is_least_time_over_busy_time():
    assert read("roofline_share", ctx(hand_made(), least_s=0.0015)) == \
        pytest.approx(25.0)
    assert read("roofline_share", ctx(hand_made())) is None


def test_breakdown():
    tr = hand_made()
    ops = dict(tr.top_ops())
    assert ops == pytest.approx({
        "ell_spmv.2 = f32[1,8] custom-call": 0.002,
        "ell_spmv.7 = f32[1,8] custom-call": 0.002,
        "fusion.1 = f32[48] fusion": 0.002,
        "pad = f32[8] pad": 0.001})
    gaps = dict(tr.idle_gaps())
    # idle 0..1 (middle 0.5 ms: inside the first call), 6..8 (middle 7.0
    # ms: the first call has ended, the second not begun) and 9..10
    # (inside the second call)
    assert gaps == pytest.approx({"client.spmv": 0.002,
                                  "between calls": 0.002})


def test_nothing_to_read():
    empty = Trace()
    for m in ("idle_share", "glue_share", "roofline_share"):
        assert read(m, ctx(empty, least_s=1.0)) is None
    assert read("glue_share", ctx(Trace(
        device=[(DEV, "fusion", 0, 5)],
        host=[("main", "client.spmv", 0, 10)]))) is None


def test_op_names():
    assert op_family(GATHER) == "fusion"
    assert op_family(KERNEL % 5) == "ell_spmv"
    assert op_family("copy.1.2") == "copy"
    assert op_label(GATHER) == "fusion.1 = f32[48] fusion"
    assert op_label("%sort = (s32[4]{0:T(1024)S(1)}, s32[4]{0}) sort(s32[4]"
                    "{0} %a, s32[4]{0} %iota), dimensions={0}") == \
        "sort = (s32[4], s32[4]) sort"


def test_program_spans():
    spans = [
        {"name": "transform", "span_id": 1, "parent_id": None, "dur": 2.0},
        {"name": "partition", "span_id": 2, "parent_id": 1, "dur": 1.0},
        {"name": "transform", "span_id": 3, "parent_id": 2, "dur": 0.5},
        {"name": "transform", "span_id": 4, "parent_id": None, "dur": 0.25},
        {"name": "tune.sweep", "span_id": 5, "parent_id": None, "dur": 3.0},
        {"name": "tune.sweep", "span_id": 6, "parent_id": None, "dur": 1.5},
    ]
    assert read("transform_s", ctx(None, spans=spans)) == pytest.approx(2.25)
    assert read("tune_s", ctx(None, spans=spans)) == pytest.approx(4.5)
    assert read("transform_s", ctx(None)) is None
    assert read("tune_s", ctx(None)) is None


@pytest.fixture(scope="module")
def recorded():
    """Two ``spmv`` calls of ``xenon2.solve`` traced on a TPU v5e: the
    device's ``XLA Ops`` and the client thread's host events."""
    with open(os.path.join(DATA, "trace_v5e_xenon2_solve.json")) as f:
        return Trace.from_json(json.load(f))


def brute_busy_s(tr, step_ns=100):
    """Busy time by marking a grid of ``step_ns`` slots, for comparison."""
    import numpy as np
    w0, w1 = tr.window()
    grid = np.zeros((w1 - w0) // step_ns + 1, bool)
    for _, _, s, e in tr.device:
        s, e = max(s, w0), min(e, w1)
        if e > s:
            grid[(s - w0) // step_ns:(e - w0) // step_ns] = True
    return grid.sum() * step_ns * 1e-9


def test_recorded_trace(recorded):
    from bench.peaks import peaks
    from bench.work import least_seconds
    tr = recorded
    assert tr.planes() == [DEV]
    assert 0.10 < tr.window_s() < 0.12
    busy = tr.busy_s()
    assert busy == pytest.approx(brute_busy_s(tr), rel=1e-2)
    idle = read("idle_share", ctx(tr))
    assert idle == pytest.approx(100 * (1 - busy / tr.window_s()))
    # the Pallas kernels are found by name, and the gathers outweigh them
    assert 0 < tr.busy_s(("ell_spmv",)) < 0.1 * busy
    assert read("glue_share", ctx(tr)) > 90
    # two products of xenon2, against the v5e's peaks
    least = 2 * least_seconds(157464, 157464, 3866688, 1,
                              peaks("TPU v5 lite"))
    share = read("roofline_share", ctx(tr, least_s=least))
    assert share == pytest.approx(100 * least / busy)
    assert 0 < share < 1
    ops = tr.top_ops()
    assert len(ops) == 10 and ops[0][0].startswith("fusion")
    assert [v for _, v in ops] == sorted((v for _, v in ops), reverse=True)
    gaps = tr.idle_gaps()
    assert all(k.startswith("client.spmv") or k == "between calls"
               for k, _ in gaps)
    assert sum(v for _, v in gaps) == pytest.approx(tr.window_s() - busy,
                                                    rel=1e-6)
