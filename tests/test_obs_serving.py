"""The serving path's own telemetry: ``repro.obs`` spans as profiler
annotations on the device trace's clock, the service's and the guard's
spans, the queue-wait histogram, the host-bytes counter, and the named
scopes on the kernel wrappers' gathers and reassembly.  Everything runs
on the CPU under a FakeClock; nothing here measures a time."""
import glob
import os
import re
import sys

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import repro.obs as obs
from repro.core.plan import Planner
from repro.core.transform import TRANSFORMS_HOST, csr_from_dense
from repro.kernels import ops
from repro.obs import NOOP_SPAN, FakeClock, InMemorySink, Telemetry
from repro.partition import build_hybrid, spmm_hybrid, spmv_hybrid
from repro.serve import faults
from repro.serve.spmv_service import SpMVService, host_nbytes
from repro.stream.delta import random_delta


@pytest.fixture()
def tel():
    t = Telemetry(enabled=True, clock=FakeClock(), sinks=[InMemorySink()])
    prev = obs.set_default(t)
    yield t
    obs.set_default(prev)


@pytest.fixture(autouse=True)
def clean_faults():
    faults.clear()
    yield
    faults.clear()


def _csr(seed=7, shape=(48, 40), density=0.15):
    rng = np.random.default_rng(seed)
    d = (rng.random(shape) < density).astype(np.float32)
    return csr_from_dense(d * rng.normal(1.0, 1.0, size=shape)
                          .astype(np.float32), pad=8)


def _spans(tel, name=None):
    return [r for r in tel.sinks[0].spans()
            if name is None or r["name"] == name]


def _host_events(log_dir):
    """(name, stats, start, end) of every event on the ``/host:CPU``
    plane of the one profile under ``log_dir``."""
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                out.extend((ev.name, dict(ev.stats), ev.start_ns, ev.end_ns)
                           for ev in line.events)
    return out


# ---------------------------------------------------------------------------
# one clock: spans are profiler annotations
# ---------------------------------------------------------------------------
def test_span_shows_on_the_profiler_host_plane_with_its_attributes(
        tel, tmp_path):
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tel.span("service.spmv", key="k0", n=3) as sp:
            with tel.span("guard.probe", key="k0", op="spmv"):
                jnp.ones(4).block_until_ready()
            sp.set(compiled=True)
    finally:
        jax.profiler.stop_trace()
    evs = {name: (st, s, e) for name, st, s, e in _host_events(tmp_path)
           if name in ("service.spmv", "guard.probe")}
    assert set(evs) == {"service.spmv", "guard.probe"}
    st, s, e = evs["service.spmv"]
    assert st["key"] == "k0" and st["n"] == 3
    assert st["compiled"] == 1          # set inside the block
    pst, ps, pe = evs["guard.probe"]
    assert pst["op"] == "spmv"
    assert s <= ps <= pe <= e           # nested on the same thread
    # the telemetry's own record is unchanged by the annotation
    (rec,) = _spans(tel, "service.spmv")
    assert rec["attrs"] == {"key": "k0", "n": 3, "compiled": True}


def test_disabled_telemetry_records_nothing_and_returns_noop(tmp_path):
    t = Telemetry(enabled=False, sinks=[InMemorySink()])
    prev = obs.set_default(t)
    try:
        assert t.span("service.spmv", key="k") is NOOP_SPAN
        svc = SpMVService()
        csr = _csr()
        svc.register("m", csr, measure_baseline=False)
        jax.profiler.start_trace(str(tmp_path))
        try:
            svc.spmv("m", np.ones(csr.n_cols, np.float32))
            svc.submit("m", np.ones(csr.n_cols, np.float32))
            svc.flush("m")
        finally:
            jax.profiler.stop_trace()
    finally:
        obs.set_default(prev)
    assert t.sinks[0].records == [] and t.spans == []
    assert t.snapshot()["counters"] == {} \
        and t.snapshot()["histograms"] == {}
    names = {n for n, _, _, _ in _host_events(tmp_path)}
    assert not names & {"service.spmv", "service.submit", "service.flush",
                        "guard.dispatch", "guard.probe"}


def test_annotation_only_when_jax_is_loaded(tel, monkeypatch):
    from repro.obs.tracing import _open_annotation
    assert _open_annotation("a.b", {}) is not None
    monkeypatch.delitem(sys.modules, "jax")
    assert _open_annotation("a.b", {"k": 1}) is None
    with tel.span("a.b", k=1):           # the span itself still records
        pass
    assert [r["name"] for r in _spans(tel)] == ["a.b"]


# ---------------------------------------------------------------------------
# spans where the work happens
# ---------------------------------------------------------------------------
def test_spmv_span_covers_dispatch_and_probe_and_marks_compiles(tel):
    csr = _csr()
    svc = SpMVService()
    svc.register("m", csr, measure_baseline=False)
    x = np.ones(csr.n_cols, np.float32)
    svc.spmv("m", x)
    svc.spmv("m", x)
    calls = _spans(tel, "service.spmv")
    assert [c["attrs"]["key"] for c in calls] == ["m", "m"]
    assert calls[0]["attrs"].get("compiled") is True     # the first call
    assert "compiled" not in calls[1]["attrs"]           # reuses it
    ids = {c["span_id"] for c in calls}
    for name in ("guard.dispatch", "guard.probe"):
        got = _spans(tel, name)
        assert len(got) == 2 and {g["parent_id"] for g in got} == ids
        assert all(g["attrs"]["key"] == "m" and g["attrs"]["op"] == "spmv"
                   for g in got)
    assert {g["attrs"]["rung"] for g in _spans(tel, "guard.dispatch")} == \
        {"tuned"}


def test_flush_holds_panel_dispatch_and_scatter_under_submit(tel):
    csr = _csr()
    svc = SpMVService(max_batch=3)
    svc.register("m", csr, measure_baseline=False)
    futs = [svc.submit("m", np.full(csr.n_cols, i, np.float32))
            for i in range(3)]                  # the third flushes
    assert all(f.done() for f in futs)
    (flush,) = _spans(tel, "service.flush")
    submits = _spans(tel, "service.submit")
    assert len(submits) == 3 and flush["parent_id"] == submits[-1]["span_id"]
    assert flush["attrs"]["cause"] == "max_batch"
    assert flush["attrs"].get("compiled") is True
    for name in ("service.panel", "service.scatter", "guard.dispatch",
                 "guard.probe"):
        (sp,) = _spans(tel, name)
        assert sp["parent_id"] == flush["span_id"], name
        assert sp["attrs"]["key"] == "m"
    assert _spans(tel, "service.panel")[0]["attrs"]["batch"] == 3
    assert _spans(tel, "service.scatter")[0]["attrs"]["batch"] == 3
    assert _spans(tel, "guard.dispatch")[0]["attrs"]["op"] == "spmm"


def test_queue_wait_runs_from_enqueue_to_flush_start(tel):
    csr = _csr()
    clk = FakeClock()
    svc = SpMVService(max_batch=3, clock=clk)
    svc.register("m", csr, measure_baseline=False)
    x = np.ones(csr.n_cols, np.float32)
    svc.submit("m", x)
    clk.advance(0.002)
    svc.submit("m", x)
    clk.advance(0.003)
    svc.submit("m", x)                          # flushes at t = 5 ms
    clk.advance(0.010)
    svc.submit("m", x)
    clk.advance(0.001)
    svc.flush("m")                              # waited 1 ms
    h = tel.histogram("service.queue_wait_s", key="m")
    assert h.count == 4
    assert h.sum == pytest.approx(0.005 + 0.003 + 0.0 + 0.001)


def _on_device(matrix):
    leaves = jax.tree_util.tree_leaves(matrix)
    return bool(leaves) and all(isinstance(a, jax.Array) for a in leaves)


def test_host_bytes_counted_once_per_spmv_and_per_flush(tel):
    csr = _csr()
    svc = SpMVService(max_batch=2)
    entry = svc.register("m", csr, measure_baseline=False)
    assert _on_device(entry.matrix), "the served operator stays on the chip"
    assert entry.host_bytes == host_nbytes(entry.matrix) == 0
    assert entry.host_matrix is None            # no second copy
    x = np.ones(csr.n_cols, np.float32)
    svc.spmv("m", x)
    svc.spmv("m", x)
    svc.submit("m", x)
    svc.submit("m", x)                          # one flush
    counters = tel.snapshot()["counters"]
    assert counters["service.host_bytes{key=m,op=spmv}"] == 0
    assert counters["service.host_bytes{key=m,op=spmm}"] == 0
    assert len(_spans(tel, "service.spmv")) == 2
    assert len(_spans(tel, "service.flush")) == 1


def test_host_bytes_ignores_device_arrays():
    a, b = np.zeros(10, np.float32), jnp.zeros(1000, jnp.float32)
    assert host_nbytes({"a": a, "b": b, "c": (a, 3)}) == 80


def test_host_bytes_recomputed_after_streaming_swap(tel):
    csr = _csr(seed=13, shape=(40, 64))
    rng = np.random.default_rng(3)
    svc = SpMVService()
    svc.register("m", csr, measure_baseline=False,
                 plan=Planner().plan(csr, fmt="sell"), streaming=True)
    x = rng.normal(size=csr.n_cols).astype(np.float32)
    for n_appends in (4, 3):                    # the second delta applies
        old = svc.entries["m"].matrix           # to the first one's result
        res = svc.apply_delta("m", random_delta(rng, csr, n_appends=n_appends,
                                                n_updates=2))
        assert not res.fallback and res.mode != "rebuild"   # incremental
        entry = svc.entries["m"]
        assert entry.matrix is not old and _on_device(entry.matrix)
        assert not _on_device(entry.host_matrix)    # the host form, kept
        assert entry.host_bytes == host_nbytes(entry.matrix) == 0
        np.testing.assert_allclose(np.asarray(svc.spmv("m", x)),
                                   entry.source.todense() @ x,
                                   rtol=1e-5, atol=1e-5)
    assert svc.entries["m"].deltas == 2
    assert tel.snapshot()["counters"][
        "service.host_bytes{key=m,op=spmv}"] == 0


def test_plan_replay_places_the_operator(tel):
    csr = _csr()
    svc = SpMVService()
    plan = svc.register("a", csr, measure_baseline=False).plan
    entry = svc.register("b", csr, measure_baseline=False, plan=plan)
    assert entry.from_plan and _on_device(entry.matrix)
    assert entry.host_bytes == 0
    x = np.ones(csr.n_cols, np.float32)
    np.testing.assert_allclose(np.asarray(svc.spmv("b", x)),
                               csr.todense() @ x, rtol=1e-5, atol=1e-5)


def test_degraded_registration_places_the_operator(tel):
    csr = _csr()
    svc = SpMVService()
    with faults.inject("transform.raise", prob=1.0):
        entry = svc.register("m", csr, measure_baseline=False)
    assert entry.plan.rule == "degraded" and entry.matrix.formats == ("csr",)
    assert _on_device(entry.matrix) and entry.host_bytes == 0
    assert entry.source is not None and host_nbytes(entry.source) > 0
    x = np.ones(csr.n_cols, np.float32)
    np.testing.assert_allclose(np.asarray(svc.spmv("m", x)),
                               csr.todense() @ x, rtol=1e-5, atol=1e-5)


def test_place_span_records_the_placed_bytes(tel):
    csr = _csr()
    svc = SpMVService()
    entry = svc.register("m", csr, measure_baseline=False)
    (reg,) = _spans(tel, "service.register")
    (place,) = _spans(tel, "service.place")
    assert place["parent_id"] == reg["span_id"]
    leaf_bytes = sum(a.nbytes for a in jax.tree_util.tree_leaves(entry.matrix))
    assert place["attrs"]["bytes"] == leaf_bytes > 0
    assert svc.stats()["m"]["device_bytes"] == leaf_bytes


def test_evict_and_reregister_drop_the_device_copy(tel):
    csr = _csr()
    svc = SpMVService()
    first = svc.register("m", csr, measure_baseline=False)
    second = svc.register("m", csr, measure_baseline=False)
    assert first.matrix is None and _on_device(second.matrix)
    svc.evict("m")
    assert second.matrix is None


def test_breaker_reports_its_state_gauge_only(tel):
    csr = _csr()
    svc = SpMVService(breaker_failures=1)
    svc.register("m", csr, measure_baseline=False)
    faults.arm("kernel.raise", prob=1.0)
    svc.spmv("m", np.ones(csr.n_cols, np.float32))
    gauges = tel.snapshot()["gauges"]
    assert gauges["service.breaker_state{fmt=hybrid,key=m,op=spmv}"] == 1.0
    assert not [k for k in gauges if k.startswith("guard.breaker_open")]


# ---------------------------------------------------------------------------
# named device work
# ---------------------------------------------------------------------------
def _hlo(fn, *args):
    """Optimized HLO text of ``fn(*args)`` with its metadata and its
    source-location tables taken out, and the op names it carried."""
    txt = jax.jit(fn).lower(*args).compile().as_text()
    names = re.findall(r'op_name="([^"]*)"', txt)
    txt = re.sub(r",? ?metadata=\{[^}]*\}", "", txt)
    keep, skip = [], False
    for line in txt.splitlines():
        if line in ("FileNames", "FunctionNames", "FileLocations",
                    "StackFrames"):
            skip = True
        elif skip and not line:
            skip = False
        elif not skip:
            keep.append(line)
    return "\n".join(keep).replace("jit__gather_x", "jit_plain"), names


@pytest.mark.parametrize("rhs", [(37,), (37, 5)])
def test_gather_scope_changes_metadata_only(rhs):
    x = jnp.arange(np.prod(rhs), dtype=jnp.float32).reshape(rhs)
    idx = jnp.asarray(np.random.default_rng(0).integers(0, 37, (4, 9)),
                      jnp.int32)

    def plain(x, idx):
        if x.ndim == 1:
            return x[idx]
        return jnp.take(x.T, idx, axis=1, mode="clip")

    scoped, names = _hlo(ops._gather_x, x, idx)
    bare, bare_names = _hlo(plain, x, idx)
    assert scoped == bare
    assert any("/gather_x/" in n for n in names)
    assert not any("gather_x" in n for n in bare_names)
    np.testing.assert_array_equal(ops._gather_x(x, idx), plain(x, idx))


def _containers(seed=5):
    csr = _csr(seed=seed, shape=(64, 64), density=0.2)
    return {"csr": csr, "ell_row": TRANSFORMS_HOST["ell_row"](csr),
            "sell": TRANSFORMS_HOST["sell"](csr)}


@pytest.mark.parametrize("fmt,op", [("ell_row", "spmv"), ("ell_row", "spmm"),
                                    ("csr", "spmv"), ("csr", "spmm")])
def test_every_x_gather_runs_under_gather_x(fmt, op):
    m = _containers()[fmt]
    fn = {("ell_row", "spmv"): ops.spmv_ell, ("ell_row", "spmm"): ops.spmm_ell,
          ("csr", "spmv"): ops.spmv_csr, ("csr", "spmm"): ops.spmm_csr}
    x = jnp.ones((64,) if op == "spmv" else (64, 8), jnp.float32)
    _, names = _hlo(fn[(fmt, op)], m, x)
    assert any("/gather_x/" in n and n.endswith("/gather") for n in names)


@pytest.mark.parametrize("op", ["spmv", "spmm"])
def test_row_scatters_run_under_reassemble(op):
    sell = _containers()["sell"]
    x = jnp.ones((64,) if op == "spmv" else (64, 8), jnp.float32)
    fn = ops.spmv_sell if op == "spmv" else ops.spmm_sell
    _, names = _hlo(fn, sell, x)
    assert any("/reassemble/" in n and "scatter" in n for n in names)
    hyb, _ = build_hybrid(_csr(seed=9, shape=(64, 64), density=0.2),
                          sort_rows=True)
    assert not hyb.identity_perm
    hfn = spmv_hybrid if op == "spmv" else spmm_hybrid
    _, names = _hlo(hfn, hyb, x)
    # the hybrid's SpMV undoes its row permutation by a key-value sort
    undo = "sort" if op == "spmv" else "scatter"
    assert any("/reassemble/" in n and n.endswith(undo) for n in names)
