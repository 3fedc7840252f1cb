"""Batch-parallel SpMM across the stack: the (format, op) dispatch
registry, per-format SpMM parity against the dense oracle, the SELL
empty-bucket regression, the batch-aware auto-tuner, and the micro-batched
serving queue."""
import numpy as np
import pytest
import jax.numpy as jnp

from repro.core import dispatch, spmm, spmv
from repro.core.autotune import (FormatMeasurement, MachineModel,
                                 OfflineRecord, TuningDB,
                                 decide_generalized, offline_phase)
from repro.core.formats import FORMAT_NAMES, BucketedELL, MatrixStats
from repro.core.transform import (TRANSFORMS_HOST, csr_from_dense,
                                  host_csr_to_sell)
from repro.serve import SpMVService

# every registered format (FORMAT_NAMES is now derived from the registry,
# so the literal here is a deliberate second witness, not a copy)
ALL_FORMATS = ("csr", "coo_row", "coo_col", "ccs", "ell_row", "ell_col",
               "sell", "bcsr", "hybrid")


def random_dense(rng, n_rows, n_cols, density):
    d = (rng.random((n_rows, n_cols)) < density).astype(np.float32)
    return d * rng.normal(1.0, 1.0, size=d.shape).astype(np.float32)


@pytest.fixture(scope="module")
def rng():
    return np.random.default_rng(23)


@pytest.fixture(scope="module")
def problem(rng):
    dense = random_dense(rng, 96, 72, 0.12)
    return dense, csr_from_dense(dense, pad=8)


# ---------------------------------------------------------------------------
# dispatch registry: the single source of truth
# ---------------------------------------------------------------------------
def test_every_format_registered_for_both_ops():
    for f in ALL_FORMATS:
        for op in dispatch.OPS:
            assert dispatch.has_impl(f, op, tier="reference"), (f, op)
    assert set(FORMAT_NAMES) <= set(dispatch.registered_formats("spmm"))


def test_format_of_roundtrip(problem):
    _, m = problem
    for f in ALL_FORMATS:
        assert dispatch.format_of(TRANSFORMS_HOST[f](m)) == f


def test_kernel_tables_are_registry_views():
    from repro.kernels import ops
    from repro.kernels.ops import KERNEL_SPMM_IMPLS, KERNEL_SPMV_IMPLS
    assert KERNEL_SPMV_IMPLS == dispatch.impl_table("spmv", "kernel")
    assert KERNEL_SPMM_IMPLS == dispatch.impl_table("spmm", "kernel")
    # a format without a kernel-tier entry falls back to the reference tier
    assert not dispatch.has_impl("dense", "spmm", tier="kernel")
    dispatch.register_impl("dense", "spmm", lambda m, x: m @ x)
    try:
        assert dispatch.get_impl("dense", "spmm", tier="kernel") \
            is dispatch.get_impl("dense", "spmm", tier="reference")
    finally:
        dispatch._IMPLS.pop(("dense", "spmm", "reference"))
    # csr is served by its native kernel; ccs and bcsr have none and
    # resolve to the reference tier
    assert dispatch.get_impl("csr", "spmv", tier="kernel") is ops.spmv_csr
    for fmt in ("ccs", "bcsr"):
        assert dispatch.get_impl(fmt, "spmm", tier="kernel") \
            is dispatch.get_impl(fmt, "spmm", tier="reference")


def test_unknown_format_and_op_raise(problem):
    _, m = problem
    with pytest.raises(TypeError):
        dispatch.format_of(object())
    with pytest.raises(KeyError):
        dispatch.register_impl("csr", "spmv_t", lambda m, x: x)
    with pytest.raises(ValueError):
        dispatch.spmm(m, jnp.ones((72,)))


# ---------------------------------------------------------------------------
# SpMM parity: every registered format vs the dense A @ X oracle
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("fmt", ALL_FORMATS)
@pytest.mark.parametrize("batch", [1, 3, 128])
def test_spmm_matches_dense_oracle(problem, rng, fmt, batch):
    dense, m = problem
    obj = TRANSFORMS_HOST[fmt](m)
    X = jnp.asarray(rng.normal(size=(m.n_cols, batch)).astype(np.float32))
    Y = spmm(obj, X)
    assert Y.shape == (m.n_rows, batch)
    np.testing.assert_allclose(np.asarray(Y), dense @ np.asarray(X),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("fmt", ALL_FORMATS)
def test_spmm_b1_consistent_with_spmv(problem, rng, fmt):
    dense, m = problem
    obj = TRANSFORMS_HOST[fmt](m)
    x = jnp.asarray(rng.normal(size=m.n_cols).astype(np.float32))
    y = spmv(obj, x)
    Y = spmm(obj, x[:, None])
    np.testing.assert_allclose(np.asarray(Y[:, 0]), np.asarray(y),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("fmt", ["csr", "coo_row", "ell_row", "ell_col",
                                 "sell", "hybrid"])
def test_spmm_kernel_tier_matches_dense(problem, rng, fmt):
    dense, m = problem
    obj = TRANSFORMS_HOST[fmt](m)
    X = jnp.asarray(rng.normal(size=(m.n_cols, 3)).astype(np.float32))
    Y = dispatch.spmm(obj, X, tier="kernel")
    np.testing.assert_allclose(np.asarray(Y), dense @ np.asarray(X),
                               rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# SELL empty-bucket regression (all-zero matrix)
# ---------------------------------------------------------------------------
def test_sell_empty_buckets_return_zeros():
    from repro.kernels import ops
    x = jnp.ones((9,), jnp.float32)
    empty = BucketedELL(perm=np.arange(12, dtype=np.int32), buckets=(),
                        row_offsets=(), shape=(12, 9), nnz=0)
    for fn in (ops.spmv_sell, spmv):
        y = fn(empty, x)
        assert y.shape == (12,) and y.dtype == x.dtype
        assert not np.any(np.asarray(y))
    for fn in (ops.spmm_sell, spmm):
        Y = fn(empty, jnp.ones((9, 4), jnp.float32))
        assert Y.shape == (12, 4) and not np.any(np.asarray(Y))


def test_sell_all_zero_matrix_via_transform():
    from repro.kernels import ops
    z = csr_from_dense(np.zeros((12, 9), np.float32), pad=8)
    sell = host_csr_to_sell(z)
    x = jnp.ones((9,), jnp.float32)
    for got in (ops.spmv_sell(sell, x), spmv(sell, x)):
        assert got.shape == (12,) and got.dtype == x.dtype
        assert not np.any(np.asarray(got))


# ---------------------------------------------------------------------------
# batch-aware auto-tuner
# ---------------------------------------------------------------------------
def test_machine_model_batch_scales_gathers():
    st = MatrixStats(n=1000, nnz=5000, mu=5, sigma=1, d_mat=0.2,
                     max_row=8, min_row=3)
    mm = MachineModel()
    for fmt in ("csr", "coo_row", "ell_row", "sell", "hybrid"):
        t1, t8 = mm.t_spmv(fmt, st, batch=1), mm.t_spmv(fmt, st, batch=8)
        # matrix stream amortizes: dearer per call, cheaper per product
        assert t1 < t8 < 8 * t1, fmt


def test_decide_generalized_batch_amortizes_transform():
    # transform worth 30 CSR-SpMVs, speedup 2x: k=20 single-vector calls
    # cannot amortize it, but 20 calls x 16 RHS can (k*B rule)
    st = MatrixStats(n=1000, nnz=5000, mu=5, sigma=1, d_mat=0.2,
                     max_row=8, min_row=3)
    rec = OfflineRecord(name="a", n=1000, nnz=5000, mu=5, sigma=1,
                        d_mat=0.2, t_crs=1.0,
                        formats={"ell_row": FormatMeasurement(
                            t_spmv=0.5, t_trans=30.0, sp=2.0, tt=30.0,
                            r=2.0 / 30, mem_ratio=1.5)})
    db = TuningDB(machine="t", c=1.0, records=[rec],
                  d_star={"ell_row": 0.5})
    assert decide_generalized(db, st, 20, formats=["ell_row"]).fmt == "csr"
    assert decide_generalized(db, st, 20, formats=["ell_row"],
                              batch=16).fmt == "ell_row"


def test_predict_rescales_tt_across_batches():
    # records measured at batch=4, queried at batch=8: tt is per-4-wide
    # call, so the per-8-wide-call overhead is tt * 4/8 — not tt / 8
    meas = FormatMeasurement(t_spmv=0.5, t_trans=30.0, sp=2.0, tt=7.5,
                             r=2.0 / 7.5, mem_ratio=1.5)
    rec = OfflineRecord(name="a", n=1000, nnz=5000, mu=5, sigma=1,
                        d_mat=0.2, t_crs=1.0, batch=4,
                        formats={"ell_row": meas})
    db = TuningDB(machine="t", c=1.0, records=[rec],
                  d_star={"ell_row": 0.5})
    assert db.predict("ell_row", 0.2, batch=4)["tt"] == pytest.approx(7.5)
    pred = db.predict("ell_row", 0.2, batch=8)
    assert not pred["batch_matched"]
    assert pred["tt"] == pytest.approx(7.5 * 4 / 8)
    # legacy call without a batch axis is untouched
    assert db.predict("ell_row", 0.2)["tt"] == pytest.approx(7.5)


def test_offline_phase_with_batch(rng):
    dense = random_dense(rng, 64, 64, 0.1)
    m = csr_from_dense(dense, pad=8)
    db = offline_phase([("r", m)], formats=("ell_row",), iters=1, batch=3)
    rec = db.records[0]
    assert rec.batch == 3
    meas = rec.formats["ell_row"]
    assert meas.t_spmv > 0 and np.isfinite(meas.r)
    # records round-trip with their batch axis
    assert TuningDB.from_json(db.to_json()).records[0].batch == 3
    # batch-matched prediction is preferred over the global fallback
    assert db.predict("ell_row", rec.d_mat, batch=3)["batch_matched"]
    assert not db.predict("ell_row", rec.d_mat, batch=64)["batch_matched"]


# ---------------------------------------------------------------------------
# serving: direct SpMM + the micro-batching queue
# ---------------------------------------------------------------------------
def test_service_spmm_and_microbatch_queue(rng):
    dense = random_dense(rng, 100, 80, 0.1)
    m = csr_from_dense(dense, pad=8)
    svc = SpMVService(max_batch=4)
    svc.register("m", m, expected_iterations=200, batch=8)

    X = rng.normal(size=(80, 5)).astype(np.float32)
    Y = svc.spmm("m", jnp.asarray(X))
    np.testing.assert_allclose(np.asarray(Y), dense @ X, rtol=1e-4,
                               atol=1e-4)

    # 6 submits with max_batch=4: one auto-flush, then a ragged tail of 2
    futs = [svc.submit("m", jnp.asarray(X[:, i % 5])) for i in range(6)]
    assert svc.pending_count("m") == 2
    assert svc.flush("m") == 2
    for i, f in enumerate(futs):
        np.testing.assert_allclose(np.asarray(f.result()),
                                   dense @ X[:, i % 5],
                                   rtol=1e-4, atol=1e-4)
    st = svc.stats()["m"]
    assert st["n_spmm_calls"] == 3 and st["n_spmm_cols"] == 11
    assert st["pending"] == 0 and st["builds"] == 1


def test_service_flush_all_and_empty(rng):
    dense = random_dense(rng, 40, 30, 0.2)
    m = csr_from_dense(dense, pad=8)
    svc = SpMVService(max_batch=8)
    svc.register("a", m, measure_baseline=False)
    svc.register("b", m, measure_baseline=False)
    assert svc.flush() == 0
    fa = svc.submit("a", jnp.ones((30,), jnp.float32))
    fb = svc.submit("b", jnp.ones((30,), jnp.float32))
    assert svc.flush() == 2
    np.testing.assert_allclose(np.asarray(fa.result()),
                               dense @ np.ones(30, np.float32),
                               rtol=1e-4, atol=1e-4)
    assert fb.done()


def test_service_submit_rejects_bad_shape_and_flush_fails_whole_panel(rng):
    dense = random_dense(rng, 40, 30, 0.2)
    m = csr_from_dense(dense, pad=8)
    # guard=False: with the degradation ladder on, a failing SpMM is
    # served by a fallback rung instead of raising (tests/test_guard.py);
    # this test pins the raw failure-propagation contract underneath it
    svc = SpMVService(max_batch=8, guard=False)
    svc.register("m", m, measure_baseline=False)
    with pytest.raises(ValueError):
        svc.submit("m", jnp.ones((31,), jnp.float32))   # wrong n_cols
    # a failing SpMM must resolve every queued future with the exception,
    # never strand one
    fut = svc.submit("m", jnp.ones((30,), jnp.float32))
    svc.entries["m"].spmm_fn = _boom
    # a healthy second matrix must still be served by the same flush()
    dense2 = random_dense(rng, 40, 30, 0.2)
    svc.register("ok", csr_from_dense(dense2, pad=8),
                 measure_baseline=False)
    x2 = np.arange(30, dtype=np.float32)
    fut2 = svc.submit("ok", jnp.asarray(x2))
    with pytest.raises(RuntimeError):
        svc.flush()
    with pytest.raises(RuntimeError):
        fut.result(timeout=0)
    np.testing.assert_allclose(np.asarray(fut2.result(timeout=0)),
                               dense2 @ x2, rtol=1e-4, atol=1e-4)


def _boom(m, x):
    raise RuntimeError("kernel failure")


def test_service_reregister_drains_pending_first(rng):
    dense = random_dense(rng, 40, 30, 0.2)
    m = csr_from_dense(dense, pad=8)
    svc = SpMVService(max_batch=8)
    svc.register("m", m, measure_baseline=False)
    x = np.arange(30, dtype=np.float32)
    fut = svc.submit("m", jnp.asarray(x))
    svc.register("m", m, measure_baseline=False)   # drains, then rebuilds
    np.testing.assert_allclose(np.asarray(fut.result(timeout=0)), dense @ x,
                               rtol=1e-4, atol=1e-4)
    assert svc.stats()["m"]["builds"] == 2


def test_service_evict_releases_and_reregister_counts(rng):
    dense = random_dense(rng, 50, 50, 0.1)
    m = csr_from_dense(dense, pad=8)
    svc = SpMVService()
    e1 = svc.register("m", m, measure_baseline=False)
    svc.spmv("m", jnp.ones((50,), jnp.float32))
    assert svc.stats()["m"]["compiled"] >= 1
    e2 = svc.register("m", m, measure_baseline=False)   # replaces e1
    assert e2 is not e1 and svc.stats()["m"]["builds"] == 2
    # the stale entry's dispatchers are released
    with pytest.raises(RuntimeError):
        e1.fn(e1.matrix, jnp.ones((50,), jnp.float32))
    fut = svc.submit("m", jnp.ones((50,), jnp.float32))
    svc.evict("m")
    assert "m" not in svc.entries
    with pytest.raises(KeyError):
        fut.result(timeout=0)


def test_service_deadline_flush_and_poll(rng):
    from repro.obs import FakeClock

    dense = random_dense(rng, 40, 30, 0.2)
    m = csr_from_dense(dense, pad=8)
    # deadline ages are read off the service's injected clock, so the whole
    # policy is tested deterministically — no sleeps, no scheduler jitter
    clk = FakeClock()
    svc = SpMVService(max_batch=64, deadline_ms=1.0, clock=clk)
    svc.register("m", m, measure_baseline=False)
    x = np.arange(30, dtype=np.float32)
    f1 = svc.submit("m", jnp.asarray(x))
    assert not f1.done()                      # queue far below max_batch
    clk.advance(0.005)                        # 5 ms > the 1 ms deadline
    # the next submit sees the oldest future past its deadline and flushes
    f2 = svc.submit("m", jnp.asarray(x))
    assert f1.done() and f2.done()
    np.testing.assert_allclose(np.asarray(f1.result(timeout=0)), dense @ x,
                               rtol=1e-4, atol=1e-4)
    # poll() sweeps overdue queues without new traffic
    f3 = svc.submit("m", jnp.asarray(x))
    assert svc.poll() == 0                    # not yet overdue
    clk.advance(0.0015)                       # now past the deadline
    assert svc.poll() == 1 and f3.done()
    # no deadline configured -> poll is a no-op and nothing auto-flushes
    clk2 = FakeClock()
    svc2 = SpMVService(max_batch=64, clock=clk2)
    svc2.register("m", m, measure_baseline=False)
    f4 = svc2.submit("m", jnp.asarray(x))
    clk2.advance(0.005)
    svc2.submit("m", jnp.asarray(x))
    assert svc2.poll() == 0 and not f4.done()
    assert svc2.flush("m") == 2


def test_service_register_with_tuner_serves_tuned_kernels(rng):
    from repro.core.kernel_tune import KernelTuner

    def fake_timer(thunk, g):
        thunk()
        return 1.0 if g is None else 0.5

    dense = random_dense(rng, 96, 64, 0.15)
    m = csr_from_dense(dense, pad=8)
    svc = SpMVService(tuner=KernelTuner(timer=fake_timer, interpret=True),
                      max_batch=4)
    svc.register("m", m, measure_baseline=False)
    st = svc.stats()["m"]
    assert st["tuned"].get("spmv"), st  # a geometry won per block format
    x = rng.normal(size=64).astype(np.float32)
    np.testing.assert_allclose(np.asarray(svc.spmv("m", jnp.asarray(x))),
                               dense @ x, rtol=1e-4, atol=1e-4)
    X = rng.normal(size=(64, 4)).astype(np.float32)
    np.testing.assert_allclose(np.asarray(svc.spmm("m", jnp.asarray(X))),
                               dense @ X, rtol=1e-4, atol=1e-4)


def test_service_sell_blocks_carry_per_bucket_geometry(rng):
    """A sell block registered through the service is tuned per bucket:
    the baked geometry carries a width-keyed table, and queries serve
    through it (the serve-side half of the per-bucket SELL story)."""
    from repro.core.autotune import MachineModel
    from repro.core.kernel_tune import KernelTuner
    from repro.core.policy import MemoryPolicy

    def width_timer(thunk, g):
        thunk()
        return 1.0 if g is None else 0.5 - (g.block_w or 0) * 1e-3

    # skewed rows so the sell transform produces a real bucket structure
    dense = np.zeros((128, 96), np.float32)
    for r in range(16):
        dense[r, rng.choice(96, 50, replace=False)] = rng.normal(size=50)
    for r in range(16, 128):
        dense[r, rng.choice(96, 6, replace=False)] = rng.normal(size=6)
    m = csr_from_dense(dense, pad=8)
    svc = SpMVService(tuner=KernelTuner(timer=width_timer, interpret=True),
                      strategy="fixed",
                      # steer the block decision onto sell: csr priced out,
                      # sell's padded footprint allowed
                      model=MachineModel(segment_penalty=1e4),
                      policy=MemoryPolicy(budget_ratio=10.0))
    svc.register("m", m, measure_baseline=False, formats=("sell",))
    st = svc.stats()["m"]
    assert st["formats"] == {"sell": 1}, st["formats"]
    for op in ("spmv", "spmm"):
        tuned = st["tuned"][op].get("sell")
        assert tuned is not None and tuned.get("buckets"), (op, tuned)
    x = rng.normal(size=96).astype(np.float32)
    np.testing.assert_allclose(np.asarray(svc.spmv("m", jnp.asarray(x))),
                               dense @ x, rtol=1e-4, atol=1e-4)
    X = rng.normal(size=(96, 4)).astype(np.float32)
    np.testing.assert_allclose(np.asarray(svc.spmm("m", jnp.asarray(X))),
                               dense @ X, rtol=1e-4, atol=1e-4)
