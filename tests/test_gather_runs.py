"""The SpMV path's x gather by column runs (``kernels/ops._gather_band``).

A block whose every run of 8 band slots reads at most 8 consecutive
columns gathers one 8-wide slice of x per run; any other block gathers one
element per slot, as before.  On finite x the two give the same y element
for element: the scalar branch below is forced by patching the block's
decision (``band_runs``) to "does not fit" with a traced predicate, so
both sides run the same program around the gather.  Non-finite x reaches
exactly the rows whose real columns touch it, as in a float64 CSR product.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

import repro.obs as obs
from repro.core.formats import ELL
from repro.core.kernel_tune import KernelTuner
from repro.core.plan import Planner
from repro.core.suite import TABLE1, synthesize
from repro.core.transform import (csr_from_rows, host_csr_to_ell,
                                  host_csr_to_sell)
from repro.kernels import ops
from repro.partition.hybrid import build_hybrid
from repro.serve.spmv_service import SpMVService
from repro.stream.delta import DeltaBatch

SPECS = {s.name: s for s in TABLE1}


def _banded(lens, n_cols, seed=0):
    """CSR whose row i holds ``lens[i]`` consecutive columns around i."""
    rng = np.random.default_rng(seed)
    n = len(lens)
    rows_c, rows_v = [], []
    for i, ln in enumerate(lens):
        start = min(max(i * n_cols // n - ln // 2, 0), n_cols - ln)
        rows_c.append(np.arange(start, start + ln, dtype=np.int32))
        rows_v.append(rng.uniform(0.5, 1.5, ln).astype(np.float32))
    return csr_from_rows(rows_c, rows_v, n_cols=n_cols, pad=8)


def _structure(name):
    """Reduced banded structures: the three Table-1 configurations, and
    one whose band width (13) and column count (301) are not multiples of
    8, so that its last run and x's last window are partial."""
    if name == "ragged":
        lens = 13 - np.arange(300) % 5
        return _banded(lens, n_cols=301)
    scale = {"xenon2": 0.004, "torso1": 0.004, "chem_master1": 0.01}[name]
    return synthesize(SPECS[name], scale=scale, seed=1)


def _operator(csr, fmt):
    if fmt == "ell_row":
        return host_csr_to_ell(csr)
    if fmt == "ell_col":
        return host_csr_to_ell(csr, order="col")
    if fmt == "sell":
        return host_csr_to_sell(csr)
    return build_hybrid(csr, strategy="variance")[0]


_IMPL = {"ell_row": ops.spmv_ell, "ell_col": ops.spmv_ell,
         "sell": ops.spmv_sell, "hybrid": ops.spmv_hybrid}


def _served(m, fmt, x):
    """y as the service serves it: jitted, the operator a traced argument."""
    fn = jax.jit(lambda mm, v: _IMPL[fmt](mm, v))
    return np.asarray(fn(jax.device_put(m), jnp.asarray(x)))


def _scalar(m, fmt, x, monkeypatch):
    """y through the scalar branch: every block decides "does not fit"."""
    real = ops.band_runs

    def never(data_t, cols_t, xp=jnp):
        *runs, fits = real(data_t, cols_t, xp)
        return (*runs, fits & (cols_t.min() < 0))

    with monkeypatch.context() as mp:
        mp.setattr(ops, "band_runs", never)
        return _served(m, fmt, x)


def _csr64(csr, x):
    """float64 CSR product: the reference for which rows x reaches."""
    n = csr.n_rows
    ip = np.asarray(csr.indptr)
    rows = np.repeat(np.arange(n), np.diff(ip))
    nnz = int(ip[-1])
    prod = (np.asarray(csr.data)[:nnz].astype(np.float64)
            * np.asarray(x, np.float64)[np.asarray(csr.cols)[:nnz]])
    y = np.zeros(n)
    np.add.at(y, rows, prod)
    return y


def _x(n, seed=3):
    return np.random.default_rng(seed).standard_normal(n).astype(np.float32)


# ---------------------------------------------------------------------------
# the helper
# ---------------------------------------------------------------------------
def test_band_runs_numpy_and_jnp_agree_on_a_partial_last_run():
    csr = _structure("ragged")
    m = host_csr_to_ell(csr)
    data_t, cols_t = np.asarray(m.data).T, np.asarray(m.cols).T
    assert data_t.shape[0] % ops.RUN != 0
    host = ops.band_runs(data_t, cols_t, np)
    dev = jax.jit(ops.band_runs)(jnp.asarray(data_t), jnp.asarray(cols_t))
    for h, d in zip(host, dev):
        np.testing.assert_array_equal(h, np.asarray(d))
    cols, zero, base, fits = host
    assert bool(fits)
    n_runs = -(-data_t.shape[0] // ops.RUN)
    assert cols.shape == zero.shape == (n_runs, ops.RUN, csr.n_rows)
    assert base.shape == (n_runs, csr.n_rows)
    # the last run's padding slots are marked, and every real slot lies
    # in [base, base + RUN)
    assert zero[-1, data_t.shape[0] % ops.RUN:].all()
    off = cols - base[:, None, :]
    assert np.all((off[~zero] >= 0) & (off[~zero] < ops.RUN))


def test_band_runs_fits_only_when_every_run_spans_under_8_columns():
    cols_t = np.array([[0, 5], [7, 6], [3, 12]], np.int32)   # (W=3, n=2)
    data_t = np.ones_like(cols_t, np.float32)
    assert bool(ops.band_runs(data_t, cols_t, np)[3])
    cols_t[2, 1] = 13                     # row 1 now spans 5..13: 9 columns
    assert not bool(ops.band_runs(data_t, cols_t, np)[3])


# ---------------------------------------------------------------------------
# run path == scalar path, element for element, on finite x
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("fmt", ["ell_row", "ell_col", "sell", "hybrid"])
@pytest.mark.parametrize("name", ["xenon2", "torso1", "chem_master1",
                                  "ragged"])
def test_runs_equal_the_scalar_gather(name, fmt, monkeypatch):
    csr = _structure(name)
    m = _operator(csr, fmt)
    assert ops.run_share(m) == 1.0
    x = _x(csr.n_cols)
    got = _served(m, fmt, x)
    np.testing.assert_array_equal(got, _scalar(m, fmt, x, monkeypatch))
    np.testing.assert_allclose(got, _csr64(csr, x), rtol=1e-5, atol=1e-4)


def test_a_heavy_row_wider_than_the_row_tile_takes_runs(monkeypatch):
    """torso1's heavy block in small: a few rows of hundreds of slots, a
    band far wider than the block's row tile."""
    csr = _banded(np.array([403] * 9 + [12] * 7), n_cols=1000)
    heavy = host_csr_to_ell(csr)
    br, _ = ops._ell_geometry(heavy.n_rows, heavy.width, None)
    assert heavy.width > br
    assert ops.run_share(heavy) == 1.0
    x = _x(csr.n_cols, seed=5)
    got = _served(heavy, "ell_row", x)
    np.testing.assert_array_equal(got,
                                  _scalar(heavy, "ell_row", x, monkeypatch))
    np.testing.assert_allclose(got, _csr64(csr, x), rtol=1e-5, atol=1e-4)


# ---------------------------------------------------------------------------
# fallback: the whole block keeps the scalar gather
# ---------------------------------------------------------------------------
def test_scattered_columns_fall_back(monkeypatch):
    spec = SPECS["memplus"]
    assert spec.scatter
    csr = synthesize(spec, scale=0.02, seed=1)
    hyb = build_hybrid(csr, strategy="variance")[0]
    assert ops.run_share(hyb) == 0.0
    x = _x(csr.n_cols)
    got = _served(hyb, "hybrid", x)
    np.testing.assert_array_equal(got, _scalar(hyb, "hybrid", x, monkeypatch))
    np.testing.assert_allclose(got, _csr64(csr, x), rtol=1e-5, atol=1e-4)


def test_one_run_that_does_not_fit_sends_the_whole_block_back(monkeypatch):
    lens = np.full(64, 12)
    csr = _banded(lens, n_cols=200)
    m = host_csr_to_ell(csr)
    cols = np.asarray(m.cols).copy()
    cols[17, 3] = (cols[17, 2] + 40) % 200     # one run now spans 40+ columns
    m = ELL(data=m.data, cols=cols, shape=m.shape, nnz=m.nnz)
    assert ops.run_share(m) == 0.0
    x = _x(200)
    got = _served(m, "ell_row", x)
    np.testing.assert_array_equal(got, _scalar(m, "ell_row", x, monkeypatch))
    np.testing.assert_allclose(got, m.todense() @ x, rtol=1e-5, atol=1e-4)


# ---------------------------------------------------------------------------
# non-finite x reaches exactly the rows the CSR product marks
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("bad", [np.nan, np.inf])
@pytest.mark.parametrize("where", ["beside_a_band", "column_0"])
def test_non_finite_x_reaches_exactly_the_csr_rows(bad, where):
    # rows of 3 to 13 columns padded to 16 slots: padding points at column
    # 0, and every run's 8-wide window reaches past its row's band
    lens = 3 + np.arange(240) % 11
    csr = _banded(lens, n_cols=300)
    m = host_csr_to_ell(csr)
    assert ops.run_share(m) == 1.0
    x = _x(300)
    ip, cols = np.asarray(csr.indptr), np.asarray(csr.cols)
    col = int(cols[ip[100]:ip[101]].max()) + 1 if where == "beside_a_band" \
        else 0
    x[col] = bad
    got = _served(m, "ell_row", x)
    want = _csr64(csr, x)
    touched = np.isin(np.arange(csr.n_rows), np.repeat(
        np.arange(csr.n_rows), np.diff(ip))[cols[:ip[-1]] == col])
    assert touched.any() and not touched.all()
    np.testing.assert_array_equal(~np.isfinite(got), ~np.isfinite(want))
    np.testing.assert_array_equal(np.isnan(got), np.isnan(want))
    np.testing.assert_array_equal(~np.isfinite(got), touched)


# ---------------------------------------------------------------------------
# the service: run_share at placement, streaming swaps
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("name", ["xenon2", "torso1", "chem_master1",
                                  "memplus"])
def test_run_share_at_placement(name):
    spec = SPECS[name]
    csr = synthesize(spec, scale=0.02 if spec.scatter else 0.01, seed=1)
    sink = obs.InMemorySink()
    tel = obs.enable(sink=sink)
    try:
        svc = SpMVService(tuner=KernelTuner(max_candidates=0))
        svc.register(name, csr, measure_baseline=False)
    finally:
        tel.sinks.remove(sink)
        obs.disable()
    want = 0.0 if spec.scatter else 1.0
    assert svc.stats()[name]["run_share"] == want
    place = [r for r in sink.spans() if r["name"] == "service.place"]
    assert [r["attrs"]["run_share"] for r in place] == [want]
    x = _x(csr.n_cols)
    np.testing.assert_allclose(np.asarray(svc.spmv(name, x)), _csr64(csr, x),
                               rtol=1e-5, atol=1e-4)


def test_streaming_delta_serves_the_post_delta_product():
    csr = _banded(np.full(96, 10), n_cols=128)
    svc = SpMVService()
    plan = Planner(tier="kernel").plan(csr, fmt="sell")
    svc.register("m", csr, measure_baseline=False, plan=plan, streaming=True)
    assert svc.stats()["m"]["run_share"] == 1.0
    # one stored entry moves 50 columns off its row's band: its block no
    # longer fits and must serve the new product through the scalar branch
    ip = np.asarray(csr.indptr)
    r, c = 40, int(np.asarray(csr.cols)[ip[40]])
    delta = DeltaBatch(
        n_cols=csr.n_cols,
        update_rows=np.array([r]), update_cols=np.array([(c + 50) % 128]),
        update_vals=np.array([2.5], np.float32),
        delete_rows=np.array([r]), delete_cols=np.array([c]))
    res = svc.apply_delta("m", delta)
    assert not res.fallback
    entry = svc.entries["m"]
    assert svc.stats()["m"]["run_share"] < 1.0
    x = _x(128)
    np.testing.assert_allclose(np.asarray(svc.spmv("m", x)),
                               entry.source.todense() @ x,
                               rtol=1e-5, atol=1e-4)
