"""Every Table-1 matrix, and one power-law graph, served through
``SpMVService`` on the kernel tier as the benchmark serves them: one
registration per matrix, then ``spmv``, ``spmm`` at B=4 and four
``submit``s with a ``flush``.  Each product is held to a float64 CSR
product computed here, row by row, and every call must be served by the
guard's ``tuned`` rung."""
import numpy as np
import pytest

from repro.core.autotune import TuningDB
from repro.core.kernel_tune import KernelTuner
from repro.core.suite import TABLE1, synthesize, synthesize_power_law
from repro.serve.spmv_service import SpMVService

#: the benchmark's row-relative criterion: |y_i - yref_i| over
#: sum_j |a_ij x_j|
ROW_TOL = 1e-5
SCALE = 0.01
BATCH = 4
MATRICES = [spec.name for spec in TABLE1] + ["power_law"]


def make(name):
    if name == "power_law":
        return synthesize_power_law(n=2048)
    spec = next(s for s in TABLE1 if s.name == name)
    return synthesize(spec, scale=SCALE, seed=0)


def assert_rows_close(csr, x, y):
    """``y`` against ``A @ x`` in float64, each row within ``ROW_TOL`` of
    its absolute sum."""
    nnz = csr.nnz
    rows = np.repeat(np.arange(csr.n_rows),
                     np.diff(np.asarray(csr.indptr).astype(np.int64)))
    prod = (np.asarray(csr.data)[:nnz].astype(np.float64)
            * np.asarray(x, np.float64)[np.asarray(csr.cols)[:nnz]])
    ref = np.bincount(rows, prod, minlength=csr.n_rows)
    scale = np.bincount(rows, np.abs(prod), minlength=csr.n_rows)
    y = np.asarray(y, np.float64)
    assert y.shape == (csr.n_rows,)
    bad = np.abs(y - ref) > ROW_TOL * scale
    assert not bad.any(), f"{int(bad.sum())} rows off, first {np.argmax(bad)}"


def assert_all_tuned(svc, key, op):
    guard = svc.stats()[key]["guard"][op]
    assert guard["fallback_calls"] == 0, guard
    assert guard["served_by"]["tuned"] == guard["calls"] >= 1, guard


@pytest.fixture(scope="module", params=MATRICES)
def served(request):
    name = request.param
    csr = make(name)
    svc = SpMVService(
        tuner=KernelTuner(db=TuningDB(machine="bench", c=1.0, records=[],
                                      d_star={}), max_candidates=0),
        max_batch=BATCH)
    entry = svc.register(name, csr, expected_iterations=1000,
                         measure_baseline=False)
    assert entry.plan.tier == "kernel" and entry.plan.rule != "degraded"
    return svc, name, csr


def test_spmv(served):
    svc, key, csr = served
    x = np.random.default_rng(1).standard_normal(csr.n_cols) \
        .astype(np.float32)
    assert_rows_close(csr, x, svc.spmv(key, x))
    assert_all_tuned(svc, key, "spmv")


def test_spmm(served):
    svc, key, csr = served
    X = np.random.default_rng(2).standard_normal((csr.n_cols, BATCH)) \
        .astype(np.float32)
    Y = np.asarray(svc.spmm(key, X))
    assert Y.shape == (csr.n_rows, BATCH)
    for j in range(BATCH):
        assert_rows_close(csr, X[:, j], Y[:, j])
    assert_all_tuned(svc, key, "spmm")


def test_submit_flush(served):
    svc, key, csr = served
    rng = np.random.default_rng(3)
    xs = [rng.standard_normal(csr.n_cols).astype(np.float32)
          for _ in range(BATCH)]
    futs = [svc.submit(key, x) for x in xs]
    svc.flush(key)
    assert svc.pending_count(key) == 0
    for x, f in zip(xs, futs):
        assert_rows_close(csr, x, f.result())
    assert_all_tuned(svc, key, "spmm")
