"""chem_master1 (Table 1 no. 2) served as the benchmark serves it, at a
small scale on the CPU: registered in ``SpMVService`` with one tuner
candidate, multiplied through the guard ladder, and compared with a
float64 CSR product by the benchmark's row error and limit."""
import numpy as np
import pytest

from repro.core.autotune import TuningDB
from repro.core.kernel_tune import KernelTuner
from repro.core.suite import TABLE1, synthesize
from repro.serve.spmv_service import SpMVService

#: the benchmark's ``correct`` limit on the row error (PERF.md section 2)
ROW_ERR = 2e-5


def row_err(csr, x, y):
    """max_i |y_i - yref_i| / sum_j |a_ij x_j|, with yref in float64."""
    nnz = csr.nnz
    indptr = np.asarray(csr.indptr).astype(np.int64)
    rows = np.repeat(np.arange(csr.n_rows), np.diff(indptr))
    prod = (np.asarray(csr.data)[:nnz].astype(np.float64)
            * x.astype(np.float64)[np.asarray(csr.cols)[:nnz]])
    ref = np.bincount(rows, prod, minlength=csr.n_rows)
    scale = np.bincount(rows, np.abs(prod), minlength=csr.n_rows)
    return float((np.abs(np.asarray(y, np.float64) - ref)
                  / np.maximum(scale, 1e-300)).max())


@pytest.fixture(scope="module")
def served():
    spec = next(s for s in TABLE1 if s.name == "chem_master1")
    csr = synthesize(spec, scale=0.05, seed=0)
    svc = SpMVService(
        tuner=KernelTuner(db=TuningDB(machine="bench", c=1.0, records=[],
                                      d_star={}), max_candidates=0),
        max_batch=32)
    entry = svc.register("chem_master1", csr, batch=1,
                         expected_iterations=1000, measure_baseline=False)
    return svc, entry, csr


def test_plan_is_on_the_kernel_tier_with_ell_blocks(served):
    _, entry, _ = served
    assert entry.plan.tier == "kernel" and entry.plan.rule != "degraded"
    assert "ell_row" in entry.matrix.formats


def test_guarded_spmv_matches_the_float64_reference(served):
    svc, _, csr = served
    rng = np.random.default_rng(2)
    for _ in range(3):
        x = rng.standard_normal(csr.n_cols).astype(np.float32)
        y = svc.spmv("chem_master1", x)
        assert y.shape == (csr.n_rows,)
        assert row_err(csr, x, y) <= ROW_ERR
    guard = svc.stats()["chem_master1"]["guard"]["spmv"]
    assert guard["fallback_calls"] == 0 and guard["failures"] == {}
    assert guard["served_by"]["tuned"] == guard["calls"] >= 3


def test_published_size_matches_table1():
    spec = next(s for s in TABLE1 if s.name == "chem_master1")
    csr = synthesize(spec, scale=1.0, seed=0)
    assert csr.shape == (spec.n, spec.n) and csr.nnz == spec.nnz
    lens = np.diff(np.asarray(csr.indptr))
    assert lens.sum() == spec.nnz
    assert lens.mean() == pytest.approx(spec.mu, rel=0.01)
    assert lens.std() == pytest.approx(spec.sigma, rel=0.05)


def test_submitted_vectors_flush_to_the_float64_reference(served):
    svc, _, csr = served
    rng = np.random.default_rng(3)
    xs = [rng.standard_normal(csr.n_cols).astype(np.float32)
          for _ in range(3)]
    futs = [svc.submit("chem_master1", x) for x in xs]
    assert svc.flush("chem_master1") == 3
    for x, f in zip(xs, futs):
        assert row_err(csr, x, np.asarray(f.result())) <= ROW_ERR
