"""What decides ``correct``: the control fails the limit, and a run whose
timed path is broken underneath reads ``correct`` false.

Small matrices with the configurations' own row models, on the CPU with
the Pallas kernels interpreted.  The harness's look for a chip is
skipped: ``harness.run`` is called directly.
"""
import copy

import numpy as np
import pytest

from bench import harness, table1
from bench.reference import Control, HostReference

#: the share of each configuration's published size used here
SCALE = {"xenon2": 0.004, "torso1": 0.004}


def small_cell(config: str, traffic: str) -> harness.Cell:
    cell = harness.Cell.load(f"{config}.{traffic}")
    cell = copy.deepcopy(cell)
    m = cell.config["matrix"]
    m["n"] = int(m["n"] * SCALE[config])
    m["nnz"] = int(m["nnz"] * SCALE[config])
    return cell


@pytest.mark.parametrize("config", ["xenon2", "torso1"])
@pytest.mark.parametrize("seed", [5, 6, 2**31 + 7])
def test_control_fails_the_limit(config, seed):
    cell = small_cell(config, "solve")
    host = table1.synthesize(cell.config["matrix"],
                             cell.config["matrix"]["seed"])
    ref, control = HostReference(host), Control(host)
    x = np.random.default_rng(seed).standard_normal(host.n_cols).astype(
        np.float32)
    limit = cell.config["limits"]["row_err"]
    assert ref.row_err(control.product(x), x) > 3 * limit
    # the float32 product of the same operands reads far below it
    y32 = np.zeros(host.n_rows, np.float32)
    rows = np.repeat(np.arange(host.n_rows), np.diff(host.indptr))
    np.add.at(y32, rows, host.data[:host.nnz] * x[host.cols[:host.nnz]])
    assert ref.row_err(y32, x) < limit / 3


def alter_one_row(op, y):
    return y.at[0].multiply(1.001)


def drop_half_the_batch(op, y):
    return y.at[:, y.shape[1] // 2:].set(0.0) if op == "spmm" else y


def run_broken(monkeypatch, cell, fault):
    from repro.serve.spmv_service import SpMVService
    if fault is not None:
        orig = SpMVService._run

        def broken(self, entry, op, x):
            return fault(op, orig(self, entry, op, x))
        monkeypatch.setattr(SpMVService, "_run", broken)
    return harness.run(cell, 2**31 + 3, 0.5, False, 0.0)


@pytest.mark.parametrize("traffic,fault", [
    ("solve", None),
    ("solve", alter_one_row),
    ("batch32", None),
    ("batch32", alter_one_row),
    ("batch32", drop_half_the_batch),
])
def test_broken_timed_path_is_not_correct(monkeypatch, traffic, fault):
    out = run_broken(monkeypatch, small_cell("xenon2", traffic), fault)
    assert out["attempted"] > 0
    assert out["correct"] is (fault is None), out["checks"]
    assert list(out["checks"]) == ["row_err", "compared", "fallbacks",
                                   "raised", "degraded"]


def test_a_request_that_raises_fails_the_run(monkeypatch):
    from repro.serve.spmv_service import SpMVService
    orig = SpMVService.spmv
    calls = {"n": 0}

    def flaky(self, key, x):
        calls["n"] += 1
        if calls["n"] == 8:
            raise RuntimeError("lost")
        return orig(self, key, x)
    monkeypatch.setattr(SpMVService, "spmv", flaky)
    out = harness.run(small_cell("xenon2", "solve"), 9, 0.5, False, 0.0)
    assert out["correct"] is False
    assert out["failed"] >= 1 and out["checks"]["raised"]["value"] == 1
