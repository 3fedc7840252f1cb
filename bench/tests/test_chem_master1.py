"""chem_master1 (Table 1 no. 2): the benchmark's generator builds the
program synthesizer's matrix bit for bit, and the configuration states
the published statistics."""
import json
import os

import numpy as np
import pytest

from bench import table1

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def spec():
    with open(os.path.join(BENCH, "configs", "chem_master1.json")) as f:
        return json.load(f)["matrix"]


@pytest.fixture(scope="module")
def published():
    from repro.core.suite import TABLE1
    return next(s for s in TABLE1 if s.name == "chem_master1")


def test_same_matrix_as_the_program_synthesizer(spec, published):
    from repro.core.suite import synthesize
    want = synthesize(published, scale=1.0, seed=spec["seed"])
    got = table1.synthesize(spec, spec["seed"])
    assert got.nnz == want.nnz == spec["nnz"]
    assert (got.n_rows, got.n_cols) == want.shape
    for f in ("data", "cols", "indptr"):
        w = np.asarray(getattr(want, f))
        g = getattr(got, f)
        assert g.dtype == w.dtype, f
        assert np.array_equal(g, w), f


def test_config_states_the_published_statistics(spec, published):
    assert (spec["table1_no"], spec["n"], spec["nnz"], spec["mu"],
            spec["sigma"], spec["d_mat"]) == \
        (published.no, published.n, published.nnz, published.mu,
         published.sigma, published.d_mat)


def test_row_lengths_follow_mu_and_sigma(spec):
    lens = table1.row_lengths(spec, spec["seed"])
    assert lens.sum() == spec["nnz"]
    assert lens.mean() == pytest.approx(spec["mu"], rel=0.01)
    assert lens.std() == pytest.approx(spec["sigma"], rel=0.05)
    # Table 1 publishes D_mat 0.02 for chem_master1, but its own
    # sigma / mu is 0.14 / 4.98 = 0.0281: the generator targets mu and
    # sigma, so the built matrix's variation is sigma / mu, not D_mat.
    assert lens.std() / lens.mean() == pytest.approx(
        spec["sigma"] / spec["mu"], rel=0.05)
