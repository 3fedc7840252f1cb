"""The copied Table-1 generator builds the matrices the program's own
synthesizer builds, bit for bit, at the published sizes."""
import json
import os

import numpy as np
import pytest

from bench import table1

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", ["torso1", "xenon2"])
def test_same_matrix_as_the_program_synthesizer(name):
    from repro.core.suite import TABLE1, synthesize
    spec = config(name)["matrix"]
    want = synthesize(next(s for s in TABLE1 if s.name == name), scale=1.0,
                      seed=spec["seed"])
    got = table1.synthesize(spec, spec["seed"])
    assert got.nnz == want.nnz == spec["nnz"]
    assert (got.n_rows, got.n_cols) == want.shape
    for f in ("data", "cols", "indptr"):
        w = np.asarray(getattr(want, f))
        g = getattr(got, f)
        assert g.dtype == w.dtype, f
        assert np.array_equal(g, w), f


@pytest.mark.parametrize("name", ["torso1", "xenon2"])
def test_config_states_the_published_statistics(name):
    from repro.core.suite import TABLE1
    spec = config(name)["matrix"]
    pub = next(s for s in TABLE1 if s.name == name)
    assert (spec["table1_no"], spec["n"], spec["nnz"], spec["mu"],
            spec["sigma"], spec["d_mat"]) == (pub.no, pub.n, pub.nnz, pub.mu,
                                              pub.sigma, pub.d_mat)
    lens = table1.row_lengths(spec, spec["seed"])
    assert lens.sum() == spec["nnz"]
    assert lens.mean() == pytest.approx(spec["mu"], rel=0.01)
    assert lens.std() / lens.mean() == pytest.approx(spec["d_mat"], rel=0.05)
