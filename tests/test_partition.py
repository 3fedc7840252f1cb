"""Partitioned hybrid-format SpMV: strategies, per-block decisions,
HybridMatrix correctness vs the dense/CSR reference, format integration,
and the serve path."""
import dataclasses

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import repro.obs as obs
from repro.core import (MatrixStats, csr_from_dense, memory_bytes,
                        offline_phase, spmv)
from repro.core.formats import FORMAT_NAMES
from repro.core.plan import Planner
from repro.core.policy import MemoryPolicy
from repro.core.suite import TABLE1, synthesize, synthesize_power_law
from repro.core.transform import TRANSFORMS_HOST
from repro.partition import (BLOCK_FORMATS, PARTITIONERS, build_hybrid,
                             choose_block_format, host_csr_to_hybrid,
                             partition_balanced_nnz, partition_fixed,
                             partition_variance, reassembly, slice_csr,
                             spmm_hybrid, spmv_hybrid, take_rows_csr)
from repro.serve import SpMVService
from repro.stream.delta import DeltaBatch


def random_dense(rng, n_rows, n_cols, density):
    d = (rng.random((n_rows, n_cols)) < density).astype(np.float32)
    return d * rng.normal(1.0, 1.0, size=d.shape).astype(np.float32)


def power_law_csr(n=2048, alpha=1.8, seed=0):
    return synthesize_power_law(n=n, alpha=alpha, seed=seed,
                                random_values=True)


@pytest.fixture(scope="module")
def rng():
    return np.random.default_rng(11)


# ---------------------------------------------------------------------------
# partitioning strategies
# ---------------------------------------------------------------------------
def _check_boundaries(b, n):
    assert b[0] == 0 and b[-1] == n
    assert np.all(np.diff(b) > 0)


@pytest.mark.parametrize("name", sorted(PARTITIONERS))
def test_strategy_boundaries_valid(rng, name):
    for n in (1, 7, 64, 1000):
        lens = rng.integers(1, 50, size=n)
        _check_boundaries(PARTITIONERS[name](lens), n)


def test_fixed_blocks():
    b = partition_fixed(np.ones(100), block_rows=32)
    np.testing.assert_array_equal(b, [0, 32, 64, 96, 100])


def test_balanced_nnz_equalizes_work(rng):
    # one huge row among many small: balanced split isolates it
    lens = np.full(1000, 5, dtype=np.int64)
    lens[500] = 5000
    b = partition_balanced_nnz(lens, n_blocks=4)
    per_block = [lens[s:e].sum() for s, e in zip(b[:-1], b[1:])]
    # no block exceeds ~a half of total (perfect balance impossible with
    # one dominant row, but the split must not lump everything together)
    assert len(b) >= 3
    assert max(per_block) <= 0.75 * lens.sum()


def test_variance_split_isolates_tail():
    # sorted lengths: 100 heavy rows then 900 uniform rows
    lens = np.concatenate([np.full(100, 500), np.full(900, 5)]).astype(np.int64)
    b = partition_variance(lens, max_blocks=8, min_rows=50)
    _check_boundaries(b, 1000)
    # some cut must separate heavy from light within min_rows slack
    assert any(abs(int(c) - 100) <= 50 for c in b[1:-1])
    # within-block variance collapses vs whole-matrix variance
    sse = sum(float(np.var(lens[s:e]) * (e - s)) for s, e in zip(b[:-1], b[1:]))
    assert sse < 0.1 * float(np.var(lens) * 1000)


# ---------------------------------------------------------------------------
# CSR slicing
# ---------------------------------------------------------------------------
def test_slice_and_take_rows(rng):
    dense = random_dense(rng, 60, 40, 0.2)
    m = csr_from_dense(dense, pad=8)
    sub = slice_csr(m, 10, 35)
    np.testing.assert_allclose(sub.todense(), dense[10:35], rtol=1e-6)
    rows = np.array([3, 1, 59, 17])
    sub2 = take_rows_csr(m, rows)
    np.testing.assert_allclose(sub2.todense(), dense[rows], rtol=1e-6)


# ---------------------------------------------------------------------------
# hybrid correctness vs dense
# ---------------------------------------------------------------------------
STRATEGY_KW = [("fixed", {"block_rows": 64}),
               ("balanced_nnz", {"n_blocks": 4}),
               ("variance", {"max_blocks": 6, "min_rows": 16})]


@pytest.mark.parametrize("strategy,kw", STRATEGY_KW,
                         ids=[s for s, _ in STRATEGY_KW])
def test_hybrid_spmv_matches_dense(rng, strategy, kw):
    dense = random_dense(rng, 300, 200, 0.08)
    m = csr_from_dense(dense, pad=8)
    hyb, rep = build_hybrid(m, strategy=strategy, **kw)
    assert rep.n_blocks == hyb.n_blocks == len(hyb.formats)
    np.testing.assert_allclose(hyb.todense(), dense, rtol=1e-5, atol=1e-6)
    x = jnp.asarray(rng.normal(size=200).astype(np.float32))
    y = jax.jit(spmv)(hyb, x)   # generic dispatch, jitted
    np.testing.assert_allclose(np.asarray(y), dense @ np.asarray(x),
                               rtol=1e-4, atol=1e-4)
    X = jnp.asarray(rng.normal(size=(200, 5)).astype(np.float32))
    Y = spmm_hybrid(hyb, X)
    np.testing.assert_allclose(np.asarray(Y), dense @ np.asarray(X),
                               rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("mname", ["memplus", "chem_master1", "torso1",
                                   "epb2"])
def test_hybrid_matches_csr_on_suite(rng, mname):
    spec = [s for s in TABLE1 if s.name == mname][0]
    m = synthesize(spec, scale=0.02)
    hyb, _ = build_hybrid(m, strategy="variance", max_blocks=8, min_rows=32)
    x = jnp.asarray(rng.normal(size=m.n_cols).astype(np.float32))
    want = np.asarray(spmv(m, x))
    got = np.asarray(spmv_hybrid(hyb, x))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5 *
                               max(1.0, float(np.abs(want).max())))


def test_hybrid_kernel_path_matches(rng):
    from repro.kernels import ops
    m = power_law_csr(n=512, alpha=1.8, seed=3)
    hyb, _ = build_hybrid(m, strategy="variance", max_blocks=6, min_rows=32)
    x = jnp.asarray(rng.normal(size=m.n_cols).astype(np.float32))
    want = np.asarray(spmv(m, x))
    got = np.asarray(ops.spmv_hybrid(hyb, x, interpret=True))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4 *
                               max(1.0, float(np.abs(want).max())))


# ---------------------------------------------------------------------------
# reassembly: the key-value sort on perm equals the scatter it replaced
# ---------------------------------------------------------------------------
def _scatter_form(m, y):
    """The reassembly ``spmv_hybrid`` ran before the sort."""
    return jnp.zeros(m.n_rows, y.dtype).at[jnp.asarray(m.perm)].set(y)


def _bits(a):
    return np.asarray(a, np.float32).view(np.uint32)


def _sorted_hybrid(case, perm, seed=0):
    """A length-sorted hybrid: 301 rows (a multiple of neither 8 nor 128)
    in several blocks or in one, or 203 rows of which every 7th is empty;
    ``perm`` "random" swaps its stable length-sort for a random one."""
    rng = np.random.default_rng(seed)
    n = 203 if case == "empty_rows" else 301
    dense = random_dense(rng, n, n, 0.05)
    if case == "empty_rows":
        dense[::7] = 0.0
    kw = ({"max_blocks": 1} if case == "single"
          else {"max_blocks": 6, "min_rows": 16})
    hyb, _ = build_hybrid(csr_from_dense(dense, pad=8), strategy="variance",
                          **kw)
    assert not hyb.identity_perm
    assert (hyb.n_blocks == 1) == (case == "single")
    if perm == "random":
        hyb = dataclasses.replace(
            hyb, perm=rng.permutation(n).astype(np.int32))
    return hyb


def _tier(name):
    if name == "kernel":
        from repro.kernels import ops
        return lambda m, x: ops.spmv_hybrid(m, x, interpret=True)
    return spmv_hybrid


@pytest.mark.parametrize("tier", ["reference", "kernel"])
@pytest.mark.parametrize("perm", ["length_sort", "random"])
@pytest.mark.parametrize("case", ["ragged", "single", "empty_rows"])
def test_sort_reassembly_equals_scatter_bitwise(case, perm, tier):
    hyb = _sorted_hybrid(case, perm)
    assert reassembly(hyb) == "sort"
    x = np.random.default_rng(1).normal(size=hyb.n_cols).astype(np.float32)
    x[[3, 50, 120]] = [np.nan, np.inf, -np.inf]
    x = jnp.asarray(x)
    fn = _tier(tier)
    concat = fn(dataclasses.replace(hyb, identity_perm=True), x)
    got = fn(hyb, x)
    np.testing.assert_array_equal(_bits(got), _bits(_scatter_form(hyb, concat)))


@pytest.mark.parametrize("perm", ["length_sort", "random"])
@pytest.mark.parametrize("case", ["ragged", "single", "empty_rows"])
def test_sort_reassembly_moves_special_values_in_place(case, perm):
    """Block outputs holding NaN, +-inf and -0.0 land, unchanged, at row
    ``perm[i]``: the sort moves values and computes nothing."""
    hyb = _sorted_hybrid(case, perm, seed=2)
    y = np.random.default_rng(3).normal(size=hyb.n_rows).astype(np.float32)
    y[::5] = -0.0
    y[1::11], y[2::13], y[3::17] = np.nan, np.inf, -np.inf
    offs = {id(b): o for b, o in zip(hyb.blocks, hyb.row_offsets)}

    def block_out(b, v):
        return v[offs[id(b)]:offs[id(b)] + b.n_rows]

    impls = {f: block_out for f in BLOCK_FORMATS + ("csr",)}
    got = spmv_hybrid(hyb, jnp.asarray(y), impls=impls)
    np.testing.assert_array_equal(_bits(got)[np.asarray(hyb.perm)], _bits(y))
    np.testing.assert_array_equal(
        _bits(got), _bits(_scatter_form(hyb, jnp.asarray(y))))


def test_identity_perm_hybrid_bypasses_reassembly():
    dense = random_dense(np.random.default_rng(4), 301, 301, 0.05)
    hyb, _ = build_hybrid(csr_from_dense(dense, pad=8), strategy="variance",
                          sort_rows=False, max_blocks=6, min_rows=16)
    assert hyb.identity_perm and hyb.n_blocks > 1
    assert reassembly(hyb) == "none"
    x = jnp.asarray(np.random.default_rng(5).normal(size=301), jnp.float32)
    txt = jax.jit(spmv_hybrid).lower(hyb, x).as_text(debug_info=True)
    assert "reassemble" not in txt and "stablehlo.sort" not in txt
    outs = [spmv(b, x) for b in hyb.blocks]
    np.testing.assert_array_equal(_bits(spmv_hybrid(hyb, x)),
                                  _bits(jnp.concatenate(outs)))
    sorted_hyb = _sorted_hybrid("ragged", "length_sort")
    txt = jax.jit(spmv_hybrid).lower(sorted_hyb, x).as_text(debug_info=True)
    assert "reassemble" in txt and "stablehlo.sort" in txt


# ---------------------------------------------------------------------------
# acceptance: skewed matrix -> >= 2 distinct block formats, bounded memory
# ---------------------------------------------------------------------------
def test_skewed_matrix_gets_multiple_formats():
    m = power_law_csr(n=2048, alpha=1.8, seed=0)
    hyb, rep = build_hybrid(m, strategy="variance", max_blocks=16,
                            min_rows=64)
    assert len(set(hyb.formats)) >= 2, rep.format_counts()
    # per-block budget filtering keeps the whole thing near CSR footprint
    assert memory_bytes(hyb) <= MemoryPolicy().budget_ratio * \
        memory_bytes(m) * 1.1
    # transformation-time accounting is populated
    assert rep.t_transform > 0 and all(d.t_transform >= 0
                                       for d in rep.decisions)
    assert sum(d.nnz for d in rep.decisions) == m.nnz


def test_memory_policy_filters_block_candidates():
    # a block with one huge row among short ones: ELL must be filtered out
    skewed = MatrixStats(n=1000, nnz=6000, mu=6.0, sigma=80.0, d_mat=13.3,
                         max_row=900, min_row=1)
    fmt = choose_block_format(skewed, policy=MemoryPolicy(budget_ratio=2.0))
    assert fmt not in ("ell_row", "ell_col")
    uniform = MatrixStats(n=1000, nnz=6000, mu=6.0, sigma=0.1, d_mat=0.017,
                          max_row=7, min_row=5)
    fmt_u = choose_block_format(uniform, policy=MemoryPolicy(budget_ratio=2.0))
    assert fmt_u in ("ell_row", "ell_col", "sell")
    # an absolute hard cap below any candidate forces the CSR fallback
    fmt_h = choose_block_format(
        uniform, policy=MemoryPolicy(budget_ratio=2.0, hard_bytes=1))
    assert fmt_h == "csr"


# ---------------------------------------------------------------------------
# first-class format integration
# ---------------------------------------------------------------------------
def test_hybrid_registered_everywhere():
    from repro.kernels.ops import KERNEL_SPMV_IMPLS
    assert "hybrid" in FORMAT_NAMES
    assert "hybrid" in TRANSFORMS_HOST
    assert "hybrid" in KERNEL_SPMV_IMPLS
    assert MemoryPolicy().estimate_bytes(
        "hybrid", MatrixStats(n=10, nnz=50, mu=5, sigma=1, d_mat=0.2,
                              max_row=7, min_row=3)) > 0


def test_offline_phase_measures_hybrid(rng):
    dense = random_dense(rng, 128, 128, 0.1)
    m = csr_from_dense(dense, pad=8)
    db = offline_phase([("rand", m)], formats=("hybrid", "ell_row"),
                       iters=1, machine="test")
    meas = db.records[0].formats["hybrid"]
    assert meas.t_spmv > 0 and meas.t_trans > 0
    assert np.isfinite(meas.r)
    assert "hybrid" in db.d_star


def test_host_csr_to_hybrid_via_transforms(rng):
    dense = random_dense(rng, 100, 80, 0.1)
    m = csr_from_dense(dense, pad=8)
    hyb = TRANSFORMS_HOST["hybrid"](m)
    np.testing.assert_allclose(hyb.todense(), dense, rtol=1e-5, atol=1e-6)
    assert host_csr_to_hybrid(m).shape == m.shape


# ---------------------------------------------------------------------------
# serve path
# ---------------------------------------------------------------------------
def test_spmv_service(rng):
    dense = random_dense(rng, 200, 200, 0.05)
    m = csr_from_dense(dense, pad=8)
    svc = SpMVService()
    entry = svc.register("m0", m, expected_iterations=500)
    assert entry.matrix.n_blocks >= 1
    x = rng.normal(size=200).astype(np.float32)
    for _ in range(3):
        y = svc.spmv("m0", jnp.asarray(x))
    np.testing.assert_allclose(np.asarray(y), dense @ x, rtol=1e-4,
                               atol=1e-4)
    st = svc.stats()["m0"]
    assert st["n_calls"] == 3 and st["t_build_s"] > 0
    assert sum(st["formats"].values()) == st["n_blocks"]
    svc.evict("m0")
    assert "m0" not in svc.entries


def test_reassembly_reported_at_placement(rng):
    """``reassembly`` in ``stats()`` and on every ``service.place`` span:
    "sort" for the default length-sorted hybrid, "none" for a plan that
    keeps row order, also after a streaming swap, and for any operator
    that is not a hybrid."""
    dense = random_dense(rng, 200, 200, 0.05)
    m = csr_from_dense(dense, pad=8)
    sink = obs.InMemorySink()
    tel = obs.enable(sink=sink)
    try:
        svc = SpMVService()
        svc.register("sorted", m, measure_baseline=False)
        svc.register("kept", m, measure_baseline=False, streaming=True,
                     plan=Planner(tier="kernel").plan(m, fmt="sell"))
        svc.apply_delta("kept", DeltaBatch(
            n_cols=200, update_rows=np.array([5]),
            update_cols=np.array([7]),
            update_vals=np.array([2.5], np.float32)))
    finally:
        tel.sinks.remove(sink)
        obs.disable()
    st = svc.stats()
    assert (st["sorted"]["reassembly"], st["kept"]["reassembly"]) == \
        ("sort", "none")
    assert [r["attrs"]["reassembly"] for r in sink.spans()
            if r["name"] == "service.place"] == ["sort", "none", "none"]
    assert reassembly(m) == "none"
    x = rng.normal(size=200).astype(np.float32)
    np.testing.assert_allclose(np.asarray(svc.spmv("sorted", jnp.asarray(x))),
                               dense @ x, rtol=1e-4, atol=1e-4)
