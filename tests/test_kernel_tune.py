"""Kernel launch-geometry auto-tuner: deterministic search, persistence,
nearest-neighbour fallback, and the per-call tuning hint."""
import numpy as np
import pytest
import jax.numpy as jnp

from repro.core import dispatch
from repro.core.autotune import TuningDB
from repro.core.kernel_tune import (GeometryRecord, KernelTuner, TileGeometry,
                                    candidate_geometries, nearest_geometry)
from repro.core.transform import csr_from_dense, host_csr_to_ell


@pytest.fixture(scope="module")
def problem():
    rng = np.random.default_rng(11)
    dense = ((rng.random((150, 120)) < 0.1) *
             rng.normal(size=(150, 120))).astype(np.float32)
    return dense, csr_from_dense(dense, pad=8)


def fake_timer(prefer_rows=32, prefer_nnz=1024):
    """Deterministic cost model: still executes each candidate once (so the
    sweep validates every launch), but 'times' it by geometry alone."""
    calls = []

    def timer(thunk, g):
        thunk()
        calls.append(g)
        if g is None:
            return 1.0
        cost = 0.5
        cost += abs((g.block_rows or prefer_rows) - prefer_rows) * 1e-3
        cost += abs((g.block_nnz or prefer_nnz) - prefer_nnz) * 1e-6
        return cost

    timer.calls = calls
    return timer


# ---------------------------------------------------------------------------
# candidate grids
# ---------------------------------------------------------------------------
def test_candidates_bounded_and_deduped():
    for fmt in ("ell_row", "csr", "sell"):
        for op in ("spmv", "spmm"):
            cands = candidate_geometries(fmt, op, n_rows=150, width=20,
                                         nnz_pad=1800, batch=16)
            assert 0 < len(cands) <= 40, (fmt, op, len(cands))
            keys = [(g.block_rows, g.block_w, g.block_k, g.block_nnz)
                    for g in cands]
            assert len(keys) == len(set(keys)), (fmt, op)
    # formats without a tunable kernel stay out of the search
    for fmt in ("hybrid", "coo_row", "ccs", "bcsr"):
        assert candidate_geometries(fmt, "spmv") == []


def test_candidates_clamped_to_profile():
    cands = candidate_geometries("ell_row", "spmv", n_rows=20, width=10)
    assert all(g.block_rows <= 24 for g in cands)
    assert all(g.block_w <= 16 for g in cands)


# ---------------------------------------------------------------------------
# deterministic tuning + memoization
# ---------------------------------------------------------------------------
@pytest.mark.slow
@pytest.mark.parametrize("transform,fmt", [
    (lambda m: m, "csr"),
    (host_csr_to_ell, "ell_row"),
], ids=["csr", "ell_row"])
def test_tune_is_deterministic_with_fake_timer(problem, transform, fmt):
    _, m = problem
    obj = transform(m)
    recs = [KernelTuner(timer=fake_timer(), interpret=True).tune(obj)
            for _ in range(2)]
    assert recs[0].fmt == fmt
    assert recs[0].geometry == recs[1].geometry
    assert recs[0].t_best <= recs[0].t_default
    assert recs[0].speedup >= 1.0


@pytest.mark.slow
def test_tune_memoizes_per_profile(problem):
    _, m = problem
    timer = fake_timer()
    tuner = KernelTuner(timer=timer, interpret=True)
    r1 = tuner.tune(m)
    n_timed = len(timer.calls)
    r2 = tuner.tune(m)
    assert r2 is r1 and len(timer.calls) == n_timed  # no re-timing
    assert tuner.best(m) == r1.geometry


def test_csr_winner_carries_exact_slab_bound(problem):
    _, m = problem
    rec = KernelTuner(timer=fake_timer(), interpret=True).tune(m)
    from repro.kernels.csr_spmv import slabs_needed
    g = rec.geometry
    assert g.slabs_per_block == slabs_needed(m.indptr, g.block_rows,
                                             g.block_nnz)


@pytest.mark.slow
def test_force_retune_replaces_record_in_place(problem):
    """force=True supersedes the memoized record instead of appending a
    duplicate — a re-tuned db keeps one record per key across save/load,
    and nearest_geometry can never resurrect the stale loser."""
    _, m = problem
    db = TuningDB(machine="t", c=1.0, records=[], d_star={})
    tuner = KernelTuner(db=db, timer=fake_timer(prefer_rows=128),
                        interpret=True)
    r1 = tuner.tune(m)
    assert r1.geometry.block_rows == 128
    # the machine "changed its mind": re-tune now prefers a different tile
    # (the whole-matrix row tile: 150 rows, 8-aligned)
    tuner._timer = fake_timer(prefer_rows=152)
    r2 = tuner.tune(m, force=True)
    assert r2.geometry.block_rows == 152
    assert len(db.geometries) == 1, "re-tune must not accumulate duplicates"
    db2 = TuningDB.from_json(db.to_json())
    assert len(db2.geometries) == 1
    assert db2.geometries[0].geometry == r2.geometry
    # the NN fallback sees only the fresh winner
    assert (nearest_geometry(db2.geometries, "csr", "spmv",
                             d_mat=r2.d_mat).block_rows == 152)


# ---------------------------------------------------------------------------
# per-bucket SELL geometry
# ---------------------------------------------------------------------------
def width_loving_timer():
    """Prefers the widest band tile a launch offers: buckets of different
    widths then *must* record different winners (their clamped candidate
    grids top out at different block_w)."""
    def timer(thunk, g):
        thunk()
        if g is None:
            return 1.0
        return 0.5 - (g.block_w or 0) * 1e-3
    return timer


def test_legacy_duplicate_records_healed_on_load():
    """A db persisted by the old append-only force=True path carries
    stale duplicates; seeding a tuner from it must keep only the last
    (freshest) record per key, through the db's own list."""
    mk = lambda rows: GeometryRecord(
        fmt="csr", op="spmv", batch=1, n=100, nnz=1000, d_mat=1.0,
        geometry=TileGeometry(block_rows=rows), t_best=1.0, t_default=2.0,
        sig=7)
    db = TuningDB(machine="t", c=1.0, records=[], d_star={},
                  geometries=[mk(64), mk(256)])   # stale loser first
    tuner = KernelTuner(db=db)
    assert len(db.geometries) == 1
    assert db.geometries[0].geometry.block_rows == 256
    assert tuner.best(fmt="csr", d_mat=1.0).block_rows == 256
    assert (nearest_geometry(db.geometries, "csr", "spmv",
                             d_mat=1.0).block_rows == 256)


@pytest.mark.slow
def test_sell_buckets_record_distinct_geometries():
    """Two buckets of different widths each get their own candidate sweep
    and record distinct winning geometries, composed into the aggregate's
    per-bucket table and persisted through the TuningDB."""
    from repro.core.transform import csr_from_dense, host_csr_to_sell
    from repro.kernels import ops
    rng = np.random.default_rng(5)
    # 32 long rows (~60 nnz) + 64 short rows (~10 nnz): two SELL buckets
    dense = np.zeros((96, 128), np.float32)
    for r in range(32):
        cols = rng.choice(128, size=60, replace=False)
        dense[r, cols] = rng.normal(size=60)
    for r in range(32, 96):
        cols = rng.choice(128, size=10, replace=False)
        dense[r, cols] = rng.normal(size=10)
    m = csr_from_dense(dense, pad=8)
    sell = host_csr_to_sell(m, slice_rows=32, width_quantum=8)
    assert len(sell.buckets) >= 2
    db = TuningDB(machine="t", c=1.0, records=[], d_star={})
    tuner = KernelTuner(db=db, timer=width_loving_timer(), interpret=True)
    rec = tuner.tune(sell)

    comps = {g.bucket_w: g for g in db.geometries
             if g.fmt == "sell" and g.bucket_w is not None}
    assert set(comps) == set(sell.widths)
    winners = {w: comps[w].geometry for w in comps}
    assert len(set(winners.values())) >= 2, \
        "buckets of different widths must be able to win different tiles"
    # each bucket's winner saturates its own band, not a broadcast one
    for w, g in winners.items():
        assert g.block_w == w, (w, g)

    # the aggregate's geometry carries the composed table...
    table = dict(rec.geometry.buckets)
    assert table == winners
    # ...the per-bucket component records stay out of the NN fallback...
    nn = nearest_geometry(db.geometries, "sell", "spmv", d_mat=rec.d_mat)
    assert nn is not None and nn.buckets is not None
    # ...and tune -> persist -> reload -> serve is bit-exact
    db2 = TuningDB.from_json(db.to_json())
    g2 = KernelTuner(db=db2).best(sell)
    assert g2 == rec.geometry
    x = rng.normal(size=128).astype(np.float32)
    got = ops.spmv_sell(sell, jnp.asarray(x), interpret=True, tuning=g2)
    np.testing.assert_allclose(np.asarray(got), dense @ x,
                               rtol=2e-4, atol=2e-4)


def test_sell_tune_memoizes_per_bucket():
    """A second tune() answers every bucket from the memo (no re-timing)."""
    from repro.core.transform import csr_from_dense, host_csr_to_sell
    rng = np.random.default_rng(6)
    dense = ((rng.random((64, 50)) < 0.2) *
             rng.normal(size=(64, 50))).astype(np.float32)
    sell = host_csr_to_sell(csr_from_dense(dense, pad=8), slice_rows=16)
    timer = fake_timer()
    tuner = KernelTuner(timer=timer, interpret=True)
    r1 = tuner.tune(sell)
    n_timed = len(timer.calls)
    r2 = tuner.tune(sell)
    assert r2 is r1 and len(timer.calls) == n_timed


# ---------------------------------------------------------------------------
# TuningDB persistence + nearest-neighbour fallback
# ---------------------------------------------------------------------------
def test_tuningdb_geometry_roundtrip(problem):
    _, m = problem
    db = TuningDB(machine="t", c=1.0, records=[], d_star={})
    tuner = KernelTuner(db=db, timer=fake_timer(), interpret=True)
    rec = tuner.tune(m)
    assert db.geometries, "tuner must record into the shared db"
    db2 = TuningDB.from_json(db.to_json())
    assert db2.geometries[0].geometry == rec.geometry
    assert db2.geometries[0].d_mat == rec.d_mat
    # a fresh tuner seeded from the reloaded db answers from memo
    tuner2 = KernelTuner(db=db2)
    assert tuner2.best(m) == rec.geometry


def test_tuningdb_json_backcompat():
    """Old dbs (no geometries key) still load."""
    db = TuningDB(machine="t", c=1.0, records=[], d_star={})
    import json
    obj = json.loads(db.to_json())
    obj.pop("geometries")
    db2 = TuningDB.from_json(json.dumps(obj))
    assert db2.geometries == []


def test_nearest_geometry_is_dmat_keyed():
    mk = lambda d, rows: GeometryRecord(
        fmt="ell_row", op="spmv", batch=1, n=100, nnz=1000, d_mat=d,
        geometry=TileGeometry(block_rows=rows, slabs_per_block=7),
        t_best=1.0, t_default=2.0)
    recs = [mk(0.05, 8), mk(3.0, 256)]
    low = nearest_geometry(recs, "ell_row", "spmv", d_mat=0.08)
    high = nearest_geometry(recs, "ell_row", "spmv", d_mat=2.0)
    assert low.block_rows == 8 and high.block_rows == 256
    # the data-dependent coverage bound never travels to another matrix
    assert low.slabs_per_block is None
    assert nearest_geometry(recs, "coo_row", "spmv", d_mat=1.0) is None


def test_nearest_geometry_prefers_batch_match():
    mk = lambda b, rows: GeometryRecord(
        fmt="ell_row", op="spmm", batch=b, n=100, nnz=1000, d_mat=1.0,
        geometry=TileGeometry(block_rows=rows), t_best=1.0, t_default=2.0)
    recs = [mk(8, 8), mk(128, 256)]
    assert nearest_geometry(recs, "ell_row", "spmm", d_mat=1.0,
                            batch=128).block_rows == 256


# ---------------------------------------------------------------------------
# the per-call tuning hint through dispatch
# ---------------------------------------------------------------------------
def test_dispatch_tuning_hint_matches_reference(problem):
    dense, m = problem
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=120).astype(np.float32))
    g = TileGeometry(block_rows=64, block_nnz=1024)
    got = dispatch.spmv(m, x, tier="kernel", tuning=g)
    np.testing.assert_allclose(np.asarray(got), dense @ np.asarray(x),
                               rtol=2e-4, atol=2e-4)
    # reference tier ignores the hint instead of crashing
    ref = dispatch.spmv(m, x, tier="reference", tuning=g)
    np.testing.assert_allclose(np.asarray(ref), dense @ np.asarray(x),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.slow
def test_offline_phase_records_geometries(problem):
    _, m = problem
    from repro.core.autotune import offline_phase
    from repro.kernels import ops
    tuner = KernelTuner(timer=fake_timer(), interpret=True)
    db = offline_phase([("m0", m)], formats=("ell_row",), iters=1,
                       spmv_impls=ops.KERNEL_SPMV_IMPLS, tuner=tuner,
                       machine="fake")
    assert {g.fmt for g in db.geometries} == {"csr", "ell_row"}
    assert db.best_geometry("ell_row", d_mat=1.0) is not None
