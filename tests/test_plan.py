"""The unified ExecutionPlan API: planning, persistence, binding, and the
rewired consumers (AutoTunedSpMV shim, SpMVService plan registration)."""
import json
import warnings

import numpy as np
import pytest

import repro
from repro.core import dispatch
from repro.core.autotune import AutoTunedSpMV, TuningDB, offline_phase
from repro.core.formats import MatrixStats
from repro.core.kernel_tune import KernelTuner
from repro.core.plan import (SCHEMA_VERSION, BlockPlan, ExecutionPlan,
                             PlanError, PlanFingerprint, PlanSchemaError,
                             Planner, TransformRecipe)
from repro.core.suite import paper_suite
from repro.core.transform import csr_from_dense
from repro.serve import SpMVService

BATCHES = (1, 3, 128)


def random_dense(rng, n_rows, n_cols, density):
    d = (rng.random((n_rows, n_cols)) < density).astype(np.float32)
    return d * rng.normal(1.0, 1.0, size=d.shape).astype(np.float32)


@pytest.fixture(scope="module")
def rng():
    return np.random.default_rng(7)


@pytest.fixture(scope="module")
def problem(rng):
    dense = random_dense(rng, 180, 140, 0.08)
    # a heavy tail so variance partitioning produces >1 block regime
    dense[:3, :] = rng.normal(size=(3, 140)).astype(np.float32)
    return dense, csr_from_dense(dense, pad=8)


@pytest.fixture(scope="module")
def tiny_db():
    return offline_phase(paper_suite(scale=0.004, skip_ell_overflow=True),
                         formats=("ell_row", "sell", "coo_row"), iters=1,
                         machine="test")


def fake_timer(prefer_rows=32):
    calls = []

    def timer(thunk, g):
        thunk()
        calls.append(g)
        if g is None:
            return 1.0
        return 0.5 + abs((g.block_rows or prefer_rows) - prefer_rows) * 1e-3

    timer.calls = calls
    return timer


def assert_parity(P, dense, rng):
    x = rng.normal(size=dense.shape[1]).astype(np.float32)
    np.testing.assert_allclose(np.asarray(P @ x), dense @ x,
                               rtol=2e-4, atol=2e-4)
    for b in BATCHES[1:]:
        X = rng.normal(size=(dense.shape[1], b)).astype(np.float32)
        np.testing.assert_allclose(np.asarray(P @ X), dense @ X,
                                   rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# the package-level API surface
# ---------------------------------------------------------------------------
def test_top_level_reexports():
    from repro import ExecutionPlan as EP, Planner as PL  # noqa: F401
    assert "Planner" in repro.__all__
    assert "ExecutionPlan" in repro.__all__
    assert repro.Planner is Planner
    assert repro.ExecutionPlan is ExecutionPlan
    # the facade module agrees with the core definitions
    assert repro.api.Planner is Planner


def test_deprecated_entry_points_still_importable():
    from repro.core import (AutoTunedSpMV, decide_cost_model,  # noqa: F401
                            decide_generalized, decide_paper)
    from repro.api import decide_paper as dp
    assert dp is not None


# ---------------------------------------------------------------------------
# leaf plans: decide + persist + bind
# ---------------------------------------------------------------------------
def test_leaf_plan_roundtrip_and_parity(problem, rng, tmp_path):
    dense, csr = problem
    plan = Planner().plan(csr, batch=3)
    assert plan.rule == "cost_model"
    assert plan.fingerprint is not None and plan.fingerprint.matches(csr)
    path = tmp_path / "plan.json"
    plan.save(str(path))
    loaded = ExecutionPlan.load(str(path))
    assert loaded.fmt == plan.fmt
    assert loaded.transform.name == plan.transform.name
    assert loaded.transform.params == plan.transform.params
    assert loaded.batch == plan.batch
    P = loaded.bind(csr)
    assert P.fingerprint_matched
    assert_parity(P, dense, rng)


def test_plan_with_db_rules(problem, rng, tiny_db):
    dense, csr = problem
    for rule in ("paper", "generalized"):
        plan = Planner(db=tiny_db).plan(csr, rule=rule)
        assert plan.rule == rule
        assert plan.machine == "test"
        assert_parity(plan.bind(csr), dense, rng)
    # identical decision after a JSON round trip in a fresh binder
    plan = Planner(db=tiny_db).plan(csr, rule="generalized")
    again = ExecutionPlan.from_json(plan.to_json())
    assert again.fmt == plan.fmt
    assert again.d_star == plan.d_star or (
        np.isnan(again.d_star) and np.isnan(plan.d_star))


def test_geometry_roundtrip_including_sell_buckets(problem, rng, tmp_path):
    dense, csr = problem
    tuner = KernelTuner(timer=fake_timer(), interpret=True)
    plan = Planner(tuner=tuner).plan(csr, fmt="sell", batch=3)
    assert plan.tier == "kernel"
    assert set(plan.geometry) == {"spmv", "spmm"}
    assert plan.geometry["spmv"].buckets, "per-bucket SELL table missing"
    path = tmp_path / "sell_plan.json"
    plan.save(str(path))
    loaded = ExecutionPlan.load(str(path))
    assert loaded.geometry["spmv"] == plan.geometry["spmv"]
    assert loaded.geometry["spmm"] == plan.geometry["spmm"]
    P = loaded.bind(csr, interpret=True)
    assert P.tiers["spmv"] == "kernel"
    assert_parity(P, dense, rng)


def test_fixed_format_plans_all_parity(problem, rng):
    dense, csr = problem
    for fmt in ("csr", "ccs", "coo_row", "coo_col", "ell_row", "ell_col",
                "sell", "bcsr"):
        P = ExecutionPlan.from_json(
            Planner().plan(csr, fmt=fmt).to_json()).bind(csr)
        assert P.fmt == fmt and P.plan.rule == "fixed"
        assert_parity(P, dense, rng)


# ---------------------------------------------------------------------------
# hybrid plans: per-block sub-plans
# ---------------------------------------------------------------------------
def test_hybrid_plan_roundtrip_with_subplans(problem, rng, tmp_path):
    dense, csr = problem
    plan = Planner().plan(csr, partition="variance", max_blocks=4,
                          min_rows=16)
    assert plan.is_hybrid and plan.blocks
    assert all(isinstance(bp, BlockPlan) for bp in plan.blocks)
    assert plan.blocks[-1].rows[1] == csr.n_rows
    path = tmp_path / "hybrid.json"
    plan.save(str(path))
    loaded = ExecutionPlan.load(str(path))
    assert loaded.block_formats() == plan.block_formats()
    H = loaded.bind(csr)
    assert H.fingerprint_matched
    # replay keeps the recorded per-block formats exactly
    assert H.matrix.formats == tuple(plan.block_formats())
    assert_parity(H, dense, rng)


def test_build_hybrid_decisions_carry_subplans(problem):
    _, csr = problem
    from repro.partition import build_hybrid
    _, report = build_hybrid(csr, strategy="variance", max_blocks=4,
                             min_rows=16)
    for d in report.decisions:
        assert d.plan is not None
        assert d.plan.fmt == d.fmt
        assert d.plan.fingerprint is not None


# ---------------------------------------------------------------------------
# persistence failure modes
# ---------------------------------------------------------------------------
def test_corrupted_json_rejected():
    with pytest.raises(PlanError, match="not valid JSON"):
        ExecutionPlan.from_json("{this is not json")


def test_old_schema_version_rejected(problem):
    _, csr = problem
    d = Planner().plan(csr).to_dict()
    d["schema_version"] = SCHEMA_VERSION + 1
    with pytest.raises(PlanSchemaError, match="schema_version"):
        ExecutionPlan.from_dict(d)
    d.pop("schema_version")
    with pytest.raises(PlanSchemaError):
        ExecutionPlan.from_dict(d)


def test_plan_json_is_strict_rfc(problem):
    """Hybrid/cost-model plans carry NaN d_star internally but the saved
    artifact must stay RFC-compliant JSON (NaN → null) so non-Python
    consumers can read it."""
    _, csr = problem
    plan = Planner().plan(csr, partition="variance", max_blocks=3,
                          min_rows=16)

    def no_constants(c):
        raise AssertionError(f"non-RFC JSON constant {c!r} in plan")

    json.loads(plan.to_json(), parse_constant=no_constants)
    back = ExecutionPlan.from_json(plan.to_json())
    assert np.isnan(back.d_star)


def test_hybrid_plan_formats_restriction(problem, rng):
    """A formats= restriction must reach the per-block decisions of a
    hybrid plan (and never allow a nested hybrid block)."""
    dense, csr = problem
    plan = Planner().plan(csr, partition="variance",
                          formats=("sell", "hybrid"), max_blocks=4,
                          min_rows=16)
    assert set(plan.block_formats()) <= {"sell", "csr"}
    assert_parity(plan.bind(csr), dense, rng)


def test_malformed_payload_rejected(problem):
    _, csr = problem
    d = Planner().plan(csr).to_dict()
    d.pop("fmt")
    with pytest.raises(PlanError, match="malformed"):
        ExecutionPlan.from_dict(d)


# ---------------------------------------------------------------------------
# cross-matrix reuse
# ---------------------------------------------------------------------------
def test_cross_matrix_bind_strips_slab_bound(problem, rng):
    dense, csr = problem
    tuner = KernelTuner(timer=fake_timer(), interpret=True)
    plan = Planner(tuner=tuner).plan(csr, fmt="csr")
    assert plan.geometry["spmv"].slabs_per_block is not None
    other_dense = random_dense(rng, 90, 140, 0.12)
    other = csr_from_dense(other_dense, pad=8)
    P = plan.bind(other, interpret=True)
    assert not P.fingerprint_matched
    # the bound actually used was re-derived for the *new* matrix, never
    # transplanted from the tuned one
    g = P.tunings["spmv"]
    assert g.slabs_per_block is not None
    x = rng.normal(size=140).astype(np.float32)
    np.testing.assert_allclose(np.asarray(P @ x), other_dense @ x,
                               rtol=2e-4, atol=2e-4)


def test_cross_matrix_bind_uses_nearest_geometry_from_db(problem, rng):
    """Binding to a fingerprint-mismatched matrix with a db at hand falls
    back to the D_mat-keyed nearest recorded winner."""
    dense, csr = problem
    db = TuningDB(machine="x", c=1.0, records=[], d_star={})
    tuner = KernelTuner(db=db, timer=fake_timer(prefer_rows=8),
                        interpret=True)
    plan = Planner(tuner=tuner, db=db).plan(csr, fmt="ell_row")
    tuned_g = plan.geometry["spmv"]
    other_dense = random_dense(rng, 96, 140, 0.1)
    other = csr_from_dense(other_dense, pad=8)
    P = plan.bind(other, db=db, interpret=True)
    assert not P.fingerprint_matched
    expect = db.best_geometry("ell_row", MatrixStats.of(other).d_mat,
                              op="spmv", batch=plan.batch)
    assert P.tunings["spmv"] == expect
    assert expect == tuned_g.without_slab_bound()
    x = rng.normal(size=140).astype(np.float32)
    np.testing.assert_allclose(np.asarray(P @ x), other_dense @ x,
                               rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# the deprecated AutoTunedSpMV shim
# ---------------------------------------------------------------------------
def test_autotuned_spmv_warns_and_matches_reference(problem, rng, tiny_db):
    dense, csr = problem
    with pytest.warns(DeprecationWarning, match="Planner"):
        op = AutoTunedSpMV(csr, db=tiny_db, rule="paper")
    # unchanged numerics vs the dense oracle (reference tier by default)
    x = rng.normal(size=140).astype(np.float32)
    np.testing.assert_allclose(np.asarray(op(x)), dense @ x,
                               rtol=2e-4, atol=2e-4)
    # the shim now routes through a plan...
    assert isinstance(op.plan, ExecutionPlan)
    assert op.decision.fmt == op.plan.fmt
    # ...and serves SpMM panels through the same __call__
    X = rng.normal(size=(140, 3)).astype(np.float32)
    np.testing.assert_allclose(np.asarray(op(X)), dense @ X,
                               rtol=2e-4, atol=2e-4)


def test_autotuned_spmv_picks_up_tuned_geometry(problem, rng):
    dense, csr = problem
    db = TuningDB(machine="g", c=1.0, records=[], d_star={})
    tuner = KernelTuner(db=db, timer=fake_timer(), interpret=True)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        op = AutoTunedSpMV(csr, db=None, tuner=tuner)
    assert op.plan.tier == "kernel"
    assert "spmv" in op.plan.geometry
    x = rng.normal(size=140).astype(np.float32)
    np.testing.assert_allclose(np.asarray(op(x)), dense @ x,
                               rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# serving: register accepts / returns plans
# ---------------------------------------------------------------------------
def test_service_register_returns_plan_and_replays_it(problem, rng):
    dense, csr = problem
    timer = fake_timer()
    db = TuningDB(machine="svc", c=1.0, records=[], d_star={})
    svc = SpMVService(tuner=KernelTuner(db=db, timer=timer, interpret=True),
                      max_batch=4)
    entry = svc.register("a", csr, measure_baseline=False)
    assert entry.plan is not None and entry.plan.is_hybrid
    assert not entry.from_plan
    n_timed = len(timer.calls)
    assert n_timed > 0

    # save → load → register-with-plan: zero additional tuner timings
    plan = ExecutionPlan.from_json(entry.plan.to_json())
    entry2 = svc.register("b", csr, plan=plan, measure_baseline=False)
    assert entry2.from_plan
    assert len(timer.calls) == n_timed, "register(plan=...) must skip tuning"
    assert entry2.matrix.formats == entry.matrix.formats
    x = rng.normal(size=140).astype(np.float32)
    np.testing.assert_allclose(np.asarray(svc.spmv("b", x)), dense @ x,
                               rtol=2e-4, atol=2e-4)
    X = rng.normal(size=(140, 4)).astype(np.float32)
    np.testing.assert_allclose(np.asarray(svc.spmm("b", X)), dense @ X,
                               rtol=2e-4, atol=2e-4)
    st = svc.stats()
    assert st["b"]["plan"]["from_plan"] is True
    assert st["a"]["plan"]["from_plan"] is False
    assert st["b"]["plan"]["schema_version"] == SCHEMA_VERSION


def test_service_mismatched_plan_falls_back(problem, rng):
    dense, csr = problem
    svc = SpMVService()
    entry = svc.register("a", csr, measure_baseline=False)
    other_dense = random_dense(rng, 77, 140, 0.15)
    other = csr_from_dense(other_dense, pad=8)
    entry2 = svc.register("o", other, plan=entry.plan,
                          measure_baseline=False)
    assert not entry2.from_plan        # rebuilt + re-decided
    x = rng.normal(size=140).astype(np.float32)
    np.testing.assert_allclose(np.asarray(svc.spmv("o", x)),
                               other_dense @ x, rtol=2e-4, atol=2e-4)


def test_service_plan_roundtrips_through_disk(problem, rng, tmp_path):
    """The acceptance-criteria path: tune, save, reload 'in a fresh
    process' (fresh service + deserialized plan), bind, serve — identical
    format decisions and dense-oracle parity for SpMV and SpMM."""
    dense, csr = problem
    svc = SpMVService()
    entry = svc.register("m", csr, measure_baseline=False)
    p = tmp_path / "svc_plan.json"
    entry.plan.save(str(p))

    fresh = SpMVService()
    loaded = ExecutionPlan.load(str(p))
    entry2 = fresh.register("m", csr, plan=loaded, measure_baseline=False)
    assert entry2.from_plan
    assert entry2.matrix.formats == entry.matrix.formats
    x = rng.normal(size=140).astype(np.float32)
    np.testing.assert_allclose(np.asarray(fresh.spmv("m", x)), dense @ x,
                               rtol=2e-4, atol=2e-4)
    for b in BATCHES[1:]:
        X = rng.normal(size=(140, b)).astype(np.float32)
        np.testing.assert_allclose(np.asarray(fresh.spmm("m", X)),
                                   dense @ X, rtol=2e-4, atol=2e-4)


def test_hybrid_bind_honors_impls_override(problem, rng):
    """The AutoTunedSpMV compat path: a per-format impls override must be
    used even when the plan resolved to the hybrid container."""
    dense, csr = problem
    called = []

    def my_hybrid(m, x):
        called.append(True)
        from repro.partition import spmv_hybrid
        return spmv_hybrid(m, x)

    plan = Planner().plan(csr, partition="variance", max_blocks=3,
                          min_rows=16)
    P = plan.bind(csr, impls={"hybrid": my_hybrid})
    x = rng.normal(size=140).astype(np.float32)
    y = P @ x
    assert called, "hybrid impls override was ignored"
    np.testing.assert_allclose(np.asarray(y), dense @ x,
                               rtol=2e-4, atol=2e-4)


def test_plan_replay_with_tuning_less_user_impl(problem, rng):
    """register(plan=) must not partial tuning= onto a user-supplied impl
    that does not accept it (bind_tunings signature guard)."""
    dense, csr = problem

    def plain_csr_impl(m, v):      # no tuning kwarg
        from repro.core.spmv import spmv
        return spmv(m, v)

    def ft(thunk, g):
        thunk()
        return 1.0 if g is None else 0.6

    db = TuningDB(machine="m", c=1.0, records=[], d_star={})
    tuned = SpMVService(tuner=KernelTuner(db=db, timer=ft, interpret=True),
                        max_batch=4)
    plan = tuned.register("k", csr, measure_baseline=False).plan
    svc = SpMVService(impls={"csr": plain_csr_impl}, max_batch=4)
    entry = svc.register("k", csr,
                         plan=ExecutionPlan.from_json(plan.to_json()),
                         measure_baseline=False)
    assert entry.from_plan
    x = rng.normal(size=140).astype(np.float32)
    np.testing.assert_allclose(np.asarray(svc.spmv("k", x)), dense @ x,
                               rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# planner edge cases
# ---------------------------------------------------------------------------
def test_planner_paper_rule_requires_db(problem):
    _, csr = problem
    with pytest.raises(PlanError, match="TuningDB"):
        Planner(rule="paper").plan(csr)


def test_planner_unknown_rule_and_tier(problem):
    _, csr = problem
    with pytest.raises(PlanError, match="unknown rule"):
        Planner(rule="vibes").plan(csr)
    with pytest.raises(PlanError, match="unknown tier"):
        Planner(tier="gpu").plan(csr)


def test_recipe_params_round_trip():
    r = TransformRecipe("sell", {"slice_rows": 64, "width_quantum": 8})
    r2 = TransformRecipe.from_dict(json.loads(json.dumps(r.to_dict())))
    assert r2.name == r.name and r2.params == r.params


def test_fingerprint_requires_structure(problem, rng):
    _, csr = problem
    fp = PlanFingerprint.of(csr)
    assert fp.matches(csr)
    other = csr_from_dense(random_dense(rng, 60, 140, 0.2), pad=8)
    assert not fp.matches(other)


def test_kernel_tier_plan_via_dispatch_formats(problem):
    """Every kernel-tier registered base format can be planned (fixed
    fmt) without error — the plan layer stays in sync with the dispatch
    registry."""
    _, csr = problem
    fmts = [f for f in dispatch.registered_formats("spmv", tier="kernel")
            if f != "hybrid"]
    assert {"csr", "ell_row", "ell_col", "sell"} <= set(fmts)
    for f in fmts:
        plan = Planner().plan(csr, fmt=f)
        assert plan.transform.name == f
