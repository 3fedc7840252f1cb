"""Multi-device sharded SpMV/SpMM: ShardedPlan / ShardedPlannedMatrix.

In-process tests run on the single default device through the dispatch
mode (which supports more shards than devices); the shard_map SPMD path
is exercised in subprocesses under
``XLA_FLAGS=--xla_force_host_platform_device_count=8``.
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import repro
from repro import obs
from repro.core.autotune import TuningDB
from repro.core.kernel_tune import KernelTuner
from repro.core.plan import (SHARDED_SCHEMA_VERSION, PlanError,
                             PlanSchemaError, Planner, ShardedPlan)
from repro.core.transform import csr_from_dense
from repro.obs import FakeClock, InMemorySink, Telemetry
from repro.partition import partition_for_devices, slice_csr_cols
from repro.serve import SpMVService
from repro.sharding import ShardedPlannedMatrix, build_sharded, shard_csr

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
STRATEGIES = ("fixed", "balanced_nnz", "variance")


def run_with_devices(code: str, n: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=480)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nERR:\n{out.stderr}"
    return out.stdout


def random_dense(rng, n_rows, n_cols, density):
    d = (rng.random((n_rows, n_cols)) < density).astype(np.float32)
    return d * rng.normal(1.0, 1.0, size=d.shape).astype(np.float32)


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


@pytest.fixture(scope="module")
def rng():
    return np.random.default_rng(11)


@pytest.fixture(scope="module")
def problem(rng):
    dense = random_dense(rng, 220, 180, 0.06)
    dense[:4, :] = rng.normal(size=(4, 180)).astype(np.float32)  # heavy tail
    return dense, csr_from_dense(dense, pad=8)


def assert_parity(spm, dense, rng, batches=(1, 8)):
    for b in batches:
        if b == 1:
            x = rng.normal(size=dense.shape[1]).astype(np.float32)
            np.testing.assert_allclose(np.asarray(spm @ x), dense @ x,
                                       rtol=2e-4, atol=2e-4)
        else:
            X = rng.normal(size=(dense.shape[1], b)).astype(np.float32)
            np.testing.assert_allclose(np.asarray(spm @ X), dense @ X,
                                       rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# partitioning at device-count granularity
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("strategy", STRATEGIES)
@pytest.mark.parametrize("n_dev", (1, 3, 8))
def test_partition_for_devices_exact_count(problem, strategy, n_dev):
    _, csr = problem
    lens = csr.row_lengths()
    b = partition_for_devices(lens, n_dev, strategy=strategy)
    assert b.shape[0] == n_dev + 1
    assert b[0] == 0 and b[-1] == lens.shape[0]
    assert np.all(np.diff(b) > 0)


def test_partition_for_devices_rejects_bad_counts(problem):
    _, csr = problem
    lens = csr.row_lengths()
    with pytest.raises(ValueError):
        partition_for_devices(lens, 0)
    with pytest.raises(ValueError):
        partition_for_devices(lens, lens.shape[0] + 1)
    with pytest.raises(KeyError):
        partition_for_devices(lens, 2, strategy="nope")


def test_partition_for_devices_skewed_splits(rng):
    # one row holds almost all the nnz: balanced_nnz must still cut 4 slabs
    lens = np.ones(64, dtype=np.int64)
    lens[0] = 10_000
    b = partition_for_devices(lens, 4, strategy="balanced_nnz")
    assert b.shape[0] == 5 and np.all(np.diff(b) > 0)


def test_slice_csr_cols_matches_dense(problem):
    dense, csr = problem
    sub = slice_csr_cols(csr, 40, 120)
    assert sub.shape == (dense.shape[0], 80)
    np.testing.assert_allclose(sub.todense(), dense[:, 40:120],
                               rtol=0, atol=0)


def test_shard_csr_covers_matrix(problem):
    dense, csr = problem
    b, subs = shard_csr(csr, 4, axis="col")
    assert len(subs) == 4 and b[-1] == dense.shape[1]
    assert sum(m.nnz for m in subs) == csr.nnz
    b, subs = shard_csr(csr, 4, axis="row")
    assert sum(m.nnz for m in subs) == csr.nnz
    np.testing.assert_allclose(np.concatenate([m.todense() for m in subs]),
                               dense, rtol=0, atol=0)


# ---------------------------------------------------------------------------
# dispatch mode (single device, many shards)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("axis", ("row", "col"))
@pytest.mark.parametrize("strategy", STRATEGIES)
def test_dispatch_mode_parity(problem, rng, axis, strategy):
    dense, csr = problem
    spm = build_sharded(csr, n_shards=4, axis=axis, strategy=strategy,
                        mode="dispatch")
    assert spm.mode == "dispatch" and spm.n_shards == 4
    assert_parity(spm, dense, rng)


def test_auto_mode_falls_back_to_dispatch_on_one_device(problem, rng):
    dense, csr = problem
    import jax
    if len(jax.devices()) >= 4:
        pytest.skip("needs a 1-device environment for the fallback")
    spm = build_sharded(csr, n_shards=4)
    assert spm.mode == "dispatch"
    assert_parity(spm, dense, rng, batches=(1,))


def test_single_shard_degenerates_to_planned_matrix(problem, rng):
    dense, csr = problem
    spm = build_sharded(csr, n_shards=1)
    assert spm.mode == "single" and spm.n_shards == 1
    from repro.core.plan import PlannedMatrix
    assert isinstance(spm.planned[0], PlannedMatrix)
    assert_parity(spm, dense, rng)


def test_shard_map_mode_requires_devices(problem):
    _, csr = problem
    import jax
    if len(jax.devices()) >= 4:
        pytest.skip("needs a 1-device environment")
    with pytest.raises(PlanError):
        build_sharded(csr, n_shards=4, mode="shard_map")


# ---------------------------------------------------------------------------
# the ShardedPlan artifact
# ---------------------------------------------------------------------------
def test_sharded_plan_roundtrip(problem, rng, tmp_path):
    dense, csr = problem
    plan = Planner().plan_sharded(csr, n_shards=4, axis="row",
                                  strategy="balanced_nnz")
    assert plan.n_shards == 4
    assert plan.schema_version == SHARDED_SCHEMA_VERSION
    assert plan.boundaries()[-1] == dense.shape[0]
    p = tmp_path / "sharded.json"
    plan.save(str(p))
    plan2 = ShardedPlan.load(str(p))
    assert plan2.to_dict() == plan.to_dict()
    assert plan2.shard_formats() == plan.shard_formats()
    assert plan2.matches(csr)
    spm = plan2.bind(csr, mode="dispatch")
    assert spm.fingerprint_matched
    assert_parity(spm, dense, rng)


def test_sharded_plan_rejects_future_schema(problem):
    _, csr = problem
    plan = Planner().plan_sharded(csr, n_shards=2)
    d = plan.to_dict()
    d["schema_version"] = SHARDED_SCHEMA_VERSION + 1
    with pytest.raises(PlanSchemaError):
        ShardedPlan.from_dict(d)
    with pytest.raises(PlanError):
        ShardedPlan.from_json("not json{")
    with pytest.raises(PlanError):
        ShardedPlan(shards=[], axis="row")


def test_sharded_plan_mismatch_rebinds(problem, rng):
    dense, csr = problem
    plan = Planner().plan_sharded(csr, n_shards=4, axis="row")
    other = random_dense(rng, 150, 150, 0.1)
    csr2 = csr_from_dense(other, pad=8)
    spm = plan.bind(csr2, mode="dispatch")
    assert not spm.fingerprint_matched
    # the recipe survives: same shard count, recomputed slabs on the new
    # matrix's row space
    assert spm.n_shards == 4
    assert spm.boundaries[-1] == 150
    assert [r["rows"][1] for r in spm.report()][-1] == 150
    x = rng.normal(size=150).astype(np.float32)
    np.testing.assert_allclose(np.asarray(spm @ x), other @ x,
                               rtol=2e-4, atol=2e-4)


def test_col_axis_plan_partitions_column_space(problem):
    dense, csr = problem
    plan = Planner().plan_sharded(csr, n_shards=3, axis="col")
    assert plan.axis == "col"
    assert plan.boundaries()[-1] == dense.shape[1]


def test_sharded_telemetry_spans_and_gauge(problem, rng):
    dense, csr = problem
    sink = InMemorySink()
    prev = obs.set_default(Telemetry(enabled=True, clock=FakeClock(),
                                     sinks=[sink]))
    try:
        spm = build_sharded(csr, n_shards=4, axis="col", mode="dispatch")
        x = rng.normal(size=dense.shape[1]).astype(np.float32)
        spm @ x
        tel = obs.get()
        gauges = {name: m.value for kind, name, labels, m in tel.metrics()
                  if kind == "gauge"}
        assert gauges.get("sharded.load_imbalance", 0) >= 1.0
        names = {r["name"] for r in sink.spans()}
        assert {"sharded.bind", "sharded.spmv", "shard.spmv",
                "shard.gather"} <= names
    finally:
        obs.set_default(prev)


def test_api_exports_sharding_surface():
    from repro import api
    for name in ("ShardedPlan", "ShardedPlannedMatrix", "build_sharded",
                 "SHARDED_SCHEMA_VERSION"):
        assert name in api.__all__ and hasattr(api, name), name
    assert repro.ShardedPlan is ShardedPlan
    assert repro.ShardedPlannedMatrix is ShardedPlannedMatrix
    import repro.sharding as sh
    assert sh.__all__ == ["ShardedPlannedMatrix", "build_sharded",
                          "shard_csr"]
    assert all(hasattr(sh, name) for name in sh.__all__)


# ---------------------------------------------------------------------------
# service integration: sharded registration, plan cache, batch seeding
# ---------------------------------------------------------------------------
def test_service_registers_sharded_plan(problem, rng):
    dense, csr = problem
    svc = SpMVService()
    plan = Planner().plan_sharded(csr, n_shards=4, axis="row")
    entry = svc.register("g", csr, plan=plan, measure_baseline=False,
                         mode="dispatch")
    assert entry.from_plan
    assert isinstance(entry.matrix, ShardedPlannedMatrix)
    x = rng.normal(size=dense.shape[1]).astype(np.float32)
    np.testing.assert_allclose(np.asarray(svc.spmv("g", x)), dense @ x,
                               rtol=2e-4, atol=2e-4)
    X = rng.normal(size=(dense.shape[1], 4)).astype(np.float32)
    np.testing.assert_allclose(np.asarray(svc.spmm("g", X)), dense @ X,
                               rtol=2e-4, atol=2e-4)
    fut = svc.submit("g", x)
    svc.flush()
    np.testing.assert_allclose(np.asarray(fut.result()), dense @ x,
                               rtol=2e-4, atol=2e-4)
    st = svc.stats()["g"]
    assert st["n_blocks"] == 4
    assert sum(st["formats"].values()) == 4
    assert st["bytes"] > 0
    assert st["plan"]["schema_version"] == SHARDED_SCHEMA_VERSION
    svc.evict("g")


def test_service_plan_cache_replays_across_keys_and_evicts(problem, rng):
    dense, csr = problem
    timer = fake_timer()
    db = TuningDB(machine="pc", c=1.0, records=[], d_star={})
    svc = SpMVService(tuner=KernelTuner(db=db, timer=timer, interpret=True))
    e1 = svc.register("a", csr, measure_baseline=False)
    assert not e1.from_plan
    n_timed = len(timer.calls)
    assert n_timed > 0

    # same structure, different key: served from the plan cache, no tuning
    e2 = svc.register("b", csr, measure_baseline=False)
    assert e2.from_plan
    assert len(timer.calls) == n_timed

    # survives evict: the cache lives on the service, not the entry
    svc.evict("a")
    svc.evict("b")
    e3 = svc.register("c", csr, measure_baseline=False)
    assert e3.from_plan
    assert len(timer.calls) == n_timed
    x = rng.normal(size=dense.shape[1]).astype(np.float32)
    np.testing.assert_allclose(np.asarray(svc.spmv("c", x)), dense @ x,
                               rtol=2e-4, atol=2e-4)

    pc = svc.stats()["plan_cache"]
    assert pc["hits"] == 2 and pc["misses"] == 1 and pc["size"] == 1

    # different registration knobs miss (the key includes them)
    svc.register("d", csr, measure_baseline=False, expected_iterations=7)
    assert svc.stats()["plan_cache"]["misses"] == 2


def test_service_plan_cache_keyed_by_structure(problem, rng):
    dense, csr = problem
    svc = SpMVService()
    svc.register("a", csr, measure_baseline=False)
    other = csr_from_dense(random_dense(rng, 64, 64, 0.2), pad=8)
    e = svc.register("b", other, measure_baseline=False)
    assert not e.from_plan
    assert svc.stats()["plan_cache"]["hits"] == 0


def test_plan_batch_seeds_entry_max_batch(problem, rng):
    dense, csr = problem
    svc = SpMVService(max_batch=32)
    minted = svc.register("mint", csr, batch=2, measure_baseline=False)
    assert minted.max_batch is None          # no plan supplied: global width
    plan = minted.plan
    assert plan.batch == 2
    entry = svc.register("p", csr, plan=plan, measure_baseline=False)
    assert entry.max_batch == 2
    # two submits fill the plan-seeded panel and auto-flush — no explicit
    # flush(), no waiting for the global max_batch of 32
    x1 = rng.normal(size=dense.shape[1]).astype(np.float32)
    x2 = rng.normal(size=dense.shape[1]).astype(np.float32)
    f1, f2 = svc.submit("p", x1), svc.submit("p", x2)
    assert f1.done() and f2.done()
    np.testing.assert_allclose(np.asarray(f1.result()), dense @ x1,
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(f2.result()), dense @ x2,
                               rtol=2e-4, atol=2e-4)


def test_sharded_plan_batch_seeds_entry_max_batch(problem, rng):
    dense, csr = problem
    svc = SpMVService(max_batch=32)
    plan = Planner().plan_sharded(csr, n_shards=2, batch=4)
    entry = svc.register("s", csr, plan=plan, measure_baseline=False,
                         mode="dispatch")
    assert entry.max_batch == 4
    futs = [svc.submit("s", rng.normal(size=dense.shape[1]
                                       ).astype(np.float32))
            for _ in range(4)]
    assert all(f.done() for f in futs)


def test_sharded_plan_save_load_register_zero_retuning(problem, rng,
                                                       tmp_path):
    """The acceptance path: ShardedPlan save -> load -> register(plan=)
    serves with zero re-tuning, counted by the fake timer."""
    dense, csr = problem
    timer = fake_timer()
    db = TuningDB(machine="zs", c=1.0, records=[], d_star={})
    planner = Planner(tuner=KernelTuner(db=db, timer=timer, interpret=True))
    plan = planner.plan_sharded(csr, n_shards=4, axis="row")
    n_timed = len(timer.calls)
    assert n_timed > 0                      # minting did tune
    assert all(bp.plan.tier == "kernel" for bp in plan.shards)

    p = tmp_path / "sharded.json"
    plan.save(str(p))
    loaded = ShardedPlan.load(str(p))
    svc = SpMVService(tuner=KernelTuner(db=db, timer=timer, interpret=True))
    entry = svc.register("z", csr, plan=loaded, measure_baseline=False,
                         mode="dispatch")
    assert entry.from_plan
    assert len(timer.calls) == n_timed, \
        "register(plan=<ShardedPlan>) must not re-tune"
    x = rng.normal(size=dense.shape[1]).astype(np.float32)
    np.testing.assert_allclose(np.asarray(svc.spmv("z", x)), dense @ x,
                               rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# the shard_map SPMD path (8 simulated devices, subprocess)
# ---------------------------------------------------------------------------
def test_shard_map_parity_all_strategies_8dev():
    run_with_devices("""
        import numpy as np, jax
        assert len(jax.devices()) == 8, jax.devices()
        from repro.core.transform import csr_from_dense
        from repro.sharding import build_sharded
        rng = np.random.default_rng(3)
        dense = ((rng.random((240, 200)) < 0.05)
                 * rng.normal(size=(240, 200))).astype(np.float32)
        dense[:3, :] = rng.normal(size=(3, 200)).astype(np.float32)
        csr = csr_from_dense(dense, pad=8)
        x = rng.normal(size=200).astype(np.float32)
        X = rng.normal(size=(200, 8)).astype(np.float32)
        for axis in ("row", "col"):
            for strat in ("fixed", "balanced_nnz", "variance"):
                spm = build_sharded(csr, n_shards=8, axis=axis,
                                    strategy=strat)
                assert spm.mode == "shard_map", spm.mode
                np.testing.assert_allclose(np.asarray(spm @ x), dense @ x,
                                           rtol=2e-4, atol=2e-4)
                np.testing.assert_allclose(np.asarray(spm @ X), dense @ X,
                                           rtol=2e-4, atol=2e-4)
        print("SHARD_MAP_OK")
    """)


def test_shard_map_service_roundtrip_8dev(tmp_path):
    plan_path = str(tmp_path / "plan.json").replace("\\", "/")
    run_with_devices(f"""
        import numpy as np, jax
        assert len(jax.devices()) == 8
        from repro.core.plan import Planner, ShardedPlan
        from repro.core.transform import csr_from_dense
        from repro.serve import SpMVService
        rng = np.random.default_rng(5)
        dense = ((rng.random((200, 200)) < 0.06)
                 * rng.normal(size=(200, 200))).astype(np.float32)
        csr = csr_from_dense(dense, pad=8)
        plan = Planner().plan_sharded(csr, n_shards=8, axis="row")
        plan.save({plan_path!r})
        loaded = ShardedPlan.load({plan_path!r})
        svc = SpMVService()
        entry = svc.register("m", csr, plan=loaded, measure_baseline=False)
        assert entry.matrix.mode == "shard_map", entry.matrix.mode
        x = rng.normal(size=200).astype(np.float32)
        np.testing.assert_allclose(np.asarray(svc.spmv("m", x)), dense @ x,
                                   rtol=2e-4, atol=2e-4)
        st = svc.stats()["m"]
        assert st["n_blocks"] == 8 and st["bytes"] > 0
        print("SERVICE_SHARDED_OK")
    """)
