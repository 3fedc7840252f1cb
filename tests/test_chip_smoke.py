"""The chip smoke test's own checks, run on the CPU at a small size: a
clean plan and serve pass, an injected fault that the service would
absorb through a fallback (a kernel that raises, a transform that raises)
fails the serve phase, a shard kernel that raises fails the four-device
sharded phase, and without a TPU the script refuses to run."""
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke as smoke  # noqa: E402
from repro.serve import faults  # noqa: E402

#: a small off-line suite around xenon2's D_mat (0.16) and past it
OFFLINE = dict(scale=0.005, include=("torso2", "airfoil_2d", "memplus"))


@pytest.fixture(scope="module")
def problem():
    csr = smoke.synthesize("xenon2", 0.002, seed=0)
    x, X = smoke.make_inputs(csr, seed=0)
    refs = {"x": smoke.host_reference(csr, x),
            "X": smoke.host_reference(csr, X)}
    return csr, x, X, refs


@pytest.fixture(scope="module")
def db():
    return smoke.offline_db(seed=0, **OFFLINE)


def test_offline_db_learns_d_star(db):
    assert len(db.records) == len(OFFLINE["include"])
    assert set(db.d_star) == set(smoke.PLAN_FORMATS)
    assert db.geometries, "the off-line phase tuned no launch"


def test_plan_phase_passes_clean(problem, db):
    out = smoke.plan_phase("xenon2", *problem, db, max_candidates=1)
    assert out["tiers"] == {"spmv": "kernel", "spmm": "kernel"}
    assert out["fmt"] == ("ell_row" if out["d_mat"] < out["d_star"]
                          else "csr")
    assert out["worst_err_over_bound"] <= 1.0


def test_serve_phase_passes_clean(problem):
    out = smoke.serve_phase("xenon2", *problem, max_candidates=1)
    assert out["fmt"] == "hybrid"
    assert out["served_by"] == {"spmv": "3/3", "spmm": "3/3"}


@pytest.mark.parametrize("point", ["kernel.raise", "transform.raise"])
def test_serve_phase_rejects_a_fallback(problem, point):
    with faults.inject(point):
        with pytest.raises(smoke.SmokeFailure):
            smoke.serve_phase("xenon2", *problem, max_candidates=1)


def test_sharded_phase_rejects_a_shard_fallback_on_four_devices():
    """Dispatch mode gives each shard its own ladder (kernel, then
    reference CSR); a shard kernel that raises must fail the phase even
    though the product still comes out right."""
    code = textwrap.dedent(f"""
        import sys
        sys.path.insert(0, {ROOT!r})
        import chip_smoke as smoke
        from repro.serve import faults
        csr = smoke.synthesize("torso1", 0.002, 0)
        x, X = smoke.make_inputs(csr, 0)
        refs = {{"x": smoke.host_reference(csr, x),
                 "X": smoke.host_reference(csr, X)}}
        db = smoke.offline_db(0, **{OFFLINE!r})
        one = smoke.plan_phase("torso1", csr, x, X, refs, db, 0)
        smoke.sharded_phase("torso1", csr, x, X, refs, db, one, 4)
        print("CLEAN OK")
        with faults.inject("kernel.raise"):
            try:
                smoke.sharded_phase("torso1", csr, x, X, refs, db, one, 4)
            except smoke.SmokeFailure as e:
                print("REJECTED", e)
    """)
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=480)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "CLEAN OK" in out.stdout, out.stdout
    # two timed calls per op, on each of four shards
    assert "body=kernel served_by=spmv:8/8,spmm:8/8" in out.stdout, \
        out.stdout
    rejected = [ln for ln in out.stdout.splitlines()
                if ln.startswith("REJECTED")]
    assert rejected and "shard" in rejected[0] and "fell back" in rejected[0]


def test_check_rejects_a_result_outside_the_bound(problem):
    csr, x, _, refs = problem
    y, bound = refs["x"]
    assert smoke.check("exact", y, y, bound) == 0.0
    bad = y.copy()
    i = int(bound.argmax())
    bad[i] += 2 * bound[i]
    with pytest.raises(smoke.SmokeFailure):
        smoke.check("perturbed", bad, y, bound)


def test_main_refuses_without_a_tpu(capsys):
    assert smoke.main([]) != 0
    assert '"ok"' not in capsys.readouterr().out
