"""The static-analysis subsystem (docs/analysis.md): plan lint over
crafted bad artifacts, the registry audit run against the real tree, the
AST rules and their ``# repro: noqa`` waivers, the CLI exit codes (proven
jax-free in a subprocess), and the three integration points — PlanStore
quarantine with reason ``lint``, ``register(strict_lint=)``, and the
Planner's mint-time self-check."""
import copy
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.analyze import (PlanLintError, errors, has_errors, lint_plan,
                           lint_source, lint_text)
from repro.analyze import registry as reg
from repro.analyze.cli import main as analyze_main

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURE = os.path.join(REPO, "tests", "fixtures", "plan_good.json")


def rules(findings, severity=None):
    return {f.rule for f in findings
            if severity is None or f.severity == severity}


@pytest.fixture()
def good():
    with open(FIXTURE) as f:
        payload = json.load(f)
    return copy.deepcopy(payload)


# ---------------------------------------------------------------------------
# plan lint (RPL)
# ---------------------------------------------------------------------------
def test_good_fixture_is_clean(good):
    assert lint_plan(good) == []


def test_misaligned_block_rows(good):
    good["geometry"]["spmv"]["block_rows"] = 100
    assert "RPL002" in rules(lint_plan(good), "error")


def test_slab_bound_below_structure(good):
    # n=1024, nnz=16384, block_rows=256 -> 4 segments; block_nnz=2048
    # -> ceil(16384 / (4 * 2048)) = 2 slabs needed, 1 recorded
    good["geometry"]["spmv"]["slabs_per_block"] = 1
    found = lint_plan(good)
    assert "RPL003" in rules(found, "error")
    assert any("slabs_per_block=1" in f.message for f in errors(found))


def test_vmem_over_budget_and_override(good):
    # a 16384 x 256 one-hot row-recovery tile: ~32 MiB
    good["geometry"]["spmv"]["block_nnz"] = 2 ** 14
    good["geometry"]["spmv"]["slabs_per_block"] = 1
    assert "RPL004" in rules(lint_plan(good), "error")
    # a bigger part makes the same geometry feasible
    assert "RPL004" not in rules(lint_plan(good, vmem_budget=128 * 2 ** 20))


def test_vmem_only_applies_to_kernel_tier(good):
    good["geometry"]["spmv"]["block_nnz"] = 2 ** 23
    good["geometry"]["spmv"]["slabs_per_block"] = 1
    good["tier"] = "reference"
    assert "RPL004" not in rules(lint_plan(good))


def test_missing_required_fields(good):
    del good["transform"]
    found = lint_plan(good)
    assert "RPL001" in rules(found, "error")
    assert has_errors(found)


def test_unknown_format(good):
    good["fmt"] = "quantum_csr"
    assert "RPL001" in rules(lint_plan(good), "error")


def test_transform_cannot_produce_fmt(good):
    good["transform"]["name"] = "sell"
    assert "RPL008" in rules(lint_plan(good), "error")


def test_fingerprint_nonsense(good):
    good["fingerprint"]["n"] = 0          # nnz=16384 on zero rows
    assert "RPL009" in rules(lint_plan(good), "error")


def test_fingerprint_mu_drift_warns(good):
    good["fingerprint"]["mu"] = 99.0      # nnz/n is 16
    found = lint_plan(good)
    assert "RPL009" in rules(found, "warn")
    assert not has_errors(found)


def _sell_plan():
    return {
        "schema_version": 1, "fmt": "sell", "rule": "paper",
        "tier": "kernel", "batch": 1, "expected_iterations": 100,
        "transform": {"name": "sell",
                      "params": {"slice_rows": 64, "width_quantum": 8}},
        "geometry": {"spmv": {
            "block_rows": 256, "block_w": 128,
            "buckets": [[32, {"block_rows": 256, "block_w": 32}],
                        [8, {"block_rows": 256, "block_w": 8}]]}},
        "machine": "", "d_mat": 0.25, "d_star": None,
        "expected_gain": 0.0,
        "fingerprint": {"n": 1024, "nnz": 16384, "mu": 16.0,
                        "sigma": 4.0, "d_mat": 0.25, "sig": 7},
        "blocks": None,
    }


def test_sell_plan_is_clean():
    assert not has_errors(lint_plan(_sell_plan()))


def test_sell_bucket_width_off_quantum():
    d = _sell_plan()
    d["geometry"]["spmv"]["buckets"][0][0] = 12   # not a multiple of 8
    assert "RPL005" in rules(lint_plan(d), "error")


def test_sell_too_many_buckets():
    d = _sell_plan()
    d["transform"]["params"]["slice_rows"] = 1024  # at most 1 bucket
    assert "RPL005" in rules(lint_plan(d), "error")


def _leaf(n, nnz):
    return {
        "schema_version": 1, "fmt": "ell_row", "rule": "cost_model",
        "tier": "reference", "batch": 1, "expected_iterations": 100,
        "transform": {"name": "ell_row", "params": {}}, "geometry": {},
        "machine": "", "d_mat": None, "d_star": None,
        "expected_gain": 0.0,
        "fingerprint": {"n": n, "nnz": nnz, "mu": None, "sigma": None,
                        "d_mat": None, "sig": 1},
        "blocks": None,
    }


def _hybrid_plan():
    return {
        "schema_version": 1, "fmt": "hybrid", "rule": "cost_model",
        "tier": "reference", "batch": 1, "expected_iterations": 100,
        "transform": {"name": "hybrid", "params": {}}, "geometry": {},
        "machine": "", "d_mat": None, "d_star": None,
        "expected_gain": 0.0,
        "fingerprint": {"n": 96, "nnz": 600, "mu": None, "sigma": None,
                        "d_mat": None, "sig": 2},
        "blocks": [{"rows": [0, 64], "plan": _leaf(64, 400)},
                   {"rows": [64, 96], "plan": _leaf(32, 200)}],
    }


def test_hybrid_plan_is_clean():
    assert not has_errors(lint_plan(_hybrid_plan()))


def test_hybrid_blocks_must_tile_from_zero():
    d = _hybrid_plan()
    d["blocks"][0]["rows"] = [8, 64]
    assert "RPL006" in rules(lint_plan(d), "error")


def test_hybrid_nnz_must_sum():
    d = _hybrid_plan()
    d["blocks"][1]["plan"]["fingerprint"]["nnz"] = 150
    assert "RPL006" in rules(lint_plan(d), "error")


def _sharded_plan():
    return {
        "kind": "sharded_plan", "schema_version": 1, "axis": "row",
        "strategy": "balanced_nnz", "params": {}, "mesh_shape": [2],
        "mesh_axis": "shards", "batch": 1,
        "fingerprint": {"n": 128, "nnz": 900, "mu": None, "sigma": None,
                        "d_mat": None, "sig": 3},
        "shards": [{"rows": [0, 64], "plan": _leaf(64, 500)},
                   {"rows": [64, 128], "plan": _leaf(64, 400)}],
    }


def test_sharded_plan_is_clean():
    assert not has_errors(lint_plan(_sharded_plan()))


def test_sharded_spans_must_cover_rows():
    d = _sharded_plan()
    d["shards"][1]["rows"] = [64, 100]    # fingerprint says n=128
    assert "RPL007" in rules(lint_plan(d), "error")


def test_sharded_shard_fingerprint_required():
    d = _sharded_plan()
    d["shards"][0]["plan"]["fingerprint"] = None
    assert "RPL007" in rules(lint_plan(d), "error")


def test_envelope_checksum(good):
    import hashlib
    canonical = json.dumps(good, sort_keys=True, separators=(",", ":"))
    env = {"store_version": 1,
           "sha256": hashlib.sha256(canonical.encode()).hexdigest(),
           "plan": good}
    assert lint_text(json.dumps(env)) == []
    env["plan"]["batch"] = 16             # tamper without re-signing
    found = lint_text(json.dumps(env))
    assert has_errors(found)
    assert any("sha256" in f.message for f in errors(found))


def test_not_json_is_one_error():
    found = lint_text("{not json")
    assert [f.rule for f in found] == ["RPL001"]


# ---------------------------------------------------------------------------
# AST lint (RPA)
# ---------------------------------------------------------------------------
BLIND = """\
def f(g):
    try:
        g()
    except Exception:
        pass
"""


def test_rpa001_blind_except():
    assert "RPA001" in rules(lint_source(BLIND, "src/x.py"), "error")


@pytest.mark.parametrize("handler", [
    "        raise RuntimeError('wrapped') from e",
    "        tel.counter('errs').inc()",
    "        last_err = e",
])
def test_rpa001_accounted_handlers_pass(handler):
    code = (f"def f(g, tel):\n    try:\n        g()\n"
            f"    except Exception as e:\n{handler}\n")
    assert "RPA001" not in rules(lint_source(code, "src/x.py"))


def test_rpa001_noqa_same_line():
    code = BLIND.replace("except Exception:",
                         "except Exception:  # repro: noqa[RPA001]")
    assert lint_source(code, "src/x.py") == []


def test_rpa001_noqa_line_above():
    code = BLIND.replace(
        "    except Exception:",
        "    # best-effort cleanup — repro: noqa[RPA001]\n"
        "    except Exception:")
    assert lint_source(code, "src/x.py") == []


def test_bare_noqa_waives_everything():
    code = BLIND.replace("except Exception:",
                         "except Exception:  # repro: noqa")
    assert lint_source(code, "src/x.py") == []


def test_noqa_for_other_rule_does_not_waive():
    code = BLIND.replace("except Exception:",
                         "except Exception:  # repro: noqa[RPA005]")
    assert "RPA001" in rules(lint_source(code, "src/x.py"))


CLOCK = """\
import time
def flush_due(deadline):
    return time.time() > deadline
"""


def test_rpa002_clock_only_inside_serve():
    assert "RPA002" in rules(
        lint_source(CLOCK, "src/repro/serve/queue.py"), "error")
    assert "RPA002" not in rules(
        lint_source(CLOCK, "src/repro/core/queue.py"))


def test_rpa003_jax_import_in_jax_free_package():
    code = "import jax\n"
    assert "RPA003" in rules(
        lint_source(code, "src/repro/obs/new_sink.py"), "error")
    assert "RPA003" in rules(
        lint_source("from jax import numpy\n",
                    "src/repro/analyze/helper.py"), "error")
    assert "RPA003" not in rules(lint_source(code, "src/repro/core/x.py"))


TIMING = """\
import time
import jax.numpy as jnp
def bench(a):
    t0 = time.perf_counter()
    y = jnp.dot(a, a){sync}
    t1 = time.perf_counter()
    return t1 - t0, y
"""


def test_rpa004_timing_without_sync():
    assert "RPA004" in rules(
        lint_source(TIMING.format(sync=""), "src/bench.py"), "error")
    assert "RPA004" not in rules(
        lint_source(TIMING.format(sync=".block_until_ready()"),
                    "src/bench.py"))


def test_rpa005_mutable_default():
    code = "def f(x, acc=[]):\n    acc.append(x)\n    return acc\n"
    assert "RPA005" in rules(lint_source(code, "src/x.py"), "error")
    assert "RPA005" not in rules(
        lint_source("def f(x, acc=None):\n    return acc\n", "src/x.py"))


def test_rpa000_unparseable_source():
    assert "RPA000" in rules(lint_source("def broken(:\n", "src/x.py"),
                             "error")


# ---------------------------------------------------------------------------
# registry audit (RPR) — against the real tree
# ---------------------------------------------------------------------------
def test_audit_real_tree_has_no_errors():
    found = reg.audit(src=os.path.join(REPO, "src"),
                      docs=os.path.join(REPO, "docs", "observability.md"))
    assert not has_errors(found), "\n".join(f.render() for f in found)


def test_emitted_telemetry_sees_known_names():
    emitted = reg.emitted_telemetry(Path(REPO) / "src")
    assert "store.quarantine" in emitted
    assert "service.plan_lint" in emitted
    assert "plan.lint" in emitted


def test_documented_telemetry_reads_the_vocabulary():
    documented = reg.documented_telemetry(
        Path(REPO) / "docs" / "observability.md")
    assert documented is not None
    assert {"store.quarantine", "plan.lint", "tune.winner"} <= documented


def test_registrations_cover_reference_formats():
    provs = reg.providers(
        Path(REPO) / "src" / "repro" / "core" / "dispatch.py")
    assert "reference" in provs and "kernel" in provs
    fmts = set()
    impls = set()
    for mod in provs["reference"]:
        path = Path(REPO) / "src" / (os.path.join(*mod.split(".")) + ".py")
        f, i = reg.registrations(path)
        fmts |= f
        impls |= i
    assert "csr" in fmts and "sell" in fmts
    assert ("csr", "spmv", "reference") in impls


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------
def test_cli_lint_plan_good_fixture(capsys):
    assert analyze_main(["lint-plan", FIXTURE]) == 0
    assert "0 error(s)" in capsys.readouterr().out


def test_cli_lint_plan_bad_artifact(tmp_path, good, capsys):
    good["geometry"]["spmv"]["block_rows"] = 100
    bad = tmp_path / "bad_plan.json"
    bad.write_text(json.dumps(good))
    assert analyze_main(["lint-plan", str(bad)]) == 1
    assert "RPL002" in capsys.readouterr().out


def test_cli_lint_src_exit_codes(tmp_path, capsys):
    dirty = tmp_path / "dirty.py"
    dirty.write_text(BLIND)
    assert analyze_main(["lint-src", str(dirty)]) == 1
    capsys.readouterr()
    clean = tmp_path / "clean.py"
    clean.write_text("def f():\n    return 1\n")
    assert analyze_main(["lint-src", str(clean)]) == 0


def test_cli_strict_warn_promotes_warnings(tmp_path, good):
    good["fingerprint"]["mu"] = 99.0      # warning only
    p = tmp_path / "warny.json"
    p.write_text(json.dumps(good))
    assert analyze_main(["lint-plan", str(p)]) == 0
    assert analyze_main(["--strict-warn", "lint-plan", str(p)]) == 1


def test_cli_audit_real_tree():
    assert analyze_main([
        "audit", "--src", os.path.join(REPO, "src"),
        "--docs", os.path.join(REPO, "docs", "observability.md")]) == 0


def test_cli_usage_error():
    with pytest.raises(SystemExit) as exc:
        analyze_main(["no-such-command"])
    assert exc.value.code == 2


def test_cli_is_jax_free():
    code = ("import sys; import repro.analyze, repro.analyze.cli; "
            "from repro.analyze.planlint import lint_plan; "
            "assert 'jax' not in sys.modules, 'analyze must not import jax'")
    proc = subprocess.run([sys.executable, "-c", code],
                          capture_output=True, text=True,
                          env={**os.environ,
                               "PYTHONPATH": os.path.join(REPO, "src")})
    assert proc.returncode == 0, proc.stderr


def test_module_lint_plan_subprocess_is_jax_free():
    proc = subprocess.run(
        [sys.executable, "-X", "importtime", "-m", "repro.analyze",
         "lint-plan", FIXTURE],
        capture_output=True, text=True,
        env={**os.environ, "PYTHONPATH": os.path.join(REPO, "src")})
    assert proc.returncode == 0, proc.stderr
    assert "jax" not in [ln.split("|")[-1].strip()
                         for ln in proc.stderr.splitlines()]


# ---------------------------------------------------------------------------
# integration: store quarantine, register(strict_lint=), planner self-check
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def csr():
    from repro.core.transform import csr_from_dense
    rng = np.random.default_rng(5)
    dense = (rng.random((64, 64)) < 0.1).astype(np.float32)
    return csr_from_dense(dense)


def _corrupt(plan_dict):
    """Semantically break a plan in a way only the lint can see."""
    d = json.loads(json.dumps(plan_dict))
    if d.get("blocks"):
        d["blocks"][0]["rows"][0] = 8      # no longer tiles from row 0
    else:
        d["fingerprint"]["n"] = 0          # nnz on zero rows
    return d


def test_store_quarantines_lint_failures(tmp_path, csr):
    from repro.core.plan import Planner
    from repro.core.plan_store import BAD_DIR, PlanStore, _canonical, \
        _sha256
    store = PlanStore(str(tmp_path / "plans"))
    plan = Planner().plan(csr)
    key = store.key_for(csr, batch=1)
    path = store.put(key, plan)
    # corrupt the payload semantically but re-sign the checksum, so the
    # envelope/checksum/schema stages all pass and only the lint can
    # reject it
    env = json.load(open(path))
    env["plan"] = _corrupt(env["plan"])
    env["sha256"] = _sha256(_canonical(env["plan"]))
    json.dump(env, open(path, "w"))
    assert store.get(key) is None          # quarantined, never raised
    assert store.quarantined == 1
    bad = os.listdir(tmp_path / "plans" / BAD_DIR)
    assert len(bad) == 1 and bad[0].endswith(".lint")


def test_register_strict_lint_raises(csr):
    from repro.core.plan import ExecutionPlan
    from repro.serve.spmv_service import SpMVService
    svc = SpMVService()
    minted = svc.register("m", csr, measure_baseline=False).plan
    bad = ExecutionPlan.from_dict(_corrupt(minted.to_dict()))
    with pytest.raises(PlanLintError) as exc:
        svc.register("strict", csr, plan=bad, strict_lint=True,
                     measure_baseline=False)
    assert exc.value.findings                 # carries the findings


def test_register_nonstrict_drops_plan_and_rebuilds(csr):
    import jax.numpy as jnp
    from repro.core.plan import ExecutionPlan
    from repro.core.spmv import spmv as spmv_ref
    from repro.serve.spmv_service import SpMVService
    minted = SpMVService().register("m", csr,
                                    measure_baseline=False).plan
    bad = ExecutionPlan.from_dict(_corrupt(minted.to_dict()))
    svc = SpMVService()                       # fresh: empty plan cache
    entry = svc.register("lax", csr, plan=bad, measure_baseline=False)
    assert entry.from_plan is False           # rebuilt, not replayed
    assert not has_errors(lint_plan(entry.plan.to_dict()))
    x = jnp.ones((csr.n_cols,), jnp.float32)
    np.testing.assert_allclose(np.asarray(svc.spmv("lax", x)),
                               np.asarray(spmv_ref(csr, x)),
                               rtol=1e-4, atol=1e-5)


def test_planner_self_check_rejects_corrupt_plan(csr):
    from repro.core.plan import ExecutionPlan, PlanError, Planner
    planner = Planner()
    plan = planner.plan(csr)                  # self-check passes on mint
    bad = ExecutionPlan.from_dict(_corrupt(plan.to_dict()))
    with pytest.raises(PlanError):
        planner._self_check(bad)


# ---------------------------------------------------------------------------
# container validators behind the lint (satellite b)
# ---------------------------------------------------------------------------
def test_new_validators_pass_on_real_transforms(csr):
    from repro.core.formats import validate_container
    from repro.core.transform import TRANSFORMS_HOST
    for name, fn in TRANSFORMS_HOST.items():
        validate_container(fn(csr))


def test_validators_catch_corruption(csr):
    from repro.core.formats import MatrixValidationError
    from repro.core.transform import TRANSFORMS_HOST
    coo = TRANSFORMS_HOST["coo_row"](csr)
    coo.cols[:csr.nnz] = csr.n_cols + 5       # out-of-range columns
    with pytest.raises(MatrixValidationError):
        coo.validate()
    ell = TRANSFORMS_HOST["ell_row"](csr)
    object.__setattr__(ell, "nnz", ell.data.size + 1)
    with pytest.raises(MatrixValidationError):
        ell.validate()
    bcsr = TRANSFORMS_HOST["bcsr"](csr)
    bcsr.indptr[0] = 1                        # indptr must start at 0
    with pytest.raises(MatrixValidationError):
        bcsr.validate()
