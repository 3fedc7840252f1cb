"""BENCHMARK.json holds to the benchmark's contract, and every name in it
resolves to a file of its own under bench/."""
import ast
import json
import os
import re

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bm():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_top_level_keys(bm):
    assert set(bm) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert bm["paths"] == ["bench"]
    assert 1 <= bm["run_seconds"] <= 51
    n_cells = 24
    total = (2 + 14 * n_cells) * (bm["run_seconds"] + 60) \
        + n_cells * 2 * 90 + 1200
    assert total <= 43200


def test_names_units_and_keys(bm):
    for c in bm["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["file"].startswith("bench/")
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
    for w in bm["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
    for m in bm["end_to_end"] + bm["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in bm["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert "setup_s" in {m["name"] for m in bm["end_to_end"]}
    e2e = {m["name"] for m in bm["end_to_end"]}
    for m in bm["per_layer"]:
        assert m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")


def test_every_name_resolves_to_its_file(bm):
    configs = {c["name"] for c in bm["configs"]}
    for w in bm["workloads"]:
        assert w["config"] in configs
        with open(os.path.join(BENCH, "traffic", w["traffic"] + ".json")) \
                as f:
            traffic = json.load(f)
        assert os.path.isfile(os.path.join(BENCH, "drivers",
                                           traffic["driver"] + ".py"))
    for m in bm["per_layer"]:
        assert os.path.isfile(os.path.join(BENCH, "metrics",
                                           m["name"] + ".py"))


def test_bench_imports_nothing_of_the_old_measurement_code():
    banned = ("benchmarks", "chip_smoke", "repro.core.suite")
    for d, _, files in os.walk(BENCH):
        if os.path.basename(d) == "tests":
            continue
        for fn in files:
            if not fn.endswith(".py"):
                continue
            with open(os.path.join(d, fn)) as f:
                tree = ast.parse(f.read())
            for node in ast.walk(tree):
                mods = []
                if isinstance(node, ast.Import):
                    mods = [a.name for a in node.names]
                elif isinstance(node, ast.ImportFrom):
                    mods = [node.module or ""]
                for m in mods:
                    assert not m.startswith(banned), (fn, m)
