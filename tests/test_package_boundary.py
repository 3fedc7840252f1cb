"""The package is the SpMV system and nothing else: importing its public
surface loads only its own subpackages, and ``src/repro`` holds no other."""
import json
import os
import subprocess
import sys

SUBPACKAGES = {"core", "kernels", "partition", "serve", "sharding",
               "stream", "analyze", "obs"}
SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "src")

PROBE = """
import json, sys
import repro.api, repro.serve, repro.kernels, repro.sharding, repro.stream
print(json.dumps(sorted(m for m in sys.modules
                        if m == "repro" or m.startswith("repro."))))
"""


def test_public_surface_loads_only_spmv_subpackages():
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": os.pathsep.join(
               p for p in (SRC, os.environ.get("PYTHONPATH")) if p)}
    out = subprocess.run([sys.executable, "-c", PROBE], env=env, check=True,
                         capture_output=True, text=True).stdout
    loaded = json.loads(out.strip().splitlines()[-1])
    stray = [m for m in loaded if m not in ("repro", "repro.api")
             and m.split(".")[1] not in SUBPACKAGES]
    assert not stray, stray
    pkg = os.path.join(SRC, "repro")
    found = {d for d in os.listdir(pkg)
             if os.path.isfile(os.path.join(pkg, d, "__init__.py"))}
    assert found == SUBPACKAGES
