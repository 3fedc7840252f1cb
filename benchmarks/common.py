"""Shared benchmark plumbing: CSV rows, suite construction, timing."""
from __future__ import annotations

import dataclasses
import os
from typing import Any, Dict, List

#: the persistent compilation cache's home when the environment names
#: none: one fixed path inside the checkout (listed in .gitignore), since
#: the path is part of the cache key and a moving directory never hits
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its path.
    ``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself and no
    path is set here."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    import jax
    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    return COMPILE_CACHE_DIR

SCALE = 0.08          # suite scale for CPU wall-clock runs (stats invariant)
ITERS = 3


@dataclasses.dataclass
class Row:
    name: str
    us_per_call: float
    derived: Dict[str, Any]

    def csv(self) -> str:
        d = ";".join(f"{k}={v}" for k, v in self.derived.items())
        return f"{self.name},{self.us_per_call:.2f},{d}"


def print_rows(rows: List[Row]) -> None:
    print("name,us_per_call,derived")
    for r in rows:
        print(r.csv())
