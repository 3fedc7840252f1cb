"""repro — auto-tuned run-time sparse-format transformation for SpMV
(Katagiri & Sato) built out as a JAX/Pallas SpMV serving system.

The public API is the plan pipeline (see ``repro.api`` and
``docs/plans.md``)::

    from repro import Planner, ExecutionPlan

    plan = Planner(db=db).plan(csr)      # decide + tune, one artifact
    plan.save("plan.json")
    P = ExecutionPlan.load("plan.json").bind(csr)
    y = P @ x

Attribute access is lazy so ``import repro`` stays lightweight; the full
surface lives in :mod:`repro.api`.
"""

__version__ = "0.1.0"

# lazily re-exported from repro.api (keeps `import repro` free of jax)
_API_EXPORTS = (
    "Planner", "ExecutionPlan", "PlannedMatrix", "BlockPlan",
    "ShardedPlan", "ShardedPlannedMatrix", "build_sharded",
    "TransformRecipe", "PlanFingerprint", "PlanError", "PlanSchemaError",
    "SpMVService", "TuningDB", "KernelTuner", "TileGeometry",
    "offline_phase", "MachineModel", "MatrixStats", "csr_from_dense",
    "csr_from_rows", "obs", "Telemetry",
)

__all__ = ["__version__", "api", *_API_EXPORTS]


def __getattr__(name: str):
    import importlib
    if name == "obs":
        # resolved directly (not via repro.api) so the stdlib-only
        # telemetry surface never drags jax into the importing process
        return importlib.import_module("repro.obs")
    if name in _API_EXPORTS or name == "api":
        api = importlib.import_module("repro.api")
        return api if name == "api" else getattr(api, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
