from . import faults
from .faults import FaultRegistry, InjectedFault
from .guard import CircuitBreaker, GuardedImpl, GuardError, guard_ladder
from .spmv_service import (AdmissionError, EvictedError, MatrixEntry,
                           SpMVService)

__all__ = [
    "MatrixEntry", "SpMVService",
    # fault tolerance (docs/robustness.md)
    "GuardedImpl", "CircuitBreaker", "GuardError", "guard_ladder",
    "AdmissionError", "EvictedError",
    "faults", "FaultRegistry", "InjectedFault",
]
