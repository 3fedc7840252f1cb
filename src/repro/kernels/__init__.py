"""Pallas TPU kernels for the paper's compute hot spot (SpMV/SpMM) with
jit wrappers (ops) and pure-jnp oracles (ref)."""
from . import ops, ref
from .ell_spmv import ell_spmv, ell_spmm
from .csr_spmv import csr_spmm_t
