"""Pure-jnp oracles for the Pallas kernels — identical math, no tiling.

Accumulation is f32 with a final cast to the input result type, matching
the kernels (standard MXU/VPU practice for bf16 inputs).  These operate on
raw arrays (not the pytree format classes) so kernel tests can sweep
shapes/dtypes directly; ``repro.core.spmv`` provides the format-level
references."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def ell_spmv_ref(data: jax.Array, cols: jax.Array, x: jax.Array) -> jax.Array:
    out_dtype = jnp.result_type(data.dtype, x.dtype)
    y32 = (data.astype(jnp.float32) * x.astype(jnp.float32)[cols]).sum(axis=1)
    return y32.astype(out_dtype)


def ell_spmm_ref(data: jax.Array, cols: jax.Array, x: jax.Array) -> jax.Array:
    out_dtype = jnp.result_type(data.dtype, x.dtype)
    y32 = jnp.einsum("rw,rwk->rk", data.astype(jnp.float32),
                     x.astype(jnp.float32)[cols])
    return y32.astype(out_dtype)


def coo_spmv_ref(data: jax.Array, rows: jax.Array, cols: jax.Array,
                 x: jax.Array, n_rows: int) -> jax.Array:
    out_dtype = jnp.result_type(data.dtype, x.dtype)
    contrib = data.astype(jnp.float32) * x.astype(jnp.float32)[cols]
    y32 = jnp.zeros((n_rows,), jnp.float32).at[rows].add(contrib)
    return y32.astype(out_dtype)


def sell_spmv_ref(perm: jax.Array, bucket_arrays, row_offsets, n_rows: int,
                  x: jax.Array) -> jax.Array:
    """bucket_arrays: sequence of (data, cols) pairs."""
    y = None
    for (data, cols), off in zip(bucket_arrays, row_offsets):
        yb = ell_spmv_ref(data, cols, x)
        if y is None:
            y = jnp.zeros((n_rows,), yb.dtype)
        y = y.at[perm[off:off + data.shape[0]]].set(yb)
    return y

