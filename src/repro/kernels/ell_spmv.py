"""ELL SpMV / SpMM Pallas TPU kernels — the paper's hero format.

TPU mapping of the paper's §3.3/§3.4 parallelizations:
  * the N-loop (rows) becomes the *parallel* grid axis, tiled in
    ``block_rows`` chunks laid along the 128 vector lanes (the paper's
    "outer" parallelization);
  * the NE-loop (band) becomes the sequential accumulation axis, tiled in
    ``block_w`` sublane rows (the paper's "inner" parallelization).

Layout: the kernels take the band *transposed*, ``(width, n_rows)`` — the
paper's ELL-Col storage — so one band slot of ``block_rows`` consecutive
rows is one lane-dense vector row, and the reduction over the band is a
sublane reduction.  The output is a lane-dense ``(1, n_rows)`` row
(SpMM: ``(k, n_rows)``), never a 1-D or lane-width-1 block.

The x gather happens in XLA before the kernel (``ops.py``): ``x[ICOL]`` is
streamed in as a dense panel of the same shape as VAL, so the kernel body
is a dense multiply-reduce (Mosaic has no general 1-D gather).  x is
therefore never pinned in VMEM, and its size is bounded by HBM only.  For
SpMV, a block whose every run of 8 band slots of a row reads at most 8
consecutive columns gathers one 8-wide slice of x per run and selects
each slot's value from it; any other block, and SpMM, gathers one element
per slot.  Either way the kernel reads the same panel.

Block alignment: ``block_w % 8 == 0`` (sublanes); ``block_rows`` is a
multiple of 128 (lanes) or covers every row.  The ops.py wrapper pads
inputs to these multiples (pad entries: val=0, col=0 — contributing zero,
the paper's own padding convention).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _ell_spmv_kernel(data_ref, xg_ref, y_ref):
    """Grid = (row_blocks, w_blocks); w is the sequential accumulation
    axis.  Accumulation is always f32."""
    j = pl.program_id(1)
    partial = jnp.sum(data_ref[...].astype(jnp.float32) *
                      xg_ref[...].astype(jnp.float32), axis=0,
                      keepdims=True)                      # (1, block_rows)

    @pl.when(j == 0)
    def _init():
        y_ref[...] = partial

    @pl.when(j != 0)
    def _acc():
        y_ref[...] = y_ref[...] + partial


@functools.partial(jax.jit, static_argnames=("block_rows", "block_w",
                                             "interpret"))
def ell_spmv(data_t: jax.Array, xg_t: jax.Array, *, block_rows: int = 256,
             block_w: int = 128, interpret: bool = False) -> jax.Array:
    """y = A @ x with A's band transposed: ``data_t``/``xg_t`` are
    ``(width, n_rows)`` — VAL and the gathered ``x[ICOL]``.

    Shapes must already be block-aligned (see ops.ell_spmv_raw for the
    padding wrapper).  Returns ``(n_rows,)`` float32."""
    width, n_rows = data_t.shape
    assert n_rows % block_rows == 0 and width % block_w == 0, (
        f"unaligned ELL shapes {data_t.shape} for blocks "
        f"({block_rows},{block_w})")
    y = pl.pallas_call(
        _ell_spmv_kernel,
        grid=(n_rows // block_rows, width // block_w),
        in_specs=[
            pl.BlockSpec((block_w, block_rows), lambda i, j: (j, i)),
            pl.BlockSpec((block_w, block_rows), lambda i, j: (j, i)),
        ],
        out_specs=pl.BlockSpec((1, block_rows), lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, n_rows), jnp.float32),
        interpret=interpret,
        name="ell_spmv",
    )(data_t, xg_t)
    return y[0]


def _ell_spmm_kernel(data_ref, xg_ref, y_ref):
    """Multi-RHS: ``xg_ref`` is ``(block_k, block_w, block_rows)`` — for
    each right-hand side, the gathered x of each band slot, laid out like
    VAL.  Each right-hand side is the SpMV body over that slab.
    Grid = (row_blocks, k_blocks, w_blocks); w innermost (sequential)."""
    j = pl.program_id(2)
    data = data_ref[...].astype(jnp.float32)              # (bw, br)

    @pl.when(j == 0)
    def _init():
        y_ref[...] = jnp.zeros(y_ref.shape, y_ref.dtype)

    def _rhs(c, carry):
        row = jnp.sum(data * xg_ref[c].astype(jnp.float32), axis=0,
                      keepdims=True)                      # (1, br)
        y_ref[pl.ds(c, 1), :] = y_ref[pl.ds(c, 1), :] + row
        return carry

    jax.lax.fori_loop(0, y_ref.shape[0], _rhs, 0)


@functools.partial(jax.jit, static_argnames=("block_rows", "block_w",
                                             "block_k", "interpret"))
def ell_spmm(data_t: jax.Array, xg_t: jax.Array, *, block_rows: int = 128,
             block_w: int = 8, block_k: int = 128,
             interpret: bool = False) -> jax.Array:
    """Y = A @ X with ``data_t`` ``(width, n_rows)`` and ``xg_t``
    ``(k, width, n_rows)`` (``X[ICOL]`` with the RHS axis leading).
    Returns Y transposed, ``(k, n_rows)`` float32."""
    width, n_rows = data_t.shape
    k = xg_t.shape[0]
    assert n_rows % block_rows == 0 and width % block_w == 0 \
        and k % block_k == 0, (data_t.shape, xg_t.shape)
    return pl.pallas_call(
        _ell_spmm_kernel,
        grid=(n_rows // block_rows, k // block_k, width // block_w),
        in_specs=[
            pl.BlockSpec((block_w, block_rows), lambda i, kk, j: (j, i)),
            pl.BlockSpec((block_k, block_w, block_rows),
                         lambda i, kk, j: (kk, j, i)),
        ],
        out_specs=pl.BlockSpec((block_k, block_rows),
                               lambda i, kk, j: (kk, i)),
        out_shape=jax.ShapeDtypeStruct((k, n_rows), jnp.float32),
        interpret=interpret,
        name="ell_spmm",
    )(data_t, xg_t)
