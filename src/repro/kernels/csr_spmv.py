"""Native row-segmented CSR SpMV / SpMM Pallas TPU kernel.

  * the grid is ``(row_blocks, k_blocks, slabs_per_block)``: each row
    block owns a private ``(block_k, block_rows)`` output tile, so row
    blocks are *parallel* — there is no whole-matrix y in VMEM and no
    global sequential walk;
  * a row block's nonzeros are contiguous in CSR order
    (``IRP[i*br] : IRP[(i+1)*br]``), so its slabs are located by *scalar
    prefetch*: ``slab_start[i] = IRP[i*br] // block_nnz`` feeds the
    BlockSpec index map and the VAL slabs stream straight out of the row
    block's own span — the TPU form of the paper's per-thread contiguous
    CRS walk (§3.1's outer parallelization);
  * within a slab, rows are recovered with a one-hot compare: entry ``k``
    belongs to local row ``r`` iff ``IRP[r] <= k < IRP[r+1]``.  The
    ``(block_nnz, block_rows)`` 0/1 matrix is contracted against the
    slab's contributions on the MXU (at full f32 precision), which takes
    the place of a scatter-add (Mosaic has none).  Entries outside the
    row block — a neighbour's entries in a shared slab, or tail padding —
    match no row and contribute nothing.

The x gather happens in XLA before the kernel: ``X[ICOL]`` streams in as
a lane-dense ``(k, nnz)`` panel beside VAL, so the kernel needs no gather
and x is never pinned in VMEM.  SpMV is the ``k = 1`` case of the same
kernel body.

``slabs_per_block`` must statically bound ``ceil(span / block_nnz) + 1``
over all row blocks.  It is data-dependent, which is why the launch
geometry auto-tuner (``core/kernel_tune.py``) exists: tuning happens with
the concrete matrix in hand, and the winning :class:`TileGeometry` carries
the exact bound into traced hot paths.  Callers without a bound pass
``slabs_per_block=0`` and every row block sweeps every slab (always
correct, never fast) — see ``slabs_needed``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def slabs_needed(indptr, block_rows: int, block_nnz: int) -> int:
    """Exact ``slabs_per_block`` for a concrete IRP: the static per-row-block
    slab count that guarantees every nonzero is visited.  Slab starts are
    floor-aligned to ``block_nnz`` boundaries, so a block needs the slabs
    from ``floor(first / bn)`` through ``floor((last - 1) / bn)``."""
    ip = np.asarray(indptr)
    n_rows = ip.shape[0] - 1
    edges = ip[np.minimum(np.arange(0, n_rows + block_rows, block_rows),
                          n_rows)]
    starts, ends = edges[:-1], edges[1:]
    if starts.size == 0:
        return 1
    needed = np.where(ends > starts,
                      (ends - 1) // block_nnz - starts // block_nnz + 1, 1)
    return max(int(needed.max()), 1)


def _slab_schedule(indptr, r: int, block_rows: int, block_nnz: int,
                   total: int, slabs_per_block: int):
    """(spb, slab_start) for the (row_blocks, ..., spb) grid.  Tight slab
    starts are clamped to ``total - spb`` so the furthest reachable slab is
    always the last real one — a clamped window still covers its block's
    span (the span's last slab is < total), and no extra padding slabs
    exist."""
    if slabs_per_block:
        spb = min(slabs_per_block, total)
        start = jnp.asarray(indptr)[::block_rows][:r] // block_nnz
        return spb, jnp.minimum(start, total - spb)
    return total, jnp.zeros((r,), jnp.int32)


def _csr_kernel(slab_ref, start_ref, end_ref, data_ref, xg_ref, y_ref):
    i, j = pl.program_id(0), pl.program_id(2)
    bn = data_ref.shape[1]
    k = ((slab_ref[i] + j) * bn +
         jax.lax.broadcasted_iota(jnp.int32, (bn, 1), 0))
    onehot = ((k >= start_ref[...]) & (k < end_ref[...])
              ).astype(jnp.float32)                       # (bn, br)
    contrib = (data_ref[...].astype(jnp.float32) *
               xg_ref[...].astype(jnp.float32))           # (bk, bn)
    partial = jnp.dot(contrib, onehot,
                      preferred_element_type=jnp.float32,
                      precision=jax.lax.Precision.HIGHEST)

    @pl.when(j == 0)
    def _init():
        y_ref[...] = partial

    @pl.when(j != 0)
    def _acc():
        y_ref[...] = y_ref[...] + partial


@functools.partial(jax.jit, static_argnames=("block_rows", "block_nnz",
                                             "block_k", "slabs_per_block",
                                             "interpret"))
def csr_spmm_t(data: jax.Array, xg_t: jax.Array, indptr: jax.Array, *,
               block_rows: int = 256, block_nnz: int = 2048,
               block_k: int = 1, slabs_per_block: int = 0,
               interpret: bool = False) -> jax.Array:
    """Y^T = (A @ X)^T with A in CSR: ``data`` is VAL ``(nnz_pad,)`` (zeros
    past ``IRP[-1]``) and ``xg_t`` is the gathered ``X[ICOL]^T``,
    ``(k, nnz_pad)``.  ``nnz_pad`` must be a multiple of ``block_nnz``
    and ``k`` of ``block_k``.  Returns ``(k, row_blocks * block_rows)``
    float32; rows past ``n_rows`` are zero."""
    n_rows = indptr.shape[0] - 1
    kk, nnz_pad = xg_t.shape
    assert nnz_pad % block_nnz == 0 and kk % block_k == 0, (
        xg_t.shape, block_nnz, block_k)
    r = -(-n_rows // block_rows)
    total = nnz_pad // block_nnz
    spb, slab_start = _slab_schedule(indptr, r, block_rows, block_nnz,
                                     total, slabs_per_block)
    ip = jnp.asarray(indptr, jnp.int32)
    pad = r * block_rows - n_rows
    starts = jnp.pad(ip[:-1], (0, pad), constant_values=ip[-1])[None, :]
    ends = jnp.pad(ip[1:], (0, pad), constant_values=ip[-1])[None, :]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(r, kk // block_k, spb),
        in_specs=[
            pl.BlockSpec((1, block_rows), lambda i, c, j, s: (0, i)),
            pl.BlockSpec((1, block_rows), lambda i, c, j, s: (0, i)),
            pl.BlockSpec((1, block_nnz), lambda i, c, j, s: (0, s[i] + j)),
            pl.BlockSpec((block_k, block_nnz),
                         lambda i, c, j, s: (c, s[i] + j)),
        ],
        out_specs=pl.BlockSpec((block_k, block_rows),
                               lambda i, c, j, s: (c, i)),
    )
    return pl.pallas_call(
        _csr_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((kk, r * block_rows), jnp.float32),
        interpret=interpret,
        name="csr_spmm",
    )(slab_start.astype(jnp.int32), starts, ends, data[None, :], xg_t)
