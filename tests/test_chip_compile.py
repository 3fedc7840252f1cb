"""Every kernel of the kernel tier compiles for a TPU v5e, at Table-1
shapes, without a chip: the TPU compiler is installed and compiles for a
described ``v5e:2x2`` topology.  Covers ELL (both storage orders), SELL
and CSR, SpMV and SpMM, at the default launch geometry, and every tuner
candidate for one shape per format.  Nothing runs, so nothing here says
anything about results or times."""
import os

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core.formats import CSR, ELL, BucketedELL
from repro.core.kernel_tune import TileGeometry, candidate_geometries
from repro.kernels import ops
from repro.partition import HybridMatrix

BATCH = 32
# xenon2 (Table 1): 157,464 rows, band of at most 42 -> 48 slots
XENON2 = dict(n=157464, width=48)
# xenon2's two widest SELL buckets as the hybrid build cuts them
XENON2_SELL = ((2098, 48), (4638, 40))
# the largest ELL-Row blocks of the solve cells' hybrids, (rows, width,
# columns): xenon2's 25-wide block, torso1's 37-wide block and its
# 4,959-wide block of heavy rows
RUN_BLOCKS = ((15435, 25, 157464), (114801, 37, 116158), (857, 4959, 116158))
# the published row counts of the solve cells' configurations: xenon2,
# torso1, chem_master1 (Table 1)
SOLVE_ROWS = (157464, 116158, 40401)
# torso1 (Table 1): 116,158 rows, 8,516,500 nonzeros (8-padded)
TORSO1 = dict(n=116158, nnz_pad=8516504)
# a static slab bound of torso1's order at the default tiles
TORSO1_SLABS = 12


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no TPU compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """A described v5e chip, with the persistent compilation cache off
    (a described-device compile is written to it but can never be read
    back without a chip)."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _sds(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _ell(sh, n, width, order="row", n_cols=None):
    shape = (n, width) if order == "row" else (width, n)
    return ELL(data=_sds(sh, shape), cols=_sds(sh, shape, jnp.int32),
               shape=(n, n_cols or n), nnz=n * width, order=order)


def _sell(sh, n, buckets):
    rows = sum(r for r, _ in buckets)
    offs = tuple(int(o) for o in np.cumsum([0] + [r for r, _ in buckets])[:-1])
    return BucketedELL(
        perm=_sds(sh, (rows,), jnp.int32),
        buckets=tuple(ELL(data=_sds(sh, (r, w)),
                          cols=_sds(sh, (r, w), jnp.int32),
                          shape=(r, n), nnz=r * w) for r, w in buckets),
        row_offsets=offs, shape=(rows, n), nnz=sum(r * w for r, w in buckets))


def _csr(sh, n, nnz_pad):
    return CSR(data=_sds(sh, (nnz_pad,)), cols=_sds(sh, (nnz_pad,), jnp.int32),
               indptr=_sds(sh, (n + 1,), jnp.int32), shape=(n, n),
               nnz=nnz_pad)


def _compile(fn, m, sh, op, tuning=None):
    n_cols = m.shape[1]
    x = _sds(sh, (n_cols,) if op == "spmv" else (n_cols, BATCH))
    compiled = jax.jit(lambda mm, v: fn(mm, v, interpret=False,
                                        tuning=tuning)).lower(m, x).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


OPS = ("spmv", "spmm")
IMPLS = {("ell", "spmv"): ops.spmv_ell, ("ell", "spmm"): ops.spmm_ell,
         ("sell", "spmv"): ops.spmv_sell, ("sell", "spmm"): ops.spmm_sell,
         ("csr", "spmv"): ops.spmv_csr, ("csr", "spmm"): ops.spmm_csr}


# ---------------------------------------------------------------------------
# default launch geometry at Table-1 shapes
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("op", OPS)
@pytest.mark.parametrize("order", ["row", "col"])
def test_ell_default_compiles(one_chip, order, op):
    _compile(IMPLS["ell", op], _ell(one_chip, XENON2["n"], XENON2["width"],
                                    order), one_chip, op)


@pytest.mark.parametrize("op", OPS)
def test_sell_default_compiles(one_chip, op):
    _compile(IMPLS["sell", op], _sell(one_chip, XENON2["n"], XENON2_SELL),
             one_chip, op)


@pytest.mark.parametrize("op", OPS)
def test_csr_default_compiles(one_chip, op):
    _compile(IMPLS["csr", op], _csr(one_chip, **TORSO1), one_chip, op,
             TileGeometry(slabs_per_block=TORSO1_SLABS))


@pytest.mark.parametrize("n,width,n_cols", RUN_BLOCKS)
def test_ell_run_gather_compiles(one_chip, n, width, n_cols):
    """The SpMV path at the block shapes the solve cells run: the block's
    columns choose, on the device, between the gather by 8-wide column
    runs and the gather of one element per slot."""
    hlo = _compile(IMPLS["ell", "spmv"],
                   _ell(one_chip, n, width, n_cols=n_cols), one_chip,
                   "spmv").as_text()
    assert "conditional" in hlo


@pytest.mark.parametrize("n", SOLVE_ROWS)
def test_hybrid_sort_reassembly_compiles(one_chip, n):
    """A length-sorted hybrid's SpMV at a solve cell's row count: two
    ELL-Row blocks, their outputs put back in row order by one key-value
    sort on ``perm``, with no scatter left."""
    cut = n // 3
    hyb = HybridMatrix(
        perm=_sds(one_chip, (n,), jnp.int32),
        blocks=(_ell(one_chip, cut, 16, n_cols=n),
                _ell(one_chip, n - cut, 8, n_cols=n)),
        row_offsets=(0, cut), formats=("ell_row", "ell_row"), shape=(n, n),
        nnz=cut * 16 + (n - cut) * 8)
    hlo = _compile(ops.spmv_hybrid, hyb, one_chip, "spmv").as_text()
    assert "sort(" in hlo and "scatter(" not in hlo


# ---------------------------------------------------------------------------
# every tuner candidate for one shape per format
# ---------------------------------------------------------------------------
def _grid(fmt, op):
    if fmt == "csr":
        return candidate_geometries("csr", op, n_rows=TORSO1["n"],
                                    nnz_pad=TORSO1["nnz_pad"], batch=BATCH)
    return candidate_geometries(fmt, op, n_rows=XENON2["n"],
                                width=XENON2["width"], batch=BATCH)


CANDIDATES = [(fmt, op, g) for fmt in ("ell_row", "csr") for op in OPS
              for g in _grid(fmt, op)]


@pytest.mark.parametrize(
    "fmt,op,g", CANDIDATES,
    ids=[f"{f}-{o}-" + "-".join(f"{k}{v}" for k, v in g.to_dict().items())
         for f, o, g in CANDIDATES])
def test_every_candidate_compiles(one_chip, fmt, op, g):
    if fmt == "csr":
        m = _csr(one_chip, **TORSO1)
        g = TileGeometry(**g.to_dict(), slabs_per_block=TORSO1_SLABS)
        _compile(IMPLS["csr", op], m, one_chip, op, g)
    else:
        _compile(IMPLS["ell", op], _ell(one_chip, **XENON2), one_chip, op, g)


def test_candidate_grids_are_nonempty():
    for fmt in ("ell_row", "ell_col", "sell", "csr"):
        for op in OPS:
            assert _grid(fmt, op), (fmt, op)
