"""The native row-segmented CSR Pallas kernel vs the dense oracle: SpMV +
SpMM for B in {1, 3, 128}, ragged shapes, geometry sweeps, and the traced
(full-sweep / tuned-bound) launch modes."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core.kernel_tune import TileGeometry
from repro.core.transform import csr_from_dense
from repro.kernels import ops
from repro.kernels.csr_spmv import slabs_needed


def random_dense(rng, n_rows, n_cols, density):
    d = (rng.random((n_rows, n_cols)) < density).astype(np.float32)
    return d * rng.normal(1.0, 1.0, size=d.shape).astype(np.float32)


@pytest.fixture(scope="module")
def rng():
    return np.random.default_rng(23)


TOL = dict(rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# CSR native kernel
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n_rows,n_cols,density", [
    (256, 256, 0.05),    # aligned
    (100, 61, 0.2),      # ragged, denser
    (513, 37, 0.02),     # ragged rows, skinny
    (8, 8, 0.5),         # minimum tile
])
def test_csr_spmv_vs_dense(rng, n_rows, n_cols, density):
    dense = random_dense(rng, n_rows, n_cols, density)
    m = csr_from_dense(dense, pad=8)
    x = rng.normal(size=n_cols).astype(np.float32)
    got = ops.spmv_csr(m, jnp.asarray(x), interpret=True)
    np.testing.assert_allclose(np.asarray(got), dense @ x, **TOL)


@pytest.mark.parametrize("batch", [1, 3, 128])
def test_csr_spmm_vs_dense(rng, batch):
    dense = random_dense(rng, 150, 90, 0.1)
    m = csr_from_dense(dense, pad=8)
    X = rng.normal(size=(90, batch)).astype(np.float32)
    got = ops.spmm_csr(m, jnp.asarray(X), interpret=True)
    np.testing.assert_allclose(np.asarray(got), dense @ X, **TOL)


@pytest.mark.parametrize("g", [
    TileGeometry(block_rows=8, block_nnz=1024),
    TileGeometry(block_rows=64, block_nnz=1024),
    TileGeometry(block_rows=512, block_nnz=8192),
    TileGeometry(block_rows=32, block_w=8, block_nnz=2048, block_k=8),
], ids=["r8", "r64", "r512-bn8192", "spmm-k8"])
def test_csr_geometry_sweep(rng, g):
    dense = random_dense(rng, 200, 120, 0.15)
    m = csr_from_dense(dense, pad=8)
    x = rng.normal(size=120).astype(np.float32)
    X = rng.normal(size=(120, 5)).astype(np.float32)
    got = ops.spmv_csr(m, jnp.asarray(x), interpret=True, tuning=g)
    np.testing.assert_allclose(np.asarray(got), dense @ x, **TOL)
    gotm = ops.spmm_csr(m, jnp.asarray(X), interpret=True, tuning=g)
    np.testing.assert_allclose(np.asarray(gotm), dense @ X, **TOL)


def test_csr_traced_full_sweep_and_tuned_bound(rng):
    """Under jit the index structure is abstract: with no geometry the
    kernel takes the always-correct full slab sweep; a tuned geometry
    carries the exact static slab bound into the trace."""
    dense = random_dense(rng, 120, 80, 0.1)
    m = csr_from_dense(dense, pad=8)
    x = jnp.asarray(rng.normal(size=80).astype(np.float32))
    y0 = jax.jit(lambda mm, v: ops.spmv_csr(mm, v, interpret=True))(m, x)
    np.testing.assert_allclose(np.asarray(y0), dense @ np.asarray(x), **TOL)
    g = TileGeometry(block_rows=64, block_nnz=1024,
                     slabs_per_block=slabs_needed(m.indptr, 64, 1024))
    y1 = jax.jit(lambda mm, v: ops.spmv_csr(mm, v, interpret=True,
                                            tuning=g))(m, x)
    np.testing.assert_allclose(np.asarray(y1), dense @ np.asarray(x), **TOL)


def test_csr_heavy_tail_rows(rng):
    """A few very long rows (the memplus/torso pathology) still fit the
    per-row-block slab coverage."""
    n_rows, n_cols = 128, 200
    dense = np.zeros((n_rows, n_cols), np.float32)
    dense[5, :] = rng.normal(size=n_cols)           # one dense row
    dense[70, :150] = rng.normal(size=150)
    mask = rng.random((n_rows, n_cols)) < 0.01      # sparse elsewhere
    dense += mask * rng.normal(size=dense.shape).astype(np.float32)
    m = csr_from_dense(dense.astype(np.float32), pad=8)
    x = rng.normal(size=n_cols).astype(np.float32)
    got = ops.spmv_csr(m, jnp.asarray(x), interpret=True,
                       tuning=TileGeometry(block_rows=32, block_nnz=64))
    np.testing.assert_allclose(np.asarray(got), dense @ x, **TOL)


def test_csr_big_matrix_geometry_on_tiny_matrix(rng, monkeypatch):
    """A D_mat-nearest geometry recorded on a much larger matrix may carry
    a block_nnz far beyond this matrix's nnz_pad; the wrapper must clamp
    it to the matrix (it used to be the only knob passed through _geom
    with no cap, silently inflating every slab to the foreign size)."""
    dense = random_dense(rng, 24, 16, 0.3)
    m = csr_from_dense(dense, pad=8)
    x = rng.normal(size=16).astype(np.float32)
    X = rng.normal(size=(16, 3)).astype(np.float32)
    big = TileGeometry(block_rows=512, block_nnz=65536)
    seen = []
    orig = ops._csr.csr_spmm_t

    def spy(*args, **kw):
        seen.append(kw["block_nnz"])
        return orig(*args, **kw)

    monkeypatch.setattr(ops._csr, "csr_spmm_t", spy)
    got = ops.spmv_csr(m, jnp.asarray(x), interpret=True, tuning=big)
    np.testing.assert_allclose(np.asarray(got), dense @ x, **TOL)
    gotm = ops.spmm_csr(m, jnp.asarray(X), interpret=True, tuning=big)
    np.testing.assert_allclose(np.asarray(gotm), dense @ X, **TOL)
    assert seen and all(bn <= ops._align8(m.nnz_pad) for bn in seen), seen


def test_slabs_needed_exact(rng):
    indptr = np.array([0, 3, 3, 10, 64, 64, 64, 65, 130], np.int32)
    # blocks of 4 rows, slab 64: block0 covers slab {0}, block1 slabs {1,2}
    assert slabs_needed(indptr, 4, 64) == 2
    assert slabs_needed(indptr, 8, 64) == 3  # one block over slabs {0,1,2}
    assert slabs_needed(np.array([0, 0], np.int32), 8, 64) == 1


# ---------------------------------------------------------------------------
# the registry serves the native kernels (no COO detour, no reference CCS)
# ---------------------------------------------------------------------------
def test_registry_serves_native_csr_ccs_and_bcsr():
    """CSR is served by its native kernel; CCS and BCSR have no kernel the
    TPU compiler accepts, so they resolve to the reference tier and say
    so."""
    from repro.core import dispatch
    assert dispatch.get_impl("csr", "spmv", tier="kernel") is ops.spmv_csr
    assert dispatch.get_impl("csr", "spmm", tier="kernel") is ops.spmm_csr
    for fmt in ("ccs", "bcsr"):
        for op in ("spmv", "spmm"):
            assert not dispatch.has_impl(fmt, op, tier="kernel")
            assert dispatch.resolve_impl(fmt, op, tier="kernel")[1] \
                == "reference"


def test_block_sizes_covers_narrow_band_tightly():
    """8 < width < 128 used to pad the band to 128 lanes (up to 16x wasted
    work per tile); now the tile is the smallest aligned cover."""
    assert ops._block_sizes(100, 40) == (104, 40)
    assert ops._block_sizes(1000, 8) == (256, 8)
    assert ops._block_sizes(1000, 9) == (256, 16)
    assert ops._block_sizes(1000, 500) == (256, 128)
