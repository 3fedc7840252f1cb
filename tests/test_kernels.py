"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps, interpret=True."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

pytest.importorskip("hypothesis", reason="property tests need hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core import csr_from_dense, host_csr_to_ell, host_csr_to_sell
from repro.kernels import ops, ref


def random_dense(rng, n_rows, n_cols, density, dtype=np.float32):
    d = (rng.random((n_rows, n_cols)) < density).astype(dtype)
    return d * rng.normal(1.0, 1.0, size=d.shape).astype(dtype)


@pytest.fixture(scope="module")
def rng():
    return np.random.default_rng(7)


def tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# ELL SpMV: aligned + ragged shapes, f32 + bf16
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("n_rows,width,n_cols", [
    (256, 128, 512),     # exactly one block
    (512, 256, 300),     # multi-block both axes
    (8, 8, 16),          # minimum tile
    (100, 37, 61),       # ragged -> wrapper pads
    (1024, 5, 2048),     # skinny band
])
def test_ell_spmv_kernel(rng, n_rows, width, n_cols, dtype):
    data = rng.normal(size=(n_rows, width)).astype(np.float32)
    mask = rng.random((n_rows, width)) < 0.7
    data = np.where(mask, data, 0.0)
    cols = np.where(mask, rng.integers(0, n_cols, (n_rows, width)), 0)
    x = rng.normal(size=(n_cols,)).astype(np.float32)
    d, c, xx = (jnp.asarray(data, dtype), jnp.asarray(cols, jnp.int32),
                jnp.asarray(x, dtype))
    got = ops.ell_spmv_raw(d, c, xx, interpret=True)
    want = ref.ell_spmv_ref(d, c, xx)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **tol(dtype))


@pytest.mark.parametrize("n_rows,width,n_cols,k", [
    (128, 128, 256, 128),
    (64, 40, 100, 17),
    (8, 8, 8, 8),
])
def test_ell_spmm_kernel(rng, n_rows, width, n_cols, k):
    data = rng.normal(size=(n_rows, width)).astype(np.float32)
    cols = rng.integers(0, n_cols, (n_rows, width)).astype(np.int32)
    x = rng.normal(size=(n_cols, k)).astype(np.float32)
    got = ops.ell_spmm_raw(jnp.asarray(data), jnp.asarray(cols),
                           jnp.asarray(x), interpret=True)
    want = ref.ell_spmm_ref(jnp.asarray(data), jnp.asarray(cols),
                            jnp.asarray(x))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# format-level kernels vs dense oracle (all formats through one matrix)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("impl,transform", [
    (ops.spmv_csr, lambda m: m),
    (ops.spmv_ell, host_csr_to_ell),
    (ops.spmv_ell, lambda m: host_csr_to_ell(m, order="col")),
    (ops.spmv_sell, host_csr_to_sell),
], ids=["csr", "ell_row", "ell_col", "sell"])
def test_format_kernels_vs_dense(rng, impl, transform):
    dense = random_dense(rng, 200, 150, 0.08)
    m = transform(csr_from_dense(dense, pad=8))
    x = rng.normal(size=150).astype(np.float32)
    got = impl(m, jnp.asarray(x), interpret=True)
    np.testing.assert_allclose(np.asarray(got), dense @ x,
                               rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# property sweep: kernel == oracle on random ELL structures
# ---------------------------------------------------------------------------
@settings(deadline=None, max_examples=15)
@given(seed=st.integers(0, 2**31 - 1), n_rows=st.integers(1, 300),
       width=st.integers(1, 150), n_cols=st.integers(1, 400))
def test_property_ell_kernel(seed, n_rows, width, n_cols):
    r = np.random.default_rng(seed)
    data = jnp.asarray(r.normal(size=(n_rows, width)).astype(np.float32))
    cols = jnp.asarray(r.integers(0, n_cols, (n_rows, width)), jnp.int32)
    x = jnp.asarray(r.normal(size=n_cols).astype(np.float32))
    got = ops.ell_spmv_raw(data, cols, x, interpret=True)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(ref.ell_spmv_ref(data, cols, x)),
                               rtol=1e-3, atol=1e-3)


# ---------------------------------------------------------------------------
# gradients through the differentiable wrapper
# ---------------------------------------------------------------------------
def test_ell_spmv_ad_grads(rng):
    n_rows, width, n_cols = 32, 16, 48
    data = rng.normal(size=(n_rows, width)).astype(np.float32)
    cols = rng.integers(0, n_cols, (n_rows, width)).astype(np.int32)
    x = rng.normal(size=n_cols).astype(np.float32)
    d, c, xx = jnp.asarray(data), jnp.asarray(cols), jnp.asarray(x)

    def loss_kernel(dd, v):
        return jnp.sum(ops.ell_spmv_ad(dd, c, v) ** 2)

    def loss_ref(dd, v):
        return jnp.sum(ref.ell_spmv_ref(dd, c, v) ** 2)

    gd_k, gx_k = jax.grad(loss_kernel, argnums=(0, 1))(d, xx)
    gd_r, gx_r = jax.grad(loss_ref, argnums=(0, 1))(d, xx)
    np.testing.assert_allclose(np.asarray(gd_k), np.asarray(gd_r),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(gx_k), np.asarray(gx_r),
                               rtol=1e-4, atol=1e-4)


def test_kernel_autotune_integration(rng):
    """The auto-tuner runs end-to-end with kernel impls plugged in."""
    from repro.core import offline_phase
    from repro.core.suite import paper_suite
    suite = paper_suite(scale=0.01, include=["wang3", "memplus"])
    db = offline_phase(suite, formats=("ell_row",), iters=1,
                       spmv_impls=ops.KERNEL_SPMV_IMPLS, machine="kernel-cpu")
    assert "ell_row" in db.d_star
    assert all("ell_row" in r.formats for r in db.records)

