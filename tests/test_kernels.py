"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode
(the kernel body executes in Python on CPU; BlockSpecs and grids are real)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


def _rand(key, shape, dtype):
    x = jax.random.normal(key, shape, jnp.float32)
    return x.astype(dtype)


@pytest.mark.parametrize("B,H,S,hd", [(1, 2, 128, 64), (2, 1, 256, 128),
                                      (1, 4, 384, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_sweep(B, H, S, hd, dtype, causal):
    ks = jax.random.split(jax.random.key(0), 3)
    q = _rand(ks[0], (B, H, S, hd), dtype)
    k = _rand(ks[1], (B, H, S, hd), dtype)
    v = _rand(ks[2], (B, H, S, hd), dtype)
    got = ops.flash_attention(q, k, v, causal=causal, block_q=128, block_k=128,
                              interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=causal)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("H,W,br", [(18, 32, 8), (34, 130, 4), (10, 16, 8)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_stencil_pipeline_sweep(H, W, br, dtype):
    key = jax.random.key(1)
    img = _rand(key, (H, W), dtype)
    wx = jnp.asarray([0.25, 0.5, 0.25], dtype)
    wy = jnp.asarray([0.25, 0.5, 0.25], dtype)
    got = ops.stencil_pipeline(img, wx, wy, block_rows=br, interpret=True)
    want = ref.stencil_pipeline_ref(img.astype(jnp.float32),
                                    wx.astype(jnp.float32),
                                    wy.astype(jnp.float32))
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want), rtol=tol, atol=tol)


def test_stencil_pipeline_single_implementation():
    """ops.stencil_pipeline and stencil_pipeline.stencil_pipeline used to be
    two diverging definitions; both import paths must now resolve to the
    same function object."""
    from repro.kernels import stencil_pipeline as spmod
    assert ops.stencil_pipeline is spmod.stencil_pipeline
    assert ops.ilp_halo_rows is spmod.ilp_halo_rows
    assert ops.stencil_dse_config is spmod.stencil_dse_config


def test_stencil_config_from_dse_sweep():
    """The kernel's block/halo config is read off the generated kernel of
    the DSE knee point (emit_pallas): the winning fusion's row shift is the
    halo.  It must agree with the (demoted, fallback-only) fixed probe for
    the 3-tap chain — and must actually have COME from the sweep, not from
    the fallback quietly returning the same values.  The old entry point
    survives as a deprecated wrapper with the same values."""
    from repro.core.codegen import SUBLANE_ROWS
    from repro.kernels.stencil_pipeline import (_stencil_codegen_config,
                                                stencil_config_source)
    block_rows, halo = _stencil_codegen_config()
    assert stencil_config_source() == "dse"
    assert halo == 2 == ops.ilp_halo_rows(3)
    assert block_rows >= SUBLANE_ROWS and block_rows % SUBLANE_ROWS == 0
    with pytest.warns(DeprecationWarning, match="emit_pallas"):
        assert ops.stencil_dse_config() == (block_rows, halo)


def test_stencil_pipeline_dse_default_config():
    """Calling the kernel without an explicit block/halo must route through
    the DSE-derived config and still match the oracle."""
    key = jax.random.key(4)
    img = _rand(key, (18, 34), jnp.float32)
    wx = jnp.asarray([0.25, 0.5, 0.25])
    wy = jnp.asarray([0.2, 0.6, 0.2])
    got = ops.stencil_pipeline(img, wx, wy, interpret=True)
    want = ref.stencil_pipeline_ref(img, wx, wy)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("B,H,S,hd,chunk", [(1, 2, 128, 64, 64),
                                            (2, 1, 256, 32, 32),
                                            (1, 1, 64, 16, 16)])
def test_wkv6_sweep(B, H, S, hd, chunk):
    ks = jax.random.split(jax.random.key(2), 4)
    r = _rand(ks[0], (B, H, S, hd), jnp.float32)
    k = _rand(ks[1], (B, H, S, hd), jnp.float32)
    v = _rand(ks[2], (B, H, S, hd), jnp.float32)
    w = jax.nn.sigmoid(_rand(ks[3], (B, H, S, hd), jnp.float32)) * 0.5 + 0.45
    u = _rand(jax.random.key(3), (H, hd), jnp.float32) * 0.1
    got = ops.wkv6(r, k, v, w, u, chunk=chunk, interpret=True)
    want, _ = ref.wkv6_ref(r, k, v, w, u)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_wkv6_matches_model_layer():
    """The kernel must agree with the chunked jnp implementation used by the
    rwkv6 model layer (same math, different engine)."""
    import dataclasses
    from repro.config import get_config
    from repro.models import layers as L

    cfg = dataclasses.replace(get_config("rwkv6_3b", reduced=True),
                              dtype="float32")
    B, S = 1, 64
    D = cfg.d_model
    Hh = D // cfg.rwkv_head_dim
    hd = cfg.rwkv_head_dim
    ks = jax.random.split(jax.random.key(5), 4)
    r, k, v = (jax.random.normal(ks[i], (B, Hh, S, hd)) for i in range(3))
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (B, Hh, S, hd))) * 0.5 + 0.45
    u = jnp.zeros((Hh, hd))
    s0 = jnp.zeros((B, Hh, hd, hd))
    out_model, _ = L._wkv_chunk(r, k, v, w, u, s0)
    out_kernel = ops.wkv6(r, k, v, w, u, chunk=S, interpret=True)
    np.testing.assert_allclose(np.asarray(out_kernel), np.asarray(out_model),
                               rtol=1e-4, atol=1e-4)
