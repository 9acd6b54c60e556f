"""Pallas kernels vs pure-jnp oracles (interpret=True on CPU).

Every kernel is swept over shapes and dtypes with assert_allclose against
ref.py, per the deliverable contract.
"""
import jax
import jax.numpy as jnp
import jax.random as jr
import numpy as np
import pytest

from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.ingest_norm.ops import ingest_norm
from repro.kernels.ingest_norm.ref import ingest_norm_ref
from repro.kernels.rmsnorm.ops import rmsnorm
from repro.kernels.rmsnorm.ref import rmsnorm_ref
from repro.kernels.rwkv6_wkv.ops import wkv
from repro.kernels.rwkv6_wkv.ref import wkv_ref

TOL = {jnp.float32: dict(rtol=1e-5, atol=1e-5), jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}


# -- rmsnorm -------------------------------------------------------------------


@pytest.mark.parametrize("shape", [(8, 128), (4, 16, 256), (1, 384), (130, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_matches_ref(shape, dtype):
    x = jr.normal(jr.PRNGKey(0), shape).astype(dtype)
    scale = jr.normal(jr.PRNGKey(1), (shape[-1],)).astype(dtype)
    got = rmsnorm(x, scale, interpret=True, block_rows=32)
    want = rmsnorm_ref(x, scale)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), **TOL[dtype]
    )


def test_rmsnorm_row_padding():
    x = jr.normal(jr.PRNGKey(0), (7, 128))  # 7 rows, block 4 -> pad to 8
    scale = jnp.ones((128,))
    got = rmsnorm(x, scale, interpret=True, block_rows=4)
    np.testing.assert_allclose(np.asarray(got), np.asarray(rmsnorm_ref(x, scale)), rtol=1e-5)


# -- flash attention -----------------------------------------------------------


@pytest.mark.parametrize("S,D,bq,bk", [(64, 32, 16, 16), (128, 64, 32, 64), (96, 32, 32, 32)])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_matches_ref(S, D, bq, bk, causal, dtype):
    B, H = 2, 3
    q = (jr.normal(jr.PRNGKey(0), (B, H, S, D)) / np.sqrt(D)).astype(dtype)
    k = (jr.normal(jr.PRNGKey(1), (B, H, S, D)) / np.sqrt(D)).astype(dtype)
    v = jr.normal(jr.PRNGKey(2), (B, H, S, D)).astype(dtype)
    got = flash_attention(q, k, v, causal=causal, interpret=True, block_q=bq, block_k=bk)
    want = attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), **TOL[dtype]
    )


def test_flash_gqa_head_expansion():
    B, Hq, Hkv, S, D = 2, 8, 2, 64, 32
    q = jr.normal(jr.PRNGKey(0), (B, Hq, S, D)) / np.sqrt(D)
    k = jr.normal(jr.PRNGKey(1), (B, Hkv, S, D)) / np.sqrt(D)
    v = jr.normal(jr.PRNGKey(2), (B, Hkv, S, D))
    got = flash_attention(q, k, v, causal=True, interpret=True, block_q=32, block_k=32)
    want = attention_ref(q, jnp.repeat(k, 4, 1), jnp.repeat(v, 4, 1), causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


def test_flash_odd_seq_padding():
    B, H, S, D = 1, 2, 50, 32  # S not a block multiple
    q = jr.normal(jr.PRNGKey(0), (B, H, S, D)) / np.sqrt(D)
    k = jr.normal(jr.PRNGKey(1), (B, H, S, D)) / np.sqrt(D)
    v = jr.normal(jr.PRNGKey(2), (B, H, S, D))
    got = flash_attention(q, k, v, causal=True, interpret=True, block_q=16, block_k=16)
    want = attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


# -- rwkv6 wkv -----------------------------------------------------------------


def _wkv_inputs(B, S, H, D, key=0):
    ks = jr.split(jr.PRNGKey(key), 5)
    r = jr.normal(ks[0], (B, S, H, D)) * 0.5
    k = jr.normal(ks[1], (B, S, H, D)) * 0.5
    v = jr.normal(ks[2], (B, S, H, D))
    w = jnp.exp(-jnp.exp(jr.normal(ks[3], (B, S, H, D)) * 0.5 - 0.6))
    u = jr.normal(ks[4], (H, D)) * 0.1
    return r, k, v, w, u


@pytest.mark.parametrize("S,chunk", [(32, 8), (64, 16), (48, 16), (40, 16)])
def test_wkv_matches_ref(S, chunk):
    B, H, D = 2, 3, 16
    r, k, v, w, u = _wkv_inputs(B, S, H, D)
    s0 = jnp.zeros((B, H, D, D))
    got_y, got_s = wkv(r, k, v, w, u, s0, chunk=chunk, interpret=True)
    to_bh = lambda a: a.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    ub = jnp.broadcast_to(u[None], (B, H, D)).reshape(B * H, D)
    want_y, want_s = wkv_ref(to_bh(r), to_bh(k), to_bh(v), to_bh(w), ub,
                             jnp.zeros((B * H, D, D)))
    want_y = want_y.reshape(B, H, S, D).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(got_y), np.asarray(want_y), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(
        np.asarray(got_s), np.asarray(want_s.reshape(B, H, D, D)), rtol=2e-4, atol=2e-4
    )


def test_wkv_nonzero_initial_state():
    B, S, H, D = 1, 16, 2, 8
    r, k, v, w, u = _wkv_inputs(B, S, H, D, key=5)
    s0 = jr.normal(jr.PRNGKey(9), (B, H, D, D)) * 0.3
    got_y, got_s = wkv(r, k, v, w, u, s0, chunk=8, interpret=True)
    to_bh = lambda a: a.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    ub = jnp.broadcast_to(u[None], (B, H, D)).reshape(B * H, D)
    want_y, want_s = wkv_ref(
        to_bh(r), to_bh(k), to_bh(v), to_bh(w), ub, s0.reshape(B * H, D, D)
    )
    want_y = want_y.reshape(B, H, S, D).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(got_y), np.asarray(want_y), rtol=5e-4, atol=5e-4)
    np.testing.assert_allclose(
        np.asarray(got_s), np.asarray(want_s.reshape(B, H, D, D)), rtol=5e-4, atol=5e-4
    )


def test_wkv_kernel_agrees_with_model_layer():
    """kernels/rwkv6_wkv is a drop-in for models.rwkv6.wkv_scan_chunked."""
    from repro.models.rwkv6 import wkv_scan_chunked

    B, S, H, D = 2, 32, 2, 16
    r, k, v, w, u = _wkv_inputs(B, S, H, D, key=7)
    s0 = jnp.zeros((B, H, D, D))
    ky, ks = wkv(r, k, v, w, u, s0, chunk=16, interpret=True)
    my, ms = wkv_scan_chunked(r, k, v, w, u, s0, chunk=16)
    np.testing.assert_allclose(np.asarray(ky), np.asarray(my), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(ks), np.asarray(ms), rtol=2e-4, atol=2e-4)


# -- ingest norm ---------------------------------------------------------------


@pytest.mark.parametrize("shape", [(2, 24, 24, 3), (1, 32, 16, 3), (4, 8, 8, 4)])
@pytest.mark.parametrize("out_dtype", [jnp.float32, jnp.bfloat16])
def test_ingest_norm_matches_ref(shape, out_dtype):
    img = jr.randint(jr.PRNGKey(0), shape, 0, 256).astype(jnp.uint8)
    C = shape[-1]
    mean = jnp.linspace(0.4, 0.5, C)
    std = jnp.linspace(0.2, 0.3, C)
    got = ingest_norm_ref(img, mean, std, out_dtype)  # oracle sanity
    kern = ingest_norm(img, mean, std, interpret=True).astype(out_dtype)
    np.testing.assert_allclose(
        np.asarray(kern, np.float32), np.asarray(got, np.float32),
        **TOL[out_dtype if out_dtype == jnp.bfloat16 else jnp.float32],
    )
    assert kern.shape == (shape[0], C, shape[1], shape[2])


def test_pallas_attention_wired_into_model():
    """cfg.attention_impl='pallas_interpret' routes train-time
    self-attention through the Pallas flash kernel (in interpret mode, as
    this runs on CPU) with matching loss."""
    import dataclasses

    import jax
    import repro.models.transformer as T
    from repro.config import get_arch

    cfg = get_arch("granite-8b", smoke=True)
    params = T.init_lm(jax.random.PRNGKey(0), cfg)
    batch = {
        "tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0,
                                     cfg.vocab_size),
        "targets": jax.random.randint(jax.random.PRNGKey(2), (2, 32), 0,
                                      cfg.vocab_size),
    }
    l_ref, _ = T.forward_train(params, batch, cfg)
    l_pal, _ = T.forward_train(
        params, batch, dataclasses.replace(cfg, attention_impl="pallas_interpret"))
    assert abs(float(l_ref) - float(l_pal)) < 5e-3
