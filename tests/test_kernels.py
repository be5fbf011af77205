"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret=True)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.ref import (decode_attention_ref, flash_attention_ref,
                               ssd_scan_ref)
from repro.kernels.ssd_scan import ssd_scan

TOL = {jnp.float32: dict(rtol=2e-5, atol=2e-5),
       jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}


def qkv(key, B, Sq, Skv, H, KV, HD, dtype):
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, Sq, H, HD), dtype)
    k = jax.random.normal(ks[1], (B, Skv, KV, HD), dtype)
    v = jax.random.normal(ks[2], (B, Skv, KV, HD), dtype)
    return q, k, v


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,Sq,Skv,H,KV,HD,causal,window", [
    (2, 256, 256, 4, 2, 64, True, 0),      # GQA causal
    (1, 128, 128, 8, 8, 128, True, 0),     # MHA, MXU-wide head
    (2, 256, 256, 4, 2, 64, False, 0),     # bidirectional (encoder)
    (1, 256, 256, 4, 1, 64, True, 128),    # MQA + sliding window
    (1, 384, 384, 2, 2, 32, True, 0),      # non-128 block tail (384=3*128)
])
def test_flash_attention_sweep(dtype, B, Sq, Skv, H, KV, HD, causal, window):
    q, k, v = qkv(jax.random.key(0), B, Sq, Skv, H, KV, HD, dtype)
    out = flash_attention(q, k, v, causal=causal, window=window)
    ref = flash_attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **TOL[dtype])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,H,KV,HD,length", [
    (2, 512, 8, 2, 64, 512),
    (2, 512, 8, 2, 64, 300),      # partially-valid (ring) cache
    (1, 1024, 4, 4, 128, 777),
    (4, 256, 2, 1, 64, 1),        # single valid entry
])
def test_decode_attention_sweep(dtype, B, S, H, KV, HD, length):
    ks = jax.random.split(jax.random.key(1), 3)
    q = jax.random.normal(ks[0], (B, 1, H, HD), dtype)
    kc = jax.random.normal(ks[1], (B, S, KV, HD), dtype)
    vc = jax.random.normal(ks[2], (B, S, KV, HD), dtype)
    out = decode_attention(q, kc, vc, length)
    ref = decode_attention_ref(q, kc, vc, length)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **TOL[dtype])


@pytest.mark.parametrize("B,L,H,P,N,chunk", [
    (2, 128, 4, 8, 16, 32),
    (1, 256, 2, 64, 64, 64),
    (2, 64, 3, 16, 8, 64),        # chunk == L
    (1, 512, 1, 32, 128, 128),    # long sequence, wide state
])
def test_ssd_scan_sweep(B, L, H, P, N, chunk):
    ks = jax.random.split(jax.random.key(2), 5)
    x = jax.random.normal(ks[0], (B, L, H, P), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, L, H)))
    a_log = jax.random.normal(ks[2], (H,)) * 0.5
    b = jax.random.normal(ks[3], (B, L, N)) * 0.3
    c = jax.random.normal(ks[4], (B, L, N)) * 0.3
    out = ssd_scan(x, dt, a_log, b, c, chunk=chunk)
    ref = ssd_scan_ref(x, dt, a_log, b, c)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=5e-4, atol=5e-4)


def test_ops_wrappers_differentiable():
    """custom_vjp wrappers: kernel forward + oracle-recompute backward."""
    from repro.kernels import ops

    ks = jax.random.split(jax.random.key(3), 3)
    q, k, v = qkv(jax.random.key(0), 1, 128, 128, 4, 2, 32, jnp.float32)

    def f(q, k, v):
        return ops.flash_attention(q, k, v, True, 0, 0).sum()

    g_kernel = jax.grad(f, argnums=(0, 1, 2))(q, k, v)

    def f_ref(q, k, v):
        return flash_attention_ref(q, k, v, causal=True).sum()

    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_kernel, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-5)


def test_flash_fully_masked_rows_are_zero():
    """window smaller than block: early rows attend to nothing beyond
    causal+window -> finite outputs, no NaN."""
    q, k, v = qkv(jax.random.key(4), 1, 256, 256, 2, 2, 32, jnp.float32)
    out = flash_attention(q, k, v, causal=True, window=16)
    assert np.isfinite(np.asarray(out)).all()


@pytest.mark.parametrize("backend,expected", [("cpu", True), ("gpu", True),
                                              ("tpu", False)])
def test_interpret_default_follows_platform(monkeypatch, backend, expected):
    """``interpret=None`` interprets on every backend but the TPU; an
    explicit value always wins."""
    from repro.runtime import kernel_interpret

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert kernel_interpret(None) is expected
    assert kernel_interpret(True) is True
    assert kernel_interpret(False) is False


def test_kernel_on_tpu_backend_is_never_interpreted(monkeypatch):
    """With the backend steered to TPU, the default path hands Mosaic a
    compiled kernel — which the CPU backend then refuses — instead of
    quietly interpreting.  (A shape no other test traces, so the jit cache
    holds no interpreted program for it.)"""
    q, k, v = qkv(jax.random.key(5), 1, 120, 120, 2, 1, 8, jnp.float32)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match="interpret mode"):
        flash_attention(q, k, v)
