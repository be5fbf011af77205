"""jit'd public wrappers for the Pallas kernels.

Each op is differentiable via ``jax.custom_vjp`` whose backward pass
recomputes through the pure-jnp oracle (``ref.py``) — the standard
flash-attention trick of trading recompute for never materializing the
forward's O(S^2) intermediates.  Forward runs the Pallas kernel, compiled
on a TPU backend and interpreted on any other (``repro.runtime``).

Model code reaches these through ``cfg.attention_impl == "pallas"``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ref as R
from repro.kernels.flash_attention import flash_attention as _flash_pallas
from repro.kernels.decode_attention import decode_attention as _decode_pallas
from repro.kernels.ssd_scan import ssd_scan as _ssd_pallas


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention(q, k, v, causal=True, window=0, q_offset=0):
    return _flash_pallas(q, k, v, causal=causal, window=window,
                         q_offset=q_offset)


def _fa_fwd(q, k, v, causal, window, q_offset):
    return flash_attention(q, k, v, causal, window, q_offset), (q, k, v)


def _fa_bwd(causal, window, q_offset, res, g):
    q, k, v = res
    _, vjp = jax.vjp(
        lambda q, k, v: R.flash_attention_ref(
            q, k, v, causal=causal, window=window, q_offset=q_offset),
        q, k, v)
    return vjp(g)


flash_attention.defvjp(_fa_fwd, _fa_bwd)


# ---------------------------------------------------------------------------
# decode attention (inference only; no vjp needed, but harmless to add)
# ---------------------------------------------------------------------------
def decode_attention(q, k_cache, v_cache, length):
    return _decode_pallas(q, k_cache, v_cache, length)


# ---------------------------------------------------------------------------
# SSD scan
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def ssd_scan(x, dt, a_log, b, c, chunk=128):
    return _ssd_pallas(x, dt, a_log, b, c, chunk=chunk)


def _ssd_fwd(x, dt, a_log, b, c, chunk):
    return ssd_scan(x, dt, a_log, b, c, chunk), (x, dt, a_log, b, c)


def _ssd_bwd(chunk, res, g):
    x, dt, a_log, b, c = res
    _, vjp = jax.vjp(lambda *a: R.ssd_scan_ref(*a), x, dt, a_log, b, c)
    return vjp(g)


ssd_scan.defvjp(_ssd_fwd, _ssd_bwd)
