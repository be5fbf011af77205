"""Flash attention (causal, GQA, optional sliding window) — Pallas TPU.

Tiles are MXU-aligned (q/k blocks are multiples of 128 where shapes
allow), the online-softmax running state (m, l, acc) lives in VMEM scratch
and persists across the innermost (arbitrary-semantics) K-block grid
dimension, and the K/V stream stays in (block_k, HD) tiles so the working
set is ~4 * block * HD * dtype bytes — far under v5e VMEM at the default
128x128 tiling.

The wrapper puts the head axis before the sequence axis, so every block's
last two dims are (block, HD) as the TPU lowering requires; the transposes
cost O(S * H * HD), against the kernel's O(S^2 * H * HD).

Grid: (B, H, nQ, nK), nK innermost/sequential.  Causal skipping is done by
masking; fully-masked K blocks are not pruned.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.runtime import kernel_interpret

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_sc, l_sc, acc_sc, *,
            block_q, block_k, n_k, causal, window, q_offset, scale):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    q = q_ref[...]                              # (block_q, HD)
    k = k_ref[...]                              # (block_k, HD)
    v = v_ref[...]                              # (block_k, HD)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    q_pos = q_offset + iq * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    k_pos = ik * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    mask = jnp.ones((block_q, block_k), jnp.bool_)
    if causal:
        mask &= q_pos >= k_pos
    if window:
        mask &= (q_pos - k_pos) < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_sc[...]                          # (block_q, 1)
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_cur)
    p = jnp.exp(s - m_cur)
    p = jnp.where(mask, p, 0.0)

    l_sc[...] = l_sc[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_sc[...] = acc_sc[...] * alpha + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_sc[...] = m_cur

    @pl.when(ik == n_k - 1)
    def _finish():
        denom = jnp.maximum(l_sc[...], 1e-30)
        o_ref[...] = (acc_sc[...] / denom).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "q_offset", "block_q",
                              "block_k", "interpret"))
def flash_attention(q, k, v, *, causal=True, window=0, q_offset=0,
                    block_q=128, block_k=128, interpret=None):
    """q: (B, Sq, H, HD); k, v: (B, Skv, KV, HD) -> (B, Sq, H, HD).

    ``interpret=None`` compiles on a TPU backend and interprets elsewhere."""
    b, sq, h, hd = q.shape
    skv, kv = k.shape[1], k.shape[2]
    g = h // kv
    scale = 1.0 / math.sqrt(hd)
    block_q = min(block_q, sq)
    block_k = min(block_k, skv)
    assert sq % block_q == 0 and skv % block_k == 0, (sq, skv, block_q, block_k)
    n_q, n_k = sq // block_q, skv // block_k

    grid = (b, h, n_q, n_k)
    kernel = functools.partial(
        _kernel, block_q=block_q, block_k=block_k, n_k=n_k, causal=causal,
        window=window, q_offset=q_offset, scale=scale)
    # head-major: (B, heads, S, HD)
    qt, kt, vt = (jnp.swapaxes(a, 1, 2) for a in (q, k, v))

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, None, block_q, hd),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((None, None, block_k, hd),
                         lambda bi, hi, qi, ki, g=g: (bi, hi // g, ki, 0)),
            pl.BlockSpec((None, None, block_k, hd),
                         lambda bi, hi, qi, ki, g=g: (bi, hi // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, block_q, hd),
                               lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=kernel_interpret(interpret),
    )(qt, kt, vt)
    return jnp.swapaxes(out, 1, 2)
