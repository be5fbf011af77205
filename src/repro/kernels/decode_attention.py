"""Single-token (decode) attention over a KV cache — Pallas TPU.

Flash-decoding-style split-K: the cache's sequence axis is tiled into
``block_s`` blocks; the grid walks them innermost while (m, l, acc) online-
softmax state for all Q heads of one KV head persists in VMEM scratch.
Entries at index >= ``length`` (ring validity) are masked.

q is tiny ((G, HD) per grid step), so the kernel is bandwidth-bound on the
K/V stream; the block size keeps each VMEM tile at block_s * HD * 2B.

The wrapper puts the KV-head axis of the caches before the sequence axis,
so each K/V block's last two dims are (block_s, HD) as the TPU lowering
requires.  That transpose copies the whole cache on every call — as many
bytes as the kernel reads — so a served decode path would keep its cache
head-major instead.

Grid: (B, KV, nS); ``length`` arrives as a scalar-prefetch operand (SMEM).
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


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_sc, l_sc, acc_sc, *,
            block_s, n_s, scale):
    si = pl.program_id(2)

    @pl.when(si == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    q = q_ref[...]                              # (G, HD)
    k = k_ref[...]                              # (block_s, HD)
    v = v_ref[...]                              # (block_s, HD)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    pos = si * block_s + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = pos < len_ref[0]
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_sc[...]                          # (G, 1)
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_cur)
    p = jnp.where(mask, jnp.exp(s - m_cur), 0.0)

    l_sc[...] = l_sc[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_sc[...] = acc_sc[...] * alpha + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_sc[...] = m_cur

    @pl.when(si == n_s - 1)
    def _finish():
        denom = jnp.maximum(l_sc[...], 1e-30)
        o_ref[...] = (acc_sc[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("block_s", "interpret"))
def decode_attention(q, k_cache, v_cache, length, *, block_s=256,
                     interpret=None):
    """q: (B, 1, H, HD); caches: (B, S, KV, HD); length: scalar int32.

    ``interpret=None`` compiles on a TPU backend and interprets elsewhere."""
    b, _, h, hd = q.shape
    s_c, kv = k_cache.shape[1], k_cache.shape[2]
    g = h // kv
    scale = 1.0 / math.sqrt(hd)
    block_s = min(block_s, s_c)
    assert s_c % block_s == 0, (s_c, block_s)
    n_s = s_c // block_s

    qg = q.reshape(b, kv, g, hd)
    kt, vt = jnp.swapaxes(k_cache, 1, 2), jnp.swapaxes(v_cache, 1, 2)
    grid = (b, kv, n_s)
    kernel = functools.partial(_kernel, block_s=block_s, n_s=n_s, scale=scale)

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((None, None, g, hd),
                             lambda bi, ki, si, *_: (bi, ki, 0, 0)),
                pl.BlockSpec((None, None, block_s, hd),
                             lambda bi, ki, si, *_: (bi, ki, si, 0)),
                pl.BlockSpec((None, None, block_s, hd),
                             lambda bi, ki, si, *_: (bi, ki, si, 0)),
            ],
            out_specs=pl.BlockSpec((None, None, g, hd),
                                   lambda bi, ki, si, *_: (bi, ki, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((g, 1), jnp.float32),
                pltpu.VMEM((g, 1), jnp.float32),
                pltpu.VMEM((g, hd), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, kv, g, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=kernel_interpret(interpret),
    )(jnp.asarray(length, jnp.int32)[None], qg, kt, vt)
    return out.reshape(b, 1, h, hd)
