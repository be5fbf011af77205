"""Mamba2 SSD chunked scan — Pallas TPU.

The SSD dual form (arXiv:2405.21060 §6) maps naturally onto the MXU: per
chunk, three small matmuls (C Bᵀ, masked-decay weighting, state in/out
contractions) over (Q, N)/(Q, P) tiles, plus an O(P x N) recurrent state that
persists in VMEM scratch across the innermost (sequential) chunk dimension —
the TPU analog of the paper's SM-resident recurrence.

Grid: (B, H, n_chunks).  Blocks: x (Q, P), b/c (Q, N), and the within-chunk
cumulative decay both as a column (Q, 1) and as a row (1, Q) — with
Q=chunk (128-256), P=64, N=64-128 every tile is MXU-aligned and the VMEM
working set is < 1 MB.  ngroups=1: B/C blocks are shared across the H grid
dimension (index map drops h).  The wrapper puts the head axis before the
sequence axis, so each block's last two dims are (Q, P) or (1, Q) as the
TPU lowering requires.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.runtime import kernel_interpret


def _dot(a, b, contract):
    """f32 contraction at full f32 precision: the recurrence sums long
    runs of terms that largely cancel, and one bf16 pass per operand (the
    MXU's default) moves small outputs by more than bf16 tolerance."""
    return jax.lax.dot_general(a, b, (contract, ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


def _kernel(x_ref, cum_col_ref, cum_row_ref, b_ref, c_ref, y_ref, state_sc,
            *, chunk):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_sc[...] = jnp.zeros_like(state_sc)

    x = x_ref[...]                              # (Q, P) f32, dt folded in
    cum = cum_col_ref[...]                      # (Q, 1) within-chunk cumsum
    cum_row = cum_row_ref[...]                  # (1, Q) the same, as a row
    b = b_ref[...].astype(jnp.float32)          # (Q, N)
    c = c_ref[...].astype(jnp.float32)          # (Q, N)
    total = cum[chunk - 1:, :]                  # (1, 1) whole-chunk decay

    # within-chunk decay L[q, s] = exp(cum[q] - cum[s]) for q >= s
    tri = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    decay = jnp.where(tri, jnp.exp(cum - cum_row), 0.0)

    scores = _dot(c, b, ((1,), (1,)))
    w = scores * decay                          # (Q, Q)
    y_diag = _dot(w, x, ((1,), (0,)))

    # inter-chunk: contribution of the incoming state
    state = state_sc[...]                       # (P, N)
    c_dec = c * jnp.exp(cum)                    # (Q, N)
    y_off = _dot(c_dec, state, ((1,), (1,)))

    y_ref[...] = (y_diag + y_off).astype(y_ref.dtype)

    # state update: s' = exp(sum dta) * s + sum_q exp(cum[-1]-cum[q]) x_q b_qᵀ
    b_dec = b * jnp.exp(total - cum)            # (Q, N)
    inject = _dot(x, b_dec, ((0,), (0,)))
    # (1, 1) -> (P, 1) -> (P, N): Mosaic broadcasts one axis at a time
    state_sc[...] = jnp.exp(jnp.broadcast_to(total, (state.shape[0], 1))) \
        * state + inject


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, dt, a_log, b, c, *, chunk=128, interpret=None):
    """x: (B, L, H, P); dt: (B, L, H); a_log: (H,); b, c: (B, L, N).

    dt is folded into x (in f32, as the reference does) and into the decay
    outside the kernel (cheap elementwise, plus the within-chunk cumulative
    sum of the decay); the
    kernel does the chunked scan proper.  Returns y: (B, L, H, P).
    ``interpret=None`` compiles on a TPU backend and interprets elsewhere.
    """
    bs, l, h, p = x.shape
    n = b.shape[-1]
    chunk = min(chunk, l)
    assert l % chunk == 0, (l, chunk)
    n_c = l // chunk

    a = -jnp.exp(a_log.astype(jnp.float32))
    dta = jnp.swapaxes(dt.astype(jnp.float32) * a, 1, 2)      # (B, H, L)
    cum = jnp.cumsum(dta.reshape(bs, h, n_c, chunk), axis=-1).reshape(
        bs, h, l)
    xdt = jnp.swapaxes(x.astype(jnp.float32) * dt[..., None], 1, 2)  # f32

    grid = (bs, h, n_c)
    kernel = functools.partial(_kernel, chunk=chunk)

    y = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, None, chunk, p),
                         lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((None, None, chunk, 1),
                         lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((None, None, 1, chunk),
                         lambda bi, hi, ci: (bi, hi, 0, ci)),
            pl.BlockSpec((None, chunk, n), lambda bi, hi, ci: (bi, ci, 0)),
            pl.BlockSpec((None, chunk, n), lambda bi, hi, ci: (bi, ci, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, chunk, p),
                               lambda bi, hi, ci: (bi, hi, ci, 0)),
        out_shape=jax.ShapeDtypeStruct((bs, h, l, p), x.dtype),
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=kernel_interpret(interpret),
    )(xdt, cum[..., None], cum[:, :, None, :], b, c)
    return jnp.swapaxes(y, 1, 2)
