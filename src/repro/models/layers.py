"""Shared neural-net layers (pure JAX, functional).

Conventions:
* params are plain dict pytrees; per-layer tensors carry a leading ``L`` dim
  when the stack is scanned,
* activations flow in ``cfg.dtype`` (bf16); softmax/norm accumulate in fp32,
* ``shard_as(x, *logical_dims)`` applies the active logical sharding rules
  (no-op outside a rules context) — model code never names mesh axes.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.distributed.sharding import shard_as


def dtype_of(cfg):
    return jnp.dtype(cfg.dtype)


# ---------------------------------------------------------------------------
# norms / embeddings / positional
# ---------------------------------------------------------------------------
def rms_norm(x, w, eps: float = 1e-5):
    h = x.astype(jnp.float32)
    h = h * jax.lax.rsqrt(jnp.mean(h * h, axis=-1, keepdims=True) + eps)
    return (h * (1.0 + w.astype(jnp.float32))).astype(x.dtype)


def init_rms(key, dim):
    # stored as delta from 1 (so zeros-init == identity scale)
    return jnp.zeros((dim,), jnp.float32)


def rope(x, positions, theta: float):
    """Rotary embedding. x: (..., S, n, HD); positions: (S,) or (B, S)."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=jnp.float32) / half)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, half)
    cos = jnp.cos(angles)[..., None, :]  # broadcast over heads
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention (GQA; chunked reference / pallas)
# ---------------------------------------------------------------------------
def _attn_chunk_body(q_chunk, k, v, *, q_start, causal, window, scale):
    """One query chunk vs the full K/V. q_chunk: (B, Cq, H, HD).

    The 'attn_q' rule (when set to 'model') pins the big score tensor to
    query-position sharding: with GQA kv_heads < mesh axis, head sharding
    can't cover the axis and GSPMD otherwise picks mismatched intermediate
    shardings and reshards the O(S^2) scores every layer.
    """
    b, cq, h, hd = q_chunk.shape
    kv = k.shape[2]
    g = h // kv
    qg = q_chunk.reshape(b, cq, kv, g, hd)
    qg = shard_as(qg, "batch", "attn_q", "kv_heads", None, None)
    # scores: (B, KV, G, Cq, Skv)
    s = jnp.einsum("bqkgh,bskh->bkgqs", qg, k,
                   preferred_element_type=jnp.float32) * scale
    s = shard_as(s, "batch", "kv_heads", None, "attn_q", None)
    skv = k.shape[1]
    q_pos = q_start + jnp.arange(cq)
    k_pos = jnp.arange(skv)
    mask = jnp.ones((cq, skv), bool)
    if causal:
        mask &= q_pos[:, None] >= k_pos[None, :]
    if window:
        mask &= q_pos[:, None] - k_pos[None, :] < window
    s = jnp.where(mask[None, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(q_chunk.dtype)
    o = jnp.einsum("bkgqs,bskh->bqkgh", p, v)
    o = shard_as(o, "batch", "attn_q", "kv_heads", None, None)
    return o.reshape(b, cq, h, hd)


def attention(q, k, v, cfg, *, causal=True, q_offset=0):
    """q: (B, Sq, H, HD); k, v: (B, Skv, KV, HD) -> (B, Sq, H, HD).

    ``chunked``: lax.scan over query chunks with an inner remat so the O(S^2)
    score tensor never exceeds one chunk — the XLA-path analog of the Pallas
    flash kernel (which replaces this on TPU via cfg.attention_impl).
    """
    scale = 1.0 / math.sqrt(q.shape[-1])
    window = cfg.sliding_window
    if cfg.attention_impl == "pallas":
        from repro.kernels.ops import flash_attention as _fa

        return _fa(q, k, v, causal=causal, window=window, q_offset=q_offset)
    nq = q.shape[1]
    chunk = min(cfg.attn_chunk, nq)
    if nq <= chunk or nq % chunk != 0:
        return _attn_chunk_body(q, k, v, q_start=q_offset, causal=causal,
                                window=window, scale=scale)

    n_chunks = nq // chunk
    qs = q.reshape(q.shape[0], n_chunks, chunk, *q.shape[2:])

    @jax.checkpoint
    def body(_, qc_i):
        qc, i = qc_i
        o = _attn_chunk_body(qc, k, v, q_start=q_offset + i * chunk,
                             causal=causal, window=window, scale=scale)
        return None, o

    _, out = jax.lax.scan(
        body, None, (jnp.moveaxis(qs, 1, 0), jnp.arange(n_chunks)))
    return jnp.moveaxis(out, 0, 1).reshape(q.shape)


def decode_attention(q, k_cache, v_cache, length, cfg):
    """Single-position attention against a (possibly ring) KV cache.

    q: (B, 1, H, HD); caches: (B, S_cache, KV, HD); ``length`` = number of
    valid entries — a scalar (lockstep batch) or a ``(B,)`` vector
    (continuous batching: each row's cache is left-aligned and valid up to
    its own length).  Softmax in fp32; masked beyond ``length``.
    """
    b, _, h, hd = q.shape
    kv = k_cache.shape[2]
    g = h // kv
    scale = 1.0 / math.sqrt(hd)
    qg = q.reshape(b, kv, g, hd)
    s = jnp.einsum("bkgh,bskh->bkgs", qg, k_cache,
                   preferred_element_type=jnp.float32) * scale
    length = jnp.asarray(length)
    if length.ndim == 0:
        valid = jnp.arange(k_cache.shape[1]) < length          # (S,)
        s = jnp.where(valid[None, None, None], s, -1e30)
    else:
        valid = jnp.arange(k_cache.shape[1])[None, :] < length[:, None]
        s = jnp.where(valid[:, None, None, :], s, -1e30)       # (B, S)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    o = jnp.einsum("bkgs,bskh->bkgh", p, v_cache)
    return o.reshape(b, 1, h, hd)


def init_attn(key, cfg, d_model=None):
    d = d_model or cfg.d_model
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    ks = jax.random.split(key, 4)
    std = 1.0 / math.sqrt(d)
    dt = dtype_of(cfg)
    p = {
        "wq": jax.random.normal(ks[0], (d, h * hd), dt) * std,
        "wk": jax.random.normal(ks[1], (d, kv * hd), dt) * std,
        "wv": jax.random.normal(ks[2], (d, kv * hd), dt) * std,
        "wo": jax.random.normal(ks[3], (h * hd, d), dt) * std / math.sqrt(2 * cfg.n_layers),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h * hd,), dt)
        p["bk"] = jnp.zeros((kv * hd,), dt)
        p["bv"] = jnp.zeros((kv * hd,), dt)
    return p


def attn_qkv(p, x, cfg, positions):
    """Project + RoPE. x: (B, S, D) -> q (B,S,H,HD), k/v (B,S,KV,HD)."""
    b, s, _ = x.shape
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.qkv_bias:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    q = shard_as(q.reshape(b, s, h, hd), "batch", "seq", "heads", None)
    k = shard_as(k.reshape(b, s, kv, hd), "batch", "seq", "kv_heads", None)
    v = shard_as(v.reshape(b, s, kv, hd), "batch", "seq", "kv_heads", None)
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    return q, k, v


def attn_block(p, x, cfg, *, positions, causal=True, memory=None):
    """Full attention sublayer (self or cross). x: (B, S, D)."""
    if memory is None:
        q, k, v = attn_qkv(p, x, cfg, positions)
    else:  # cross-attention: keys/values from encoder memory (no RoPE)
        b, s, _ = x.shape
        h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
        q = (x @ p["wq"]).reshape(b, s, h, hd)
        k = (memory @ p["wk"]).reshape(b, memory.shape[1], kv, hd)
        v = (memory @ p["wv"]).reshape(b, memory.shape[1], kv, hd)
        causal = False
    o = attention(q, k, v, cfg, causal=causal)
    o = o.reshape(*x.shape[:2], -1)
    return shard_as(o @ p["wo"], "batch", "act_seq", "embed")


# ---------------------------------------------------------------------------
# MLP (SwiGLU / GELU)
# ---------------------------------------------------------------------------
def init_mlp(key, cfg, d_model=None, d_ff=None):
    d = d_model or cfg.d_model
    f = d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    std = 1.0 / math.sqrt(d)
    dt = dtype_of(cfg)
    p = {
        "w_up": jax.random.normal(ks[0], (d, f), dt) * std,
        "w_down": jax.random.normal(ks[1], (f, d), dt) * (std / math.sqrt(2 * cfg.n_layers)),
    }
    if cfg.act == "silu":
        p["w_gate"] = jax.random.normal(ks[2], (d, f), dt) * std
    return p


def mlp_block(p, x, cfg):
    h = x @ p["w_up"]
    if cfg.act == "silu":
        h = jax.nn.silu(x @ p["w_gate"]) * h
    else:
        h = jax.nn.gelu(h)
    h = shard_as(h, "batch", "seq", "ff")
    return shard_as(h @ p["w_down"], "batch", "act_seq", "embed")


# ---------------------------------------------------------------------------
# embeddings + chunked loss
# ---------------------------------------------------------------------------
def init_embeddings(key, cfg):
    dt = dtype_of(cfg)
    ks = jax.random.split(key, 2)
    v = cfg.padded_vocab
    p = {"emb": jax.random.normal(ks[0], (v, cfg.d_model), dt) * 0.02,
         "ln_f": init_rms(ks[1], cfg.d_model)}
    if not cfg.tie_embeddings:
        p["unemb"] = jax.random.normal(ks[1], (cfg.d_model, v), dt) * 0.02
    return p


def unembed(p, x, cfg):
    w = p["emb"].T if cfg.tie_embeddings else p["unemb"]
    return shard_as(x @ w, "batch", "seq", "vocab")


def chunked_xent(p, x, labels, cfg, weights=None):
    """Sequence-chunked softmax cross-entropy; never materializes full logits.

    x: (B, S, D), labels: (B, S), weights: optional (B, S) or (1, S)
    -> scalar mean nll (fp32) over weighted positions.
    """
    b, s, d = x.shape
    chunk = min(cfg.loss_chunk, s)
    if s % chunk != 0:
        chunk = s
    n = s // chunk
    w = p["emb"].T if cfg.tie_embeddings else p["unemb"]
    if weights is None:
        weights = jnp.ones((1, s), jnp.float32)
    weights = jnp.broadcast_to(weights, (b, s))

    @jax.checkpoint
    def body(acc, xlw):
        xc, lc, wc = xlw  # (B, chunk, D), (B, chunk), (B, chunk)
        logits = shard_as((xc @ w).astype(jnp.float32), "batch", "seq", "vocab")
        logz = jax.nn.logsumexp(logits, axis=-1)
        # one-hot contraction (not take_along_axis): partitions cleanly when
        # the vocab dim is sharded -> partial sums + one small all-reduce
        onehot = jax.nn.one_hot(lc, logits.shape[-1], dtype=logits.dtype)
        gold = jnp.einsum("bcv,bcv->bc", logits, onehot)
        return acc + jnp.sum((logz - gold) * wc), None

    def chunks(a):
        return jnp.moveaxis(a.reshape(b, n, chunk, *a.shape[2:]), 1, 0)

    xs = (chunks(x), chunks(labels), chunks(weights))
    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), xs)
    return total / jnp.maximum(jnp.sum(weights), 1.0)
