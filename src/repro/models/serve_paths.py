"""Serving paths: prefill (build cache) + single-token decode, per family.

Cache layouts (leading L dim so layer scans carry them):
  attention: {"k","v": (L, B, S_c, KV, HD)}  S_c = sliding window if set
  audio:     + {"ck","cv": (L, B, F, KV, HD)} cross-attn KV (precomputed)
  ssm:       {"conv": (L, B, K-1, cd), "state": (L, B, H, P, N)}
  hybrid:    {"ssm": ..., "attn": {"k","v": (n_sites, B, S_c, H, HD)}}

Ring-buffer semantics for sliding windows: slot = pos % W; validity by
count, not order (softmax is order-invariant; RoPE is baked in at write).

:class:`KVBlockPool` (bottom of this module) is the serving engine's paged
KV storage: a request's prefilled/decoded KV lives in fixed-size *blocks*
backed by refcounted arena slots with TTL leases, so cache memory is
request-lifetime-managed by the same ownership machinery as every other
object on the proxy data plane.
"""
from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed.sharding import shard_as
from repro.models import layers as L
from repro.models.moe import moe_block, moe_decode
from repro.models.ssm import ssm_decode_block, ssm_prefill_block
from repro.models.transformer import _embed_inputs, _encode


def _cache_len(cfg, seq_len: int) -> int:
    return min(seq_len, cfg.sliding_window) if cfg.sliding_window else seq_len


def _shard_cache(t):
    return shard_as(t, "batch", "cache_seq", "kv_heads", "hd_tp")


# ---------------------------------------------------------------------------
# dense / moe / vlm decoder
# ---------------------------------------------------------------------------
def decoder_prefill(params, batch, cfg):
    tokens = batch["tokens"]
    bsz, s = tokens.shape
    positions = jnp.arange(s)
    w = _cache_len(cfg, s)
    x = _embed_inputs(params, batch, cfg)

    def body(x, p):
        h = L.rms_norm(x, p["ln1"], cfg.norm_eps)
        q, k, v = L.attn_qkv(p["attn"], h, cfg, positions)
        o = L.attention(q, k, v, cfg, causal=True)
        x = x + shard_as(o.reshape(bsz, s, -1) @ p["attn"]["wo"],
                         "batch", "act_seq", "embed")
        h2 = L.rms_norm(x, p["ln2"], cfg.norm_eps)
        if cfg.family == "moe":
            y, _ = moe_block(p["moe"], h2, cfg)
        else:
            y = L.mlp_block(p["mlp"], h2, cfg)
        x = shard_as(x + y, "batch", "act_seq", "embed")
        kc = _shard_cache(k[:, s - w:])
        vc = _shard_cache(v[:, s - w:])
        return x, (kc, vc)

    x, (ks, vs) = jax.lax.scan(jax.checkpoint(body), x, params["layers"])
    h = L.rms_norm(x, params["tok"]["ln_f"], cfg.norm_eps)
    logits = L.unembed(params["tok"], h[:, -1:], cfg)[:, 0]
    return logits.astype(jnp.float32), {"k": ks, "v": vs}


def decoder_decode_step(params, cache, token, pos, cfg):
    """token: (B, 1) int32; pos: next-position index — a scalar int32
    (lockstep batch: every row at the same position) or a ``(B,)`` vector
    (continuous batching: per-row positions; each row's KV is left-aligned
    in its cache row and the new entry scatters to ``pos[b]``).

    The stacked caches ride in the layer scan's carry and each layer writes
    its new entry into them in place; as scanned outputs they would be
    rebuilt in a loop buffer and copied whole into the result every step."""
    bsz = token.shape[0]
    x = params["tok"]["emb"][token]
    pos = jnp.asarray(pos)
    per_row = pos.ndim == 1
    positions = pos[:, None] if per_row else pos[None]
    n_layers, w = cache["k"].shape[0], cache["k"].shape[2]
    # a lockstep position writes through the same one-token-per-row scatter:
    # a dynamic-update-slice there makes the TPU compiler re-lay out (copy)
    # the whole carried cache
    slot = jnp.broadcast_to(pos % w if cfg.sliding_window else pos, (bsz,))
    length = jnp.minimum(pos + 1, w)
    rows = jnp.arange(bsz)

    def body(carry, p_i):
        x, k_all, v_all = carry
        p, i = p_i
        h = L.rms_norm(x, p["ln1"], cfg.norm_eps)
        q, k, v = L.attn_qkv(p["attn"], h, cfg, positions)
        k_all = k_all.at[i, rows, slot].set(k[:, 0])
        v_all = v_all.at[i, rows, slot].set(v[:, 0])
        kc = jax.lax.dynamic_index_in_dim(k_all, i, 0, keepdims=False)
        vc = jax.lax.dynamic_index_in_dim(v_all, i, 0, keepdims=False)
        o = L.decode_attention(q, kc, vc, length, cfg)
        x = x + o.reshape(bsz, 1, -1) @ p["attn"]["wo"]
        h2 = L.rms_norm(x, p["ln2"], cfg.norm_eps)
        if cfg.family == "moe":
            y, _ = moe_decode(p["moe"], h2, cfg)
        else:
            y = L.mlp_block(p["mlp"], h2, cfg)
        return (x + y, k_all, v_all), None

    (x, ks, vs), _ = jax.lax.scan(body, (x, cache["k"], cache["v"]),
                                  (params["layers"], jnp.arange(n_layers)))
    h = L.rms_norm(x, params["tok"]["ln_f"], cfg.norm_eps)
    logits = L.unembed(params["tok"], h, cfg)[:, 0]
    return logits.astype(jnp.float32), {"k": ks, "v": vs}


# ---------------------------------------------------------------------------
# whisper-style enc-dec
# ---------------------------------------------------------------------------
def audio_prefill(params, batch, cfg):
    tokens = batch["tokens"]
    bsz, s = tokens.shape
    memory = _encode(params, batch["frames"], cfg)
    positions = jnp.arange(s)
    x = params["tok"]["emb"][tokens]
    f = memory.shape[1]
    kv, hd = cfg.n_kv_heads, cfg.hd

    def body(x, p):
        h = L.rms_norm(x, p["ln1"], cfg.norm_eps)
        q, k, v = L.attn_qkv(p["attn"], h, cfg, positions)
        o = L.attention(q, k, v, cfg, causal=True)
        x = x + o.reshape(bsz, s, -1) @ p["attn"]["wo"]
        hx = L.rms_norm(x, p["lnx"], cfg.norm_eps)
        ck = (memory @ p["xattn"]["wk"]).reshape(bsz, f, kv, hd)
        cv = (memory @ p["xattn"]["wv"]).reshape(bsz, f, kv, hd)
        qx = (hx @ p["xattn"]["wq"]).reshape(bsz, s, cfg.n_heads, hd)
        ox = L.attention(qx, ck, cv, cfg, causal=False)
        x = x + ox.reshape(bsz, s, -1) @ p["xattn"]["wo"]
        x = x + L.mlp_block(p["mlp"], L.rms_norm(x, p["ln2"], cfg.norm_eps), cfg)
        return x, (k, v, ck, cv)

    x, (ks, vs, cks, cvs) = jax.lax.scan(jax.checkpoint(body), x,
                                         params["layers"])
    h = L.rms_norm(x, params["tok"]["ln_f"], cfg.norm_eps)
    logits = L.unembed(params["tok"], h[:, -1:], cfg)[:, 0]
    return logits.astype(jnp.float32), {"k": ks, "v": vs, "ck": cks, "cv": cvs}


def audio_decode_step(params, cache, token, pos, cfg):
    bsz = token.shape[0]
    x = params["tok"]["emb"][token]
    positions = pos[None]
    f = cache["ck"].shape[2]
    length = pos + 1

    def body(x, p_kv):
        p, kc, vc, ck, cv = p_kv
        h = L.rms_norm(x, p["ln1"], cfg.norm_eps)
        q, k, v = L.attn_qkv(p["attn"], h, cfg, positions)
        kc = jax.lax.dynamic_update_slice_in_dim(kc, k, pos, axis=1)
        vc = jax.lax.dynamic_update_slice_in_dim(vc, v, pos, axis=1)
        o = L.decode_attention(q, kc, vc, length, cfg)
        x = x + o.reshape(bsz, 1, -1) @ p["attn"]["wo"]
        hx = L.rms_norm(x, p["lnx"], cfg.norm_eps)
        qx = (hx @ p["xattn"]["wq"]).reshape(bsz, 1, cfg.n_heads, cfg.hd)
        ox = L.decode_attention(qx, ck, cv, f, cfg)
        x = x + ox.reshape(bsz, 1, -1) @ p["xattn"]["wo"]
        x = x + L.mlp_block(p["mlp"], L.rms_norm(x, p["ln2"], cfg.norm_eps), cfg)
        return x, (kc, vc)

    x, (ks, vs) = jax.lax.scan(
        body, x, (params["layers"], cache["k"], cache["v"], cache["ck"],
                  cache["cv"]))
    h = L.rms_norm(x, params["tok"]["ln_f"], cfg.norm_eps)
    logits = L.unembed(params["tok"], h, cfg)[:, 0]
    return logits.astype(jnp.float32), {"k": ks, "v": vs, "ck": cache["ck"],
                                        "cv": cache["cv"]}


# ---------------------------------------------------------------------------
# ssm
# ---------------------------------------------------------------------------
def ssm_prefill(params, batch, cfg):
    tokens = batch["tokens"]
    x = _embed_inputs(params, batch, cfg)

    def body(x, p):
        y, c = ssm_prefill_block(p["ssm"],
                                 L.rms_norm(x, p["ln"], cfg.norm_eps), cfg)
        return shard_as(x + y, "batch", "act_seq", "embed"), c

    x, cache = jax.lax.scan(jax.checkpoint(body), x, params["layers"])
    h = L.rms_norm(x, params["tok"]["ln_f"], cfg.norm_eps)
    logits = L.unembed(params["tok"], h[:, -1:], cfg)[:, 0]
    return logits.astype(jnp.float32), cache


def ssm_decode_step(params, cache, token, pos, cfg):
    x = params["tok"]["emb"][token]

    def body(x, p_c):
        p, conv, state = p_c
        y, c = ssm_decode_block(p["ssm"],
                                L.rms_norm(x, p["ln"], cfg.norm_eps),
                                {"conv": conv, "state": state}, cfg)
        return x + y, (c["conv"], c["state"])

    x, (convs, states) = jax.lax.scan(
        body, x, (params["layers"], cache["conv"], cache["state"]))
    h = L.rms_norm(x, params["tok"]["ln_f"], cfg.norm_eps)
    logits = L.unembed(params["tok"], h, cfg)[:, 0]
    return logits.astype(jnp.float32), {"conv": convs, "state": states}


# ---------------------------------------------------------------------------
# hybrid (zamba2): python loop; shared attention keeps per-site KV caches
# ---------------------------------------------------------------------------
def _attn_sites(cfg) -> list[int]:
    return [i for i in range(cfg.n_layers) if i % cfg.attn_every == 0]


def hybrid_prefill(params, batch, cfg):
    tokens = batch["tokens"]
    bsz, s = tokens.shape
    positions = jnp.arange(s)
    x = _embed_inputs(params, batch, cfg)
    shared = params["shared"]
    sites = _attn_sites(cfg)
    ssm_caches, aks, avs = [], [], []
    for i in range(cfg.n_layers):
        if i in sites:
            h = L.rms_norm(x, shared["ln1"], cfg.norm_eps)
            q, k, v = L.attn_qkv(shared["attn"], h, cfg, positions)
            o = L.attention(q, k, v, cfg, causal=True)
            x = x + o.reshape(bsz, s, -1) @ shared["attn"]["wo"]
            x = x + L.mlp_block(shared["mlp"],
                                L.rms_norm(x, shared["ln2"], cfg.norm_eps), cfg)
            aks.append(k)
            avs.append(v)
        p = jax.tree.map(lambda a, i=i: a[i], params["layers"])
        y, c = ssm_prefill_block(p["ssm"],
                                 L.rms_norm(x, p["ln"], cfg.norm_eps), cfg)
        x = x + y
        ssm_caches.append(c)
    h = L.rms_norm(x, params["tok"]["ln_f"], cfg.norm_eps)
    logits = L.unembed(params["tok"], h[:, -1:], cfg)[:, 0]
    cache = {"ssm": jax.tree.map(lambda *a: jnp.stack(a), *ssm_caches),
             "attn": {"k": jnp.stack(aks), "v": jnp.stack(avs)}}
    return logits.astype(jnp.float32), cache


def hybrid_decode_step(params, cache, token, pos, cfg):
    bsz = token.shape[0]
    x = params["tok"]["emb"][token]
    positions = pos[None]
    shared = params["shared"]
    sites = _attn_sites(cfg)
    length = pos + 1
    new_ssm, new_k, new_v = [], [], []
    for i in range(cfg.n_layers):
        if i in sites:
            s_i = sites.index(i)
            h = L.rms_norm(x, shared["ln1"], cfg.norm_eps)
            q, k, v = L.attn_qkv(shared["attn"], h, cfg, positions)
            kc = jax.lax.dynamic_update_slice_in_dim(cache["attn"]["k"][s_i],
                                                     k, pos, axis=1)
            vc = jax.lax.dynamic_update_slice_in_dim(cache["attn"]["v"][s_i],
                                                     v, pos, axis=1)
            o = L.decode_attention(q, kc, vc, length, cfg)
            x = x + o.reshape(bsz, 1, -1) @ shared["attn"]["wo"]
            x = x + L.mlp_block(shared["mlp"],
                                L.rms_norm(x, shared["ln2"], cfg.norm_eps), cfg)
            new_k.append(kc)
            new_v.append(vc)
        p = jax.tree.map(lambda a, i=i: a[i], params["layers"])
        c = jax.tree.map(lambda a, i=i: a[i], cache["ssm"])
        y, c2 = ssm_decode_block(p["ssm"],
                                 L.rms_norm(x, p["ln"], cfg.norm_eps), c, cfg)
        x = x + y
        new_ssm.append(c2)
    h = L.rms_norm(x, params["tok"]["ln_f"], cfg.norm_eps)
    logits = L.unembed(params["tok"], h, cfg)[:, 0]
    cache = {"ssm": jax.tree.map(lambda *a: jnp.stack(a), *new_ssm),
             "attn": {"k": jnp.stack(new_k), "v": jnp.stack(new_v)}}
    return logits.astype(jnp.float32), cache


# ---------------------------------------------------------------------------
# paged KV-cache storage (the serving engine's block-granular data plane)
# ---------------------------------------------------------------------------
class KVPoolExhausted(RuntimeError):
    """The pool's byte budget cannot fit another block even after expiring
    overdue leases — callers defer admission until completions free blocks."""


class KVBlock(NamedTuple):
    """One stored KV block: ``key`` pins an arena slot (or a serialized
    object on channels without block reservation) holding a
    ``(2, L, ntok, KV, HD)`` slab — K stacked over V."""

    key: tuple
    ntok: int
    nbytes: int


class KVBlockPool:
    """Refcounted, leased, arena-backed KV-cache block storage.

    Replaces grow-by-concatenate caches with fixed-size *pages*: a
    request's KV occupies ``ceil(tokens / block_tokens)`` blocks, each one
    Store object whose lifetime is the ownership subsystem's —

    * ``put_block`` holds ONE reference per block (the owning request) and
      puts a TTL lease on it: when the request completes, :meth:`release`
      decrefs and the channel evicts the slot; when the request's worker
      crashes without releasing, the lease expires and the next pool under
      pressure (or an explicit ``sweep``) reclaims the slot;
    * on channels with ``supports_blocks`` (the shm arena) the block is
      written straight into the reserved slot view — no serializer and no
      staging copy; other channels fall back to an ordinary serialized put;
    * ``budget_bytes`` bounds the pool: an over-budget ``put_block``
      expires overdue leases first and raises :class:`KVPoolExhausted` if
      still full — the engine's admission control defers the request.
    """

    def __init__(self, store, cfg, *, block_tokens: int = 16,
                 budget_bytes: int | None = 64 << 20,
                 lease_ttl: float | None = 60.0) -> None:
        from repro.core.serialize import _resolve_dtype

        self.store = store
        self.block_tokens = int(block_tokens)
        self.budget_bytes = budget_bytes
        self.lease_ttl = lease_ttl
        self.n_layers = cfg.n_layers
        self.n_kv_heads = cfg.n_kv_heads
        self.head_dim = cfg.hd
        self.dtype = _resolve_dtype(cfg.dtype)
        self._direct = getattr(store.connector, "supports_blocks", False)
        self._blocks: dict[tuple, KVBlock] = {}   # key -> tracked block
        self._bytes = 0                            # sum of their nbytes

    # -- write path ----------------------------------------------------------
    def put_block(self, k, v) -> KVBlock:
        """Store one block. ``k``/``v``: (L, t, KV, HD) host arrays with
        t <= block_tokens."""
        k = np.ascontiguousarray(k)
        v = np.ascontiguousarray(v)
        ntok = k.shape[1]
        nbytes = k.nbytes + v.nbytes
        self._ensure_budget(nbytes)
        if self._direct:
            key, view = self.store.reserve_block(nbytes)
            flat = np.frombuffer(view, self.dtype)
            flat[:k.size] = k.ravel()
            flat[k.size:k.size + v.size] = v.ravel()
            self.store.commit_block(key)
        else:
            key = self.store.put(np.stack([k, v]))
        self.store.incref(key)               # the owning request's reference
        if self.lease_ttl:
            self.store.lease(key, self.lease_ttl)   # crashed-owner backstop
        blk = KVBlock(tuple(key), ntok, nbytes)
        self._blocks[blk.key] = blk
        self._bytes += nbytes
        return blk

    def put_prefill(self, k, v) -> list[KVBlock]:
        """Page a prefilled cache — ``k``/``v``: (L, plen, KV, HD) — into
        block_tokens-sized blocks."""
        t = k.shape[1]
        return [self.put_block(k[:, s:s + self.block_tokens],
                               v[:, s:s + self.block_tokens])
                for s in range(0, t, self.block_tokens)]

    # -- read path -----------------------------------------------------------
    def read_block(self, blk: KVBlock):
        """(k, v) arrays of one block — zero-copy views of the arena slot
        on block-capable channels (stable while the block's key is pinned)."""
        if self._direct:
            raw = self.store.block_view(blk.key)
            if raw is None:
                raise LookupError(f"KV block {blk.key} is gone "
                                  f"(evicted or lease-expired)")
            arr = np.frombuffer(raw, self.dtype).reshape(
                2, self.n_layers, blk.ntok, self.n_kv_heads, self.head_dim)
        else:
            obj = self.store.get(blk.key)
            if obj is None:
                raise LookupError(f"KV block {blk.key} is gone "
                                  f"(evicted or lease-expired)")
            arr = obj
        return arr[0], arr[1]

    def gather(self, blocks: list[KVBlock]):
        """Assemble a request's blocks into dense (L, T, KV, HD) k/v
        arrays (the admission path: blocks -> a working-cache row)."""
        ks, vs = zip(*(self.read_block(b) for b in blocks))
        return (np.concatenate(ks, axis=1) if len(ks) > 1 else ks[0],
                np.concatenate(vs, axis=1) if len(vs) > 1 else vs[0])

    # -- lifetime ------------------------------------------------------------
    def release(self, blocks: list[KVBlock]) -> None:
        """Drop the owning references (request completion): each block's
        refcount hits zero and the channel evicts/frees its slot."""
        for blk in blocks:
            gone = self._blocks.pop(blk.key, None)
            if gone is not None:
                self._bytes -= gone.nbytes
            self.store.decref(blk.key)

    def touch(self, blocks: list[KVBlock]) -> None:
        """Refresh the leases of a live request's blocks (the heartbeat a
        long-running generation sends so its pages outlive lease_ttl)."""
        if self.lease_ttl:
            for blk in blocks:
                self.store.lease(blk.key, self.lease_ttl)

    def sweep(self) -> int:
        """Expire overdue leases now (reclaiming crashed owners' blocks);
        returns the number of keys reclaimed."""
        n = self.store.sweep_leases()
        if n:
            self._prune()
        return n

    # -- accounting ----------------------------------------------------------
    def _prune(self) -> None:
        dead = [key for key in self._blocks if not self.store.exists(key)]
        for key in dead:
            self._bytes -= self._blocks.pop(key).nbytes

    def bytes_in_use(self) -> int:
        return self._bytes

    def _ensure_budget(self, nbytes: int) -> None:
        if self.budget_bytes is None:
            return
        if self.bytes_in_use() + nbytes <= self.budget_bytes:
            return
        self.sweep()                    # reclaim crashed owners' blocks
        self._prune()
        used = self.bytes_in_use()
        if used + nbytes > self.budget_bytes:
            raise KVPoolExhausted(
                f"KV pool over budget: {used} + {nbytes} > "
                f"{self.budget_bytes} bytes ({len(self._blocks)} blocks)")

    def stats(self) -> dict[str, Any]:
        return {"n_blocks": len(self._blocks),
                "bytes_in_use": self.bytes_in_use(),
                "budget_bytes": self.budget_bytes,
                "block_tokens": self.block_tokens,
                "direct": self._direct}
