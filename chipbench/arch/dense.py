"""A dense GQA decoder (Phi-3 / Qwen2 style): RMSNorm with a gain, GQA
attention with rotary embeddings, optional q/k/v biases, a SwiGLU MLP, a
final RMSNorm and a tied or untied output head.  One stack of layers,
``layers``; the engine keeps a K and a V cache of (layers, rows,
positions, KV heads, head size)."""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import HI, rms, rope
from chipbench.weights import BIAS_STD, EMB_STD, NORM_STD


@dataclass(frozen=True)
class ModelSpec:
    """A dense decoder as served: published sizes plus the serving sizes.

    Keys of the configuration file's ``model`` group follow the published
    ``config.json`` (Hugging Face names)."""

    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    qkv_bias: bool
    tie_embeddings: bool
    rope_theta: float
    norm_eps: float
    dtype: str
    vocab_multiple: int      # the program pads embedding rows to a multiple
    max_batch: int
    max_context: int
    block_tokens: int

    @property
    def vocab_rows(self) -> int:
        return -(-self.vocab // self.vocab_multiple) * self.vocab_multiple


def model_spec(conf: dict) -> ModelSpec:
    m, s = conf["model"], conf["serving"]
    heads = m["num_attention_heads"]
    return ModelSpec(
        name=conf["name"],
        n_layers=m["num_hidden_layers"],
        d_model=m["hidden_size"],
        n_heads=heads,
        n_kv_heads=m["num_key_value_heads"],
        head_dim=m.get("head_dim") or m["hidden_size"] // heads,
        d_ff=m["intermediate_size"],
        vocab=m["vocab_size"],
        qkv_bias=bool(m.get("attention_bias", False)),
        tie_embeddings=bool(m["tie_word_embeddings"]),
        rope_theta=float(m["rope_theta"]),
        norm_eps=float(m["rms_norm_eps"]),
        dtype=s["dtype"],
        vocab_multiple=s["embedding_rows_multiple"],
        max_batch=s["max_batch"],
        max_context=s["max_context"],
        block_tokens=s["block_tokens"],
    )


def arch_config(spec: ModelSpec):
    from repro.configs.base import ArchConfig

    return ArchConfig(
        name=spec.name, family="dense", n_layers=spec.n_layers,
        d_model=spec.d_model, n_heads=spec.n_heads,
        n_kv_heads=spec.n_kv_heads, d_ff=spec.d_ff, vocab=spec.vocab,
        head_dim=spec.head_dim, qkv_bias=spec.qkv_bias,
        tie_embeddings=spec.tie_embeddings, rope_theta=spec.rope_theta,
        norm_eps=spec.norm_eps, dtype=spec.dtype,
        pad_vocab_to=spec.vocab_multiple)


# -- weights ----------------------------------------------------------------
def stacks(spec: ModelSpec) -> dict[str, int]:
    return {"layers": spec.n_layers}


def leaves(spec: ModelSpec) -> dict:
    d, hd, f = spec.d_model, spec.head_dim, spec.d_ff
    q, kv = spec.n_heads * hd, spec.n_kv_heads * hd
    dt = jnp.dtype(spec.dtype)
    f32 = jnp.float32
    out = {
        "tok/emb": ((spec.vocab_rows, d), dt, EMB_STD, None),
        "tok/ln_f": ((d,), f32, NORM_STD, None),
        "layers/ln1": ((d,), f32, NORM_STD, "layers"),
        "layers/attn/wq": ((d, q), dt, 1 / math.sqrt(d), "layers"),
        "layers/attn/wk": ((d, kv), dt, 1 / math.sqrt(d), "layers"),
        "layers/attn/wv": ((d, kv), dt, 1 / math.sqrt(d), "layers"),
        "layers/attn/wo": ((q, d), dt, 1 / math.sqrt(q), "layers"),
        "layers/ln2": ((d,), f32, NORM_STD, "layers"),
        "layers/mlp/w_up": ((d, f), dt, 1 / math.sqrt(d), "layers"),
        "layers/mlp/w_gate": ((d, f), dt, 1 / math.sqrt(d), "layers"),
        "layers/mlp/w_down": ((f, d), dt, 1 / math.sqrt(f), "layers"),
    }
    if not spec.tie_embeddings:
        out["tok/unemb"] = ((d, spec.vocab_rows), dt, 1 / math.sqrt(d), None)
    if spec.qkv_bias:
        out["layers/attn/bq"] = ((q,), dt, BIAS_STD, "layers")
        out["layers/attn/bk"] = ((kv,), dt, BIAS_STD, "layers")
        out["layers/attn/bv"] = ((kv,), dt, BIAS_STD, "layers")
    return out


# -- reference ----------------------------------------------------------------
def layer(x, w, *, spec: ModelSpec, stack: str, mm):
    """One decoder layer over x: (n, T, D) float32, causal from position 0."""
    n, t, _ = x.shape
    h_, kv, hd = spec.n_heads, spec.n_kv_heads, spec.head_dim
    a = w["attn"]
    h = rms(x, w["ln1"], spec.norm_eps)
    q, k, v = mm(h, a["wq"]), mm(h, a["wk"]), mm(h, a["wv"])
    if spec.qkv_bias:
        q = q + a["bq"].astype(jnp.float32)
        k = k + a["bk"].astype(jnp.float32)
        v = v + a["bv"].astype(jnp.float32)
    q = rope(q.reshape(n, t, h_, hd), spec.rope_theta)
    k = rope(k.reshape(n, t, kv, hd), spec.rope_theta)
    v = v.reshape(n, t, kv, hd)
    q = q.reshape(n, t, kv, h_ // kv, hd)
    s = jnp.einsum("nqkgh,nskh->nkgqs", q, k, precision=HI) / np.sqrt(hd)
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    s = jnp.where(causal, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("nkgqs,nskh->nqkgh", p, v, precision=HI)
    x = x + mm(o.reshape(n, t, h_ * hd), a["wo"])
    m = w["mlp"]
    h = rms(x, w["ln2"], spec.norm_eps)
    g = jax.nn.silu(mm(h, m["w_gate"])) * mm(h, m["w_up"])
    return x + mm(g, m["w_down"])


def final_norm(x, tok, spec: ModelSpec):
    return rms(x, tok["ln_f"], spec.norm_eps)


def head(spec: ModelSpec, tok):
    return tok["emb"].T if spec.tie_embeddings else tok["unemb"]


# -- useful operations ------------------------------------------------------
def layer_weights(spec: ModelSpec) -> int:
    """Weights a token multiplies in one layer (projections and MLP)."""
    d, hd = spec.d_model, spec.head_dim
    q, kv = spec.n_heads * hd, spec.n_kv_heads * hd
    return d * q + 2 * d * kv + q * d + 3 * d * spec.d_ff


def token_weights(spec: ModelSpec) -> int:
    return spec.n_layers * layer_weights(spec)


def attention_ops(spec: ModelSpec) -> int:
    """Scores and weighted values: a multiply-add each per head dimension."""
    return spec.n_layers * 4 * spec.n_heads * spec.head_dim


# -- the engine's cache -------------------------------------------------------
def cache_shape(spec: ModelSpec) -> tuple[int, ...]:
    return (spec.n_layers, spec.max_batch, spec.max_context, spec.n_kv_heads,
            spec.head_dim)
