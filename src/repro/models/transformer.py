"""Model assembly for all assigned families.

``build_model(cfg)`` returns a :class:`Model` namespace:

* ``init(key) -> params``                    (materializes the parameters)
* ``loss(params, batch) -> (loss, metrics)`` (train shapes)
* ``prefill(params, batch) -> (last_logits, cache)``
* ``decode_step(params, cache, token, pos) -> (logits, cache)``

Layer stacks are one ``lax.scan`` over stacked params with per-layer remat,
so the traced program and the saved activations do not grow with depth; the
hybrid serving paths are a Python loop (38 small layers, shared attention
needs per-site KV).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.distributed.sharding import shard_as
from repro.models import layers as L
from repro.models.moe import init_moe, moe_block
from repro.models.ssm import init_ssm, ssm_block


@dataclass
class Model:
    cfg: Any
    init: Callable
    loss: Callable
    prefill: Callable
    decode_step: Callable


def _stacked(init_one, key, n):
    """vmap an init over layer indices -> stacked (L, ...) params."""
    return jax.vmap(init_one)(jax.random.split(key, n))


def _scan_stack(body, carry, stacked_params):
    """Scan ``body(carry, layer_params) -> carry`` over the layer stack,
    each layer rematerialized in the backward pass."""
    carry, _ = jax.lax.scan(jax.checkpoint(lambda c, p: (body(c, p), None)),
                            carry, stacked_params)
    return carry


# ---------------------------------------------------------------------------
# decoder blocks (dense / moe)
# ---------------------------------------------------------------------------
def _init_dense_layer(cfg):
    def one(key):
        k1, k2, k3, k4 = jax.random.split(key, 4)
        return {"ln1": L.init_rms(k1, cfg.d_model),
                "attn": L.init_attn(k2, cfg),
                "ln2": L.init_rms(k3, cfg.d_model),
                "mlp": L.init_mlp(k4, cfg)}
    return one


def _init_moe_layer(cfg):
    def one(key):
        k1, k2, k3, k4 = jax.random.split(key, 4)
        return {"ln1": L.init_rms(k1, cfg.d_model),
                "attn": L.init_attn(k2, cfg),
                "ln2": L.init_rms(k3, cfg.d_model),
                "moe": init_moe(k4, cfg)}
    return one


def _dense_body(x, p, cfg, positions):
    x = x + L.attn_block(p["attn"], L.rms_norm(x, p["ln1"], cfg.norm_eps),
                         cfg, positions=positions)
    x = x + L.mlp_block(p["mlp"], L.rms_norm(x, p["ln2"], cfg.norm_eps), cfg)
    return shard_as(x, "batch", "act_seq", "embed")


def _moe_body(carry, p, cfg, positions):
    x, aux = carry
    x = x + L.attn_block(p["attn"], L.rms_norm(x, p["ln1"], cfg.norm_eps),
                         cfg, positions=positions)
    y, a = moe_block(p["moe"], L.rms_norm(x, p["ln2"], cfg.norm_eps), cfg)
    x = shard_as(x + y, "batch", "act_seq", "embed")
    return x, {k: aux[k] + a[k] for k in aux}


# ---------------------------------------------------------------------------
# forward passes (hidden states)
# ---------------------------------------------------------------------------
def _embed_inputs(params, batch, cfg):
    x = params["tok"]["emb"][batch["tokens"]]
    if cfg.family == "vlm":
        # patch embeddings (stub frontend) occupy the first n_img positions
        x = jax.lax.dynamic_update_slice(
            x, batch["vision_emb"].astype(x.dtype), (0, 0, 0))
    return shard_as(x, "batch", "act_seq", "embed")


def _decoder_hidden(params, x, cfg, positions):
    """Dense/vlm/moe decoder stack -> (hidden, aux)."""
    if cfg.family == "moe":
        def body(carry, p):
            return _moe_body(carry, p, cfg, positions)
        aux0 = {"lb_loss": jnp.zeros((), jnp.float32),
                "z_loss": jnp.zeros((), jnp.float32)}
        x, aux = _scan_stack(body, (x, aux0), params["layers"])
    else:
        def body(x, p):
            return _dense_body(x, p, cfg, positions)
        x = _scan_stack(body, x, params["layers"])
        aux = {}
    return L.rms_norm(x, params["tok"]["ln_f"], cfg.norm_eps), aux


# ---------------------------------------------------------------------------
# ssm / hybrid stacks
# ---------------------------------------------------------------------------
def _ssm_body(x, p, cfg):
    x = x + ssm_block(p["ssm"], L.rms_norm(x, p["ln"], cfg.norm_eps), cfg)
    return shard_as(x, "batch", "act_seq", "embed")


def _shared_attn_block(shared, x, cfg, positions):
    x = x + L.attn_block(shared["attn"],
                         L.rms_norm(x, shared["ln1"], cfg.norm_eps),
                         cfg, positions=positions)
    x = x + L.mlp_block(shared["mlp"],
                        L.rms_norm(x, shared["ln2"], cfg.norm_eps), cfg)
    return x


def _hybrid_hidden(params, x, cfg, positions):
    shared = params["shared"]
    flags = (jnp.arange(cfg.n_layers) % cfg.attn_every) == 0

    def body(x, p_flag):
        p, flag = p_flag
        x = jax.lax.cond(
            flag,
            lambda x: _shared_attn_block(shared, x, cfg, positions),
            lambda x: x, x)
        return _ssm_body(x, p, cfg)

    x = _scan_stack(body, x, (params["layers"], flags))
    return L.rms_norm(x, params["tok"]["ln_f"], cfg.norm_eps), {}


# ---------------------------------------------------------------------------
# whisper-style encoder-decoder
# ---------------------------------------------------------------------------
def _enc_body(x, p, cfg, positions):
    x = x + L.attn_block(p["attn"], L.rms_norm(x, p["ln1"], cfg.norm_eps),
                         cfg, positions=positions, causal=False)
    x = x + L.mlp_block(p["mlp"], L.rms_norm(x, p["ln2"], cfg.norm_eps), cfg)
    return shard_as(x, "batch", "act_seq", "embed")


def _dec_body(x, p, cfg, positions, memory):
    x = x + L.attn_block(p["attn"], L.rms_norm(x, p["ln1"], cfg.norm_eps),
                         cfg, positions=positions)
    x = x + L.attn_block(p["xattn"], L.rms_norm(x, p["lnx"], cfg.norm_eps),
                         cfg, positions=positions, memory=memory)
    x = x + L.mlp_block(p["mlp"], L.rms_norm(x, p["ln2"], cfg.norm_eps), cfg)
    return shard_as(x, "batch", "act_seq", "embed")


def _encode(params, frames, cfg):
    x = frames.astype(L.dtype_of(cfg)) + params["enc_pos"].astype(L.dtype_of(cfg))
    pos = jnp.arange(frames.shape[1])

    def body(x, p):
        return _enc_body(x, p, cfg, pos)

    x = _scan_stack(body, x, params["enc"])
    return L.rms_norm(x, params["enc_ln_f"], cfg.norm_eps)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------
def init_params(key, cfg):
    ks = jax.random.split(key, 8)
    params: dict[str, Any] = {"tok": L.init_embeddings(ks[0], cfg)}
    if cfg.family in ("dense", "vlm"):
        params["layers"] = _stacked(_init_dense_layer(cfg), ks[1], cfg.n_layers)
    elif cfg.family == "moe":
        params["layers"] = _stacked(_init_moe_layer(cfg), ks[1], cfg.n_layers)
    elif cfg.family == "ssm":
        def one(key):
            k1, k2 = jax.random.split(key)
            return {"ln": L.init_rms(k1, cfg.d_model), "ssm": init_ssm(k2, cfg)}
        params["layers"] = _stacked(one, ks[1], cfg.n_layers)
    elif cfg.family == "hybrid":
        def one(key):
            k1, k2 = jax.random.split(key)
            return {"ln": L.init_rms(k1, cfg.d_model), "ssm": init_ssm(k2, cfg)}
        params["layers"] = _stacked(one, ks[1], cfg.n_layers)
        k1, k2, k3, k4 = jax.random.split(ks[2], 4)
        params["shared"] = {"ln1": L.init_rms(k1, cfg.d_model),
                            "attn": L.init_attn(k2, cfg),
                            "ln2": L.init_rms(k3, cfg.d_model),
                            "mlp": L.init_mlp(k4, cfg)}
    elif cfg.family == "audio":
        def dec_one(key):
            k1, k2, k3, k4, k5, k6 = jax.random.split(key, 6)
            return {"ln1": L.init_rms(k1, cfg.d_model),
                    "attn": L.init_attn(k2, cfg),
                    "lnx": L.init_rms(k3, cfg.d_model),
                    "xattn": L.init_attn(k4, cfg),
                    "ln2": L.init_rms(k5, cfg.d_model),
                    "mlp": L.init_mlp(k6, cfg)}
        params["layers"] = _stacked(dec_one, ks[1], cfg.n_layers)
        params["enc"] = _stacked(_init_dense_layer(cfg), ks[2], cfg.n_enc_layers)
        params["enc_pos"] = jax.random.normal(
            ks[3], (cfg.enc_frames, cfg.d_model), jnp.float32) * 0.02
        params["enc_ln_f"] = L.init_rms(ks[4], cfg.d_model)
    else:
        raise ValueError(cfg.family)
    return params


# ---------------------------------------------------------------------------
# loss (train shapes)
# ---------------------------------------------------------------------------
def model_loss(params, batch, cfg):
    tokens = batch["tokens"]
    positions = jnp.arange(tokens.shape[1])
    if cfg.family == "audio":
        memory = _encode(params, batch["frames"], cfg)

        def body(x, p):
            return _dec_body(x, p, cfg, positions, memory)

        x = _embed_inputs(params, batch, cfg)
        x = _scan_stack(body, x, params["layers"])
        h = L.rms_norm(x, params["tok"]["ln_f"], cfg.norm_eps)
        aux = {}
    elif cfg.family == "hybrid":
        x = _embed_inputs(params, batch, cfg)
        h, aux = _hybrid_hidden(params, x, cfg, positions)
    elif cfg.family == "ssm":
        x = _embed_inputs(params, batch, cfg)

        def body(x, p):
            return _ssm_body(x, p, cfg)

        x = _scan_stack(body, x, params["layers"])
        h = L.rms_norm(x, params["tok"]["ln_f"], cfg.norm_eps)
        aux = {}
    else:
        x = _embed_inputs(params, batch, cfg)
        h, aux = _decoder_hidden(params, x, cfg, positions)

    weights = None
    if cfg.family == "vlm":  # no next-token loss on image positions
        weights = (positions >= cfg.n_img_tokens).astype(jnp.float32)[None, :]
    nll = L.chunked_xent(params["tok"], h, batch["labels"], cfg, weights=weights)
    metrics = {"nll": nll}
    loss = nll
    if aux:
        n_l = cfg.n_layers
        loss = loss + 0.01 * aux["lb_loss"] / n_l + 1e-3 * aux["z_loss"] / n_l
        metrics.update({k: v / n_l for k, v in aux.items()})
    return loss, metrics
