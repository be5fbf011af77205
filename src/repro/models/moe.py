"""Mixture-of-Experts FFN — GShard-style capacity dispatch (top-k, dropping).

Einsum formulation so GSPMD can shard it:
* experts dim -> 'experts' logical axis (EP when E divides the mesh axis,
  e.g. qwen3's 128 experts; otherwise the per-expert ff dim shards, e.g.
  mixtral's 8 experts with TP inside each expert),
* dispatch/combine tensors (G, S, E, C) shard on batch-group and experts,
* capacity C = ceil(S * top_k / E * capacity_factor); overflow tokens drop
  (residual passes through, standard for dropping MoE).

Router extras: load-balance aux loss (Switch) + router z-loss, both returned
for the train loss to weight.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.distributed.sharding import shard_as
from repro.models.layers import dtype_of


def init_moe(key, cfg):
    d, e = cfg.d_model, cfg.n_experts
    f = cfg.moe_d_ff or cfg.d_ff
    ks = jax.random.split(key, 4)
    std = 1.0 / math.sqrt(d)
    dt = dtype_of(cfg)
    return {
        "router": jax.random.normal(ks[0], (d, e), jnp.float32) * std,
        "w_gate": jax.random.normal(ks[1], (e, d, f), dt) * std,
        "w_up": jax.random.normal(ks[2], (e, d, f), dt) * std,
        "w_down": jax.random.normal(ks[3], (e, f, d), dt)
        * (std / math.sqrt(2 * cfg.n_layers)),
    }


def _capacity(tokens_per_group: int, cfg) -> int:
    c = math.ceil(tokens_per_group * cfg.top_k / cfg.n_experts
                  * cfg.capacity_factor)
    return max(c, 1)


def _dispatch_einsum(x, idx, pos, keep, gate_vals, e, cap, cfg):
    """GShard dense one-hot dispatch: (x_e, combine tensor)."""
    dt = dtype_of(cfg)
    disp_e = jax.nn.one_hot(idx, e, dtype=dt)                     # (B, S, k, E)
    disp_c = jax.nn.one_hot(pos, cap, dtype=dt) * keep[..., None].astype(dt)
    dispatch = jnp.einsum("bske,bskc->bsec", disp_e, disp_c)
    dispatch = shard_as(dispatch, "batch", None, "experts", None)
    combine = jnp.einsum("bske,bskc,bsk->bsec", disp_e, disp_c,
                         gate_vals.astype(dt))
    combine = shard_as(combine, "batch", None, "experts", None)
    x_e = jnp.einsum("bsec,bsd->becd", dispatch, x)               # (B, E, C, D)
    return x_e, combine


def _dispatch_scatter(x, idx, pos, keep, gate_vals, e, cap, cfg):
    """Scatter/gather dispatch: O(S*k*D) instead of O(S*k*E*C).

    Returns (x_e, combine_fn) where combine_fn gathers expert outputs back
    to token order with gate weighting.
    """
    b, s, d = x.shape
    k = idx.shape[-1]
    dt = dtype_of(cfg)
    # flat slot id per (token, k): e * cap + pos; dropped tokens -> e*cap
    slot = jnp.where(keep, idx * cap + pos, e * cap)              # (B, S, k)
    slot_flat = slot.reshape(b, s * k)
    x_rep = jnp.repeat(x, k, axis=1)                              # (B, S*k, D)

    def scatter_row(slots_row, x_row):
        buf = jnp.zeros((e * cap + 1, d), dt)
        return buf.at[slots_row].add(x_row)

    x_e = jax.vmap(scatter_row)(slot_flat, x_rep)[:, :-1]         # drop sink
    x_e = x_e.reshape(b, e, cap, d)

    def combine_gather(y_e):
        y_flat = y_e.reshape(b, e * cap, d)
        sink = jnp.zeros((b, 1, d), y_flat.dtype)
        y_pad = jnp.concatenate([y_flat, sink], axis=1)
        gathered = jnp.take_along_axis(
            y_pad, slot_flat[..., None], axis=1)                  # (B, S*k, D)
        gathered = gathered.reshape(b, s, k, d)
        w = (gate_vals * keep).astype(gathered.dtype)
        return jnp.einsum("bskd,bsk->bsd", gathered, w)

    return x_e, combine_gather


def moe_block(p, x, cfg):
    """x: (B, S, D) -> (y: (B, S, D), aux: dict of router losses).

    Groups = batch rows (tokens never cross rows, so dispatch stays sharded
    over the batch axes).  Two dispatch implementations:

    * ``einsum`` (GShard classic, the default): dense one-hot
      dispatch/combine tensors — MXU-friendly but costs O(S*k*E*C) extra
      FLOPs per layer, about the cost of the experts themselves for mixtral;
    * ``scatter``: segment-sum into capacity slots + gather back, O(S*k*D).
    """
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    cap = _capacity(s, cfg)

    logits = (x.astype(jnp.float32) @ p["router"])          # (B, S, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, idx = jax.lax.top_k(probs, k)                # (B, S, k)
    gate_vals = gate_vals / jnp.clip(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)   # renormalize top-k

    # position of each (token, k) inside its expert's capacity buffer
    expert_onehot = jax.nn.one_hot(idx, e, dtype=jnp.int32)      # (B, S, k, E)
    flat = expert_onehot.reshape(b, s * k, e)
    pos = jnp.cumsum(flat, axis=1) - 1                            # (B, S*k, E)
    pos = (pos * flat).sum(-1).reshape(b, s, k)                   # (B, S, k)
    keep = pos < cap                                              # drop overflow

    dt = dtype_of(cfg)
    if cfg.moe_impl == "scatter":
        x_e, combine_gather = _dispatch_scatter(x, idx, pos, keep, gate_vals,
                                                e, cap, cfg)
    else:
        x_e, combine = _dispatch_einsum(x, idx, pos, keep, gate_vals, e, cap,
                                        cfg)
    x_e = shard_as(x_e, "batch", "experts", None, None)
    h = jnp.einsum("becd,edf->becf", x_e, p["w_up"])
    h = jax.nn.silu(jnp.einsum("becd,edf->becf", x_e, p["w_gate"])) * h
    h = shard_as(h, "batch", "experts", None, "moe_ff")
    y_e = jnp.einsum("becf,efd->becd", h, p["w_down"])
    if cfg.moe_impl == "scatter":
        y = combine_gather(y_e)
    else:
        y = jnp.einsum("bsec,becd->bsd", combine, y_e)
    y = shard_as(y, "batch", "act_seq", "embed")

    # -- router losses -----------------------------------------------------
    # load-balance: mean fraction of tokens per expert x mean router prob
    me = jnp.mean(expert_onehot.astype(jnp.float32).sum(2), axis=(0, 1))  # (E,)
    ce = jnp.mean(probs, axis=(0, 1))
    lb_loss = e * jnp.sum(me * ce) / 1.0
    z_loss = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    return y, {"lb_loss": lb_loss, "z_loss": z_loss}


def moe_decode(p, x, cfg):
    """Decode-time MoE: one token per row. x: (B, 1, D).

    The whole batch forms ONE dispatch group so the capacity buffer stays at
    ~B*top_k*cf/E slots per expert instead of all-experts-per-token.
    """
    b, s, d = x.shape
    assert s == 1
    xt = x.reshape(1, b, d)  # group over batch
    sub = cfg.replace(capacity_factor=max(cfg.capacity_factor, 2.0))
    y, aux = moe_block(p, xt, sub)
    return y.reshape(b, 1, d), aux
