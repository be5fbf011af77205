"""AdamW + schedules, implemented directly in JAX (no optax dependency).

First/second moments are kept in fp32.  Global-norm clipping and decoupled
weight decay included.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class OptConfig:
    peak_lr: float = 3e-4
    warmup_steps: int = 100
    decay_steps: int = 10_000
    min_lr_ratio: float = 0.1
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    clip_norm: float = 1.0


def lr_at(step, cfg: OptConfig):
    step = step.astype(jnp.float32) if hasattr(step, "astype") else float(step)
    warm = cfg.peak_lr * jnp.minimum(1.0, (step + 1) / max(cfg.warmup_steps, 1))
    t = jnp.clip((step - cfg.warmup_steps)
                 / max(cfg.decay_steps - cfg.warmup_steps, 1), 0.0, 1.0)
    cos = cfg.min_lr_ratio + (1 - cfg.min_lr_ratio) * 0.5 * (1 + jnp.cos(math.pi * t))
    return jnp.where(step < cfg.warmup_steps, warm, cfg.peak_lr * cos)


def init_opt_state(params, cfg: OptConfig):
    zeros = lambda p: jnp.zeros(p.shape, jnp.float32)
    return {"mu": jax.tree.map(zeros, params),
            "nu": jax.tree.map(zeros, params),
            "step": jnp.zeros((), jnp.int32)}


def global_norm(tree):
    return jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                        for g in jax.tree.leaves(tree)))


def _decayable(path) -> bool:
    """No weight decay on norms/biases/scalars (standard practice)."""
    name = str(path[-1]) if path else ""
    return not any(t in name for t in ("ln", "norm", "bias", "b'", "a_log",
                                       "dt_bias", "d_skip", "pos"))


def adamw_update(grads, opt_state, params, cfg: OptConfig):
    """Returns (new_params, new_opt_state, stats)."""
    step = opt_state["step"] + 1
    gnorm = global_norm(grads)
    scale = jnp.minimum(1.0, cfg.clip_norm / jnp.maximum(gnorm, 1e-9)) \
        if cfg.clip_norm else 1.0
    lr = lr_at(step, cfg)

    b1c = 1 - cfg.b1 ** step.astype(jnp.float32)
    b2c = 1 - cfg.b2 ** step.astype(jnp.float32)

    def upd(path, p, g, mu, nu):
        g = g.astype(jnp.float32) * scale
        mu = cfg.b1 * mu + (1 - cfg.b1) * g
        nu = cfg.b2 * nu + (1 - cfg.b2) * g * g
        update = (mu / b1c) / (jnp.sqrt(nu / b2c) + cfg.eps)
        if cfg.weight_decay and _decayable(path):
            update = update + cfg.weight_decay * p.astype(jnp.float32)
        new_p = p.astype(jnp.float32) - lr * update
        return new_p.astype(p.dtype), mu, nu

    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    treedef = jax.tree.structure(params)
    g_l = jax.tree.leaves(grads)
    mu_l = jax.tree.leaves(opt_state["mu"])
    nu_l = jax.tree.leaves(opt_state["nu"])
    out = [upd(path, p, g, mu, nu)
           for (path, p), g, mu, nu in zip(flat, g_l, mu_l, nu_l)]
    new_params = jax.tree.unflatten(treedef, [o[0] for o in out])
    new_mu = jax.tree.unflatten(treedef, [o[1] for o in out])
    new_nu = jax.tree.unflatten(treedef, [o[2] for o in out])
    stats = {"grad_norm": gnorm, "lr": lr}
    return new_params, {"mu": new_mu, "nu": new_nu, "step": step}, stats
