"""Train-state + step factories (what ``Trainer`` jits)."""
from __future__ import annotations

from typing import Callable

import jax

from repro.models.model import build_model
from repro.train.optimizer import OptConfig, adamw_update, init_opt_state


def init_train_state(key, cfg, opt_cfg: OptConfig):
    model = build_model(cfg)
    params = model.init(key)
    return {"params": params, "opt": init_opt_state(params, opt_cfg)}


def make_train_step(cfg, opt_cfg: OptConfig) -> Callable:
    model = build_model(cfg)
    grad_fn = jax.value_and_grad(model.loss, has_aux=True)

    def train_step(state, batch):
        (loss, metrics), grads = grad_fn(state["params"], batch)
        new_params, new_opt, stats = adamw_update(
            grads, state["opt"], state["params"], opt_cfg)
        metrics = dict(metrics, loss=loss, **stats)
        return {"params": new_params, "opt": new_opt}, metrics

    return train_step
