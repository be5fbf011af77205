"""build_model(cfg) for every family, and abstract_params(cfg) (shapes only)."""
from __future__ import annotations

from typing import Any

import jax

from repro.configs.base import ArchConfig
from repro.models import serve_paths as S
from repro.models import transformer as T


def build_model(cfg: ArchConfig) -> T.Model:
    fam = cfg.family
    if fam in ("dense", "vlm", "moe"):
        prefill, decode = S.decoder_prefill, S.decoder_decode_step
    elif fam == "audio":
        prefill, decode = S.audio_prefill, S.audio_decode_step
    elif fam == "ssm":
        prefill, decode = S.ssm_prefill, S.ssm_decode_step
    elif fam == "hybrid":
        prefill, decode = S.hybrid_prefill, S.hybrid_decode_step
    else:
        raise ValueError(fam)
    return T.Model(
        cfg=cfg,
        init=lambda key: T.init_params(key, cfg),
        loss=lambda params, batch: T.model_loss(params, batch, cfg),
        prefill=lambda params, batch: prefill(params, batch, cfg),
        decode_step=lambda params, cache, token, pos: decode(
            params, cache, token, pos, cfg),
    )


def abstract_params(cfg: ArchConfig) -> Any:
    """Parameter ShapeDtypeStructs without materializing (eval_shape)."""
    return jax.eval_shape(lambda: T.init_params(jax.random.key(0), cfg))
