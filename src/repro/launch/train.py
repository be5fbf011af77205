"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train --arch phi4-mini-3.8b \
        --steps 200 --preset small --workdir /tmp/run1 [--resume]

Presets scale the assigned architecture down for CPU execution while keeping
its family structure; ``full`` keeps the published widths.
"""
from __future__ import annotations

import argparse
import json

from repro.configs import ARCHS
from repro.runtime import enable_compile_cache
from repro.train.optimizer import OptConfig
from repro.train.trainer import TrainConfig, Trainer

PRESETS = {
    # ~2M params: CI-speed smoke of the full loop
    "tiny": dict(n_layers=2, d_model=128, d_ff=256, vocab=512),
    # ~20M params: default e2e demo
    "small": dict(n_layers=4, d_model=384, d_ff=1024, vocab=4096),
    # ~100M params: the deliverable-scale run (slow on 1 CPU core)
    "100m": dict(n_layers=12, d_model=768, d_ff=2048, vocab=32000),
    "full": {},
}


def build_cfg(arch: str, preset: str):
    cfg = ARCHS[arch]
    if preset == "full":
        return cfg
    red = cfg.reduced()
    kw = dict(PRESETS[preset])
    if cfg.n_heads:
        kw.update(n_heads=min(cfg.n_heads, 8), n_kv_heads=min(cfg.n_kv_heads, 4),
                  head_dim=kw.get("d_model", 128) // min(cfg.n_heads, 8))
    if cfg.family in ("ssm", "hybrid"):
        kw.update(ssm_state=32, ssm_head_dim=32, ssm_chunk=64)
    if cfg.family == "moe":
        kw.update(n_experts=min(cfg.n_experts, 8), top_k=min(cfg.top_k, 2),
                  moe_d_ff=kw.get("d_ff", 256) // 2)
    kw["name"] = f"{cfg.name}-{preset}"
    return red.replace(**kw)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="phi4-mini-3.8b", choices=sorted(ARCHS))
    ap.add_argument("--preset", default="small", choices=sorted(PRESETS))
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--workdir", default="/tmp/repro_train")
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--crash-at", type=int, default=None)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = build_cfg(args.arch, args.preset)
    tc = TrainConfig(steps=args.steps, batch=args.batch, seq=args.seq,
                     workdir=args.workdir, resume=args.resume,
                     ckpt_every=args.ckpt_every, crash_at_step=args.crash_at)
    opt = OptConfig(peak_lr=args.lr, warmup_steps=max(args.steps // 20, 5),
                    decay_steps=args.steps)
    trainer = Trainer(cfg, tc, opt)
    result = trainer.run()
    print(json.dumps({"arch": cfg.name,
                      "final_loss": result["final_loss"],
                      "pipeline": result["pipeline"]}, indent=1))


if __name__ == "__main__":
    main()
