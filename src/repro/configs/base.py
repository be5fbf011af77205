"""Architecture + run configuration.

One ``ArchConfig`` instance per assigned architecture (see sibling modules).
``reduced()`` returns a same-family miniature for CPU smoke tests; the
configs that fit one TPU chip whole (phi4-mini-3.8b) are served at full
width.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any


@dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str                      # dense | moe | vlm | audio | ssm | hybrid
    n_layers: int
    d_model: int
    n_heads: int                     # 0 for attention-free (ssm)
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int = 0                # 0 -> d_model // n_heads
    qkv_bias: bool = False
    tie_embeddings: bool = False
    rope_theta: float = 10_000.0
    norm_eps: float = 1e-5
    act: str = "silu"                # silu (SwiGLU) | gelu

    # --- MoE ---
    n_experts: int = 0
    top_k: int = 0
    moe_d_ff: int = 0                # per-expert hidden dim (0 -> d_ff)
    capacity_factor: float = 1.25
    moe_impl: str = "einsum"         # einsum (GShard baseline) | scatter

    # --- attention variants ---
    sliding_window: int = 0          # 0 -> full causal

    # --- SSM (Mamba2/SSD) ---
    ssm_state: int = 0
    ssm_expand: int = 2
    ssm_head_dim: int = 64
    ssm_conv: int = 4
    ssm_chunk: int = 256

    # --- hybrid (zamba2-style shared attention) ---
    attn_every: int = 0              # shared attn block applied every k layers

    # --- enc-dec (whisper) ---
    n_enc_layers: int = 0
    enc_frames: int = 1500           # precomputed frame embeddings (stub frontend)

    # --- VLM ---
    n_img_tokens: int = 0            # prefix positions carrying patch embeddings

    # --- numerics / structure ---
    pad_vocab_to: int = 256   # embedding rows padded so 'model' axis divides
    dtype: str = "bfloat16"
    attn_chunk: int = 1024           # query-chunked reference attention
    loss_chunk: int = 512            # sequence-chunked softmax-xent
    attention_impl: str = "chunked"  # chunked | pallas

    @property
    def hd(self) -> int:
        if self.head_dim:
            return self.head_dim
        return self.d_model // max(self.n_heads, 1)

    @property
    def padded_vocab(self) -> int:
        """Embedding-table rows: vocab rounded up so TP axes divide evenly.

        Indivisible vocabs (whisper 51865, mamba2 50280, internvl2 92553)
        otherwise force the logits/loss compute to replicate across the
        'model' axis.  Standard practice (MaxText et al.).
        """
        m = self.pad_vocab_to
        return ((self.vocab + m - 1) // m) * m if m else self.vocab

    @property
    def d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def n_ssm_heads(self) -> int:
        return self.d_inner // self.ssm_head_dim

    def replace(self, **kw) -> "ArchConfig":
        return dataclasses.replace(self, **kw)

    # -- analytic parameter count ---------------------------------------------
    def param_count(self) -> tuple[int, int]:
        """(total_params, active_params) — active differs for MoE."""
        D, FF, V, L = self.d_model, self.d_ff, self.vocab, self.n_layers
        HD = self.hd

        def attn_params() -> int:
            p = D * self.n_heads * HD + 2 * D * self.n_kv_heads * HD \
                + self.n_heads * HD * D
            if self.qkv_bias:
                p += (self.n_heads + 2 * self.n_kv_heads) * HD
            return p

        def mlp_params(ff: int) -> int:
            return 3 * D * ff if self.act == "silu" else 2 * D * ff

        def ssm_params() -> int:
            di, st, nh = self.d_inner, self.ssm_state, self.n_ssm_heads
            in_proj = D * (2 * di + 2 * st + nh)
            conv = (di + 2 * st) * self.ssm_conv
            out = di * D
            extra = 2 * nh + nh + di  # A_log, dt_bias, D_skip, gating norm
            return in_proj + conv + out + extra

        emb = V * D * (1 if self.tie_embeddings else 2)
        total = active = emb

        if self.family in ("dense", "vlm"):
            per = attn_params() + mlp_params(FF) + 2 * D
            total += L * per
            active = total
        elif self.family == "moe":
            moe_ff = self.moe_d_ff or FF
            per_tot = attn_params() + self.n_experts * mlp_params(moe_ff) \
                + D * self.n_experts + 2 * D
            per_act = attn_params() + self.top_k * mlp_params(moe_ff) \
                + D * self.n_experts + 2 * D
            total += L * per_tot
            active += L * per_act
        elif self.family == "audio":
            dec = attn_params() * 2 + mlp_params(FF) + 3 * D  # self+cross
            enc = attn_params() + mlp_params(FF) + 2 * D
            total += L * dec + self.n_enc_layers * enc
            active = total
        elif self.family == "ssm":
            total += L * (ssm_params() + D)
            active = total
        elif self.family == "hybrid":
            shared = attn_params() + mlp_params(FF) + 2 * D
            total += L * (ssm_params() + D) + shared
            active = total
        else:
            raise ValueError(self.family)
        return total, active

    def reduced(self) -> "ArchConfig":
        """Same-family miniature for CPU smoke tests."""
        kw: dict[str, Any] = dict(
            name=self.name + "-smoke",
            n_layers=min(self.n_layers, 4 if self.family != "hybrid" else 5),
            d_model=128,
            n_heads=min(self.n_heads, 4) if self.n_heads else 0,
            n_kv_heads=min(self.n_kv_heads, 2) if self.n_kv_heads else 0,
            head_dim=32 if self.n_heads else 0,
            d_ff=256 if self.d_ff else 0,
            vocab=512,
            attn_chunk=64,
            loss_chunk=64,
        )
        if self.family == "moe":
            kw.update(n_experts=4, top_k=2, moe_d_ff=64)
        if self.family in ("ssm", "hybrid"):
            kw.update(ssm_state=16, ssm_head_dim=16, ssm_chunk=32)
        if self.family == "hybrid":
            kw.update(attn_every=2, n_heads=4, n_kv_heads=4, head_dim=32)
        if self.family == "audio":
            kw.update(n_enc_layers=2, enc_frames=8)
        if self.family == "vlm":
            kw.update(n_img_tokens=4)
        if self.sliding_window:
            kw.update(sliding_window=64)
        return self.replace(**kw)

