"""InternVL2-26B — InternViT frontend (STUB) + InternLM2-20B-style backbone.
[arXiv:2404.16821; hf]

The modality frontend is a stub: the batch's ``vision_emb`` carries
precomputed patch embeddings occupying the first ``n_img_tokens`` sequence
positions.
"""
from repro.configs.base import ArchConfig

CONFIG = ArchConfig(
    name="internvl2-26b", family="vlm",
    n_layers=48, d_model=6144, n_heads=48, n_kv_heads=8, d_ff=16384,
    vocab=92553, head_dim=128, n_img_tokens=256,
)
