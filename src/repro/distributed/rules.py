"""Per-arch logical-axis rules + parameter/batch PartitionSpecs.

The single rules dict drives everything: activations and KV caches (via
``shard_as`` inside model code), parameters and optimizer state (via the
leaf-name -> logical-dims tables below) and batches.  ``resolve_spec`` silently drops
axes that don't divide, which implements the per-arch fallbacks:

* qwen3-moe: experts (128) % model(16) == 0 -> EP on 'model'; moe_ff stays
  unsharded (axis already used),
* mixtral: experts (8) %% 16 -> dropped; moe_ff (14336) takes 'model' (TP
  inside each expert),
* kv_heads (8) vs model(16) in decode: cache_seq takes 'model' first
  (sequence-sharded decode), kv_heads dropped.
"""
from __future__ import annotations

from typing import Any

import jax
from jax.sharding import Mesh, PartitionSpec as P

from repro.distributed.sharding import resolve_spec

# ---------------------------------------------------------------------------
# logical rules
# ---------------------------------------------------------------------------
def make_rules(mesh: Mesh) -> dict[str, Any]:
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    return {
        # activations
        "batch": batch_axes,
        "seq": None,
        "act_seq": None,     # residual-stream seq sharding (Megatron-SP) when set to 'model'
        "hd_tp": None,       # KV-cache head_dim sharding (alternative to cache_seq)
        "attn_q": None,      # score-tensor q-position sharding (fixes GQA reshard)
        "embed": None,
        "heads": "model",
        "kv_heads": "model",
        "ff": "model",
        "vocab": "model",
        "experts": "model",
        "moe_ff": "model",
        "ssm_heads": "model",
        "cache_seq": "model",
        # parameters
        "layers": None,
        "p_embed": batch_axes,         # FSDP dim of weight matrices
        "p_heads": "model",            # TP dim of weight matrices
        "p_ff": "model",
        "p_vocab": "model",
        "p_experts": "model",
        "p_moe_ff": "model",
        "p_ssm": "model",
    }


# ---------------------------------------------------------------------------
# parameter dims by leaf name (matched against pytree path suffix)
# ---------------------------------------------------------------------------
_2D = {
    "emb": ("p_vocab", "p_embed"),
    "unemb": ("p_embed", "p_vocab"),
    "wq": ("p_embed", "p_heads"),
    "wk": ("p_embed", "p_heads"),
    "wv": ("p_embed", "p_heads"),
    "wo": ("p_heads", "p_embed"),
    "w_up": ("p_embed", "p_ff"),
    "w_gate": ("p_embed", "p_ff"),
    "w_down": ("p_ff", "p_embed"),
    "router": ("p_embed", None),
    "in_proj": ("p_embed", "p_ssm"),
    "out_proj": ("p_ssm", "p_embed"),
    "enc_pos": (None, None),
}
_3D = {  # MoE expert-stacked
    "w_up": ("p_experts", "p_embed", "p_moe_ff"),
    "w_gate": ("p_experts", "p_embed", "p_moe_ff"),
    "w_down": ("p_experts", "p_moe_ff", "p_embed"),
}
_1D = {
    "bq": ("p_heads",), "bk": ("p_heads",), "bv": ("p_heads",),
    "conv_b": ("p_ssm",), "norm_w": ("p_ssm",),
    "a_log": ("p_ssm",), "dt_bias": ("p_ssm",), "d_skip": ("p_ssm",),
}
_2D_OTHER = {"conv_w": (None, "p_ssm")}


def _leaf_dims(path, leaf) -> tuple:
    name = None
    for part in reversed(path):
        if hasattr(part, "key"):
            name = str(part.key)
            break
    nd = leaf.ndim
    in_moe = any(getattr(pp, "key", None) == "moe" for pp in path)
    # per-layer stacking adds a leading 'layers' dim
    def with_layers(dims, rank):
        if len(dims) == rank:
            return dims
        if len(dims) + 1 == rank:
            return ("layers",) + dims
        return (None,) * rank

    if name in _3D and (in_moe or nd >= 3) and name in ("w_up", "w_gate",
                                                        "w_down") and in_moe:
        return with_layers(_3D[name], nd)
    if name in _2D:
        return with_layers(_2D[name], nd)
    if name in _2D_OTHER:
        return with_layers(_2D_OTHER[name], nd)
    if name in _1D:
        return with_layers(_1D[name], nd)
    return (None,) * nd  # norms, scalars, step counters


def tree_specs(mesh: Mesh, rules: dict, tree) -> Any:
    """PartitionSpec tree for a parameter/optimizer pytree."""
    def spec(path, leaf):
        if leaf.ndim == 0:
            return P()
        dims = _leaf_dims(path, leaf)
        return resolve_spec(mesh, leaf.shape, dims, rules)

    return jax.tree_util.tree_map_with_path(spec, tree)


# ---------------------------------------------------------------------------
# batch dims
# ---------------------------------------------------------------------------
_BATCH_DIMS = {
    "tokens": ("batch", None),
    "labels": ("batch", None),
    "vision_emb": ("batch", None, None),
    "frames": ("batch", None, None),
}


def batch_specs_tree(mesh: Mesh, rules: dict, batch) -> Any:
    return {k: resolve_spec(mesh, v.shape, _BATCH_DIMS[k], rules)
            for k, v in batch.items()}

