"""Per-arch smoke tests (deliverable f) + cross-family consistency."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hlo_ops import cache_aliases, ops_of_shape
from repro.configs import ARCHS, get_arch
from repro.models import layers as L
from repro.models.model import build_model
from repro.serve.engine import ServeEngine


def tiny_batch(cfg, B=2, S=32, with_labels=True, key=0):
    ks = jax.random.split(jax.random.key(key), 3)
    batch = {"tokens": jax.random.randint(ks[0], (B, S), 0, cfg.vocab)}
    if with_labels:
        batch["labels"] = jax.random.randint(ks[1], (B, S), 0, cfg.vocab)
    if cfg.family == "vlm":
        batch["vision_emb"] = jax.random.normal(
            ks[2], (B, cfg.n_img_tokens, cfg.d_model),
            jnp.dtype(cfg.dtype)) * 0.02
    if cfg.family == "audio":
        batch["frames"] = jax.random.normal(
            ks[2], (B, cfg.enc_frames, cfg.d_model),
            jnp.dtype(cfg.dtype)) * 0.02
    return batch


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_smoke_reduced_train_step(arch):
    """Reduced same-family config: one forward/train step, shapes + finite."""
    cfg = ARCHS[arch].reduced()
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    batch = tiny_batch(cfg)
    (loss, metrics), grads = jax.jit(
        jax.value_and_grad(model.loss, has_aux=True))(params, batch)
    assert np.isfinite(float(loss)), arch
    assert float(loss) > 0
    for g in jax.tree.leaves(grads):
        assert np.isfinite(np.asarray(g, np.float32)).all(), arch
    # shapes preserved through the update path
    assert jax.tree.structure(grads) == jax.tree.structure(params)


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_smoke_prefill_decode(arch):
    cfg = ARCHS[arch].reduced()
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    B, S = 2, 32
    batch = tiny_batch(cfg, B=B, S=S, with_labels=False)
    logits, cache = jax.jit(model.prefill)(params, batch)
    assert logits.shape == (B, cfg.vocab)
    assert np.isfinite(np.asarray(logits)).all()
    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    pos = jnp.asarray(S - 1 if cfg.family not in ("ssm",) else S, jnp.int32)
    # write into the last slot for attention caches (capacity == S)
    logits2, cache2 = jax.jit(model.decode_step)(params, cache, tok, pos)
    assert logits2.shape == (B, cfg.vocab)
    assert np.isfinite(np.asarray(logits2)).all()


def test_decode_matches_full_forward_dense():
    cfg = ARCHS["qwen2.5-14b"].reduced().replace(dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    S = 32
    toks = jax.random.randint(jax.random.key(2), (1, S + 1), 0, cfg.vocab)
    full, _ = model.prefill(params, {"tokens": toks})
    _, cache = model.prefill(params, {"tokens": toks[:, :S]})
    cache = {k: jnp.concatenate(
        [v, jnp.zeros((*v.shape[:2], 1, *v.shape[3:]), v.dtype)], axis=2)
        for k, v in cache.items()}
    dec, _ = model.decode_step(params, cache, toks[:, S:],
                               jnp.asarray(S, jnp.int32))
    np.testing.assert_allclose(np.asarray(dec), np.asarray(full),
                               rtol=2e-3, atol=2e-3)


def test_sliding_window_ring_cache():
    cfg = ARCHS["mixtral-8x7b"].reduced().replace(
        dtype="float32", capacity_factor=16.0)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    S = cfg.sliding_window  # ring exactly full
    toks = jax.random.randint(jax.random.key(2), (1, S + 1), 0, cfg.vocab)
    full, _ = model.prefill(params, {"tokens": toks})
    _, cache = model.prefill(params, {"tokens": toks[:, :S]})
    dec, _ = model.decode_step(params, cache, toks[:, S:],
                               jnp.asarray(S, jnp.int32))
    np.testing.assert_allclose(np.asarray(dec), np.asarray(full),
                               rtol=2e-3, atol=2e-3)


def test_moe_scatter_equals_einsum():
    cfg_e = ARCHS["qwen3-moe-30b-a3b"].reduced().replace(
        dtype="float32", moe_impl="einsum")
    cfg_s = cfg_e.replace(moe_impl="scatter")
    me, ms = build_model(cfg_e), build_model(cfg_s)
    params = me.init(jax.random.key(0))
    batch = tiny_batch(cfg_e)
    (l1, _), g1 = jax.value_and_grad(lambda p: me.loss(p, batch),
                                     has_aux=True)(params)
    (l2, _), g2 = jax.value_and_grad(lambda p: ms.loss(p, batch),
                                     has_aux=True)(params)
    assert abs(float(l1) - float(l2)) < 1e-5
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_ssd_chunk_invariance():
    """SSD output must not depend on the chunk size (property of the
    chunked decomposition)."""
    cfg = ARCHS["mamba2-2.7b"].reduced().replace(dtype="float32")
    model8 = build_model(cfg.replace(ssm_chunk=8))
    model32 = build_model(cfg.replace(ssm_chunk=32))
    params = model8.init(jax.random.key(0))
    batch = tiny_batch(cfg)
    l1, _ = model8.loss(params, batch)
    l2, _ = model32.loss(params, batch)
    assert abs(float(l1) - float(l2)) < 1e-5


@pytest.mark.parametrize("causal,window", [(True, 0), (True, 16),
                                           (False, 0)],
                         ids=["causal", "window16", "noncausal"])
def test_chunked_attention_matches_one_chunk(causal, window):
    """The query-chunk scan over four chunks gives what one chunk over all
    the queries gives: each chunk's positions, mask and window line up."""
    cfg = ARCHS["qwen2.5-14b"].reduced().replace(
        dtype="float32", sliding_window=window)
    B, S = 2, 256
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (B, S, cfg.n_heads, cfg.hd))
    k = jax.random.normal(ks[1], (B, S, cfg.n_kv_heads, cfg.hd))
    v = jax.random.normal(ks[2], (B, S, cfg.n_kv_heads, cfg.hd))
    chunked = L.attention(q, k, v, cfg.replace(attn_chunk=64), causal=causal)
    whole = L.attention(q, k, v, cfg.replace(attn_chunk=S), causal=causal)
    np.testing.assert_allclose(np.asarray(chunked), np.asarray(whole),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("masked", [False, True], ids=["all", "masked"])
@pytest.mark.parametrize("arch", ["phi4-mini-3.8b", "qwen2.5-14b"],
                         ids=["tied", "untied"])
def test_chunked_xent_matches_plain_cross_entropy(arch, masked):
    """The sequence-chunked loss equals logsumexp minus the gold logit on
    the full float32 logits, averaged over the weighted positions."""
    cfg = ARCHS[arch].reduced().replace(dtype="float32", loss_chunk=64)
    B, S = 2, 256
    tok = L.init_embeddings(jax.random.key(0), cfg)
    ks = jax.random.split(jax.random.key(1), 3)
    x = jax.random.normal(ks[0], (B, S, cfg.d_model))
    labels = jax.random.randint(ks[1], (B, S), 0, cfg.vocab)
    weights = (jax.random.uniform(ks[2], (B, S)) < 0.5).astype(jnp.float32) \
        if masked else None
    got = L.chunked_xent(tok, x, labels, cfg, weights=weights)

    w = tok["emb"].T if cfg.tie_embeddings else tok["unemb"]
    logits = (x @ w).astype(jnp.float32)
    gold = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    nll = jax.nn.logsumexp(logits, -1) - gold
    wts = jnp.ones((B, S)) if weights is None else weights
    want = jnp.sum(nll * wts) / jnp.maximum(jnp.sum(wts), 1.0)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-5)


@pytest.mark.parametrize("arch", ["phi4-mini-3.8b", "qwen2.5-14b"])
def test_continuous_decode_matches_full_forward(arch):
    """Rows at different lengths decode several steps into one donated
    cache, as the continuous engine runs them: every step's logits equal
    the full forward over that row's prefix."""
    cfg = ARCHS[arch].reduced().replace(dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    lens, steps, S = (5, 11, 18), 4, 32
    toks = jax.random.randint(jax.random.key(2), (len(lens), S), 0,
                              cfg.vocab)
    shape = (cfg.n_layers, len(lens), S, cfg.n_kv_heads, cfg.hd)
    cache = {"k": jnp.zeros(shape), "v": jnp.zeros(shape)}
    for row, n in enumerate(lens):       # left-aligned, as the engine keeps
        _, kv = model.prefill(params, {"tokens": toks[row:row + 1, :n]})
        cache = {name: c.at[:, row, :n].set(kv[name][:, 0])
                 for name, c in cache.items()}
    step = jax.jit(model.decode_step, donate_argnums=(1,))
    prefill = jax.jit(model.prefill)
    pos = np.asarray(lens, np.int32)
    for _ in range(steps):
        tok = np.asarray(toks)[np.arange(len(lens)), pos][:, None]
        dec, cache = step(params, cache, jnp.asarray(tok), jnp.asarray(pos))
        for row, p in enumerate(pos):
            full, _ = prefill(params, {"tokens": toks[row:row + 1, :p + 1]})
            np.testing.assert_allclose(np.asarray(dec[row]),
                                       np.asarray(full[0]),
                                       rtol=2e-3, atol=2e-3)
        pos = pos + 1


@pytest.mark.parametrize("pos_shape", [(4,), ()], ids=["per_row", "scalar"])
@pytest.mark.parametrize("arch", ["phi4-mini-3.8b", "qwen2.5-14b"],
                         ids=["tied", "biased"])
def test_engine_decode_updates_the_cache_in_place(arch, pos_shape):
    """The engine's compiled decode step writes each new entry into the
    donated cache: no copy of the whole stacked cache, and no rebuilt
    stack written back into it."""
    cfg = ARCHS[arch].reduced()
    engine = ServeEngine(cfg, max_batch=4, max_context=64)
    cache = engine._alloc_cache()
    txt = engine._decode.lower(
        engine.params, cache, jnp.zeros((4, 1), jnp.int32),
        jnp.zeros(pos_shape, jnp.int32)).compile().as_text()
    assert ops_of_shape(txt, ("copy", "dynamic-update-slice"),
                        cache["k"].shape) == []
    assert len(cache_aliases(txt, engine.params)) == 2


def test_vlm_loss_masks_image_positions():
    cfg = ARCHS["internvl2-26b"].reduced().replace(dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    batch = tiny_batch(cfg)
    # corrupting labels at image positions must not change the loss
    l1, _ = model.loss(params, batch)
    labels2 = batch["labels"].at[:, :cfg.n_img_tokens].set(0)
    l2, _ = model.loss(params, dict(batch, labels=labels2))
    assert abs(float(l1) - float(l2)) < 1e-6


def test_param_counts_match_published():
    expected = {"qwen2.5-14b": 14.8, "llama3-405b": 405.9,
                "qwen3-moe-30b-a3b": 30.5, "mixtral-8x7b": 46.7,
                "mamba2-2.7b": 2.8, "zamba2-1.2b": 1.2}
    for name, billions in expected.items():
        tot, _ = get_arch(name).param_count()
        assert abs(tot / 1e9 - billions) / billions < 0.06, name

