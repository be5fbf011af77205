"""Plain float32 forward of a served model, and the comparison that decides
``correct`` for it.

It embeds the tokens, walks the architecture's stacks of layers in order
and applies its final norm and output head, each as the architecture's
module (``chipbench/arch/<name>.py``) writes the published equations.  It
computes in float32 at ``HIGHEST`` matmul precision, layer by layer, with
weights remade from the seed by ``chipbench.weights`` one layer at a time:
it imports nothing of the program and takes nothing the program made.

:func:`served_gaps` runs every sequence (prompt + served tokens) through
the whole forward once and reads, at each served position, how far the
served token's logit lies below the reference's best logit.  The control
(``control=True``) is the same forward with every weight matmul computed in
float8 (e4m3, one scale per row of activations and per column of
weights), the precision step below the bfloat16 the configurations state;
its reading is the gap of the token that the float8 forward puts first.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import arch
from chipbench import weights as W

HI = jax.lax.Precision.HIGHEST
F8_MAX = 448.0                 # largest finite float8_e4m3fn
HEAD_ROWS = 512                # positions per output-head block


def mm(a, w):
    return jnp.matmul(a, w.astype(jnp.float32), precision=HI)


def _q8(a, axis):
    s = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def mm8(a, w):
    return jnp.matmul(_q8(a, -1), _q8(w.astype(jnp.float32), 0), precision=HI)


def rms(x, gain, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + gain.astype(jnp.float32))


def rope(x, theta):
    """x: (n, T, heads, hd) at positions 0..T-1; pairs dim i with i+hd/2."""
    t, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) * 2 / hd)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@partial(jax.jit, static_argnames=("spec", "stack", "f8"))
def _layer(x, w, *, spec, stack: str, f8: bool):
    """One layer of ``stack`` over x: (n, T, D) float32."""
    return arch.of(spec).layer(x, w, spec=spec, stack=stack,
                               mm=mm8 if f8 else mm)


@partial(jax.jit, static_argnames=("spec", "f8"))
def _head_block(h, targets, head, *, spec, f8: bool):
    """Logits of a block of positions over the real vocabulary: the best
    logit, the target's logit and the argmax."""
    logits = (mm8 if f8 else mm)(h, head)[:, :spec.vocab]
    best = jnp.max(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
    return best, tgt, jnp.argmax(logits, axis=-1), logits


@partial(jax.jit, static_argnames=("spec",))
def _embed(tokens, tok, *, spec):
    return tok["emb"][tokens].astype(jnp.float32)


@partial(jax.jit, static_argnames=("spec",))
def _final(x, tok, *, spec):
    return arch.of(spec).final_norm(x, tok, spec).reshape(-1, spec.d_model)


@jax.jit
def _gap_at(logits, best, idx):
    return best - jnp.take_along_axis(logits, idx[:, None], axis=-1)[:, 0]


def _hidden(spec, words, toks: np.ndarray, modes) -> tuple:
    """Final normed hidden states, (n * T, D) float32, for each mode
    (False: float32, True: float8 control), and the token parameters."""
    tok = W.token_params(words, spec)
    x = _embed(jnp.asarray(toks), tok, spec=spec)
    xs = {f8: x for f8 in modes}
    for stack, depth in arch.of(spec).stacks(spec).items():
        for layer in range(depth):
            w = W.layer_params(words, jnp.int32(layer), spec, stack)
            xs = {f8: _layer(v, w, spec=spec, stack=stack, f8=f8)
                  for f8, v in xs.items()}
            del w
    return {f8: _final(v, tok, spec=spec) for f8, v in xs.items()}, tok


def logits(spec, seed: int, toks: np.ndarray) -> np.ndarray:
    """Reference logits over the real vocabulary at every position of
    ``toks`` (n, T) — for tests at small sizes."""
    hs, tok = _hidden(spec, W.seed_words(seed), toks, (False,))
    out = mm(hs[False], arch.of(spec).head(spec, tok))[:, :spec.vocab]
    return np.asarray(out).reshape(*toks.shape, spec.vocab)


def served_gaps(spec, seed: int, seqs, *, length: int,
                control: bool = False) -> dict:
    """Reference readings over served sequences.

    ``seqs``: list of ``(prompt, served)`` token lists; ``length``: the
    padded sequence length (one shape for every run, so one compile).
    Returns ``{"gaps": [array per sequence]}`` — per served token, the
    reference's best logit minus the served token's — and with
    ``control=True`` also ``"control_gaps"``: per served position, the
    reference's best logit minus its logit for the float8 forward's argmax.
    """
    n = len(seqs)
    toks = np.zeros((n, length), np.int32)
    for i, (prompt, out) in enumerate(seqs):
        s = list(prompt) + list(out)
        if len(s) > length:
            raise ValueError(f"sequence of {len(s)} tokens > {length}")
        toks[i, :len(s)] = s
    targets = np.zeros_like(toks)
    targets[:, :-1] = toks[:, 1:]
    hs, tok = _hidden(spec, W.seed_words(seed), toks,
                      (False, True) if control else (False,))
    head = arch.of(spec).head(spec, tok)
    flat_t = jnp.asarray(targets.reshape(-1))
    rows = n * length
    gap = np.zeros(rows, np.float64)
    cgap = np.zeros(rows, np.float64)
    for s0 in range(0, rows, HEAD_ROWS):
        sl = slice(s0, min(s0 + HEAD_ROWS, rows))
        best, tgt, _, lg = _head_block(hs[False][sl], flat_t[sl], head,
                                       spec=spec, f8=False)
        gap[sl] = np.asarray(best - tgt, np.float64)
        if control:
            _, _, arg8, _ = _head_block(hs[True][sl], flat_t[sl], head,
                                        spec=spec, f8=True)
            cgap[sl] = np.asarray(_gap_at(lg, best, arg8), np.float64)
        del lg
    gap = gap.reshape(n, length)
    cgap = cgap.reshape(n, length)
    out = {"gaps": []}
    if control:
        out["control_gaps"] = []
    for i, (prompt, served) in enumerate(seqs):
        pos = slice(len(prompt) - 1, len(prompt) - 1 + len(served))
        out["gaps"].append(gap[i, pos])
        if control:
            out["control_gaps"].append(cgap[i, pos])
    return out
