"""Seeded random weights of a model, made on the device.

The architecture's module (``chipbench/arch``) lists every leaf: its shape,
dtype, scale and stack.  The tree has the layout the serving engine takes:
``params["tok"]`` and one subtree per stack of layers, each leaf with a
leading layer axis of its stack's depth.  Every leaf, and every layer of a
stacked leaf, draws from a key of its own: the key of ``--seed`` folded
with the leaf's path and then with the layer index within its stack.  So
:func:`layer_params` remakes any one layer bit for bit without the others,
which is how the reference gets its weights: from the seed, never from the
program.

Scales keep every sublayer's output near unit variance, so that each
sublayer moves the residual stream; norm gains and biases are drawn as
well (not zeros), so that they are exercised.
"""
from __future__ import annotations

import zlib
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import arch

EMB_STD = 0.02        # token embedding (and the tied output head)
NORM_STD = 0.1        # RMSNorm gains, stored as a delta from 1
BIAS_STD = 0.5        # q/k/v projection biases


def seed_words(seed: int) -> np.ndarray:
    """``--seed`` (any non-negative int up to 2**62) as two uint32 words,
    passed to the jitted makers as data so that no seed compiles anew."""
    if seed < 0 or seed >= 1 << 62:
        raise ValueError(f"seed {seed} out of range [0, 2**62)")
    return np.asarray([seed & 0x7FFFFFFF, seed >> 31], np.uint32)


def _leaf_key(words, path: str):
    k = jax.random.fold_in(jax.random.key(words[0]), words[1])
    return jax.random.fold_in(k, zlib.crc32(path.encode()) & 0x7FFFFFFF)


def _draw(key, shape, dtype, std):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def _nest(flat: dict) -> dict:
    tree: dict = {}
    for path, leaf in flat.items():
        *head, last = path.split("/")
        node = tree
        for part in head:
            node = node.setdefault(part, {})
        node[last] = leaf
    return tree


def _make(words, spec):
    flat = {}
    mod = arch.of(spec)
    depth = mod.stacks(spec)
    for path, (shape, dt, std, stack) in mod.leaves(spec).items():
        k = _leaf_key(words, path)
        if stack is not None:
            flat[path] = jax.vmap(
                lambda i, k=k, shape=shape, dt=dt, std=std:
                _draw(jax.random.fold_in(k, i), shape, dt, std))(
                    jnp.arange(depth[stack]))
        else:
            flat[path] = _draw(k, shape, dt, std)
    return _nest(flat)


def make_params(spec, seed: int):
    """The whole parameter tree, on the default device, in one program."""
    return jax.jit(partial(_make, spec=spec))(seed_words(seed))


def _only_stack(spec) -> str:
    names = list(arch.of(spec).stacks(spec))
    if len(names) != 1:
        raise ValueError(f"{spec.name} has stacks {names}: name one")
    return names[0]


def _layer(words, layer, spec, stack: str):
    flat = {}
    for path, (shape, dt, std, s) in arch.of(spec).leaves(spec).items():
        if s == stack:
            k = jax.random.fold_in(_leaf_key(words, path), layer)
            flat[path.removeprefix(f"{stack}/")] = _draw(k, shape, dt, std)
    return _nest(flat)


def _token(words, spec):
    flat = {}
    for path, (shape, dt, std, stack) in arch.of(spec).leaves(spec).items():
        if stack is None:
            flat[path.removeprefix("tok/")] = _draw(
                _leaf_key(words, path), shape, dt, std)
    return _nest(flat)


@partial(jax.jit, static_argnames=("spec", "stack"))
def layer_params(words, layer, spec, stack: str | None = None):
    """One layer's weights, equal to ``make_params(...)[stack][layer]``;
    ``stack`` may be left out where the architecture has one stack."""
    return _layer(words, layer, spec,
                  stack if stack is not None else _only_stack(spec))


@partial(jax.jit, static_argnames=("spec",))
def token_params(words, spec):
    """Embedding, final norm and (untied) output head."""
    return _token(words, spec)
