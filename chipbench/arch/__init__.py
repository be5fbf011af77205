"""One module per architecture: everything in the benchmark that knows how a
model is built.

A configuration file names its module in its top-level ``"architecture"``
key; ``chipbench/arch/<architecture>.py`` is that module.  The rest of the
benchmark (weights, reference, operation counts, harness) is generic and
reaches the module through the spec it made (:func:`of`).  A module gives:

``model_spec(conf) -> spec``
    A frozen dataclass (a static argument of jitted functions) with at
    least ``name``, ``n_layers``, ``vocab``, ``vocab_rows``, ``d_model``,
    ``dtype``, ``max_batch``, ``max_context`` and ``block_tokens``.
``arch_config(spec)``
    The program's configuration of the model (``repro.configs.base``).
``stacks(spec) -> {stack: depth}``
    The stacks of layers, in the order the model runs them.
``leaves(spec) -> {path: (shape, dtype, std, stack)}``
    Every weight: its shape (of one layer, for a stacked leaf), dtype,
    standard deviation and stack (``None`` for a leaf of the token level,
    whose path starts ``tok/`` and which includes ``tok/emb``).  A stacked
    leaf's path starts with its stack's name.
``layer(x, w, *, spec, stack, mm)``
    The plain float32 reference of one layer of ``stack`` over ``x``
    (n, T, d_model), causal from position 0; ``mm(a, w)`` is every weight
    matmul (float32 or the float8 control).
``final_norm(x, tok, spec)`` and ``head(spec, tok)``
    The last norm and the output head matrix (d_model, vocab rows).
``token_weights(spec)`` and ``attention_ops(spec)``
    Weights one token multiplies through every layer, and the operations
    it spends per position it attends to, over every layer.
``cache_shape(spec)``
    The engine's cache (layers, rows, positions, ...), whose page-out
    slices the warm-up compiles.
"""
from __future__ import annotations

import importlib.util
import sys
from pathlib import Path
from types import ModuleType

ARCH_DIR = Path(__file__).resolve().parent


def load(name: str | None, arch_dir: Path = ARCH_DIR) -> ModuleType:
    """The module ``arch_dir/<name>.py``; an error naming the modules that
    exist when there is none of that name."""
    known = sorted(p.stem for p in arch_dir.glob("*.py")
                   if p.stem != "__init__")
    if name not in known:
        raise ValueError(f"unknown architecture {name!r}: a configuration's "
                         f"\"architecture\" names one of {known}")
    if arch_dir == ARCH_DIR:
        return importlib.import_module(f"{__name__}.{name}")
    path = arch_dir / f"{name}.py"
    mod_name = f"chipbench_arch:{path}"
    if mod_name not in sys.modules:
        mod_spec = importlib.util.spec_from_file_location(mod_name, path)
        mod = importlib.util.module_from_spec(mod_spec)
        sys.modules[mod_name] = mod
        mod_spec.loader.exec_module(mod)
    return sys.modules[mod_name]


def of(spec) -> ModuleType:
    """The architecture module that made ``spec``."""
    return sys.modules[type(spec).__module__]
