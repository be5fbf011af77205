"""Process-level JAX runtime decisions, made from the platform.

* :func:`enable_compile_cache` — where the entry points keep JAX's
  persistent compilation cache;
* :func:`kernel_interpret` — whether a Pallas kernel runs interpreted
  (any backend but the TPU) or compiled (TPU);
* :func:`refuse_child_workers_on_tpu` — one process per chip: a parent that
  has touched JAX holds the TPU, so a child that needs it would fail or hang.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# <repo>/.jax_cache: a fixed path, so a later run finds what an earlier
# one compiled (git-ignored)
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for this process and
    return its directory.  ``JAX_COMPILATION_CACHE_DIR``, when set, is
    JAX's own setting and is left alone; otherwise the cache lives at
    :data:`DEFAULT_CACHE_DIR`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)


def kernel_interpret(interpret: bool | None) -> bool:
    """Resolve a kernel's ``interpret`` argument: ``None`` means compiled
    on a TPU backend and interpreted everywhere else."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def refuse_child_workers_on_tpu(what: str) -> None:
    """Raise before ``what`` spawns child processes that use JAX, when this
    process runs on a TPU backend: the chip belongs to one process at a
    time, so the children could not reach it (and must not quietly fall
    back to the CPU)."""
    if jax.default_backend() == "tpu":
        raise RuntimeError(
            f"{what} spawns child processes that use JAX, but a TPU chip "
            "serves one process at a time and this process holds it "
            "(one process per chip); run it on a CPU backend "
            "(JAX_PLATFORMS=cpu) or serve from this one process")
