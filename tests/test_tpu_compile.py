"""Compile for a described TPU v5e chip (no chip attached).

The TPU compiler is installed with jaxlib's TPU library and compiles for a
topology that is described rather than attached.  These tests compile the
Pallas kernels at the served model's widths and phi4-mini-3.8b's
continuous decode step, which interpret-mode tests cannot check: block
layouts the TPU lowering refuses, and programs that do not fit the chip.
Nothing runs, so they say nothing about results or times.

Only one process at a time may load the TPU library, and it keeps it until
it exits: the topology is described inside a fixture of this one file,
never at import time.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from hlo_ops import cache_aliases, ops_of_shape
from repro.configs import ARCHS
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.ssd_scan import ssd_scan
from repro.models.model import abstract_params, build_model

V5E_HBM_BYTES = 16 * 10**9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    # a described chip's compiles could be written to a persistent cache
    # but never read back without the chip
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was_on)
        cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=sharding)


def _compile_kernel(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()   # a Mosaic kernel ran
    return compiled


def test_flash_attention_compiles_at_phi4_widths(one_chip):
    b, s, h, kv, hd = 1, 2048, 24, 8, 128
    _compile_kernel(
        lambda q, k, v: flash_attention(q, k, v, causal=True,
                                        interpret=False),
        _sds((b, s, h, hd), "bfloat16", one_chip),
        _sds((b, s, kv, hd), "bfloat16", one_chip),
        _sds((b, s, kv, hd), "bfloat16", one_chip))


def test_decode_attention_compiles_at_phi4_widths(one_chip):
    b, s, h, kv, hd = 8, 2048, 24, 8, 128
    _compile_kernel(
        lambda q, k, v, n: decode_attention(q, k, v, n, interpret=False),
        _sds((b, 1, h, hd), "bfloat16", one_chip),
        _sds((b, s, kv, hd), "bfloat16", one_chip),
        _sds((b, s, kv, hd), "bfloat16", one_chip),
        _sds((), "int32", one_chip))


def test_ssd_scan_compiles_at_mamba2_widths(one_chip):
    cfg = ARCHS["mamba2-2.7b"]
    b, length = 1, 1024
    h, p, n = cfg.n_ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    _compile_kernel(
        lambda x, dt, a, b_, c_: ssd_scan(x, dt, a, b_, c_,
                                          chunk=cfg.ssm_chunk,
                                          interpret=False),
        _sds((b, length, h, p), "bfloat16", one_chip),
        _sds((b, length, h), "float32", one_chip),
        _sds((h,), "float32", one_chip),
        _sds((b, length, n), "bfloat16", one_chip),
        _sds((b, length, n), "bfloat16", one_chip))


def test_phi4_decode_step_compiles_and_fits_one_chip(one_chip):
    """The continuous engine's decode step (per-row positions, donated
    cache) at B=8 rows x 2048 context, full width."""
    cfg = ARCHS["phi4-mini-3.8b"]
    batch, context = 8, 2048
    params = jax.tree.map(
        lambda a: _sds(a.shape, a.dtype, one_chip), abstract_params(cfg))
    shape = (cfg.n_layers, batch, context, cfg.n_kv_heads, cfg.hd)
    cache = {"k": _sds(shape, cfg.dtype, one_chip),
             "v": _sds(shape, cfg.dtype, one_chip)}
    step = jax.jit(build_model(cfg).decode_step, donate_argnums=(1,))
    compiled = step.lower(params, cache,
                          _sds((batch, 1), "int32", one_chip),
                          _sds((batch,), "int32", one_chip)).compile()
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert m.argument_size_in_bytes > 9 * 10**9      # weights + cache
    assert total < V5E_HBM_BYTES, total


@pytest.mark.parametrize("arch,n_layers", [("phi4-mini-3.8b", 32),
                                           ("qwen2.5-14b", 12)])
@pytest.mark.parametrize("pos_shape", [(16,), ()], ids=["per_row", "scalar"])
def test_decode_step_updates_the_cache_in_place(one_chip, arch, n_layers,
                                                pos_shape):
    """At the served size (16 rows x 1024 positions; qwen2.5-14b as a
    12-layer pipeline stage) the decode step writes each new entry into
    the donated cache: no copy of the whole stacked cache, no rebuilt
    stack written back into it, and no temporary of its size."""
    cfg = ARCHS[arch].replace(n_layers=n_layers)
    batch, context = 16, 1024
    params = jax.tree.map(
        lambda a: _sds(a.shape, a.dtype, one_chip), abstract_params(cfg))
    shape = (cfg.n_layers, batch, context, cfg.n_kv_heads, cfg.hd)
    cache = {"k": _sds(shape, cfg.dtype, one_chip),
             "v": _sds(shape, cfg.dtype, one_chip)}
    step = jax.jit(build_model(cfg).decode_step, donate_argnums=(1,))
    compiled = step.lower(params, cache,
                          _sds((batch, 1), "int32", one_chip),
                          _sds(pos_shape, "int32", one_chip)).compile()
    txt = compiled.as_text()
    assert ops_of_shape(txt, ("copy", "dynamic-update-slice"), shape) == []
    assert len(cache_aliases(txt, params)) == 2
    cache_bytes = jnp.dtype(cfg.dtype).itemsize * int(np.prod(shape))
    assert compiled.memory_analysis().temp_size_in_bytes < cache_bytes
