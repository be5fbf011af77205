"""Pallas TPU kernels for the framework's compute hot-spots.

Each kernel ships as <name>.py (pl.pallas_call + explicit BlockSpec VMEM
tiling), with ops.py as the jit'd differentiable wrapper and ref.py as the
pure-jnp oracle used by tests/test_kernels.py allclose sweeps.

Each kernel compiles on a TPU backend and runs interpreted on any other
(``repro.runtime.kernel_interpret``); ``interpret=`` overrides that.
"""
from repro.kernels.ops import decode_attention, flash_attention, ssd_scan

__all__ = ["flash_attention", "decode_attention", "ssd_scan"]
