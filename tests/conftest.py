import os
import sys

# the tests run on the CPU: with libtpu installed, JAX would otherwise take
# the TPU (which belongs to one process at a time)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
# NOTE: no XLA_FLAGS here by design — smoke tests and benches must see ONE
# device; multi-device tests force fake devices in a subprocess.
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
# repo root, so the benchmark smoke tests can import the benchmarks package
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import pytest


@pytest.fixture(autouse=True)
def _clean_store_registry():
    """Each test gets a fresh global Store registry + lifecycle tables."""
    yield
    from repro.core import connector as conn_mod
    from repro.core import store as store_mod

    with store_mod._REGISTRY_LOCK:
        store_mod._REGISTRY.clear()
    with conn_mod._LIFETIME_LOCK:
        conn_mod._LIFETIME_TABLES.clear()
