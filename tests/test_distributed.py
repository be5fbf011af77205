"""Distribution layer: sharding rules (host-side) + multi-device subprocess
tests (8 fake devices; the main pytest process keeps 1 device)."""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

SRC = str(Path(__file__).resolve().parents[1] / "src")


def run_on_fake_devices(code: str, n: int = 8, timeout: int = 420) -> str:
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={n}",
               PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         env=env, capture_output=True, text=True,
                         timeout=timeout)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


# ---------------------------------------------------------------------------
# host-side rule resolution (no devices needed)
# ---------------------------------------------------------------------------
def test_resolve_spec_divisibility_fallback():
    import jax
    from jax.sharding import PartitionSpec as P

    from repro.distributed.sharding import resolve_spec
    from repro.launch.mesh import make_mesh

    # 1 real device is fine: mesh shape (1,1) won't exercise divisibility;
    # use abstract mesh math via a fake mesh of size 1 but rule table sizes
    # come from mesh.shape -> use subprocess for real 16x16; here just the
    # degenerate no-op case
    mesh = make_mesh((1,), ("model",))
    spec = resolve_spec(mesh, (8, 16), ("kv_heads", None),
                        {"kv_heads": "model"})
    assert spec == P("model", None) or spec == P(None, None)


def test_rules_tables_cover_all_arch_params():
    """Every parameter leaf of every arch resolves to a spec (subprocess
    with a 16x16-like mesh via 8 devices 4x2)."""
    out = run_on_fake_devices("""
        import jax
        from repro.configs import ARCHS
        from repro.distributed.rules import make_rules, tree_specs
        from repro.launch.mesh import make_mesh
        from repro.models.model import abstract_params

        mesh = make_mesh((4, 2), ("data", "model"))
        rules = make_rules(mesh)
        for name, cfg in ARCHS.items():
            params = abstract_params(cfg.reduced())
            specs = tree_specs(mesh, rules, params)
            assert jax.tree.structure(specs) == jax.tree.structure(params)
        print("OK")
    """)
    assert "OK" in out


def test_dp_tp_loss_matches_single_device():
    """The sharded train loss equals the unsharded loss bit-for-bit-ish."""
    out = run_on_fake_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding
        from repro.configs import ARCHS
        from repro.distributed.rules import (batch_specs_tree, make_rules,
                                             tree_specs)
        from repro.distributed.sharding import sharding_rules
        from repro.launch.mesh import make_mesh
        from repro.models.model import build_model

        cfg = ARCHS["qwen2.5-14b"].reduced().replace(
            dtype="float32", n_heads=4, n_kv_heads=2, head_dim=32)
        model = build_model(cfg)
        params = model.init(jax.random.key(0))
        batch = {"tokens": jax.random.randint(jax.random.key(1), (4, 64),
                                              0, cfg.vocab),
                 "labels": jax.random.randint(jax.random.key(2), (4, 64),
                                              0, cfg.vocab)}
        ref, _ = model.loss(params, batch)          # single device

        mesh = make_mesh((4, 2), ("data", "model"))
        rules = make_rules(mesh)
        with sharding_rules(mesh, rules):
            ns = lambda s: NamedSharding(mesh, s)
            p_sh = jax.tree.map(ns, tree_specs(mesh, rules, params))
            b_sh = jax.tree.map(ns, batch_specs_tree(mesh, rules, batch))
            f = jax.jit(lambda p, b: model.loss(p, b)[0],
                        in_shardings=(p_sh, b_sh))
            sharded = f(params, batch)
        np.testing.assert_allclose(float(ref), float(sharded), rtol=2e-5)
        print("LOSS", float(ref), float(sharded))
    """)
    assert "LOSS" in out


def test_pipeline_parallel_selftest():
    out = run_on_fake_devices(
        "import repro.distributed.pipeline_parallel as pp; pp._selftest()")
    assert "selftest OK" in out


def test_multipod_mesh_axes():
    out = run_on_fake_devices("""
        from repro.launch.mesh import make_mesh
        m = make_mesh((2, 2, 2), ("pod", "data", "model"))
        assert m.axis_names == ("pod", "data", "model")
        print("MESH OK")
    """)
    assert "MESH OK" in out
