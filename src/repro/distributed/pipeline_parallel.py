"""GPipe-style pipeline parallelism over a 'stage' mesh axis.

Microbatches stream through stages connected by ``jax.lax.ppermute`` inside a
``shard_map``; the schedule is the classic (n_micro + n_stages - 1)-tick
pipeline with bubble fraction (S-1)/(M+S-1).

Not part of the 2-axis ("data", "model") mesh the logical rules target —
provided and tested (fake 8-device mesh, ``--selftest``) for deployments that
add a 'stage' axis.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
try:  # jax >= 0.5 exports shard_map at top level
    from jax import shard_map
except ImportError:
    from jax.experimental.shard_map import shard_map


def pipeline_apply(mesh: Mesh, stage_fn, stage_params, x,
                   *, axis: str = "stage"):
    """Run ``y = stage_{S-1}(...stage_0(x))`` as a microbatch pipeline.

    stage_params: pytree with leading dim S (one slice per stage), sharded
    over ``axis``.  x: (M, mb, ...) microbatches (M total), replicated.
    Returns y with the same shape as x.
    """
    n_stages = mesh.shape[axis]

    def pp(params_local, xs):  # params: (1, ...) slice; xs: (M, mb, ...)
        params_local = jax.tree.map(lambda a: a[0], params_local)
        stage = jax.lax.axis_index(axis)
        m = xs.shape[0]
        ticks = m + n_stages - 1
        perm = [(i, i + 1) for i in range(n_stages - 1)]

        def tick(carry, t):
            buf, out = carry  # buf: (mb, ...) activation entering this stage
            inject = jnp.where(t < m, xs[jnp.minimum(t, m - 1)], xs[0])
            inp = jnp.where(stage == 0, inject, buf)
            y = stage_fn(params_local, inp)
            # harvest finished microbatch at the last stage
            done_idx = t - (n_stages - 1)
            out = jax.lax.cond(
                jnp.logical_and(stage == n_stages - 1, done_idx >= 0),
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, y, jnp.maximum(done_idx, 0), 0),
                lambda o: o, out)
            nxt = jax.lax.ppermute(y, axis, perm)
            return (nxt, out), None

        buf0 = jnp.zeros_like(xs[0])
        out0 = jnp.zeros_like(xs)
        (_, out), _ = jax.lax.scan(tick, (buf0, out0), jnp.arange(ticks))
        # out lives on the last stage; broadcast so every shard returns it
        out = jax.lax.psum(
            jnp.where(stage == n_stages - 1, out, jnp.zeros_like(out)), axis)
        return out

    pspec = jax.tree.map(lambda _: P(axis), stage_params)
    try:
        wrapped = shard_map(pp, mesh=mesh, in_specs=(pspec, P()),
                            out_specs=P(), check_vma=False)
    except TypeError:  # jax 0.4.x spells the flag check_rep
        wrapped = shard_map(pp, mesh=mesh, in_specs=(pspec, P()),
                            out_specs=P(), check_rep=False)
    return wrapped(stage_params, x)


def _selftest() -> None:
    import os

    assert os.environ.get("XLA_FLAGS", "").find("device_count") >= 0, \
        "run with XLA_FLAGS=--xla_force_host_platform_device_count=8"
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((4,), ("stage",))
    key = jax.random.key(0)
    d = 16
    w = jax.random.normal(key, (4, d, d)) * 0.3  # one matrix per stage

    def stage_fn(wi, x):
        return jnp.tanh(x @ wi)

    x = jax.random.normal(jax.random.key(1), (8, 4, d))  # 8 microbatches
    y = pipeline_apply(mesh, stage_fn, w, x)
    # sequential reference
    ref = x
    for i in range(4):
        ref = jnp.tanh(ref @ w[i])
    import numpy as np

    np.testing.assert_allclose(np.asarray(y), np.asarray(ref), rtol=2e-5,
                               atol=2e-5)
    print("pipeline_parallel selftest OK")


if __name__ == "__main__":
    _selftest()
