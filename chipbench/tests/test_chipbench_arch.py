"""Architecture modules: the dense module reproduces the readings of the
code it was moved out of, and a new architecture comes in through new
files alone."""
import hashlib
import json
import shutil
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import arch, flops, harness, peaks, reference
from chipbench import spec as S
from chipbench import weights as W
from chipbench.tests.conftest import tiny_cell

SEED = 2**33 + 17

# Readings of the benchmark before its dense-decoder code moved into
# chipbench/arch/dense.py, on the CPU: sha256 of every weight (by path, with
# dtype and shape) and of the reference logits, both at SEED.
PARAMS_SHA256 = {
    "tied_spec":
        "e0d70c15c9654df9c4b0d7ae714493bc763cedb4258ba0c650a40af954c2895b",
    "biased_spec":
        "ec6f02aaf535e01fe8913b126eac95cbec07f78af7c96d3ff9adf8d193ea4f5e",
}
LOGITS_SHA256 = {
    "tied_spec":
        "6bbf05094d65fa2fd526258b276aba5a4d138597651040759b90fb6e6e8521f2",
    "biased_spec":
        "de82062d8e2568e45973063529c8c0845fbe59ee2f8b14ae084ce11766c64a88",
}
# useful operations of a whole request, full size, (prompt, answer) tokens
REQUEST_FLOPS = {
    "phi4-mini-3.8b": {(16, 58): 542654201856, (128, 58): 1269911912448,
                       (128, 900): 7930241089536},
    "qwen2.5-14b-pp4": {(16, 58): 573217751040, (128, 58): 1316657479680,
                        (128, 900): 8315544698880},
}


def _params_sha256(tree) -> str:
    h = hashlib.sha256()
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    for path, leaf in sorted(flat,
                             key=lambda pl: jax.tree_util.keystr(pl[0])):
        a = np.asarray(leaf)
        h.update(jax.tree_util.keystr(path).encode())
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("fixture", sorted(PARAMS_SHA256))
def test_weights_are_the_pinned_ones(fixture, request):
    spec = request.getfixturevalue(fixture)
    assert _params_sha256(W.make_params(spec, SEED)) == PARAMS_SHA256[fixture]


# XLA on a CPU of one core sums the biased spec's logits in another order
@pytest.mark.parametrize("fixture", sorted(LOGITS_SHA256))
def test_reference_logits_are_the_pinned_ones(fixture, request):
    spec = request.getfixturevalue(fixture)
    toks = np.random.default_rng(4).integers(0, spec.vocab, (2, 11))
    out = reference.logits(spec, SEED, toks.astype(np.int32))
    assert out.shape == (2, 11, spec.vocab) and out.dtype == np.float32
    assert hashlib.sha256(out.tobytes()).hexdigest() == \
        LOGITS_SHA256[fixture]


@pytest.mark.parametrize("config,sizes", [
    (c, s) for c, by in sorted(REQUEST_FLOPS.items()) for s in sorted(by)])
def test_request_flops_are_the_pinned_ones(config, sizes):
    spec = S.model_spec(S.load_json(S.BENCH_DIR / "configs"
                                    / f"{config}.json"))
    assert flops.request_flops(spec, *sizes) == REQUEST_FLOPS[config][sizes]


@pytest.mark.parametrize("fixture", ["tied_spec", "biased_spec"])
def test_warm_up_slices_a_cache_of_the_engines_shape(fixture, request):
    """The page-out slices that set-up compiles are of the cache the engine
    keeps."""
    spec = request.getfixturevalue(fixture)
    engine = harness.build_engine(spec, SEED)
    try:
        for leaf in jax.tree.leaves(engine._alloc_cache()):
            assert leaf.shape == arch.of(spec).cache_shape(spec)
            assert leaf.dtype == jnp.dtype(spec.dtype)
    finally:
        engine.close()


def _bench_in(tmp_path, architecture: str | None) -> dict:
    """A benchmark of one cell under ``tmp_path``: phi4-chat's configuration
    with ``architecture`` in its key (left out for None), its traffic and
    its cell file, and a copy of every architecture module."""
    shutil.copytree(S.BENCH_DIR / "arch", tmp_path / "arch",
                    ignore=shutil.ignore_patterns("__pycache__"))
    for sub in ("traffic", "cells"):
        shutil.copytree(S.BENCH_DIR / sub, tmp_path / sub)
    conf = S.load_json(S.BENCH_DIR / "configs" / "phi4-mini-3.8b.json")
    conf.pop("architecture")
    if architecture is not None:
        conf["architecture"] = architecture
    (tmp_path / "configs").mkdir()
    (tmp_path / "configs" / "model.json").write_text(json.dumps(conf))
    bench = S.load_benchmark()
    return dict(bench,
                configs=[dict(bench["configs"][0],
                              file=str(tmp_path / "configs" / "model.json"))],
                workloads=[w for w in bench["workloads"]
                           if w["name"] == "phi4-chat"])


def test_a_new_architecture_runs_from_new_files_alone(tmp_path):
    """A copy of the dense module under a new name, and a configuration
    that names it, resolve to a cell that runs end to end and is correct."""
    bench = _bench_in(tmp_path, "dense_copy")
    shutil.copy(S.BENCH_DIR / "arch" / "dense.py",
                tmp_path / "arch" / "dense_copy.py")
    cell = tiny_cell("phi4-chat", bench=bench, base=tmp_path)
    assert cell.arch.__file__ == str(tmp_path / "arch" / "dense_copy.py")
    assert arch.of(cell.spec) is cell.arch
    res = harness.run_cell(cell, SEED, 2.0, False, time.perf_counter(),
                           {"platform": "cpu", "kind": "cpu", "count": 1},
                           peaks.PEAKS["TPU v5 lite"])
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0


@pytest.mark.parametrize("architecture", [None, "moe_latent"])
def test_a_missing_or_unknown_architecture_names_the_modules(tmp_path,
                                                             architecture):
    bench = _bench_in(tmp_path, architecture)
    with pytest.raises(ValueError, match=r"one of \[.dense.\]"):
        S.resolve("phi4-chat", bench, tmp_path)


TWO_STACKS = '''
from dataclasses import dataclass

import jax.numpy as jnp


@dataclass(frozen=True)
class Spec:
    name: str
    d_model: int


def model_spec(conf):
    return Spec(name=conf["name"], d_model=conf["model"]["hidden_size"])


def stacks(spec):
    return {"first": 1, "rest": 3}


def leaves(spec):
    d, f32 = spec.d_model, jnp.float32
    return {"tok/emb": ((10, d), f32, 1.0, None),
            "first/w": ((d, d), f32, 1.0, "first"),
            "first/ln": ((d,), f32, 0.1, "first"),
            "rest/w": ((d, d), f32, 1.0, "rest"),
            "rest/mlp/w": ((d, 2 * d), jnp.bfloat16, 1.0, "rest")}
'''


def test_stacks_of_two_depths_remake_layer_by_layer(tmp_path):
    (tmp_path / "two_stacks.py").write_text(TWO_STACKS)
    mod = arch.load("two_stacks", tmp_path)
    spec = mod.model_spec({"name": "two", "model": {"hidden_size": 8}})
    params = W.make_params(spec, SEED)
    assert params["first"]["w"].shape == (1, 8, 8)
    assert params["rest"]["mlp"]["w"].shape == (3, 8, 16)
    words = W.seed_words(SEED)
    for stack, depth in mod.stacks(spec).items():
        for layer in range(depth):
            one = W.layer_params(words, jnp.int32(layer), spec, stack)
            want = jax.tree.map(lambda a: a[layer], params[stack])
            assert jax.tree.structure(one) == jax.tree.structure(want)
            for got, exp in zip(jax.tree.leaves(one), jax.tree.leaves(want)):
                np.testing.assert_array_equal(np.asarray(got),
                                              np.asarray(exp))
    # each leaf draws from its own path's key: the stacks' layer 0 differ
    assert not np.array_equal(np.asarray(params["first"]["w"][0]),
                              np.asarray(params["rest"]["w"][0]))
    with pytest.raises(ValueError, match="name one"):
        W.layer_params(words, jnp.int32(0), spec)
