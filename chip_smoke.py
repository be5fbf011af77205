"""Smoke run of the serving path on one TPU chip.

    python chip_smoke.py

Serves phi4-mini-3.8b at full width (bf16 weights from the model's seeded
init) through ``repro.serve.engine.ServeEngine``: once as a batch
(``generate``) and once through a ProxyStream on the shared-memory data
plane (``serve_stream``).  Then runs each Pallas kernel, compiled as the
platform chooses on a TPU, against its pure-jnp reference in
``repro.kernels.ref``.

One process, no children.  Exits non-zero when JAX finds no TPU or any
check fails; otherwise the last line of standard output is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
The seconds, compile time and peak memory it prints on the way are set-up
figures of this run, not benchmark metrics.
"""
from __future__ import annotations

import json
import sys
import tempfile
import threading
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.runtime import (enable_compile_cache,  # noqa: E402
                           kernel_interpret)

ARCH = "phi4-mini-3.8b"
MAX_BATCH = 8
MAX_CONTEXT = 1024
NEW_TOKENS = 32
PROMPT_LENS = (128,) * 4 + (512,) * 4
SEED = 0
# kernel shapes: phi4-mini attention widths, and mamba2-2.7b's SSD widths
FLASH = dict(b=1, s=2048, h=24, kv=8, hd=128)
DECODE = dict(b=8, s=2048, h=24, kv=8, hd=128, length=1500)
SSD_ARCH, SSD_LEN = "mamba2-2.7b", 1024
BF16_TOL = dict(rtol=2e-2, atol=2e-2)      # tests/test_kernels.py, bf16
# bf16 rounding moves each logit by ~1e-2 relative at worst, which keeps
# the cosine of two logit vectors above 0.999; 0.99 leaves room for the
# different summation orders of cached decode and full prefill
MIN_LOGITS_COSINE = 0.99

_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)
    print(f"  ok: {what}", flush=True)


def log(msg: str) -> None:
    print(msg, flush=True)


def make_requests(vocab: int):
    """Greedy requests in two length groups; request 1 repeats request 0's
    prompt (same length group)."""
    from repro.serve.engine import Request

    rng = np.random.default_rng(SEED)
    prompts = [list(map(int, rng.integers(1, vocab, size=n)))
               for n in PROMPT_LENS]
    prompts[1] = list(prompts[0])
    return [Request(prompt=p, max_new_tokens=NEW_TOKENS, req_id=f"req-{i}")
            for i, p in enumerate(prompts)]


def check_outputs(outputs: list[list[int]], vocab: int) -> None:
    check(all(len(o) == NEW_TOKENS for o in outputs),
          f"every request produced {NEW_TOKENS} tokens")
    check(all(0 <= t < vocab for o in outputs for t in o),
          f"every token is in [0, {vocab})")


def phase_batch(engine, reqs) -> list[list[int]]:
    cfg = engine.cfg
    t0 = time.perf_counter()
    out = engine.generate(reqs)
    log(f"batch: generate took {time.perf_counter() - t0:.3f} s "
        f"({out['decode_steps']} decode steps)")
    outputs = out["outputs"]
    check_outputs(outputs, cfg.vocab)
    check(outputs[0] == outputs[1], "identical prompts gave identical tokens")
    check(engine.kv_pool().stats()["n_blocks"] == 0,
          "KV pool released every block")

    # reference for the first two tokens of the 128-token group: one
    # prefill and one decode step through the engine's own programs, with
    # no scheduler in between (random weights may repeat one token, so the
    # logits comparison below is the stronger check)
    group = [i for i, n in enumerate(PROMPT_LENS) if n == PROMPT_LENS[0]]
    toks = np.zeros((MAX_BATCH, PROMPT_LENS[0]), np.int32)
    for row, i in enumerate(group):
        toks[row] = reqs[i].prompt
    logits, kv = engine._prefill(engine.params, {"tokens": jnp.asarray(toks)})
    logits = np.asarray(logits)
    check(bool(np.isfinite(logits).all()), "prefill logits are finite")
    first = logits[:, :cfg.vocab].argmax(-1).astype(np.int32)
    check(all(int(first[row]) == outputs[i][0] for row, i in enumerate(group)),
          "first tokens match the argmax of a separate prefill")
    mask = np.zeros(MAX_BATCH, bool)
    mask[:len(group)] = True
    cache = engine._scatter(engine._alloc_cache(), kv["k"], kv["v"],
                            jnp.arange(MAX_BATCH, dtype=jnp.int32),
                            jnp.asarray(mask))
    lengths = np.where(mask, PROMPT_LENS[0], 0).astype(np.int32)
    logits, _ = engine._decode(engine.params, cache,
                               jnp.asarray(np.where(mask, first, 0)[:, None]),
                               jnp.asarray(lengths))
    logits = np.asarray(logits)
    check(bool(np.isfinite(logits).all()), "decode logits are finite")
    second = logits[:, :cfg.vocab].argmax(-1)
    check(all(int(second[row]) == outputs[i][1]
              for row, i in enumerate(group)),
          "second tokens match the argmax of a separate decode step")

    # the cached decode step against a full prefill of prompt + first token:
    # two programs that share no KV cache must give the same next-token
    # logits up to bf16 rounding
    full, _ = engine._prefill(engine.params, {"tokens": jnp.asarray(
        np.concatenate([toks, first[:, None]], axis=1))})
    full = np.asarray(full)[:len(group), :cfg.vocab].astype(np.float64)
    step = logits[:len(group), :cfg.vocab].astype(np.float64)
    cos = (full * step).sum(-1) / (np.linalg.norm(full, axis=-1)
                                   * np.linalg.norm(step, axis=-1))
    log(f"decode vs full prefill: logits cosine {cos.tolist()}, max "
        f"|diff| {float(np.abs(full - step).max())}")
    check(bool((cos > MIN_LOGITS_COSINE).all()),
          f"decode-step logits match a full prefill (cosine > "
          f"{MIN_LOGITS_COSINE})")
    return outputs


def phase_stream(engine, reqs, batch_outputs) -> None:
    from repro.core import Store
    from repro.core.connectors import SharedMemoryConnector
    from repro.core.proxy import extract, is_proxy

    with tempfile.TemporaryDirectory(prefix="chip-smoke-") as reg:
        store = Store("chip-smoke-stream", SharedMemoryConnector(reg))
        try:
            def feed() -> None:
                prod = store.stream_producer("requests")
                for r in reqs:
                    prod.append(store.proxy(
                        {"prompt": r.prompt, "max_new_tokens": NEW_TOKENS,
                         "temperature": 0.0, "req_id": r.req_id},
                        evict=True))
                prod.close()

            feeder = threading.Thread(target=feed)
            feeder.start()
            t0 = time.perf_counter()
            try:
                stats = engine.serve_stream(store, "requests", "results",
                                            data_store=store, timeout=120.0)
            finally:
                feeder.join()
            log(f"stream: serve_stream took {time.perf_counter() - t0:.3f} s "
                f"({stats['decode_steps']} decode steps)")
            results = []
            with store.stream_consumer("results", timeout=30.0) as stream:
                for item in stream:
                    results.append(extract(item) if is_proxy(item) else item)
        finally:
            store.close()

    ids = sorted(r["req_id"] for r in results)
    check(ids == sorted(r.req_id for r in reqs),
          "each req_id came back exactly once")
    by_id = {r["req_id"]: r["tokens"] for r in results}
    outputs = [by_id[r.req_id] for r in reqs]
    check_outputs(outputs, engine.cfg.vocab)
    check(outputs == batch_outputs, "stream tokens equal batch tokens")
    pool = engine.kv_pool().stats()
    check(pool["n_blocks"] == 0 and pool["bytes_in_use"] == 0,
          "KV pool released every block")


def check_kernel(name: str, out, ref) -> None:
    out = np.asarray(out, np.float32)
    ref = np.asarray(ref, np.float32)
    err = float(np.max(np.abs(out - ref)))
    log(f"kernel {name}: shape {out.shape}, max |kernel - ref| = {err}")
    check(bool(np.isfinite(out).all()) and np.allclose(out, ref, **BF16_TOL),
          f"{name} matches kernels/ref.py within bf16 tolerance")


def _ref(fn, *args, **kw):
    """A reference at full f32 matmul precision.  Only the reference: the
    Mosaic lowering refuses an fp32 contract precision on bf16 operands."""
    with jax.default_matmul_precision("highest"):
        return fn(*args, **kw)


def phase_kernels() -> None:
    from repro.configs import ARCHS
    from repro.kernels.decode_attention import decode_attention
    from repro.kernels.flash_attention import flash_attention
    from repro.kernels.ref import (decode_attention_ref, flash_attention_ref,
                                   ssd_scan_ref)
    from repro.kernels.ssd_scan import ssd_scan

    check(not kernel_interpret(None), "Pallas kernels compile on this backend")
    ks = jax.random.split(jax.random.key(SEED), 8)
    bf = jnp.bfloat16

    f = FLASH
    q = jax.random.normal(ks[0], (f["b"], f["s"], f["h"], f["hd"]), bf)
    k = jax.random.normal(ks[1], (f["b"], f["s"], f["kv"], f["hd"]), bf)
    v = jax.random.normal(ks[2], (f["b"], f["s"], f["kv"], f["hd"]), bf)
    check_kernel("flash_attention", flash_attention(q, k, v, causal=True),
           _ref(flash_attention_ref, q, k, v, causal=True))

    d = DECODE
    q = jax.random.normal(ks[3], (d["b"], 1, d["h"], d["hd"]), bf)
    kc = jax.random.normal(ks[4], (d["b"], d["s"], d["kv"], d["hd"]), bf)
    vc = jax.random.normal(ks[5], (d["b"], d["s"], d["kv"], d["hd"]), bf)
    check_kernel("decode_attention", decode_attention(q, kc, vc, d["length"]),
           _ref(decode_attention_ref, q, kc, vc, d["length"]))

    m = ARCHS[SSD_ARCH]
    h, p, n = m.n_ssm_heads, m.ssm_head_dim, m.ssm_state
    kx = jax.random.split(ks[6], 5)
    x = jax.random.normal(kx[0], (1, SSD_LEN, h, p), bf)
    dt = jax.nn.softplus(jax.random.normal(kx[1], (1, SSD_LEN, h)))
    a_log = jax.random.normal(kx[2], (h,)) * 0.5
    b = (jax.random.normal(kx[3], (1, SSD_LEN, n)) * 0.3).astype(bf)
    c = (jax.random.normal(kx[4], (1, SSD_LEN, n)) * 0.3).astype(bf)
    check_kernel("ssd_scan", ssd_scan(x, dt, a_log, b, c, chunk=m.ssm_chunk),
           _ref(ssd_scan_ref, x, dt, a_log, b, c))


def main() -> int:
    cache_dir = enable_compile_cache()
    compile_s = [0.0]

    def on_duration(event: str, duration: float, **_) -> None:
        if event in _COMPILE_EVENTS:
            compile_s[0] += duration

    jax.monitoring.register_event_duration_secs_listener(on_duration)

    devices = jax.devices()
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    log(f"device: {json.dumps(device)}")
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform}",
              file=sys.stderr)
        return 1
    log(f"compile cache: {cache_dir}")

    from repro.launch.train import build_cfg
    from repro.serve.engine import ServeEngine

    t0 = time.perf_counter()
    cfg = build_cfg(ARCH, "full")
    engine = ServeEngine(cfg, max_batch=MAX_BATCH, max_context=MAX_CONTEXT)
    try:
        jax.block_until_ready(engine.params)
        n_bytes = sum(a.nbytes for a in jax.tree.leaves(engine.params))
        log(f"engine: {cfg.name} at full width, {n_bytes} parameter bytes, "
            f"built in {time.perf_counter() - t0:.3f} s")
        reqs = make_requests(cfg.vocab)
        batch_outputs = phase_batch(engine, reqs)
        phase_stream(engine, make_requests(cfg.vocab), batch_outputs)
    finally:
        engine.close()
    del engine
    phase_kernels()

    stats = dev.memory_stats() or {}
    log(f"set-up figure, not a benchmark metric: peak_bytes_in_use "
        f"{stats.get('peak_bytes_in_use')}")
    log(f"set-up figure, not a benchmark metric: compile seconds "
        f"(trace + lower + backend compile) {compile_s[0]:.3f}")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
