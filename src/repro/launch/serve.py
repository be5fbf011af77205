"""Serving driver: batch mode, stream mode, and multi-worker weight sharing.

    # batch: generate for N synthetic requests (continuous batching when the
    # family supports it)
    PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-14b \
        --preset small --requests 8 --new-tokens 32 [--ckpt-dir DIR]

    # stream: feed the engine through a ProxyStream (requests -> proxies ->
    # engine; completions -> evict=True proxies -> result stream)
    PYTHONPATH=src python -m repro.launch.serve --stream [--workers N]

``--workers N`` additionally spawns N worker processes that each construct
an engine from a ``borrow()`` of the parent's published weight proxy —
on the shm data plane all N resolve zero-copy views of ONE arena mapping
(no per-worker deep copy of the parameters).  It runs on a CPU backend
only: a TPU chip serves one process at a time and the parent holds it, so
on a TPU backend ``--workers`` fails before anything is spawned.

    # full width on one TPU chip (phi4-mini-3.8b fits whole in 16 GB)
    PYTHONPATH=src python -m repro.launch.serve --arch phi4-mini-3.8b \
        --preset full --requests 8 --prompt-len 128 --new-tokens 32
"""
from __future__ import annotations

import argparse
import json
import multiprocessing as mp
import tempfile
import threading

import numpy as np

from repro.configs import ARCHS
from repro.launch.train import build_cfg
from repro.runtime import enable_compile_cache, refuse_child_workers_on_tpu
from repro.serve.engine import Request, ServeEngine


def _build_ckpts(ckpt_dir: str):
    from repro.core import Store
    from repro.core.connectors import FileConnector
    from repro.train.checkpoints import ProxyCheckpointManager

    store = Store("serve-ckpts", FileConnector(ckpt_dir + "/data"))
    return ProxyCheckpointManager(store, ckpt_dir + "/ckpts")


def _worker_main(arch: str, preset: str, borrowed, conn) -> None:
    """A serving worker: builds its engine from a borrowed weight proxy
    (zero-copy views of the publisher's arena slot) and reports how many
    parameter bytes it mapped without copying."""
    cfg = build_cfg(arch, preset)
    engine = ServeEngine(cfg, weights=borrowed, max_batch=2)
    rng = np.random.default_rng(7)
    out = engine.generate([Request(prompt=list(rng.integers(
        1, cfg.vocab, size=8)), max_new_tokens=4)])
    conn.send({"tokens": out["outputs"][0]})
    conn.close()


def _run_workers(args, engine: ServeEngine) -> None:
    from repro.core import Store, borrow
    from repro.core.connectors import SharedMemoryConnector

    reg = tempfile.mkdtemp(prefix="serve-weights-")
    wstore = Store("serve-weights", SharedMemoryConnector(reg))
    owned = engine.publish_weights(wstore, ttl=300.0)
    ctx = mp.get_context("spawn")
    procs, pipes = [], []
    for _ in range(args.workers):
        parent, child = ctx.Pipe()
        p = ctx.Process(target=_worker_main,
                        args=(args.arch, args.preset, borrow(owned), child))
        p.start()
        procs.append(p)
        pipes.append(parent)
    results = [c.recv() for c in pipes]
    for p in procs:
        p.join()
    agree = len({tuple(r["tokens"]) for r in results}) == 1
    print(json.dumps({"workers": args.workers,
                      "outputs_agree": agree,
                      "sample": results[0]["tokens"]}))
    wstore.close()


def _run_stream(args, engine: ServeEngine) -> None:
    from repro.core import Store
    from repro.core.connectors import SharedMemoryConnector

    reg = tempfile.mkdtemp(prefix="serve-stream-")
    store = Store("serve-stream", SharedMemoryConnector(reg))
    rng = np.random.default_rng(0)

    def feed() -> None:
        prod = store.stream_producer("requests")
        for i in range(args.requests):
            prod.append(store.proxy({
                "prompt": list(map(int, rng.integers(
                    1, engine.cfg.vocab, size=args.prompt_len))),
                "max_new_tokens": args.new_tokens,
                "temperature": args.temperature,
                "req_id": f"req-{i}",
            }, evict=True))
        prod.close()

    t = threading.Thread(target=feed)
    t.start()
    stats = engine.serve_stream(store, "requests", "results",
                                data_store=store, timeout=60.0)
    t.join()
    from repro.core.proxy import extract, is_proxy

    results = []
    with store.stream_consumer("results", timeout=10.0) as stream:
        for item in stream:
            results.append(extract(item) if is_proxy(item) else item)
    print(json.dumps({
        "mode": "stream", "served": stats["completed"],
        "decode_steps": stats["decode_steps"],
        "p50_total_s": round(float(np.median(
            [r["total_s"] for r in results])), 4) if results else None,
    }, indent=1))
    store.close()


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-14b", choices=sorted(ARCHS))
    ap.add_argument("--preset", default="small")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--ckpt-dir", default=None,
                    help="restore weights from a proxy-checkpoint directory")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--stream", action="store_true",
                    help="feed requests through a ProxyStream instead of a "
                         "static list")
    ap.add_argument("--workers", type=int, default=0,
                    help="spawn N workers sharing the weights zero-copy "
                         "via a borrowed arena proxy")
    ap.add_argument("--max-context", type=int, default=None)
    args = ap.parse_args()
    if args.workers:
        refuse_child_workers_on_tpu("--workers")
    enable_compile_cache()

    cfg = build_cfg(args.arch, args.preset)
    ckpts = _build_ckpts(args.ckpt_dir) if args.ckpt_dir else None
    max_ctx = args.max_context or (args.prompt_len + args.new_tokens + 8)
    engine = ServeEngine(cfg, ckpts=ckpts, max_batch=args.requests,
                         max_context=max_ctx)

    if args.workers:
        _run_workers(args, engine)
    if args.stream:
        _run_stream(args, engine)
        engine.close()
        return
    if args.workers:
        engine.close()
        return

    rng = np.random.default_rng(0)
    reqs = [Request(prompt=list(rng.integers(1, cfg.vocab,
                                             size=args.prompt_len)),
                    max_new_tokens=args.new_tokens,
                    temperature=args.temperature)
            for _ in range(args.requests)]
    out = engine.generate(reqs)
    print(json.dumps({
        "arch": cfg.name, "requests": len(reqs),
        "prefill_s": round(out["prefill_s"], 3),
        "decode_s": round(out["decode_s"], 3),
        "tokens_per_s": round(out["tokens_per_s"], 1),
        "sample_output": out["outputs"][0][:16],
    }, indent=1))
    engine.close()


if __name__ == "__main__":
    main()
