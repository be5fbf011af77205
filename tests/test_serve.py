"""Serving-engine tests: continuous batching correctness, per-request
sampling, proxy weight/KV/stream planes, and multi-process zero-copy
weight sharing (N workers -> ONE arena mapping)."""
import multiprocessing as mp
import threading
import time
import uuid

import numpy as np
import pytest

from repro.configs import ARCHS
from repro.core import Store, borrow
from repro.core.connectors import FileConnector, SharedMemoryConnector
from repro.core.proxy import extract, get_factory, is_proxy
from repro.core.store import unregister_store
from repro.models.serve_paths import KVBlockPool, KVPoolExhausted
from repro.serve.engine import (_EXHAUSTED, Request, ServeEngine,
                                _StreamSource, metrics_tap)
from repro.train.checkpoints import ProxyCheckpointManager

CFG = ARCHS["qwen2.5-14b"].reduced().replace(dtype="float32", n_layers=2)


@pytest.fixture(scope="module")
def engine():
    eng = ServeEngine(CFG, max_batch=4, max_context=64, block_tokens=8)
    assert eng._continuous
    yield eng
    eng.close()


@pytest.fixture
def shm_store(tmp_path):
    name = f"serve-test-{uuid.uuid4().hex[:8]}"
    store = Store(name, SharedMemoryConnector(str(tmp_path / "shm")))
    yield store
    store.close()
    unregister_store(name)


def _prompt(rng, n):
    return list(map(int, rng.integers(1, CFG.vocab, size=n)))


def _solo(engine, req: Request) -> list[int]:
    """Reference output: the request alone through a lockstep B=1 run."""
    ref = ServeEngine(CFG, params=engine.params, max_batch=1,
                      max_context=engine.max_context)
    ref._continuous = False
    return ref.generate([Request(prompt=req.prompt,
                                 max_new_tokens=req.max_new_tokens)]
                        )["outputs"][0]


# ---------------------------------------------------------------------------
# continuous batching: mixed lengths, per-request temperature
# ---------------------------------------------------------------------------
def test_mixed_length_continuous_matches_solo(engine):
    """Rows with different prompt lengths AND different max_new_tokens,
    batched continuously, must each produce exactly the tokens a solo
    run produces — and stop at their OWN max_new_tokens."""
    rng = np.random.default_rng(11)
    reqs = [Request(prompt=_prompt(rng, p), max_new_tokens=m)
            for p, m in [(5, 6), (9, 3), (7, 9), (12, 2)]]
    out = engine.generate(reqs)
    for req, toks in zip(reqs, out["outputs"]):
        assert len(toks) == req.max_new_tokens
        assert toks == _solo(engine, req)
    # six requests through four rows: slots recycle mid-run
    more = reqs + [Request(prompt=list(reqs[0].prompt), max_new_tokens=4),
                   Request(prompt=list(reqs[2].prompt), max_new_tokens=5)]
    out2 = engine.generate(more)
    assert [len(t) for t in out2["outputs"]] == \
        [r.max_new_tokens for r in more]
    assert out2["outputs"][:4] == out["outputs"]


def test_per_request_temperature(engine):
    """A greedy (temperature=0) row next to a hot row must stay exactly
    deterministic — sampling uses each row's OWN temperature, not
    reqs[0]'s."""
    rng = np.random.default_rng(23)
    prompt = _prompt(rng, 8)
    greedy = Request(prompt=list(prompt), max_new_tokens=6, temperature=0.0)
    hot = Request(prompt=list(prompt), max_new_tokens=6, temperature=1.5)
    ref = _solo(engine, greedy)
    for _ in range(2):   # fresh RNG draws each call; greedy row immune
        out = engine.generate([Request(prompt=list(prompt), max_new_tokens=6,
                                       temperature=0.0),
                               Request(prompt=list(prompt), max_new_tokens=6,
                                       temperature=1.5)])
        assert out["outputs"][0] == ref
        assert len(out["outputs"][1]) == hot.max_new_tokens


# ---------------------------------------------------------------------------
# weight plane: proxy-checkpoint restore feeds the engine
# ---------------------------------------------------------------------------
def test_engine_restores_weights_from_checkpoint_manager(engine, tmp_path):
    import jax

    name = f"serve-ckpt-{uuid.uuid4().hex[:8]}"
    store = Store(name, FileConnector(str(tmp_path / "data")))
    try:
        mgr = ProxyCheckpointManager(store, str(tmp_path / "ckpts"))
        host = jax.tree.map(np.asarray, engine.params)
        mgr.save(1, {"params": host})
        restored = ServeEngine(CFG, ckpts=mgr, max_batch=2, max_context=32)
        rng = np.random.default_rng(3)
        req = Request(prompt=_prompt(rng, 6), max_new_tokens=4)
        assert restored.generate([req])["outputs"][0] == _solo(engine, req)
        restored.close()
    finally:
        store.close()
        unregister_store(name)


# ---------------------------------------------------------------------------
# KV plane: refcounted block lifecycle + lease reclamation
# ---------------------------------------------------------------------------
def test_kv_blocks_released_after_completion(engine, shm_store):
    pool = KVBlockPool(shm_store, CFG, block_tokens=8, lease_ttl=None)
    k = np.ones((CFG.n_layers, 8, CFG.n_kv_heads, CFG.hd), np.float32)
    blocks = pool.put_prefill(k, k)
    assert pool.stats()["n_blocks"] == 1
    assert shm_store.refcount(blocks[0].key) == 1    # the pool's owning ref
    kk, vv = pool.gather(blocks)
    np.testing.assert_array_equal(kk, k)
    pool.release(blocks)                             # refcount -> 0 -> freed
    assert shm_store.refcount(blocks[0].key) == 0
    assert not shm_store.exists(blocks[0].key)
    assert pool.stats() == {**pool.stats(), "n_blocks": 0, "bytes_in_use": 0}

    # end-to-end: a generate() leaves the engine's pool empty
    rng = np.random.default_rng(5)
    engine.generate([Request(prompt=_prompt(rng, 10), max_new_tokens=12)])
    st = engine.kv_pool().stats()
    assert st["n_blocks"] == 0 and st["bytes_in_use"] == 0


def test_crashed_worker_blocks_reclaimed_by_lease(shm_store):
    """Blocks whose owner never calls release() (a crashed worker) are
    reclaimed once their lease expires, and the freed budget admits new
    requests again."""
    per_block = 2 * CFG.n_layers * 8 * CFG.n_kv_heads * CFG.hd * 4
    pool = KVBlockPool(shm_store, CFG, block_tokens=8,
                       budget_bytes=2 * per_block, lease_ttl=0.05)
    k = np.zeros((CFG.n_layers, 8, CFG.n_kv_heads, CFG.hd), np.float32)
    orphans = [pool.put_block(k, k), pool.put_block(k, k)]
    with pytest.raises(KVPoolExhausted):
        pool.put_block(k, k)                 # budget full, leases still live
    time.sleep(0.12)                         # the "worker" died; leases lapse
    assert pool.sweep() >= 1
    assert pool.stats()["bytes_in_use"] == 0
    for blk in orphans:
        assert not shm_store.exists(blk.key)
    fresh = pool.put_block(k, k)             # reclaimed budget is usable
    pool.release([fresh])


def test_starved_pool_defers_admission_and_completes_all(engine):
    """A pool that holds ~2 requests' pages must still complete 5 requests
    (admission defers until completions free blocks) with outputs equal to
    the unconstrained engine's."""
    rng = np.random.default_rng(17)
    reqs = [Request(prompt=_prompt(rng, 8), max_new_tokens=6)
            for _ in range(5)]
    want = engine.generate([Request(prompt=list(r.prompt),
                                    max_new_tokens=r.max_new_tokens)
                            for r in reqs])["outputs"]
    per_tok = 2 * CFG.n_layers * CFG.n_kv_heads * CFG.hd * 4
    tight = ServeEngine(CFG, params=engine.params, max_batch=4,
                        max_context=32, block_tokens=8,
                        kv_budget_bytes=2 * 16 * per_tok)   # ~2 requests
    out = tight.generate([Request(prompt=list(r.prompt),
                                  max_new_tokens=r.max_new_tokens)
                          for r in reqs])["outputs"]
    assert out == want
    tight.close()


# ---------------------------------------------------------------------------
# stream plane: requests in as proxies, completions out as evict proxies
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("staggered", [False, True],
                         ids=["burst", "staggered"])
def test_serve_stream_roundtrip(engine, shm_store, monkeypatch, staggered):
    """Every request comes back exactly once with ``generate()``'s greedy
    tokens.  ``staggered``: the feeder appends each request only after the
    engine has run two decode steps since the last one, so each arrives
    while earlier rows decode and is taken by a poll that does not wait."""
    rng = np.random.default_rng(29)
    reqs = [Request(prompt=_prompt(rng, 7), max_new_tokens=5,
                    req_id=f"s-{i}") for i in range(5)]
    want = {r.req_id: t for r, t in zip(
        reqs, engine.generate([Request(prompt=list(r.prompt),
                                       max_new_tokens=r.max_new_tokens)
                               for r in reqs])["outputs"])}
    steps = [0]
    stepped = threading.Condition()
    if staggered:
        decode = engine._decode

        def counting_decode(*args):
            with stepped:
                steps[0] += 1
                stepped.notify_all()
            return decode(*args)

        monkeypatch.setattr(engine, "_decode", counting_decode)

    def feed():
        prod = shm_store.stream_producer("req")
        for r in reqs:
            prod.append(shm_store.proxy(
                {"prompt": r.prompt, "max_new_tokens": r.max_new_tokens,
                 "req_id": r.req_id}, ttl=30.0))
            if staggered:
                with stepped:
                    seen = steps[0]
                    stepped.wait_for(lambda: steps[0] >= seen + 2,
                                     timeout=30.0)
        prod.close()

    t = threading.Thread(target=feed)
    t.start()
    stats = engine.serve_stream(shm_store, "req", "res",
                                data_store=shm_store, timeout=30.0,
                                result_groups=("metrics",))
    t.join()
    assert stats["completed"] == len(reqs)
    if staggered:
        # one admission per arrival, and rows decoded side by side: fewer
        # steps than the requests' 4 decode steps each run one after another
        assert stats["spans"]["engine.admit"]["n"] == len(reqs)
        assert stats["decode_steps"] < len(reqs) * 4
    got = {}
    for item in shm_store.stream_consumer("res", timeout=10.0):
        c = extract(item) if is_proxy(item) else item
        got[c["req_id"]] = c["tokens"]
        assert c["total_s"] >= c["queued_s"] >= 0.0
    assert got == want
    # completions published ONCE fan out to the pre-subscribed metrics
    # group too: the tap reads per-request metadata without resolving
    # (or stealing) a single result payload
    with metrics_tap(shm_store, "res", timeout=10.0) as tap:
        metas = {m["req_id"]: m["n_tokens"] for m in tap}
    assert metas == {rid: len(toks) for rid, toks in want.items()}


class _RecordingStream:
    """Stand-in for a stream consumer: records the ``timeout`` each
    ``next()`` finds set, then returns the next scripted item or raises
    it when it is an exception class."""

    def __init__(self, script) -> None:
        self._script = list(script)
        self.timeout = None
        self.timeouts: list = []

    def __next__(self):
        self.timeouts.append(self.timeout)
        item = self._script.pop(0)
        if isinstance(item, type) and issubclass(item, BaseException):
            raise item
        return item


def test_stream_source_polls_without_waiting_while_not_blocking():
    """A non-blocking poll sets the stream's timeout to 0, a blocking one
    the source's own; an empty non-blocking poll returns ``None`` and
    keeps the source open; the stream's end is ``_EXHAUSTED`` for good."""
    stream = _RecordingStream([TimeoutError, "a", "b", TimeoutError,
                               StopIteration])
    src = _StreamSource(stream, timeout=7.5)
    assert src.poll(block=False) is None
    assert src.poll(block=True) == "a"
    assert src.poll(block=False) == "b"
    assert src.poll(block=False) is None
    assert stream.timeouts == [0, 7.5, 0, 0]
    src.push_back("c")
    assert src.poll(block=False) == "c"      # in hand: no stream call
    assert stream.timeouts == [0, 7.5, 0, 0]
    assert src.poll(block=True) is _EXHAUSTED
    assert stream.timeouts == [0, 7.5, 0, 0, 7.5]
    assert src.poll(block=False) is _EXHAUSTED
    assert len(stream.timeouts) == 5         # done: the stream is not called


def test_stream_source_gives_up_after_a_blocking_timeout():
    stream = _RecordingStream([TimeoutError])
    src = _StreamSource(stream, timeout=3.0)
    assert src.poll(block=True) is _EXHAUSTED
    assert src.poll(block=False) is _EXHAUSTED
    assert stream.timeouts == [3.0]


def test_zero_timeout_take_never_waits_on_the_channel(shm_store):
    """The cells' request path (a store's ``ProxyStream`` over the shared
    memory connector's broker) with a timeout of 0: an empty take raises
    ``TimeoutError`` without entering the channel's condition wait, and a
    waiting item is taken."""
    consumer = shm_store.stream_consumer("zt", timeout=0)
    cond = shm_store.connector._channel_state()["cond"]

    def no_wait(*args, **kwargs):
        raise AssertionError("a zero-timeout take waited on the channel")

    cond.wait = no_wait
    try:
        with pytest.raises(TimeoutError):
            next(consumer)
        prod = shm_store.stream_producer("zt")
        prod.append(shm_store.proxy({"x": 1}, ttl=30.0))
        got = next(consumer)
        assert (extract(got) if is_proxy(got) else got) == {"x": 1}
        with pytest.raises(TimeoutError):
            next(consumer)
    finally:
        del cond.wait
        consumer.close()


def test_pool_byte_count_follows_puts_releases_and_reclaims(shm_store):
    """``bytes_in_use`` is a running total: after every put, release and
    lease reclaim it equals the sum over the blocks the pool tracks."""
    pool = KVBlockPool(shm_store, CFG, block_tokens=8, budget_bytes=None,
                       lease_ttl=0.05)

    def summed():
        return sum(b.nbytes for b in pool._blocks.values())

    k = np.zeros((CFG.n_layers, 8, CFG.n_kv_heads, CFG.hd), np.float32)
    short = k[:, :3]
    kept = pool.put_prefill(np.concatenate([k, short], 1),
                            np.concatenate([k, short], 1))   # 8 + 3 tokens
    orphan = pool.put_block(k, k)
    assert pool.bytes_in_use() == summed() == \
        sum(b.nbytes for b in kept) + orphan.nbytes
    pool.release(kept[:1])
    pool.release(kept[:1])                   # a second release counts once
    assert pool.bytes_in_use() == summed() == kept[1].nbytes + orphan.nbytes
    time.sleep(0.12)                         # every lease lapses
    pool.sweep()
    assert pool.bytes_in_use() == summed() == 0
    assert pool.stats()["n_blocks"] == 0


# ---------------------------------------------------------------------------
# the serve loop's spans and counters
# ---------------------------------------------------------------------------
def _serve(engine, store, reqs, **kw):
    def feed():
        prod = store.stream_producer("req")
        for r in reqs:
            prod.append(store.proxy(
                {"prompt": r.prompt, "max_new_tokens": r.max_new_tokens,
                 "req_id": r.req_id}, evict=True))
        prod.close()

    t = threading.Thread(target=feed)
    t.start()
    stats = engine.serve_stream(store, "req", "res", data_store=store,
                                timeout=30.0, **kw)
    t.join(timeout=30.0)
    assert not t.is_alive()
    return stats


def test_serve_stats_agree_with_spans_and_counters(engine, shm_store):
    """The loop's stats read from its tracer: ``decode_s`` is the
    ``engine.decode`` total over one span per decode step, admission
    counts every prefilled row, and no parent span is shorter than the
    children it holds."""
    rng = np.random.default_rng(41)
    reqs = [Request(prompt=_prompt(rng, n), max_new_tokens=m,
                    req_id=f"st-{i}")
            for i, (n, m) in enumerate([(5, 20), (9, 11), (5, 3), (12, 17),
                                        (9, 9), (7, 1)])]
    stats = _serve(engine, shm_store, reqs)
    spans, counters = stats["spans"], stats["counters"]
    assert stats["completed"] == len(reqs)
    assert spans["engine.decode"]["n"] == stats["decode_steps"] > 0
    assert stats["decode_s"] == spans["engine.decode"]["total_s"]
    assert stats["prefill_s"] == spans["engine.admit"]["total_s"]
    assert counters["rows_admitted"] == stats["completed"]
    # one engine.admit span per admission group, of max_batch rows each
    assert counters["prefill_rows"] == \
        spans["engine.admit"]["n"] * engine.max_batch
    assert set(counters) == {"prefill_rows", "rows_admitted"}
    assert spans["engine.retire"]["n"] == spans["kv.release"]["n"] == \
        spans["result.publish"]["n"] == len(reqs)
    assert spans["request.resolve"]["n"] == len(reqs)
    assert spans["engine.poll"]["n"] >= len(reqs)
    assert spans["engine.poll_empty"]["n"] >= 1   # the stream's close
    assert spans["engine.pageout"]["n"] >= 1       # 20 tokens, 8 a block
    assert spans["engine.serve"]["n"] == 1
    assert spans["engine.serve"]["parent"] is None
    children: dict[str, float] = {}
    for name, rec in spans.items():
        if rec["parent"] is not None:
            children[rec["parent"]] = children.get(rec["parent"], 0.0) \
                + rec["total_s"]
    assert set(children) >= {"engine.serve", "engine.admit", "engine.poll",
                             "engine.retire"}
    for parent, child_s in children.items():
        assert spans[parent]["total_s"] >= child_s - 1e-9, parent
        assert spans[parent]["self_s"] == pytest.approx(
            spans[parent]["total_s"] - child_s, abs=1e-6)


def test_first_token_stamp_is_published(engine, shm_store):
    """Each completion carries ``first_token_s`` (poll to the first token
    on the host), between ``queued_s`` and ``total_s``, in the payload
    and in the event metadata a tap reads."""
    rng = np.random.default_rng(43)
    reqs = [Request(prompt=_prompt(rng, 6), max_new_tokens=4,
                    req_id=f"ft-{i}") for i in range(3)]
    _serve(engine, shm_store, reqs, result_groups=("metrics",))
    got = {}
    for item in shm_store.stream_consumer("res", timeout=10.0):
        c = extract(item) if is_proxy(item) else item
        got[c["req_id"]] = c
        assert c["queued_s"] <= c["first_token_s"] <= c["total_s"]
    assert set(got) == {r.req_id for r in reqs}
    with metrics_tap(shm_store, "res", timeout=10.0) as tap:
        metas = {m["req_id"]: m["first_token_s"] for m in tap}
    assert metas == {rid: c["first_token_s"] for rid, c in got.items()}


# ---------------------------------------------------------------------------
# multi-worker zero-copy weight sharing: N processes, ONE arena mapping
# ---------------------------------------------------------------------------
def _first_big_leaf(tree):
    """Deterministic walk to the first >=512-byte array leaf (PSJ2 ships
    arrays that size out-of-band as zero-copy views)."""
    if isinstance(tree, dict):
        for k in sorted(tree):
            leaf = _first_big_leaf(tree[k])
            if leaf is not None:
                return leaf
        return None
    arr = np.asarray(tree)
    return arr if arr.nbytes >= 512 else None


def _weight_worker(borrowed, conn):
    tree = extract(borrowed)                 # zero-copy views of the slot
    leaf = _first_big_leaf(tree)
    conn.send((float(leaf.flat[0]), bool(leaf.flags["OWNDATA"])))
    conn.recv()                              # parent mutated its own view
    conn.send(float(leaf.flat[0]))           # same mapping -> sees the write
    conn.close()


def test_multi_worker_zero_copy_weight_sharing(engine, tmp_path):
    """N spawned workers resolve the same published weight proxy to views
    of ONE arena mapping: no worker owns its data, borrows add no
    references, and an in-place write through the publisher's view is
    visible to every worker without re-transfer."""
    name = f"serve-weights-{uuid.uuid4().hex[:8]}"
    store = Store(name, SharedMemoryConnector(str(tmp_path / "shm")))
    procs, pipes = [], []
    try:
        owned = engine.publish_weights(store, ttl=60.0)
        key = get_factory(owned).key
        assert store.refcount(key) == 1      # exactly the owner's reference

        ctx = mp.get_context("spawn")
        for _ in range(2):
            parent, child = ctx.Pipe()
            p = ctx.Process(target=_weight_worker,
                            args=(borrow(owned), child), daemon=True)
            p.start()
            child.close()
            procs.append(p)
            pipes.append(parent)
        first = [c.recv() for c in pipes]
        assert len({v for v, _ in first}) == 1
        assert all(not owndata for _, owndata in first)  # views, not copies
        assert store.refcount(key) == 1      # borrows carry no refs

        store.cache.pop(key)                 # bypass the put-side cache
        view = _first_big_leaf(store.get(key))
        assert not view.flags["OWNDATA"]     # publisher's view is shm too
        assert float(view.flat[0]) == first[0][0]
        view.flat[0] = 123.25                # in-place write into the slot
        for c in pipes:
            c.send("go")
        assert [c.recv() for c in pipes] == [123.25, 123.25]
        for p in procs:
            p.join(30)
            assert p.exitcode == 0
    finally:
        for p in procs:
            if p.is_alive():  # pragma: no cover - failed mid-protocol
                p.terminate()
        store.close()
        unregister_store(name)


def test_workers_refused_on_tpu_backend(monkeypatch):
    """``launch/serve.py --workers`` on a TPU backend fails before it builds
    an engine or spawns a child: the parent would hold the chip."""
    import jax

    from repro.launch import serve as serve_mod

    def must_not_run(*a, **kw):
        raise AssertionError("reached engine construction or spawn")

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(serve_mod, "ServeEngine", must_not_run)
    monkeypatch.setattr(serve_mod.mp, "get_context", must_not_run)
    monkeypatch.setattr("sys.argv", ["serve", "--preset", "tiny",
                                     "--workers", "2"])
    with pytest.raises(RuntimeError, match="one process per chip"):
        serve_mod.main()
