"""Proxy-native serving engine: continuous batching over the proxy data plane.

Every tensor that crosses a process boundary rides the proxy substrate
PRs 1-5 built:

* **weights** — loaded from a proxy (``weights=``: an engine's published
  :meth:`ServeEngine.publish_weights` ``OwnedProxy`` whose ``borrow()``
  each worker process resolves to zero-copy views of ONE shm arena
  mapping) or lazily from a proxy-checkpoint manifest (``ckpts=``; the
  restore is ONE batched ``get_batch`` per store);
* **KV cache** — the grow-by-``jnp.concatenate`` static cache is replaced
  by paged storage: each request's KV lives in fixed-size
  :class:`~repro.models.serve_paths.KVBlockPool` blocks backed by
  refcounted arena slots with TTL leases (completion releases them;
  crashed owners are reclaimed by lease expiry under memory pressure);
* **scheduling** — a continuous-batching loop with per-request admission
  and completion: rows join as slots free up (each prefilled alone at its
  natural length, positions per row) and retire at their own
  ``max_new_tokens`` — no padded lockstep, no wasted decode steps.
  :meth:`ServeEngine.serve_stream` feeds the loop from a ``ProxyStream``
  (requests arrive as proxies; responses publish as ephemeral
  ``evict=True`` proxies through a result stream).

Families without a left-aligned attention cache (ssm / audio / hybrid)
and sliding-window configs keep a lockstep static batcher
(:meth:`ServeEngine._generate_static`) behind the same ``generate`` API.
"""
from __future__ import annotations

import time
import uuid
from collections import deque
from dataclasses import dataclass, field
from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.core.proxy import extract, get_factory, is_proxy
from repro.models.model import build_model
from repro.models.serve_paths import KVBlockPool, KVPoolExhausted
from repro.serve.tracing import Tracer
from repro.train.checkpoints import ProxyCheckpointManager

_EXHAUSTED = object()     # source sentinel: no request will ever come again


@dataclass
class Request:
    prompt: list[int]
    max_new_tokens: int = 16
    temperature: float = 0.0   # 0 -> greedy
    req_id: str = ""           # assigned at submission when empty


@dataclass
class Completion:
    req_id: str
    tokens: list[int]
    prompt_len: int
    queued_s: float            # poll -> prefill dispatch
    total_s: float             # poll -> completion
    first_token_s: float       # poll -> first token on the host


@dataclass
class _Active:
    """One admitted request's scheduler state."""

    req: Request
    row: int
    length: int                # tokens in the dense row (prompt + generated)
    flushed: int               # tokens already paged out to KV blocks
    submit_t: float
    admit_t: float
    first_t: float
    out: list[int] = field(default_factory=list)
    blocks: list = field(default_factory=list)


class _ListSource:
    """Source over a known request list (the ``generate`` compat path)."""

    def __init__(self, reqs: list[Request]) -> None:
        self._q = deque(reqs)

    def poll(self, block: bool):
        return self._q.popleft() if self._q else _EXHAUSTED

    def push_back(self, req: Request) -> None:
        self._q.appendleft(req)


class _StreamSource:
    """Source over a ProxyStream consumer: requests arrive as stream items
    (optionally proxies, which the engine resolves with
    :func:`_as_request`), end with the producer's close.  A non-blocking
    poll (rows decoding, or a request already in hand) never waits: it
    takes what the stream holds or returns ``None`` at once.  A blocking
    poll (the engine idle) waits up to the configured ``timeout``."""

    def __init__(self, stream, *, timeout: float) -> None:
        self._stream = stream
        self._timeout = timeout
        self._pending: deque = deque()
        self._done = False

    def poll(self, block: bool):
        if self._pending:
            return self._pending.popleft()
        if self._done:
            return _EXHAUSTED
        self._stream.timeout = self._timeout if block else 0
        try:
            item = next(self._stream)
        except StopIteration:
            self._done = True
            return _EXHAUSTED
        except TimeoutError:
            if block:
                self._done = True      # idle past the deadline: give up
                return _EXHAUSTED
            return None
        return item

    def push_back(self, req: Request) -> None:
        self._pending.appendleft(req)


def _as_request(item) -> Request:
    factory = get_factory(item) if is_proxy(item) else None
    if factory is not None:
        item = extract(item)
    if isinstance(item, dict):
        req = Request(prompt=list(item["prompt"]),
                      max_new_tokens=int(item.get("max_new_tokens", 16)),
                      temperature=float(item.get("temperature", 0.0)),
                      req_id=str(item.get("req_id", "")))
    elif isinstance(item, Request):
        req = item
    else:
        raise TypeError(
            f"cannot interpret stream item as a request: {type(item)}")
    if factory is not None and not getattr(factory, "evict", True) \
            and not getattr(factory, "owned", True):
        # the engine has copied what it needs: free the request's slot now
        # instead of waiting out its lease (keeps the arena's working set
        # at the in-flight batch, not the whole request history)
        try:
            factory._store().evict(factory.key)
        except Exception:  # noqa: BLE001 - reclamation is best-effort
            pass
    return req


@partial(jax.jit, static_argnames=("vocab",))
def _sample_tokens(logits, temps, key, *, vocab: int):
    """Per-row sampling: each row uses ITS OWN temperature (greedy where
    temperature == 0) — one batched categorical, not ``temps[0]`` for all."""
    lv = logits[:, :vocab].astype(jnp.float32)
    greedy = jnp.argmax(lv, axis=-1)
    scaled = lv / jnp.maximum(temps, 1e-6)[:, None]
    sampled = jax.random.categorical(key, scaled, axis=-1)
    return jnp.where(temps > 0, sampled, greedy).astype(jnp.int32)


class ServeEngine:
    def __init__(self, cfg: ArchConfig, params=None, *,
                 ckpts: ProxyCheckpointManager | None = None,
                 weights=None, kv_store=None,
                 max_batch: int = 8, max_context: int = 256,
                 block_tokens: int = 16,
                 kv_budget_bytes: int | None = None,
                 lease_ttl: float | None = 60.0,
                 seed: int = 1234) -> None:
        self.cfg = cfg
        self.model = build_model(cfg)
        if params is None:
            if weights is not None:
                # worker path: a borrowed weight proxy resolves to zero-copy
                # views of the publisher's arena mapping; jnp.asarray is the
                # single host->device upload per worker
                params = jax.tree.map(jnp.asarray, extract(weights))
            elif ckpts is not None:   # lazy proxy restore (batched get)
                state = ckpts.restore()
                params = jax.tree.map(jnp.asarray, state["params"])
            else:
                # one program: at full width the random init is GBs, and
                # op-by-op dispatch would compile each op on its own
                params = jax.jit(self.model.init)(jax.random.key(0))
        self.params = params
        self.max_batch = int(max_batch)
        self.max_context = int(max_context)
        self.block_tokens = int(block_tokens)
        self.lease_ttl = lease_ttl
        self._kv_budget = kv_budget_bytes
        self._kv_store = kv_store
        self._own_kv_store = False
        self._kv_pool: KVBlockPool | None = None
        self._weights_owned = None
        self._key = jax.random.key(seed)
        # continuous batching needs a left-aligned dense attention cache;
        # ring (sliding-window) and state-cache families stay lockstep
        self._continuous = (cfg.family in ("dense", "moe", "vlm")
                            and not cfg.sliding_window)
        self._prefill = jax.jit(self.model.prefill)
        self._decode = jax.jit(self.model.decode_step, donate_argnums=(1,))
        self._scatter = jax.jit(self._scatter_rows, donate_argnums=(0,))

    # ------------------------------------------------------------------
    # weight plane
    # ------------------------------------------------------------------
    def publish_weights(self, store, *, ttl: float | None = None):
        """Broadcast this engine's parameters once through ``store`` as ONE
        PSJ2 frame (on an shm store: one arena slot every consumer maps
        zero-copy).  Returns an :class:`~repro.core.OwnedProxy` the engine
        holds; hand each worker process a pickled ``borrow()`` of it —
        borrows pin the owner, carry no reference of their own, and resolve
        without the deep-copy an owned resolve pays."""
        host = jax.tree.map(np.asarray, self.params)
        self._weights_owned = store.owned_proxy(host, ttl=ttl)
        return self._weights_owned

    # ------------------------------------------------------------------
    # KV plane
    # ------------------------------------------------------------------
    def kv_pool(self) -> KVBlockPool:
        """The paged KV-cache pool (created lazily; a private shm-arena
        store when none was injected)."""
        if self._kv_pool is None:
            if self._kv_store is None:
                import tempfile

                from repro.core import Store
                from repro.core.connectors import SharedMemoryConnector

                self._kv_store = Store(
                    f"serve-kv-{uuid.uuid4().hex[:8]}",
                    SharedMemoryConnector(
                        tempfile.mkdtemp(prefix="repro-kv-")))
                self._own_kv_store = True
            budget = self._kv_budget
            pool = KVBlockPool(self._kv_store, self.cfg,
                               block_tokens=self.block_tokens,
                               budget_bytes=None,
                               lease_ttl=self.lease_ttl)
            if budget is None:
                # default: 2x the dense working set, so completed requests'
                # pages linger long enough for stats/debug without growing
                per_tok = 2 * self.cfg.n_layers * self.cfg.n_kv_heads \
                    * self.cfg.hd * pool.dtype.itemsize
                budget = 2 * self.max_batch * self.max_context * per_tok
            pool.budget_bytes = budget
            self._kv_pool = pool
        return self._kv_pool

    # ------------------------------------------------------------------
    # continuous scheduler
    # ------------------------------------------------------------------
    def _alloc_cache(self):
        cfg = self.cfg
        shape = (cfg.n_layers, self.max_batch, self.max_context,
                 cfg.n_kv_heads, cfg.hd)
        dt = jnp.dtype(cfg.dtype)
        return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}

    @staticmethod
    def _scatter_rows(cache, kk, vv, perm, mask):
        """Write admitted rows' prefill KV into their cache rows in one
        fixed-shape update (`perm` maps cache row -> prefill row, `mask`
        selects admitted rows), so the trace count is one per prompt
        length — independent of how many rows each group admits."""
        s = cache["k"].shape[2]
        plen = kk.shape[2]
        pad = [(0, 0), (0, 0), (0, s - plen), (0, 0), (0, 0)]
        m = (mask[:, None] & (jnp.arange(s) < plen)[None, :]
             )[None, :, :, None, None]
        return {"k": jnp.where(m, jnp.pad(kk[:, perm], pad), cache["k"]),
                "v": jnp.where(m, jnp.pad(vv[:, perm], pad), cache["v"])}

    def _admit_group(self, group: list[tuple[Request, float]],
                     rows: list[int], state: dict, tr: Tracer,
                     ) -> tuple[list[_Active], list[Request]]:
        """Admit a same-prompt-length group with ONE batched prefill
        (padded to ``max_batch`` rows so the trace count is bounded by the
        number of distinct prompt lengths, not group sizes), page each
        request's KV into pool blocks, scatter the batch into its target
        rows in one cache update.  Returns (admitted, deferred) — requests
        the pool could not hold pages for come back deferred instead of
        failing the whole group."""
        cfg = self.cfg
        pool = self.kv_pool()
        plen = len(group[0][0].prompt)
        n = len(group)
        B = self.max_batch
        tr.count("prefill_rows", B)
        with tr.span("engine.admit.prefill"):
            toks = np.zeros((B, plen), np.int32)
            for i, (req, _) in enumerate(group):
                toks[i] = req.prompt
            batch = {"tokens": jnp.asarray(toks)}
            if cfg.family == "vlm":
                batch["vision_emb"] = jnp.zeros(
                    (B, cfg.n_img_tokens, cfg.d_model), jnp.dtype(cfg.dtype))
            logits, kv = self._prefill(self.params, batch)
            admit_t = time.perf_counter()
            self._key, sub = jax.random.split(self._key)
            temps = np.zeros(B, np.float32)
            temps[:n] = [req.temperature for req, _ in group]
            first = np.asarray(self._sample(logits, temps, sub))
            first_t = time.perf_counter()
        with tr.span("engine.admit.kv_to_host"):
            kh_all = np.asarray(kv["k"])            # (L, B, plen, KV, HD)
            vh_all = np.asarray(kv["v"])

        admitted: list[_Active] = []
        deferred: list[Request] = []
        for i, (req, submit_t) in enumerate(group):
            if deferred:
                deferred.append(req)
                continue
            try:
                with tr.span("kv.put_prefill"):
                    blocks = pool.put_prefill(kh_all[:, i], vh_all[:, i])
            except KVPoolExhausted:
                deferred.append(req)
                continue
            a = _Active(req=req, row=rows[i], length=plen, flushed=plen,
                        submit_t=submit_t, admit_t=admit_t, first_t=first_t,
                        blocks=blocks)
            a.out.append(int(first[i]))
            state["tokens"][a.row] = a.out[0]
            state["lengths"][a.row] = plen
            state["temps"][a.row] = req.temperature
            admitted.append(a)
        tr.count("rows_admitted", len(admitted))
        if admitted:
            with tr.span("engine.admit.scatter"):
                perm = np.arange(B, dtype=np.int32)
                mask = np.zeros(B, bool)
                for i, a in enumerate(admitted):
                    perm[a.row] = i
                    mask[a.row] = True
                state["cache"] = self._scatter(state["cache"], kv["k"],
                                               kv["v"], jnp.asarray(perm),
                                               jnp.asarray(mask))
        return admitted, deferred

    def _sample(self, logits, temps, key):
        return _sample_tokens(logits, jnp.asarray(temps, jnp.float32), key,
                              vocab=self.cfg.vocab)

    def _flush_blocks(self, a: _Active, cache, tr: Tracer) -> None:
        """Page freshly decoded KV out of the dense row whenever a full
        block has accumulated, so the refcounted pool (not the working
        cache) is the cache's durable home."""
        pool = self.kv_pool()
        bt = pool.block_tokens
        while a.length - a.flushed >= bt:
            e = a.flushed + bt
            with tr.span("engine.pageout"):
                kh = np.asarray(cache["k"][:, a.row, a.flushed:e])
                vh = np.asarray(cache["v"][:, a.row, a.flushed:e])
                try:
                    a.blocks.append(pool.put_block(kh, vh))
                except KVPoolExhausted:
                    return                   # defer: retry next boundary
            a.flushed = e

    def _run_continuous(self, source, sink) -> dict:
        """The continuous-batching loop: admit-as-slots-free, decode the
        whole active set each step with per-row positions, retire each row
        at its own ``max_new_tokens``.  Returns the loop's counts, its
        admission and decode time (``prefill_s``/``decode_s``) and the
        tracer's per-span sums and counters (``spans``/``counters``)."""
        tr = Tracer()
        with tr.span("engine.serve", annotate=False):
            steps, completed = self._serve_loop(source, sink, tr)
        return {"prefill_s": tr.total_s("engine.admit"),
                "decode_s": tr.total_s("engine.decode"),
                "decode_steps": steps, "completed": completed,
                **tr.summary()}

    def _serve_loop(self, source, sink, tr: Tracer) -> tuple[int, int]:
        B = self.max_batch
        state = {
            "cache": self._alloc_cache(),
            "tokens": np.zeros(B, np.int32),
            "lengths": np.zeros(B, np.int32),    # inactive rows: pos 0,
            "temps": np.zeros(B, np.float32),    # masked + greedy (harmless)
        }
        free_rows = deque(range(B))
        active: dict[int, _Active] = {}
        exhausted = False
        steps = 0
        completed = 0
        last_touch = time.perf_counter()           # lease heartbeat clock

        kv_starved = False                         # pool full: admissions
                                                   # wait for a retirement

        def retire(a: _Active) -> None:
            nonlocal completed, kv_starved
            kv_starved = False
            with tr.span("engine.retire"):
                with tr.span("kv.release"):
                    self.kv_pool().release(a.blocks)  # refcounts -> 0 -> freed
                now = time.perf_counter()
                with tr.span("result.publish"):
                    sink(Completion(req_id=a.req.req_id, tokens=a.out,
                                    prompt_len=len(a.req.prompt),
                                    queued_s=a.admit_t - a.submit_t,
                                    total_s=now - a.submit_t,
                                    first_token_s=a.first_t - a.submit_t))
                active.pop(a.row)
                state["lengths"][a.row] = 0
                state["temps"][a.row] = 0.0
                state["tokens"][a.row] = 0
                free_rows.append(a.row)
            completed += 1

        while True:
            # -- admission: pull ready requests, admit per length group ---
            ready: list[tuple[Request, float]] = []
            while not kv_starved and len(ready) < len(free_rows) \
                    and not exhausted:
                with tr.span("engine.poll") as poll:
                    item = source.poll(block=not active and not ready)
                    if item is None or item is _EXHAUSTED:
                        poll.outcome = "empty"
                    else:
                        with tr.span("request.resolve"):
                            req = _as_request(item)
                if item is _EXHAUSTED:
                    exhausted = True
                    break
                if item is None:
                    break                          # nothing waiting right now
                if not req.req_id:
                    req.req_id = uuid.uuid4().hex[:12]
                if len(req.prompt) + req.max_new_tokens > self.max_context:
                    raise ValueError(
                        f"request {req.req_id}: prompt {len(req.prompt)} + "
                        f"max_new_tokens {req.max_new_tokens} exceeds "
                        f"max_context {self.max_context}")
                ready.append((req, time.perf_counter()))
            groups: dict[int, list[tuple[Request, float]]] = {}
            for entry in ready:
                groups.setdefault(len(entry[0].prompt), []).append(entry)
            for group in groups.values():
                rows = [free_rows.popleft() for _ in group]
                with tr.span("engine.admit"):
                    admitted, deferred = self._admit_group(group, rows,
                                                           state, tr)
                for row in rows[len(admitted):]:
                    free_rows.append(row)
                for req in reversed(deferred):     # keep arrival order
                    source.push_back(req)
                if deferred:
                    kv_starved = True
                    exhausted = False              # pushed-back work remains
                for a in admitted:
                    active[a.row] = a
                    if len(a.out) >= a.req.max_new_tokens:
                        retire(a)                  # max_new_tokens == 1
            if kv_starved and not active:
                raise KVPoolExhausted(
                    "KV pool cannot hold a single request's prefill "
                    f"({self.kv_pool().stats()})")
            if not active:
                if exhausted:
                    break
                continue                           # idle: block in poll()

            # -- one decode step over the whole active set ----------------
            with tr.span("engine.decode"):
                logits, state["cache"] = self._decode(
                    self.params, state["cache"],
                    jnp.asarray(state["tokens"][:, None]),
                    jnp.asarray(state["lengths"]))
                self._key, sub = jax.random.split(self._key)
                nxt = np.asarray(self._sample(logits, state["temps"], sub))
            steps += 1

            for a in list(active.values()):
                state["lengths"][a.row] += 1
                a.length += 1
                tok = int(nxt[a.row])
                a.out.append(tok)
                state["tokens"][a.row] = tok
                self._flush_blocks(a, state["cache"], tr)
                if len(a.out) >= a.req.max_new_tokens:
                    retire(a)

            # -- lease heartbeat for long-running requests ----------------
            if self.lease_ttl and \
                    time.perf_counter() - last_touch > self.lease_ttl / 2:
                with tr.span("engine.heartbeat"):
                    pool = self.kv_pool()
                    for a in active.values():
                        pool.touch(a.blocks)
                last_touch = time.perf_counter()
        return steps, completed

    # ------------------------------------------------------------------
    # public API
    # ------------------------------------------------------------------
    def generate(self, reqs: list[Request]) -> dict:
        """Generate for a request list.  Continuous-capable families run
        the per-request scheduler (any number of requests — rows recycle);
        state-cache / sliding-window families use the lockstep batcher."""
        if not reqs:
            return {"outputs": [], "completions": [], "prefill_s": 0.0,
                    "decode_s": 0.0, "tokens_per_s": 0.0}
        if not self._continuous:
            return self._generate_static(reqs)
        for r in reqs:
            if not r.req_id:
                r.req_id = uuid.uuid4().hex[:12]
        completions: list[Completion] = []
        stats = self._run_continuous(_ListSource(list(reqs)),
                                     completions.append)
        by_id = {c.req_id: c for c in completions}
        outputs = [by_id[r.req_id].tokens for r in reqs]
        n_tok = sum(len(o) for o in outputs)
        return {"outputs": outputs, "completions": completions,
                "prefill_s": stats["prefill_s"],
                "decode_s": stats["decode_s"],
                "decode_steps": stats["decode_steps"],
                "tokens_per_s": n_tok / max(stats["decode_s"], 1e-9)}

    def serve_stream(self, store, request_topic: str,
                     result_topic: str | None = None, *,
                     data_store=None, timeout: float = 60.0,
                     result_ttl: float | None = 120.0,
                     result_groups: Sequence[str] | None = None) -> dict:
        """Serve until the request stream closes (or stays idle past
        ``timeout``).  Requests are stream items (optionally proxies);
        completions publish ONCE to ``result_topic`` — as ephemeral
        ``evict=True`` proxies through ``data_store`` when given (each
        result is consumed exactly once per group, then its slot is
        reclaimed), or inline otherwise — and fan out to every consumer
        group on the topic.  Each completion carries its metadata
        (``req_id``/``n_tokens``/latencies) on the event itself, so a
        ``payload=False`` tap (:func:`metrics_tap`) observes the serve
        loop without resolving a single result payload.  Groups named in
        ``result_groups`` are pre-subscribed before serving starts, so
        consumers attaching mid-stream (the client, a metrics dashboard)
        miss nothing.  Returns the scheduler's stats."""
        consumer = store.stream_consumer(request_topic, timeout=timeout)
        producer = (store.stream_producer(result_topic)
                    if result_topic else None)
        if producer is not None:
            for group in result_groups or ():
                store.connector.stream_subscribe(result_topic, group,
                                                 start="begin")
        local: list[Completion] = []

        def sink(c: Completion) -> None:
            if producer is None:
                local.append(c)
                return
            payload = {"req_id": c.req_id, "tokens": c.tokens,
                       "prompt_len": c.prompt_len,
                       "queued_s": c.queued_s, "total_s": c.total_s,
                       "first_token_s": c.first_token_s}
            meta = {"req_id": c.req_id, "n_tokens": len(c.tokens),
                    "queued_s": c.queued_s, "total_s": c.total_s,
                    "first_token_s": c.first_token_s}
            if data_store is not None:
                producer.append(data_store.proxy(payload, evict=True,
                                                 ttl=result_ttl),
                                meta=meta)
            else:
                producer.append(payload, meta=meta)

        try:
            stats = self._run_continuous(
                _StreamSource(consumer, timeout=timeout), sink)
        finally:
            consumer.close()        # return prefetched requests, if any
            if producer is not None:
                producer.close()
        stats["completions"] = local
        return stats

    # ------------------------------------------------------------------
    # lockstep fallback (state-cache + sliding-window families)
    # ------------------------------------------------------------------
    def _pad_prompts(self, reqs: list[Request]) -> tuple[np.ndarray, int]:
        max_len = max(len(r.prompt) for r in reqs)
        toks = np.zeros((len(reqs), max_len), np.int32)
        for i, r in enumerate(reqs):
            toks[i, max_len - len(r.prompt):] = r.prompt  # left-pad
        return toks, max_len

    def _generate_static(self, reqs: list[Request]) -> dict:
        assert len(reqs) <= self.max_batch
        cfg = self.cfg
        toks, plen = self._pad_prompts(reqs)
        batch = {"tokens": jnp.asarray(toks)}
        if cfg.family == "vlm":
            batch["vision_emb"] = jnp.zeros(
                (len(reqs), cfg.n_img_tokens, cfg.d_model),
                jnp.dtype(cfg.dtype))
        if cfg.family == "audio":
            batch["frames"] = jnp.zeros(
                (len(reqs), cfg.enc_frames, cfg.d_model),
                jnp.dtype(cfg.dtype))
        n_new = max(r.max_new_tokens for r in reqs)

        tr = Tracer()
        with tr.span("engine.admit"):
            logits, cache = self._prefill(self.params, batch)

        # extend lockstep attention caches for the generated tokens (the
        # state-cache/ring families this path serves; the continuous
        # scheduler's families page through the KV pool instead)
        def grow(path, a):
            name = str(path[-1].key) if path else ""
            if name in ("k", "v") and a.ndim == 5 and not cfg.sliding_window:
                pad = np.zeros((*a.shape[:2], n_new, *a.shape[3:]), a.dtype)
                return jnp.concatenate([a, jnp.asarray(pad)], axis=2)
            return a
        cache = jax.tree_util.tree_map_with_path(grow, cache)

        out: list[list[int]] = [[] for _ in reqs]
        temps = np.asarray([r.temperature for r in reqs], np.float32)
        for t in range(n_new):
            with tr.span("engine.decode"):
                self._key, sub = jax.random.split(self._key)
                nxt = self._sample(logits, temps, sub)[:, None]
                for i, token in enumerate(np.asarray(nxt)[:, 0]):
                    if t < reqs[i].max_new_tokens:
                        out[i].append(int(token))
                if all(len(out[i]) >= r.max_new_tokens
                       for i, r in enumerate(reqs)):
                    break
                logits, cache = self._decode(self.params, cache, nxt,
                                             jnp.asarray(plen + t, jnp.int32))
        decode_s = tr.total_s("engine.decode")
        n_tok = sum(len(o) for o in out)
        return {"outputs": out,
                "completions": [],
                "prefill_s": tr.total_s("engine.admit"),
                "decode_s": decode_s,
                "tokens_per_s": n_tok / max(decode_s, 1e-9)}

    # ------------------------------------------------------------------
    def stats(self) -> dict:
        out = {"max_batch": self.max_batch, "max_context": self.max_context,
               "continuous": self._continuous}
        if self._kv_pool is not None:
            out["kv_pool"] = self._kv_pool.stats()
        return out

    def close(self) -> None:
        """Release the published-weights reference and any private KV
        store (freeing their arena slots)."""
        if self._weights_owned is not None:
            from repro.core.proxy import release

            try:
                release(self._weights_owned)
            except RuntimeError:
                pass                  # borrows still alive: owner keeps it
            self._weights_owned = None
        if self._own_kv_store and self._kv_store is not None:
            self._kv_store.close()
            self._kv_store = None
            self._kv_pool = None


def metrics_tap(store, result_topic: str, *, group: str = "metrics",
                start: str = "begin", timeout: float = 60.0):
    """Metadata-only consumer group over an engine's result stream.

    Subscribes ``group`` to ``result_topic`` with ``payload=False``: the
    tap iterates completion metadata (``req_id``/``n_tokens``/latencies)
    that :meth:`ServeEngine.serve_stream` attaches to every event, while
    the broker serves the actual result payloads only to the groups that
    resolve them.  The serve loop publishes each completion exactly once
    — adding (or removing) taps changes zero bytes on the data plane.

    Pass the returned consumer's group name in ``result_groups`` when
    starting ``serve_stream`` (or attach with ``start="begin"``, the
    default here) so no completions are missed.
    """
    return store.stream_consumer(result_topic, group=group, start=start,
                                 payload=False, timeout=timeout)
