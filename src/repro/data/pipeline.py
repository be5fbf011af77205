"""Proxy-fed input pipeline (paper §3.5 async-resolve pattern + §5.6 style).

Producer subprocesses build batches, ``put`` them through a Store connector
(shm by default — zero-copy on-node), and enqueue tiny *proxies* on a
multiprocessing queue.  The consumer begins ``resolve_async`` on batch N+1
while step N computes, so host->store->host movement overlaps compute.

Straggler mitigation: each batch index can be produced by ``redundancy``
producers (first proxy wins; duplicates are evicted), and a consumer-side
deadline falls back to producing the batch inline — training never stalls on
a dead or slow producer.  Batches are deterministic by (seed, index), so
redundant/fallback production yields identical bytes.
"""
from __future__ import annotations

import multiprocessing as mp
import pickle
import queue as queue_mod
import threading
import time
from multiprocessing import connection as mp_connection
from typing import Any, Callable

from repro.core import Store, get_factory, resolve_async
from repro.core.proxy import Proxy, extract, is_resolved
from repro.core.serialize import materialize
from repro.core.store import StoreConfig, StoreFactory, get_or_create_store

# mp 'spawn' keeps producers free of the parent's JAX/XLA state
_CTX = mp.get_context("spawn")


def _producer_main(store_config_blob: bytes, make_batch_blob: bytes,
                   q, indices, redundancy_rank: int, delay_s: float) -> None:
    store_cfg: StoreConfig = pickle.loads(store_config_blob)
    make_batch: Callable[[int], Any] = pickle.loads(make_batch_blob)
    store = get_or_create_store(store_cfg)
    for idx in indices:
        if delay_s:
            time.sleep(delay_s)  # straggler injection (tests/benchmarks)
        batch = make_batch(idx)
        proxy = store.proxy(batch)
        q.put((idx, redundancy_rank, pickle.dumps(proxy)))


class ProxyDataPipeline:
    """Iterator of resolved batches with prefetch-by-proxy.

    ``make_batch`` runs in spawned producer processes, so it builds host
    (NumPy) arrays and never touches JAX: on a TPU host the chip belongs
    to the consuming process."""

    def __init__(self, store: Store, make_batch: Callable[[int], Any], *,
                 n_producers: int = 2, redundancy: int = 1,
                 prefetch: int = 2, deadline_s: float = 30.0,
                 straggler_delay_s: float = 0.0,
                 start_index: int = 0) -> None:
        self.store = store
        self.make_batch = make_batch
        self.deadline_s = deadline_s
        self.prefetch = prefetch
        self.next_index = start_index
        # ONE bounded queue PER producer (backpressure: at most ~prefetch
        # batches in flight each).  Per-producer queues are the crash
        # isolation the redundancy guarantee rests on: a producer killed
        # mid-enqueue can leave its own queue's shared write-lock held
        # forever, and with a single shared queue that deadlock would take
        # every *surviving* producer down with it — exactly the straggler
        # scenario redundancy exists to absorb.
        self._queues: list = []
        self._pending: dict[int, Proxy] = {}
        self._fallbacks = 0
        self._duplicates = 0
        self._procs: list[mp.Process] = []

        cfg_blob = pickle.dumps(store.config())
        fn_blob = pickle.dumps(make_batch)
        # round-robin index assignment x redundancy
        horizon = 1 << 16
        for r in range(redundancy):
            for w in range(n_producers):
                idxs = list(range(start_index + w, horizon, n_producers))
                delay = straggler_delay_s if (r == 0 and w == 0 and
                                              straggler_delay_s) else 0.0
                q = _CTX.Queue(maxsize=max(prefetch, 1) + 1)
                p = _CTX.Process(
                    target=_producer_main,
                    args=(cfg_blob, fn_blob, q, idxs, r, delay),
                    daemon=True)
                p.start()
                self._queues.append(q)
                self._procs.append(p)

    # ------------------------------------------------------------------
    def _take_one(self, timeout: float | None) -> tuple | None:
        """Pull one (idx, rank, blob) across the producer queues: non-
        blocking round-robin sweeps, then a blocking multi-pipe wait on
        every queue's reader until data or the deadline.  A queue whose
        producer died mid-write may yield garbage — it is skipped, never
        trusted to block."""
        # monotonic: a wall-clock (NTP) step must neither stall the drain
        # nor truncate it to an instant-empty poll.  None means block
        # until data (the Queue.get(timeout=None) contract this replaced).
        deadline = time.monotonic() + \
            (float("inf") if timeout is None else timeout)
        while True:
            for q in self._queues:
                try:
                    return q.get_nowait()
                except queue_mod.Empty:
                    continue
                except (EOFError, OSError, pickle.UnpicklingError):
                    continue     # crashed producer's queue: ignore
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                return None
            try:
                # kernel-blocking wait across every producer pipe: wakes
                # exactly on data (the parent's writer ends keep the pipes
                # from spurious EOF-readiness)
                mp_connection.wait(
                    [q._reader for q in self._queues],
                    timeout=None if remaining == float("inf")
                    else remaining)
            except OSError:      # a torn-down queue: fall back to a nap
                time.sleep(min(remaining, 0.005))

    def _drain(self, timeout: float | None) -> None:
        item = self._take_one(timeout)
        if item is None:
            return
        idx, rank, blob = item
        proxy = pickle.loads(blob)
        if idx in self._pending or idx < self.next_index:
            self._duplicates += 1
            self.store.evict(get_factory(proxy).key)  # redundant copy
        else:
            self._pending[idx] = proxy
            resolve_async(proxy)  # overlap: fetch while compute runs

    def __iter__(self):
        return self

    def __next__(self) -> Any:
        idx = self.next_index
        deadline = time.monotonic() + self.deadline_s
        while idx not in self._pending:
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                self._fallbacks += 1  # straggler: produce inline
                self._pending[idx] = Proxy(lambda i=idx: self.make_batch(i))
                break
            self._drain(timeout=min(remaining, 0.25))
        # opportunistically pull prefetch proxies that already arrived
        for _ in range(self.prefetch):
            self._drain(timeout=0)
        proxy = self._pending.pop(idx)
        self.next_index = idx + 1
        batch = extract(proxy)
        factory = get_factory(proxy)
        if isinstance(factory, StoreFactory):  # consumed once -> evict
            if getattr(self.store.connector, "borrows_get", False):
                # shm-arena gets are views the producer recycles post-
                # evict: detach the batch before dropping the key, or the
                # next produced batch could overwrite this one mid-step
                batch = materialize(batch)
            self.store.evict(factory.key)
        return batch

    @property
    def stats(self) -> dict:
        return {"fallbacks": self._fallbacks, "duplicates": self._duplicates,
                "pending": len(self._pending), "next": self.next_index}

    def close(self) -> None:
        for p in self._procs:
            p.terminate()
        for p in self._procs:
            p.join(timeout=2)
