"""Federated learning orchestrator (paper §5.5, Fig 10 — FLoX analog).

An aggregator drives rounds of local training on edge workers executed
through the FaaS executor (payload-capped cloud control plane, as in the
paper).  Data movement is pluggable:

* ``transport="value"`` — the baseline: model weights ride the FaaS payload
  (fails beyond the cap as model size grows; Fig 10's truncated baseline),
* ``transport="proxy"`` — weights go through a Store once per round; workers
  receive a ~300-byte proxy and resolve just-in-time; updates return by
  proxy too,
* ``pipeline=True`` (with ``transport="proxy"``) — the futures + streaming
  mode: the aggregator mints every round's weight :class:`ProxyFuture`
  upfront and dispatches round ``r+1``'s workers with a *pre-data* proxy
  BEFORE round ``r``'s aggregation finishes (they park in the channel's
  ``wait`` and are released by ``set_result``), and workers stream their
  updates (``Store.stream_producer``) instead of barrier-putting — the
  aggregator consumes updates as they land, overlapping collection with
  stragglers and dispatch with aggregation.

Round data uses the ownership subsystem (``Store.owned_proxy``): the round's
weights are an :class:`~repro.core.OwnedProxy` — every worker submit clones a
reference, each worker drops its reference after materializing the weights,
and the aggregator drops its own at round end, so the key is evicted exactly
once, after the LAST consumer (stragglers past the deadline still resolve
safely instead of hitting the old evict race).  A TTL lease bounds leaks from
workers that crash while holding references.  Pipelined-round weights are
future-backed plain proxies under a TTL lease; streamed updates are
refcounted stream items (consumed exactly once) under the same lease
backstop.

Production FL features: update compression (int8/topk + error feedback),
round deadlines with straggler dropping, worker failure injection +
over-provisioning, elastic worker counts per round, heartbeats.
"""
from __future__ import annotations

import math
import pickle
import random
import time
from concurrent.futures import FIRST_COMPLETED, wait
from dataclasses import dataclass, field
from typing import Any

import jax
import numpy as np

from repro.configs.base import ArchConfig
from repro.core import Store
from repro.core.proxy import extract, is_proxy, release
from repro.core.store import StoreConfig, get_or_create_store
from repro.data.datasets import lm_batch
from repro.distributed.compression import Compressor
from repro.federated.faas import FaasExecutor, PayloadTooLarge
from repro.runtime import refuse_child_workers_on_tpu


@dataclass
class FLConfig:
    rounds: int = 3
    workers_per_round: int = 4
    local_steps: int = 4
    batch: int = 4
    seq: int = 32
    lr: float = 0.05
    transport: str = "proxy"          # proxy | value
    pipeline: bool = False            # futures + streamed updates
    compression: str = "none"         # none | int8 | int8_ef | topk
    deadline_s: float = 60.0
    fail_rate: float = 0.0            # injected worker failures
    seed: int = 0


# ---------------------------------------------------------------------------
# worker task (module-level: picklable by reference for spawn workers)
# ---------------------------------------------------------------------------
def local_train_task(model_ref: Any, cfg: ArchConfig, fl_blob: bytes,
                     worker_seed: int, store_cfg_blob: bytes | None,
                     compression: str, stream_topic: str | None = None) -> Any:
    fl: FLConfig = pickle.loads(fl_blob)
    store = (get_or_create_store(pickle.loads(store_cfg_blob))
             if store_cfg_blob is not None else None)
    # streamed-update mode: the update goes out through the round's stream
    # as soon as it exists; failures go out the same way (in order), so the
    # aggregator never stalls waiting for a worker that already died
    producer = (store.stream_producer(stream_topic, ttl=4 * fl.deadline_s)
                if store is not None and stream_topic else None)
    try:
        if fl.fail_rate and random.random() < fl.fail_rate:
            raise RuntimeError(f"injected worker failure (seed {worker_seed})")

        if is_proxy(model_ref):
            # pre-data round weights (pipeline mode) park here in wait
            # until the aggregator's set_result lands them
            params = jax.tree.map(np.asarray, extract(model_ref))
            release(model_ref)  # weights materialized: drop worker's ref
        else:
            params = jax.tree.map(np.asarray, model_ref)

        from repro.models.model import build_model

        model = build_model(cfg)

        def loss_fn(p, batch):
            return model.loss(p, batch)[0]

        grad_fn = jax.jit(jax.value_and_grad(loss_fn))
        p = jax.tree.map(jax.numpy.asarray, params)
        for step in range(fl.local_steps):
            batch = lm_batch(worker_seed, step, fl.batch, fl.seq, cfg.vocab)
            _, g = grad_fn(p, {k: jax.numpy.asarray(v)
                               for k, v in batch.items()})
            p = jax.tree.map(lambda w, gg: (w.astype(np.float32)
                                            - fl.lr * gg.astype(np.float32)
                                            ).astype(w.dtype), p, g)
        update = jax.tree.map(
            lambda new, old: np.asarray(new, np.float32)
            - np.asarray(old, np.float32), p, params)
        if compression != "none":
            update = Compressor(compression).compress(update)
        if producer is not None:
            # meta rides the broker (not the data plane): a payload=False
            # monitor group can tail who delivered what without resolving
            # a single update payload
            return {"streamed": producer.append(
                update, meta={"worker": worker_seed, "ok": True})}
        if store is not None:
            # owned reference back: the aggregator releases it after
            # averaging; the lease reaps it if the aggregator dies first
            return store.owned_proxy(update, ttl=4 * fl.deadline_s)
        return update
    except Exception as e:
        if producer is not None:
            try:
                producer.append_exception(     # the aggregator counts it
                    e, meta={"worker": worker_seed, "ok": False})
            except Exception:  # noqa: BLE001 - stream already closed (the
                pass           # round's deadline passed): don't mask `e`
        raise


class FLOrchestrator:
    def __init__(self, cfg: ArchConfig, fl: FLConfig,
                 executor: FaasExecutor, store: Store | None,
                 monitor_group: str | None = None) -> None:
        # the executor's workers jit and run local training
        refuse_child_workers_on_tpu("a federated round's FaaS workers")
        self.cfg, self.fl = cfg, fl
        self.executor = executor
        self.store = store
        # pipelined rounds only: a second consumer group pre-subscribed on
        # every round's update stream, so a dashboard can tail worker
        # updates without stealing them from the aggregator (see
        # monitor_updates())
        self.monitor_group = monitor_group
        self._round_topics: list[str] = []
        from repro.models.model import build_model

        self.model = build_model(cfg)
        self.params = jax.tree.map(np.asarray,
                                   self.model.init(jax.random.key(fl.seed)))
        self.log: list[dict] = []

    # ------------------------------------------------------------------
    def _dispatch_model(self):
        if self.fl.transport == "proxy":
            assert self.store is not None
            # ONE put per round; every worker submit clones a reference and
            # the round's weights die after the LAST consumer drops it (the
            # lease reaps them if workers crash holding references)
            return self.store.owned_proxy(self.params,
                                          ttl=4 * self.fl.deadline_s)
        return self.params                         # by value (cap applies)

    def run_round(self, rnd: int, n_workers: int | None = None) -> dict:
        fl = self.fl
        n = n_workers or fl.workers_per_round
        model_ref = self._dispatch_model()
        store_blob = pickle.dumps(self.store.config()) \
            if fl.transport == "proxy" else None
        fl_blob = pickle.dumps(fl)
        t0 = time.perf_counter()
        futures = {}
        for w in range(n):
            fut = self.executor.submit(
                local_train_task, model_ref, self.cfg, fl_blob,
                1000 * rnd + w, store_blob, fl.compression)
            futures[fut] = w
        done, not_done = wait(list(futures), timeout=fl.deadline_s)
        updates, failures = [], 0
        for fut in done:
            try:
                result = fut.result()
                if is_proxy(result):
                    payload = extract(result)
                    release(result)   # drop the aggregator's reference
                else:
                    payload = result
                updates.append(Compressor.decompress(payload))
            except (RuntimeError, PayloadTooLarge):
                failures += 1
        stragglers = len(not_done)
        if updates:
            mean_update = jax.tree.map(
                lambda *us: np.mean(np.stack(us), axis=0), *updates)
            self.params = jax.tree.map(
                lambda p, u: (p.astype(np.float32) + u).astype(p.dtype),
                self.params, mean_update)
        if is_proxy(model_ref):  # round over: drop the aggregator's ref —
            release(model_ref)   # eviction happens after the LAST worker's
        info = {"round": rnd, "workers": n, "ok": len(updates),
                "failures": failures, "stragglers": stragglers,
                "wall_s": time.perf_counter() - t0}
        self.log.append(info)
        return info

    # ------------------------------------------------------------------
    # pipelined rounds: pre-data weight futures + streamed updates
    # ------------------------------------------------------------------
    def _dispatch_round(self, rnd: int, model_ref: Any, topic: str,
                        n: int) -> list:
        fl_blob = pickle.dumps(self.fl)
        store_blob = pickle.dumps(self.store.config())
        return [self.executor.submit(
            local_train_task, model_ref, self.cfg, fl_blob,
            1000 * rnd + w, store_blob, self.fl.compression, topic)
            for w in range(n)]

    def _consume_updates(self, topic: str, n: int) -> tuple[list, int, int]:
        """Take ``n`` streamed updates as they land (no barrier): worker
        failures arrive in-stream (``append_exception``) and are counted;
        workers that haven't appended when the ROUND deadline passes are
        stragglers (the deadline bounds the round, not each item)."""
        deadline = time.monotonic() + self.fl.deadline_s
        stream = self.store.stream_consumer(topic, group="aggregator",
                                            timeout=self.fl.deadline_s)
        updates, failures = [], 0
        try:
            for _ in range(n):
                remaining = deadline - time.monotonic()
                if remaining <= 0 and not stream.pending():
                    # past the deadline, but DRAIN prefetched updates
                    # first: they are already taken for this group
                    break
                stream.timeout = max(remaining, 0.05)  # per blocking next
                try:
                    updates.append(Compressor.decompress(next(stream)))
                except StopIteration:
                    break
                except TimeoutError:
                    break
                except Exception:  # noqa: BLE001 - worker's streamed failure
                    failures += 1
        finally:
            # flush acks; requeue anything prefetched-but-undelivered so
            # the group (and the payload refcounts) stay consistent
            stream.close()
            self.store.connector.stream_close(topic)  # reject late appends
        stragglers = n - len(updates) - failures
        return updates, failures, stragglers

    @staticmethod
    def _streams_cross_process(conn) -> bool:
        """True when the connector's streams live on a server (visible to
        worker PROCESSES), not in the in-process fallback table."""
        from repro.core.connector import BaseConnector

        child = getattr(conn, "_future_child", None)
        if child is not None:            # MultiConnector: ask its route
            return FLOrchestrator._streams_cross_process(child()[1])
        return type(conn).stream_next is not BaseConnector.stream_next

    def _run_pipelined(self, worker_schedule: list[int] | None) -> dict:
        """Rounds overlap: every round's weight future is minted upfront,
        round ``r+1``'s workers are dispatched (with a pre-data proxy)
        BEFORE round ``r``'s updates are aggregated, and ``set_result``
        releases them once the new weights exist.  Workers stream updates
        the moment they finish, so collection overlaps the stragglers."""
        fl = self.fl
        assert self.store is not None, "pipeline mode needs a store"
        if not self._streams_cross_process(self.store.connector):
            # the fallback stream table is process-local: worker processes
            # would append into their own tables and every round would
            # silently time out with zero updates
            raise ValueError(
                "pipeline=True needs a server-backed store connector "
                "(kvserver/socket/endpoint) — "
                f"{type(self.store.connector).__name__} streams are "
                "in-process only")
        run_id = f"fl-{id(self) & 0xffffff:x}-{random.randrange(1 << 24):x}"
        counts = [worker_schedule[r] if worker_schedule
                  else fl.workers_per_round for r in range(fl.rounds)]
        topics = [f"{run_id}-r{r}" for r in range(fl.rounds)]
        self._round_topics = topics
        if self.monitor_group:
            # pre-subscribe the monitor on every round's topic BEFORE any
            # worker appends: each update is then retained until BOTH the
            # aggregator and the monitor ack it, so tailing the stream
            # steals nothing from aggregation (updates publish once; the
            # producer's TTL lease backstops a monitor that never drains)
            for t in topics:
                self.store.connector.stream_subscribe(
                    t, self.monitor_group, start="begin")
        # every round's weights exist as a future BEFORE any aggregation
        weight_futs = [self.store.future(timeout=4 * fl.deadline_s,
                                         ttl=8 * fl.deadline_s)
                       for _ in range(fl.rounds)]
        weight_futs[0].set_result(self.params)
        losses = [self.eval_loss()]
        self._dispatch_round(0, weight_futs[0].proxy(), topics[0], counts[0])
        for rnd in range(fl.rounds):
            t0 = time.perf_counter()
            if rnd + 1 < fl.rounds:
                # next round goes out NOW: its workers transit the cloud
                # hop and park in wait while this round aggregates
                self._dispatch_round(rnd + 1, weight_futs[rnd + 1].proxy(),
                                     topics[rnd + 1], counts[rnd + 1])
            updates, failures, stragglers = self._consume_updates(
                topics[rnd], counts[rnd])
            if updates:
                mean_update = jax.tree.map(
                    lambda *us: np.mean(np.stack(us), axis=0), *updates)
                self.params = jax.tree.map(
                    lambda p, u: (p.astype(np.float32) + u).astype(p.dtype),
                    self.params, mean_update)
            if rnd + 1 < fl.rounds:
                weight_futs[rnd + 1].set_result(self.params)  # release them
            info = {"round": rnd, "workers": counts[rnd],
                    "ok": len(updates), "failures": failures,
                    "stragglers": stragglers,
                    "wall_s": time.perf_counter() - t0}
            self.log.append(info)
            losses.append(self.eval_loss())
        return {"losses": losses, "rounds": self.log}

    def monitor_updates(self, rnd: int, *, payload: bool = False,
                        timeout: float = 5.0):
        """Consumer tailing round ``rnd``'s update stream in the monitor
        group (pipelined runs with ``monitor_group`` set).  Defaults to
        ``payload=False``: iteration yields each update's metadata
        (``worker``/``ok``) without resolving the update tensors, so a
        live dashboard costs zero data-plane bytes.  The group's cursor
        is independent of the aggregator's — taking here steals nothing
        from aggregation.  Close (or ``with``) the returned consumer."""
        if not self.monitor_group:
            raise ValueError("orchestrator was built without monitor_group")
        if rnd >= len(self._round_topics):
            raise IndexError(f"round {rnd} has not been dispatched")
        return self.store.stream_consumer(
            self._round_topics[rnd], group=self.monitor_group,
            start="begin", payload=payload, timeout=timeout)

    def eval_loss(self) -> float:
        batch = lm_batch(999, 0, self.fl.batch, self.fl.seq, self.cfg.vocab)
        p = jax.tree.map(jax.numpy.asarray, self.params)
        loss, _ = self.model.loss(p, {k: jax.numpy.asarray(v)
                                      for k, v in batch.items()})
        return float(np.asarray(loss))

    def run(self, worker_schedule: list[int] | None = None) -> dict:
        if self.fl.pipeline:
            if self.fl.transport != "proxy":
                raise ValueError("pipeline=True requires transport='proxy'")
            return self._run_pipelined(worker_schedule)
        losses = [self.eval_loss()]
        for rnd in range(self.fl.rounds):
            n = worker_schedule[rnd] if worker_schedule else None
            self.run_round(rnd, n)
            losses.append(self.eval_loss())
        return {"losses": losses, "rounds": self.log}
