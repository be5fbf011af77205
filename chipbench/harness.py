"""One run of one cell: set-up, the measured window, the checks, the result.

The timed path is the program's own entry: ``ServeEngine.serve_stream``
over a shared-memory ``Store``, fed by a generator thread that appends each
request as an ``evict=True`` proxy at its due time, and read by a client
thread that takes each result off the result stream and stamps it on
arrival.  Only the main thread touches JAX.
"""
from __future__ import annotations

import gc
import os
import sys
import tempfile
import threading
import time
from collections import Counter
from contextlib import ExitStack

import jax
import numpy as np

from chipbench import arch, loadgen, reference, trace_reduce
from chipbench import weights as W
from chipbench.spec import ROOT, Cell, metric_reader

LOWERING = "/jax/core/compile/jaxpr_to_mlir_module_duration"
REQ_TOPIC, RES_TOPIC, CLIENT = "requests", "results", "client"
# JAX's persistent compile cache: inside the checkout, at a fixed path
CACHE_DIR = ROOT / ".jax_cache"


def use_compile_cache() -> None:
    """Keep every program, the smallest eager slices too, in the
    checkout's compile cache, so that only a checkout's first run
    compiles.  The program reads the same directory from the
    environment."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


class CompileLog:
    """Times of every lowering to a new executable (a compile or a
    persistent-cache load) in this process."""

    def __init__(self) -> None:
        self.times: list[float] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event == LOWERING:
            self.times.append(time.perf_counter())

    def between(self, t0: float, t1: float) -> int:
        return sum(t0 <= t <= t1 for t in self.times)


def build_engine(spec, seed: int):
    from repro.serve.engine import ServeEngine

    params = W.make_params(spec, seed)
    jax.block_until_ready(params)
    cfg = arch.of(spec).arch_config(spec)
    return ServeEngine(cfg, params=params, max_batch=spec.max_batch,
                       max_context=spec.max_context,
                       block_tokens=spec.block_tokens,
                       seed=seed & 0x7FFFFFFF)


def _new_store(stack: ExitStack):
    from repro.core import Store
    from repro.core.connectors import SharedMemoryConnector

    reg = stack.enter_context(tempfile.TemporaryDirectory(prefix="chipbench-"))
    store = Store("chipbench", SharedMemoryConnector(reg))
    stack.callback(store.close)
    return store


def warm_up(engine, store, spec, shapes: list[int]) -> None:
    """Compile every program the window will run: one request per prompt
    shape through ``serve_stream`` (prefill, scatter, decode, sampling, the
    stream plane, a KV page-out), then every page-out slice the window can
    take.  The engine pages KV out of its cache (layers, rows, positions,
    then the architecture's own axes: its module's ``cache_shape``) one
    block at a time with a slice at Python-int bounds, one executable per
    (row, offset); the same slices of an array of that shape compile them
    all.  Should the engine page out another way, these slices are warmed
    for nothing, and any compile left in the window shows in
    ``compiles_in_window``."""
    import jax.numpy as jnp
    from repro.serve.engine import Request

    bt = spec.block_tokens
    reqs = [Request(prompt=[1] * p, max_new_tokens=min(bt + 2,
                                                       spec.max_context - p),
                    req_id=f"warm{p}") for p in shapes]
    t0 = time.perf_counter()
    done = _serve_list(engine, store, reqs, "warm")
    if sorted(done) != sorted(r.req_id for r in reqs):
        raise RuntimeError(f"warm-up returned {sorted(done)}")
    _log(f"set-up: {len(reqs)} prompt shapes served in "
         f"{time.perf_counter() - t0:.3f} s")
    cache = jnp.zeros(arch.of(spec).cache_shape(spec), jnp.dtype(spec.dtype))
    for row in range(spec.max_batch):
        for s in range(min(shapes), spec.max_context - bt + 1, bt):
            cache[:, row, s:s + bt].block_until_ready()
    del cache


def _serve_list(engine, store, reqs, tag: str) -> list[str]:
    from repro.core.proxy import extract, is_proxy

    prod = store.stream_producer(f"{tag}.{REQ_TOPIC}")
    for r in reqs:
        prod.append(store.proxy({"prompt": r.prompt,
                                 "max_new_tokens": r.max_new_tokens,
                                 "temperature": 0.0, "req_id": r.req_id},
                                evict=True))
    prod.close()
    engine.serve_stream(store, f"{tag}.{REQ_TOPIC}", f"{tag}.{RES_TOPIC}",
                        data_store=store, timeout=120.0)
    ids = []
    with store.stream_consumer(f"{tag}.{RES_TOPIC}", timeout=30.0) as s:
        for item in s:
            ids.append((extract(item) if is_proxy(item) else item)["req_id"])
    return ids


class Window:
    """The measured window: generator and client threads around the
    engine's ``serve_stream`` in the calling thread.  An open loop's window
    opens with its first arrival; a backlog's ``ramp_s`` after the client
    starts to send, so that it measures the full pipeline and not its
    filling (the ramp is neither set-up nor window)."""

    def __init__(self, engine, store, cell: Cell, seed: int, seconds: float,
                 trace_dir: str | None, tag: str = "") -> None:
        self.engine, self.store, self.cell = engine, store, cell
        self.req_topic, self.res_topic = tag + REQ_TOPIC, tag + RES_TOPIC
        self.mix = cell.traffic
        self.seconds = seconds
        self.trace_dir = trace_dir
        spec = cell.spec
        self.reqs = loadgen.requests(self.mix, cell.data, seed, seconds,
                                     spec.vocab, spec.max_context)
        self.open_loop = self.mix["kind"] == "open_loop"
        self.n_window = (loadgen.block_size(self.mix, cell.data, seconds)
                         if self.open_loop else None)
        self.drain_limit = float(self.mix["drain_limit_s"])
        # a backlog's window opens once the pipeline is full (set-up)
        self.ramp = float(self.mix.get("ramp_s", 0.0))
        self.sent: dict[str, dict] = {}        # req_id -> record
        self.received: list[tuple[str, float, dict]] = []
        self.window_done = threading.Event()   # every window request back
        self.lock = threading.Lock()
        self.t_start = self.t_open = self.t_close = 0.0
        self.errors: list[BaseException] = []

    # -- client side -------------------------------------------------------
    def _send(self, prod, r: loadgen.Req, due: float) -> None:
        rec = {"req": r, "due": due, "sent": None, "arrived": None,
               "result": None, "in_window": r.block == 0}
        with self.lock:
            self.sent[r.req_id] = rec
        with jax.profiler.TraceAnnotation("client.send"):
            prod.append(self.store.proxy(
                {"prompt": r.prompt, "max_new_tokens": r.max_new_tokens,
                 "temperature": 0.0, "req_id": r.req_id}, evict=True))
        rec["sent"] = time.perf_counter()

    def _generate(self) -> None:
        prod = self.store.stream_producer(self.req_topic)
        try:
            if self.open_loop:
                self._open_loop(prod)
            else:
                self._backlog(prod)
        except BaseException as e:  # noqa: BLE001 - reported by run()
            self.errors.append(e)
        finally:
            prod.close()

    def _open_loop(self, prod) -> None:
        """Every request at its due time; past the window, until every
        request due in it is back or the drain limit passes."""
        limit = self.t_open + self.seconds + self.drain_limit
        for r in self.reqs:
            due = self.t_open + r.offset_s
            while True:
                now = time.perf_counter()
                if r.block > 0 and (self.window_done.is_set() or now > limit):
                    return
                if now >= due:
                    break
                time.sleep(min(due - now, 0.005))
            self._send(prod, r, due)

    def _backlog(self, prod) -> None:
        """Keep ``outstanding`` requests unanswered until the window closes."""
        depth = int(self.mix["outstanding"])
        end = self.t_open + self.seconds
        for r in self.reqs:
            while True:
                if time.perf_counter() >= end:
                    return
                with self.lock:
                    waiting = len(self.sent) - len(self.received)
                if waiting < depth:
                    break
                time.sleep(0.001)
            self._send(prod, r, time.perf_counter())

    def _clock(self) -> None:
        """Holds the window's span from its opening to its close, then ends
        the trace, so the trace is the window."""
        while (now := time.perf_counter()) < self.t_open:
            time.sleep(min(self.t_open - now, 0.01))
        end = self.t_open + self.seconds
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
            while (now := time.perf_counter()) < end:
                time.sleep(min(end - now, 0.01))
        self.t_close = end
        if self.trace_dir:
            try:
                jax.profiler.stop_trace()
            except BaseException as e:  # noqa: BLE001 - reported by run()
                self.errors.append(e)

    def _receive(self, consumer) -> None:
        from repro.core.proxy import extract, is_proxy

        try:
            for item in consumer:
                now = time.perf_counter()
                with jax.profiler.TraceAnnotation("client.receive"):
                    res = extract(item) if is_proxy(item) else item
                with self.lock:
                    self.received.append((res["req_id"], now, res))
                    rec = self.sent.get(res["req_id"])
                    if rec is not None and rec["arrived"] is None:
                        rec["arrived"], rec["result"] = now, res
                    if self.open_loop and self._window_complete():
                        self.window_done.set()
        except BaseException as e:  # noqa: BLE001 - reported by run()
            self.errors.append(e)

    def _window_complete(self) -> bool:
        n = sum(1 for r in self.sent.values()
                if r["in_window"] and r["arrived"] is not None)
        return n >= self.n_window

    # -- the run -----------------------------------------------------------
    def run(self) -> dict:
        consumer = self.store.stream_consumer(
            self.res_topic, group=CLIENT, start="begin",
            timeout=self.seconds + self.drain_limit + 120.0)
        threads = [threading.Thread(target=self._generate, name="cb-gen"),
                   threading.Thread(target=self._receive, args=(consumer,),
                                    name="cb-client"),
                   threading.Thread(target=self._clock, name="cb-clock")]
        if self.trace_dir:
            # host spans are TraceMe's and the annotations here; the Python
            # tracer (every function call) would slow the host it measures
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        self.t_start = time.perf_counter()
        self.t_open = self.t_start + self.ramp
        for t in threads:
            t.start()
        try:
            with jax.profiler.TraceAnnotation("engine.serve_stream"):
                stats = self.engine.serve_stream(
                    self.store, self.req_topic, self.res_topic,
                    data_store=self.store,
                    timeout=self.seconds + self.drain_limit + 60.0,
                    result_groups=[CLIENT])
        finally:
            for t in threads:
                t.join()
            consumer.close()
        stats["t_end"] = time.perf_counter()
        if self.errors:
            raise self.errors[0]
        return stats


# ---------------------------------------------------------------------------
# the whole run
# ---------------------------------------------------------------------------
def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _records(win: Window) -> list[dict]:
    out = []
    for rec in win.sent.values():
        r, res = rec["req"], rec["result"]
        out.append({
            "req_id": r.req_id, "prompt": r.prompt, "prompt_len": len(r.prompt),
            "max_new_tokens": r.max_new_tokens, "due": rec["due"],
            "sent": rec["sent"], "arrived": rec["arrived"],
            "in_window": (rec["in_window"] if win.open_loop else
                          rec["arrived"] is not None
                          and win.t_open <= rec["arrived"] <= win.t_close),
            "tokens": res["tokens"] if res else None,
            "n_out": len(res["tokens"]) if res else 0,
            "queued_s": res["queued_s"] if res else None,
            "total_s": res["total_s"] if res else None,
            # from the send until the engine started this request's prefill
            "wait_s": (rec["arrived"] - rec["sent"]
                       - (res["total_s"] - res["queued_s"])) if res else None,
        })
    return out


def end_to_end(recs: list[dict], open_loop: bool, seconds: float) -> dict:
    """The end-to-end readings of one window (host clock)."""
    from chipbench.stats import percentile

    win = [r for r in recs if r["in_window"]]
    out = {}
    if open_loop:
        lat = [r["arrived"] - r["due"] for r in win if r["arrived"] is not None]
        out["latency_p50_s"] = percentile(lat, 50)
        out["latency_p90_s"] = percentile(lat, 90)
    else:
        out["tokens_per_s"] = sum(r["prompt_len"] + r["n_out"]
                                  for r in win) / seconds
    return out


def checks(recs: list[dict], received: list[str], pool_blocks: int,
           vocab: int, gap: float | None, gap_limit: float) -> dict:
    """Every number compared, with its limit; ``correct`` iff each is at or
    under its limit."""
    sent = {r["req_id"] for r in recs}
    counts = Counter(received)
    bad_ids = (sum(counts.get(i, 0) != 1 for i in sent)
               + sum(1 for i in counts if i not in sent))
    bad_out = sum(1 for r in recs if r["tokens"] is not None and (
        len(r["tokens"]) != r["max_new_tokens"]
        or any(not 0 <= t < vocab for t in r["tokens"])))
    return {
        "ids_not_once": {"value": bad_ids, "limit": 0},
        "bad_lengths_or_ids": {"value": bad_out, "limit": 0},
        "kv_blocks_left": {"value": pool_blocks, "limit": 0},
        "widest_gap": {"value": gap, "limit": gap_limit},
    }


def check_sample(recs: list[dict], n: int, seed: int) -> list[dict]:
    """The longest finished request and ``n - 1`` others drawn from the
    seed."""
    done = [r for r in recs if r["tokens"]]
    if not done:
        return []
    longest = max(done, key=lambda r: (r["prompt_len"] + r["n_out"],
                                       r["req_id"]))
    rest = sorted((r for r in done if r is not longest),
                  key=lambda r: r["req_id"])
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 7])
    pick = rng.choice(len(rest), min(n - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def gap_readings(cell: Cell, seed: int, recs: list[dict],
                 control: bool = False) -> dict:
    """Widest gaps over the seed's sample of finished requests: the
    program's served tokens (``"program"``) and, with ``control``, the
    float8 forward's first choices at the same positions (``"control"``);
    an empty dict when nothing finished."""
    sample = check_sample(recs, int(cell.data["check_sequences"]), seed)
    if not sample:
        return {}
    out = reference.served_gaps(
        cell.spec, seed, [(r["prompt"], r["tokens"]) for r in sample],
        length=cell.spec.max_context, control=control)
    read = {"program": max(float(g.max()) for g in out["gaps"]),
            "compared_tokens": int(sum(g.size for g in out["gaps"]))}
    if control:
        read["control"] = max(float(g.max()) for g in out["control_gaps"])
    return read


def verdict(cell: Cell, recs: list[dict], received: list[str],
            pool_blocks: int, gap: float | None) -> tuple[dict, bool]:
    """The numbers compared, each with its limit, and ``correct``: every
    one read and at or under its limit.  ``gap`` is the widest gap of what
    stands in the program's place: its served tokens, or the control's."""
    chk = checks(recs, received, pool_blocks, cell.spec.vocab, gap,
                 float(cell.data["widest_gap_limit"]))
    return chk, all(c["value"] is not None and c["value"] <= c["limit"]
                    for c in chk.values())


def per_layer(cell: Cell, run: dict) -> dict:
    out = {}
    for m in cell.per_layer:
        v = metric_reader(m["name"])(run)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, device: dict, peak: dict) -> dict:
    compiles = CompileLog()
    spec = cell.spec
    dev = jax.devices()[0]
    with ExitStack() as stack:
        store = _new_store(stack)
        engine = build_engine(spec, seed)
        stack.callback(engine.close)
        _log(f"set-up: weights and engine at {time.perf_counter() - t_start:.3f} s")
        warm_up(engine, store, spec, loadgen.prompt_shapes(cell.traffic))
        _log(f"set-up: warm-up done at {time.perf_counter() - t_start:.3f} s")
        trace_dir = (stack.enter_context(tempfile.TemporaryDirectory(
            prefix="chipbench-trace-")) if trace else None)
        win = Window(engine, store, cell, seed, seconds, trace_dir)
        stats = win.run()
        setup_s = win.t_start - t_start
        pool_blocks = engine.kv_pool().stats()["n_blocks"]
        mem = dev.memory_stats() or {}
        summary = None
        if trace:
            xp = trace_reduce.find_xplane(trace_dir)
            summary = trace_reduce.reduce_trace(
                trace_reduce.load_xplane(xp)) if xp else None
        recs = _records(win)
        received = [i for i, _, _ in win.received]
        engine.params = None
        del engine
    gc.collect()
    _log(f"window: {len(recs)} requests sent, {len(received)} answered, "
         f"{stats['decode_steps']} decode steps, serve_stream "
         f"{stats['t_end'] - win.t_open:.3f} s for a {seconds} s window")
    gap = gap_readings(cell, seed, recs).get("program")
    chk, correct = verdict(cell, recs, received, pool_blocks, gap)
    win_recs = [r for r in recs if r["in_window"]]
    if win.open_loop:
        attempted = len(win_recs)
        failed = sum(1 for r in win_recs if r["arrived"] is None)
    else:
        attempted = len(recs)
        failed = sum(1 for r in recs if r["arrived"] is None)
    correct = correct and failed == 0
    units = {m["name"]: m["unit"] for m in cell.end_to_end}
    if trace:
        run = {"spec": spec, "peak": peak, "seconds": seconds,
               "records": recs, "window": win_recs, "stats": stats,
               "call_s": stats["t_end"] - win.t_start,
               "compiles_in_window": compiles.between(win.t_open,
                                                      win.t_close),
               "trace": summary}
        metrics = per_layer(cell, run)
    else:
        e2e = end_to_end(recs, win.open_loop, seconds)
        e2e["setup_s"] = setup_s
        metrics = {k: {"value": v, "unit": units[k]} for k, v in e2e.items()
                   if k in units and v is not None}
    device = dict(device, memory_peak_bytes=mem.get("peak_bytes_in_use"))
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        result["breakdown"] = {"device_ops": summary["device_ops"],
                               "idle_gaps": summary["idle_gaps"]}
    _log(f"set-up {setup_s:.3f} s; compiles in window "
         f"{compiles.between(win.t_open, win.t_close)}")
    for name, c in chk.items():
        _log(f"check {name}: {c['value']} (limit {c['limit']})")
    result["checks"] = chk
    return result
