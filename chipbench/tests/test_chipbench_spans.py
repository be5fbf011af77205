"""The per-layer metrics that read the engine's spans and counters: a
tiny traced run reads each of them, an engine without spans reads
nothing without raising, and the engine's spans reach a profiler trace
where the trace reader finds them."""
import threading
import time
import uuid

import jax
import numpy as np
import pytest

from chipbench import harness, peaks, trace_reduce
from chipbench.spec import metric_reader
from chipbench.tests.conftest import tiny_cell
from repro.configs import ARCHS
from repro.core import Store
from repro.core.connectors import SharedMemoryConnector
from repro.core.store import unregister_store
from repro.serve.engine import ServeEngine

SEED = 2**31 + 12345
SPAN_METRICS = ("poll_idle_ms.chat", "padded_rows_share.chat",
                "scheduler_self_ms.chat", "kv_paging_share.chat",
                "request_plane_ms.chat")
SHARES = ("padded_rows_share.chat", "kv_paging_share.chat")


def test_traced_run_reads_every_span_metric():
    res = harness.run_cell(tiny_cell("phi4-chat"), SEED, 2.0, True,
                           time.perf_counter(),
                           {"platform": "cpu", "kind": "cpu", "count": 1},
                           peaks.PEAKS["TPU v5 lite"])
    assert res["correct"], res["checks"]
    m = res["metrics"]
    for name in SPAN_METRICS:
        assert m[name]["value"] is not None, name
        assert m[name]["value"] >= 0.0, name
    for name in SHARES:
        assert 0.0 <= m[name]["value"] <= 100.0, name
    # requests come one or two to a group of max_batch rows
    assert m["padded_rows_share.chat"]["value"] > 0.0
    assert m["kv_paging_share.chat"]["value"] > 0.0
    assert m["request_plane_ms.chat"]["value"] > 0.0


@pytest.mark.parametrize("name", SPAN_METRICS)
def test_an_engine_without_spans_reads_nothing(name):
    stats = {"prefill_s": 1.0, "decode_s": 2.0, "decode_steps": 10,
             "completed": 3}
    assert metric_reader(name)({"stats": stats}) is None


def test_engine_spans_reach_the_profiler_trace(tmp_path):
    """A tiny serve_stream under a profiler session: the engine's spans
    are host events on the ``/host:CPU`` plane, where the benchmark's
    trace reader finds them by name; ``engine.serve`` stays out of it."""
    cfg = ARCHS["qwen2.5-14b"].reduced().replace(dtype="float32",
                                                 n_layers=2)
    engine = ServeEngine(cfg, max_batch=4, max_context=64, block_tokens=8)
    name = f"trace-test-{uuid.uuid4().hex[:8]}"
    store = Store(name, SharedMemoryConnector(str(tmp_path / "shm")))
    rng = np.random.default_rng(3)
    try:
        def feed():
            prod = store.stream_producer("req")
            for i, n in enumerate((5, 9, 5)):
                prod.append(store.proxy(
                    {"prompt": rng.integers(1, cfg.vocab, n).tolist(),
                     "max_new_tokens": 10, "req_id": f"t{i}"}, evict=True))
            prod.close()

        jax.profiler.start_trace(str(tmp_path / "trace"))
        try:
            t = threading.Thread(target=feed)
            t.start()
            stats = engine.serve_stream(store, "req", "res", data_store=store,
                                        timeout=30.0)
            t.join(timeout=30.0)
            assert not t.is_alive()
        finally:
            jax.profiler.stop_trace()
    finally:
        engine.close()
        store.close()
        unregister_store(name)
    assert stats["completed"] == 3
    planes = trace_reduce.load_xplane(
        trace_reduce.find_xplane(tmp_path / "trace"))
    host = [ev for p in planes if p["name"].startswith(trace_reduce.HOST_PLANE)
            for ln in p["lines"] for ev, _, _ in ln["events"]]
    names = set(host)
    for span in ("engine.decode", "engine.admit", "engine.poll",
                 "engine.admit.prefill", "engine.pageout", "result.publish"):
        assert span in names, span
    assert "engine.serve" not in names
    assert host.count("engine.decode") == stats["decode_steps"]
