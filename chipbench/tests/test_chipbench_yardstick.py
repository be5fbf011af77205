"""Operation counts, peaks and the trace reduction, on hand-made inputs."""
import pytest

from chipbench import flops, peaks, trace_reduce
from chipbench.arch import dense
from chipbench.arch.dense import ModelSpec

SPEC = ModelSpec(name="hand", n_layers=2, d_model=8, n_heads=4, n_kv_heads=2,
                 head_dim=2, d_ff=16, vocab=10, qkv_bias=False,
                 tie_embeddings=True, rope_theta=1e4, norm_eps=1e-5,
                 dtype="bfloat16", vocab_multiple=4, max_batch=2,
                 max_context=32, block_tokens=4)


def test_layer_weights_by_hand():
    # q: 8x8, k and v: 8x4 each, o: 8x8, MLP: 3 x 8x16
    assert dense.layer_weights(SPEC) == 64 + 32 + 32 + 64 + 384


def test_decode_step_by_hand():
    # one token at context 10: per layer 2*576 (weights) + 4*4*2*10 (attn)
    per_layer = 2 * 576 + 4 * 4 * 2 * 10
    assert flops.token_flops(SPEC, 10) == 2 * per_layer
    # tokens 2 and 3 of a 7-token prompt: contexts 8 and 9, plus 2 heads
    want = (2 * (2 * 576 + 32 * 8) + 2 * 8 * 10
            + 2 * (2 * 576 + 32 * 9) + 2 * 8 * 10)
    assert flops.decode_flops(SPEC, 7, 3) == want


def test_prefill_by_hand():
    # 3 tokens attend to 1, 2 and 3 positions: 6 score/value pairs
    want = 2 * (2 * 576 * 3 + 4 * 4 * 2 * 6) + 2 * 8 * 10
    assert flops.prefill_flops(SPEC, 3) == want
    assert flops.request_flops(SPEC, 3, 1) == want


def test_unknown_device_kind_is_an_error():
    assert peaks.peak("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(peaks.UnknownDevice):
        peaks.peak("cpu")


def _trace(device_events, host_events, window=(0, 100)):
    w0, w1 = window
    return [
        {"name": "/host:CPU", "lines": [
            {"name": "python", "events": [(trace_reduce.WINDOW, w0, w1 - w0)]
             + host_events}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [("jit_step", 0, 1000)]},
            {"name": trace_reduce.OP_LINE, "events": device_events}]},
    ]


def test_busy_union_self_times_and_gap_attribution():
    ns = 1e6    # events in ms, as ns
    dev = [("%while.3 = (s32[], bf16[16,640,3072])", 10 * ns, 30 * ns),
           ("fusion.1", 12 * ns, 8 * ns), ("fusion.2", 20 * ns, 18 * ns),
           ("dot.3", 60 * ns, 10 * ns), ("late", 95 * ns, 20 * ns)]
    host = [("engine.flush_blocks", 40 * ns, 15 * ns),
            ("serve_stream", 0, 100 * ns),
            ("client.send", 71 * ns, 2 * ns)]
    out = trace_reduce.reduce_trace(_trace(dev, host, (0, 100 * ns)))
    # busy: [10, 40] + [60, 70] + [95, 100] (clipped to the window)
    assert out["busy_s"] == pytest.approx(45e-3)
    assert out["window_s"] == pytest.approx(0.1)
    ops = dict(out["device_ops"])
    assert ops["fusion.2"] == pytest.approx(0.018)
    assert ops["fusion.1"] == pytest.approx(0.008)
    # the loop's own time, less its body ops
    assert ops["%while.3 = (s32[], bf16[16,640,3072])"] == pytest.approx(0.004)
    assert out["device_ops"][0][0] == "late"       # 20 ms, starts in window
    gaps = dict(out["idle_gaps"])
    # each stretch of idle time goes to the innermost span over it
    assert gaps["engine.flush_blocks"] == pytest.approx(0.015)
    assert gaps["client.send"] == pytest.approx(0.002)
    assert gaps["serve_stream"] == pytest.approx(0.010 + 0.005 + 0.023)


def test_no_window_or_no_device_reads_nothing():
    assert trace_reduce.reduce_trace([{"name": "/host:CPU", "lines": []}]) \
        is None
    assert trace_reduce.reduce_trace(_trace([], [])) is None
