"""The serving loop's tracer: span nesting and self time, counters, and
the grouping of a span by its outcome."""
import time

import pytest

from repro.serve.tracing import Tracer


def _sleep_until(t):
    while time.perf_counter() < t:
        pass


def test_nested_spans_split_self_time_from_children():
    tr = Tracer()
    with tr.span("outer", annotate=False):
        _sleep_until(time.perf_counter() + 0.002)
        for _ in range(3):
            with tr.span("inner"):
                _sleep_until(time.perf_counter() + 0.003)
                with tr.span("leaf"):
                    _sleep_until(time.perf_counter() + 0.001)
    s = tr.summary()["spans"]
    assert s["outer"]["n"] == 1 and s["inner"]["n"] == 3 \
        and s["leaf"]["n"] == 3
    assert s["outer"]["parent"] is None
    assert s["inner"]["parent"] == "outer" and s["leaf"]["parent"] == "inner"
    # self time is the span's time less what its children cover
    assert s["outer"]["self_s"] == pytest.approx(
        s["outer"]["total_s"] - s["inner"]["total_s"], abs=1e-9)
    assert s["inner"]["self_s"] == pytest.approx(
        s["inner"]["total_s"] - s["leaf"]["total_s"], abs=1e-9)
    assert s["leaf"]["self_s"] == s["leaf"]["total_s"]
    assert s["outer"]["self_s"] >= 0.002
    assert s["inner"]["self_s"] >= 0.009
    assert s["leaf"]["total_s"] >= 0.003
    assert s["outer"]["total_s"] >= 0.014


def test_counters_add_and_summary_is_a_copy():
    tr = Tracer()
    tr.count("rows_admitted")
    tr.count("rows_admitted")
    tr.count("prefill_rows", 16)
    out = tr.summary()
    assert out["counters"] == {"rows_admitted": 2, "prefill_rows": 16}
    assert out["spans"] == {}
    out["counters"]["rows_admitted"] = 99
    assert tr.summary()["counters"]["rows_admitted"] == 2
    assert tr.total_s("never") == 0.0


def test_outcome_groups_a_span_apart_and_still_counts_for_its_parent():
    tr = Tracer()
    with tr.span("engine.serve", annotate=False):
        for found in (True, False, False, True, False):
            with tr.span("engine.poll") as poll:
                if found:
                    with tr.span("request.resolve"):
                        pass
                else:
                    poll.outcome = "empty"
    s = tr.summary()["spans"]
    assert s["engine.poll"]["n"] == 2
    assert s["engine.poll_empty"]["n"] == 3
    assert s["engine.poll_empty"]["parent"] == "engine.serve"
    assert s["request.resolve"]["n"] == 2
    assert s["request.resolve"]["parent"] == "engine.poll"
    polls = s["engine.poll"]["total_s"] + s["engine.poll_empty"]["total_s"]
    assert s["engine.serve"]["self_s"] == pytest.approx(
        s["engine.serve"]["total_s"] - polls, abs=1e-9)


def test_a_span_closes_on_an_exception():
    tr = Tracer()
    with pytest.raises(ValueError):
        with tr.span("outer", annotate=False):
            with tr.span("inner"):
                raise ValueError("boom")
    s = tr.summary()["spans"]
    assert s["inner"]["n"] == 1 and s["outer"]["n"] == 1
    with tr.span("after"):
        pass
    assert tr.summary()["spans"]["after"]["parent"] is None
