"""Spans and counters for the serving loop.

A :class:`Tracer` keeps, for each span name, how many times it ran, its
total time and its self time (its time less what its child spans cover),
plus integer counters.  Each span also opens a
``jax.profiler.TraceAnnotation`` over the same interval: with no profiler
session active that does nothing; under ``jax.profiler.start_trace`` the
span lands in the trace beside the device's operations, on the same
clock, so a trace reader can name the host work around each device gap.

The serving loop is one thread, so a span's parent is whatever span is
open when it starts.
"""
from __future__ import annotations

import time

from jax.profiler import TraceAnnotation


class Span:
    """One open span.  Set ``outcome`` before it closes to count it under
    ``<name>_<outcome>`` instead of ``<name>`` (a poll that found nothing
    is ``engine.poll_empty``)."""

    __slots__ = ("name", "outcome", "_tracer", "_annotation", "_t0",
                 "_child_s", "_parent")

    def __init__(self, tracer: "Tracer", name: str, annotate: bool) -> None:
        self.name = name
        self.outcome: str | None = None
        self._tracer = tracer
        self._annotation = TraceAnnotation(name) if annotate else None
        self._child_s = 0.0

    def __enter__(self) -> "Span":
        stack = self._tracer._stack
        self._parent = stack[-1] if stack else None
        stack.append(self)
        if self._annotation is not None:
            self._annotation.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        dt = time.perf_counter() - self._t0
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
        self._tracer._stack.pop()
        parent = self._parent
        if parent is not None:
            parent._child_s += dt
        key = self.name if self.outcome is None \
            else f"{self.name}_{self.outcome}"
        rec = self._tracer._spans.get(key)
        if rec is None:
            rec = self._tracer._spans[key] = {
                "n": 0, "total_s": 0.0, "self_s": 0.0,
                "parent": parent.name if parent is not None else None}
        rec["n"] += 1
        rec["total_s"] += dt
        rec["self_s"] += dt - self._child_s


class Tracer:
    """Per-name span sums and counters, kept in memory."""

    def __init__(self) -> None:
        self._spans: dict[str, dict] = {}
        self._counters: dict[str, int] = {}
        self._stack: list[Span] = []

    def span(self, name: str, *, annotate: bool = True) -> Span:
        """A context manager timing one span; ``annotate=False`` keeps it
        out of the profiler trace (a span that outlives a trace window
        would never be written to it)."""
        return Span(self, name, annotate)

    def count(self, name: str, n: int = 1) -> None:
        self._counters[name] = self._counters.get(name, 0) + n

    def total_s(self, name: str) -> float:
        rec = self._spans.get(name)
        return rec["total_s"] if rec is not None else 0.0

    def summary(self) -> dict:
        """``{"spans": {name: {"n", "total_s", "self_s", "parent"}},
        "counters": {name: n}}`` over every span closed so far."""
        return {"spans": {k: dict(v) for k, v in self._spans.items()},
                "counters": dict(self._counters)}
