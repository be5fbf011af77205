"""Scheduler: time the engine spent in request-stream polls that returned
nothing, per decode step, in ms (``engine.poll_empty`` over
``decode_steps``).  While some rows decode and some are free, each step
polls the stream; a poll that finds nothing waits out its timeout with
the chip idle.  Nothing to read from an engine without spans."""


def read(run):
    s = run["stats"]
    spans = s.get("spans")
    if spans is None or not s["decode_steps"]:
        return None
    idle = spans.get("engine.poll_empty", {}).get("total_s", 0.0)
    return 1e3 * idle / s["decode_steps"]
