"""Scheduler: the serve loop's own time per decode step, in ms: the
``engine.serve`` span less what its child spans (poll, admission,
decode, page-out, retirement, heartbeat) cover, over ``decode_steps``.
Nothing to read from an engine without spans."""


def read(run):
    s = run["stats"]
    serve = s.get("spans", {}).get("engine.serve")
    if serve is None or not s["decode_steps"]:
        return None
    return 1e3 * serve["self_s"] / s["decode_steps"]
