"""Request plane: the engine's time on the proxy store and streams per
completed request, in ms: resolving each request proxy and evicting its
slot (``request.resolve``) plus publishing each result (proxy put and
stream append, ``result.publish``), over ``completed``.  Nothing to read
from an engine without spans."""


def read(run):
    s = run["stats"]
    spans = s.get("spans")
    if spans is None or not s["completed"]:
        return None
    t = sum(spans.get(n, {}).get("total_s", 0.0)
            for n in ("request.resolve", "result.publish"))
    return 1e3 * t / s["completed"]
