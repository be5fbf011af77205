"""KV paging: share of the serve loop's time spent moving KV to and from
the block pool, in %: admission's host copy of the prefill K/V
(``engine.admit.kv_to_host``), paging it into blocks (``kv.put_prefill``),
decoded blocks paged out (``engine.pageout``) and blocks released
(``kv.release``), over ``engine.serve``, all total times.  Nothing to
read from an engine without spans."""

PAGING = ("engine.admit.kv_to_host", "kv.put_prefill", "engine.pageout",
          "kv.release")


def read(run):
    spans = run["stats"].get("spans", {})
    serve = spans.get("engine.serve")
    if serve is None or not serve["total_s"]:
        return None
    paging = sum(spans.get(n, {}).get("total_s", 0.0) for n in PAGING)
    return 100.0 * paging / serve["total_s"]
