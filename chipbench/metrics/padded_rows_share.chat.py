"""Scheduler: share of the prefilled rows that held no request, in %.
Each admission group prefills ``max_batch`` rows (``prefill_rows``) for
the requests it admits (``rows_admitted``).  Nothing to read from an
engine without these counters."""


def read(run):
    c = run["stats"].get("counters")
    if c is None or not c.get("prefill_rows"):
        return None
    return 100.0 * (1.0 - c["rows_admitted"] / c["prefill_rows"])
