"""Readers of the serving engine's own counters: the request records and
the logged warm-pool calls."""


def queue_wait_p95_ms(metric, run):
    return run["facts"].get("queue_wait_p95_ms")


def batch_occupancy_pct(metric, run):
    f = run["facts"]
    calls = [c for c in (run["trace"] or {}).get("calls", ())
             if c["kind"] == "decode" and f["w0"] <= c["t"] < f["w1"]]
    if not calls:
        return None
    slots = run["model"]["replica"]["slots"]
    return 100.0 * sum(c["active"] for c in calls) / (slots * len(calls))
