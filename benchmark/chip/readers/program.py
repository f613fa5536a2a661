"""Readers of the program's own names: ``run["program"]`` is the
reduction of ``program_trace.reduce_program`` (the ``mx.*`` host spans
and the device's ops by named-scope path).  A run that carries none —
the harness does not hand it over yet, or the program under test has no
such span or scope, as before PR 26 — reads as None."""
import common
import program_trace


def scope_device_pct(metric, run):
    """Device time of the ops under the scopes ``metric["scope_match"]`` names,
    as a share of all device time of the program it names."""
    t = run.get("program")
    if not t:
        return None
    rule = metric["scope_match"]
    seconds, n = program_trace.scope_seconds(t, rule)
    whole, _ = program_trace.scope_seconds(
        t, {"program": rule["program"], "scope": ""})
    if not n or not whole:
        return None
    return 100.0 * seconds / whole


def span_self_median_ms(metric, run):
    """Median self time of the host span ``metric["span"]``: its duration
    less what its child spans cover."""
    t = run.get("program")
    if not t:
        return None
    selfs = [s["self_s"] for s in t["spans"] if s["name"] == metric["span"]]
    return 1e3 * common.median(selfs) if selfs else None
