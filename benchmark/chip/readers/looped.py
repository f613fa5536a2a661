"""Readers of the program's own names for the looped decoder's cell:
``run["facts"]["program"]`` is the reduction of
``program_trace.reduce_program`` that ``drivers/train_tokens.py`` makes of
its traced run.  A run that carries none — another driver's, or a program
that has no such scope or kernel — reads as None."""
import program_trace


def scope_device_pct(metric, run):
    """Device time of the ops whose scope path ``metric["scope_match"]``
    names, as a share of all device time of the program it names.  The
    rule's ``op`` leaves out the ops that only hold others (a ``while``
    lasts as long as everything in its body, which the trace lists
    too), on both sides of the share."""
    t = run["facts"].get("program")
    if not t:
        return None
    rule = metric["scope_match"]
    seconds, n = program_trace.scope_seconds(t, rule)
    whole, _ = program_trace.scope_seconds(t, dict(rule, scope=""))
    if not n or not whole:
        return None
    return 100.0 * seconds / whole


def flash_train_roofline(metric, run):
    """The least time the chip could take for the causal FLOPs of the
    flash kernels, each counted as often as the trace holds it (the
    forward runs again where a block is recomputed), over those kernels'
    device time."""
    t = run["facts"].get("program")
    if not t or not run["peaks"]:
        return None
    calls, seconds = {}, 0.0
    for kernel, scope in metric["kernels"].items():
        s, n = program_trace.scope_seconds(
            t, {"program": metric["program"], "scope": scope,
                "op": metric["op"]})
        if n:
            calls[kernel] = n
            seconds += s
    if not calls or seconds <= 0:
        return None
    work = run["counts"].flash_train_flops(run["model"], calls)
    return 100.0 * work / run["peaks"][metric["bound"]] / seconds
