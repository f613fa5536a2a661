"""Readers for the sparse-attention MoE decoder's cell:
``run["facts"]["program"]`` is the reduction of
``program_trace.reduce_program`` that ``drivers/train_dsa_moe.py`` makes
of its traced run, ``run["facts"]["held_pairs"]`` the routed pairs of
each step of its window.  A run that carries none, or a program with no
such scope or kernel (the parent of the PR that brought it), reads as
None."""
import re

import program_trace


def _steps(t, rule):
    return sum(count for name, (count, _) in t["programs"].items()
               if re.search(rule["program"], name))


def scope_roofline(metric, run):
    """The least time the chip could take for a step's work of the
    count ``metric["work"]`` names, in every step the trace holds, over
    the device time of the ops the metric's rule matches."""
    t = run["facts"].get("program")
    if not t or not run["peaks"]:
        return None
    rule = metric["scope_match"]
    seconds, n = program_trace.scope_seconds(t, rule)
    steps = _steps(t, rule)
    if not n or not steps or seconds <= 0:
        return None
    work = getattr(run["counts"], metric["work"])(run["model"]) * steps
    return 100.0 * work / run["peaks"][metric["bound"]] / seconds


def experts_roofline(metric, run):
    """The held experts' products for the pairs routed to them (the
    mean over the window's steps, ``facts["held_pairs"]``) in every step
    the trace holds, over the device time of the grouped matmuls."""
    t = run["facts"].get("program")
    pairs = run["facts"].get("held_pairs")
    if not t or not pairs or not run["peaks"]:
        return None
    rule = metric["scope_match"]
    seconds, n = program_trace.scope_seconds(t, rule)
    steps = _steps(t, rule)
    if not n or not steps or seconds <= 0:
        return None
    work = run["counts"].experts_flops(run["model"], sum(pairs) / len(pairs))
    return 100.0 * work * steps / run["peaks"][metric["bound"]] / seconds
