"""Readers for EVA attention: ``run["facts"]["program"]`` is the
reduction of ``program_trace.reduce_program`` that
``drivers/train_bytes.py`` makes of its traced run.  A run that carries
none, or a program with no such scope (the parent of the PR that brought
it), reads as None."""
import re

import program_trace


def attn_roofline(metric, run):
    """The least time the chip could take for the attention's products
    over the visible pairs (``counts.eva_attention_flops``, a step) in
    every step the trace holds, over the device time of every op under
    the attention's scope: kernels, pooling and merge, forward and
    backward."""
    t = run["facts"].get("program")
    if not t or not run["peaks"]:
        return None
    seconds, n = program_trace.scope_seconds(t, metric["scope_match"])
    steps = sum(count for name, (count, _) in t["programs"].items()
                if re.search(metric["scope_match"]["program"], name))
    if not n or not steps or seconds <= 0:
        return None
    work = run["counts"].eva_attention_flops(run["model"]) * steps
    return 100.0 * work / run["peaks"][metric["bound"]] / seconds
