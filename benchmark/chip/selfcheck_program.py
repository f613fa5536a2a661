#!/usr/bin/env python3
"""Holds the reduction of the program's own names and its five readers
to a recorded trace of the chip; exits non-zero on a mismatch.

    JAX_PLATFORMS=cpu python benchmark/chip/selfcheck_program.py

``recorded/train_b256_scopes_2s.xplane.pb.gz`` is a traced run of
``resnet50_train_b256`` on a TPU v5e (a 2 s window, PR 26) with the
program's spans and scopes in it; what the reduction reads of it is kept
under ``"program"`` in the ``.expected.json`` beside it (the rest of that
file is ``selfcheck.py``'s).  ``--record`` writes that section anew from
what the reduction reads now (look at the trace by hand first).

Checked besides the kept numbers, from the trace alone: one
``mx.train.step`` span for every execution of ``jit_step``, each
enclosing one ``mx.train.step.dispatch``; the device seconds of
``jit_step`` agree with ``xplane.reduce_trace``'s, which reads the same
file through ``jax.profiler.ProfileData``; at least 95% of them lie under
the forward, backward and optimizer scopes, counting the ops jax
named; what has no ``op_name`` at all is the compiler's own (``unnamed``:
its copies and slices between memory spaces) and is reported as such.
"""
import gzip
import json
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import common  # noqa: E402
import program_trace  # noqa: E402
import xplane  # noqa: E402
from selfcheck import FAILS, check  # noqa: E402

TRACE = "train_b256_scopes_2s"
METRICS = ("fwd_device_pct.train", "bwd_device_pct.train",
           "opt_device_pct.train", "bn_device_pct.train",
           "host_step_ms.train")
PHASES = METRICS[:3]


def reductions():
    rec = os.path.join(HERE, "recorded")
    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "t.xplane.pb")
        with gzip.open(os.path.join(rec, TRACE + ".xplane.pb.gz")) as src, \
                open(path, "wb") as dst:
            shutil.copyfileobj(src, dst)
        return program_trace.reduce_program(path), xplane.reduce_trace(path)


def read_metrics(got):
    run = {"program": got, "trace": None, "facts": {}}
    out = {}
    for name in METRICS:
        spec = common.load_json(HERE, "metrics", name + ".json")
        mod, fn = spec["reader"].split(".")
        out[name] = getattr(common.module("readers", mod), fn)(spec, run)
    return out


def summary(got):
    """What is kept of the reduction: counts, totals and the readers'
    values, not the thousands of scope paths."""
    return {"window_s": got["window_s"],
            "span_totals": got["span_totals"],
            "programs": got["programs"],
            "scope_paths": len(got["scopes"]),
            "idle_gaps": got["idle_gaps"],
            "read": read_metrics(got)}


def main():
    got, plain = reductions()
    expected = os.path.join(HERE, "recorded", TRACE + ".expected.json")
    want_all = common.load_json(expected)
    if sys.argv[1:] == ["--record"]:
        want_all["program"] = summary(got)
        with open(expected, "w") as f:
            json.dump(want_all, f, indent=1)
        print(json.dumps(want_all["program"], indent=1))
        return 0
    want, have = want_all["program"], summary(got)
    check("window_s", have["window_s"], want["window_s"])
    check("scope paths", have["scope_paths"], want["scope_paths"])
    for name, row in want["span_totals"].items():
        for what, a, b in zip(("count", "seconds", "self seconds"),
                              have["span_totals"].get(name, (0, 0, 0)), row):
            check("%s %s" % (name, what), a, b)
    for prog, (runs, seconds) in want["programs"].items():
        check("runs of %s" % prog, have["programs"].get(prog, [0])[0], runs)
        check("device seconds of %s" % prog,
              have["programs"].get(prog, [0, 0])[1], seconds)
    for (name, s), (wname, ws) in zip(have["idle_gaps"],
                                      want["idle_gaps"]):
        check("idle gap under %s" % wname, s if name == wname else -1, ws)
    for name, value in want["read"].items():
        check(name, have["read"][name], value)

    # from the trace alone
    steps = have["programs"]["jit_step"][0]
    check("one mx.train.step span per jit_step execution",
          have["span_totals"]["mx.train.step"][0], steps)
    dispatches = [s for s in got["spans"]
                  if s["name"] == "mx.train.step.dispatch"]
    check("each step span encloses its dispatch",
          sum(s["parent"] == "mx.train.step" for s in dispatches), steps)
    plain_seconds = sum(v[1] for k, v in plain["ops"].items()
                        if k.startswith("jit_step|"))
    # ProfileData hands out whole nanoseconds, the file has picoseconds
    check("jit_step device seconds, against xplane.reduce_trace",
          have["programs"]["jit_step"][1], plain_seconds, rel=1e-4)
    step = {"program": "^jit_step$"}
    whole, _ = program_trace.scope_seconds(got, dict(step, scope=""))
    unnamed, _ = program_trace.scope_seconds(got, dict(step,
                                                       scope="^unnamed$"))
    moves, _ = program_trace.scope_seconds(
        got, dict(step, scope="^unnamed$", op="^(copy|slice)(-done)?$"))
    scoped = sum(have["read"][m] for m in PHASES) / 100 * whole
    named = whole - unnamed
    ok = scoped >= 0.95 * named and moves >= 0.99 * unnamed
    print("%s forward + backward + optimizer: %.3f%% of jit_step's device "
          "time and %.3f%% of its named ops'; unnamed %.3f%%, %.3f%% of "
          "that the compiler's copies and slices"
          % ("ok  " if ok else "FAIL", 100 * scoped / whole,
             100 * scoped / named, 100 * unnamed / whole,
             100 * moves / unnamed))
    if not ok:
        FAILS.append("scoped share")
    print("%d mismatch(es)" % len(FAILS))
    return 1 if FAILS else 0


if __name__ == "__main__":
    sys.exit(main())
