#!/usr/bin/env python3
"""mxrace — lockset race analyzer for the host control plane.

Level 1 (default) statically scans the repo with the R9/R10 race rules
(``mxnet_tpu/analysis/race.py``): thread-root discovery, interprocedural
lockset tracking, unguarded cross-thread access and lock-order
inversion, honoring inline suppressions and the ratcheting baseline
``tools/mxrace_baseline.txt``.  Level 2 (``--confirm``) replays a
finding's roots through the vector-clock happens-before harness
(``mxnet_tpu/analysis/racecheck.py``) under seeded forced
interleavings.

Exit code 0 = no unbaselined diagnostics / scenario clean; 1 =
findings (or a confirmed race); 2 = usage error.  ``tools/ci_checks.sh``
runs ``--smoke`` as gate 4: static self-scan + every liveness proof —
strip profiler's ``_rec_lock`` from the real source and the static
scan must flag it; drop ``launch.py``'s ``_relay_lock`` (or the step
lease's, serve scheduler's, or telemetry session's ``_lock``) and the
dynamic harness must flag them — a checker that can no longer see the
seeded bugs fails the gate, exactly like ``mxverify --smoke``.

The static path never imports mxnet_tpu (no jax): the analysis modules
are loaded by file path.  The smoke's relay scenario drives stdlib-only
``tools/launch.py``; its lease_flag scenario imports mxnet_tpu pinned
to the CPU backend (the same trade mxverify makes to execute real
protocol code).
"""
import argparse
import importlib.util
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_BASELINE = os.path.join("tools", "mxrace_baseline.txt")


def _load(name, relpath):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, relpath))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


race = _load("mxrace_race", "mxnet_tpu/analysis/race.py")


def _split_csv(text):
    """Comma-separated list -> clean names ("R9, R10" and "R9,R10"
    parse the same way; empty segments dropped)."""
    return [t.strip() for t in text.split(",") if t.strip()]


def _log(msg):
    print(msg, file=sys.stderr)


def _static_scan(args, ap):
    rules = set(_split_csv(args.rules)) if args.rules else None
    if rules:
        unknown = rules - set(race.RULES)
        if unknown:
            ap.error("unknown rule id(s) %s — known: %s" % (
                ",".join(sorted(unknown)),
                ",".join(sorted(race.RULES))))
    diags = race.scan_paths(ROOT, args.targets or None, rules=rules)
    baseline = {}
    bpath = os.path.join(ROOT, args.baseline)
    if not args.no_baseline and os.path.exists(bpath):
        baseline = race.load_baseline(bpath)
        if rules:
            baseline = {k: v for k, v in baseline.items()
                        if k[0] in rules}
    unbaselined, baselined, stale = race.apply_baseline(diags, baseline)
    for d in unbaselined:
        if args.format == "github":
            print("::error file=%s,line=%d,title=mxrace %s::%s"
                  % (d.path, d.line, d.rule_id, d.message))
        else:
            print(d.format())
    # stale entries FAIL the gate: the code improved, ratchet now —
    # printed individually with the justification so the fix is a
    # one-line edit
    for (rule_id, path), allowed, found in stale:
        why = baseline.get((rule_id, path), (0, ""))[1]
        msg = ("stale baseline entry '%s %s %d -- %s' — the scan "
               "finds only %d; ratchet the count down to %d"
               % (rule_id, path, allowed, why, found, found))
        if args.format == "github":
            print("::error file=%s,title=mxrace baseline::%s"
                  % (args.baseline, msg))
        else:
            _log("mxrace: %s" % msg)
    _log("mxrace: %d diagnostic(s) (%d baselined, %d stale baseline "
         "entr%s)" % (len(unbaselined), len(baselined), len(stale),
                      "y" if len(stale) == 1 else "ies"))
    return bool(unbaselined) or bool(stale)


def _smoke(args):
    """Gate 4's budget (<=15s): the repo self-scan must be clean AND
    every liveness proof must still see its seeded bug — the static
    strip-lock proof plus the dynamic drop-lock proofs (relay,
    lease_flag, serve_sched, telemetry_view, flightrec_ring)."""
    failed = False
    # phase 1: static self-scan against the baseline
    t0 = time.monotonic()
    failed = _static_scan(args, _AP) or failed
    _log("mxrace: self-scan %s (%.1fs)"
         % ("FAILED" if failed else "clean", time.monotonic() - t0))
    # phase 2: static liveness — strip the profiler recorder lock from
    # the REAL source and the R9 scan must flag _state again.  The
    # reduced target set keeps the rescan fast but still spans the
    # files whose thread roots reach the profiler.
    t0 = time.monotonic()
    ppath = os.path.join(ROOT, "mxnet_tpu", "profiler.py")
    with open(ppath, encoding="utf-8") as f:
        stripped = race.strip_locks_source(f.read(), ("_rec_lock",))
    diags = race.scan_paths(
        ROOT, targets=("mxnet_tpu/profiler.py", "mxnet_tpu/fault.py",
                       "mxnet_tpu/fault_dist.py"),
        rules={"R9"},
        override={"mxnet_tpu/profiler.py": stripped})
    hit = [d for d in diags
           if d.rule_id == "R9" and d.path == "mxnet_tpu/profiler.py"
           and "_state" in d.message]
    if hit:
        _log("mxrace: static liveness ok — stripping _rec_lock "
             "re-exposes %d R9 finding(s) on profiler._state (%.1fs)"
             % (len(hit), time.monotonic() - t0))
    else:
        print("mxrace: STATIC LIVENESS FAILURE — _rec_lock stripped "
              "from profiler.py yet R9 stayed silent: the analyzer "
              "has gone blind")
        failed = True
    # phase 3: dynamic liveness — drop launch.py's _relay_lock; the
    # vector-clock harness must confirm the race, and restoring the
    # lock must run clean (stdlib-only scenario: no jax in the gate)
    rc = _load("mxrace_racecheck", "mxnet_tpu/analysis/racecheck.py")
    failed = _drop_lock_liveness(rc, "relay", "drop_relay_lock",
                                 "_relay_lock") or failed
    # phase 4: same proof for the step-lease state (PR 13) — the
    # lease/escalation flag is shared between the step thread and the
    # maintenance-poller/preemption thread; drop the lease's _lock and
    # the harness must flag it, restored it must run clean.  These
    # scenarios import mxnet_tpu (jax, pinned to the CPU backend) —
    # the non-stdlib piece of the gate, same trade mxverify makes.
    failed = _drop_lock_liveness(rc, "lease_flag", "drop_lease_lock",
                                 "StepLease._lock") or failed
    # phase 5: same proof for the mx.serve scheduler (the most
    # thread-heavy host code yet: client submit/cancel threads racing
    # the engine's admit/begin/commit transactions)
    failed = _drop_lock_liveness(rc, "serve_sched", "drop_sched_lock",
                                 "SlotScheduler._lock") or failed
    # phase 6: same proof for the fleet telemetry session (PR 16) —
    # the heartbeat thread's payload/on_beat aggregation shares the
    # session state with the step thread's note_step_time and
    # fleet_view readers
    failed = _drop_lock_liveness(rc, "telemetry_view",
                                 "drop_telemetry_lock",
                                 "TelemetrySession._lock") or failed
    # phase 7: same proof for the flight recorder (PR 18) — every
    # protocol seam's record() shares the ring state with the dump
    # thread's events()/snapshot(); stdlib-only, as cheap as relay
    failed = _drop_lock_liveness(rc, "flightrec_ring",
                                 "drop_flightrec_lock",
                                 "flightrec._lock") or failed
    return failed


def _drop_lock_liveness(rc, scenario, mutation, lock_name):
    """One drop-lock liveness proof: mutated must be racy, restored
    must be clean.  Returns True on failure."""
    t0 = time.monotonic()
    with rc.mutations(mutation):
        rep = rc.confirm(scenario)
    if not rep.racy:
        print("mxrace: DYNAMIC LIVENESS FAILURE — %s dropped yet no "
              "race confirmed: the harness has gone blind" % lock_name)
        return True
    clean = rc.confirm(scenario)
    if clean.racy:
        print("mxrace: DYNAMIC LIVENESS FAILURE — %s scenario races "
              "even WITH %s:\n%s"
              % (scenario, lock_name, clean.summary()))
        return True
    _log("mxrace: dynamic liveness ok — dropped %s confirmed racy "
         "(%d witness(es)), restored lock clean (%.1fs)"
         % (lock_name, len(rep.witnesses), time.monotonic() - t0))
    return False


_AP = None


def main(argv=None):
    global _AP
    ap = argparse.ArgumentParser(
        prog="mxrace", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    _AP = ap
    ap.add_argument("targets", nargs="*",
                    help="repo-relative files/dirs to scan (default: %s)"
                    % " ".join(race.DEFAULT_TARGETS))
    ap.add_argument("--baseline", default=DEFAULT_BASELINE,
                    help="baseline file (default: %(default)s)")
    ap.add_argument("--no-baseline", action="store_true",
                    help="report every diagnostic, baseline ignored")
    ap.add_argument("--rules", default=None,
                    help="comma-separated rule ids to run, e.g. "
                    "'R9, R10' (default: all)")
    ap.add_argument("--format", choices=("text", "github"),
                    default="text",
                    help="diagnostic format: plain text (default) or "
                    "GitHub workflow commands (::error file=...)")
    ap.add_argument("--list-rules", action="store_true",
                    help="print the rule table and exit")
    ap.add_argument("--list-scenarios", action="store_true",
                    help="print the dynamic confirmation scenarios/"
                    "mutations and exit")
    ap.add_argument("--confirm", default=None, metavar="SCENARIO",
                    help="run one dynamic confirmation scenario "
                    "instead of the static scan (exit 1 when the race "
                    "is confirmed)")
    ap.add_argument("--mutate", default=None, metavar="NAME",
                    help="arm a deliberately dropped lock for "
                    "--confirm — exit 1 with witnesses proves the "
                    "harness finds it")
    ap.add_argument("--seeds", default="0,1,2",
                    help="comma-separated interleaving seeds for "
                    "--confirm (default: %(default)s)")
    ap.add_argument("--smoke", action="store_true",
                    help="CI gate budget (<=10s): self-scan + static "
                    "strip-lock liveness + dynamic drop-lock liveness")
    args = ap.parse_args(argv)

    if args.list_rules:
        for r in sorted(race.RULES.values(), key=lambda r: r.rule_id):
            print("%s %-28s %s" % (r.rule_id, r.name, r.invariant))
            print("%s scope: %s" % (" " * 4, ", ".join(r.scope)))
        return 0

    if args.list_scenarios:
        rc = _load("mxrace_racecheck",
                   "mxnet_tpu/analysis/racecheck.py")
        for name in sorted(rc.SCENARIOS):
            s = rc.SCENARIOS[name]
            print("%s — %s" % (name, s.doc))
            print("    confirms: %s" % s.confirms)
        print("mutations: %s" % ", ".join(sorted(rc.KNOWN_MUTATIONS)))
        return 0

    if args.smoke:
        return 1 if _smoke(args) else 0

    if args.confirm:
        rc = _load("mxrace_racecheck",
                   "mxnet_tpu/analysis/racecheck.py")
        if args.confirm not in rc.SCENARIOS:
            ap.error("unknown scenario %r — known: %s"
                     % (args.confirm,
                        ", ".join(sorted(rc.SCENARIOS))))
        if args.mutate and args.mutate not in rc.KNOWN_MUTATIONS:
            ap.error("unknown mutation %r — known: %s"
                     % (args.mutate,
                        ", ".join(sorted(rc.KNOWN_MUTATIONS))))
        try:
            seeds = tuple(int(s) for s in _split_csv(args.seeds))
        except ValueError:
            ap.error("--seeds wants integers, got %r" % args.seeds)
        import contextlib
        armed = rc.mutations(args.mutate) if args.mutate \
            else contextlib.nullcontext()
        with armed:
            rep = rc.confirm(args.confirm, seeds=seeds or (0,))
        print(rep.summary())
        return 1 if rep.racy else 0

    if args.mutate:
        ap.error("--mutate only applies to --confirm/--smoke")

    return 1 if _static_scan(args, ap) else 0


if __name__ == "__main__":
    sys.exit(main())
