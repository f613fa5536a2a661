"""mxrace (``mx.analysis.race`` / ``mx.analysis.racecheck``) — the race
rules must actually fire, and the checker must be provably alive.

Per rule R9/R10: known-violation snippets and clean counterexamples,
scanned under a virtual repo path so scoping is exercised too (mirrors
tests/test_mxlint.py).  Plus: suppression-justification enforcement,
baseline ratchet semantics, the dynamic vector-clock confirmation
roundtrip on a seeded race (drop a real lock -> flagged; restore ->
clean), the static strip-lock liveness proof, the self-scan (repo
clean modulo the checked-in baseline), and regression tests for the
real findings this PR fixed (the unlocked ``profiler.counter_bump``
read-modify-write, the lazy ``fault_dist.generation()`` singleton, the
unguarded ``fault._preempt_handler`` swap).
"""
import os
import subprocess
import sys
import threading

import pytest

from mxnet_tpu.analysis import race
from mxnet_tpu.analysis import racecheck as rc

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _ids(src, relpath, rules=None):
    return [d.rule_id
            for d in race.race_source(src, relpath, rules=rules)]


# ----------------------------------------------------------------------
# R9 — unguarded cross-thread access
# ----------------------------------------------------------------------
R9_BAD = """
import threading

_counts = {}

def _worker():
    _counts["n"] = _counts.get("n", 0) + 1

def start():
    threading.Thread(target=_worker).start()
    _counts["n"] = _counts.get("n", 0) + 1
"""

R9_CLEAN = """
import threading

_lock = threading.Lock()
_counts = {}

def _worker():
    with _lock:
        _counts["n"] = _counts.get("n", 0) + 1

def start():
    threading.Thread(target=_worker).start()
    with _lock:
        _counts["n"] = _counts.get("n", 0) + 1
"""

R9_READONLY = """
import threading

_config = {"poll": 0.1}

def _worker():
    return _config["poll"]

def start():
    threading.Thread(target=_worker).start()
    return _config["poll"]
"""

R9_SINGLE_ROOT = """
import threading

_counts = {}

def bump():
    _counts["n"] = _counts.get("n", 0) + 1

def probe():
    return threading.get_ident(), _counts.get("n")
"""

R9_SAFE_TYPE = """
import threading

_stop = threading.Event()

def _worker():
    _stop.set()

def start():
    threading.Thread(target=_worker).start()
    return _stop.is_set()
"""


def test_r9_fires_on_unguarded_cross_thread_write():
    assert _ids(R9_BAD, "mxnet_tpu/fx.py") == ["R9"]


def test_r9_clean_when_both_sides_hold_the_lock():
    assert _ids(R9_CLEAN, "mxnet_tpu/fx.py") == []


def test_r9_read_only_sharing_is_not_a_race():
    assert _ids(R9_READONLY, "mxnet_tpu/fx.py") == []


def test_r9_single_root_state_is_not_shared():
    # no thread is ever spawned: main-only mutation is not R9's business
    assert _ids(R9_SINGLE_ROOT, "mxnet_tpu/fx.py") == []


def test_r9_thread_safe_types_are_exempt():
    assert _ids(R9_SAFE_TYPE, "mxnet_tpu/fx.py") == []


def test_r9_scoped_to_control_plane_paths():
    # the same source under tests/ (or analysis/) is out of scope
    assert _ids(R9_BAD, "tests/fx.py") == []
    assert _ids(R9_BAD, "mxnet_tpu/analysis/fx.py") == []


R9_ATTR_BAD = """
import threading

class Poller:
    def __init__(self):
        self.events = 0
        self._thread = None

    def _loop(self):
        self.events = self.events + 1

    def start(self):
        self._thread = threading.Thread(target=self._loop)
        self._thread.start()

    def snapshot(self):
        return self.events
"""

R9_ATTR_CLEAN = """
import threading

class Poller:
    def __init__(self):
        self.events = 0
        self._lock = threading.Lock()
        self._thread = None

    def _loop(self):
        with self._lock:
            self.events = self.events + 1

    def start(self):
        self._thread = threading.Thread(target=self._loop)
        self._thread.start()

    def snapshot(self):
        with self._lock:
            return self.events
"""


def test_r9_tracks_self_attributes():
    assert _ids(R9_ATTR_BAD, "mxnet_tpu/fx.py") == ["R9"]
    assert _ids(R9_ATTR_CLEAN, "mxnet_tpu/fx.py") == []


R9_MULTI = """
import threading

_total = {}

def _worker(i):
    _total[i] = _total.get(i, 0) + 1

def start_all():
    for i in range(4):
        threading.Thread(target=_worker, args=(i,)).start()
"""


def test_r9_multi_instance_root_races_itself():
    # a root spawned in a loop runs concurrently with its own siblings
    diags = race.race_source(R9_MULTI, "mxnet_tpu/fx.py")
    assert [d.rule_id for d in diags] == ["R9"]
    assert "multi-instance" in diags[0].message


R9_TRYLOCK = """
import threading

_lock = threading.Lock()
_state = {}

def _worker():
    with _lock:
        _state["n"] = 1

def fire():
    if not _lock.acquire(blocking=False):
        return None
    try:
        _state["n"] = 2
    finally:
        _lock.release()

def start():
    threading.Thread(target=_worker).start()
    fire()
"""


def test_r9_understands_the_trylock_idiom():
    # `if not lock.acquire(blocking=False): return` holds the lock on
    # the fall-through path (the PreemptionHandler.fire shape)
    assert _ids(R9_TRYLOCK, "mxnet_tpu/fx.py") == []


R9_CONDITION = """
import threading

class Runner:
    def __init__(self):
        self._cond = threading.Condition(threading.Lock())
        self.state = 0

    def _loop(self):
        with self._cond:
            self.state = self.state + 1
            self._cond.notify_all()

    def start(self):
        threading.Thread(target=self._loop).start()

    def wait_done(self):
        with self._cond:
            return self.state
"""

R9_ACQUIRE_RELEASE = """
import threading

_l = threading.Lock()
_n = {}

def _worker():
    _l.acquire()
    _n["x"] = 1
    _l.release()

def start():
    threading.Thread(target=_worker).start()
    with _l:
        _n["x"] = 2
"""


def test_r9_condition_embeds_a_lock():
    assert _ids(R9_CONDITION, "mxnet_tpu/fx.py") == []


def test_r9_acquire_release_pair_holds_the_lock():
    assert _ids(R9_ACQUIRE_RELEASE, "mxnet_tpu/fx.py") == []


R9_RELEASE_IN_FINALLY = """
import threading

_l = threading.Lock()
_shared = {}

def _worker():
    with _l:
        _shared["n"] = 1

def start():
    threading.Thread(target=_worker).start()
    _l.acquire()
    try:
        _shared["n"] = 2
    finally:
        _l.release()
    _shared["n"] = 3
"""


def test_r9_release_in_finally_ends_the_held_region():
    """The canonical acquire();try:...finally:release() shape: the
    guarded write is clean, but the write AFTER the try must be seen
    unguarded — a release inside the finally ends the region."""
    diags = race.race_source(R9_RELEASE_IN_FINALLY, "mxnet_tpu/fx.py")
    assert [d.rule_id for d in diags] == ["R9"]


def test_r9_sees_across_modules():
    """The load-bearing property: the thread spawned in one file must
    be seen touching the global living in another (how the real
    profiler._state finding was caught from fault_dist's poller)."""
    prog = race.Program()
    race._add_module(
        prog, "mxnet_tpu/store.py",
        "import threading\n_db = {}\n\n"
        "def put(k, v):\n    _db[k] = v\n")
    race._add_module(
        prog, "mxnet_tpu/driver.py",
        "import threading\nfrom . import store as _store\n\n"
        "def _worker():\n    _store.put('a', 1)\n\n"
        "def start():\n"
        "    threading.Thread(target=_worker).start()\n"
        "    _store.put('b', 2)\n")
    race._finalize_program(prog)
    diags = race.scan_program(prog)
    assert [d.rule_id for d in diags] == ["R9"]
    assert "mxnet_tpu.store._db" in diags[0].message


# ----------------------------------------------------------------------
# R10 — lock-order inversion
# ----------------------------------------------------------------------
R10_BAD = """
import threading

_a = threading.Lock()
_b = threading.Lock()

def worker():
    with _a:
        with _b:
            pass

def main_path():
    with _b:
        with _a:
            pass

def boot():
    threading.Thread(target=worker).start()
    main_path()
"""

R10_CLEAN = """
import threading

_a = threading.Lock()
_b = threading.Lock()

def worker():
    with _a:
        with _b:
            pass

def main_path():
    with _a:
        with _b:
            pass

def boot():
    threading.Thread(target=worker).start()
    main_path()
"""

R10_SINGLE_THREAD = """
import threading

_a = threading.Lock()
_b = threading.Lock()

def a_then_b():
    with _a:
        with _b:
            pass

def b_then_a():
    with _b:
        with _a:
            pass
"""


def test_r10_fires_on_opposite_orders_across_roots():
    diags = race.race_source(R10_BAD, "mxnet_tpu/fx.py")
    assert [d.rule_id for d in diags] == ["R10"]
    assert "opposite order" in diags[0].message


def test_r10_clean_on_consistent_order():
    assert _ids(R10_CLEAN, "mxnet_tpu/fx.py") == []


def test_r10_needs_two_roots():
    # both orders exist but only the main thread ever runs them — a
    # single thread cannot ABBA-deadlock itself
    assert _ids(R10_SINGLE_THREAD, "mxnet_tpu/fx.py") == []


# ----------------------------------------------------------------------
# suppressions + baseline (shared vocabulary with mxlint)
# ----------------------------------------------------------------------
R9_SUPPRESSED = """
import threading

_flag = {}

def _worker():
    # mxlint: disable=R9 -- intentionally torn test flag; the reader
    # tolerates staleness by design
    _flag["x"] = 1

def start():
    threading.Thread(target=_worker).start()
    return _flag.get("x")
"""

R9_BARE_SUPPRESS = """
import threading

_flag = {}

def _worker():
    # mxlint: disable=R9
    _flag["x"] = 1

def start():
    threading.Thread(target=_worker).start()
    return _flag.get("x")
"""


def test_suppression_with_justification_is_honored():
    assert _ids(R9_SUPPRESSED, "mxnet_tpu/fx.py") == []


def test_bare_suppression_is_flagged():
    # a bare disable=R9 suppresses but is itself a finding — race
    # suppressions cannot rot into unexplained noise
    assert _ids(R9_BARE_SUPPRESS, "mxnet_tpu/fx.py") == ["MX901"]


def test_baseline_machinery_is_shared_with_mxlint():
    diags = [race.Diagnostic("R9", "mxnet_tpu/fx.py", i, "m")
             for i in (1, 2, 3)]
    baseline = {("R9", "mxnet_tpu/fx.py"): (2, "known"),
                ("R10", "gone.py"): (1, "stale")}
    un, kept, stale = race.apply_baseline(diags, baseline)
    assert [d.line for d in un] == [3]
    assert len(kept) == 2
    assert stale == [(("R10", "gone.py"), 1, 0)]


# ----------------------------------------------------------------------
# self-scan + liveness (the gate)
# ----------------------------------------------------------------------
def test_self_scan_repo_clean_modulo_baseline():
    """THE gate: the repo's own control plane carries zero unbaselined
    race diagnostics, and no baseline entry is stale — the ratchet."""
    diags = race.scan_paths(ROOT)
    baseline = race.load_baseline(
        os.path.join(ROOT, "tools", "mxrace_baseline.txt"))
    un, kept, stale = race.apply_baseline(diags, baseline)
    assert not un, "unbaselined race diagnostics:\n%s" % "\n".join(
        d.format() for d in un)
    assert not stale, ("stale baseline entries — the code improved, "
                       "ratchet the baseline down: %s" % stale)
    assert kept, "baseline lists entries the scan no longer produces"


def test_strip_lock_static_liveness():
    """Stripping profiler's _rec_lock from the REAL source must
    re-expose the R9 on _state — the analyzer still sees the bug class
    it was built for."""
    with open(os.path.join(ROOT, "mxnet_tpu", "profiler.py"),
              encoding="utf-8") as f:
        text = f.read()
    stripped = race.strip_locks_source(text, ("_rec_lock",))
    assert "with _rec_lock:" not in stripped
    diags = race.scan_paths(
        ROOT,
        targets=("mxnet_tpu/profiler.py", "mxnet_tpu/fault.py",
                 "mxnet_tpu/fault_dist.py"),
        rules={"R9"},
        override={"mxnet_tpu/profiler.py": stripped})
    hits = [d for d in diags
            if d.rule_id == "R9" and d.path == "mxnet_tpu/profiler.py"
            and "_state" in d.message]
    assert hits, "analyzer went blind: stripped lock not flagged"


def test_strip_lock_refuses_vacuous_proof():
    with pytest.raises(ValueError):
        race.strip_locks_source("x = 1\n", ("_rec_lock",))


def test_every_rule_is_live():
    assert set(race.RULES) == {"R9", "R10"}
    for r in race.RULES.values():
        assert r.invariant and r.scope


# ----------------------------------------------------------------------
# dynamic confirmation (vector-clock happens-before harness)
# ----------------------------------------------------------------------
def test_relay_scenario_clean_with_real_lock():
    rep = rc.confirm("relay")
    assert not rep.racy, "\n".join(w.format() for w in rep.witnesses)
    assert rep.info["lines_moved"] == 40


def test_relay_scenario_flags_dropped_lock():
    """The seeded-mutation liveness proof: drop launch.py's
    _relay_lock and the harness must confirm the PR-5 torn-stdout
    race, with witnesses naming the real _relay write sites."""
    with rc.mutations("drop_relay_lock"):
        rep = rc.confirm("relay")
    assert rep.racy, "harness went blind: dropped lock not flagged"
    assert rep.witnesses
    text = rep.witnesses[0].format()
    assert "UNORDERED" in text and "launch.py" in text
    # and restoring the lock runs clean again (same process)
    assert not rc.confirm("relay").racy


def test_counter_bump_scenario_confirms_the_fix():
    """The self-scan's first real catch, dynamically: with _rec_lock
    the three bump roots are ordered and the count is exact; with the
    lock dropped the harness confirms the race."""
    rep = rc.confirm("counter_bump")
    assert not rep.racy
    assert rep.info["final"] == rep.info["expected"]
    with rc.mutations("drop_counter_lock"):
        rep = rc.confirm("counter_bump")
    assert rep.racy


def test_lease_flag_scenario_clean_with_real_lock():
    """PR 13's new cross-thread state: the StepLease's lease/escalation
    flag shared between the step thread (op bookkeeping, the active()
    gate) and the poller/preemption thread (revoke_local) — with the
    real ``_lock``, the vector-clock harness must find every access
    ordered."""
    rep = rc.confirm("lease_flag")
    assert not rep.racy, "\n".join(w.format() for w in rep.witnesses)
    assert rep.info["state"] == "revoked"  # both roots really ran


def test_lease_flag_scenario_flags_dropped_lock():
    """The PR-13 liveness proof: drop the lease's ``_lock`` and the
    harness must confirm the race with witnesses naming the real
    StepLease access sites; restoring the lock runs clean again."""
    with rc.mutations("drop_lease_lock"):
        rep = rc.confirm("lease_flag")
    assert rep.racy, "harness went blind: dropped lease lock not flagged"
    text = "\n".join(w.format() for w in rep.witnesses)
    assert "UNORDERED" in text and "StepLease" in text
    # the poller root's revoke leg (revoke_local routes through the
    # shared _revoke_locked transition) must appear as one side
    assert "_revoke_locked" in text or "revoke_local" in text
    assert not rc.confirm("lease_flag").racy


def test_flightrec_ring_scenario_clean_with_real_lock():
    """PR 18's black box: protocol seams' record() shares the ring
    state with the dump thread's events()/snapshot() — with the real
    RLock, the vector-clock harness must find every access ordered."""
    rep = rc.confirm("flightrec_ring")
    assert not rep.racy, "\n".join(w.format() for w in rep.witnesses)
    assert rep.info["seq"] == 25  # the step root's records all landed


def test_flightrec_ring_scenario_flags_dropped_lock():
    """The PR-18 liveness proof: drop the recorder's ``_lock`` and the
    harness must confirm the race with witnesses naming the flightrec
    state; restoring the lock runs clean again."""
    with rc.mutations("drop_flightrec_lock"):
        rep = rc.confirm("flightrec_ring")
    assert rep.racy, "harness went blind: dropped flightrec lock"
    text = "\n".join(w.format() for w in rep.witnesses)
    assert "UNORDERED" in text and "flightrec" in text
    assert not rc.confirm("flightrec_ring").racy


def test_unknown_mutation_rejected_and_nothing_left_armed():
    with pytest.raises(KeyError):
        with rc.mutations("no_such_lock"):
            pass  # pragma: no cover
    # a typo after a valid name must not leave the valid one armed
    with pytest.raises(KeyError):
        with rc.mutations("drop_relay_lock", "drop_relay_lok"):
            pass  # pragma: no cover
    assert not rc._ARMED


def test_vector_clock_orders_lock_handoffs():
    """Unit-level: a release->acquire chain orders accesses (no race);
    the same accesses without the lock are unordered (race)."""
    det = rc.RaceDetector()
    lock = rc.InstrumentedLock(det, "l")
    done = threading.Event()

    def a():
        with lock:
            det.on_access("v", True)
        done.set()

    def b():
        done.wait(5.0)
        with lock:
            det.on_access("v", True)

    ta = threading.Thread(target=det.spawned(a))
    tb = threading.Thread(target=det.spawned(b))
    ta.start(), tb.start()
    ta.join(5.0), tb.join(5.0)
    assert det.races() == []  # common lock AND ordered

    det2 = rc.RaceDetector()

    def w():
        det2.on_access("v", True)

    ts = [threading.Thread(target=det2.spawned(w)) for _ in range(2)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(5.0)
    assert det2.races(), "unsynchronized writes must be unordered"


# ----------------------------------------------------------------------
# regression tests for the fixes the self-scan forced
# ----------------------------------------------------------------------
def test_counter_bump_is_thread_safe():
    """The unlocked read-modify-write lost updates (mxrace's first
    real catch); under _rec_lock the count is exact."""
    from mxnet_tpu import profiler
    name = "test::mxrace::bump"
    start = profiler.get_counter(name)
    n_threads, per_thread = 4, 2000
    barrier = threading.Barrier(n_threads)

    def root():
        barrier.wait()
        for _ in range(per_thread):
            profiler.counter_bump(name, 1, cat="fault")

    ts = [threading.Thread(target=root) for _ in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert profiler.get_counter(name) - start == n_threads * per_thread


def test_user_counter_increment_is_thread_safe():
    """mx.profiler.Counter's increment is the same RMW class as
    counter_add — it must hold the recorder lock, not just publish."""
    from mxnet_tpu import profiler
    c = profiler.Domain("test::mxrace").new_counter("inc", 0)
    n_threads, per_thread = 4, 1000
    barrier = threading.Barrier(n_threads)

    def root():
        barrier.wait()
        for _ in range(per_thread):
            c.increment(1)

    ts = [threading.Thread(target=root) for _ in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert c.value == n_threads * per_thread


def test_generation_singleton_under_contention(monkeypatch):
    """Two threads racing the first generation() call must get ONE
    Generation object — a split singleton would gen-gate retries
    against the wrong epoch."""
    import mxnet_tpu.fault_dist as fdist
    monkeypatch.setattr(fdist, "_generation", None)
    got = []
    barrier = threading.Barrier(8)
    lock = threading.Lock()

    def grab():
        barrier.wait()
        g = fdist.generation()
        with lock:
            got.append(g)

    ts = [threading.Thread(target=grab) for _ in range(8)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert len(got) == 8 and len({id(g) for g in got}) == 1


def test_preempt_handler_locked_accessor(tmp_path):
    """fault.preempt_handler() reads the handler under _fault_lock —
    the maintenance poller consults it while the main thread swaps
    handlers."""
    from mxnet_tpu import fault
    h = fault.on_preemption(str(tmp_path))
    try:
        assert fault.preempt_handler() is h
    finally:
        h.uninstall()
    assert fault.preempt_handler() is None


def test_set_default_comm_locked_roundtrip():
    import mxnet_tpu.fault_dist as fdist
    prev = fdist._default_comm
    try:
        sentinel = fdist.LocalComm()
        assert fdist.set_default_comm(sentinel) is sentinel
        assert fdist.default_comm() is sentinel
    finally:
        fdist.set_default_comm(prev)


# ----------------------------------------------------------------------
# CLI
# ----------------------------------------------------------------------
@pytest.mark.integration
def test_mxrace_cli_standalone(tmp_path):
    """tools/mxrace.py static path: exit 0 on the clean repo, 2 on a
    typo'd rule, spaced commas tolerated, --mutate needs --confirm."""
    cli = os.path.join(ROOT, "tools", "mxrace.py")
    r = subprocess.run([sys.executable, cli], cwd=ROOT,
                       capture_output=True, text=True, timeout=180)
    assert r.returncode == 0, r.stdout + r.stderr
    r = subprocess.run([sys.executable, cli, "--rules", "R99"],
                       cwd=ROOT, capture_output=True, text=True,
                       timeout=180)
    assert r.returncode == 2 and "unknown rule" in r.stderr
    # comma syntax tolerates spaces (subset targets keep this fast)
    r = subprocess.run([sys.executable, cli, "--rules", "R9, R10",
                        "--no-baseline", "tools"],
                       cwd=ROOT, capture_output=True, text=True,
                       timeout=180)
    assert r.returncode == 0, r.stdout + r.stderr
    r = subprocess.run([sys.executable, cli, "--mutate",
                        "drop_relay_lock"],
                       cwd=ROOT, capture_output=True, text=True,
                       timeout=180)
    assert r.returncode == 2 and "--confirm" in r.stderr


@pytest.mark.integration
def test_mxrace_cli_github_format_and_stale_baseline(tmp_path):
    """--no-baseline surfaces the deliberately-baselined _ACTIVE
    finding as a ::error workflow command; a stale baseline entry
    fails the gate and is printed with its justification."""
    cli = os.path.join(ROOT, "tools", "mxrace.py")
    # the subset spanning the poller's root and fault.py surfaces
    # the deliberately-baselined _ACTIVE finding without a full scan
    r = subprocess.run([sys.executable, cli, "--format", "github",
                        "--no-baseline", "mxnet_tpu/fault.py",
                        "mxnet_tpu/fault_dist.py"],
                       cwd=ROOT, capture_output=True, text=True,
                       timeout=180)
    assert r.returncode == 1
    assert "::error file=" in r.stdout and "title=mxrace R9" in r.stdout
    stale = tmp_path / "stale.txt"
    stale.write_text("R9 tools/gone.py 3 -- torn writer long since "
                     "fixed\n")
    r = subprocess.run([sys.executable, cli, "--baseline", str(stale),
                        "tools"],
                       cwd=ROOT, capture_output=True, text=True,
                       timeout=180)
    assert r.returncode == 1
    assert "stale baseline entry 'R9 tools/gone.py 3" in r.stderr
    assert "torn writer long since fixed" in r.stderr


@pytest.mark.integration
def test_mxrace_cli_confirm_and_smoke():
    """--confirm exits 0 clean / 1 on a confirmed race; --smoke runs
    the self-scan plus every liveness proof (strip-_rec_lock static,
    drop-_relay_lock and drop-StepLease._lock dynamic) inside the
    gate budget."""
    cli = os.path.join(ROOT, "tools", "mxrace.py")
    r = subprocess.run([sys.executable, cli, "--confirm", "relay"],
                       cwd=ROOT, capture_output=True, text=True,
                       timeout=180)
    assert r.returncode == 0 and "clean" in r.stdout
    r = subprocess.run([sys.executable, cli, "--confirm", "relay",
                        "--mutate", "drop_relay_lock"],
                       cwd=ROOT, capture_output=True, text=True,
                       timeout=180)
    assert r.returncode == 1 and "RACE CONFIRMED" in r.stdout
    r = subprocess.run([sys.executable, cli, "--confirm", "nope"],
                       cwd=ROOT, capture_output=True, text=True,
                       timeout=180)
    assert r.returncode == 2 and "unknown scenario" in r.stderr
    r = subprocess.run([sys.executable, cli, "--smoke"], cwd=ROOT,
                       capture_output=True, text=True, timeout=180)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "static liveness ok" in r.stderr
    assert "dynamic liveness ok" in r.stderr


@pytest.mark.integration
def test_mxrace_cli_static_path_never_imports_jax(tmp_path):
    """The static scan is jax-free: the analysis modules load by file
    path.  (The --smoke gate's lease_flag scenario DOES import
    mxnet_tpu, pinned to the CPU backend — the same trade mxverify
    makes to execute real protocol code.)"""
    driver = tmp_path / "driver.py"
    driver.write_text(
        "import builtins, runpy, sys\n"
        "real = builtins.__import__\n"
        "def guard(name, *a, **k):\n"
        "    if name == 'jax' or name.startswith('jax.'):\n"
        "        raise AssertionError('jax imported by mxrace static "
        "path')\n"
        "    return real(name, *a, **k)\n"
        "builtins.__import__ = guard\n"
        "sys.argv = ['mxrace', '--no-baseline', '--rules', 'R9',\n"
        "            'mxnet_tpu/profiler.py', 'mxnet_tpu/fault.py']\n"
        "runpy.run_path(%r, run_name='__main__')\n"
        % os.path.join(ROOT, "tools", "mxrace.py"))
    r = subprocess.run([sys.executable, str(driver)], cwd=ROOT,
                       capture_output=True, text=True, timeout=180)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "jax imported" not in r.stdout + r.stderr
