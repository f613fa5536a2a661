"""mxverify (``mx.analysis.modelcheck``) — the protocol checker must be
BOTH sound on the real protocols and provably alive.

Liveness is the load-bearing half: a model checker that reports green
is only trustworthy while it still finds known bugs.  Three bugs are
deliberately reintroducible behind test-only mutation flags —
``solo_reissue`` (a transiently-failed rank retries without voting, the
deadlock class the consensus barrier exists for), ``skip_commit_funnel``
(any rank commits its own view on an identical round, the resize-fork
class), and ``skip_lease_revoke`` (a rank ignores a peer's failure flag
in the step-lease beat and reports the step successful, the
silent-success class of PR 13's amortized consensus) — and each must
produce a replayable minimized counterexample within a modest budget.

Also here: the regression tests for the REAL bug mxverify found during
this PR's development — the resize commit's sweep-then-post TOCTOU (a
slow leader waking after its peers drained it could post a second,
stale commit record).  The fix makes the commit an atomic first-writer-
wins ``Board.claim`` of one winner slot per epoch.
"""
import json
import os
import subprocess
import sys
import threading

import pytest

from mxnet_tpu import fault_elastic as felastic
from mxnet_tpu.analysis import modelcheck as mc

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# small deterministic budgets: tier-1 runs this file on every change.
# The schedule count is the budget (2-5 s on an idle core); the seconds
# are only a cap, wide enough that five other xdist workers compiling on
# the same cores cannot cut a run short of the counts asserted below
_SMOKE = dict(schedules=250, seconds=90, seed=0)
_HUNT = dict(schedules=500, seconds=90, seed=0)


# ----------------------------------------------------------------------
# the real protocols are green
# ----------------------------------------------------------------------
def test_consensus_protocol_green():
    rep = mc.verify_scenario("consensus", budget=mc.Budget(**_SMOKE))
    assert rep.ok, rep.counterexample.format()
    assert rep.schedules >= 200
    # every phase actually ran: systematic DFS, the slow-rank delay
    # sweep, and (budget permitting) random walks
    assert rep.dfs > 0 and rep.sweeps > 0


def test_resize_protocol_green():
    rep = mc.verify_scenario("resize", budget=mc.Budget(**_SMOKE))
    assert rep.ok, rep.counterexample.format()
    assert rep.schedules >= 200
    assert rep.dfs > 0 and rep.sweeps > 0


def test_consensus_amortized_protocol_green():
    """The step-lease protocol (PR 13): success path, entry-fail
    mid-step escalation, mid-op failure on a mutating window, and the
    late-peer-flag window — all green under the amortized oracles
    (including lease_amortized: zero per-op rounds on clean schedules)."""
    rep = mc.verify_scenario("consensus_amortized",
                             budget=mc.Budget(**_SMOKE))
    assert rep.ok, rep.counterexample.format()
    assert rep.schedules >= 200
    assert rep.dfs > 0 and rep.sweeps > 0
    assert "lease_amortized" in rep.oracles
    assert "no_lease_false_success" in rep.oracles


# ----------------------------------------------------------------------
# checker liveness: the two reintroduced bugs MUST be found
# ----------------------------------------------------------------------
def test_mutation_solo_reissue_is_caught():
    with mc.mutations("solo_reissue"):
        rep = mc.verify_scenario("consensus", budget=mc.Budget(**_HUNT))
    assert not rep.ok, "checker went blind: solo re-issue not found"
    cex = rep.counterexample
    assert cex.oracle == "no_solo_reissue"
    assert cex.events, "counterexample must carry a replayable trace"
    # the minimized schedule REPLAYS: deterministic with the mutation
    # armed, clean without it (the barrier really is the fix)
    with mc.mutations("solo_reissue"):
        violation, _ = mc.replay(cex.to_json())
    assert violation is not None and violation.oracle == cex.oracle
    violation, _ = mc.replay(cex.to_json())
    assert violation is None


def test_mutation_skip_commit_funnel_is_caught():
    with mc.mutations("skip_commit_funnel"):
        rep = mc.verify_scenario("resize", budget=mc.Budget(**_HUNT))
    assert not rep.ok, "checker went blind: resize fork not found"
    cex = rep.counterexample
    assert cex.oracle == "no_fork"
    with mc.mutations("skip_commit_funnel"):
        violation, _ = mc.replay(cex.to_json())
    assert violation is not None and violation.oracle == "no_fork"
    violation, _ = mc.replay(cex.to_json())
    assert violation is None, \
        "the claim()-based commit should close the fork"


def test_mutation_skip_lease_revoke_is_caught():
    """The PR-13 liveness proof: a rank that ignores a peer's failure
    flag in the lease beat (keeps its lease, reports the step
    successful) must be found — and the counterexample must replay
    mutated and come back clean unmutated (the revocation really is
    the fix)."""
    with mc.mutations("skip_lease_revoke"):
        rep = mc.verify_scenario("consensus_amortized",
                                 budget=mc.Budget(**_HUNT))
    assert not rep.ok, "checker went blind: skipped lease revoke " \
        "not found"
    cex = rep.counterexample
    assert cex.oracle == "no_lease_false_success"
    assert cex.events, "counterexample must carry a replayable trace"
    with mc.mutations("skip_lease_revoke"):
        violation, _ = mc.replay(cex.to_json())
    assert violation is not None and violation.oracle == cex.oracle
    violation, _ = mc.replay(cex.to_json())
    assert violation is None, \
        "the beat-round revocation should close the silent success"


def test_counterexample_trace_is_json_roundtrippable():
    with mc.mutations("solo_reissue"):
        rep = mc.verify_scenario("consensus", budget=mc.Budget(**_HUNT))
    payload = json.dumps(rep.counterexample.to_json())
    back = json.loads(payload)
    assert back["oracle"] == "no_solo_reissue"
    assert back["schedule"] is not None and back["events"]
    text = rep.counterexample.format()
    assert "minimized schedule" in text and "replayed events" in text


def test_unknown_mutation_rejected():
    with pytest.raises(KeyError):
        with mc.mutations("no_such_bug"):
            pass  # pragma: no cover
    # a typo AFTER a valid name must not leave the valid one armed (the
    # names are validated before anything arms)
    with pytest.raises(KeyError):
        with mc.mutations("solo_reissue", "skip_commit_funel"):
            pass  # pragma: no cover
    # and nothing leaked into the production flag sets
    import mxnet_tpu.fault_dist as fdist
    assert not fdist._TEST_MUTATIONS
    assert not felastic._TEST_MUTATIONS


# ----------------------------------------------------------------------
# budgets
# ----------------------------------------------------------------------
def test_budget_env_knobs(monkeypatch):
    monkeypatch.setenv("MXNET_VERIFY_SCHEDULES", "77")
    monkeypatch.setenv("MXNET_VERIFY_PREEMPTIONS", "5")
    b = mc.Budget()
    assert b.schedules == 77 and b.preemptions == 5
    # explicit args beat the env
    assert mc.Budget(schedules=3).schedules == 3
    subs = mc.Budget(schedules=90, seconds=9).split(3)
    assert [s.schedules for s in subs] == [30, 30, 30]


# ----------------------------------------------------------------------
# regression: the commit claim (the TOCTOU fork mxverify found)
# ----------------------------------------------------------------------
def test_inprocess_board_claim_first_writer_wins():
    board = felastic.InProcessBoard()
    assert board.claim("rz/1/commit/W", {"survivors": [0, 1]})
    assert not board.claim("rz/1/commit/W", {"survivors": [1]})
    rec = board.sweep("rz/1/commit/")
    assert list(rec.values()) == [{"survivors": [0, 1]}]


def test_file_board_claim_atomic_under_contention(tmp_path):
    board = felastic.FileBoard(str(tmp_path))
    wins = []
    lock = threading.Lock()

    def contender(i):
        if board.claim("rz/1/commit/W", {"winner": i}):
            with lock:
                wins.append(i)

    ts = [threading.Thread(target=contender, args=(i,))
          for i in range(8)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert len(wins) == 1, "claim must have exactly one winner"
    rec = board.sweep("rz/1/commit/")
    assert list(rec.values()) == [{"winner": wins[0]}]
    # the winner record survives a re-read and no tmp litter remains
    assert not [f for f in os.listdir(str(tmp_path)) if ".claim." in f]


def test_vote_resize_commits_exactly_one_winner_record():
    """Whatever the interleaving, an epoch ends with ONE winner record;
    every returned intent matches it (here: the plain 3-rank all-alive
    case over real threads)."""
    board = felastic.InProcessBoard()
    intents = {}

    def voter(rank):
        intents[rank] = felastic.vote_resize(
            board, rank=rank, world=3, lost=(), gen=0, epoch=1,
            drain=5.0, min_world=1)

    ts = [threading.Thread(target=voter, args=(r,)) for r in range(3)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    winners = {k: v for k, v in board.sweep("rz/1/commit/").items()
               if k.endswith("/W")}
    assert len(winners) == 1
    surv = tuple(list(winners.values())[0]["survivors"])
    assert surv == (0, 1, 2)
    for rank, it in intents.items():
        assert tuple(it.survivors) == surv and it.gen == 1


# ----------------------------------------------------------------------
# CLI
# ----------------------------------------------------------------------
@pytest.mark.integration
def test_mxverify_cli(tmp_path):
    cli = os.path.join(ROOT, "tools", "mxverify.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, cli, "--list"], cwd=ROOT,
                       capture_output=True, text=True, timeout=120,
                       env=env)
    assert r.returncode == 0
    assert "consensus" in r.stdout and "resize" in r.stdout
    assert "skip_commit_funnel" in r.stdout
    # a mutated run exits 1 and writes a replayable trace
    trace = tmp_path / "trace.json"
    r = subprocess.run(
        [sys.executable, cli, "--scenario", "resize", "--mutate",
         "skip_commit_funnel", "--schedules", "500", "--seconds", "20",
         "--trace-out", str(trace)],
        cwd=ROOT, capture_output=True, text=True, timeout=300, env=env)
    assert r.returncode == 1, r.stdout + r.stderr
    assert "counterexample" in r.stdout and trace.exists()
    # replaying it WITHOUT the mutation reports the fix holds (exit 0)
    r = subprocess.run([sys.executable, cli, "--replay", str(trace)],
                       cwd=ROOT, capture_output=True, text=True,
                       timeout=120, env=env)
    assert r.returncode == 0 and "no longer reproduces" in r.stdout
    # replaying WITH --mutate re-arms the bug: the recorded violation
    # must reproduce deterministically (exit 1)
    r = subprocess.run([sys.executable, cli, "--replay", str(trace),
                        "--mutate", "skip_commit_funnel"],
                       cwd=ROOT, capture_output=True, text=True,
                       timeout=120, env=env)
    assert r.returncode == 1 and "VIOLATES no_fork" in r.stdout
    # unknown scenario is a usage error
    r = subprocess.run([sys.executable, cli, "--scenario", "nope"],
                       cwd=ROOT, capture_output=True, text=True,
                       timeout=120, env=env)
    assert r.returncode == 2


def test_resize_grow_protocol_green():
    """The GROW protocol (join barrier + folding vote): single joiner,
    a pair of joiners, and a dead-rank-replaced variant all survive the
    schedule sweep under the grow oracles."""
    rep = mc.verify_scenario("resize_grow", budget=mc.Budget(**_SMOKE))
    assert rep.ok, rep.counterexample.format()
    assert rep.schedules >= 200
    assert rep.dfs > 0 and rep.sweeps > 0
    assert "no_stale_world_commit" in rep.oracles
    assert "joiner_adopts_committed_gen" in rep.oracles


def test_mutation_skip_join_barrier_is_caught():
    """The grow liveness proof: a joiner that starts stepping before
    the commit folds it (guessed survivors, stale generation) must be
    found — and the counterexample must replay mutated and come back
    clean unmutated (the barrier really is the fix)."""
    with mc.mutations("skip_join_barrier"):
        rep = mc.verify_scenario("resize_grow", budget=mc.Budget(**_HUNT))
    assert not rep.ok, "checker went blind: skipped join barrier " \
        "not found"
    cex = rep.counterexample
    assert cex.oracle in ("no_fork", "equal_generations",
                          "joiner_adopts_committed_gen")
    assert cex.events, "counterexample must carry a replayable trace"
    with mc.mutations("skip_join_barrier"):
        violation, _ = mc.replay(cex.to_json())
    assert violation is not None and violation.oracle == cex.oracle
    violation, _ = mc.replay(cex.to_json())
    assert violation is None, \
        "the join barrier should close the premature entry"
