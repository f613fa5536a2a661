"""Coordinated multi-host fault tolerance (``mx.fault.dist``).

The consensus machinery is exercised against an in-process fake comm
(threads as workers), the maintenance poller against a stub HTTP
metadata server, and the resilient bootstrap against a monkeypatched
``jax.distributed.initialize`` — no real multi-process job needed, so
these stay in tier-1 (the real-fleet paths run under
``tools/chaos_check.py --multihost`` / the ``dist`` marker).
"""
import http.server
import os
import threading
import time

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import fault
from mxnet_tpu import fault_dist as fdist
from mxnet_tpu import profiler as prof
from mxnet_tpu.gluon import nn


@pytest.fixture(autouse=True)
def _clean_faults():
    fault.clear()
    yield
    fault.clear()
    fdist.disable_step_lease()
    fdist.disable_step_heartbeat()


def _fast_policy(max_retries=3):
    return fault.RetryPolicy(max_retries=max_retries, base_delay=0.001,
                             max_delay=0.005, jitter=0.0, timeout=False)


def _run_workers(worker, world=2):
    """Run ``worker(rank, comm)`` on one thread per simulated worker;
    returns per-rank results, re-raising the first worker error."""
    comms = fdist.InProcessComm.create(world)
    results, errors = {}, {}

    def go(rank):
        try:
            results[rank] = worker(rank, comms[rank])
        except BaseException as e:  # noqa: BLE001 — re-raised below
            errors[rank] = e

    threads = [threading.Thread(target=go, args=(r,)) for r in range(world)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    return results, errors


# ----------------------------------------------------------------------
# Generation + consensus barrier (coordinated_call)
# ----------------------------------------------------------------------
def test_coordinated_all_agree_single_attempt():
    gens = {r: fdist.Generation() for r in range(2)}
    calls = {0: 0, 1: 0}

    def worker(rank, comm):
        def fn():
            calls[rank] += 1
            return "ok-%d" % rank
        return fdist.coordinated_call(fn, comm=comm, op="t", gen=gens[rank],
                                      policy=_fast_policy())

    results, errors = _run_workers(worker)
    assert not errors
    assert results == {0: "ok-0", 1: "ok-1"}
    assert calls == {0: 1, 1: 1}          # nobody retried
    assert gens[0].value == 0 and gens[1].value == 0


def test_coordinated_split_vote_everyone_retries_together():
    """One worker fails, the OTHER one succeeded locally — yet both must
    bump the generation and re-issue (the healthy worker discards its
    result): a lone-retry would deadlock a real collective."""
    gens = {r: fdist.Generation() for r in range(2)}
    calls = {0: 0, 1: 0}
    before = prof.get_counter("fault::dist::coordinated_retries")

    def worker(rank, comm):
        def fn():
            calls[rank] += 1
            if rank == 0 and calls[0] == 1:
                raise fault.InjectedFault("boom on worker 0")
            return gens[rank].value
        return fdist.coordinated_call(fn, comm=comm, op="t", gen=gens[rank],
                                      policy=_fast_policy())

    results, errors = _run_workers(worker)
    assert not errors
    assert calls == {0: 2, 1: 2}          # BOTH re-issued
    assert gens[0].value == 1 and gens[1].value == 1
    assert results[0] == results[1] == 1  # re-issue ran at generation 1
    assert prof.get_counter("fault::dist::coordinated_retries") >= before + 2


def test_coordinated_repeated_failure_gives_up_everywhere():
    gens = {r: fdist.Generation() for r in range(2)}
    calls = {0: 0, 1: 0}

    def worker(rank, comm):
        def fn():
            calls[rank] += 1
            if rank == 1:
                raise fault.TransientError("always down")
            return "fine"
        return fdist.coordinated_call(fn, comm=comm, op="t", gen=gens[rank],
                                      policy=_fast_policy(max_retries=2))

    results, errors = _run_workers(worker)
    assert set(errors) == {0, 1}          # both workers raise, same round
    # the failing rank wraps its transient error too (an escaping
    # TransientError would let an outer retry_call re-enter solo);
    # the local error stays reachable as __cause__
    assert isinstance(errors[1], fdist.CoordinatedAbortError)
    assert isinstance(errors[1].__cause__, fault.TransientError)
    assert isinstance(errors[0], fdist.CoordinatedAbortError)
    assert "process(es) [1]" in str(errors[0])
    assert calls[0] == calls[1] == 3      # 1 + max_retries, in lockstep
    assert gens[0].value == gens[1].value


def test_no_solo_retry_reissue_waits_for_all_acks():
    """The acceptance-criteria invariant: NO worker re-issues the
    collective at a generation its peers have not acknowledged.  Every
    attempt at generation g > 0 must be preceded — on the attempting
    worker's own timeline — by a COMPLETE vote round (all ranks' votes)
    for generation g-1."""
    world = 3
    gens = {r: fdist.Generation() for r in range(world)}
    log_lock = threading.Lock()
    timeline = {r: [] for r in range(world)}  # per-rank ordered events

    class RecordingComm:
        def __init__(self, inner):
            self.inner = inner
            self.rank = inner.rank
            self.world = inner.world

        def allgather(self, payload, timeout=None):
            votes = self.inner.allgather(payload, timeout=timeout)
            with log_lock:
                timeline[self.rank].append(
                    ("round", payload["gen"], sorted(v["rank"]
                                                     for v in votes)))
            return votes

    def worker(rank, comm):
        comm = RecordingComm(comm)

        def fn():
            with log_lock:
                timeline[rank].append(("attempt", gens[rank].value))
            # two rounds of failure from different workers, then success
            attempts = sum(1 for e in timeline[rank] if e[0] == "attempt")
            if attempts == 1 and rank == 0:
                raise fault.InjectedFault("gen0 failure on rank 0")
            if attempts == 2 and rank == 2:
                raise fault.InjectedFault("gen1 failure on rank 2")
            return "done"

        return fdist.coordinated_call(fn, comm=comm, op="t", gen=gens[rank],
                                      policy=_fast_policy())

    results, errors = _run_workers(worker, world=world)
    assert not errors and set(results.values()) == {"done"}
    all_ranks = list(range(world))
    for rank in range(world):
        events = timeline[rank]
        for i, ev in enumerate(events):
            if ev[0] != "attempt" or ev[1] == 0:
                continue
            g = ev[1]
            prior_rounds = [e for e in events[:i] if e[0] == "round"]
            # a complete (all-ranks) vote round at g-1 happened first
            assert ("round", g - 1, all_ranks) in prior_rounds, (
                "rank %d re-issued at generation %d without a complete "
                "vote round for %d: %s" % (rank, g, g - 1, events))
        # and every attempted generation is contiguous — no skipping
        gens_attempted = [e[1] for e in events if e[0] == "attempt"]
        assert gens_attempted == sorted(set(gens_attempted))


def test_no_reissue_when_peer_never_votes():
    """A worker whose peer goes silent must NOT retry solo: it raises
    PeerLostError (naming the rank) with its attempt count still 1."""
    calls = {0: 0}
    comms = fdist.InProcessComm.create(2)

    def fn():
        calls[0] += 1
        raise fault.InjectedFault("transient")

    with pytest.raises(fdist.PeerLostError) as ei:
        fdist.coordinated_call(fn, comm=comms[0], op="t",
                               gen=fdist.Generation(),
                               policy=_fast_policy(), timeout=0.2)
    assert calls[0] == 1                  # never re-issued alone
    assert ei.value.process_indices == (1,)


def test_mutating_midop_failure_aborts_all_no_retry():
    """Cross-host extension of the entry-seam rule: a mid-op failure on
    a mutating (optimizer-applying) op must abort EVERY worker — a retry
    could double-apply the gradient on workers that already committed."""
    gens = {r: fdist.Generation() for r in range(2)}
    calls = {0: 0, 1: 0}

    def worker(rank, comm):
        def fn():
            calls[rank] += 1
            if rank == 0:
                # TransientError that is NOT an entry-seam InjectedFault
                raise fault.TransientError("mid-op network drop")
            return "applied"
        return fdist.coordinated_call(fn, comm=comm, op="push",
                                      gen=gens[rank], mutating=True,
                                      policy=_fast_policy())

    results, errors = _run_workers(worker)
    assert set(errors) == {0, 1}
    assert isinstance(errors[0], fdist.CoordinatedAbortError)
    assert isinstance(errors[0].__cause__, fault.TransientError)
    assert isinstance(errors[1], fdist.CoordinatedAbortError)
    assert calls == {0: 1, 1: 1}          # nobody retried

    # ...an entry-seam failure on ONE rank while a peer already applied
    # must ALSO abort: re-running would double-apply on the peer
    calls2 = {0: 0, 1: 0}

    def worker2(rank, comm):
        def fn():
            calls2[rank] += 1
            if rank == 0 and calls2[0] == 1:
                raise fault.InjectedFault("entry seam")
            return "applied"
        return fdist.coordinated_call(fn, comm=comm, op="push",
                                      gen=fdist.Generation(),
                                      mutating=True, policy=_fast_policy())

    results2, errors2 = _run_workers(worker2)
    assert set(errors2) == {0, 1}
    assert isinstance(errors2[0], fdist.CoordinatedAbortError)
    assert isinstance(errors2[0].__cause__, fault.InjectedFault)
    assert isinstance(errors2[1], fdist.CoordinatedAbortError)
    assert calls2 == {0: 1, 1: 1}         # the applied update stands once

    # ...only a fleet-wide entry-seam failure (NO worker mutated any
    # state) may retry a mutating op — and then every worker re-issues
    calls3 = {0: 0, 1: 0}

    def worker3(rank, comm):
        def fn():
            calls3[rank] += 1
            if calls3[rank] == 1:
                raise fault.InjectedFault("entry seam everywhere")
            return "applied"
        return fdist.coordinated_call(fn, comm=comm, op="push",
                                      gen=fdist.Generation(),
                                      mutating=True, policy=_fast_policy())

    results3, errors3 = _run_workers(worker3)
    assert not errors3
    assert set(results3.values()) == {"applied"}
    assert calls3 == {0: 2, 1: 2}


def test_fatal_error_is_voted_abort_keeps_rounds_aligned():
    """A non-transient (fatal) local error must still VOTE before
    re-raising: peers get an immediate CoordinatedAbortError instead of
    burning the consensus timeout, nobody retries, and — crucially —
    the round counters stay aligned, so the same comms keep working for
    the next coordinated op instead of consuming stale votes."""
    comms = {}

    def worker(rank, comm):
        comms[rank] = comm

        def fn():
            if rank == 0:
                raise ValueError("compile bug — not transient")
            return "ok"
        return fdist.coordinated_call(fn, comm=comm, op="t",
                                      gen=fdist.Generation(),
                                      policy=_fast_policy(), timeout=5)

    results, errors = _run_workers(worker)
    assert isinstance(errors[0], ValueError)
    assert isinstance(errors[1], fdist.CoordinatedAbortError)
    assert "non-transient" in str(errors[1])

    # the comms are not desynced: a fresh coordinated op completes
    def worker_again(rank, comm):
        return fdist.coordinated_call(lambda: "again", comm=comms[rank],
                                      op="t2", gen=fdist.Generation(),
                                      policy=_fast_policy(), timeout=5)

    results2, errors2 = _run_workers(worker_again)
    assert not errors2
    assert set(results2.values()) == {"again"}


def test_abort_not_retryable_by_outer_retry_call():
    """No error escaping a coordinated abort may be transient-typed: a
    user wrapping the dist op in mx.fault.retry_call (the module's
    advertised retry API) would otherwise re-enter coordinated_call
    solo — a vote round with no peers, burning the consensus timeout."""
    gens = {r: fdist.Generation() for r in range(2)}
    entered = {0: 0, 1: 0}

    def worker(rank, comm):
        def coordinated():
            entered[rank] += 1

            def fn():
                if rank == 0:
                    raise fault.TransientError("mid-op network drop")
                return "applied"
            return fdist.coordinated_call(fn, comm=comm, op="push",
                                          gen=gens[rank], mutating=True,
                                          policy=_fast_policy())
        return fault.retry_call(coordinated, policy=_fast_policy(),
                                op="outer")

    results, errors = _run_workers(worker)
    assert set(errors) == {0, 1}
    assert all(isinstance(e, fdist.CoordinatedAbortError)
               for e in errors.values())
    assert entered == {0: 1, 1: 1}        # the outer wrapper never re-entered


def test_generation_mismatch_detected():
    class SkewComm:
        rank, world = 0, 2

        def allgather(self, payload, timeout=None):
            return [payload, {"gen": payload["gen"] + 5, "ok": True,
                              "entry": True, "rank": 1}]

    with pytest.raises(fdist.GenerationMismatchError):
        fdist.coordinated_call(lambda: 1, comm=SkewComm(), op="t",
                               gen=fdist.Generation(),
                               policy=_fast_policy())


def test_coordinated_call_local_comm_uses_plain_retry():
    """Single-process degenerates to mx.fault.retry_call — same policy
    semantics, no barrier overhead."""
    fault.inject("collective_fail", at=1)
    before = prof.get_counter("fault::retries")
    calls = [0]

    def fn():
        calls[0] += 1
        fault.collective_check("t")
        return 7

    out = fdist.coordinated_call(fn, comm=fdist.LocalComm(), op="t",
                                 policy=_fast_policy())
    assert out == 7 and calls[0] == 2
    assert prof.get_counter("fault::retries") == before + 1


# ----------------------------------------------------------------------
# comms
# ----------------------------------------------------------------------
def test_filecomm_allgather_and_timeout(tmp_path):
    root = str(tmp_path / "comm")
    c0 = fdist.FileComm(root, 0, 2, poll=0.01)
    c1 = fdist.FileComm(root, 1, 2, poll=0.01)
    out = {}

    def go(c):
        out[c.rank] = c.allgather({"rank": c.rank, "x": c.rank * 10},
                                  timeout=5)

    ts = [threading.Thread(target=go, args=(c,)) for c in (c0, c1)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=10)
    assert out[0] == out[1] == [{"rank": 0, "x": 0}, {"rank": 1, "x": 10}]

    # missing peer: timeout names the silent rank
    with pytest.raises(fdist.PeerLostError) as ei:
        c0.allgather({"rank": 0}, timeout=0.1)
    assert ei.value.process_indices == (1,)

    # ...and the slow peer still completes the round from the persisted
    # votes, keeping the two round counters aligned
    assert c1.allgather({"rank": 1}, timeout=1)[0] == {"rank": 0}


def test_inprocess_comm_timeout_names_missing_rank():
    comms = fdist.InProcessComm.create(3)
    with pytest.raises(fdist.PeerLostError) as ei:
        comms[0].allgather({"v": 1}, timeout=0.1)
    assert ei.value.process_indices == (1, 2)


def test_filecomm_two_logical_comms_on_one_root_do_not_collide(tmp_path):
    """A second comm on the same root (heartbeat next to the collective
    comm) must not consume the first one's round files: the default
    namespace is the per-(root, rank) construction sequence — same for
    every rank endpoint of one logical comm, different between comms."""
    root = str(tmp_path / "comm")
    a0 = fdist.FileComm(root, 0, 2, poll=0.01)   # logical comm A
    a1 = fdist.FileComm(root, 1, 2, poll=0.01)
    b0 = fdist.FileComm(root, 0, 2, poll=0.01)   # logical comm B
    b1 = fdist.FileComm(root, 1, 2, poll=0.01)
    assert a0._ns == a1._ns and b0._ns == b1._ns  # endpoints rendezvous
    assert a0._ns != b0._ns                       # comms are isolated
    assert a0._path(0, 0) != b0._path(0, 0)

    out = {}

    def go(tag, c, payload):
        out[(tag, c.rank)] = c.allgather(payload, timeout=5)

    ts = [threading.Thread(target=go, args=args) for args in (
        ("a", a0, {"gen": 0}), ("a", a1, {"gen": 0}),
        ("b", b0, {"step": 1}), ("b", b1, {"step": 1}))]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=10)
    assert out[("a", 0)] == [{"gen": 0}, {"gen": 0}]
    assert out[("b", 0)] == [{"step": 1}, {"step": 1}]


def test_filecomm_garbage_collects_own_old_votes(tmp_path):
    """Completed rounds must not accumulate vote files forever (a
    heartbeat-per-step job would otherwise grow the shared directory
    without bound)."""
    root = str(tmp_path / "comm")
    c0 = fdist.FileComm(root, 0, 2, poll=0.01)
    c1 = fdist.FileComm(root, 1, 2, poll=0.01)

    def rounds(c, n):
        for _ in range(n):
            c.allgather({"rank": c.rank}, timeout=5)

    ts = [threading.Thread(target=rounds, args=(c, 5)) for c in (c0, c1)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=10)
    leftover = sorted(os.listdir(root))
    # only the LAST round's votes may remain (each rank GCs its own
    # older files once a newer round completes)
    ns = c0._ns
    assert leftover == ["%s_ag_4.0.json" % ns, "%s_ag_4.1.json" % ns], \
        leftover


def test_default_comm_not_frozen_before_bootstrap(monkeypatch):
    """Resolving the ambient comm before jax.distributed is up (e.g.
    enable_step_heartbeat during setup) must not freeze a later
    multi-process job into uncoordinated LocalComm behavior."""
    import jax
    fdist.set_default_comm(None)
    try:
        assert isinstance(fdist.default_comm(), fdist.LocalComm)
        monkeypatch.setattr(jax, "process_count", lambda: 2)
        monkeypatch.setattr(jax, "process_index", lambda: 0)
        monkeypatch.setattr(fdist, "_coord_client", lambda: object())
        assert isinstance(fdist.default_comm(), fdist.CoordServiceComm)
    finally:
        fdist.set_default_comm(None)


def test_default_comm_pre_bootstrap_does_not_init_jax_backend():
    """Resolving the ambient comm before jax.distributed is up must not
    query jax.process_count(): that initializes the XLA backend, which
    pins a later jax.distributed.initialize to single-process.  Needs a
    fresh interpreter — this test process already has live backends."""
    import subprocess
    import sys
    code = (
        "from mxnet_tpu import fault_dist as fdist\n"
        "assert isinstance(fdist.default_comm(), fdist.LocalComm)\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge._backends, "
        "'default_comm() initialized a backend: %r' % xla_bridge._backends\n"
        "print('NO-BACKEND OK')\n")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=120)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "NO-BACKEND OK" in r.stdout


def test_detect_process_index_pre_bootstrap_does_not_init_jax_backend():
    """fault._detect_process_index() (per-process snapshot suffixes) has
    the same constraint: a pre-bootstrap load_snapshot() on a TPU-pod
    job (no MX_NUM_WORKERS env) must not initialize the XLA backend
    single-process while probing for the rank."""
    import subprocess
    import sys
    code = (
        "import os\n"
        "os.environ.pop('MX_NUM_WORKERS', None)\n"
        "from mxnet_tpu import fault\n"
        "assert fault._detect_process_index() is None\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge._backends, "
        "'_detect_process_index initialized a backend'\n"
        "print('NO-BACKEND OK')\n")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=120)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "NO-BACKEND OK" in r.stdout


def test_coordservice_votes_read_via_dir_get_fast_path():
    """One key_value_dir_get round-trip serves a whole vote round (the
    success path is O(1) in world size); a broken/short dir listing
    falls back to authoritative per-rank blocking gets."""
    votes = {"/mx_fault_ag/0/0": '{"rank": 0, "ok": true}',
             "/mx_fault_ag/0/1": '{"rank": 1, "ok": true}'}
    calls = []

    class Client:
        def key_value_dir_get(self, prefix):
            calls.append(("dir", prefix))
            return [(k, v) for k, v in votes.items()
                    if k.startswith(prefix)]

        def blocking_key_value_get(self, key, ms):
            calls.append(("get", key))
            return votes[key]

    comm = fdist.CoordServiceComm(client=Client(), rank=0, world=2,
                                  namespace="mx")
    out = comm._read_votes(0, 1000)
    assert [v["rank"] for v in out] == [0, 1]
    assert calls == [("dir", "/mx_fault_ag/0/")]

    class ShortClient(Client):
        def key_value_dir_get(self, prefix):
            return []                     # e.g. older server: no listing

    calls.clear()
    comm = fdist.CoordServiceComm(client=ShortClient(), rank=0, world=2,
                                  namespace="mx")
    out = comm._read_votes(0, 1000)
    assert [v["rank"] for v in out] == [0, 1]
    assert [c[0] for c in calls] == ["get", "get"]

    # two default-constructed comms never share keys or barrier names:
    # each instance gets its own construction-sequence namespace, so a
    # heartbeat comm cannot consume the kvstore comm's vote rounds (or
    # collide on the coordination service's single-use barriers)
    a = fdist.CoordServiceComm(client=Client(), rank=0, world=2)
    b = fdist.CoordServiceComm(client=Client(), rank=0, world=2)
    assert a._ns != b._ns
    assert a._key(0, 0) != b._key(0, 0)


def test_coordservice_slow_rank_completes_round_late():
    """A slow-but-alive rank whose peers already timed out at the
    barrier (and raised PeerLostError naming it) must still complete its
    round from the persisted KV votes — the same hang-recovery semantics
    FileComm/InProcessComm provide — instead of raising an unattributed
    PeerLostError even though every vote is readable."""
    store = {"/mx_fault_ag/0/0": '{"rank": 0, "ok": true}',
             "/mx_fault_ag/0/1": '{"rank": 1, "ok": true}'}

    class LateClient:
        def key_value_set(self, key, value):
            store[key] = value

        def wait_at_barrier(self, name, ms):
            raise RuntimeError("DEADLINE_EXCEEDED: barrier timed out")

        def blocking_key_value_get(self, key, ms):
            return store[key]

        def key_value_dir_get(self, prefix):
            return [(k, v) for k, v in store.items()
                    if k.startswith(prefix)]

        def key_value_delete(self, key):
            store.pop(key, None)

    before = prof.get_counter("fault::dist::late_rounds")
    comm = fdist.CoordServiceComm(client=LateClient(), rank=0, world=2,
                                  namespace="mx")
    out = comm.allgather({"rank": 0, "ok": True}, timeout=0.2)
    assert [v["rank"] for v in out] == [0, 1]
    assert prof.get_counter("fault::dist::late_rounds") == before + 1

    # ...but a peer whose vote truly never landed is still named
    store.pop("/mx_fault_ag/1/1", None)

    class DeadPeerClient(LateClient):
        def blocking_key_value_get(self, key, ms):
            if key not in store:
                raise RuntimeError("NOT_FOUND: %s" % key)
            return store[key]

    comm = fdist.CoordServiceComm(client=DeadPeerClient(), rank=0, world=2,
                                  namespace="mx")
    comm._round = 1                        # fresh round with no peer vote
    with pytest.raises(fdist.PeerLostError) as ei:
        comm.allgather({"rank": 0, "ok": True}, timeout=0.2)
    assert ei.value.process_indices == (1,)


def test_heartbeat_comm_resolved_lazily(monkeypatch):
    """A Heartbeat created pre-bootstrap (LocalComm world) must pick up
    the multi-process comm once the job is up."""
    fdist.set_default_comm(None)
    try:
        hb = fdist.Heartbeat(every=1, timeout=1)
        assert hb.beat(step=0) is None       # single-process: no-op

        class TwoComm:
            rank, world = 0, 2

            def allgather(self, payload, timeout=None):
                return [payload, {"rank": 1, "step": 0, "t": 0.0}]

        fdist.set_default_comm(TwoComm())    # "bootstrap happened"
        assert len(hb.beat(step=1)) == 2
        assert hb.beats == 1
    finally:
        fdist.set_default_comm(None)


def test_heartbeat_never_shares_default_coordservice_rounds(monkeypatch):
    """A Heartbeat falling back to the ambient comm must NOT consume the
    cached default CoordServiceComm's vote rounds: a beat and a
    coordinated_call reading each other's payloads dies with an opaque
    KeyError and skews rounds forever.  The heartbeat gets a dedicated
    comm on a FIXED namespace (aligned across ranks regardless of when
    each rank first beats)."""
    import jax
    fdist.set_default_comm(None)
    try:
        monkeypatch.setattr(jax, "process_count", lambda: 2)
        monkeypatch.setattr(jax, "process_index", lambda: 0)
        monkeypatch.setattr(fdist, "_coord_client", lambda: object())
        ambient = fdist.default_comm()
        assert isinstance(ambient, fdist.CoordServiceComm)
        hb = fdist.Heartbeat(every=1, timeout=1)
        hc = hb.comm
        assert isinstance(hc, fdist.CoordServiceComm)
        assert hc is not ambient
        assert hc._ns.startswith("mxhb")
        assert hc._ns != ambient._ns
        assert hb.comm is hc                 # stable across beats
        # a re-enabled heartbeat gets a fresh epoch: reusing the first
        # incarnation's namespace would collide with its already-passed
        # single-use barriers and GC'd round keys
        hb2 = fdist.Heartbeat(every=1, timeout=1)
        assert hb2.comm._ns.startswith("mxhb")
        assert hb2.comm._ns != hc._ns
    finally:
        fdist.set_default_comm(None)


def test_dist_env_probe_tolerates_torn_exception_lines():
    """tests/test_dist.py's env-skip probe: workers share the parent's
    stdio unsynchronized, so an exception summary can tear at the
    message boundary ("XlaRuntimeError: " + message on the next line).
    The torn line must be judged by its continuation — not vetoed on the
    empty message — while real regressions and message-less asserts
    still veto."""
    import test_dist as td
    torn = ("Traceback (most recent call last):\n"
            "jaxlib.xla_extension.XlaRuntimeError: \n"
            "INVALID_ARGUMENT: Multiprocess computations aren't "
            "implemented on the CPU backend.\n")
    assert td._env_cannot_dist(torn) is not None
    # an intact marker line still skips
    assert td._env_cannot_dist(
        "RuntimeError: Unable to connect to the coordinator\n") is not None
    # a torn NON-env exception still vetoes
    assert td._env_cannot_dist(
        "TypeError: \n'NoneType' object is not callable\n") is None
    # a message-less assert vetoes even next to env noise
    assert td._env_cannot_dist(
        "AssertionError\nDEADLINE_EXCEEDED\n") is None


# ----------------------------------------------------------------------
# heartbeat / peer health
# ----------------------------------------------------------------------
def test_heartbeat_round_tracks_peers():
    comms = fdist.InProcessComm.create(2)
    before = prof.get_counter("fault::dist::heartbeats")

    def worker(rank, comm):
        hb = fdist.Heartbeat(comm=comm, every=1, timeout=5)
        hb.beat(step=3 + rank)
        return hb

    results, errors = _run_workers(worker)
    assert not errors
    assert results[0].peers[1][0] == 4    # saw peer 1 at step 4
    assert results[1].peers[0][0] == 3
    assert prof.get_counter("fault::dist::heartbeats") == before + 2


def test_heartbeat_silent_peer_raises_peer_lost():
    comms = fdist.InProcessComm.create(2)
    hb = fdist.Heartbeat(comm=comms[0], every=1, timeout=0.15)
    before = prof.get_counter("fault::dist::peer_lost")
    with pytest.raises(fdist.PeerLostError) as ei:
        hb.beat(step=0)
    assert ei.value.process_indices == (1,)
    assert prof.get_counter("fault::dist::peer_lost") == before + 1


def test_injected_peer_hang_detected_by_peer():
    """The armed ``peer_hang`` fault delays the victim's (rank 1's) vote
    past the timeout; the healthy worker's beat raises PeerLostError
    naming it.  The injection registry is process-global, so the victim
    arms the fault itself and signals the healthy rank to start only
    after the hang began — the fault deterministically fires on rank 1.
    """
    hung = threading.Event()
    seen = {}

    def worker(rank, comm):
        hb = fdist.Heartbeat(comm=comm, every=1, timeout=0.3)
        if rank == 0:
            assert hung.wait(5)
            time.sleep(0.1)             # victim is mid-hang (sleeps 0.5s)
            with pytest.raises(fdist.PeerLostError) as ei:
                hb.beat(step=0)         # deadline 0.4s < victim's vote
            seen[0] = ei.value.process_indices
        else:
            fault.inject("peer_hang", at=1)
            hung.set()                  # consumed within microseconds...
            hb.beat(step=0)             # ...as beat() hits the seam here
        return hb

    results, errors = _run_workers(worker)
    assert not errors
    assert seen[0] == (1,)
    assert fault.stats().get("peer_hang") == 1


def test_trainer_step_beats_installed_heartbeat():
    class OneRankComm:           # world=1 but NOT LocalComm, so beat runs
        rank, world = 0, 1

        def allgather(self, payload, timeout=None):
            return [payload]

    hb = fdist.enable_step_heartbeat(comm=OneRankComm(), every=1,
                                     timeout=1)
    try:
        from mxnet_tpu import autograd, gluon
        net = nn.Dense(2, in_units=3)
        net.initialize()
        trainer = gluon.Trainer(net.collect_params(), "sgd",
                                {"learning_rate": 0.1}, kvstore=None)
        x = mx.np.ones((2, 3))
        with autograd.record():
            loss = net(x).sum()
        loss.backward()
        trainer.step(2)
        assert hb.beats == 1
    finally:
        fdist.disable_step_heartbeat()


def test_trainer_step_inits_kvstore_before_beat():
    """The beat resolves the ambient comm, so it must run after
    Trainer._init_kvstore (whose dist path performs the jax.distributed
    bootstrap) — beating first would query jax pre-bootstrap."""
    seen = {}

    class ProbeComm:
        rank, world = 0, 1

        def allgather(self, payload, timeout=None):
            seen["kv_initialized_at_beat"] = trainer._kv_initialized
            return [payload]

    hb = fdist.enable_step_heartbeat(comm=ProbeComm(), every=1, timeout=1)
    try:
        from mxnet_tpu import autograd, gluon
        net = nn.Dense(2, in_units=3)
        net.initialize()
        trainer = gluon.Trainer(net.collect_params(), "sgd",
                                {"learning_rate": 0.1}, kvstore=None)
        x = mx.np.ones((2, 3))
        with autograd.record():
            loss = net(x).sum()
        loss.backward()
        trainer.step(2)
        assert hb.beats == 1
        assert seen["kv_initialized_at_beat"] is True
    finally:
        fdist.disable_step_heartbeat()


def test_dist_env_skip_probe_vetoed_by_assertion_failure():
    """tests/test_dist.py's environment probe: a rank that died of an
    AssertionError is a regression, not an environment skip — even when
    a surviving rank's teardown emitted DEADLINE_EXCEEDED noise."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "_test_dist_probe",
        os.path.join(os.path.dirname(__file__), "test_dist.py"))
    td = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(td)

    env_only = ("Traceback (most recent call last):\n"
                "  File \"kv.py\", line 1, in <module>\n"
                "jaxlib.xla_extension.XlaRuntimeError: INVALID_ARGUMENT: "
                "Multiprocess computations aren't implemented on the CPU "
                "backend.\n")
    assert td._env_cannot_dist(env_only) is not None

    mixed = ("Traceback (most recent call last):\n"
             "  File \"kv.py\", line 9, in <module>\n"
             "AssertionError: rank 0 sum mismatch\n"
             "jaxlib.xla_extension.XlaRuntimeError: DEADLINE_EXCEEDED: "
             "barrier timed out\n")
    assert td._env_cannot_dist(mixed) is None
    # a message-less `assert` ends its traceback with a bare
    # "AssertionError" line (no colon) — it must veto the skip too
    bare = ("Traceback (most recent call last):\n"
            "  File \"kv.py\", line 9, in <module>\n"
            "AssertionError\n"
            "jaxlib.xla_extension.XlaRuntimeError: DEADLINE_EXCEEDED: "
            "barrier timed out\n")
    assert td._env_cannot_dist(bare) is None
    # ANY non-environment exception is a regression, not just
    # AssertionError: a TypeError from a refactor must veto the skip
    # even when the surviving rank aborted with an env-looking error
    typeerr = ("TypeError: push() missing 1 required argument\n"
               "jaxlib.xla_extension.XlaRuntimeError: DEADLINE_EXCEEDED: "
               "barrier timed out\n")
    assert td._env_cannot_dist(typeerr) is None
    # non-exception mention of a marker (retry-warning noise) never skips
    noise = "retrying: saw DEADLINE_EXCEEDED from coordinator\n"
    assert td._env_cannot_dist(noise) is None


# ----------------------------------------------------------------------
# maintenance notices (stub HTTP metadata server)
# ----------------------------------------------------------------------
class _MetaHandler(http.server.BaseHTTPRequestHandler):
    value = "NONE"

    def do_GET(self):
        assert self.headers.get("Metadata-Flavor") == "Google"
        body = type(self).value.encode()
        self.send_response(200)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def log_message(self, *a):
        pass


@pytest.fixture()
def meta_server():
    srv = http.server.HTTPServer(("127.0.0.1", 0), _MetaHandler)
    th = threading.Thread(target=srv.serve_forever, daemon=True)
    th.start()
    _MetaHandler.value = "NONE"
    yield "http://127.0.0.1:%d/maintenance-event" % srv.server_port
    srv.shutdown()
    th.join(timeout=5)


def test_maintenance_poller_fires_once_and_rearms(meta_server):
    events = []
    poller = fdist.MaintenancePoller(url=meta_server, interval=0.01,
                                     on_event=events.append)
    before = prof.get_counter("fault::dist::maintenance_events")
    assert poller.poll_once() == "NONE"
    assert poller.tick() is None
    _MetaHandler.value = "TERMINATE_ON_HOST_MAINTENANCE"
    assert poller.tick() == "TERMINATE_ON_HOST_MAINTENANCE"
    assert poller.tick() is None          # one autosave per pending event
    _MetaHandler.value = "NONE"
    assert poller.tick() is None          # notice cleared -> re-armed
    _MetaHandler.value = "MIGRATE_ON_HOST_MAINTENANCE"
    assert poller.tick() == "MIGRATE_ON_HOST_MAINTENANCE"
    assert events == ["TERMINATE_ON_HOST_MAINTENANCE",
                      "MIGRATE_ON_HOST_MAINTENANCE"]
    assert prof.get_counter("fault::dist::maintenance_events") == before + 2


def test_maintenance_poller_thread_feeds_preemption_autosave(
        meta_server, tmp_path):
    net = nn.Dense(2, in_units=3)
    net.initialize()
    net(mx.np.ones((1, 3)))
    handler = fault.on_preemption(str(tmp_path), net=net,
                                  process_index=None)
    try:
        poller = fdist.MaintenancePoller(url=meta_server, interval=0.01)
        poller.start()
        _MetaHandler.value = "TERMINATE"
        deadline = time.monotonic() + 5
        while handler.fired == 0 and time.monotonic() < deadline:
            time.sleep(0.01)
        poller.stop()
        assert handler.fired == 1
        assert poller.events == 1
        fault.load_snapshot(str(tmp_path), net=net)
    finally:
        handler.uninstall()


def test_maintenance_poller_unreachable_server_is_quiet():
    poller = fdist.MaintenancePoller(url="http://127.0.0.1:9/nope",
                                     http_timeout=0.2)
    assert poller.poll_once() is None
    assert poller.tick() is None


def test_maintenance_blip_does_not_refire_pending_notice(meta_server):
    """A transient metadata-server failure mid-notice must NOT re-arm:
    one pending TERMINATE fires exactly one autosave even if a poll in
    between comes back unreachable."""
    events = []
    poller = fdist.MaintenancePoller(url=meta_server, interval=0.01,
                                     on_event=events.append,
                                     http_timeout=0.2)
    _MetaHandler.value = "TERMINATE"
    assert poller.tick() == "TERMINATE"
    good_url = poller.url
    poller.url = "http://127.0.0.1:9/nope"   # blip: server unreachable
    assert poller.tick() is None
    poller.url = good_url                    # notice still pending
    assert poller.tick() is None             # must not fire again
    assert events == ["TERMINATE"]


def test_injected_maintenance_event_needs_no_server():
    fault.inject("maintenance_event", at=1)
    events = []
    poller = fdist.MaintenancePoller(url="http://127.0.0.1:9/nope",
                                     on_event=events.append,
                                     http_timeout=0.2)
    assert poller.tick() == "TERMINATE_ON_HOST_MAINTENANCE"
    assert events == ["TERMINATE_ON_HOST_MAINTENANCE"]


# ----------------------------------------------------------------------
# resilient bootstrap
# ----------------------------------------------------------------------
@pytest.fixture()
def fake_dist_init(monkeypatch):
    """Replace jax.distributed.initialize with a scriptable fake."""
    import jax
    calls = {"n": 0, "raise": []}

    def fake(coordinator_address=None, num_processes=None, process_id=None,
             **kw):
        calls["n"] += 1
        calls.setdefault("kwargs", []).append(dict(kw))
        if calls["raise"]:
            raise calls["raise"].pop(0)

    monkeypatch.setattr(jax.distributed, "initialize", fake)
    return calls


def test_bootstrap_retries_injected_failure(fake_dist_init):
    fault.inject("dist_bootstrap_fail", at=1)
    before = prof.get_counter("fault::dist::bootstrap_retries")
    assert fdist.initialize("127.0.0.1:1", 2, 0,
                            policy=_fast_policy()) is True
    assert fake_dist_init["n"] == 1       # attempt 1 died at the seam
    assert prof.get_counter("fault::dist::bootstrap_retries") == before + 1


def test_bootstrap_retries_coordinator_unreachable(fake_dist_init):
    fake_dist_init["raise"] = [
        RuntimeError("DEADLINE_EXCEEDED: coordinator unreachable"),
        ConnectionError("refused"),
    ]
    assert fdist.initialize("127.0.0.1:1", 2, 0,
                            policy=_fast_policy()) is True
    assert fake_dist_init["n"] == 3


def test_bootstrap_retries_bare_oserror(fake_dist_init, monkeypatch):
    """socket.gaierror (DNS not yet propagated) is an OSError the
    transient classifier accepts — the attempt loop must actually catch
    it (it is neither RuntimeError nor ConnectionError/TimeoutError), not
    let it crash the bootstrap past both the retry and fallback paths."""
    import socket
    monkeypatch.setenv("MXNET_FAULT_BOOTSTRAP_RETRIES", "2")
    monkeypatch.setenv("MXNET_FAULT_BOOTSTRAP_BACKOFF", "0.001")
    monkeypatch.setenv("MXNET_FAULT_BOOTSTRAP_BACKOFF_MAX", "0.002")
    fake_dist_init["raise"] = [
        socket.gaierror(-3, "Temporary failure in name resolution")]
    assert fdist.initialize("127.0.0.1:1", 2, 0) is True
    assert fake_dist_init["n"] == 2       # attempt 1 failed, retried


def test_bootstrap_already_initialized_is_success(fake_dist_init,
                                                  monkeypatch):
    # a live coordination client is what proves the prior init was real
    monkeypatch.setattr(fdist, "_coord_client", lambda: object())
    fake_dist_init["raise"] = [RuntimeError("already initialized")]
    assert fdist.initialize("127.0.0.1:1", 2, 0,
                            policy=_fast_policy()) is True


def test_kvstore_failed_bootstrap_is_retried_on_next_create(monkeypatch):
    """A BootstrapError out of mx.kv.create must leave the join
    retryable: the done-flag is only set on success, so the next
    create() attempts the bootstrap again instead of silently running
    single-process forever."""
    from mxnet_tpu.kvstore import kvstore as kvs
    monkeypatch.setattr(kvs, "_dist_initialized", False)
    monkeypatch.setenv("MX_COORD_ADDR", "127.0.0.1:1")
    monkeypatch.setenv("MX_NUM_WORKERS", "2")
    monkeypatch.setenv("MX_WORKER_ID", "0")
    calls = {"n": 0, "fail": True}

    def fake_init(coordinator_address=None, num_processes=None,
                  process_id=None, **kw):
        calls["n"] += 1
        if calls["fail"]:
            raise fdist.BootstrapError("coordinator down")
        return True

    monkeypatch.setattr(fdist, "initialize", fake_init)
    with pytest.raises(fdist.BootstrapError):
        kvs._maybe_init_distributed()
    assert kvs._dist_initialized is False     # retryable
    calls["fail"] = False
    kvs._maybe_init_distributed()             # coordinator recovered
    assert calls["n"] == 2
    assert kvs._dist_initialized is True


def test_bootstrap_too_late_is_not_success(fake_dist_init, monkeypatch):
    """jax's 'must be called before backends are initialized' refusal
    with NO live coordination client means jax was touched before the
    bootstrap and this process would silently run single-process —
    that must raise, not report membership in the distributed job."""
    monkeypatch.setattr(fdist, "_coord_client", lambda: None)
    fake_dist_init["raise"] = [RuntimeError(
        "jax.distributed.initialize must be called before any backend "
        "is initialized")]
    with pytest.raises(fdist.BootstrapError) as ei:
        fdist.initialize("127.0.0.1:1", 2, 0, policy=_fast_policy())
    assert "before" in str(ei.value)


def test_bootstrap_port_in_use_retries_not_success(fake_dist_init):
    """"Address already in use" (coordinator port in TIME_WAIT after a
    crash) is a TRANSIENT failure that must retry — a bare "already"
    substring match would swallow it as already-initialized and let the
    job proceed un-bootstrapped."""
    fake_dist_init["raise"] = [
        RuntimeError("Failed to bind: Address already in use")]
    assert fdist.initialize("127.0.0.1:1", 2, 0,
                            policy=_fast_policy()) is True
    assert fake_dist_init["n"] == 2       # attempt 1 failed, retried


def test_bootstrap_exhausted_raises_with_diagnostics(fake_dist_init):
    fake_dist_init["raise"] = [
        RuntimeError("UNAVAILABLE: failed to connect")] * 10
    with pytest.raises(fdist.BootstrapError) as ei:
        fdist.initialize("10.0.0.9:1234", 4, 2,
                         policy=_fast_policy(max_retries=2))
    msg = str(ei.value)
    assert "10.0.0.9:1234" in msg and "3 attempts" in msg
    assert "process 2/4" in msg
    assert fake_dist_init["n"] == 3


def test_bootstrap_fallback_degrades_to_single_process(fake_dist_init):
    fake_dist_init["raise"] = [RuntimeError("UNAVAILABLE")] * 10
    before = prof.get_counter("fault::dist::bootstrap_fallbacks")
    assert fdist.initialize("127.0.0.1:1", 2, 0, fallback=True,
                            policy=_fast_policy(max_retries=1)) is False
    assert prof.get_counter("fault::dist::bootstrap_fallbacks") == \
        before + 1


def test_bootstrap_fallback_not_taken_on_config_error(fake_dist_init):
    """The single-process fallback is for transient exhaustion only: a
    non-transient error is a config bug and must still raise, or every
    worker would silently train its own divergent model."""
    fake_dist_init["raise"] = [RuntimeError("invalid process id")]
    with pytest.raises(fdist.BootstrapError):
        fdist.initialize("127.0.0.1:1", 2, 0, fallback=True,
                         policy=_fast_policy(max_retries=3))
    assert fake_dist_init["n"] == 1       # no retry, no fallback


def test_bootstrap_nontransient_error_fails_fast(fake_dist_init):
    fake_dist_init["raise"] = [RuntimeError("invalid process id"),
                               RuntimeError("never reached")]
    with pytest.raises(fdist.BootstrapError):
        fdist.initialize("127.0.0.1:1", 2, 0, policy=_fast_policy())
    assert fake_dist_init["n"] == 1       # no blind retry of a config bug


def test_bootstrap_timeout_env_passes_initialization_timeout(
        fake_dist_init, monkeypatch):
    monkeypatch.setenv("MXNET_FAULT_BOOTSTRAP_TIMEOUT", "7")
    assert fdist.initialize("127.0.0.1:1", 2, 0,
                            policy=_fast_policy()) is True
    assert fake_dist_init["kwargs"][0] == {"initialization_timeout": 7}


# ----------------------------------------------------------------------
# per-process preemption snapshots (shared save_dir)
# ----------------------------------------------------------------------
def _snap_net():
    net = nn.Dense(2, in_units=3)
    net.initialize()
    net(mx.np.ones((1, 3)))
    return net


def test_preemption_snapshots_do_not_clobber_across_processes(tmp_path):
    """Two workers autosaving into one shared directory: distinct
    ``.p<rank>`` manifests/files, and each resume restores its OWN
    weights."""
    save = str(tmp_path)
    nets = {r: _snap_net() for r in (0, 1)}
    for r, net in nets.items():
        net.weight.set_data(mx.np.ones(net.weight.shape) * (r + 1))
        h = fault.PreemptionHandler(save, net=net, process_index=r)
        h.fire(reason="test")
    names = sorted(os.listdir(save))
    assert "preempt.p0.resume.json" in names
    assert "preempt.p1.resume.json" in names
    assert not any(n == "preempt.resume.json" for n in names)
    for r in (0, 1):
        fresh = _snap_net()
        fault.load_snapshot(save, net=fresh, process_index=r)
        onp.testing.assert_allclose(fresh.weight.data().asnumpy(),
                                    onp.ones((2, 3)) * (r + 1))


def test_preemption_snapshot_single_process_keeps_legacy_names(tmp_path):
    net = _snap_net()
    h = fault.PreemptionHandler(str(tmp_path), net=net)
    h.fire(reason="test")
    assert os.path.exists(os.path.join(str(tmp_path),
                                       "preempt.resume.json"))
    fault.load_snapshot(str(tmp_path), net=_snap_net())


def test_load_snapshot_prefers_local_then_legacy(tmp_path):
    """A tagged worker resumes from the un-suffixed single-process
    snapshot when its own is absent — but never from a sibling's."""
    save = str(tmp_path)
    net = _snap_net()
    net.weight.set_data(mx.np.ones(net.weight.shape) * 9)
    fault.PreemptionHandler(save, net=net).fire(reason="legacy")
    fresh = _snap_net()
    fault.load_snapshot(save, net=fresh, process_index=3)  # falls back
    onp.testing.assert_allclose(fresh.weight.data().asnumpy(),
                                onp.ones((2, 3)) * 9)

    other = _snap_net()
    fault.PreemptionHandler(save, net=other, process_index=5).fire()
    os.remove(os.path.join(save, "preempt.resume.json"))
    with pytest.raises(fault.CorruptCheckpointError):
        # p3 has no snapshot and no legacy fallback; p5's must NOT load
        fault.load_snapshot(save, net=_snap_net(), process_index=3)


def test_preemption_generations_are_per_process(tmp_path):
    save = str(tmp_path)
    h0 = fault.PreemptionHandler(save, net=_snap_net(), process_index=0)
    h1 = fault.PreemptionHandler(save, net=_snap_net(), process_index=1)
    h0.fire()
    h1.fire()
    h0.fire()          # prunes only its OWN older generation
    names = sorted(os.listdir(save))
    assert any(n.startswith("preempt.p0.g1.") for n in names)
    assert any(n.startswith("preempt.p1.g0.") for n in names)
    assert not any(n.startswith("preempt.p0.g0.") for n in names)


def test_host_prefix_not_frozen_while_rank_unresolvable(tmp_path,
                                                        monkeypatch):
    """An autosave fired BEFORE the rank is resolvable (pre-bootstrap,
    no launcher env) must not pin the handler to the untagged name: once
    the job is up, later fires pick up the ``.p<rank>`` tag instead of
    clobbering siblings in a shared save_dir."""
    monkeypatch.delenv("MX_NUM_WORKERS", raising=False)
    monkeypatch.setattr(fault, "_detect_process_index", lambda: None)
    h = fault.PreemptionHandler(str(tmp_path), net=_snap_net())
    h.fire(reason="early")                 # rank unknown: untagged
    assert os.path.exists(os.path.join(str(tmp_path),
                                       "preempt.resume.json"))
    monkeypatch.setattr(fault, "_detect_process_index", lambda: 2)
    h.fire(reason="late")                  # job up: tagged from now on
    assert os.path.exists(os.path.join(str(tmp_path),
                                       "preempt.p2.resume.json"))
    assert h._host_prefix() == "preempt.p2"


# ----------------------------------------------------------------------
# launcher hardening
# ----------------------------------------------------------------------
def _launch():
    import importlib.util
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "launch.py")
    spec = importlib.util.spec_from_file_location("mx_launch", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_launch_kills_survivors_and_propagates_first_failure():
    import sys
    launch = _launch()
    code = ("import os, sys, time\n"
            "if os.environ['MX_WORKER_ID'] == '1':\n"
            "    sys.exit(3)\n"
            "time.sleep(60)\n")
    t0 = time.monotonic()
    rc = launch.launch_local(3, [sys.executable, "-c", code])
    assert rc == 3
    assert time.monotonic() - t0 < 30     # survivors were terminated


def test_launch_timeout_kills_job():
    import sys
    launch = _launch()
    code = "import time; time.sleep(60)"
    t0 = time.monotonic()
    rc = launch.launch_local(2, [sys.executable, "-c", code], timeout=1.5)
    assert rc == 124
    assert time.monotonic() - t0 < 30


def test_launch_all_ok_returns_zero():
    import sys
    launch = _launch()
    rc = launch.launch_local(2, [sys.executable, "-c", "pass"])
    assert rc == 0


def test_launch_refuses_local_workers_that_would_share_tpu_chips(
        monkeypatch):
    """One process per chip: -n 2 on a TPU host is refused with a
    message that says why, unless the workers are CPU by environment;
    one worker, or a host with no chip, launches as ever."""
    import sys
    launch = _launch()
    monkeypatch.setattr(launch, "_host_tpu_chips", lambda: 4)
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    with pytest.raises(SystemExit, match="one process per chip"):
        launch.launch_local(2, [sys.executable, "-c", "pass"])
    launch.check_one_process_per_chip(1)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert launch.launch_local(2, [sys.executable, "-c", "pass"]) == 0
    monkeypatch.delenv("JAX_PLATFORMS")
    monkeypatch.setattr(launch, "_host_tpu_chips", lambda: 0)
    launch.check_one_process_per_chip(2)


def test_launch_relays_worker_lines_untorn():
    """Two workers blasting long lines concurrently: every relayed line
    must arrive whole, never spliced with another rank's bytes — workers
    sharing the parent's stdio tore exception summaries mid-line, which
    broke test_dist's env-skip probe (garbled lines read as genuine
    non-env failures and vetoed the skip)."""
    import re
    import subprocess
    import sys
    code = (
        "import os, sys\n"
        "r = os.environ['MX_WORKER_ID']\n"
        "for i in range(300):\n"
        "    sys.stdout.write('L' + r + ':' + 'x' * 150 + ':END\\n')\n"
        "    sys.stdout.flush()\n")
    launcher = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "launch.py")
    r = subprocess.run(
        [sys.executable, launcher, "-n", "2", sys.executable, "-c", code],
        capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stdout[-500:] + r.stderr[-500:]
    lines = [ln for ln in r.stdout.splitlines() if ln.startswith("L")]
    assert len(lines) == 600, len(lines)
    ok = re.compile(r"^L[01]:x{150}:END$")
    torn = [ln for ln in lines if not ok.match(ln)]
    assert not torn, torn[:5]


def test_launch_relay_flushes_stalled_partial_line():
    """A rank hung mid-write must surface its last (unterminated)
    diagnostic DURING the hang — the relay flushes a partial line after
    its idle deadline instead of withholding it until timeout/EOF."""
    import io
    launch = _launch()
    rfd, wfd = os.pipe()
    out = io.BytesIO()
    reader = os.fdopen(rfd, "rb", 0)
    t = threading.Thread(target=launch._relay,
                         args=(reader, out), kwargs={"idle_flush": 0.2},
                         daemon=True)
    t.start()
    try:
        os.write(wfd, b"rank 0: joining barrier ...")   # no newline
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline and not out.getvalue():
            time.sleep(0.05)
        assert b"joining barrier" in out.getvalue()     # visible mid-hang
    finally:
        os.close(wfd)
        t.join(timeout=5)


# ----------------------------------------------------------------------
# DCN/XLA transient classification (classify_xla_error)
# ----------------------------------------------------------------------
class XlaRuntimeError(RuntimeError):
    """Stub carrying the REAL type's name — classify_xla_error matches
    on mro type names, so canned messages test without jaxlib internals
    (and the real jaxlib.xla_extension.XlaRuntimeError matches the same
    way)."""


def test_classify_xla_transient_messages():
    for msg in (
            "UNAVAILABLE: connection reset by peer",
            "DEADLINE_EXCEEDED: operation timed out after 60s",
            "ABORTED: coordination service shutting down",
            "INTERNAL: Socket closed while reading gRPC frame",
            "INTERNAL: failed to connect to remote host 10.0.0.7",
            "Connection reset by peer (os error 104)",
    ):
        assert fdist.classify_xla_error(XlaRuntimeError(msg)) == \
            "transient", msg


def test_classify_xla_fatal_messages():
    for msg in (
            "RESOURCE_EXHAUSTED: Out of memory while trying to allocate "
            "17179869184 bytes",
            "INTERNAL: ran out of memory during HBM allocation",
            "INVALID_ARGUMENT: Mismatched shapes f32[8] vs f32[4]",
            "FAILED_PRECONDITION: program not compiled for this topology",
            "INTERNAL: Mosaic failed to lower custom call",
            "UNIMPLEMENTED: collective permute on this backend",
    ):
        assert fdist.classify_xla_error(XlaRuntimeError(msg)) == \
            "fatal", msg


def test_classify_fatal_wins_over_transient():
    # an OOM whose teardown mentions a transient marker must NOT retry
    e = XlaRuntimeError("RESOURCE_EXHAUSTED: Out of memory; "
                        "subsequent sends saw UNAVAILABLE")
    assert fdist.classify_xla_error(e) == "fatal"


def test_classify_non_xla_and_unknown_messages():
    assert fdist.classify_xla_error(ValueError("UNAVAILABLE")) is None
    assert fdist.classify_xla_error(RuntimeError("UNAVAILABLE")) is None
    # an unrecognized XLA message stays unclassified -> caller treats it
    # fatal (never retry a mutation on a guess)
    assert fdist.classify_xla_error(
        XlaRuntimeError("something novel went wrong")) is None


def test_coordinated_call_retries_transient_xla_error():
    """A DCN blip surfaces as XlaRuntimeError (not TransientError) — the
    classifier makes it retryable, and the retry is still COORDINATED:
    both workers re-issue together."""
    gens = {r: fdist.Generation() for r in range(2)}
    calls = {0: 0, 1: 0}

    def worker(rank, comm):
        def fn():
            calls[rank] += 1
            if rank == 0 and calls[0] == 1:
                raise XlaRuntimeError("UNAVAILABLE: connection reset "
                                      "by peer on DCN send")
            return "ok"
        return fdist.coordinated_call(fn, comm=comm, op="xla",
                                      gen=gens[rank],
                                      policy=_fast_policy())

    results, errors = _run_workers(worker)
    assert not errors, errors
    assert calls == {0: 2, 1: 2}          # both re-issued together
    assert gens[0].value == gens[1].value == 1


def test_coordinated_call_xla_oom_aborts_everywhere():
    """OOM is fatal: the failing rank re-raises the real error, its peer
    aborts in the same round — nobody retries."""
    gens = {r: fdist.Generation() for r in range(2)}
    calls = {0: 0, 1: 0}

    def worker(rank, comm):
        def fn():
            calls[rank] += 1
            if rank == 0:
                raise XlaRuntimeError("RESOURCE_EXHAUSTED: Out of "
                                      "memory allocating 2GiB")
            return "ok"
        return fdist.coordinated_call(fn, comm=comm, op="oom",
                                      gen=gens[rank],
                                      policy=_fast_policy())

    results, errors = _run_workers(worker)
    assert set(errors) == {0, 1}
    assert isinstance(errors[0], XlaRuntimeError)
    assert isinstance(errors[1], fdist.CoordinatedAbortError)
    assert "process(es) [0]" in str(errors[1])
    assert calls == {0: 1, 1: 1}          # no retry on either side


def test_coordinated_call_transient_xla_on_mutating_op_aborts():
    """A mid-op DCN failure on a MUTATING op is transient but not
    entry-seam: the round must abort everywhere (a re-run could
    double-apply on the rank that succeeded)."""
    gens = {r: fdist.Generation() for r in range(2)}

    def worker(rank, comm):
        def fn():
            if rank == 0:
                raise XlaRuntimeError("UNAVAILABLE: connection reset")
            return "applied"
        return fdist.coordinated_call(fn, comm=comm, op="mut",
                                      gen=gens[rank], mutating=True,
                                      policy=_fast_policy())

    results, errors = _run_workers(worker)
    assert set(errors) == {0, 1}
    assert isinstance(errors[0], fdist.CoordinatedAbortError)
    assert isinstance(errors[1], fdist.CoordinatedAbortError)


# ----------------------------------------------------------------------
# maintenance notice latch (the elastic drain consumer)
# ----------------------------------------------------------------------
def test_maintenance_pending_latches_and_clears(meta_server):
    poller = fdist.MaintenancePoller(url=meta_server, interval=0.01,
                                     on_event=lambda ev: None)
    assert poller.pending() is None
    _MetaHandler.value = "TERMINATE_ON_HOST_MAINTENANCE"
    poller.tick()
    assert poller.pending() == "TERMINATE_ON_HOST_MAINTENANCE"
    poller.tick()                          # still pending, no re-fire
    assert poller.pending() == "TERMINATE_ON_HOST_MAINTENANCE"
    _MetaHandler.value = "NONE"
    poller.tick()
    assert poller.pending() is None        # cleared -> re-armed


# ----------------------------------------------------------------------
# launcher --elastic (survivors outlive a preemption)
# ----------------------------------------------------------------------
def test_launch_elastic_signal_death_keeps_survivors():
    """A SIGKILLed worker (the shape of a hard preemption) must NOT take
    the elastic fleet down: the survivors run to completion and the job
    exits 0."""
    import sys
    launch = _launch()
    code = ("import os, signal, time\n"
            "if os.environ['MX_WORKER_ID'] == '1':\n"
            "    os.kill(os.getpid(), signal.SIGKILL)\n"
            "time.sleep(1.5)\n"
            "print('survivor', os.environ['MX_WORKER_ID'], 'done')\n")
    t0 = time.monotonic()
    rc = launch.launch_local(3, [sys.executable, "-c", code], elastic=True)
    assert rc == 0
    assert time.monotonic() - t0 >= 1.4   # survivors actually finished


def test_launch_elastic_exit_code_failure_still_fatal():
    """--elastic forgives signals, not real failures: a worker EXITING
    nonzero (e.g. a missed chaos defense) still tears the job down and
    propagates its code."""
    import sys
    launch = _launch()
    code = ("import os, sys, time\n"
            "if os.environ['MX_WORKER_ID'] == '1':\n"
            "    sys.exit(3)\n"
            "time.sleep(60)\n")
    t0 = time.monotonic()
    rc = launch.launch_local(3, [sys.executable, "-c", code], elastic=True)
    assert rc == 3
    assert time.monotonic() - t0 < 30     # survivors were terminated


def test_launch_elastic_all_preempted_is_failure():
    """Every worker preempted, nobody finished: that job did NOT
    succeed, elastic or not."""
    import sys
    launch = _launch()
    code = "import os, signal; os.kill(os.getpid(), signal.SIGKILL)"
    rc = launch.launch_local(2, [sys.executable, "-c", code], elastic=True)
    assert rc == 1


# ----------------------------------------------------------------------
# coordinated pipeline launch (parallel/pipeline.py — the mxlint R1
# finding: stage transfers must ride the same seam as kvstore/ring)
# ----------------------------------------------------------------------
def _pipeline_on(rank, comm, gen, stage, mutating=False,
                 schedule="gpipe", vjp=False):
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.parallel.pipeline import pipeline_apply, pipeline_vjp

    mesh = jax.sharding.Mesh(onp.array([jax.devices()[rank]]), ("pp",))
    D = 4
    ws = jnp.ones((1, D, D), jnp.float32)
    x = jnp.ones((4, D), jnp.float32)
    if vjp:
        y, _, _ = pipeline_vjp(stage, ws, x, jnp.ones_like(x), mesh,
                               num_microbatches=2, mutating=mutating,
                               schedule=schedule, _comm=comm, _gen=gen)
        return y
    return pipeline_apply(stage, ws, x, mesh, num_microbatches=2,
                          mutating=mutating, schedule=schedule,
                          _comm=comm, _gen=gen)


@pytest.mark.parametrize("schedule,vjp", [("gpipe", False),
                                          ("1f1b", False),
                                          ("1f1b", True)])
def test_pipeline_transient_entry_failure_reissues_together(schedule,
                                                            vjp):
    """An entry-seam fault during a pipeline step makes EVERY worker
    bump the generation and re-issue the stage-transfer collectives
    together (the healthy worker discards its result) — the exact
    kvstore/ring protocol, on every pipeline schedule and on the
    training (pipeline_vjp) path, which the new schedules inherit
    through the shared ``_launch`` seam."""
    gens = {r: fdist.Generation() for r in range(2)}
    before = prof.get_counter("fault::dist::coordinated_retries")
    fault.inject("collective_fail", op="pipeline", at=1)

    def worker(rank, comm):
        return _pipeline_on(rank, comm, gens[rank],
                            lambda w, xx: xx @ w,
                            schedule=schedule, vjp=vjp)

    results, errors = _run_workers(worker)
    assert not errors
    # ones @ ones over D=4: 4x4 of 4.0 on both ranks, at generation 1
    assert onp.allclose(onp.asarray(results[0]), 4.0)
    assert onp.allclose(onp.asarray(results[1]), 4.0)
    assert gens[0].value == 1 and gens[1].value == 1
    assert prof.get_counter("fault::dist::coordinated_retries") \
        >= before + 2


@pytest.mark.parametrize("schedule,vjp", [("gpipe", False),
                                          ("1f1b", False),
                                          ("1f1b", True)])
def test_pipeline_mutating_midop_failure_aborts_everywhere(schedule,
                                                           vjp):
    """A mid-op (non-entry) failure on a mutating pipeline step must
    abort every worker — one rank's stages may already have applied
    their mutation, so a coordinated re-issue would double-apply it.
    Inherited by the 1F1B schedules and the pipeline_vjp training path."""
    gens = {r: fdist.Generation() for r in range(2)}

    def worker(rank, comm):
        def stage(w, xx):
            if rank == 0:
                raise fault.TransientError("mid-op failure in stage")
            return xx @ w
        return _pipeline_on(rank, comm, gens[rank], stage, mutating=True,
                            schedule=schedule, vjp=vjp)

    results, errors = _run_workers(worker)
    assert set(errors) == {0, 1}
    for r in (0, 1):
        assert isinstance(errors[r], fdist.CoordinatedAbortError), errors
    assert isinstance(errors[0].__cause__, fault.TransientError)
    assert "process(es) [0]" in str(errors[1])


def test_local_comm_mutating_op_keeps_entry_seam_rule():
    """The degenerate LocalComm path honors the same entry-seam rule as
    a real comm (the mxlint R3 finding): a mutating op never re-runs
    after a mid-op transient, but an entry-seam InjectedFault — raised
    before any state mutation — still retries."""
    calls = [0]

    def midop():
        calls[0] += 1
        raise fault.TransientError("after the entry seam")

    with pytest.raises(fault.TransientError):
        fdist.coordinated_call(midop, comm=fdist.LocalComm(), op="t",
                               mutating=True, policy=_fast_policy())
    assert calls[0] == 1  # no solo mid-op re-run of a mutation

    entry_calls = [0]

    def entry():
        entry_calls[0] += 1
        if entry_calls[0] == 1:
            raise fault.InjectedFault("entry-seam fault")
        return "ok"

    assert fdist.coordinated_call(entry, comm=fdist.LocalComm(), op="t",
                                  mutating=True,
                                  policy=_fast_policy()) == "ok"
    assert entry_calls[0] == 2


# ----------------------------------------------------------------------
# step-granularity consensus (StepLease): fault tolerance free on the
# success path
# ----------------------------------------------------------------------
def _lease_world(world=2, rearm=1):
    """Per-rank Heartbeat+StepLease over InProcessComm endpoints, plus a
    SEPARATE op-comm set whose round counters prove (non-)voting."""
    hb_comms = fdist.InProcessComm.create(world)
    op_comms = fdist.InProcessComm.create(world)
    gens = [fdist.Generation() for _ in range(world)]
    hbs = [fdist.Heartbeat(comm=hb_comms[r], every=1, timeout=5)
           for r in range(world)]
    leases = []
    for r in range(world):
        lease = fdist.StepLease(heartbeat=hbs[r], gen=gens[r],
                                rearm=rearm)
        hbs[r].lease = lease
        leases.append(lease)
    return hb_comms, op_comms, gens, hbs, leases


def test_lease_success_path_issues_zero_per_op_rounds():
    """The tentpole claim as a unit test: with the lease ACTIVE, K
    coordinated ops per step issue ZERO per-op vote rounds (the op
    comm's round counter never moves; ``fault::dist::vote_rounds``
    stays flat) and the step pays exactly its one boundary beat —
    covered-op accounting lands in ``fault::dist::lease_ops``."""
    world, K = 2, 4
    hb_comms, op_comms, gens, hbs, leases = _lease_world(world)
    rounds_before = prof.get_counter("fault::dist::vote_rounds")
    ops_before = prof.get_counter("fault::dist::lease_ops")

    def worker(rank, _comm):
        hbs[rank].beat(step=0)            # unanimous handshake
        assert leases[rank].active()
        out = [fdist.coordinated_call(
            lambda k=k: "ok%d" % k, comm=op_comms[rank], op="op%d" % k,
            gen=gens[rank], policy=_fast_policy(),
            lease=leases[rank]) for k in range(K)]
        hbs[rank].beat(step=1)            # the aggregate vote
        return out

    results, errors = _run_workers(worker, world=world)
    assert not errors
    assert results[0] == ["ok%d" % k for k in range(K)]
    assert [c._round for c in op_comms] == [0, 0]   # never voted per-op
    assert [c._round for c in hb_comms] == [2, 2]   # handshake + 1 beat
    assert prof.get_counter("fault::dist::vote_rounds") == rounds_before
    assert prof.get_counter("fault::dist::lease_ops") \
        == ops_before + world * K
    assert gens[0].value == gens[1].value == 0


def test_lease_failure_escalates_aborts_everywhere_and_rearms():
    """A covered op failing on one rank revokes the lease on EVERY rank
    in the same beat round: CoordinatedAbortError everywhere (the local
    error chained on the failing rank), one shared generation bump, no
    re-issue of the covered op — then per-op voting resumes (escalated
    mode) until a clean beat re-arms the lease."""
    world = 2
    hb_comms, op_comms, gens, hbs, leases = _lease_world(world)
    calls = {0: 0, 1: 0}

    def worker(rank, _comm):
        hbs[rank].beat(step=0)
        aborted = None
        try:
            def fn():
                calls[rank] += 1
                if rank == 0:
                    raise fault.TransientError("covered-op failure")
                return "applied"
            fdist.coordinated_call(fn, comm=op_comms[rank], op="bad",
                                   gen=gens[rank], policy=_fast_policy(),
                                   lease=leases[rank])
            hbs[rank].beat(step=1)  # rank 1 learns of the flag here
        except fdist.CoordinatedAbortError as e:
            aborted = e
        assert aborted is not None, "rank %d never aborted" % rank
        assert not leases[rank].active()
        # escalated mode: the next op votes per-op again
        before = op_comms[rank]._round
        out = fdist.coordinated_call(
            lambda: "post", comm=op_comms[rank], op="post",
            gen=gens[rank], policy=_fast_policy(), lease=leases[rank])
        assert out == "post" and op_comms[rank]._round == before + 1
        hbs[rank].beat(step=2)  # clean beat: re-arms (rearm=1)
        assert leases[rank].active()
        return aborted

    results, errors = _run_workers(worker, world=world)
    assert not errors
    # nobody re-issued the covered op (an advanced peer may have applied
    # it — the no-double-apply rule), and both gens bumped equally from
    # the same revocation round
    assert calls == {0: 1, 1: 1}
    assert gens[0].value == gens[1].value == 1
    assert isinstance(results[0].__cause__, fault.TransientError)
    assert "process(es) [0]" in str(results[1])


def test_lease_mutating_op_never_reissued_after_peer_advanced():
    """The nasty window from the issue: rank 1 optimistically applies
    ops k and k+1 while rank 0 fails op k — the abort must leave rank
    1's applies at exactly one each (never re-run) and rank 0's failed
    op never applied anywhere."""
    world = 2
    hb_comms, op_comms, gens, hbs, leases = _lease_world(world)
    applied = {0: 0, 1: 0}

    def worker(rank, _comm):
        hbs[rank].beat(step=0)
        aborted = False
        try:
            for k in range(2):
                def fn(k=k):
                    if rank == 0 and k == 0:
                        raise fault.TransientError("fail before apply")
                    applied[rank] += 1
                    return "applied"
                fdist.coordinated_call(fn, comm=op_comms[rank],
                                       op="op%d" % k, gen=gens[rank],
                                       policy=_fast_policy(),
                                       mutating=True, lease=leases[rank])
            hbs[rank].beat(step=1)
        except fdist.CoordinatedAbortError:
            aborted = True
        assert aborted
        return applied[rank]

    results, errors = _run_workers(worker, world=world)
    assert not errors
    assert applied[0] == 0       # the failed op was never applied there
    assert applied[1] == 2       # ...and rank 1's optimistic applies stand
    assert gens[0].value == gens[1].value == 1


def test_lease_mixed_mode_world_hard_fails_fast():
    """A rank that never opts in must hard-fail the opted-in ranks at
    the FIRST beat (LeaseConfigError naming it) — not hang their per-op
    votes against a peer that never joins a round."""
    world = 2
    comms = fdist.InProcessComm.create(world)
    gens = [fdist.Generation() for _ in range(world)]
    hb0 = fdist.Heartbeat(comm=comms[0], every=1, timeout=5)
    hb0.lease = fdist.StepLease(heartbeat=hb0, gen=gens[0])
    hb1 = fdist.Heartbeat(comm=comms[1], every=1, timeout=5)  # no lease
    t0 = time.monotonic()

    def worker(rank, _comm):
        if rank == 0:
            with pytest.raises(fdist.LeaseConfigError) as ei:
                hb0.beat(step=0)
            assert "process(es) [1]" in str(ei.value)
            # revoked, not merely never-activated: a supervisor that
            # catches the config error must not find the fast lane open
            assert hb0.lease.state() == "revoked"
            return "failed-fast"
        hb1.beat(step=0)
        return "plain"

    results, errors = _run_workers(worker, world=world)
    assert not errors
    assert results[0] == "failed-fast"
    assert time.monotonic() - t0 < 4.0  # no consensus-timeout hang


def test_lease_fatal_error_reraises_as_itself_on_failing_rank():
    """The per-op fatal rule survives amortization: a non-transient
    local failure (OOM, shape bug) under the lease still flags the
    fleet — peers abort with CoordinatedAbortError — but the FAILING
    rank re-raises the real error, so a deterministically broken rank
    exits identifiably instead of looping its supervisor's
    resize-and-retry path."""
    world = 2
    hb_comms, op_comms, gens, hbs, leases = _lease_world(world)

    def worker(rank, _comm):
        hbs[rank].beat(step=0)
        try:
            def fn():
                if rank == 0:
                    raise ValueError("deterministic shape bug")
                return "applied"
            fdist.coordinated_call(fn, comm=op_comms[rank], op="bad",
                                   gen=gens[rank], policy=_fast_policy(),
                                   lease=leases[rank])
            hbs[rank].beat(step=1)
        except Exception as e:  # noqa: BLE001 — the error IS the assert
            return e
        return None

    results, errors = _run_workers(worker, world=world)
    assert not errors
    assert isinstance(results[0], ValueError)          # the real error
    assert isinstance(results[1], fdist.CoordinatedAbortError)
    assert gens[0].value == gens[1].value == 1
    assert not leases[0].active() and not leases[1].active()


def test_lease_gen_mismatch_beat_revokes_before_raising():
    """A divergence detected at the beat must CLOSE the zero-vote fast
    lane before raising: a caller that catches the beat error and keeps
    stepping falls back to per-op voting (whose own gen check re-raises
    every call) instead of applying updates on diverged worlds."""
    lease = fdist.StepLease(heartbeat=None, gen=fdist.Generation(),
                            rearm=1)
    lease._s["state"] = "active"
    votes = [{"rank": 0, "lease": {"want": True, "gen": 0, "ops": 0,
                                   "drop": None, "fail": None}},
             {"rank": 1, "lease": {"want": True, "gen": 1, "ops": 0,
                                   "drop": None, "fail": None}}]
    with pytest.raises(fdist.GenerationMismatchError):
        lease.on_beat(votes)
    assert not lease.active()
    assert lease.state() == "revoked"


def test_lease_ops_counter_not_double_counted_on_failed_beat():
    """The covered-op window is only consumed by a COMPLETED beat
    round: a beat whose allgather raises (peer lost) leaves the window
    intact and uncounted, so the recovery beat counts it exactly once."""
    comms = fdist.InProcessComm.create(2)
    hb = fdist.Heartbeat(comm=comms[0], every=1, timeout=0.5)
    lease = fdist.StepLease(heartbeat=hb, gen=fdist.Generation(),
                            rearm=1)
    hb.lease = lease
    lease._s["state"] = "active"
    before = prof.get_counter("fault::dist::lease_ops")
    for _ in range(3):
        lease.note_op("op")
    with pytest.raises(fdist.PeerLostError):
        hb.beat(step=0)  # peer never votes: round incomplete
    assert prof.get_counter("fault::dist::lease_ops") == before

    # the peer completes round 0 late from the persisted vote, then
    # posts its round-1 vote; this rank's NEXT beat completes and the
    # window is counted exactly once
    def peer():
        hb2 = fdist.Heartbeat(comm=comms[1], every=1, timeout=5)
        hb2.lease = fdist.StepLease(heartbeat=hb2,
                                    gen=fdist.Generation(), rearm=1)
        hb2.beat(step=0)
        hb2.beat(step=1)
    t = threading.Thread(target=peer)
    t.start()
    time.sleep(0.2)  # let the peer post its round-1 vote
    hb.beat(step=1)
    t.join(timeout=10)
    assert prof.get_counter("fault::dist::lease_ops") == before + 3


def test_lease_enable_requires_every_step_heartbeat():
    hb = fdist.Heartbeat(comm=fdist.InProcessComm.create(1)[0], every=3,
                         timeout=1)
    with pytest.raises(ValueError):
        fdist.enable_step_lease(heartbeat=hb)


def test_lease_env_knob_attaches_to_step_heartbeat(monkeypatch):
    monkeypatch.setenv("MXNET_FAULT_LEASE", "1")
    hb = fdist.enable_step_heartbeat(comm=fdist.LocalComm())
    try:
        assert hb.lease is not None
        assert fdist.step_lease() is hb.lease
        assert hb.lease.state() == "pending"  # activates via handshake
    finally:
        fdist.disable_step_heartbeat()
    assert fdist.step_lease() is None


def test_preemption_fire_releases_lease_fleet_wide_at_next_beat(
        tmp_path):
    """PreemptionHandler.fire must not keep the lease past the next
    beat — but the firing rank may SURVIVE (live-migration notice), so
    the release is voted: the rank keeps skipping votes (symmetric
    with its peers) until the beat carries its drop flag, where EVERY
    rank deactivates together with no abort and no generation bump."""
    world = 2
    hb_comms, op_comms, gens, hbs, leases = _lease_world(world)

    def activate(rank, _comm):
        hbs[rank].beat(step=0)
        return leases[rank].active()

    results, errors = _run_workers(activate, world=world)
    assert not errors and all(results.values())
    fault._set_step_lease(leases[0])
    try:
        handler = fault.PreemptionHandler(str(tmp_path)).install()
        try:
            handler.fire(reason="test")
        finally:
            handler.uninstall()
        # still ACTIVE (still skipping votes — symmetric), drop pending
        assert leases[0].active()
        assert leases[0].payload()["drop"] is not None

        def next_beat(rank, _comm):
            # the surviving rank can even cover one more op safely
            if rank == 0:
                fdist.coordinated_call(
                    lambda: "ok", comm=op_comms[rank], op="tail",
                    gen=gens[rank], policy=_fast_policy(),
                    lease=leases[rank])
            hbs[rank].beat(step=1)  # carries the drop -> fleet release
            return leases[rank].state()

        results, errors = _run_workers(next_beat, world=world)
        assert not errors
        assert results == {0: "revoked", 1: "revoked"}
        assert gens[0].value == gens[1].value == 0  # no abort, no bump
        assert leases[0].payload()["drop"] is None  # flag consumed
    finally:
        fault._set_step_lease(None)


def test_one_sided_disable_step_lease_fails_fast_on_both_sides():
    """disable_step_lease is SPMD-uniform (PR-13 remainder): a mid-run
    one-sided disable must fail FAST with LeaseConfigError at the next
    beat on BOTH sides — the disabled rank's error names itself (the
    detach tombstone sees peers still carrying lease state), the
    still-leased peer's names the missing rank — instead of the
    disabled rank's next per-op vote hanging into a slow
    PeerLostError."""
    world = 2
    hb_comms, op_comms, gens, hbs, leases = _lease_world(world)

    def activate(rank, _comm):
        hbs[rank].beat(step=0)
        return leases[rank].active()

    results, errors = _run_workers(activate, world=world)
    assert not errors and all(results.values())

    # rank 0 one-sidedly disables mid-run, through the public API
    fault._set_step_lease(leases[0])
    fault._DIST_HEARTBEAT = hbs[0]
    try:
        fdist.disable_step_lease()
    finally:
        fault._DIST_HEARTBEAT = None
    assert hbs[0].lease is None and hbs[0]._lease_detached
    t0 = time.monotonic()

    def worker(rank, _comm):
        with pytest.raises(fdist.LeaseConfigError) as ei:
            hbs[rank].beat(step=1)
        return str(ei.value)

    results, errors = _run_workers(worker, world=world)
    assert not errors, errors
    # the disabled rank names ITSELF and the peers still holding on
    assert "rank 0" in results[0] and "process(es) [1]" in results[0]
    assert "disable_step_lease" in results[0]
    # the still-leased peer names the rank that went missing
    assert "process(es) [0]" in results[1]
    assert time.monotonic() - t0 < 4.0  # fail-fast, no consensus hang


def test_uniform_disable_step_lease_clears_tombstone():
    """The legal shape: EVERY rank disables in the same beat window —
    the next beat sees no lease carriers, clears the detach tombstone,
    and the fleet beats on as a plain-heartbeat world."""
    world = 2
    hb_comms, op_comms, gens, hbs, leases = _lease_world(world)

    def activate(rank, _comm):
        hbs[rank].beat(step=0)

    results, errors = _run_workers(activate, world=world)
    assert not errors
    for r in range(world):  # SPMD-uniform disable on every rank
        fault._set_step_lease(leases[r])
        fault._DIST_HEARTBEAT = hbs[r]
        try:
            fdist.disable_step_lease()
        finally:
            fault._DIST_HEARTBEAT = None
        assert hbs[r]._lease_detached

    def worker(rank, _comm):
        hbs[rank].beat(step=1)
        return hbs[rank]._lease_detached

    results, errors = _run_workers(worker, world=world)
    assert not errors, errors
    assert results == {0: False, 1: False}  # tombstones cleared


def test_disable_step_lease_detaches_explicit_heartbeat():
    """disable_step_lease must detach from the heartbeat that CARRIES
    the lease — an explicitly-passed one (enable_step_lease(
    heartbeat=...)) is not _DIST_HEARTBEAT, and leaving hb.lease
    attached would keep peers vote-skipping against this rank with no
    tombstone (the slow-PeerLostError hang the tombstone prevents)."""
    class _HB:
        every = 1
        lease = None

    hb = _HB()
    try:
        lease = fdist.enable_step_lease(heartbeat=hb)
        assert hb.lease is lease
        assert fdist._fault._step_lease() is lease
        assert fdist._fault._DIST_HEARTBEAT is not hb  # not installed
        fdist.disable_step_lease()
        assert hb.lease is None          # the carrier was detached
        assert hb._lease_detached is True  # tombstone armed
        assert fdist._fault._step_lease() is None
    finally:
        fdist._fault._set_step_lease(None)


# ----------------------------------------------------------------------
# ring attention on the DCN seam (the 2-level ring's outer ppermute
# crosses slices: a transient there must re-issue TOGETHER, classified
# by classify_xla_error; fatal errors keep the abort rule)
# ----------------------------------------------------------------------
def _ring2_on(rank, comm, gen):
    """One simulated slice: a (1 dcn x 1 cp) mesh on this worker's own
    device, driving ring_attention_sharded through the coordinated
    seam — the exact call shape of the hierarchical DCN x ICI ring."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.parallel import ring_attention_sharded

    mesh = jax.sharding.Mesh(
        onp.array([jax.devices()[rank]]).reshape(1, 1), ("dcn", "cp"))
    B, H, T, D = 1, 2, 8, 4
    q = jnp.ones((B, H, T, D), jnp.float32)
    return ring_attention_sharded(q, q, q, mesh,
                                  axis_name=("dcn", "cp"), causal=True,
                                  layout="striped", _comm=comm, _gen=gen)


def test_ring2_dcn_entry_fault_reissues_together():
    """An entry-seam fault on the 2-level ring makes EVERY worker bump
    the generation and re-issue the ring together — the kvstore /
    pipeline protocol, now on the hierarchical ring's DCN seam."""
    gens = {r: fdist.Generation() for r in range(2)}
    before = prof.get_counter("fault::dist::coordinated_retries")
    fault.inject("collective_fail", op="ring_attention", at=1)

    results, errors = _run_workers(
        lambda rank, comm: _ring2_on(rank, comm, gens[rank]))
    assert not errors, errors
    for r in (0, 1):
        assert results[r].shape == (1, 2, 8, 4)
    assert gens[0].value == gens[1].value == 1
    assert prof.get_counter("fault::dist::coordinated_retries") \
        >= before + 2


def test_ring2_dcn_transient_xla_reissues_together(monkeypatch):
    """A DCN blip mid-launch surfaces as a raw XlaRuntimeError, not a
    TransientError: classify_xla_error makes it retryable and the
    re-issue is COORDINATED — both slices re-enter the ring at the
    same bumped generation instead of dying (the tentpole's seam)."""
    from mxnet_tpu.parallel import ring as ring_mod

    gens = {r: fdist.Generation() for r in range(2)}
    real = ring_mod._shard_map
    launches = {0: 0, 1: 0}
    lock = threading.Lock()

    def flaky(fn, mesh, in_specs, out_specs):
        def run(*args):
            rank = list(mesh.devices.flat)[0].id
            with lock:
                launches[rank] += 1
                first = launches[rank] == 1
            if first:
                raise XlaRuntimeError(
                    "UNAVAILABLE: connection reset by peer on DCN "
                    "transfer between slices")
            return real(fn, mesh, in_specs, out_specs)(*args)
        return run

    monkeypatch.setattr(ring_mod, "_shard_map", flaky)
    results, errors = _run_workers(
        lambda rank, comm: _ring2_on(rank, comm, gens[rank]))
    assert not errors, errors
    assert launches == {0: 2, 1: 2}        # both re-issued together
    assert gens[0].value == gens[1].value == 1
    for r in (0, 1):
        assert onp.asarray(results[r]).shape == (1, 2, 8, 4)


def test_ring2_dcn_fatal_xla_aborts_everywhere(monkeypatch):
    """classify_xla_error keeps OOM fatal on the ring seam: the failing
    slice re-raises the REAL error (identifiable exit, PR-13 rule), its
    peer aborts in the same round, nobody re-issues — the abort
    semantics the mutating ops rely on are not weakened by making DCN
    transients retryable."""
    from mxnet_tpu.parallel import ring as ring_mod

    gens = {r: fdist.Generation() for r in range(2)}
    real = ring_mod._shard_map
    launches = {0: 0, 1: 0}
    lock = threading.Lock()

    def flaky(fn, mesh, in_specs, out_specs):
        def run(*args):
            rank = list(mesh.devices.flat)[0].id
            with lock:
                launches[rank] += 1
            if rank == 0:
                raise XlaRuntimeError(
                    "RESOURCE_EXHAUSTED: Out of memory allocating the "
                    "K/V superblock")
            return real(fn, mesh, in_specs, out_specs)(*args)
        return run

    monkeypatch.setattr(ring_mod, "_shard_map", flaky)
    results, errors = _run_workers(
        lambda rank, comm: _ring2_on(rank, comm, gens[rank]))
    assert set(errors) == {0, 1}
    assert isinstance(errors[0], XlaRuntimeError)
    assert isinstance(errors[1], fdist.CoordinatedAbortError)
    assert "process(es) [0]" in str(errors[1])
    assert launches == {0: 1, 1: 1}        # no retry on either side
