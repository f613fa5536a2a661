#!/usr/bin/env python
"""Multi-process distributed launcher.

Reference parity: ``tools/launch.py`` (dmlc tracker: spawns N workers + M
servers via local/ssh/mpi/yarn/sge).  The TPU build has no parameter
servers — every process is an SPMD worker coordinated by
``jax.distributed`` — so the launcher spawns ``-n`` worker processes with
the coordination env (MX_COORD_ADDR, MX_NUM_WORKERS, MX_WORKER_ID) that
``mx.kv.create('dist_*')`` / ``mxnet_tpu.parallel`` read at init.

  python tools/launch.py -n 4 python train.py   # 4 local workers
  --launcher local|ssh (-H hostfile)            # ssh: one worker per host
  --timeout SECONDS                             # kill the whole job after
  --elastic                                     # survivors outlive a kill
  --autoscale BOARD_DIR                         # ScalePolicy up-records
                                                # become real joiners

Supervision (the part dmlc's tracker got right and a bare Popen loop
does not): when any worker dies nonzero the remaining workers are
terminated — a dead peer leaves survivors parked in a collective that
can never complete, which without this is an orphaned hung job — and
the launcher exits with the FIRST failing worker's code.  ``--timeout``
bounds the whole job (exit 124, like timeout(1)).

``--elastic`` changes the dead-peer policy to match ``mx.fault.elastic``
resize semantics: a worker killed BY SIGNAL (negative exit — a
preemption, OOM-kill, or the injected ``peer_preempt`` fault) no longer
takes the fleet down; the launcher reports the preemption and keeps
supervising the survivors, which are expected to detect the loss, vote a
resize, and continue at the smaller world size.  A worker that EXITS
nonzero (a real failure, e.g. a missed chaos defense) is still fatal to
the job.  The launcher exits 0 only when at least one worker finished
cleanly and no worker failed.

``--spawn-replacement`` (with ``--elastic``) closes the loop on the
GROW side: each preempted rank is relaunched with
``MX_ELASTIC_REPLACEMENT=1`` in its env, which tells the worker to
enter joiner mode and ``vote_join`` the live job instead of
bootstrapping a fresh one.  Each rank gets ``--respawn-budget``
replacement launches (default 1), spaced by exponential backoff
(``--respawn-backoff`` base seconds, doubling per respawn of that
rank — a host that eats every replacement shouldn't be hammered).  A
rank preempted AGAIN with its budget exhausted is a supervised
failure: the launcher terminates the fleet and exits nonzero, because
with replacement on, repeated death of the same rank is evidence of a
real fault, not scheduling weather.  Other exit-code/signal semantics
are unchanged.

``--autoscale BOARD_DIR`` (with ``--elastic --spawn-replacement``)
closes the other half of the PR 17 loop: ``mx.fault.elastic``'s
``ScalePolicy`` can only *propose* a scale-up — it posts a
``rz/scale/up<seq>`` record on the job's vote board and needs a
supervisor to turn the record into a real process.  This flag makes
the launcher that supervisor: each supervision tick sweeps the board
directory (stdlib-only — the launcher never imports the framework),
claims each new up-record exactly once (a first-writer-wins marker
file, the same link-into-place exclusivity ``FileBoard.claim`` uses,
so N supervisors watching one board launch ONE joiner per proposal),
and spawns a fresh-rank worker through the ``--spawn-replacement``
path (``MX_ELASTIC_REPLACEMENT=1`` — it enters joiner mode and
``vote_join``-s the live job).  Autoscale joiners reuse the respawn
knobs: at most ``--respawn-budget`` joiners total, spaced by
``--respawn-backoff`` exponential backoff; requests beyond the budget
are logged and left unclaimed for another supervisor.

``--flightrec-dir DIR`` arms the black box (``mx.flightrec``): every
worker gets ``MXNET_FLIGHTREC_DIR=DIR`` so terminal events write
per-rank postmortem dumps there, and after the job ends the launcher
runs ``tools/postmortem.py`` over whatever dumps the dead left behind
and prints the merged verdict (first-failing rank, protocol phase of
death, generation skew) to stderr.
"""
from __future__ import annotations

import argparse
import json
import os
import select
import signal
import socket
import subprocess
import sys
import threading
import time


def free_port():
    s = socket.socket()
    s.bind(("", 0))
    p = s.getsockname()[1]
    s.close()
    return p


def _terminate_all(procs, grace=5.0):
    """SIGTERM every live worker (letting mx.fault preemption autosave
    run), then SIGKILL whatever survives the grace period."""
    live = [p for p in procs if p.poll() is None]
    for p in live:
        try:
            p.send_signal(signal.SIGTERM)
        except OSError:
            pass
    deadline = time.monotonic() + grace
    for p in live:
        left = deadline - time.monotonic()
        try:
            p.wait(timeout=max(0.1, left))
        except subprocess.TimeoutExpired:
            try:
                p.kill()
                p.wait(timeout=5.0)
            except (OSError, subprocess.TimeoutExpired):
                pass


def _is_preempt_rc(rc, remote):
    """Exit statuses that mean "killed by the environment", not "failed
    on purpose".  Locally a signal death is a NEGATIVE returncode; over
    ssh the remote shell folds it to 128+signum, and 255 is the ssh
    client's own "connection lost" — on a preemptible fleet that is the
    host going away mid-job."""
    if rc < 0:
        return True
    return remote and (rc == 255 or 128 < rc < 255)


def sweep_scale_requests(board_dir):
    """Stdlib mirror of ``FileBoard.sweep('rz/scale/up')``: the
    ``ScalePolicy`` posts one JSON record per scale-up proposal (the
    board flattens ``/`` to ``@`` in filenames).  Returns sorted
    ``[(seq, payload), ...]``; torn or mid-replace files are skipped,
    like every board sweeper."""
    try:
        names = os.listdir(board_dir)
    except OSError:
        return []
    out = []
    for name in names:
        if not (name.startswith("rz@scale@up") and name.endswith(".json")):
            continue
        seq = name[len("rz@scale@up"):-len(".json")]
        if not seq.isdigit():
            continue
        try:
            with open(os.path.join(board_dir, name)) as f:
                out.append((int(seq), json.load(f)))
        except (OSError, ValueError):
            continue
    return sorted(out)


def claim_scale_request(board_dir, seq):
    """First-writer-wins claim marker next to the up-record — the same
    link-into-place exclusivity ``FileBoard.claim`` plays, so N
    supervisors watching one board turn each proposal into exactly ONE
    joiner process."""
    path = os.path.join(board_dir, "rz@scale@claimed@up%d.json" % seq)
    tmp = "%s.claim.%d" % (path, os.getpid())
    try:
        with open(tmp, "w") as f:
            json.dump({"claimed_by_pid": os.getpid()}, f)
        try:
            os.link(tmp, path)
            return True
        except FileExistsError:
            return False
        except OSError:
            # no hardlinks on this filesystem: O_EXCL create keeps the
            # exclusivity (a crash mid-write can tear the marker, which
            # only costs a duplicate CLAIM attempt, never a dup joiner
            # — the join vote itself dedupes by jid)
            try:
                fd = os.open(path, os.O_CREAT | os.O_EXCL | os.O_WRONLY)
            except FileExistsError:
                return False
            os.close(fd)
            return True
    finally:
        try:
            os.unlink(tmp)
        except OSError:
            pass


def make_autoscale_poll(board_dir, initial_world, budget=1, backoff=0.0):
    """Build the :func:`supervise` ``autoscale`` callable: sweep the
    vote board for ``rz/scale/up<seq>`` records, claim each new one
    once, and schedule a fresh joiner rank per claimed record —
    ``() -> [(rank, delay_seconds), ...]``.  At most ``budget`` joiners
    total (requests beyond it are logged and left unclaimed for another
    supervisor); successive joiners back off exponentially from
    ``backoff`` base seconds, mirroring the respawn policy."""
    state = {"next_rank": int(initial_world), "spawned": 0,
             "seen": set()}

    def poll():
        out = []
        for seq, payload in sweep_scale_requests(board_dir):
            if seq in state["seen"]:
                continue
            if state["spawned"] >= budget:
                state["seen"].add(seq)
                print("launch.py: scale-up request up%d ignored — "
                      "autoscale budget (%d joiner(s)) exhausted; "
                      "leaving it unclaimed" % (seq, budget),
                      file=sys.stderr)
                continue
            state["seen"].add(seq)
            if not claim_scale_request(board_dir, seq):
                continue  # another supervisor owns this proposal
            delay = (backoff * (2 ** state["spawned"])
                     if backoff > 0 else 0.0)
            rank = state["next_rank"]
            state["next_rank"] += 1
            state["spawned"] += 1
            reason = (payload or {}).get("reason") or "?"
            print("launch.py: scale-up request up%d (%s) claimed — "
                  "joiner rank %d%s"
                  % (seq, reason, rank,
                     " in %.1fs" % delay if delay else ""),
                  file=sys.stderr)
            out.append((rank, delay))
        return out

    return poll


def supervise(procs, timeout=None, poll=0.1, elastic=False, remote=False,
              spawn=None, respawn_budget=1, respawn_backoff=0.0,
              autoscale=None):
    """Wait on all workers: first nonzero exit terminates the survivors
    and becomes the launcher's exit code; ``timeout`` (seconds) bounds
    the whole job (exit 124); Ctrl-C terminates everyone (exit 130).

    With ``elastic=True`` a SIGNAL death (the shape of a preemption —
    see :func:`_is_preempt_rc`; ``remote=True`` adds the ssh encodings)
    is reported but NOT propagated: the survivors keep running (they
    are expected to resize via ``mx.fault.elastic``).  Exit-code
    failures stay fatal, and a job where EVERY worker was preempted
    (nobody finished) exits 1.

    ``spawn`` (``--spawn-replacement``): a callable ``spawn(rank) ->
    Popen`` invoked up to ``respawn_budget`` times per preempted rank
    to launch a replacement worker — the process half of an elastic
    GROW (the replacement is expected to ``vote_join`` the live job
    via the rendezvous board).  Respawns of one rank are spaced by
    exponential backoff (``respawn_backoff * 2**prior_respawns``
    seconds, non-blocking — the rest of the fleet is supervised while
    the respawn waits).  A replacement is supervised like any other
    worker; a replacement that exits nonzero is fatal, and a rank
    preempted again with its budget EXHAUSTED is a supervised failure
    (fleet terminated, exit 1) — with replacement on, the same rank
    dying ``respawn_budget + 1`` times is a fault, not weather.

    ``autoscale`` (``--autoscale``): a callable ``() -> [(rank,
    delay), ...]`` (see :func:`make_autoscale_poll`) polled each
    supervision tick; every returned rank is a claimed ``ScalePolicy``
    scale-up request, launched through ``spawn`` after ``delay``
    seconds via the same backoff queue respawns use.  The joiner is
    then supervised like any other worker."""
    deadline = None if timeout is None else time.monotonic() + timeout
    pending = {p.pid: (i, p) for i, p in enumerate(procs)}
    finished_ok = 0
    preempted = 0
    respawns = {}    # rank -> replacements launched so far
    backoff_q = {}   # rank -> monotonic time its next respawn is due
    scale_ranks = set()   # ranks born from autoscale claims
    try:
        while pending or backoff_q:
            for pid, (rank, p) in list(pending.items()):
                rc = p.poll()
                if rc is None:
                    continue
                del pending[pid]
                if rc == 0:
                    finished_ok += 1
                    continue
                if elastic and _is_preempt_rc(rc, remote):
                    preempted += 1
                    print("launch.py: worker %d killed by signal %s — "
                          "elastic: %d surviving worker(s) continue "
                          "(expect a resize to world size %d)"
                          % (rank, -rc if rc < 0 else "(remote rc %d)"
                             % rc, len(pending),
                             len(pending) + finished_ok),
                          file=sys.stderr)
                    if spawn is not None:
                        used = respawns.get(rank, 0)
                        if used >= respawn_budget:
                            print("launch.py: worker %d preempted with "
                                  "its respawn budget exhausted (%d/%d "
                                  "replacement(s) already launched) — "
                                  "supervised failure, terminating %d "
                                  "worker(s)"
                                  % (rank, used, respawn_budget,
                                     len(pending)), file=sys.stderr)
                            _terminate_all(
                                [q for _, q in pending.values()])
                            return 1
                        delay = (respawn_backoff * (2 ** used)
                                 if respawn_backoff > 0 else 0.0)
                        respawns[rank] = used + 1
                        backoff_q[rank] = time.monotonic() + delay
                        if delay:
                            print("launch.py: respawn of worker %d "
                                  "(attempt %d/%d) backing off %.1fs"
                                  % (rank, used + 1, respawn_budget,
                                     delay), file=sys.stderr)
                    continue
                print("launch.py: worker %d exited with code %d — "
                      "terminating %d remaining worker(s)"
                      % (rank, rc, len(pending)), file=sys.stderr)
                _terminate_all([q for _, q in pending.values()])
                return rc
            if autoscale is not None and spawn is not None:
                for rank, delay in autoscale():
                    scale_ranks.add(rank)
                    backoff_q[rank] = time.monotonic() + delay
            for rank, due in list(backoff_q.items()):
                if time.monotonic() >= due:
                    del backoff_q[rank]
                    np = spawn(rank)
                    pending[np.pid] = (rank, np)
                    if rank in scale_ranks:
                        print("launch.py: spawned autoscale joiner "
                              "rank %d (pid %d) — expect it to "
                              "vote_join the live job"
                              % (rank, np.pid), file=sys.stderr)
                    else:
                        print("launch.py: spawned replacement for "
                              "worker %d (pid %d, attempt %d/%d) — "
                              "expect it to join the live job"
                              % (rank, np.pid, respawns.get(rank, 1),
                                 respawn_budget), file=sys.stderr)
            if deadline is not None and time.monotonic() > deadline:
                print("launch.py: job exceeded --timeout %.0fs — "
                      "terminating %d worker(s)"
                      % (timeout, len(pending)), file=sys.stderr)
                _terminate_all([q for _, q in pending.values()])
                return 124
            if pending or backoff_q:
                time.sleep(poll)
        if preempted and not finished_ok:
            print("launch.py: every worker was preempted — no survivor "
                  "finished", file=sys.stderr)
            return 1
        if preempted:
            print("launch.py: elastic job done — %d worker(s) finished, "
                  "%d preempted" % (finished_ok, preempted),
                  file=sys.stderr)
        return 0
    except KeyboardInterrupt:
        _terminate_all([q for _, q in pending.values()])
        return 130


_relay_lock = threading.Lock()


def _relay(pipe, sink, idle_flush=2.0):
    """Pump one worker's merged stdout/stderr to ``sink`` whole lines at
    a time.  Workers sharing the parent's file descriptors directly tear
    each other's lines mid-write — two ranks' tracebacks splice into
    garbage that neither a human nor tests/test_dist.py's env-skip probe
    can parse — so each worker writes a private pipe and the launcher
    serializes complete lines under one lock.

    A partial line that stays unterminated for ``idle_flush`` seconds is
    flushed anyway: a rank hung mid-write ("joining barrier ..." with no
    newline) must show its last diagnostic DURING the hang, not only
    when timeout/EOF finally closes the pipe.  Healthy workers complete
    their lines orders of magnitude faster, so the whole-line guarantee
    holds on every non-stalled path."""
    fd = pipe.fileno()
    buf = b""
    while True:
        ready, _, _ = select.select([fd], [], [], idle_flush)
        if not ready:
            if buf:
                with _relay_lock:
                    sink.write(buf)
                    sink.flush()
                buf = b""
            continue
        try:
            chunk = os.read(fd, 65536)
        except OSError:
            break
        if not chunk:
            break
        buf += chunk
        if b"\n" in buf:
            whole, buf = buf.rsplit(b"\n", 1)
            with _relay_lock:
                sink.write(whole + b"\n")
                sink.flush()
    if buf:
        with _relay_lock:
            sink.write(buf)
            sink.flush()
    pipe.close()


def print_postmortem(dump_dir, sink=None):
    """Merge whatever flightrec dumps the job left in ``dump_dir`` and
    print the verdict (tools/postmortem.py); quiet no-op when the dir
    holds none (a clean job dumps nothing)."""
    sink = sys.stderr if sink is None else sink
    try:
        import postmortem
    except ImportError:
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        import postmortem
    report, _ = postmortem.merge_dir(dump_dir)
    if not report["dumps"] and not report["torn"]:
        return None
    print(postmortem.format_report(report), file=sink)
    return report


def _host_tpu_chips():
    """TPU chips on this host's PCI bus, counted the way jax decides
    whether to try the TPU — without loading libtpu, so the launcher
    itself never holds a chip."""
    from jax._src import hardware_utils
    return hardware_utils.num_available_tpu_chips_and_device_id()[0]


def check_one_process_per_chip(n):
    """Refuse ``-n > 1`` local workers that would open this host's TPU.

    A chip belongs to one process at a time.  Local workers inherit this
    environment and nothing binds worker *i* to chip *i*: each would
    open every chip, the first would get them and the rest would fail
    or hang.  Several chips of one host are driven by ONE process over
    a mesh (``parallel.create_mesh``); several local processes are for
    CPU jobs (``JAX_PLATFORMS=cpu``)."""
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if n <= 1 or (platforms and "tpu" not in platforms.split(",")):
        return
    chips = _host_tpu_chips()
    if chips:
        raise SystemExit(
            "launch.py: refusing -n %d local workers on a host with %d "
            "TPU chip(s): every worker would open all of them and only "
            "the first could (one process per chip; nothing here binds "
            "worker i to chip i). Drive the host's chips from one "
            "process over a mesh, or set JAX_PLATFORMS=cpu for a "
            "multi-process CPU job." % (n, chips))


def launch_local(n, command, server_count=0, timeout=None, elastic=False,
                 spawn_replacement=False, flightrec_dir=None,
                 respawn_budget=1, respawn_backoff=0.0,
                 autoscale_dir=None):
    check_one_process_per_chip(n)
    port = free_port()
    coord = "127.0.0.1:%d" % port
    procs, pumps = [], []
    sink = getattr(sys.stdout, "buffer", sys.stdout)

    def _start(rank, replacement=False):
        env = dict(os.environ)
        env.update({
            "MX_COORD_ADDR": coord,
            "MX_NUM_WORKERS": str(n),
            "MX_WORKER_ID": str(rank),
            # reference env compat (kvstore_server.py bootstrap names)
            "DMLC_ROLE": "worker",
            "DMLC_NUM_WORKER": str(n),
            "DMLC_NUM_SERVER": str(server_count),
            "DMLC_WORKER_ID": str(rank),
        })
        if flightrec_dir is not None:
            env["MXNET_FLIGHTREC_DIR"] = flightrec_dir
        if replacement:
            # the worker reads this to enter joiner mode: skip the
            # initial rendezvous bootstrap, post a join record, and
            # vote_join the LIVE job instead (mx.fault.elastic)
            env["MX_ELASTIC_REPLACEMENT"] = "1"
        p = subprocess.Popen(command, env=env, stdout=subprocess.PIPE,
                             stderr=subprocess.STDOUT)
        t = threading.Thread(target=_relay, args=(p.stdout, sink),
                             daemon=True, name="launch-relay-%d" % rank)
        t.start()
        pumps.append(t)
        return p

    for rank in range(n):
        procs.append(_start(rank))
    spawn = ((lambda rank: _start(rank, replacement=True))
             if spawn_replacement else None)
    autoscale = (make_autoscale_poll(autoscale_dir, n,
                                     budget=respawn_budget,
                                     backoff=respawn_backoff)
                 if autoscale_dir is not None else None)
    rc = supervise(procs, timeout=timeout, elastic=elastic, spawn=spawn,
                   respawn_budget=respawn_budget,
                   respawn_backoff=respawn_backoff,
                   autoscale=autoscale)
    for t in pumps:  # drain trailing output before reporting the job rc
        t.join(timeout=5.0)
    if flightrec_dir is not None:
        # the dead have finished writing (supervise reaped them):
        # merge their black boxes and print the verdict
        print_postmortem(flightrec_dir)
    return rc


def launch_ssh(hostfile, n, command, timeout=None, elastic=False):
    with open(hostfile) as f:
        hosts = [h.strip() for h in f if h.strip()]
    if len(hosts) < n:
        raise ValueError("need %d hosts, hostfile has %d" % (n, len(hosts)))
    coord = "%s:%d" % (hosts[0], 43911)
    procs = []
    for rank in range(n):
        env = ("MX_COORD_ADDR=%s MX_NUM_WORKERS=%d MX_WORKER_ID=%d"
               % (coord, n, rank))
        remote = "cd %s && %s %s" % (os.getcwd(), env, " ".join(command))
        # -tt forces a remote pty: killing the local ssh client (the
        # only handle supervise() holds) hangs the pty up, SIGHUPs the
        # remote job, and actually tears the fleet down — without it
        # _terminate_all would reap the ssh clients and leave the remote
        # workers orphaned in a collective forever
        procs.append(subprocess.Popen(["ssh", "-tt", hosts[rank], remote]))
    return supervise(procs, timeout=timeout, elastic=elastic, remote=True)


def main():
    parser = argparse.ArgumentParser(description="launch distributed job")
    parser.add_argument("-n", "--num-workers", type=int, required=True)
    parser.add_argument("-s", "--num-servers", type=int, default=0,
                        help="accepted for reference CLI compat; the "
                             "collective backend has no server role")
    parser.add_argument("--launcher", default="local",
                        choices=["local", "ssh"])
    parser.add_argument("-H", "--hostfile", default=None)
    parser.add_argument("--timeout", type=float, default=None,
                        help="kill the whole job after this many seconds "
                             "(exit 124)")
    parser.add_argument("--elastic", action="store_true",
                        help="a signal-killed worker does not take the "
                             "fleet down; survivors are expected to "
                             "resize (mx.fault.elastic)")
    parser.add_argument("--spawn-replacement", action="store_true",
                        help="with --elastic: relaunch a preempted "
                             "worker (MX_ELASTIC_REPLACEMENT=1 in its "
                             "env) so it joins the live job via the "
                             "rendezvous board")
    parser.add_argument("--respawn-budget", type=int, default=1,
                        help="with --spawn-replacement: replacement "
                             "launches allowed per rank; a rank "
                             "preempted beyond its budget fails the "
                             "job (default 1)")
    parser.add_argument("--respawn-backoff", type=float, default=1.0,
                        help="with --spawn-replacement: base seconds "
                             "between a rank's preemption and its "
                             "respawn, doubling per respawn of that "
                             "rank (default 1.0; 0 disables)")
    parser.add_argument("--autoscale", default=None, metavar="BOARD_DIR",
                        help="with --elastic --spawn-replacement: watch "
                             "this vote-board dir for ScalePolicy "
                             "rz/scale/up<seq> records and turn each "
                             "one into a real joiner process (claimed "
                             "first-writer-wins; budget/backoff reuse "
                             "--respawn-budget/--respawn-backoff)")
    parser.add_argument("--flightrec-dir", default=None,
                        help="arm the flight recorder: workers dump "
                             "per-rank postmortems here on terminal "
                             "events; the launcher prints the merged "
                             "verdict (tools/postmortem.py) at job end")
    parser.add_argument("command", nargs=argparse.REMAINDER)
    args = parser.parse_args()
    if not args.command:
        parser.error("no command given")
    if args.spawn_replacement and not args.elastic:
        parser.error("--spawn-replacement requires --elastic")
    if args.spawn_replacement and args.launcher != "local":
        parser.error("--spawn-replacement is local-launcher only")
    if args.flightrec_dir and args.launcher != "local":
        parser.error("--flightrec-dir is local-launcher only (ssh "
                     "workers dump to their own filesystems)")
    if args.autoscale and not (args.elastic and args.spawn_replacement):
        parser.error("--autoscale requires --elastic "
                     "--spawn-replacement (a claimed scale-up request "
                     "is launched through the replacement path)")
    if args.autoscale and args.launcher != "local":
        parser.error("--autoscale is local-launcher only")
    if args.launcher == "local":
        sys.exit(launch_local(args.num_workers, args.command,
                              args.num_servers, timeout=args.timeout,
                              elastic=args.elastic,
                              spawn_replacement=args.spawn_replacement,
                              flightrec_dir=args.flightrec_dir,
                              respawn_budget=args.respawn_budget,
                              respawn_backoff=args.respawn_backoff,
                              autoscale_dir=args.autoscale))
    sys.exit(launch_ssh(args.hostfile, args.num_workers, args.command,
                        timeout=args.timeout, elastic=args.elastic))


if __name__ == "__main__":
    main()
