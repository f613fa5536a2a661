"""mx.flightrec — the per-rank black box (PR 18).

Ring semantics, dump schema, and the gated auto-dump path, plus the
two perf bars: zero extra comm rounds (events ride existing seams
only; asserted against ``InProcessComm``'s round counter, the same
oracle the PR 13 lease tests and PR 16 telemetry tests use) and a
cheap record path (a loose smoke bound; no device time rides on it).
"""
import json
import threading
import time

import pytest

from mxnet_tpu import fault_dist as fdist
from mxnet_tpu import flightrec as fr


@pytest.fixture(autouse=True)
def _clean_flightrec(monkeypatch):
    monkeypatch.delenv("MXNET_FLIGHTREC_DIR", raising=False)
    monkeypatch.delenv("MXNET_FLIGHTREC_MAX_DUMPS", raising=False)
    was_cap, was_enabled = fr.capacity(), fr.enabled()
    fr.configure(enabled=True)
    fr.reset()
    yield
    fr.configure(capacity=was_cap, enabled=was_enabled)
    fr.reset()


def test_ring_wraparound():
    fr.configure(capacity=16)
    for i in range(40):
        fr.record("t.ev", step=i)
    evs = fr.events()
    assert len(evs) == 16
    assert [e["step"] for e in evs] == list(range(24, 40))  # oldest first
    assert [e["seq"] for e in evs] == list(range(24, 40))
    snap = fr.snapshot()
    assert snap["seq"] == 40 and snap["dropped"] == 24
    assert snap["capacity"] == 16


def test_events_last_bounds_tail():
    fr.configure(capacity=64)
    for i in range(10):
        fr.record("t.ev", step=i)
    assert [e["step"] for e in fr.events(last=3)] == [7, 8, 9]


def test_disabled_records_nothing():
    fr.configure(capacity=32, enabled=False)
    fr.record("t.ev", step=0)
    assert fr.events() == []
    fr.configure(enabled=True)
    fr.record("t.ev", step=1)
    assert len(fr.events()) == 1


def test_field_names_are_free_form():
    # ``kind`` is positional-only so callers may use any field name
    # that doesn't collide with the envelope (kind/seq/t are reserved)
    fr.configure(capacity=32)
    fr.record("fault.injected", fault="preempt", site="step", op=None)
    ev = fr.events()[-1]
    assert ev["kind"] == "fault.injected" and ev["fault"] == "preempt"


def test_set_context_merges_into_dump(tmp_path):
    fr.set_context(rank=1, world=3)
    fr.set_context(gen=2, world=4)   # later keys win, others persist
    fr.record("step.begin", step=5)
    p = str(tmp_path / "d.json")
    assert fr.dump(path=p, reason="manual") == p
    with open(p) as f:
        d = json.load(f)
    assert d["flightrec"]["context"] == {"rank": 1, "world": 4, "gen": 2}


def test_dump_schema(tmp_path):
    fr.configure(capacity=32)
    fr.record("coord.entry", op="allgather", gen=0)
    p = str(tmp_path / "dump.json")
    try:
        raise RuntimeError("boom")
    except RuntimeError as e:
        assert fr.dump(path=p, reason="unit", exc=e) == p
    with open(p) as f:
        d = json.load(f)
    for key in ("version", "reason", "wall_time", "pid", "rank",
                "world", "flightrec", "providers", "env", "exception",
                "counters"):
        assert key in d, key
    assert d["reason"] == "unit"
    assert any(e["kind"] == "coord.entry" for e in
               d["flightrec"]["events"])
    # the dump itself is the ring's last event (forensic breadcrumb)
    assert d["flightrec"]["events"][-1]["kind"] == "dump"
    assert any("boom" in line for line in d["exception"])


def test_note_terminal_gated_and_budgeted(tmp_path, monkeypatch):
    fr.record("hb.beat", step=0, round=1)
    # no MXNET_FLIGHTREC_DIR: terminal recorded, no dump written
    assert fr.note_terminal("unit_gate") is None
    assert fr.events()[-1]["kind"] == "terminal"
    assert list(tmp_path.iterdir()) == []
    monkeypatch.setenv("MXNET_FLIGHTREC_DIR", str(tmp_path))
    monkeypatch.setenv("MXNET_FLIGHTREC_MAX_DUMPS", "1")
    monkeypatch.setenv("MX_WORKER_ID", "3")
    p = fr.note_terminal("unit_dump")
    assert p == str(tmp_path / "flightrec.rank3.json")
    with open(p) as f:
        assert json.load(f)["rank"] == 3
    # budget spent: further terminals record but don't dump
    assert fr.note_terminal("unit_dump2") is None


def test_provider_fail_soft(tmp_path):
    fr.provide("ok", lambda: {"x": 1})
    fr.provide("boom", lambda: 1 / 0)
    try:
        p = str(tmp_path / "d.json")
        fr.dump(path=p, reason="manual")
        with open(p) as f:
            provs = json.load(f)["providers"]
        assert provs["ok"] == {"x": 1}
        assert provs["boom"].startswith("<provider failed")
    finally:
        fr.provide("ok", None)
        fr.provide("boom", None)


def test_configure_capacity_drops_ring():
    fr.configure(capacity=16)
    for i in range(10):
        fr.record("t.ev", step=i)
    fr.configure(capacity=32)
    assert fr.events() == []
    fr.record("t.ev", step=0)
    assert len(fr.events()) == 1


def test_zero_extra_comm_rounds():
    """The PR bar: recording rides existing seams, so a heartbeat
    fleet's comm round counter is identical with the ring on vs off."""
    world, steps = 2, 6

    def run(with_rec):
        fr.configure(capacity=4096, enabled=with_rec)
        fr.reset()
        comms = fdist.InProcessComm.create(world)
        hbs = [fdist.Heartbeat(comm=comms[r], every=1, timeout=60)
               for r in range(world)]
        start = threading.Barrier(world)

        def work(rank):
            start.wait()
            for t in range(steps):
                hbs[rank].beat(step=t)

        threads = [threading.Thread(target=work, args=(r,))
                   for r in range(world)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return comms[0]._round

    rounds_off = run(False)
    rounds_on = run(True)
    assert rounds_on == rounds_off
    # and with the ring on, the beats actually landed in it
    assert sum(1 for e in fr.events() if e["kind"] == "hb.beat") \
        == world * steps


def test_record_cost_smoke():
    """Loose ceiling so CI noise can't flake it: three dict operations
    under an uncontended lock, in the ring's steady state."""
    fr.configure(capacity=4096)
    for i in range(4096):         # steady state: every slot exists
        fr.record("t.fill", step=i)
    n = 20000
    t0 = time.perf_counter()
    for i in range(n):
        fr.record("t.ev", step=i, gen=0)
    per_ns = (time.perf_counter() - t0) / n * 1e9
    assert per_ns < 50_000, "record() cost %.0f ns/event" % per_ns
