"""Framework-wide instrumentation tests: the event recorder, chrome-trace
JSON validity, pause/resume, counters, and the seams that feed it (op
dispatch, KVStore bytes/compression, Trainer phases, DataLoader/DataIter
throughput).

Reference parity: ``tests/python/unittest/test_profiler.py`` (config,
scopes, pause, counters, dump) over ``src/profiler/profiler.h:256``; the
host-plane recorder here replaces the reference's C++ event aggregation.
"""
import json
import os
import subprocess
import sys
import time

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import profiler
from mxnet_tpu.gluon import nn


@pytest.fixture(autouse=True)
def clean_profiler(tmp_path):
    """Every test gets a stopped, empty recorder writing into tmp_path."""
    profiler.set_state("stop")
    profiler.reset()
    profiler.set_config(filename=str(tmp_path / "profile.json"),
                        profile_all=False, profile_imperative=True,
                        profile_kvstore=True, profile_data=True,
                        profile_memory=False, aggregate_stats=True,
                        continuous_dump=False, max_events=1000000)
    yield
    profiler.set_state("stop")
    profiler.reset()


def _dump_events(kinds=None):
    fn = profiler.dump()
    with open(fn) as f:
        data = json.load(f)
    events = data["traceEvents"]
    if kinds is not None:
        events = [e for e in events if e.get("ph") in kinds]
    return events


# ---------------------------------------------------------------------------
# recorder core
# ---------------------------------------------------------------------------
def test_scope_events_have_real_increasing_timestamps():
    profiler.set_state("run")
    d = profiler.Domain("core")
    with d.new_task("first"):
        time.sleep(0.002)
    with d.new_task("second"):
        time.sleep(0.002)
    events = _dump_events(kinds={"X"})
    byname = {e["name"]: e for e in events}
    assert "core::first" in byname and "core::second" in byname
    first, second = byname["core::first"], byname["core::second"]
    assert first["ts"] > 0 and second["ts"] > 0
    assert first["dur"] >= 2000  # slept >= 2ms, recorded in microseconds
    assert second["ts"] > first["ts"]  # real begin stamps, not all ts=0
    ts = [e["ts"] for e in events]
    assert ts == sorted(ts)


def test_dump_is_valid_chrome_trace(tmp_path):
    profiler.set_state("run")
    with profiler.annotate("valid"):
        pass
    profiler.Domain("v").new_counter("c", 1).increment(2)
    fn = profiler.dump()
    assert os.path.exists(fn)
    with open(fn) as f:
        data = json.load(f)
    assert isinstance(data["traceEvents"], list)
    for ev in data["traceEvents"]:
        assert ev["ph"] in ("X", "C", "i", "M")
        assert "name" in ev and "pid" in ev
        if ev["ph"] == "X":
            assert ev["ts"] >= 0 and ev["dur"] >= 0 and "tid" in ev
        if ev["ph"] == "C":
            assert "value" in ev["args"]


def test_pause_resume_excludes_scopes():
    profiler.set_state("run")
    with profiler.annotate("kept_before"):
        pass
    profiler.pause()
    with profiler.annotate("skipped"):
        pass
    profiler.resume()
    with profiler.annotate("kept_after"):
        pass
    table = profiler.dumps()
    assert "kept_before" in table and "kept_after" in table
    assert "skipped" not in table  # excluded from the aggregate table
    names = {e["name"] for e in _dump_events(kinds={"X"})}
    assert "kept_before" in names and "kept_after" in names
    assert "skipped" not in names  # and from the trace


def test_counters_exported_as_counter_events():
    profiler.set_state("run")
    d = profiler.Domain("mem")
    c = d.new_counter("bytes", 100)
    c.increment(50)
    c.decrement(25)
    c += 5
    cevents = [e for e in _dump_events(kinds={"C"})
               if e["name"] == "mem::bytes"]
    assert cevents, "Counter mutations must emit ph:'C' events"
    values = [e["args"]["value"] for e in cevents]
    assert 150 in values and 125 in values
    assert values[-1] == 130  # final value re-emitted at dump time


def test_event_buffer_cap_counts_drops():
    profiler.set_config(max_events=10)
    profiler.set_state("run")
    for i in range(25):
        profiler.counter_add("cap::demo", 1)
    assert len(profiler._state["events"]) == 10
    events = _dump_events(kinds={"C"})
    dropped = [e for e in events if e["name"] == "profiler::dropped_events"]
    assert dropped and dropped[-1]["args"]["value"] == 15
    assert profiler.get_counters()["cap::demo"] == 25  # totals unaffected


def test_continuous_dump_rotates_buffer(tmp_path):
    fn = str(tmp_path / "rotating.json")
    profiler.set_config(filename=fn, max_events=5, continuous_dump=True)
    profiler.set_state("run")
    for i in range(12):
        profiler.counter_add("rot::demo", 1)
    # the buffer was snapshotted to disk and cleared, never exceeding cap
    assert len(profiler._state["events"]) <= 5
    assert os.path.exists(fn)
    assert profiler.get_counters()["rot::demo"] == 12


def test_state_and_reset():
    assert profiler.state() == "stop"
    profiler.set_state("run")
    assert profiler.state() == "run"
    profiler.set_state("stop")
    with pytest.raises(ValueError):
        profiler.set_state("bogus")


# ---------------------------------------------------------------------------
# framework seams
# ---------------------------------------------------------------------------
def test_op_dispatch_events_recorded():
    profiler.set_state("run")
    a = mx.np.ones((8, 8))
    b = mx.np.ones((8, 8))
    (a @ b + a).wait_to_read()
    ops = [e for e in _dump_events(kinds={"X"}) if e["cat"] == "operator"]
    assert ops, "imperative ops must emit dispatch events"
    assert all(e["dur"] >= 0 and e["ts"] > 0 for e in ops)


def test_profile_imperative_off_records_no_op_events():
    profiler.set_config(profile_imperative=False)
    profiler.set_state("run")
    (mx.np.ones((4, 4)) + 1).wait_to_read()
    ops = [e for e in _dump_events(kinds={"X"}) if e["cat"] == "operator"]
    assert ops == []
    assert not profiler._IMPERATIVE  # hot path sees a single false flag


def test_kvstore_byte_counters():
    profiler.set_state("run")
    kv = mx.kv.create("local")
    kv.init("w", mx.np.zeros((3, 4)))
    kv.push("w", mx.np.ones((3, 4)))
    out = mx.np.zeros((3, 4))
    kv.pull("w", out=out)
    kv.pushpull("w", mx.np.ones((3, 4)), out=out)
    counters = profiler.get_counters()
    nbytes = 3 * 4 * 4  # float32
    assert counters["kvstore::push_bytes"] == 2 * nbytes  # push + pushpull
    assert counters["kvstore::pull_bytes"] == 2 * nbytes  # pull + pushpull
    names = {e["name"] for e in _dump_events(kinds={"X"})}
    assert {"KVStore::push", "KVStore::pull", "KVStore::pushpull",
            "KVStore::reduce"} <= names
    cnames = {e["name"] for e in _dump_events(kinds={"C"})}
    assert "kvstore::push_bytes" in cnames
    assert "kvstore::pull_bytes" in cnames


def test_kvstore_compression_counters():
    profiler.set_state("run")
    kv = mx.kv.create("local")
    kv.set_gradient_compression({"type": "2bit", "threshold": 0.5})
    kv.init("g", mx.np.zeros((8, 8)))
    kv.push("g", mx.np.ones((8, 8)))
    counters = profiler.get_counters()
    assert counters["kvstore::raw_bytes"] == 8 * 8 * 4
    assert counters["kvstore::compressed_bytes"] == 8 * 8 // 4
    assert counters.get("kvstore::compression_ratio") == 16.0


def test_trainer_phase_events():
    profiler.set_state("run")
    net = nn.Dense(2, in_units=3)
    net.initialize()
    trainer = mx.gluon.Trainer(net.collect_params(), "sgd",
                               {"learning_rate": 0.1})
    x = mx.np.ones((4, 3))
    with mx.autograd.record():
        y = net(x)
        loss = (y * y).sum()
    loss.backward()
    trainer.step(4)
    names = {e["name"] for e in _dump_events(kinds={"X"})}
    assert "Trainer::step" in names
    assert "Trainer::update" in names
    assert "autograd::backward" in names
    assert profiler.get_counters()["trainer::steps"] == 1


def test_dataloader_throughput_counters():
    from mxnet_tpu.gluon.data import ArrayDataset, DataLoader
    profiler.set_state("run")
    data = onp.arange(24, dtype="float32").reshape(12, 2)
    label = onp.arange(12, dtype="float32")
    loader = DataLoader(ArrayDataset(data, label), batch_size=4)
    n = sum(1 for _ in loader)
    assert n == 3
    counters = profiler.get_counters()
    assert counters["dataloader::batches"] == 3
    assert counters["dataloader::samples"] == 12
    names = {e["name"] for e in _dump_events(kinds={"X"})}
    assert "DataLoader::next" in names


def test_dataiter_throughput_counters():
    profiler.set_state("run")
    it = mx.io.NDArrayIter(onp.ones((10, 2), dtype="float32"),
                           onp.zeros((10,), dtype="float32"),
                           batch_size=5)
    n = sum(1 for _ in it)
    assert n == 2
    counters = profiler.get_counters()
    assert counters["io::batches"] == 2
    assert counters["io::samples"] == 10


def test_dataiter_padded_batch_counts_real_samples():
    profiler.set_state("run")
    it = mx.io.NDArrayIter(onp.ones((10, 2), dtype="float32"),
                           batch_size=4, last_batch_handle="pad")
    n = sum(1 for _ in it)
    assert n == 3  # 4 + 4 + (2 real, 2 pad)
    assert profiler.get_counters()["io::samples"] == 10  # pad not counted


def test_training_loop_end_to_end_trace(tmp_path):
    """Acceptance: a short train loop with profile_imperative=True dumps a
    trace holding op-dispatch, trainer-phase, and kvstore-counter events
    with real, non-decreasing timestamps."""
    from mxnet_tpu.gluon.data import ArrayDataset, DataLoader
    profiler.set_state("run")
    net = nn.Dense(1, in_units=2)
    net.initialize()
    trainer = mx.gluon.Trainer(net.collect_params(), "sgd",
                               {"learning_rate": 0.05})
    kv = mx.kv.create("local")
    kv.init(0, mx.np.zeros((2,)))
    data = onp.random.rand(8, 2).astype("float32")
    label = onp.random.rand(8, 1).astype("float32")
    for xb, yb in DataLoader(ArrayDataset(data, label), batch_size=4):
        with mx.autograd.record():
            out = net(xb)
            loss = ((out - yb) ** 2).sum()
        loss.backward()
        trainer.step(4)
        kv.push(0, mx.np.ones((2,)))  # simulated comm traffic
    events = _dump_events()
    xs = [e for e in events if e.get("ph") == "X"]
    cats = {e["cat"] for e in xs}
    assert {"operator", "trainer", "kvstore", "data"} <= cats
    cnames = {e["name"] for e in events if e.get("ph") == "C"}
    assert "kvstore::push_bytes" in cnames
    ts = [e["ts"] for e in xs]
    assert ts and ts == sorted(ts) and ts[0] > 0


# ---------------------------------------------------------------------------
# autostart + tooling satellites
# ---------------------------------------------------------------------------
@pytest.mark.slow
def test_autostart_env_dumps_at_exit(tmp_path):
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, MXNET_PROFILER_AUTOSTART="1",
               JAX_PLATFORMS="cpu",
               PYTHONPATH=repo_root + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    code = ("import mxnet_tpu.profiler as p\n"
            "assert p.state() == 'run'\n"
            "with p.annotate('boot'):\n"
            "    pass\n")
    res = subprocess.run([sys.executable, "-c", code], cwd=str(tmp_path),
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert res.returncode == 0, res.stderr
    out = tmp_path / "profile.json"
    assert out.exists()
    with open(out) as f:
        data = json.load(f)
    assert any(e.get("name") == "boot" for e in data["traceEvents"])


def test_trace_summary_tool(tmp_path, capsys):
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "tools"))
    try:
        import trace_summary
    finally:
        sys.path.pop(0)
    profiler.set_state("run")
    with profiler.annotate("summarized_scope"):
        time.sleep(0.001)
    profiler.counter_add("demo::bytes", 4096)
    # PR 16 made instant events 5-tuples carrying args; the summary
    # must digest a current-format trace (regression: the old tool
    # unpacked them as 4-tuples and crashed on telemetry traces)
    profiler.record_instant("watchdog::straggler", cat="telemetry",
                            args={"rank": 2, "z": 3.5})
    profiler.record_instant("watchdog::straggler", cat="telemetry",
                            args={"rank": 1, "z": 4.0})
    profiler.record_instant("bare_marker", cat="marker")
    fn = profiler.dump()
    report = trace_summary.summarize(fn, top=5)
    assert "summarized_scope" in report
    assert "demo::bytes" in report
    assert "4096" in report
    assert "Instant markers" in report
    assert "watchdog::straggler [telemetry]" in report
    # count of 2 and the LAST args rendered for context
    line = [ln for ln in report.splitlines()
            if "watchdog::straggler" in ln][0]
    assert " 2 " in line and '"rank": 1' in line
    assert "bare_marker [marker]" in report
    trace_summary.main([fn, "--top", "3"])
    assert "summarized_scope" in capsys.readouterr().out
