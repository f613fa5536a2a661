"""The build path's spans (``mx.profiler.build_span``): recorded on the
host plane in every process, whether or not a profiler runs — importing
the package, placing parameters, the optimizer's state, the first call
of a training step with jax's own timing of what it traced, lowered,
compiled or loaded inside — bounded, on ``time.monotonic``'s axis; and
the benchmark's readers of that record (``benchmark/chip/readers/
start.py``).  The planned step's spans are in ``test_recompute_plan.py``,
the names as ``TraceAnnotation``s in ``test_trace_names.py``."""
import json
import math
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon, parallel, profiler
from mxnet_tpu.gluon import nn

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHIP = os.path.join(ROOT, "benchmark", "chip")
if CHIP not in sys.path:
    sys.path.insert(0, CHIP)

import common  # noqa: E402

SETUP_METRICS = ["import_s.setup", "state_s.setup", "step_trace_s.setup",
                 "step_compile_s.setup", "step_programs.setup"]


@pytest.fixture(autouse=True)
def empty_record():
    assert profiler.state() == "stop"
    profiler.reset()
    yield
    profiler.reset()


def _toy(units=5):
    mx.np.random.seed(2)
    net = nn.HybridSequential()
    net.add(nn.Dense(units, in_units=3), nn.Dense(2, in_units=units))
    net.initialize()
    step = parallel.TrainStep(
        net, gluon.loss.L2Loss(),
        mx.optimizer.SGD(learning_rate=0.1, momentum=0.9), mesh=None)
    x = mx.np.array(onp.ones((4, 3), "float32"))
    y = mx.np.array(onp.zeros((4, 2), "float32"))
    return step, x, y


def _named(name):
    return [s for s in profiler.build_spans() if s["name"] == name]


def test_first_call_leaves_a_build_with_trace_and_compile_children():
    step, x, y = _toy()
    t_before = time.monotonic()
    step(x, y)
    t_after = time.monotonic()
    spans = profiler.build_spans()
    (build,) = [s for s in spans if s["name"] == "mx.train.step.build"]
    (trace,) = [s for s in spans if s["name"] == "mx.train.step.trace"]
    (compiled,) = [s for s in spans if s["name"] == "mx.train.step.compile"]
    assert spans[trace["parent"]] is build
    assert spans[compiled["parent"]] is trace       # nested by time
    assert t_before <= build["t0"] <= trace["t0"] <= compiled["t0"] \
        <= compiled["t1"] <= trace["t1"] <= build["t1"] <= t_after
    args = build["args"]
    assert args["signature"] == "4x3:float32 4x2:float32"
    assert args["trace_s"] > 0 and args["lower_s"] > 0
    assert args["compile_s"] + args["cache_load_s"] > 0
    assert args["compiles"] + args["cache_loads"] == 1
    took = build["t1"] - build["t0"]
    assert args["trace_s"] + args["lower_s"] + args["compile_s"] \
        + args["cache_load_s"] <= took
    assert trace["args"]["program"] == "step"
    assert "step" in compiled["args"]["program"]
    assert compiled["args"]["from_cache"] == bool(args["cache_loads"])
    assert compiled["t1"] - compiled["t0"] == pytest.approx(
        args["compile_s"] + args["cache_load_s"], abs=1e-4)


def test_a_built_signature_appends_nothing_and_fires_no_jax_event():
    step, x, y = _toy()
    step(x, y)
    n = len(profiler.build_spans())
    events = len(profiler._state["events"])
    counters = profiler.get_counters()
    fired = []

    def listen(event, duration, **kw):
        fired.append(event)

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        for _ in range(3):
            step(x, y)
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert not [e for e in fired if e in profiler._JAX_PHASES]
    assert len(profiler.build_spans()) == n
    assert len(profiler._state["events"]) == events
    assert profiler.get_counters() == counters


def test_step_init_span_counts_parameters_and_state_bytes():
    _toy(units=7)
    (init,) = _named("mx.train.step.init")
    assert init["args"]["params"] == 4
    # SGD with momentum: one float32 array a parameter
    assert init["args"]["state_bytes"] == 4 * (3 * 7 + 7 + 7 * 2 + 2)


@pytest.mark.parametrize("name", ["mx.gluon.initialize", "mx.gluon.cast"])
def test_placing_and_casting_parameters_are_build_spans(name):
    net = nn.Dense(6, in_units=4)
    net.initialize()
    net.cast("float16")
    (span,) = _named(name)
    assert span["args"]["params"] == 2
    itemsize = 4 if name.endswith("initialize") else 2
    assert span["args"]["bytes"] == itemsize * (6 * 4 + 6)
    assert span["parent"] is None


def test_the_record_is_bounded_and_overflow_is_counted(monkeypatch):
    monkeypatch.setattr(profiler, "_BUILD_SPANS", 3)
    for i in range(5):
        with profiler.build_span("mx.test.build", i=i):
            pass
    assert [s["args"]["i"] for s in _named("mx.test.build")] == [0, 1, 2]
    assert profiler._state["dropped"] == 2
    profiler.reset()
    with profiler.build_span("mx.test.build", i=9):
        pass
    assert [s["args"]["i"] for s in _named("mx.test.build")] == [9]


def test_a_jit_inside_a_traced_jit_counts_once():
    inner = jax.jit(lambda a: jnp.tanh(a) * 2)
    outer = jax.jit(lambda a: inner(a) + inner(a * 3))
    a = jnp.ones((3,))
    with profiler.build_span("mx.test.nested") as span:
        outer(a)
    (rec,) = _named("mx.test.nested")
    assert rec["args"]["compiles"] + rec["args"]["cache_loads"] == 1
    assert 0 < rec["args"]["trace_s"] <= rec["t1"] - rec["t0"]
    assert len(span._seen["trace"]) == 1        # the inner trace's is in it


def test_a_compile_outside_any_build_span_counts_as_other():
    a = jnp.ones((2,))
    before = profiler.get_counter("start::other_programs")
    jax.jit(lambda a: a * 5 + 1)(a)
    assert profiler.get_counter("start::other_programs") == before + 1
    assert profiler.get_counter("start::other_compile_s") > 0
    assert not profiler.build_spans()


def test_spans_of_two_threads_do_not_nest():
    import threading

    def work():
        with profiler.build_span("mx.test.thread"):
            time.sleep(0.01)

    with profiler.build_span("mx.test.main"):
        t = threading.Thread(target=work)
        t.start()
        t.join(10)
        assert not t.is_alive()
        with profiler.build_span("mx.test.child"):
            pass
    by_name = {s["name"]: s for s in profiler.build_spans()}
    spans = profiler.build_spans()
    assert by_name["mx.test.thread"]["parent"] is None
    assert spans[by_name["mx.test.child"]["parent"]]["name"] \
        == "mx.test.main"


# ----------------------------------------------------------------------
# a fresh process: no profiler, no session
# ----------------------------------------------------------------------
_FRESH = """
import json, sys, time
t0 = time.monotonic()
import mxnet_tpu as mx
from mxnet_tpu import gluon
t1 = time.monotonic()
assert mx.profiler.state() == "stop"
mx.profiler.set_config(filename=sys.argv[1])
mx.profiler.dump()
print(json.dumps({"t0": t0, "t1": t1, "spans": mx.profiler.build_spans()}))
"""


@pytest.fixture(scope="module")
def fresh(tmp_path_factory):
    out = str(tmp_path_factory.mktemp("fresh") / "profile.json")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("MXNET_PROFILER_AUTOSTART", None)
    r = subprocess.run([sys.executable, "-c", _FRESH, out], cwd=ROOT,
                       env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    with open(out) as f:
        dumped = json.load(f)
    return json.loads(r.stdout.strip().splitlines()[-1]), dumped


def test_importing_the_package_is_a_build_span(fresh):
    record, _ = fresh
    modules = {s["args"]["module"]: s for s in record["spans"]
               if s["name"] == "mx.start.import"}
    assert {"mxnet_tpu", "mxnet_tpu.gluon"} <= set(modules)
    root = modules["mxnet_tpu"]
    # on time.monotonic's axis, from the top of the package's body
    assert record["t0"] <= root["t0"] < root["t1"] <= record["t1"]
    assert root["t1"] <= modules["mxnet_tpu.gluon"]["t0"]


def test_dump_writes_the_build_spans_of_a_process_that_never_profiled(fresh):
    record, dumped = fresh
    written = [e for e in dumped["traceEvents"]
               if e.get("cat") == "build" and e["ph"] == "X"]
    assert [(e["name"], e["args"]["module"]) for e in written] \
        == [(s["name"], s["args"]["module"]) for s in record["spans"]]
    for e, s in zip(written, record["spans"]):
        assert e["dur"] == pytest.approx((s["t1"] - s["t0"]) * 1e6,
                                         rel=1e-6, abs=1.0)


# ----------------------------------------------------------------------
# the benchmark's readers of the record
# ----------------------------------------------------------------------
def _read(name):
    spec = common.load_json(CHIP, "metrics", name + ".json")
    mod, fn = spec["reader"].split(".")
    return getattr(common.module("readers", mod), fn)(spec, {})


@pytest.mark.parametrize("name", SETUP_METRICS)
def test_a_start_metric_reads_this_process(name):
    t0 = time.monotonic()
    profiler.record_build_span("mx.start.import", t0 - 0.25,
                               module="mxnet_tpu")
    step, x, y = _toy()
    step(x, y)
    took = time.monotonic() - t0
    value = _read(name)
    assert math.isfinite(value)
    if name == "step_programs.setup":
        assert value == 1
    elif name == "import_s.setup":
        assert 0.25 <= value < 0.25 + took
    else:
        assert 0 < value < took
    # what is built after the step's first call is not the start's
    with profiler.build_span("mx.gluon.initialize"):
        time.sleep(0.02)
    profiler.record_build_span("mx.start.import", time.monotonic() - 0.5,
                               module="late")
    assert _read(name) == value


def test_step_programs_counts_the_steps_programs_alone():
    a = jnp.ones((2,))
    with profiler.build_span("mx.train.step.build"):
        jax.jit(lambda a: a * 7 - 1)(a)      # an eager helper's program
        with profiler.build_span("mx.train.step.compile", program="step"):
            pass
    (build,) = _named("mx.train.step.build")
    assert build["args"]["compiles"] + build["args"]["cache_loads"] == 1
    assert _read("step_programs.setup") == 1
    assert _read("step_compile_s.setup") > 0


def test_the_start_metrics_read_none_of_an_empty_record():
    assert [_read(name) for name in SETUP_METRICS] == [None] * 5
    # nor of a process that has built no step
    with profiler.build_span("mx.gluon.initialize"):
        pass
    assert [_read(name) for name in SETUP_METRICS] == [None] * 5


def test_nested_imports_count_once():
    now = time.monotonic()

    def imported(module, since, until):
        profiler._record_build(
            "mx.start.import", (now - since - profiler._MONO_EPOCH) * 1e6,
            (now - until - profiler._MONO_EPOCH) * 1e6, {"module": module})

    imported("a", 3.0, 1.0)
    imported("a.b", 2.5, 2.0)
    imported("c", 0.5, 0.25)
    with profiler.build_span("mx.train.step.build"):
        pass
    assert _read("import_s.setup") == pytest.approx(2.25, abs=1e-6)
    assert [s["parent"] for s in profiler.build_spans()][:3] \
        == [None, 0, None]
