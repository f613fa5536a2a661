"""``mx.profiler`` — framework-wide instrumentation over ``jax.profiler``.

Reference parity: ``python/mxnet/profiler.py`` (``set_config``,
``set_state``, ``pause``/``resume``, ``dump``, user scopes
``Domain/Task/Frame/Counter/Marker`` at :228-287) over
``src/profiler/profiler.h:256`` and ``aggregate_stats.cc``.

Two recording planes, on two clocks:

1. **Device plane** — the ``.xplane.pb`` of a ``jax.profiler`` session,
   whoever started it: ``set_state('run')`` (``jax.profiler.start_trace``
   into ``<filename stem>_trace``), ``jax.profiler.trace(dir)`` in user
   code, or a benchmark's tracer.  Its clock is the profiler session's:
   host threads and the device's ``XLA Ops`` share it.  :func:`span` and
   :func:`step_span` put a ``jax.profiler.TraceAnnotation`` /
   ``StepTraceAnnotation`` there with their arguments, nested under
   whatever span the thread is already in; outside a session an
   annotation is a flag test.  **``span`` is the only way code on the
   device path (``TrainStep``, ``Server.engine_step``, the data loader)
   marks time**: all such names start ``mx.``.  Inside a jitted program
   nothing can mark time; there ``jax.named_scope`` (every gluon block
   under its registered name, ``forward`` / ``optimizer`` in
   ``TrainStep``, ``kv_write`` / ``attention`` / ``sample`` in the serving
   programs) and a ``pl.pallas_call``'s ``name=`` put the program's names
   on the device's ops.
2. **Host plane** — a central event recorder in this module, on its own
   clock: ``time.perf_counter`` microseconds since this module's import,
   which shares nothing with the device plane's.  Framework seams (op
   dispatch in ``ndarray.apply_op``, KVStore push/pull, Trainer step
   phases, DataLoader/DataIter batches), user scopes and, while
   ``mx.profiler`` itself runs, the ``mx.*`` spans emit events with real
   begin/end timestamps; ``dump()`` writes them as valid
   chrome://tracing JSON (``ph:"X"`` complete events plus ``ph:"C"``
   counter events) next to the XLA trace dir.  **The spans of the build
   path record there in every process**, profiler running or not
   (:func:`build_span`): what a process enters only when it builds
   something — importing the package, placing parameters, making the
   optimizer's state, tracing, lowering and compiling a step — and
   never once a step.  :func:`build_spans` hands them out on
   ``time.monotonic``'s axis; the record is bounded.

Hot paths are gated by module-level flags (``_IMPERATIVE``, ``_KVSTORE``,
``_STEP``, ``_DATA``, ``_MEMORY``, ``_SPAN``) recomputed on every config/state
change, so with profiling off an instrumented call site pays exactly one
attribute read + falsy branch.

The recorder is thread-safe: every ``_state`` touch happens under the
reentrant ``_rec_lock`` — ``fault::*`` counters are bumped concurrently
from the step loop, the heartbeat, the maintenance poller and signal
handlers, and the counter update is a
read-modify-write that silently lost updates before the lock (found by
``tools/mxrace.py``; confirmed by its vector-clock harness).

``MXNET_PROFILER_AUTOSTART=1`` starts the profiler at import and dumps
at interpreter exit (reference: profiler starts in ``run`` state and the
engine dumps via ``Profiler::~Profiler``).
"""
from __future__ import annotations

import atexit
import json
import os
import threading
import time
from collections import defaultdict

import jax

# epoch for all host-plane timestamps: microseconds since module import
_EPOCH = time.perf_counter()
# the same instant on time.monotonic's axis, which build_spans() answers on
_MONO_EPOCH = time.monotonic()


def _now_us():
    """Monotonic wall-clock in microseconds since profiler epoch."""
    return (time.perf_counter() - _EPOCH) * 1e6


_state = {
    "config": {"profile_all": False, "profile_symbolic": True,
               "profile_imperative": True, "profile_memory": False,
               "profile_api": False, "profile_kvstore": True,
               "profile_data": True, "filename": "profile.json",
               "aggregate_stats": False, "continuous_dump": False},
    "running": False,
    "paused": False,
    "trace_dir": None,
    "agg": defaultdict(lambda: [0, 0.0]),  # name -> [count, total_s]
    "events": [],     # ("X", name, cat, ts_us, dur_us, tid, args|None)
                      # ("C", name, cat, ts_us, value)
                      # ("i", name, cat, ts_us, args|None)
    "counters": {},   # name -> latest cumulative value (exported at dump)
    "dropped": 0,     # events discarded after the buffer cap was hit
    "build_spans": 0,  # build-path spans among the events (cat "build")
}

# One recorder lock for every ``_state`` touch.  The host plane is fed
# from genuinely concurrent threads — ``fault::*`` counters bump from
# the step heartbeat, the maintenance poller and signal handlers at
# once — and the counter path is a read-modify-write, so the unlocked
# recorder lost updates (mxrace R9's first real catch;
# tests/test_mxrace.py holds the regression).
# Reentrant because _append -> _write_trace (continuous_dump) and
# dump -> set_state re-enter on the same thread.
_rec_lock = threading.RLock()


def _append(ev):
    """Bounded event buffer.  At ``max_events`` (config, default 1M): with
    ``continuous_dump`` the buffer is snapshotted to ``filename`` and
    cleared (a long run keeps its tail on disk and totals in the
    aggregate table); otherwise new events are dropped and counted."""
    with _rec_lock:
        events = _state["events"]
        if len(events) >= _state["config"].get("max_events", 1000000):
            if _state["config"].get("continuous_dump"):
                _write_trace(_state["config"].get("filename",
                                                  "profile.json"))
                events.clear()
                _state["build_spans"] = 0
            else:
                _state["dropped"] += 1
                return
        events.append(ev)

# -- fast gating flags (one attribute read on the instrumented hot path) --
_IMPERATIVE = False   # per-op dispatch timing in ndarray.apply_op
_STEP = False         # Trainer phases, autograd backward
_KVSTORE = False      # KVStore byte/time counters
_DATA = False         # DataLoader / DataIter throughput
_MEMORY = False       # device memory_stats() counter sampling
_SPAN = False         # host-plane copy of the mx.* spans (span, step_span)


def _recompute_flags():
    global _IMPERATIVE, _STEP, _KVSTORE, _DATA, _MEMORY, _SPAN
    with _rec_lock:
        cfg = _state["config"]
        base = _state["running"] and not _state["paused"]
        _SPAN = base
        all_ = cfg.get("profile_all", False)
        _IMPERATIVE = base and (all_ or cfg.get("profile_imperative",
                                                True))
        _STEP = _IMPERATIVE
        _KVSTORE = base and (all_ or cfg.get("profile_kvstore", True))
        _DATA = base and (all_ or cfg.get("profile_data", True))
        _MEMORY = base and (all_ or cfg.get("profile_memory", False))


def _recording():
    """Host trace-plane gate for user scopes."""
    with _rec_lock:
        return _state["running"] and not _state["paused"]


# ----------------------------------------------------------------------
# recorder primitives (used by framework seams and user scopes)
# ----------------------------------------------------------------------
def record_duration(name, cat, ts_us, dur_us, args=None):
    """Append a complete (``ph:"X"``) event with a real begin timestamp."""
    with _rec_lock:
        _append(("X", name, cat, ts_us, dur_us, threading.get_ident(),
                 args))
        entry = _state["agg"][name]
        entry[0] += 1
        entry[1] += dur_us * 1e-6


def record_counter(name, value, cat="counter"):
    """Append a ``ph:"C"`` counter sample at the current timestamp."""
    with _rec_lock:
        _state["counters"][name] = value
        _append(("C", name, cat, _now_us(), value))


def counter_add(name, delta, cat="counter"):
    """Bump a cumulative counter and emit its new value as a C event.
    The read-modify-write runs under the recorder lock: counters are
    bumped from heartbeat/poller/worker threads concurrently with the
    step loop, and an unlocked bump loses updates."""
    with _rec_lock:
        value = _state["counters"].get(name, 0) + delta
        _state["counters"][name] = value
        _append(("C", name, cat, _now_us(), value))
        return value


def counter_bump(name, delta, cat="counter"):
    """Like :func:`counter_add`, but the trace event is only emitted
    while the profiler is recording — the cumulative value updates
    regardless.  For always-on subsystems (``mx.fault`` recovery
    actions) that must count even when nobody asked for a trace."""
    with _rec_lock:
        value = _state["counters"].get(name, 0) + delta
        _state["counters"][name] = value
        if _recording():
            _append(("C", name, cat, _now_us(), value))
        return value


def record_instant(name, cat="instant", args=None):
    _append(("i", name, cat, _now_us(), args))


def get_counters():
    """Snapshot of cumulative counter values (bytes moved, batches, ...).
    The fault runtime (``mx.fault``) publishes its recovery actions here
    under the ``fault::`` prefix: ``retries``, ``gave_up``, ``injected``,
    ``nonfinite_steps``, ``checkpoint_fallbacks``, ``worker_restarts``,
    ``preemptions``."""
    with _rec_lock:
        return dict(_state["counters"])


def get_counter(name, default=0):
    """Current value of one cumulative counter (``default`` if it never
    moved) — the cheap probe used by tests and ``tools/chaos_check.py``
    to assert that a defense engaged."""
    with _rec_lock:
        return _state["counters"].get(name, default)


def record_memory(tag="step"):
    """Sample per-device memory via ``device.memory_stats()`` (TPU/GPU
    backends populate it; CPU returns None) into counter events.  Only
    called by instrumented seams when ``_MEMORY`` is set."""
    try:
        devices = jax.local_devices()
    except Exception:
        return
    for dev in devices:
        try:
            stats = dev.memory_stats()
        except Exception:
            stats = None
        if not stats:
            continue
        for key in ("bytes_in_use", "peak_bytes_in_use"):
            if key in stats:
                record_counter(
                    "memory::%s_%d::%s" % (dev.platform, dev.id, key),
                    stats[key], cat="memory")


# ----------------------------------------------------------------------
# reference API
# ----------------------------------------------------------------------
def set_config(**kwargs):
    """profiler.py set_config — accepts the reference's knobs; ``filename``
    determines both the JSON path and the XLA trace directory.  Extra
    TPU-side knobs: ``profile_kvstore``, ``profile_data``."""
    with _rec_lock:
        _state["config"].update(kwargs)
        _recompute_flags()


def set_state(state="stop", profile_process="worker"):
    with _rec_lock:
        if state == "run":
            if not _state["running"]:
                trace_dir = os.path.splitext(
                    _state["config"].get("filename", "profile.json"))[0] \
                    + "_trace"
                try:
                    os.makedirs(trace_dir, exist_ok=True)
                    jax.profiler.start_trace(trace_dir)
                    _state["trace_dir"] = trace_dir
                except Exception:
                    # host-plane recording still works without the XLA
                    # trace
                    _state["trace_dir"] = None
                _state["running"] = True
        elif state == "stop":
            if _state["running"]:
                if _state["trace_dir"] is not None:
                    try:
                        jax.profiler.stop_trace()
                    except Exception:
                        pass
                _state["running"] = False
        else:
            raise ValueError("state must be 'run' or 'stop'")
        _recompute_flags()


def state():
    with _rec_lock:
        return "run" if _state["running"] else "stop"


def pause(profile_process="worker"):
    """Suspend recording: scopes entered while paused land in neither the
    trace nor the aggregate table (reference ``MXProfilePause``)."""
    with _rec_lock:
        _state["paused"] = True
        _recompute_flags()


def resume(profile_process="worker"):
    with _rec_lock:
        _state["paused"] = False
        _recompute_flags()


def dump(finished=True, profile_process="worker"):
    """Write the host-plane chrome://tracing JSON (the XLA trace is
    already on disk in ``trace_dir``)."""
    with _rec_lock:
        if _state["running"] and finished:
            set_state("stop")
        fn = _state["config"].get("filename", "profile.json")
    _write_trace(fn)
    return fn


def _write_trace(fn):
    # Snapshot under the lock, serialize and write OUTSIDE it: holding
    # _rec_lock across a megabyte JSON dump would stall every always-on
    # counter bump (heartbeat, poller, a preemption autosave) for the
    # write's duration.  The continuous_dump caller in _append already
    # holds the RLock, so its snapshot+clear stays atomic there.
    with _rec_lock:
        events = list(_state["events"])
        counters = dict(_state["counters"])
        dropped = _state["dropped"]
        trace_dir = _state["trace_dir"]
    pid = os.getpid()
    trace_events = [
        {"name": "process_name", "ph": "M", "pid": pid,
         "args": {"name": "mxnet_tpu worker"}},
    ]
    for ev in sorted(events, key=lambda e: e[3]):
        if ev[0] == "X":
            _, name, cat, ts, dur, tid, args = ev
            rec = {"name": name, "cat": cat, "ph": "X", "ts": ts,
                   "dur": dur, "pid": pid, "tid": tid}
            if args:
                rec["args"] = args
            trace_events.append(rec)
        elif ev[0] == "C":
            _, name, cat, ts, value = ev
            trace_events.append(
                {"name": name, "cat": cat, "ph": "C", "ts": ts,
                 "pid": pid, "args": {"value": value}})
        else:
            _, name, cat, ts, args = ev
            rec = {"name": name, "cat": cat, "ph": "i", "ts": ts,
                   "pid": pid, "tid": 0, "s": "g"}
            if args:
                rec["args"] = args
            trace_events.append(rec)
    # final value of every cumulative counter, so a counter that last
    # moved before the dump still shows on the track end
    ts_end = _now_us()
    for name, value in sorted(counters.items()):
        trace_events.append(
            {"name": name, "cat": "counter", "ph": "C", "ts": ts_end,
             "pid": pid, "args": {"value": value}})
    if dropped:
        trace_events.append(
            {"name": "profiler::dropped_events", "cat": "counter",
             "ph": "C", "ts": ts_end, "pid": pid,
             "args": {"value": dropped}})
    from .utils.serialization import atomic_write
    with atomic_write(fn, "w") as f:
        json.dump({
            "traceEvents": trace_events,
            "displayTimeUnit": "ms",
            "xla_trace_dir": trace_dir,
        }, f)


def dumps(reset=False, format="table"):  # noqa: A002
    """Aggregate stats table (profiler.py:154 / aggregate_stats.cc)."""
    with _rec_lock:
        lines = ["%-40s %10s %14s %14s" % ("Name", "Calls", "Total(ms)",
                                           "Avg(ms)")]
        for name, (count, total) in sorted(_state["agg"].items()):
            lines.append("%-40s %10d %14.3f %14.3f"
                         % (name, count, total * 1e3,
                            total * 1e3 / max(count, 1)))
        if _state["counters"]:
            lines.append("%-40s %10s" % ("Counter", "Value"))
            for name, value in sorted(_state["counters"].items()):
                lines.append("%-40s %10s" % (name, value))
        if reset:
            _state["agg"].clear()
            _state["counters"].clear()
            _state["events"].clear()
            _state["dropped"] = 0
            _state["build_spans"] = 0
        return "\n".join(lines)


def reset():
    """Drop all recorded events, aggregates and counters."""
    with _rec_lock:
        _state["agg"].clear()
        _state["counters"].clear()
        _state["events"].clear()
        _state["dropped"] = 0
        _state["build_spans"] = 0


class _Scope:
    """Timed + device-annotated scope.

    The annotation (``jax.profiler.TraceAnnotation`` with ``args``, a
    ``StepTraceAnnotation`` for a ``step``) is entered whenever the scope
    is; it records only inside a ``jax.profiler`` session.

    Host plane, user scopes (``gated=False``): the aggregate table is
    fed whenever the profiler is not paused (the pre-existing behavior
    user code relies on); trace events additionally require the profiler
    to be running.  Both decisions are latched at ``__enter__`` so a
    pause mid-scope keeps reference semantics: what matters is the state
    when the scope was entered.  Spans on the device path
    (``gated=True``) read ``_SPAN`` first and, with ``mx.profiler`` not
    recording, take no lock, read no clock and feed no table."""

    def __init__(self, name, cat="scope", args=None, step=False,
                 gated=False):
        self._name = name
        self._cat = cat
        self._args = args or {}
        self._step = step
        self._gated = gated
        self._ann = None
        self._rec = False
        self._agg = False

    def __enter__(self):
        if self._gated and not _SPAN:
            self._agg = self._rec = False
        else:
            with _rec_lock:
                self._agg = not _state["paused"]
                self._rec = self._agg and _state["running"]
            self._t0 = _now_us()
        annotation = jax.profiler.StepTraceAnnotation if self._step \
            else jax.profiler.TraceAnnotation
        self._ann = annotation(self._name, **self._args)
        self._ann.__enter__()
        return self

    def set(self, **args):
        """Arguments known only once the scope is open (a step span's
        batch size after scheduling): appended to the annotation and to
        the host-plane record."""
        self._ann.set_metadata(**args)
        if self._rec:
            self._args = dict(self._args, **args)

    def __exit__(self, *exc):
        if self._ann is not None:  # a stop() with no start()
            self._ann.__exit__(*exc)
        if not self._agg:
            return
        t1 = _now_us()
        if self._rec:
            record_duration(self._name, self._cat, self._t0, t1 - self._t0,
                            args=self._args or None)
        else:
            with _rec_lock:
                entry = _state["agg"][self._name]
                entry[0] += 1
                entry[1] += (t1 - self._t0) * 1e-6


class Domain:
    """Profiler domain (profiler.py:228)."""

    def __init__(self, name):
        self.name = name

    def new_task(self, name):
        return Task(self, name)

    def new_counter(self, name, value=None):
        return Counter(self, name, value)

    def new_marker(self, name):
        return Marker(self, name)


class Task(_Scope):
    def __init__(self, domain, name):
        super().__init__("%s::%s" % (domain.name, name), cat="task")
        self.domain = domain
        self.name = name

    def start(self):
        self.__enter__()

    def stop(self):
        self.__exit__(None, None, None)


class Frame(_Scope):
    def __init__(self, domain, name):
        super().__init__("%s::%s" % (domain.name, name), cat="frame")

    def start(self):
        self.__enter__()

    def stop(self):
        self.__exit__(None, None, None)


class Event(_Scope):
    def __init__(self, name):
        super().__init__(name, cat="event")

    def start(self):
        self.__enter__()

    def stop(self):
        self.__exit__(None, None, None)


class Counter:
    """User counter — every mutation records a ``ph:"C"`` sample when the
    profiler is running (reference ``profiler.h`` CounterStat)."""

    def __init__(self, domain, name, value=None):
        self.name = "%s::%s" % (domain.name, name)
        self.value = value or 0
        self._publish()

    def _publish(self):
        with _rec_lock:
            _state["counters"][self.name] = self.value
            if _recording():
                _append(("C", self.name, "counter", _now_us(),
                         self.value))

    def set_value(self, value):
        with _rec_lock:
            self.value = value
            self._publish()

    def increment(self, delta=1):
        # RMW under the recorder lock — same lost-update class as
        # counter_add (the _publish-only lock would just publish an
        # already-torn value)
        with _rec_lock:
            self.value += delta
            self._publish()

    def decrement(self, delta=1):
        with _rec_lock:
            self.value -= delta
            self._publish()

    def __iadd__(self, v):
        self.increment(v)
        return self

    def __isub__(self, v):
        self.decrement(v)
        return self


class Marker:
    def __init__(self, domain, name):
        self.name = "%s::%s" % (domain.name, name)

    def mark(self, scope="process"):
        with _rec_lock:
            entry = _state["agg"]["marker::" + self.name]
            entry[0] += 1
            if _recording():
                record_instant(self.name, cat="marker")


def annotate(name):
    """Decorator/context annotating device timeline (TPU extension)."""
    return _Scope(name)


def span(name, **args):
    """The one seam for marking time on the device path: a
    ``jax.profiler.TraceAnnotation(name, **args)`` — in the ``.xplane.pb``
    of whatever ``jax.profiler`` session is open, on the device trace's
    clock, nested under the span the thread is in — plus a host-plane
    record while ``mx.profiler`` itself runs.  No switch: a span is live
    exactly when a profiler session is."""
    return _Scope(name, cat="span", args=args, gated=True)


def step_span(name, step, **args):
    """:func:`span` over ``jax.profiler.StepTraceAnnotation``: one
    iteration of a loop (a training step, an engine step), numbered
    ``step_num`` for the profiler's per-step analysis."""
    return _Scope(name, cat="step", args=dict(args, step_num=step),
                  step=True, gated=True)


# ----------------------------------------------------------------------
# the build path: spans that record in every process
# ----------------------------------------------------------------------
#: build-path spans the recorder keeps; later ones count as dropped
_BUILD_SPANS = 4096


class _Building(threading.local):
    """A thread's open build spans, the outermost first, and whether jax
    has just said that the program it is compiling came from the
    persistent cache."""

    def __init__(self):
        self.open = []
        self.loaded = False


_building = _Building()

#: what jax times of its own tracing, lowering, compiling and loading
#: from the persistent cache, by the key a build span sums it under
_JAX_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load",
}


def _record_build(name, t0_us, t1_us, args):
    with _rec_lock:
        if _state["build_spans"] >= _BUILD_SPANS:
            _state["dropped"] += 1
            return
        _state["build_spans"] += 1
        record_duration(name, "build", t0_us, t1_us - t0_us, args or None)


class _BuildSpan:
    """A span of the build path: a ``TraceAnnotation`` like
    :func:`span`'s, and a host-plane record whether or not a profiler
    runs.  While it is open on a thread, what jax traces, lowers,
    compiles or loads from the persistent cache on that thread is added
    to its sums (and to those of the build spans round it), which join
    its arguments at exit: ``trace_s``, ``lower_s``, ``compile_s``,
    ``cache_load_s`` (each the union of jax's own intervals: a jitted
    function traced inside another's trace counts once), ``compiles``,
    ``cache_loads``.  A span that saw none carries none, and an argument
    the span's own code has set under one of these names stands."""

    def __init__(self, name, args):
        self._name = name
        self._args = args
        self._seen = {}         # phase -> [(t0_us, t1_us)], disjoint
        self._compiles_as = None

    def compiles_as(self, name):
        """Record each program jax compiles or loads right inside this
        span as a child span ``name`` (``program``, ``from_cache``),
        from jax's own timing of it: for a ``jax.jit`` that compiles
        inside its first call, where no code of ours stands round the
        compile."""
        self._compiles_as = name
        return self

    def __enter__(self):
        self._ann = jax.profiler.TraceAnnotation(self._name, **self._args)
        self._ann.__enter__()
        _building.open.append(self)
        self._t0 = _now_us()
        return self

    def set(self, **args):
        self._ann.set_metadata(**args)
        self._args = dict(self._args, **args)

    @property
    def cache_loads(self):
        """Programs jax has loaded from the persistent cache inside this
        span so far."""
        return len(self._seen.get("cache_load", ()))

    def _saw(self, phase, t0_us, t1_us):
        seen = self._seen.setdefault(phase, [])
        while seen and seen[-1][0] >= t0_us:   # it ran inside this one
            seen.pop()
        seen.append((t0_us, t1_us))

    def __exit__(self, *exc):
        t1 = _now_us()
        _building.open.pop()
        if self._seen:
            sums = {phase + "_s": sum(
                b - a for a, b in self._seen.get(phase, ())) * 1e-6
                for phase in _JAX_PHASES.values()}
            sums["compiles"] = len(self._seen.get("compile", ()))
            sums["cache_loads"] = self.cache_loads
            # (the plan span's own ``compiles``, the programs it tried,
            # stands)
            self.set(**{k: v for k, v in sums.items()
                        if k not in self._args})
        self._ann.__exit__(*exc)
        _record_build(self._name, self._t0, t1, self._args)


def _on_jax_duration(event, duration, fun_name=None, **_):
    """jax's monitoring listener: called when jax has traced, lowered,
    compiled or loaded something, never by a cached call."""
    phase = _JAX_PHASES.get(event)
    if phase is None:
        return
    if phase == "cache_load":
        # jax times the whole of a program's compile-or-load next, on
        # this thread: that event is the load, key and all
        _building.loaded = True
        return
    if phase == "compile" and _building.loaded:
        _building.loaded = False
        phase = "cache_load"
    is_program = phase in ("compile", "cache_load")
    open_ = _building.open
    if not open_:
        if is_program:      # the benchmark's own jits, a user's
            counter_bump("start::other_compile_s", duration)
            counter_bump("start::other_programs", 1)
        return
    t1 = _now_us()
    t0 = t1 - duration * 1e6
    for span_ in open_:
        span_._saw(phase, t0, t1)
    if is_program and open_[-1]._compiles_as:
        _record_build(open_[-1]._compiles_as, t0, t1,
                      {"program": fun_name,
                       "from_cache": phase == "cache_load"})


jax.monitoring.register_event_duration_secs_listener(_on_jax_duration)


def build_span(name, **args):
    """:func:`span` for the build path — what a process enters only when
    it builds something, never once a step: recorded on the host plane
    in every process, bounded (``_BUILD_SPANS``, then counted with the
    dropped events), whether or not ``mx.profiler`` or a ``jax.profiler``
    session runs; ``dump()`` writes it with the rest, :func:`build_spans`
    reads it back.  See :class:`_BuildSpan` for what it sums."""
    return _BuildSpan(name, args)


def record_build_span(name, t0, **args):
    """The build-path span ``name`` from ``t0`` (``time.monotonic()``
    seconds) to now: for what cannot stand in a ``with`` — a package's
    import (``mx.start.import``, ``module``), whose clock is read before
    this module is there."""
    _record_build(name, (t0 - _MONO_EPOCH) * 1e6, _now_us(), args)


def build_spans():
    """The build-path spans recorded so far, by start: ``[{"name", "t0",
    "t1", "parent", "args"}]``, ``t0`` and ``t1`` in ``time.monotonic()``
    seconds, ``parent`` the index in this list of the build-path span
    of the same thread that encloses it (None at the top)."""
    with _rec_lock:
        events = [e for e in _state["events"]
                  if e[0] == "X" and e[2] == "build"]
    events.sort(key=lambda e: (e[3], -e[4]))
    spans, open_ = [], {}
    for _, name, _, ts, dur, tid, args in events:
        stack = open_.setdefault(tid, [])
        while stack and stack[-1][1] < ts + dur:
            stack.pop()
        spans.append({"name": name, "t0": _MONO_EPOCH + ts * 1e-6,
                      "t1": _MONO_EPOCH + (ts + dur) * 1e-6,
                      "parent": stack[-1][0] if stack else None,
                      "args": dict(args or {})})
        stack.append((len(spans) - 1, ts + dur))
    return spans


# reference parity: MXNET_PROFILER_AUTOSTART starts the profiler in the
# `run` state at library load and dumps on process exit
if os.environ.get("MXNET_PROFILER_AUTOSTART", "0") not in ("", "0",
                                                           "false", "False"):
    set_state("run")
    atexit.register(dump)
