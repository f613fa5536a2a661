"""The program's own names in a trace: the ``mx.*`` host spans and the
named-scope paths of the device's ops, reduced from one ``.xplane.pb``.

What carries them on a TPU v5e (looked at by hand, PR 26, in the trace
kept as ``recorded/train_b256_scopes_2s.xplane.pb.gz``):

- Host spans: ``mxnet_tpu.profiler.span`` / ``step_span`` write
  ``jax.profiler.TraceAnnotation`` events on the calling thread's line of
  the ``/host:CPU`` plane; their keyword arguments are the event's stats
  (``step_num``, ``rid``, ``active`` ...), a ``StepTraceAnnotation`` adds
  ``_r``.  Nesting is by time on one line: a span's parent is the
  innermost ``mx.*`` span of that line that encloses it.
- Scope paths: every event of the device plane's ``XLA Ops`` line points
  at an event *metadata* entry whose name is the op's whole HLO text and
  whose stat ``tf_op`` is the op's ``op_name`` as jax wrote it, followed
  by ``:`` — ``jit(step)/jvp(forward)/features/1_BatchNorm/rsqrt:``.
  A fusion carries the ``op_name`` of its root.  The events' own stats
  hold times only (``device_offset_ps``, ``device_duration_ps``), and
  ``jax.profiler.ProfileData`` shows no metadata stat: hence the small
  reader of the protobuf's wire format below (``xplane.proto`` of
  tsl/profiler: XSpace > XPlane > XLine > XEvent, with the plane's
  ``event_metadata`` and ``stat_metadata`` maps).  It reads the fields
  named here and skips the rest.

Nothing here imports jax or the program.
"""
import bisect
import collections
import re
import struct

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "mx."


# ----------------------------------------------------------------------
# the wire format, as far as an xplane needs it
# ----------------------------------------------------------------------
def _varint(buf, i):
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf, start, end):
    """``(field, value)`` of one message: an int for a varint, the 8 or 4
    raw bytes of a fixed field, a ``(start, end)`` slice for a
    length-delimited one."""
    i = start
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value = (i, i + n)
            i += n
        elif wire == 1:
            value = buf[i:i + 8]
            i += 8
        elif wire == 5:
            value = buf[i:i + 4]
            i += 4
        else:
            raise ValueError("wire type %d at byte %d" % (wire, i))
        yield key >> 3, value


def _text(buf, span):
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _signed(v):
    return v - (1 << 64) if v >= 1 << 63 else v


def _stat(buf, span, stat_names):
    """One XStat as ``(name, value)``."""
    name = value = None
    for f, v in _fields(buf, *span):
        if f == 1:
            name = stat_names.get(v, str(v))
        elif f == 2:
            value = struct.unpack("<d", v)[0]
        elif f == 3:
            value = v
        elif f == 4:
            value = _signed(v)
        elif f in (5, 6):
            value = _text(buf, v)
        elif f == 7:                       # a string kept once, by id
            value = stat_names.get(v, str(v))
    return name, value


class Plane:
    """``name``; ``lines``: ``[(line name, [(metadata id, start ns,
    duration ns, stat slices)])]``; ``event_name(id)``;
    ``event_stats(id)`` the metadata's stats; ``stats(slices)`` an
    event's own."""

    def __init__(self, buf, span):
        self._buf = buf
        self.name = ""
        self.lines = []
        self._meta = {}          # id -> (name, [stat slices])
        self._stat_names = {}
        line_spans = []
        for f, v in _fields(buf, *span):
            if f == 2:
                self.name = _text(buf, v)
            elif f == 3:
                line_spans.append(v)
            elif f == 4:
                self._map_entry(v, self._event_metadata)
            elif f == 5:
                self._map_entry(v, self._stat_metadata)
        for v in line_spans:
            self.lines.append(self._line(v))

    def _map_entry(self, span, read):
        for f, v in _fields(self._buf, *span):
            if f == 2:
                read(v)

    def _event_metadata(self, span):
        mid, name, stats = 0, "", []
        for f, v in _fields(self._buf, *span):
            if f == 1:
                mid = v
            elif f == 2:
                name = _text(self._buf, v)
            elif f == 5:
                stats.append(v)
        self._meta[mid] = (name, stats)

    def _stat_metadata(self, span):
        sid, name = 0, ""
        for f, v in _fields(self._buf, *span):
            if f == 1:
                sid = v
            elif f == 2:
                name = _text(self._buf, v)
        self._stat_names[sid] = name

    def _line(self, span):
        name, t0, events = "", 0, []
        for f, v in _fields(self._buf, *span):
            if f == 2:
                name = _text(self._buf, v)
            elif f == 3:
                t0 = _signed(v)
            elif f == 4:
                events.append(v)
        out = []
        for ev in events:
            mid = offset_ps = dur_ps = 0
            stats = []
            for f, v in _fields(self._buf, *ev):
                if f == 1:
                    mid = v
                elif f == 2:
                    offset_ps = _signed(v)
                elif f == 3:
                    dur_ps = _signed(v)
                elif f == 4:
                    stats.append(v)
            out.append((mid, t0 + offset_ps / 1e3, dur_ps / 1e3, stats))
        return name, out

    def event_name(self, mid):
        return self._meta.get(mid, ("", ()))[0]

    def stats(self, slices):
        return dict(_stat(self._buf, s, self._stat_names) for s in slices)

    def event_stats(self, mid):
        return self.stats(self._meta.get(mid, ("", ()))[1])


def read_planes(path):
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    return [Plane(buf, v) for f, v in _fields(buf, 0, len(buf)) if f == 1]


# ----------------------------------------------------------------------
# the reduction
# ----------------------------------------------------------------------
def scope_of(tf_op):
    """``jit(step)/jvp(forward)/features/1_BatchNorm/rsqrt:`` ->
    ``jvp(forward)/features/1_BatchNorm``: the path between the jitted
    program and the primitive; ``unscoped`` where jax named the op and
    no scope was open; ``unnamed`` where the op has no ``op_name`` at
    all: the compiler's own ops (on the v5e the ``copy-done``,
    ``slice-done`` and ``copy`` of its memory-space moves), which no
    scope of the program can reach."""
    if not tf_op:
        return "unnamed"
    parts = tf_op.split(":")[0].split("/")
    return "/".join(parts[1:-1]) or "unscoped"


def _program(name):
    return re.sub(r"\(\d+\)$", "", name)


def _host_spans(plane):
    """Every ``mx.*`` span and the benchmark's window: per line, nested
    by time.  ``[{"name", "start", "end", "parent", "self", "args"}]``,
    times in ns; ``parent`` an index into the list."""
    spans = []
    for _, events in plane.lines:
        mine = []
        for mid, start, dur, stat_slices in events:
            name = plane.event_name(mid)
            if name.startswith(SPAN_PREFIX) or name == WINDOW_SPAN:
                mine.append((start, -(start + dur), name, stat_slices))
        stack = []
        for start, neg_end, name, stat_slices in sorted(
                mine, key=lambda s: s[:2]):
            end = -neg_end
            while stack and spans[stack[-1]]["end"] < end:
                stack.pop()
            args = {k: v for k, v in plane.stats(stat_slices).items()
                    if not k.startswith("_")}
            parent = stack[-1] if stack else None
            spans.append({"name": name, "start": start, "end": end,
                          "parent": parent, "self": end - start,
                          "args": args})
            if parent is not None \
                    and spans[parent]["name"] != WINDOW_SPAN:
                spans[parent]["self"] -= end - start
            stack.append(len(spans) - 1)
    return spans


def reduce_program(path):
    """From one ``.xplane.pb``, inside the ``bench.window`` span where
    the trace has one and over the whole trace where not:

    - ``spans``: every ``mx.*`` host span as ``{"name", "parent"`` (the
      enclosing ``mx.*`` span's name or None) ``, "start_s"`` (from the
      window's start) ``, "dur_s", "self_s"`` (duration minus what its
      child spans cover) ``, "args"}``, in order of start;
    - ``span_totals``: name -> ``[count, seconds, self seconds]``;
    - ``scopes``: ``"<program>|<scope path>|<op kind>"`` -> ``[count,
      device seconds]`` over the ``XLA Ops`` of every device plane;
    - ``programs``: program -> ``[executions, device seconds of its
      ops]``;
    - ``idle_gaps``: the device's idle time by the innermost ``mx.*``
      span open on the host when the gap began (``no_span`` outside
      all), largest first.

    None where the trace holds neither a device plane nor an ``mx.*``
    span."""
    from xplane import _union, op_kind
    planes = read_planes(path)
    host = [p for p in planes if p.name == "/host:CPU"]
    all_spans = _host_spans(host[0]) if host else []
    window = [s for s in all_spans if s["name"] == WINDOW_SPAN]
    devices = []
    for p in planes:
        if p.name.startswith("/device:TPU:"):
            lines = dict(p.lines)
            if "XLA Ops" in lines:
                devices.append((p, lines))
    mx = [s for s in all_spans if s["name"] != WINDOW_SPAN]
    if not devices and not mx:
        return None
    if window:
        w0, w1 = window[0]["start"], window[0]["end"]
    else:
        starts = [s["start"] for s in mx]
        ends = [s["end"] for s in mx]
        for _, lines in devices:
            for _, start, dur, _ in lines["XLA Ops"]:
                starts.append(start)
                ends.append(start + dur)
        w0, w1 = min(starts), max(ends)

    inside = sorted((s for s in mx if w0 <= s["start"] < w1),
                    key=lambda s: s["start"])
    spans, totals = [], {}
    for s in inside:
        parent = s["parent"]
        parent = all_spans[parent]["name"] if parent is not None \
            and all_spans[parent]["name"] != WINDOW_SPAN else None
        spans.append({"name": s["name"], "parent": parent,
                      "start_s": (s["start"] - w0) / 1e9,
                      "dur_s": (s["end"] - s["start"]) / 1e9,
                      "self_s": s["self"] / 1e9, "args": s["args"]})
        t = totals.setdefault(s["name"], [0, 0.0, 0.0])
        t[0] += 1
        t[1] += (s["end"] - s["start"]) / 1e9
        t[2] += s["self"] / 1e9

    by_start = sorted(mx, key=lambda s: s["start"])
    span_starts = [s["start"] for s in by_start]

    def host_was_in(t):
        """The innermost span open at ``t``: of those that hold ``t``,
        the one that began last."""
        i = bisect.bisect_right(span_starts, t) - 1
        while i >= 0 and by_start[i]["start"] > t - 5e9:
            if by_start[i]["end"] >= t:
                return by_start[i]["name"]
            i -= 1
        return "no_span"

    scopes, programs = {}, {}
    gaps = collections.Counter()
    for plane, lines in devices:
        mods = sorted((start, start + dur, _program(plane.event_name(mid)))
                      for mid, start, dur, _ in lines.get("XLA Modules", ())
                      if w0 <= start < w1)
        mod_starts = [m[0] for m in mods]
        for _, _, prog in mods:
            programs.setdefault(prog, [0, 0.0])[0] += 1
        named = {}               # metadata id -> (scope, kind)
        intervals = []
        for mid, start, dur, _ in lines["XLA Ops"]:
            if not (w0 <= start < w1):
                continue
            intervals.append((start, start + dur))
            if mid not in named:
                named[mid] = (
                    scope_of(plane.event_stats(mid).get("tf_op") or ""),
                    op_kind(plane.event_name(mid)))
            i = bisect.bisect_right(mod_starts, start) - 1
            prog = mods[i][2] if i >= 0 and start < mods[i][1] \
                else "no_program"
            row = scopes.setdefault("%s|%s|%s" % ((prog,) + named[mid]),
                                    [0, 0.0])
            row[0] += 1
            row[1] += dur / 1e9
            programs.setdefault(prog, [0, 0.0])[1] += dur / 1e9
        _, merged = _union(intervals)
        edges = [(w0, w0)] + merged + [(w1, w1)]
        for (_, a), (b, _) in zip(edges, edges[1:]):
            if b > a:
                gaps[host_was_in(a)] += (b - a) / 1e9
    return {"window_s": (w1 - w0) / 1e9, "spans": spans,
            "span_totals": totals, "scopes": scopes, "programs": programs,
            "idle_gaps": [[k, v] for k, v in gaps.most_common()]}


def scope_seconds(program_trace, rule):
    """Device seconds and count of the ops a match rule names:
    ``{"program": regex on the jitted program, "scope": regex on the
    scope path}`` (``"op"``: regex on the op's kind, optional)."""
    n, total = 0, 0.0
    for key, (count, seconds) in program_trace["scopes"].items():
        prog, scope, kind = key.split("|", 2)
        if re.search(rule["program"], prog) \
                and re.search(rule["scope"], scope) \
                and re.search(rule.get("op", ""), kind):
            n += count
            total += seconds
    return total, n
