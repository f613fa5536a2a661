"""The device trace: taking it (``jax.profiler``), putting the benchmark's
own spans on its clock, and reducing the ``.xplane.pb`` to what the
per-layer readers read.  Only ``jax`` reads the file.

What the trace looks like on a TPU v5e (looked at by hand, PR 25): one
plane ``/device:TPU:<n>`` per chip with the lines ``XLA Modules`` (one
event per execution of a jitted program, named ``jit_<fn>(<hash>)``) and
``XLA Ops`` (one event per HLO op, named by the op's whole HLO text, e.g.
``%decode.3 = ... custom-call(...), custom_call_target="tpu_custom_call"``
or ``%fusion.85 = ... fusion(...), kind=kOutput``); the plane
``/host:CPU`` holds one line per host thread with ``TraceAnnotation``
spans among its events.  Device and host events share one clock.
"""
import bisect
import collections
import glob
import os
import re
import shutil
import time

WINDOW_SPAN = "bench.window"


class Tracer:
    """``start()`` ... ``stop()`` around the traced window, on the
    thread that stays in the window; ``watch_server`` puts spans around
    the serving engine's calls into the warm pool."""

    keep = None  # a path: leave a copy of the .xplane.pb there (by hand)

    def __init__(self, out_dir):
        self.out_dir = out_dir
        self.calls = []      # host-side log of the watched calls
        self._span = None

    def start(self):
        import jax
        shutil.rmtree(self.out_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.out_dir, profiler_options=opts)
        self._span = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        self._span.__enter__()

    def stop(self):
        import jax
        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        paths = glob.glob(os.path.join(self.out_dir, "plugins", "profile",
                                       "*", "*.xplane.pb"))
        try:
            if self.keep and paths:
                shutil.copy(paths[0], self.keep)
            out = reduce_trace(paths[0]) if paths else None
        finally:
            shutil.rmtree(self.out_dir, ignore_errors=True)
        if out is not None:
            out["calls"] = self.calls
        return out

    def watch_server(self, server):
        """Spans and a log (time, kind, padded length, true lengths)
        around ``WarmPool.run_prefill`` / ``run_decode`` of this server's
        own pool; in the traced run only."""
        import jax
        import numpy as onp
        pool, calls = server.pool, self.calls
        run_prefill, run_decode = pool.run_prefill, pool.run_decode

        def prefill(tokens_padded, page_row, true_len, start=0, **kw):
            calls.append({"t": time.monotonic(), "kind": "prefill",
                          "padded": int(tokens_padded.shape[-1]),
                          "true": int(true_len), "start": int(start)})
            with jax.profiler.TraceAnnotation("bench.prefill"):
                return run_prefill(tokens_padded, page_row, true_len,
                                   start=start, **kw)

        def decode(page_table, lengths, tokens, active, **kw):
            act = onp.asarray(active, bool)
            calls.append({"t": time.monotonic(), "kind": "decode",
                          "active": int(act.sum()),
                          "context": int((onp.asarray(lengths)[act]
                                          + 1).sum())})
            with jax.profiler.TraceAnnotation("bench.decode"):
                return run_decode(page_table, lengths, tokens, active,
                                  **kw)

        pool.run_prefill, pool.run_decode = prefill, decode
        return calls


def _union(intervals):
    """Total length and the merged list of ``(start, end)`` intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), merged


def op_kind(name):
    """``%fusion.85 = ... fusion(...), kind=kOutput`` -> ``fusion.kOutput``;
    a custom call is named by its target."""
    m = re.match(r"%([A-Za-z_\-]+)", name)
    head = m.group(1) if m else name[:24]
    t = re.search(r'custom_call_target="([^"]+)"', name)
    if t:
        return "%s.%s" % (head, t.group(1))
    k = re.search(r"kind=(\w+)", name)
    return "%s.%s" % (head, k.group(1)) if k else head


def reduce_trace(path):
    """``{"window_s", "busy_s", "programs", "ops", "device_ops",
    "idle_gaps"}`` of one ``.xplane.pb``: everything inside the
    ``bench.window`` span.  ``programs``: name -> list of device seconds,
    one per execution.  ``ops``: (program, op kind) -> [count, seconds,
    one op's HLO text].  Times in seconds."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    spans, devices = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {ln.name: ln for ln in plane.lines}
            if "XLA Ops" in lines:
                devices.append(lines)
        elif plane.name == "/host:CPU":
            for ln in plane.lines:
                for e in ln.events:
                    if e.name.startswith("bench."):
                        spans.append((e.start_ns, e.start_ns + e.duration_ns,
                                      e.name))
    window = [s for s in spans if s[2] == WINDOW_SPAN]
    if not window or not devices:
        return None
    w0, w1 = window[0][0], window[0][1]
    inner = sorted(s for s in spans if s[2] != WINDOW_SPAN)
    inner_starts = [s[0] for s in inner]

    def host_was_in(t):
        i = bisect.bisect_right(inner_starts, t) - 1
        while i >= 0 and inner[i][0] > t - 5e9:
            if inner[i][1] >= t:
                return inner[i][2]
            i -= 1
        return "between_spans"

    busy, programs = [], collections.defaultdict(list)
    ops = {}
    gaps = collections.Counter()
    for lines in devices:
        mods = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                      for e in lines["XLA Modules"].events
                      if w0 <= e.start_ns < w1)
        mod_starts = [m[0] for m in mods]
        for s, e, name in mods:
            programs[re.sub(r"\(\d+\)$", "", name)].append((e - s) / 1e9)
        intervals = []
        for e in lines["XLA Ops"].events:
            s, d = e.start_ns, e.duration_ns
            if not (w0 <= s < w1):
                continue
            intervals.append((s, s + d))
            i = bisect.bisect_right(mod_starts, s) - 1
            prog = re.sub(r"\(\d+\)$", "", mods[i][2]) \
                if i >= 0 and s < mods[i][1] else "no_program"
            key = (prog, op_kind(e.name))
            row = ops.setdefault(key, [0, 0.0, e.name[:400]])
            row[0] += 1
            row[1] += d / 1e9
        total, merged = _union(intervals)
        busy.append(total / 1e9)
        edges = [(w0, w0)] + merged + [(w1, w1)]
        for (_, a), (b, _) in zip(edges, edges[1:]):
            if b > a:
                gaps[host_was_in(a)] += (b - a) / 1e9
    top = sorted(ops.items(), key=lambda kv: -kv[1][1])[:10]
    return {"window_s": (w1 - w0) / 1e9,
            "busy_s": sum(busy) / len(busy),
            "programs": dict(programs),
            "ops": {"%s|%s" % k: v for k, v in ops.items()},
            "device_ops": [["%s|%s" % k, v[1]] for k, v in top],
            "idle_gaps": [[k, v] for k, v in gaps.most_common(10)]}


def op_seconds(trace, rule):
    """Device seconds and count of the ops a metric's match rule names:
    ``{"program": regex on the jitted program, "op": regex on the op's
    kind}``."""
    n, total = 0, 0.0
    for key, (count, seconds, _) in trace["ops"].items():
        prog, kind = key.split("|", 1)
        if re.search(rule["program"], prog) and re.search(rule["op"], kind):
            n += count
            total += seconds
    return total, n
