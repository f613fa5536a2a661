"""Readers of the program's own record of its start: the build-path
spans ``mxnet_tpu.profiler.build_spans()`` hands out (``name``, ``t0``,
``t1`` in ``time.monotonic()`` seconds — the clock ``run.py`` takes
``T_START`` and the window's ``t0`` on —, ``parent``, ``args``), which
the program records in every process, traced or not.  Nothing of
``run["trace"]`` is read.  A rule's ``until`` names the span whose last
end closes the start: the first call of the step, after which every
call is a steady one; a later span is not the start's.  A program with
no such record (the parent of the PR that brought it) or a process that
built no step reads as None."""


def _start_spans(metric):
    try:
        from mxnet_tpu import profiler
    except ImportError:
        return None
    if not hasattr(profiler, "build_spans"):
        return None
    spans = profiler.build_spans()
    ends = [s["t1"] for s in spans if s["name"] == metric["until"]]
    if not ends:
        return None
    return [s for s in spans if s["t1"] <= max(ends)]


def union_s(metric, run):
    """Seconds covered by the spans ``metric["spans"]`` names: the union
    of their intervals, so that one inside another (a package imported
    by a package) counts once."""
    spans = _start_spans(metric)
    if spans is None:
        return None
    total, reach = 0.0, float("-inf")
    for s in sorted((s for s in spans if s["name"] in metric["spans"]),
                    key=lambda s: s["t0"]):
        total += max(s["t1"], reach) - max(s["t0"], reach)
        reach = max(s["t1"], reach)
    return total


def args_sum(metric, run):
    """Sum of the arguments ``metric["args"]`` names over the spans named
    ``metric["span"]``: what jax itself timed, or counted, inside them."""
    spans = _start_spans(metric)
    if spans is None:
        return None
    return sum(s["args"].get(a, 0) for s in spans
               if s["name"] == metric["span"] for a in metric["args"])


def span_count(metric, run):
    """How many spans named ``metric["span"]`` the start holds."""
    spans = _start_spans(metric)
    if spans is None:
        return None
    return sum(1 for s in spans if s["name"] == metric["span"])
