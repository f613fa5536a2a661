"""What every cell's run shares: finding a cell's files by the names in
``BENCHMARK.json``, the device gate, the compile cache, seeded weights,
the compile counter, memory, and the one result line.

Nothing here imports the program (``mxnet_tpu``) except ``place_cache``,
which takes the program's own rule for where the compile cache lives.
"""
import importlib
import json
import os
import statistics
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(name, rehearsal=False):
    """The cell ``name`` with its configuration, its traffic and the names
    of the metrics it reports, all found through the benchmark's JSON
    (the rehearsal's tiny cells: through ``rehearsal/cells.json``)."""
    bench = load_json(HERE, "rehearsal", "cells.json") if rehearsal \
        else load_json(REPO, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit("no workload %r; have %s" % (name, sorted(cells)))
    cell = dict(cells[name])
    cfg = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cell["config_entry"] = cfg
    cell["model"] = load_json(REPO, cfg["file"])
    base = os.path.join(HERE, "rehearsal") if rehearsal else HERE
    cell["traffic_params"] = load_json(base, "traffic",
                                       cell["traffic"] + ".json")

    def mine(metric):
        return name in metric.get("workloads", [name])

    cell["end_to_end"] = [m for m in bench["end_to_end"] if mine(m)]
    cell["per_layer"] = [m for m in bench["per_layer"] if mine(m)]
    cell["run_seconds"] = bench["run_seconds"]
    return cell


def module(kind, name):
    """``builders/<name>.py``, ``reference/<name>.py``, ``drivers/<name>.py``
    ... imported by the name a data file gives."""
    if HERE not in sys.path:
        sys.path.insert(0, HERE)
    return importlib.import_module("%s.%s" % (kind, name))


def require_chips(n, rehearsal=False):
    """The devices the cell runs on.  No accelerator, fewer chips than the
    cell asks for, or a chip that is not in the peaks table: exit 3 with
    no result line.  The rehearsal switch alone lets a CPU through."""
    import jax
    devices = jax.devices()
    d = devices[0]
    if rehearsal:
        return devices[:n], None
    if d.platform != "tpu":
        raise SystemExit("no accelerator: jax.devices() is %r" % (devices,))
    if len(devices) < n:
        raise SystemExit("the cell needs %d chip(s), jax.devices() has %d"
                         % (n, len(devices)))
    peaks = load_json(HERE, "peaks.json")
    if d.device_kind not in peaks:
        raise SystemExit("no published peaks for device kind %r in "
                         "peaks.json" % d.device_kind)
    return devices[:n], peaks[d.device_kind]


def place_cache():
    """The program's one rule (``JAX_COMPILATION_CACHE_DIR`` or
    ``<checkout>/.jax_cache``), and every program admitted to the cache,
    so that only a checkout's first run of a cell compiles."""
    import jax
    from mxnet_tpu.utils import compile_cache
    where = compile_cache.place_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


class CompileCounter:
    """Counts the programs jax compiles or loads from the persistent
    cache; the drivers read it around the measured window."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        import jax.monitoring
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event in self.EVENTS:
            self.n += 1


# ----------------------------------------------------------------------
# weights from the seed: the benchmark makes them, the program and the
# reference are both given them
# ----------------------------------------------------------------------
def _leaf(key, kind, scale, shape, dtype):
    import jax
    import jax.numpy as jnp
    if kind == "normal":
        return (jax.random.normal(key, shape, jnp.float32)
                * scale).astype(dtype)
    return jnp.full(shape, scale, dtype)


def weight_groups(specs):
    """Leaves that share (kind, scale, shape, dtype), in order of first
    appearance: a group is drawn in one vmapped call, leaf ``i`` of group
    ``g`` from ``fold_in(fold_in(key(seed), g), i)``."""
    groups = {}
    for name, spec in specs.items():
        groups.setdefault((spec["kind"], float(spec["scale"]),
                           tuple(spec["shape"]), spec["dtype"]),
                          []).append(name)
    return list(groups.items())


def make_weights(seed, specs, only=None):
    """``{name: array}`` on the device in one jitted call.  ``specs`` is
    ``{name: {"kind": "normal"|"const", "scale", "shape", "dtype"}}``;
    ``only`` restricts the call to some names (the reference draws a
    layer at a time) and gives the same values for them."""
    import jax
    groups = weight_groups(specs)
    want = set(specs if only is None else only)
    plan = []
    for g, ((kind, scale, shape, dtype), names) in enumerate(groups):
        idx = [i for i, n in enumerate(names) if n in want]
        if idx:
            plan.append((g, kind, scale, shape, dtype,
                         tuple(idx), tuple(names[i] for i in idx)))

    def draw(seed_arr):
        import jax.numpy as jnp
        root = jax.random.key(seed_arr)
        out = {}
        for g, kind, scale, shape, dtype, idx, names in plan:
            keys = jax.vmap(lambda i: jax.random.fold_in(
                jax.random.fold_in(root, g), i))(jnp.asarray(idx))
            block = jax.vmap(lambda k: _leaf(k, kind, scale, shape,
                                             dtype))(keys)
            for j, n in enumerate(names):
                out[n] = block[j]
        return out

    import numpy as onp
    return jax.jit(draw)(onp.uint32(seed % (2 ** 32)))


# ----------------------------------------------------------------------
# small arithmetic the drivers and readers share
# ----------------------------------------------------------------------
def percentile(values, q):
    """The ``q``-th percentile (0-100) by linear interpolation between
    order statistics; ``values`` need not be sorted."""
    v = sorted(values)
    if not v:
        return None
    k = (len(v) - 1) * q / 100.0
    lo = int(k)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (k - lo)


def median(values):
    return statistics.median(values) if values else None


def device_report(devices, memory_peak_bytes, trace=None):
    d = devices[0]
    rep = {"platform": d.platform, "kind": d.device_kind,
           "count": len(devices),
           "memory_peak_bytes": int(memory_peak_bytes)}
    if trace is not None:
        rep["busy_s"] = trace["busy_s"]
        rep["window_s"] = trace["window_s"]
    return rep


def peak_bytes(devices):
    """Largest ``peak_bytes_in_use`` over the devices (live arrays only:
    this runtime leaves a program's temporaries out)."""
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)


def emit(result, compared):
    """The compared numbers beside their limits as the last lines of
    stderr, and the one result line as the last line of stdout with the
    same numbers under ``compared``, its last key."""
    for name, c in compared.items():
        print("compared %s = %r  limit %r  %s"
              % (name, c["value"], c["limit"],
                 "ok" if c["ok"] else "FAILS"), file=sys.stderr)
    sys.stderr.flush()
    result = dict(result)
    result["compared"] = {n: {"value": c["value"], "limit": c["limit"]}
                          for n, c in compared.items()}
    print(json.dumps(result), flush=True)


def judge(values, limits):
    """``{name: {"value", "limit", "ok"}}``; a number that is missing,
    not finite or over its limit is not ok."""
    import math
    out = {}
    for name, limit in limits.items():
        v = values.get(name)
        ok = v is not None and math.isfinite(v) and v <= limit
        out[name] = {"value": v, "limit": limit, "ok": bool(ok)}
    return out

