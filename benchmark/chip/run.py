#!/usr/bin/env python3
"""One run of one cell of the benchmark:

    python benchmark/chip/run.py --workload <name> --seed <n> \\
        --seconds <s> --trace <0|1>

One process, which holds the chip.  It finds the cell's configuration,
traffic and metrics by the names in ``BENCHMARK.json``, builds the cell
from the seed, warms the shapes the cell uses (all of that is
``setup_s``), measures for ``--seconds``, checks what the timed path
produced against the plain reference, and prints one JSON object as the
last line of its output.  It exits non-zero, with no result line, when
jax finds no TPU, fewer chips than the cell asks for, or a chip that is
not in ``peaks.json``.

``--rehearsal 1`` runs the tiny cells of ``rehearsal/cells.json`` on
whatever jax has (the CPU, in the sandbox): every step of a run, no
device metric.
"""
import time
T_START = time.monotonic()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

import common  # noqa: E402


def per_layer_metrics(cell, run):
    out = {}
    for m in cell["per_layer"]:
        spec = common.load_json(common.HERE, "metrics", m["name"] + ".json")
        mod, fn = spec["reader"].split(".")
        value = getattr(common.module("readers", mod), fn)(spec, run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def run_cell(workload, seed, seconds, trace=False, rehearsal=False,
             controls=()):
    """Everything after the arguments: returns (result, compared).
    ``controls`` names controls of the cell's limits file (``tests/``
    pass them; a benchmark run has none): the reference then stands in
    the program's place as the control says, its numbers are judged by
    the cell's own limits, and ``result["controls"]`` says which it
    failed."""
    import xplane
    cell = common.load_cell(workload, rehearsal=rehearsal)
    devices, peaks = common.require_chips(cell["chips"], rehearsal)
    common.place_cache()
    model = cell["model"]
    base = os.path.join(common.HERE, "rehearsal") if rehearsal \
        else common.HERE
    limits = common.load_json(base, "limits", workload + ".json")
    ctx = {"cell": cell, "seed": int(seed), "seconds": float(seconds),
           "trace": bool(trace), "devices": devices, "peaks": peaks,
           "t_start": T_START, "compiles": common.CompileCounter(),
           "controls": {c: limits["control"][c] for c in controls},
           "tracer": xplane.Tracer(os.path.join(common.REPO, ".bench_trace")),
           "builder_args": {"kernel_marker": None} if rehearsal else {}}
    driver = common.module("drivers", cell["traffic_params"]["driver"])
    out = driver.run(ctx)

    compared = common.judge(out["values"], limits["limits"])
    correct = all(c["ok"] for c in compared.values())
    failed_by = {}
    for name, values in out.get("control_values", {}).items():
        judged = common.judge(values, {k: v for k, v in
                                       limits["limits"].items()
                                       if k in values})
        failed_by[name] = sorted(k for k, c in judged.items()
                                 if not c["ok"])
        compared.update({"%s.%s" % (name, k): c for k, c in judged.items()})
    if trace:
        run = {"trace": out["trace"], "facts": out["facts"],
               "peaks": peaks, "model": model,
               "counts": common.module("counts", model["family"])}
        metrics = per_layer_metrics(cell, run)
    else:
        metrics = {m["name"]: {"value": out["end_to_end"][m["name"]],
                               "unit": m["unit"]}
                   for m in cell["end_to_end"]}
    result = {"correct": correct, "attempted": out["attempted"],
              "failed": out["failed"], "metrics": metrics,
              "device": common.device_report(
                  devices, out["memory_peak_bytes"],
                  out["trace"] if trace else None)}
    if trace and out["trace"]:
        result["breakdown"] = {"device_ops": out["trace"]["device_ops"],
                               "idle_gaps": out["trace"]["idle_gaps"]}
    if controls:
        result["controls"] = failed_by
    return result, compared


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    result, compared = run_cell(a.workload, a.seed, a.seconds,
                                trace=a.trace, rehearsal=a.rehearsal)
    common.emit(result, compared)
    return 0


if __name__ == "__main__":
    sys.exit(main())
