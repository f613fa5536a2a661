#!/usr/bin/env python3
"""The readings a limit is set from, taken on the chip at the cell's own
size, many seeds in one process (set-up is long and the compile cache is
shared):

    python benchmark/chip/tests/readings.py --workload <cell> \\
        --seeds 1,2,3 --control-seeds 3 --seconds 2 --out <file.jsonl>

Every seed is a whole run of the cell through ``run.run_cell``; the first
``--control-seeds`` of them also put the reference in the program's place
as each control of the cell's limits file says (one precision lower, a
fault planted) and judge it by the cell's limits.  One JSON line per
seed: every compared number of the program and of each control beside
its limit, and which limits each control failed.  Not part of a
benchmark run.
"""
import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import common  # noqa: E402
import run  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--rehearsal", type=int, default=0)
    ap.add_argument("--out", required=True)
    a = ap.parse_args()
    os.makedirs(os.path.dirname(os.path.abspath(a.out)), exist_ok=True)
    base = os.path.join(common.HERE, "rehearsal") if a.rehearsal \
        else common.HERE
    controls = tuple(common.load_json(base, "limits", a.workload + ".json")
                     .get("control", {}))
    for i, seed in enumerate(int(s) for s in a.seeds.split(",")):
        t0 = time.monotonic()
        result, compared = run.run_cell(
            a.workload, seed, a.seconds, rehearsal=bool(a.rehearsal),
            controls=controls if i < a.control_seeds else ())
        row = {"seed": seed, "correct": result["correct"],
               "controls_failed": result.get("controls"),
               "compared": {k: [v["value"], v["limit"]]
                            for k, v in compared.items()},
               "seconds": time.monotonic() - t0}
        with open(a.out, "a") as f:
            f.write(json.dumps(row) + "\n")
        print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
