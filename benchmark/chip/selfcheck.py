#!/usr/bin/env python3
"""Checks the yardstick against itself; exits non-zero on a mismatch.

    JAX_PLATFORMS=cpu python benchmark/chip/selfcheck.py

- the count functions against the figures their sources publish;
- the trace reduction against a small recorded trace of the chip
  (``recorded/*.xplane.pb.gz``, a traced run of a few hundred
  milliseconds, PR 25) whose expected reduction is kept beside it;
- ``BENCHMARK.json`` against the files it names.
"""
import gzip
import json
import math
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import common  # noqa: E402
import xplane as tracing  # noqa: E402

FAILS = []


def check(what, got, want, rel=1e-6):
    ok = math.isclose(got, want, rel_tol=rel, abs_tol=1e-12)
    print("%s %s: %r (expected %r)" % ("ok  " if ok else "FAIL", what, got,
                                       want))
    if not ok:
        FAILS.append(what)


def counts():
    resnet = common.load_json(HERE, "configs", "resnet50_v1.json")
    c = common.module("counts", "resnet")
    # He et al.'s 50-layer network as the zoo strides it: 4.089 GMAC
    check("resnet50 forward GMAC/image",
          c.forward_macs_per_image(resnet) / 1e9,
          resnet["forward_gmac_per_image"], rel=5e-3)
    check("resnet50 convolutions", len(c.conv_layers(resnet)), 54)
    m = common.load_json(HERE, "configs", "mistral7b_v03.json")
    d = common.module("counts", "decoder")
    # 218.1 M a layer, 134.2 M in the head (ISSUE 25's reckoning)
    check("mistral matmul parameters at 12 layers", d.matmul_params(m),
          12 * 218103808 + 134217728)
    check("mistral K/V bytes a token", d.kv_bytes_per_token(m),
          m["kv_bytes_per_token"])
    check("prefill attention FLOPs, 1024 tokens",
          d.prefill_attention_flops(m, 1024),
          4.0 * (1024 * 1025 / 2) * 128 * 32 * 12)
    check("decode K/V bytes, 1000 cached tokens",
          d.decode_kv_read_bytes(m, 1000), 1000 * 49152)
    ref = common.module("reference", "decoder")
    specs = ref.leaf_specs(m)
    n = sum(math.prod(s["shape"]) for s in specs.values())
    check("mistral parameters at 12 layers (reference's leaves)", n,
          2885783552)


def recorded():
    rec = os.path.join(HERE, "recorded")
    for name in sorted(os.listdir(rec)):
        if not name.endswith(".xplane.pb.gz"):
            continue
        want = common.load_json(rec, name.replace(".xplane.pb.gz",
                                                  ".expected.json"))
        with tempfile.TemporaryDirectory() as tmp:
            path = os.path.join(tmp, "t.xplane.pb")
            with gzip.open(os.path.join(rec, name)) as src, \
                    open(path, "wb") as dst:
                shutil.copyfileobj(src, dst)
            got = tracing.reduce_trace(path)
        check(name + " window_s", got["window_s"], want["window_s"])
        check(name + " busy_s", got["busy_s"], want["busy_s"])
        for prog, n in want["program_runs"].items():
            check("%s runs of %s" % (name, prog),
                  len(got["programs"].get(prog, ())), n)
        for metric, (seconds, n) in want["matched"].items():
            rule = common.load_json(HERE, "metrics", metric + ".json")
            s, k = tracing.op_seconds(got, rule["match"])
            check("%s %s matched ops" % (name, metric), k, n)
            check("%s %s matched seconds" % (name, metric), s, seconds)
        # metrics that the trace alone decides, worked out by hand
        for metric, value in want.get("read", {}).items():
            spec = common.load_json(HERE, "metrics", metric + ".json")
            model = common.load_json(HERE, "configs",
                                     want["config"] + ".json")
            mod, fn = spec["reader"].split(".")
            run = {"trace": got, "facts": {}, "model": model,
                   "peaks": common.load_json(HERE, "peaks.json")[
                       want["device_kind"]],
                   "counts": common.module("counts", model["family"])}
            check("%s %s" % (name, metric),
                  getattr(common.module("readers", mod), fn)(spec, run),
                  value, rel=1e-3)  # the hand's 4.089 GMAC is rounded


def files():
    bench = common.load_json(common.REPO, "BENCHMARK.json")
    for c in bench["configs"]:
        model = common.load_json(common.REPO, c["file"])
        for mod in ("builders", "reference", "counts"):
            common.module(mod, model["family"])
        check("reduced of %s listed alike" % c["name"],
              sorted(c["reduced"]) == sorted(model["reduced"]), True)
    for w in bench["workloads"]:
        cell = common.load_cell(w["name"])
        common.module("drivers", cell["traffic_params"]["driver"])
        common.load_json(HERE, "limits", w["name"] + ".json")
        check("%s reports setup_s and another" % w["name"],
              len(cell["end_to_end"]) >= 2 and len(cell["per_layer"]) >= 1,
              True)
    for m in bench["per_layer"]:
        spec = common.load_json(HERE, "metrics", m["name"] + ".json")
        mod, fn = spec["reader"].split(".")
        check("reader of %s" % m["name"],
              callable(getattr(common.module("readers", mod), fn)), True)


def write_expected(pb_gz):
    """``selfcheck.py --record <file.xplane.pb.gz>``: keep what the
    reduction reads of a new recorded trace (looked at by hand first)."""
    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "t.xplane.pb")
        with gzip.open(pb_gz) as src, open(path, "wb") as dst:
            shutil.copyfileobj(src, dst)
        got = tracing.reduce_trace(path)
    matched = {}
    for name in sorted(os.listdir(os.path.join(HERE, "metrics"))):
        rule = common.load_json(HERE, "metrics", name)
        if "match" in rule:
            s, k = tracing.op_seconds(got, rule["match"])
            if k:
                matched[name[:-5]] = [s, k]
    want = {"window_s": got["window_s"], "busy_s": got["busy_s"],
            "program_runs": {p: len(v) for p, v in got["programs"].items()},
            "matched": matched, "device_ops": got["device_ops"],
            "idle_gaps": got["idle_gaps"]}
    out = pb_gz.replace(".xplane.pb.gz", ".expected.json")
    with open(out, "w") as f:
        json.dump(want, f, indent=1)
    print(json.dumps(want, indent=1))


if __name__ == "__main__":
    if len(sys.argv) == 3 and sys.argv[1] == "--record":
        write_expected(sys.argv[2])
        sys.exit(0)
    counts()
    recorded()
    files()
    print("%d mismatch(es)" % len(FAILS))
    sys.exit(1 if FAILS else 0)
