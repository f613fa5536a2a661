"""The benchmark's contract with the device and with its own files
(``benchmark/chip/run.py``, ``common.py``, ``counts/resnet.py``), held on
the CPU: a cell that finds no chip, too few chips or a chip without
published peaks exits with no result line; a cell is found by the names
in ``BENCHMARK.json``; the ResNet cell's model FLOPs are the published
count; and a rehearsal run goes through every step of a run, from the
seed to the one result line.  No device metric is read here."""
import json
import os
import subprocess
import sys
import types

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHIP = os.path.join(ROOT, "benchmark", "chip")
if CHIP not in sys.path:
    sys.path.insert(0, CHIP)

import common  # noqa: E402
from counts import resnet as resnet_counts  # noqa: E402


def _run(*args):
    """``run.py`` as the driver starts it, on the CPU; without the
    suite's virtual devices, so that it shares its compile cache with a
    run made by hand."""
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, os.path.join(CHIP, "run.py"), *args],
        capture_output=True, text=True, env=env, timeout=900, cwd=ROOT)


def test_a_cell_refuses_the_cpu_and_prints_no_result():
    r = _run("--workload", "resnet50_train_b256", "--seed", "0",
             "--seconds", "3", "--trace", "0")
    assert r.returncode != 0
    assert r.stdout.strip() == "", r.stdout[-500:]
    assert "no accelerator" in r.stderr


def _device(platform, kind):
    return types.SimpleNamespace(platform=platform, device_kind=kind)


@pytest.mark.parametrize("devices, asked, names", [
    ([_device("cpu", "cpu")], 1, ["no accelerator"]),
    ([_device("tpu", "TPU v9")], 1, ["TPU v9", "peaks.json"]),
    ([_device("tpu", "TPU v5 lite")] * 2, 4, ["4 chip", "has 2"]),
], ids=["cpu", "unknown_kind", "too_few"])
def test_require_chips_exits_naming_what_is_missing(monkeypatch, devices,
                                                    asked, names):
    monkeypatch.setattr(jax, "devices", lambda: devices)
    with pytest.raises(SystemExit) as e:
        common.require_chips(asked)
    for name in names:
        assert name in str(e.value), e.value


def test_require_chips_hands_out_the_chips_with_their_peaks(monkeypatch):
    devices = [_device("tpu", "TPU v5 lite")] * 4
    monkeypatch.setattr(jax, "devices", lambda: devices)
    got, peaks = common.require_chips(1)
    assert got == devices[:1]
    assert peaks["bf16_flops_per_s"] == 197e12
    # the rehearsal switch alone lets a CPU through, and it has no peaks
    monkeypatch.setattr(jax, "devices", lambda: [_device("cpu", "cpu")])
    assert common.require_chips(1, rehearsal=True)[1] is None


def test_an_unknown_workload_names_the_known_cells():
    with pytest.raises(SystemExit) as e:
        common.load_cell("resnet50_train_b512")
    for cell in common.load_json(ROOT, "BENCHMARK.json")["workloads"]:
        assert cell["name"] in str(e.value)


def test_the_resnet_cell_is_found_by_its_names():
    cell = common.load_cell("resnet50_train_b256")
    assert cell["config_entry"]["name"] == "resnet50_v1"
    assert (cell["chips"], cell["run_seconds"]) == (1, 40)
    assert cell["model"]["family"] == "resnet"
    assert cell["model"]["batch_size"] == 256
    assert cell["traffic_params"]["driver"]
    assert [m["name"] for m in cell["end_to_end"]] \
        == ["train_step_ms", "setup_s"]
    per_layer = {m["name"] for m in cell["per_layer"]}
    # its own metrics and none of the token cell's
    assert {"model_mfu_pct.train", "conv_roofline",
            "device_idle_pct.train"} <= per_layer
    assert not per_layer & {"flash_train_roofline", "loop_device_pct.train"}
    for name in per_layer:
        assert os.path.exists(os.path.join(CHIP, "metrics", name + ".json"))
    limits = common.load_json(CHIP, "limits", "resnet50_train_b256.json")
    assert limits["limits"]


def test_every_per_layer_metric_has_its_file_and_its_reader():
    bench = common.load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["per_layer"]:
        spec = common.load_json(CHIP, "metrics", m["name"] + ".json")
        mod, fn = spec["reader"].split(".")
        assert callable(getattr(common.module("readers", mod), fn)), m
        assert set(m["workloads"]) <= cells
    setup = [m for m in bench["per_layer"] if m["moves"] == "setup_s"]
    assert [m["name"] for m in setup] == [
        "import_s.setup", "state_s.setup", "step_trace_s.setup",
        "step_compile_s.setup", "step_programs.setup"]
    for m in setup:
        # the program's own record, read in every cell
        assert m["source"] == "program_span" and m["better"] == "lower"
        assert set(m["workloads"]) == cells
        spec = common.load_json(CHIP, "metrics", m["name"] + ".json")
        assert spec["reader"].startswith("start.") and spec["match_note"]
        assert spec["until"] == "mx.train.step.build"


def test_resnet_model_flops_are_the_published_count():
    """What ``model_mfu_pct.train`` divides by: three times the forward
    of 4.089 GMAC an image (He et al.'s network with the stride on the
    3x3), two operations a multiply-accumulate, 256 images."""
    model = common.load_json(CHIP, "configs", "resnet50_v1.json")
    assert resnet_counts.model_flops_per_step(model) \
        == pytest.approx(3 * 2 * 4.089e9 * 256, rel=5e-3)


def _rehearse(seed):
    r = _run("--rehearsal", "1", "--workload", "tiny_train", "--seed",
             str(seed), "--seconds", "3", "--trace", "0")
    assert r.returncode == 0, r.stderr[-2000:]
    lines = r.stdout.strip().splitlines()
    return json.loads(lines[-1]), r


@pytest.fixture(scope="module")
def seed0():
    return _rehearse(0)


@pytest.fixture(scope="module")
def seed1(seed0):
    # after seed0, whose run filled the compile cache
    return _rehearse(1)


def test_a_rehearsal_run_ends_in_one_correct_result_line(seed0):
    result, r = seed0
    assert [ln for ln in r.stdout.splitlines() if ln.startswith("{")] \
        == [r.stdout.strip().splitlines()[-1]]
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    for name in ("train_step_ms", "setup_s"):
        assert result["metrics"][name]["value"] > 0
    assert result["device"]["platform"] == "cpu"
    # every compared number stands beside its limit, in the line and as
    # the last lines of stderr
    assert result["compared"] and list(result)[-1] == "compared"
    for name, c in result["compared"].items():
        assert c["value"] <= c["limit"], (name, c)
        assert "compared %s = " % name in r.stderr


def test_the_seed_reaches_the_weights_and_the_batches(seed0, seed1):
    a, b = seed0[0]["compared"], seed1[0]["compared"]
    assert seed1[0]["correct"] is True
    assert a.keys() == b.keys()
    for name in ("head_grad_diff", "grad_norm_gap.median",
                 "update_norm_gap.median"):
        assert a[name]["value"] != b[name]["value"], name
