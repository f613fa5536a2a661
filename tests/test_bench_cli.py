"""bench.py's contract with the device: a device phase that cannot run
fails the run by name, nothing is rerun on the CPU or zeroed, every
result says what it ran on, and an unknown chip has no peak."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
import bench  # noqa: E402

V5E = {"platform": "tpu", "device_kind": "TPU v5 lite", "device_count": 1}


def _stub_children(monkeypatch, fail=(), stamp=V5E):
    def child(which, phase_cap=720):
        if which in fail:
            raise RuntimeError("bench %s failed:\nboom" % which)
        where = bench.PHASES[which][1]
        out = {"phase": which, **(stamp if where == "device" else
                                  {"platform": "cpu", "device_kind": "cpu",
                                   "device_count": 8})}
        out["result"] = {"platform": "cpu", "n": 1} if where == "cpu" \
            else {"tflops": 1.0} if which in ("micro", "attention") \
            else 100.0
        return out
    monkeypatch.setattr(bench, "_run_isolated", child)
    monkeypatch.setattr(sys, "argv", ["bench.py"])


def test_unknown_device_kind_raises_from_the_peak_lookup():
    assert bench.chip_peak(bench.PEAK_BF16_TFLOPS, "TPU v5 lite") == 197.0
    with pytest.raises(KeyError, match="TPU v9"):
        bench.chip_peak(bench.PEAK_BF16_TFLOPS, "TPU v9")


def test_every_result_names_the_device_it_ran_on(monkeypatch, capsys):
    _stub_children(monkeypatch)
    bench.main()
    out = json.loads(capsys.readouterr().out)
    assert {k: out["extra"][k] for k in V5E} == V5E
    assert out["extra"]["resnet50_train_mfu"] > 0
    assert "failed_phases" not in out["extra"]
    for key in ("serve_continuous_batching", "ring_attention_cpu_mesh",
                "fault_overhead_coordinated_vs_raw"):
        assert out["extra"][key]["platform"] == "cpu"


def test_a_failed_device_phase_fails_the_run_by_name(monkeypatch, capsys):
    _stub_children(monkeypatch, fail=("infer", "infer_nhwc"))
    with pytest.raises(SystemExit, match=r"infer \(device\)"):
        bench.main()
    out = json.loads(capsys.readouterr().out)
    assert set(out["extra"]["failed_phases"]) == {"infer", "infer_nhwc"}
    # what failed is absent, not 0.0 under the metric's name
    assert "resnet50_inference_bf16_b32_img_per_sec" not in out["extra"]
    assert out["value"] == 100.0


def test_an_unknown_chip_fails_the_run(monkeypatch):
    _stub_children(monkeypatch, stamp=dict(V5E, device_kind="TPU v9"))
    with pytest.raises(KeyError, match="TPU v9"):
        bench.main()


def test_a_device_phase_refuses_the_cpu():
    r = subprocess.run([sys.executable, os.path.join(ROOT, "bench.py"),
                        "--only", "micro"], capture_output=True, text=True,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"),
                       timeout=300, cwd=ROOT)
    assert r.returncode != 0 and r.stdout == ""
    assert "device phase" in r.stderr


def test_a_cpu_phase_runs_for_real_on_its_own_virtual_mesh():
    """No stub: the phase child must give its phase the 8-device CPU mesh
    itself (pp=4 here), whatever XLA_FLAGS the caller had."""
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    r = subprocess.run([sys.executable, os.path.join(ROOT, "bench.py"),
                        "--only", "pipeline_bubble"], capture_output=True,
                       text=True, env=env, timeout=300, cwd=ROOT)
    assert r.returncode == 0, r.stderr[-2000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert (out["platform"], out["device_count"]) == ("cpu", 8)
    assert out["result"]["platform"] == "cpu"
    assert out["result"]["stages"] == 4
    assert out["result"]["pipeline_1f1b_bubble_frac"] > 0
