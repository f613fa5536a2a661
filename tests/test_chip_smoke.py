"""chip_smoke.py under the CPU suite: control flow, not the chip.

The phases run here at ``tiny_config`` / a two-block ResNet on the
virtual CPU mesh — which proves the script's paths, arguments and checks,
and nothing about a TPU.  That the script REFUSES to pass without one is
the other half: with ``JAX_PLATFORMS=cpu`` it exits non-zero and prints
no ``"ok": true`` line.
"""
import json
import os
import subprocess
import sys

import jax
import pytest

from mxnet_tpu import serve
from mxnet_tpu.models import tiny_config

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
import chip_smoke  # noqa: E402


def _lines(capsys):
    return [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]


def _two_block_resnet():
    from mxnet_tpu.gluon.model_zoo.vision.resnet import (BottleneckV1,
                                                         ResNetV1)
    return ResNetV1(BottleneckV1, [1, 1], [16, 32, 64], classes=1000)


def _tiny_serve_cfg(**kw):
    base = dict(slots=2, page_size=8, pages=24, ladder=(16, 32), max_new=6,
                cache_dir=None, int8=False)
    base.update(kw)
    return serve.ServeConfig(**base)


def test_train_phase_two_block_resnet(capsys):
    chip_smoke.phase_train(jax.devices()[0], seed=0,
                           net_fn=_two_block_resnet, batch=4, image=32,
                           steps=5)
    (line,) = _lines(capsys)
    assert line["phase"] == "train" and len(line["losses"]) == 5
    assert line["compile_s"] > line["step_s"][-1]
    assert line["cache"] in ("cold", "warm")


def test_serve_phase_tiny_config(capsys):
    chip_smoke.phase_serve(jax.devices()[0], seed=0, cfg=tiny_config(),
                           serve_cfg=_tiny_serve_cfg(), n_requests=3,
                           prompt_range=(4, 30), decode_steps=3,
                           kernel_marker=None)
    (line,) = _lines(capsys)
    assert line["phase"] == "serve" and line["requests"] == 3
    assert line["max_abs_logit_diff"] <= line["logit_tolerance"]
    assert line["tokens_equal_dense_argmax"] == (not line["near_tie_tokens"])
    assert all(t["margin"] <= line["logit_tolerance"]
               for t in line["near_tie_tokens"])
    assert "reduced" not in line  # nothing was cut from tiny_config


def test_serve_phase_fails_when_the_engine_mixes_requests_up(monkeypatch):
    """Token ids of the right count are not enough: what the engine
    returns for a request must be what the dense forward of that
    request's own context picks."""
    real = chip_smoke._serve_all

    def mixed_up(srv, prompts, max_new):
        tokens = real(srv, prompts, max_new)
        return tokens[1:] + tokens[:1]

    monkeypatch.setattr(chip_smoke, "_serve_all", mixed_up)
    with pytest.raises(chip_smoke.SmokeFailure, match="no near-tie"):
        chip_smoke.phase_serve(jax.devices()[0], seed=0, cfg=tiny_config(),
                               serve_cfg=_tiny_serve_cfg(), n_requests=3,
                               prompt_range=(4, 30), decode_steps=3,
                               kernel_marker=None)


def test_serve_phase_fails_when_the_kernel_is_not_in_the_program():
    """On the CPU the dense stand-in is what compiles: the check that a
    chip run depends on must see that and fail, not pass it."""
    with pytest.raises(chip_smoke.SmokeFailure, match="dense stand-in"):
        chip_smoke.phase_serve(jax.devices()[0], seed=0, cfg=tiny_config(),
                               serve_cfg=_tiny_serve_cfg(), n_requests=1,
                               prompt_range=(4, 30))


@pytest.mark.skipif(len(jax.devices()) < 4, reason="needs 4 virtual devices")
def test_four_chip_phases_on_the_virtual_mesh(capsys):
    devices = jax.devices()[:4]
    cfg = tiny_config(n_heads=4, n_kv_heads=2)
    chip_smoke.phase_mesh_train(devices, seed=0, cfg=cfg, batch=4, seq=32,
                                kernel_marker=None, min_bytes=None)
    chip_smoke.phase_mesh_serve(devices, seed=0, cfg=cfg,
                                serve_cfg=_tiny_serve_cfg(), n_requests=2,
                                prompt_range=(4, 30), kernel_marker=None,
                                min_bytes=None)
    chip_smoke.phase_replicas(devices, seed=0, cfg=cfg,
                              serve_cfg=_tiny_serve_cfg(), n_requests=6,
                              prompt_range=(4, 30), min_bytes=None)
    train, served, replicas = _lines(capsys)
    assert train["phase"] == "mesh_train"
    assert train["max_rel_loss_diff"] <= train["loss_rtol"]
    assert served["phase"] == "mesh_serve" and served["tokens_equal"]
    assert "one_device_tokens_equal_dense_argmax" in served
    assert replicas["phase"] == "replicas"
    assert sum(replicas["dispatched_to"].values()) == 6


@pytest.mark.parametrize("argv,want", [
    ([], ["device", "train", "serve"]),
    (["--chips", "4"], ["device", "mesh_train", "mesh_serve", "replicas"]),
], ids=["one-chip", "four-chips"])
def test_chips_option_picks_only_its_own_phases(monkeypatch, capsys, argv,
                                                want):
    ran = []

    def phase(name, ret=None):
        return lambda *a, **kw: (ran.append(name), ret)[1]

    monkeypatch.setattr(chip_smoke, "phase_device",
                        lambda n: (ran.append("device"),
                                   jax.devices()[:n])[1])
    for name in ("train", "serve", "mesh_train", "mesh_serve", "replicas"):
        monkeypatch.setattr(chip_smoke, "phase_" + name, phase(name))
    # placed from outside, main() points this process's cache nowhere
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                       jax.config.jax_compilation_cache_dir or "unused")
    assert chip_smoke.main(argv) == 0
    assert ran == want
    last = _lines(capsys)[-1]
    assert last["ok"] is True and set(last) == {"ok", "device"}
    assert set(last["device"]) == {"platform", "kind", "count"}
    # the chips the run used, not the host's (eight virtual ones here)
    assert last["device"]["count"] == (4 if argv else 1)


def test_without_an_accelerator_the_script_fails_and_prints_no_ok():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                       env=env, capture_output=True, text=True, timeout=300,
                       cwd=ROOT)
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout
    assert "no accelerator" in r.stderr


# ----------------------------------------------------------------------
# what the smoke stands on
# ----------------------------------------------------------------------
def test_warm_pool_leaves_a_cache_placed_from_outside_alone(tmp_path,
                                                            monkeypatch):
    """With JAX_COMPILATION_CACHE_DIR set, WarmPool(cache_dir=...) sets
    no directory of its own and counts its hits where the variable
    points."""
    from mxnet_tpu.models import TransformerLM
    outside = str(tmp_path / "outside")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", outside)
    prev = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", outside)
    seen = []
    real_update = jax.config.update
    monkeypatch.setattr(
        jax.config, "update",
        lambda k, v: (seen.append(k), real_update(k, v))[1])
    try:
        net = TransformerLM(tiny_config())
        net.initialize()
        pool = serve.WarmPool(net, _tiny_serve_cfg(
            cache_dir=str(tmp_path / "own")))
        assert jax.config.jax_compilation_cache_dir == outside
        assert "jax_compilation_cache_dir" not in seen
        assert pool.stats["cache_dir"] == outside
        assert not (tmp_path / "own").exists()
    finally:
        real_update("jax_compilation_cache_dir", prev)


def test_compile_cache_is_the_checkout_s_when_not_placed(monkeypatch):
    from mxnet_tpu.utils import compile_cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    prev = jax.config.jax_compilation_cache_dir
    try:
        assert compile_cache.place_compile_cache() == \
            os.path.join(ROOT, ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_importing_the_framework_initialises_no_backend():
    """The launcher stays off the chip its children need: ``import
    mxnet_tpu`` must not touch a backend."""
    code = ("import mxnet_tpu\n"
            "from jax._src import xla_bridge\n"
            "assert not xla_bridge._backends, xla_bridge._backends\n")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=300, cwd=ROOT)
    assert r.returncode == 0, r.stderr
