"""The benchmark files of Laguna-S-2.1's cell (``benchmark/chip``: driver,
builder, reference, counts, readers) at toy size on the CPU: control
flow and arithmetic only, no device metric."""
import os
import sys
import time

import jax
import pytest

CHIP = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark", "chip")
if CHIP not in sys.path:
    sys.path.insert(0, CHIP)

import common  # noqa: E402
import xplane  # noqa: E402
from builders import window_moe_decoder as builder  # noqa: E402
from counts import window_moe_decoder as counts  # noqa: E402
from drivers import train_window_moe  # noqa: E402
from readers import dsa as dsa_readers  # noqa: E402
from readers import looped as looped_readers  # noqa: E402
from reference import window_moe_decoder as ref  # noqa: E402

CELL = "laguna_s21_train_1x8192"
CONTROLS = ("full_attention", "no_gate", "no_shared_expert", "fp8",
            "unchanged_state")
# Limits of the toy run, bf16 on the CPU, each between what the program
# read on three seeds and what the weakest control that moves the number
# read (a sweep by hand; the cell's own limits come from chip readings
# and live in limits/<cell>.json).  At 256 tokens a router's near tie
# that bf16 flips moves a whole expert's share, hence the router's wide
# limit.
TOY_LIMITS = {
    "loss_gap": 5e-4,                # program 1.9e-4; no gate 6.7e-4
    "head_grad_diff": 0.07,          # program 0.022; fp8 0.14
    "router_grad_diff": 0.3,         # program 0.15; fp8 0.41
    "gate_grad_diff": 0.1,           # program 0.039; fp8 0.20
    "grad_norm_gap.median": 0.006,   # program 0.0029; fp8 0.010
    "update_norm_gap.median": 0.01,  # program 3.0e-5; unchanged state 1
    "compiled_in_window": 0, "nonfinite_losses": 0}


def toy_model(param_dtype="bfloat16"):
    m = common.load_json(common.HERE, "configs", "laguna_s21.json")
    m.update({"hidden_size": 64, "num_key_value_heads": 2, "head_dim": 32,
              "num_attention_heads_per_layer": [4, 6, 6, 6, 4] + [4] * 43,
              "num_attention_heads": 4, "intermediate_size": 128,
              "moe_intermediate_size": 32,
              "shared_expert_intermediate_size": 32, "router_width": 16,
              "num_experts": 4, "first_expert_held": 2,
              "num_experts_per_tok": 4, "vocab_size": 96,
              "sliding_window": 48, "init_std": 0.05,
              "param_dtype": param_dtype, "sequences": 1, "seq_len": 256,
              "loss_chunk": 64})
    return m


def toy_ctx(seed, tmp, param_dtype="bfloat16", controls=()):
    limits = common.load_json(common.HERE, "limits", CELL + ".json")
    mix = common.load_json(common.HERE, "traffic", "train_1x8192.json")
    mix.update({"sequences": 1, "seq_len": 256})
    return {"cell": {"model": toy_model(param_dtype),
                     "traffic_params": mix},
            "seed": seed, "seconds": 2.0, "trace": False,
            "devices": jax.devices()[:1], "peaks": None,
            "t_start": time.monotonic(),
            "compiles": common.CompileCounter(),
            "controls": {c: limits["control"][c] for c in controls},
            "tracer": xplane.Tracer(os.path.join(str(tmp), "trace")),
            "builder_args": {"kernel_marker": None}}


@pytest.fixture(scope="module")
def toy_run(tmp_path_factory):
    return train_window_moe.run(toy_ctx(3000000019,
                                        tmp_path_factory.mktemp("toy"),
                                        controls=CONTROLS))


def test_two_bf16_steps_follow_the_reference(toy_run):
    # the program's first steps through TrainStep(forward_fn=...) in bf16
    # with AdamW, then the window, against the float32 reference
    judged = common.judge(toy_run["values"], TOY_LIMITS)
    assert all(c["ok"] for c in judged.values()), judged
    assert toy_run["attempted"] >= 2 and toy_run["failed"] == 0
    assert toy_run["end_to_end"]["train_step_ms"] > 0
    assert toy_run["memory_peak_bytes"] > 0
    # every step of the window said how many pairs reached the held
    # experts: 4 MoE layers, 256 tokens, top 4 of 16 with 4 held
    pairs = toy_run["facts"]["held_pairs"]
    assert len(pairs) == toy_run["attempted"]
    assert all(0 < p < 4 * 256 * 4 for p in pairs)


@pytest.mark.parametrize("control,must_fail", [
    ("full_attention", "head_grad_diff"),
    ("full_attention", "gate_grad_diff"),
    ("no_gate", "gate_grad_diff"), ("no_gate", "loss_gap"),
    ("no_shared_expert", "head_grad_diff"),
    ("no_shared_expert", "router_grad_diff"),
    ("fp8", "head_grad_diff"), ("fp8", "gate_grad_diff"),
    ("unchanged_state", "update_norm_gap.median")])
def test_each_control_fails_the_toy_limits(toy_run, control, must_fail):
    judged = common.judge(toy_run["control_values"][control],
                          {k: v for k, v in TOY_LIMITS.items()
                           if k in toy_run["control_values"][control]})
    assert not judged[must_fail]["ok"], judged


def test_in_float32_the_program_is_the_reference(tmp_path):
    """The same step with float32 parameters: loss, cross-entropy and
    every leaf's gradient and move agree to rounding."""
    got = train_window_moe.run(toy_ctx(11, tmp_path, "float32"))["values"]
    for name, v in got.items():
        assert v <= 1e-5, (name, v)


def test_the_cells_limits_file_names_what_the_driver_compares(toy_run):
    limits = common.load_json(common.HERE, "limits", CELL + ".json")
    assert set(limits["limits"]) == set(TOY_LIMITS) == set(toy_run["values"])
    assert set(limits["control"]) == set(CONTROLS)
    for how in limits["control"].values():
        ref.make_step(toy_model(), **how)


def test_the_library_config_is_the_files():
    from mxnet_tpu.models import laguna_s21_config
    model = common.load_json(common.HERE, "configs", "laguna_s21.json")
    cfg = builder.library_config(model)
    assert (cfg.dim, cfg.n_kv_heads, cfg.head_dim, cfg.hidden_dim) == \
        (3072, 8, 128, 12288)
    assert (cfg.moe_num_experts, cfg.moe_held, cfg.moe_top_k,
            cfg.moe_hidden_dim) == (256, 8, 10, 1024)
    assert (cfg.vocab_size, cfg.n_layers) == (12544, 5)
    # the layers' specs read from the file's lists are the library's own
    # constructor's
    assert cfg.layers == laguna_s21_config(n_layers=5).layers
    assert [(s.window, s.n_heads) for s in cfg.layers] == \
        [(0, 48), (512, 72), (512, 72), (512, 72), (0, 48)]
    for key in model["reduced"]:
        assert key in model["published"]
    # the file's count of what this chip holds is the reference's leaves'
    n = 0
    for spec in ref.leaf_specs(model).values():
        size = 1
        for d in spec["shape"]:
            size *= d
        n += size
    assert n == model["params_held"]
    assert abs(n / 1e6 - 811.0) < 0.5


def test_counts_at_the_cells_size():
    model = common.load_json(common.HERE, "configs", "laguna_s21.json")
    assert counts.band_pairs(model) == 4063488
    assert counts.expected_held_pairs_per_token(model) == 0.3125
    # 610 M multiply-accumulates a token forward: 30 TFLOP a step
    per_token = counts.model_flops_per_step(model) / 6 / 8192
    assert abs(per_token / 1e6 - 610) < 1.0
    # the sliding layers' share of it, and the band's own work
    assert abs(counts.window_attn_flops(model) / 1e12 - 1.573) < 0.001
    assert counts.flash_train_flops(model, {"flash_fwd": 2}) == \
        2 * 2 * 2.0 * 8192 * 8193 / 2 * 128 * 48
    assert counts.experts_flops(model, 320) == 9 * 2.0 * 3072 * 1024 * 320


def test_the_readers_read_what_the_driver_hands_them():
    model = common.load_json(common.HERE, "configs", "laguna_s21.json")
    scopes = {
        "jit_step|jvp(forward)/layer1/attention/window_attn/tiles_q512_k512"
        "/swa_fwd|tpu_custom_call": [3, 0.01],
        "jit_step|transpose(jvp(forward))/layer1/attention/window_attn/"
        "tiles_q512_k512/swa_bwd_dkv|tpu_custom_call": [3, 0.02],
        "jit_step|jvp(forward)/layer4/attention/tiles_q512_k512/flash_fwd"
        "|tpu_custom_call": [1, 0.01],
        "jit_step|jvp(forward)/layer1/feed_forward/experts/gmm/x|fusion":
            [4, 0.01],
        "jit_step|jvp(forward)/layer1/feed_forward/shared_expert/w1/dot"
        "|fusion": [4, 0.005]}
    run = {"facts": {"program": {"scopes": scopes,
                                 "programs": {"jit_step": [2, 0.6]}},
                     "held_pairs": [10240, 10240]},
           "peaks": {"bf16_flops_per_s": 197e12}, "model": model,
           "counts": counts}
    for name, reader in (("window_attn_roofline", dsa_readers),
                         ("experts_roofline", dsa_readers),
                         ("flash_train_roofline", looped_readers),
                         ("window_attn_device_pct.train", looped_readers),
                         ("shared_expert_device_pct.train", looped_readers),
                         ("experts_device_pct.train", looped_readers)):
        metric = common.load_json(common.HERE, "metrics", name + ".json")
        value = getattr(reader, metric["reader"].split(".")[1])(metric, run)
        assert value is not None and value > 0, name
    # the shared expert is beside the routed experts, not under them
    metric = common.load_json(common.HERE, "metrics",
                              "experts_device_pct.train.json")
    assert looped_readers.scope_device_pct(metric, run) == pytest.approx(
        100 * 0.01 / 0.055)
    # a run with nothing to read reads None
    metric = common.load_json(common.HERE, "metrics",
                              "window_attn_roofline.json")
    assert dsa_readers.scope_roofline(metric, dict(run, facts={})) is None
