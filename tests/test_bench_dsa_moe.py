"""The benchmark files of Keye-VL-2.0-30B-A3B's cell (``benchmark/chip``:
driver, builder, reference, counts, readers) at toy size on the CPU:
control flow and arithmetic only, no device metric."""
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
from builders import dsa_moe_decoder as builder  # noqa: E402
from counts import dsa_moe_decoder as counts  # noqa: E402
from drivers import train_dsa_moe  # noqa: E402
from readers import dsa as dsa_readers  # noqa: E402
from reference import dsa_moe_decoder as ref  # noqa: E402

CELL = "keye_vl2_30b_a3b_train_1x32768"
CONTROLS = ("dense_attention", "no_indexer_loss", "all_experts", "fp8",
            "unchanged_state")
# Limits of the toy run, bf16 on the CPU, each between what the program
# read and what the weakest control that moves the number read (a sweep
# by hand; the cell's own limits come from chip readings and live in
# limits/<cell>.json).  At 128 tokens a router's or the indexer's near
# tie that bf16 flips moves a whole expert's or key's share, hence the
# wide gradient limits.
TOY_LIMITS = {
    "ce_gap": 1e-3,                  # program 2.8e-4; fp8 3.1e-3
    "loss_gap": 0.02,                # program 1.9e-3; no indexer loss 0.044
    "head_grad_diff": 0.2,           # program 0.099; all experts 0.32
    "router_grad_diff": 0.5,         # program 0.17; fp8 1.0
    "indexer_grad_diff": 0.2,        # program 0.085; all experts 0.28
    "grad_norm_gap.median": 0.02,    # program 0.0089; all experts 0.032
    "update_norm_gap.median": 0.01,  # program 1.4e-5; unchanged state 1
    "selected_overlap_miss": 0.01,   # program 0.001; fp8 0.047
    "compiled_in_window": 0, "nonfinite_losses": 0}


def toy_model(param_dtype="bfloat16"):
    m = common.load_json(common.HERE, "configs", "keye_vl2_30b_a3b.json")
    m.update({"hidden_size": 64, "num_attention_heads": 4,
              "num_key_value_heads": 2, "head_dim": 32,
              "moe_intermediate_size": 32, "num_local_experts": 8,
              "num_experts": 4, "first_expert_held": 2,
              "num_experts_per_tok": 2, "num_hidden_layers": 2,
              "vocab_size": 96, "max_position_embeddings": 256,
              "sa_config": dict(m["sa_config"], indexer_num_heads=2,
                                indexer_head_dim=16, topk=16),
              "init_std": 0.05, "param_dtype": param_dtype, "sequences": 1,
              "seq_len": 128, "loss_chunk": 64})
    return m


def toy_ctx(seed, tmp, param_dtype="bfloat16", controls=()):
    limits = common.load_json(common.HERE, "limits", CELL + ".json")
    mix = common.load_json(common.HERE, "traffic", "train_1x32768.json")
    mix.update({"sequences": 1, "seq_len": 128})
    return {"cell": {"model": toy_model(param_dtype),
                     "traffic_params": mix},
            "seed": seed, "seconds": 0.3, "trace": False,
            "devices": jax.devices()[:1], "peaks": None,
            "t_start": time.monotonic(),
            "compiles": common.CompileCounter(),
            "controls": {c: limits["control"][c] for c in controls},
            "tracer": xplane.Tracer(os.path.join(str(tmp), "trace")),
            "builder_args": {"kernel_marker": None}}


@pytest.fixture(scope="module")
def toy_run(tmp_path_factory):
    return train_dsa_moe.run(toy_ctx(3000000019,
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
    # experts: 2 layers, 128 tokens, top 2 of 8 with 4 held
    pairs = toy_run["facts"]["held_pairs"]
    assert len(pairs) == toy_run["attempted"]
    assert all(0 < p < 2 * 128 * 2 for p in pairs)


@pytest.mark.parametrize("control,must_fail", [
    ("dense_attention", "head_grad_diff"),
    ("no_indexer_loss", "indexer_grad_diff"),
    ("no_indexer_loss", "loss_gap"),
    ("all_experts", "router_grad_diff"), ("all_experts", "head_grad_diff"),
    ("fp8", "head_grad_diff"), ("fp8", "selected_overlap_miss"),
    ("unchanged_state", "update_norm_gap.median")])
def test_each_control_fails_the_toy_limits(toy_run, control, must_fail):
    judged = common.judge(toy_run["control_values"][control],
                          {k: v for k, v in TOY_LIMITS.items()
                           if k in toy_run["control_values"][control]})
    assert not judged[must_fail]["ok"], judged


def test_in_float32_the_program_is_the_reference(tmp_path):
    """The same step with float32 parameters: loss, cross-entropy, the
    selection and every leaf's gradient and move agree to rounding."""
    got = train_dsa_moe.run(toy_ctx(11, tmp_path, "float32"))["values"]
    for name, v in got.items():
        assert v <= 1e-5, (name, v)


def test_the_cells_limits_file_names_what_the_driver_compares(toy_run):
    limits = common.load_json(common.HERE, "limits", CELL + ".json")
    assert set(limits["limits"]) == set(TOY_LIMITS) == set(toy_run["values"])
    assert set(limits["control"]) == set(CONTROLS)
    for how in limits["control"].values():
        ref.make_step(toy_model(), **how)


def test_the_library_config_is_the_files():
    model = common.load_json(common.HERE, "configs", "keye_vl2_30b_a3b.json")
    cfg = builder.library_config(model)
    assert (cfg.dim, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == \
        (2048, 32, 4, 128)
    assert (cfg.index_heads, cfg.index_head_dim, cfg.index_topk) == \
        (16, 64, 2048)
    assert (cfg.moe_num_experts, cfg.moe_held, cfg.moe_top_k,
            cfg.moe_hidden_dim, cfg.moe_every) == (128, 16, 8, 768, 1)
    assert (cfg.vocab_size, cfg.n_layers) == (18992, 4)
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


def test_counts_at_the_cells_size():
    model = common.load_json(common.HERE, "configs", "keye_vl2_30b_a3b.json")
    assert counts.selected_pairs(model) == 65012736
    assert counts.expected_held_pairs_per_token(model) == 1.0
    # indexer 5.5, sparse attention 14.9, projections 20.5, head 7.6 TFLOP
    assert abs(counts.model_flops_per_step(model) / 1e12 - 48.6) < 0.1
    assert abs(counts.sparse_attn_flops(model) / 1e12 - 14.9) < 0.05
    assert abs(counts.index_flops(model) / 1e12 - 5.5) < 0.05


def test_the_readers_read_what_the_driver_hands_them():
    model = common.load_json(common.HERE, "configs", "keye_vl2_30b_a3b.json")
    scopes = {
        "jit_step|jvp(forward)/layer0/attention/sparse_attn/gather|fusion":
            [4, 0.5],
        "jit_step|jvp(forward)/layer0/feed_forward/experts/gmm/x|fusion":
            [4, 0.01],
        "jit_step|jvp(forward)/layer0/attention/indexer/tiles/dsa_index"
        "|tpu_custom_call": [4, 0.02]}
    run = {"facts": {"program": {"scopes": scopes,
                                 "programs": {"jit_step": [2, 3.0]}},
                     "held_pairs": [131072, 131072]},
           "peaks": {"bf16_flops_per_s": 197e12}, "model": model,
           "counts": counts}
    for name in ("sparse_attn_roofline", "dsa_index_roofline",
                 "experts_roofline"):
        metric = common.load_json(common.HERE, "metrics", name + ".json")
        value = getattr(dsa_readers, metric["reader"].split(".")[1])(
            metric, run)
        assert value is not None and value > 0, name
    # a run with nothing to read reads None
    metric = common.load_json(common.HERE, "metrics",
                              "experts_roofline.json")
    assert dsa_readers.experts_roofline(metric, dict(run, facts={})) is None
