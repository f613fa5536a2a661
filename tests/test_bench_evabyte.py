"""The benchmark files of EvaByte's cell (``benchmark/chip``: driver,
builder, reference, counts, readers) at toy size on the CPU: control
flow and arithmetic only, no device metric."""
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import pytest

CHIP = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark", "chip")
if CHIP not in sys.path:
    sys.path.insert(0, CHIP)

import common  # noqa: E402
import xplane  # noqa: E402
from counts import eva_decoder as counts  # noqa: E402
from drivers import train_bytes  # noqa: E402
from readers import eva as eva_readers  # noqa: E402
from readers import looped as looped_readers  # noqa: E402
from reference import eva_decoder as ref  # noqa: E402

CELL = "evabyte_6p5b_train_1x8192"
CONTROLS = ("fp8", "no_summaries", "mean_pooling", "own_window_too",
            "next_byte_only", "unchanged_state", "no_bias_correction")
# Limits of the toy run, bf16 on the CPU, each between what three seeds
# of the program read and what the weakest control that moves the number
# read (a sweep by hand, PR 32; the cell's own limits come from chip
# readings and live in limits/<cell>.json)
TOY_LIMITS = {
    "ce_gap": 3e-4,                  # program <= 4.4e-5; fp8 >= 4.5e-4
    "loss_gap": 3e-4,                # program <= 1.3e-5; next byte only
                                     # >= 8.3e-4, no summaries >= 1.2e-3
    "head_grad_diff": 0.03,          # program <= 0.0056; fp8 >= 0.079
    "summary_grad_diff": 0.05,       # program <= 0.0084; fp8 >= 0.13, the
                                     # three summary controls >= 0.84
    "grad_norm_gap.median": 0.002,   # program <= 2.2e-4; fp8 >= 0.0039
    "update_norm_gap.median": 0.01,  # program <= 2.2e-5; no bias correction
                                     # 0.49, a state left unchanged 1
    "compiled_in_window": 0, "nonfinite_losses": 0}


def toy_model():
    m = common.load_json(common.HERE, "configs", "evabyte_6p5b.json")
    m.update({"hidden_size": 64, "num_attention_heads": 4,
              "num_key_value_heads": 4, "intermediate_size": 128,
              "num_hidden_layers": 2, "max_position_embeddings": 256,
              "window_size": 32, "chunk_size": 4, "init_std": 0.05,
              "sequences": 2, "seq_len": 128})
    return m


def toy_ctx(seed, tmp, controls=()):
    limits = common.load_json(common.HERE, "limits", CELL + ".json")
    mix = common.load_json(common.HERE, "traffic",
                           "train_bytes_1x8192.json")
    mix.update({"sequences": 2, "seq_len": 128})
    return {"cell": {"model": toy_model(), "traffic_params": mix},
            "seed": seed, "seconds": 0.3, "trace": False,
            "devices": jax.devices()[:1], "peaks": None,
            "t_start": time.monotonic(),
            "compiles": common.CompileCounter(),
            "controls": {c: limits["control"][c] for c in controls},
            "tracer": xplane.Tracer(os.path.join(str(tmp), "trace")),
            "builder_args": {"kernel_marker": None}}


@pytest.fixture(scope="module")
def toy_run(tmp_path_factory):
    return train_bytes.run(toy_ctx(3000000019,
                                   tmp_path_factory.mktemp("toy"),
                                   CONTROLS))


def test_three_bf16_steps_follow_the_reference(toy_run):
    # the program's first steps through TrainStep(forward_fn=...) in
    # bf16 with AdamW, then the window, against the float32 reference
    judged = common.judge(toy_run["values"], TOY_LIMITS)
    assert all(c["ok"] for c in judged.values()), judged
    assert toy_run["attempted"] >= 2 and toy_run["failed"] == 0
    assert toy_run["end_to_end"]["train_step_ms"] > 0
    assert toy_run["memory_peak_bytes"] > 0
    assert toy_run["facts"] == {} and toy_run["trace"] is None


@pytest.mark.parametrize("control,must_fail", [
    ("fp8", "head_grad_diff"), ("no_summaries", "summary_grad_diff"),
    ("mean_pooling", "summary_grad_diff"),
    ("own_window_too", "summary_grad_diff"),
    ("next_byte_only", "head_grad_diff"), ("next_byte_only", "loss_gap"),
    ("unchanged_state", "update_norm_gap.median"),
    ("no_bias_correction", "update_norm_gap.median")])
def test_each_control_fails_the_toy_limits(toy_run, control, must_fail):
    judged = common.judge(toy_run["control_values"][control],
                          {k: v for k, v in TOY_LIMITS.items()
                           if k in toy_run["control_values"][control]})
    assert not judged[must_fail]["ok"], judged


def test_the_cells_limits_file_names_what_the_driver_compares(toy_run):
    limits = common.load_json(common.HERE, "limits", CELL + ".json")
    # every number the driver computes is compared
    assert set(limits["limits"]) == set(TOY_LIMITS) == set(toy_run["values"])
    assert "summary_grad_diff" in limits["limits"]
    assert set(limits["control"]) == set(CONTROLS)
    # the limits file's controls are arguments of the reference's step
    for how in limits["control"].values():
        ref.make_step(toy_model(), **how)
    # a number no control moves is not compared
    for name in limits["limits"]:
        if name in ("compiled_in_window", "nonfinite_losses"):
            continue
        assert any(toy_run["control_values"][c][name]
                   > 3 * toy_run["values"][name] for c in CONTROLS), name


@pytest.mark.parametrize("how", [
    {}, {"summaries": "none"}, {"summaries": "own_window_too"},
    {"pooling": "mean"}, {"heads": "next_byte"}],
    ids=["plain", "no_summaries", "own_window_too", "mean_pooling",
         "next_byte_only"])
def test_the_reference_in_blocks_is_the_gradient_of_the_whole(how):
    """The reference follows its gradient a piece at a time (one jitted
    program a block and the heads, each run again under ``jax.vjp``):
    that is ``jax.grad`` of the same pieces composed in one function."""
    m = dict(toy_model(), param_dtype="float32")
    specs = ref.leaf_specs(m)
    params = ref.clamp(specs, common.make_weights(11, specs))
    x, y = train_bytes.byte_ring({"ring": 1, "sequences": 2,
                                  "seq_len": 128}, 11, m["vocab_size"], 8)
    x, y = x[0], y[0]
    loss, parts, grads = ref.make_step(m, **how).gradient(params, x, y)
    block, exits = ref._pieces(m, "f32", how.get("summaries", "before"),
                               how.get("pooling", "learned"),
                               how.get("heads", "all"))

    def whole(p):
        total = 0.0
        for row, lab in zip(x, y):
            h = jnp.take(p["embed"], row, axis=0)
            for i in range(m["num_hidden_layers"]):
                h = block(h, ref._layer(p, i))
            total += exits(h, p["final_norm"], p["lm_head"], lab,
                           1.0 / x.shape[0])[0]
        return total

    with jax.default_matmul_precision("highest"):
        want_loss, want = jax.value_and_grad(whole)(params)
    assert abs(float(loss) - float(want_loss)) < 1e-5 * float(want_loss)
    assert parts["ce"].shape == (8,)
    assert set(grads) == set(want)
    for k, w in want.items():
        assert float(jnp.linalg.norm(grads[k] - w)) \
            <= 1e-5 * float(jnp.linalg.norm(w)) + 1e-12, k
    if how.get("summaries") == "none" or how.get("pooling") == "mean":
        # the pooling vectors see nothing but the summaries
        assert all(float(jnp.abs(g).max()) == 0 for k, g in grads.items()
                   if k.split(".")[-1] in ref.POOLS)


def test_byte_ring_is_seeded_and_its_eight_labels_follow_the_ids():
    mix = {"ring": 3, "sequences": 2, "seq_len": 16}
    x, y = train_bytes.byte_ring(mix, 4100000101, 320, 8)
    x2, _ = train_bytes.byte_ring(mix, 4100000101, 320, 8)
    x3, _ = train_bytes.byte_ring(mix, 7, 320, 8)
    assert x.shape == (3, 2, 16) and y.shape == (3, 2, 16, 8)
    assert x.dtype == y.dtype == jnp.int32
    assert bool(jnp.all(x == x2)) and not bool(jnp.all(x == x3))
    for k in range(8):       # head k's label at t is the id at t + 1 + k
        assert bool(jnp.all(y[..., :16 - 1 - k, k] == x[..., 1 + k:]))
        assert bool(jnp.all(y[..., :-1, k][..., 1:] == y[..., 1:, k][..., :-1]))
    assert bool(jnp.all(y[..., :-1, 1] == y[..., 1:, 0]))
    assert int(x.min()) >= 0 and int(y.max()) < 320


def test_clamped_leaves_are_the_pooling_vectors_alone():
    m = toy_model()
    specs = ref.leaf_specs(m)
    raw = common.make_weights(5, specs)
    w = ref.clamp(specs, raw)
    s = (m["hidden_size"] // m["num_attention_heads"]) ** -0.5
    for n in specs:
        if n.split(".")[-1] in ref.POOLS:
            assert float(jnp.abs(w[n].astype(jnp.float32)).max()) <= s
            assert float(jnp.abs(raw[n].astype(jnp.float32)).max()) > s
        else:
            assert w[n] is raw[n]


def test_model_flops_against_the_issues_reckoning():
    m = common.load_json(common.HERE, "configs", "evabyte_6p5b.json")
    assert counts.block_matmul_params(m) == 202375168 \
        == m["matrix_params_per_block"]
    assert counts.visible_pairs(m) == (8392704, 1572864)
    assert round(counts.forward_flops_per_sequence(m) / 1e12, 2) == 14.09
    assert round(counts.model_flops_per_step(m) / 1e12, 2) == 42.26
    # attention: 163.3 GFLOP forward a layer, 4.6% of the model's FLOPs
    attn = 4.0 * 4096 * 9965568
    assert round(attn / 1e9, 1) == 163.3
    assert round(100 * 4 * attn / counts.forward_flops_per_sequence(m),
                 1) == 4.6
    assert counts.eva_attention_flops(m) == 7 * 2.0 * 4096 * 9965568 * 4
    # every parameter of the cut: 821.4 M
    specs = ref.leaf_specs(m)
    total = 0
    for s in specs.values():
        n = 1
        for d in s["shape"]:
            n *= d
        total += n
    assert total == m["params_held"] == 821366784


def test_forward_flops_against_xla_cost_analysis():
    """XLA's own count of the toy forward (the model's logits and loss,
    dense stand-ins for the kernels) against
    ``forward_flops_per_sequence``.  XLA counts the whole square of a
    window where the count is causal (half), every summary the masked
    stand-in multiplies, and the elementwise work (norms, softmax,
    SwiGLU, rotary, pooling, merge) the count leaves out: at these
    widths they add 5-25%."""
    from builders.eva_decoder import TrainCell
    from mxnet_tpu.gluon.block import swapped_params
    from mxnet_tpu.ndarray.ndarray import NDArray
    m = toy_model()
    m.update({"hidden_size": 256, "intermediate_size": 512,
              "window_size": 64, "chunk_size": 8, "seq_len": 256,
              "sequences": 1, "param_dtype": "float32"})
    specs = ref.leaf_specs(m)
    cell = TrainCell(m, common.make_weights(1, specs), kernel_marker=None)
    handles = [p._data for p in cell.net.collect_params().values()]

    def forward(arrays, x, y):
        with swapped_params(handles, arrays):
            return cell.net.loss(NDArray(x), NDArray(y))._data

    x, y = train_bytes.byte_ring({"ring": 1, "sequences": 1,
                                  "seq_len": 256}, 1, 320, 8)
    got = jax.jit(forward).lower([h._data for h in handles], x[0],
                                 y[0]).compile().cost_analysis()["flops"]
    want = counts.forward_flops_per_sequence(m)
    assert 1.0 <= got / want <= 1.3, (got, want)


def test_readers_on_a_synthetic_reduction():
    base = "jit_step|jvp(forward)/layer0/attention/eva/"
    back = "jit_step|transpose(jvp(forward))/layer0/layer0/checkpoint/" \
        "attention/eva/"
    program = {"programs": {"jit_step": [2, 1.0], "jit_other": [1, 5.0]},
               "scopes": {
        base + "eva_local/tiles_q512_k512/jvp(flash_fwd)|"
        "custom-call.tpu_custom_call": [8, 0.04],
        base + "eva_remote/tiles_q512_k128/jvp(flash_fwd)|"
        "custom-call.tpu_custom_call": [24, 0.02],
        base + "eva_prep/reduce_sum|fusion.kLoop": [8, 0.01],
        base + "eva_merge/add|fusion.kLoop": [8, 0.01],
        back + "eva_local/tiles_q512_k512/flash_bwd_dkv|"
        "custom-call.tpu_custom_call": [8, 0.06],
        back + "eva_remote/tiles_q512_k128/flash_bwd_dq|"
        "custom-call.tpu_custom_call": [24, 0.03],
        back + "eva_merge/mul|fusion.kLoop": [8, 0.01],
        "jit_step|transpose(jvp(forward))/layer0/layer0/checkpoint/"
        "rematted_computation/feed_forward/w1|fusion.kOutput": [8, 0.12],
        "jit_step|jvp(forward)/layer0/feed_forward/w1|fusion.kOutput":
            [8, 0.5],
        "jit_step|jvp(forward)/mbp_loss/dot_general|fusion.kOutput":
            [2, 0.1],
        "jit_step|optimizer|fusion.kLoop": [90, 0.1],
        # a scope that only starts with the attention's name is not it
        "jit_step|jvp(forward)/evaluate|fusion.kLoop": [1, 0.0],
        "jit_step|jvp(forward)/layer0|while": [1, 0.6],
        "jit_other|eva|fusion.kLoop": [1, 5.0]}}
    m = common.load_json(common.HERE, "configs", "evabyte_6p5b.json")
    run = {"facts": {"program": program}, "model": m, "counts": counts,
           "peaks": {"bf16_flops_per_s": 197e12}, "trace": None}

    def read(name):
        spec = common.load_json(common.HERE, "metrics", name + ".json")
        mod, fn = spec["reader"].split(".")
        return getattr({"looped": looped_readers,
                        "eva": eva_readers}[mod], fn)(spec, run)

    assert read("eva_attn_device_pct.train") == pytest.approx(18.0)
    assert read("eva_remote_device_pct.train") == pytest.approx(8.0)
    assert read("recompute_device_pct.train") == pytest.approx(12.0)
    want = 100.0 * 2 * counts.eva_attention_flops(m) / 197e12 / 0.18
    assert read("eva_attn_roofline") == pytest.approx(want)
    # a program without the names (the parent's), or another driver's
    # facts: nothing to read, and no error
    for facts in ({}, {"program": None},
                  {"program": {"scopes": {}, "programs": {}}}):
        run["facts"] = facts
        for name in ("eva_attn_device_pct.train", "eva_attn_roofline",
                     "eva_remote_device_pct.train"):
            assert read(name) is None


def test_selfcheck_has_no_mismatch_with_the_new_entries(capsys):
    import selfcheck
    del selfcheck.FAILS[:]
    selfcheck.counts()
    selfcheck.files()
    assert selfcheck.FAILS == []
    bench = common.load_json(common.REPO, "BENCHMARK.json")
    cell = common.load_cell(CELL)
    assert cell["model"]["family"] == "eva_decoder"
    assert cell["traffic_params"]["driver"] == "train_bytes"
    assert {m["name"] for m in cell["end_to_end"]} == \
        {"train_step_ms", "setup_s"}
    assert {m["name"] for m in cell["per_layer"]} == {
        "device_idle_pct.train", "model_mfu_pct.train",
        "recompute_device_pct.train", "eva_attn_device_pct.train",
        "eva_remote_device_pct.train", "eva_attn_roofline"} | {
        # read from the program's own record of its start, in every cell
        "import_s.setup", "state_s.setup", "step_trace_s.setup",
        "step_compile_s.setup", "step_programs.setup"}
    assert len(json.dumps(bench)) < 64 * 1024
    # the library's constructor is the file: published keys, one cut
    from builders.eva_decoder import _FIELDS, library_config
    from mxnet_tpu.models import evabyte_6p5b_config
    m = cell["model"]
    lib = evabyte_6p5b_config()
    assert {f: getattr(lib, f) for f in _FIELDS} == \
        {f: m[k] for f, k in _FIELDS.items()}
    assert lib.n_layers == m["published"]["num_hidden_layers"] == 32
    assert (lib.attn_impl, lib.residual_dtype) == ("eva", "float32")
    built = library_config(m)
    assert (built.n_layers, built.dtype) == (4, "bfloat16")
    assert m["num_hidden_layers"] == 4 and m["reduced"] == \
        ["num_hidden_layers"]
    assert (m["hidden_size"], m["intermediate_size"],
            m["num_attention_heads"], m["num_key_value_heads"],
            m["vocab_size"], m["window_size"], m["chunk_size"],
            m["num_pred_heads"], m["rope_theta"], m["rms_norm_eps"]) == \
        (4096, 11008, 32, 32, 320, 2048, 16, 8, 100000, 1e-5)
    # every key of the catalog's row is in the file as published
    published = {"attention_bias": False, "attention_class": "eva",
                 "fp32_ln": False, "fp32_logits": True,
                 "fp32_skip_add": True, "hidden_act": "silu",
                 "init_std": 0.01275, "max_position_embeddings": 32768,
                 "mixedp_attn": True, "norm_add_unit_offset": True,
                 "tie_word_embeddings": False}
    assert {k: m[k] for k in published} == published
    assert set(m["assumed"]) >= {"pooling_scale", "rope", "head_weights",
                                 "optimizer", "seq_len", "weights",
                                 "inputs", "head_dim"}
