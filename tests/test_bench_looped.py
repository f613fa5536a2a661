"""The benchmark files of the looped decoder's cell
(``benchmark/chip``: driver, builder, reference, counts, readers) at toy
size on the CPU: control flow and arithmetic only, no device metric."""
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
from counts import looped_decoder as counts  # noqa: E402
from drivers import train_tokens  # noqa: E402
from readers import looped as readers  # noqa: E402

CELL = "ouro_2p6b_train_2x4096"
CONTROLS = ("fp8", "half_batch", "three_passes", "last_pass_grad",
            "unchanged_state", "no_bias_correction")
# Limits of the toy run, bf16 on the CPU, each between what three seeds
# of the program read and what the weakest control that moves the number
# read (a sweep by hand, PR 28; the cell's own limits come from chip
# readings and live in limits/<cell>.json)
TOY_LIMITS = {
    "ce_gap": 2e-4,                  # program <= 7.4e-5; fp8 >= 2.6e-4
    "mean_p_gap": 4e-4,              # program <= 1.4e-4; fp8 >= 1e-3
    "loss_gap": 5e-4,                # program <= 5.2e-5; three passes
                                     # >= 1.6e-3, half the batch >= 2e-3
    "head_grad_diff": 0.04,          # program <= 0.011; fp8 >= 0.115
    "gate_grad_diff": 0.03,          # program <= 0.0101; fp8 >= 0.092
    "grad_norm_gap.median": 0.004,   # program <= 0.0013; fp8 >= 0.0069
    "update_norm_gap.median": 0.01,  # program <= 9e-5; no bias correction
                                     # 0.48, a state left unchanged 1
    "compiled_in_window": 0, "nonfinite_losses": 0}


def toy_model():
    m = common.load_json(common.HERE, "configs", "ouro_2p6b.json")
    m.update({"hidden_size": 64, "head_dim": 16,
              "num_attention_heads": 4, "num_key_value_heads": 4,
              "intermediate_size": 128, "vocab_size": 256,
              "num_hidden_layers": 2, "max_position_embeddings": 128,
              "sequences": 2, "seq_len": 32, "loss_chunk": 24})
    return m


def toy_ctx(seed, tmp, controls=()):
    limits = common.load_json(common.HERE, "limits", CELL + ".json")
    mix = common.load_json(common.HERE, "traffic", "train_2x4096.json")
    mix.update({"sequences": 2, "seq_len": 32})
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
    return train_tokens.run(toy_ctx(3000000019,
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
    ("fp8", "head_grad_diff"), ("half_batch", "head_grad_diff"),
    ("three_passes", "mean_p_gap"), ("three_passes", "loss_gap"),
    ("last_pass_grad", "grad_norm_gap.median"),
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
    assert set(limits["control"]) == set(CONTROLS)
    # a number no control moves is not compared
    for name in limits["limits"]:
        if name in ("compiled_in_window", "nonfinite_losses"):
            continue
        assert any(toy_run["control_values"][c][name]
                   > 3 * toy_run["values"][name] for c in CONTROLS), name


@pytest.mark.parametrize("how", [
    {}, {"last_pass_grad": True}, {"passes": 3}, {"rows": 1}],
    ids=["plain", "last_pass_grad", "three_passes", "one_row"])
def test_the_reference_in_blocks_is_the_gradient_of_the_whole(how):
    """The reference follows its gradient a piece at a time (one jitted
    program a block, a norm, the exits, each run again under ``jax.vjp``,
    a shared weight's gradient added up over its uses): that is
    ``jax.grad`` of the same pieces composed in one function."""
    from reference import looped_decoder as ref
    m = dict(toy_model(), param_dtype="float32")
    params = common.make_weights(11, ref.leaf_specs(m))
    x, y = train_tokens.token_ring({"ring": 1, "sequences": 2,
                                    "seq_len": 32}, 11, m["vocab_size"])
    x, y = x[0], y[0]
    loss, parts, grads = ref.make_step(m, **how).gradient(params, x, y)
    block, final, exits = ref._pieces(m, "f32")
    passes = how.get("passes", m["total_ut_steps"])
    rows = how.get("rows", x.shape[0])

    def whole(p):
        total = 0.0
        for row, lab in zip(x[:rows], y[:rows]):
            h, hs = jnp.take(p["embed"], row, axis=0), []
            for t in range(passes):
                if how.get("last_pass_grad") and t == passes - 1:
                    h = jax.lax.stop_gradient(h)
                for i in range(m["num_hidden_layers"]):
                    h = block(h, ref._layer(p, i))
                h = final(h, p["final_norm"])
                hs.append(h)
            total += exits(hs, p["lm_head"], p["gate.w"], p["gate.b"],
                           lab, 1.0 / rows)[0]
        return total

    with jax.default_matmul_precision("highest"):
        want_loss, want = jax.value_and_grad(whole)(params)
    assert abs(float(loss) - float(want_loss)) < 1e-5 * float(want_loss)
    assert parts["ce"].shape == parts["p"].shape == (passes,)
    assert set(grads) == set(want)
    for k, w in want.items():
        # sums over 64 tokens in another order, float32
        assert float(jnp.linalg.norm(grads[k] - w)) \
            <= 1e-5 * float(jnp.linalg.norm(w)) + 1e-12, k


def test_token_ring_is_seeded_and_labels_follow_tokens():
    mix = {"ring": 3, "sequences": 2, "seq_len": 16}
    x, y = train_tokens.token_ring(mix, 4100000101, 50)
    x2, _ = train_tokens.token_ring(mix, 4100000101, 50)
    x3, _ = train_tokens.token_ring(mix, 7, 50)
    assert x.shape == y.shape == (3, 2, 16) and x.dtype == jnp.int32
    assert bool(jnp.all(x == x2)) and not bool(jnp.all(x == x3))
    assert bool(jnp.all(x[..., 1:] == y[..., :-1]))
    assert int(x.min()) >= 0 and int(x.max()) < 50


def test_model_flops_against_the_issues_reckoning():
    m = common.load_json(common.HERE, "configs", "ouro_2p6b.json")
    assert counts.block_matmul_params(m) == 51380224 \
        == m["matrix_params_per_block"]
    assert counts.applications(m) == 32
    assert round(counts.forward_flops_per_token(m) / 1e9, 2) == 4.63
    assert round(counts.model_flops_per_step(m) / 1e12, 1) == 113.8
    # the dq kernel makes three products a call, the dkv kernel four
    one = 2.0 * (4096 * 4097 / 2) * 128 * 16 * 2
    assert counts.flash_train_flops(
        m, {"flash_fwd": 64, "flash_bwd_dq": 32, "flash_bwd_dkv": 32}) \
        == (2 * 64 + 3 * 32 + 4 * 32) * one


def test_forward_flops_against_xla_cost_analysis():
    """XLA's own count of the toy forward against
    ``forward_flops_per_token``.  XLA counts a loop's body once, so the
    forward is counted by its parts: one pass over the stack of blocks
    and one exit's head with its cross-entropy, each four times.  XLA
    counts the whole attention square where the count is causal (half),
    and the elementwise work (norms, softmax, SwiGLU, rotary) that the
    count leaves out: at these widths the two add 3% (margin: 0-10%)."""
    from builders.looped_decoder import TrainCell
    from reference import looped_decoder as ref
    from mxnet_tpu.gluon.block import swapped_params
    from mxnet_tpu.ndarray.ndarray import NDArray
    from mxnet_tpu.ops.nn import chunked_softmax_cross_entropy
    m = toy_model()
    m.update({"hidden_size": 256, "head_dim": 64, "intermediate_size": 512,
              "vocab_size": 1024, "seq_len": 64, "param_dtype": "float32"})
    cell = TrainCell(m, common.make_weights(1, ref.leaf_specs(m)),
                     kernel_marker=None)
    handles = [p._data for p in cell.net.collect_params().values()]
    B, T, d = m["sequences"], m["seq_len"], m["hidden_size"]

    def one_pass(arrays, h):
        with swapped_params(handles, arrays):
            return cell.net._one_pass(NDArray(h))._data

    def flops(fn, *args):
        return jax.jit(fn).lower(*args).compile().cost_analysis()["flops"]

    h = jnp.zeros((B, T, d), jnp.float32)
    head = jnp.zeros((m["vocab_size"], d), jnp.float32)
    got = m["total_ut_steps"] * (
        flops(one_pass, [h_._data for h_ in handles], h)
        + flops(lambda h, w, y: chunked_softmax_cross_entropy(h, w, y, B * T),
                h.reshape(-1, d), head, jnp.zeros((B * T,), jnp.int32)))
    want = counts.forward_flops_per_token(m) * B * T
    assert 1.0 <= got / want <= 1.1, (got, want)


def test_readers_on_a_synthetic_reduction():
    program = {"scopes": {
        "jit_step|jvp(forward)/loop/while/body/layer0/attention/attention/"
        "jvp(flash_fwd)|custom-call.tpu_custom_call": [8, 0.08],
        "jit_step|transpose(jvp(forward))/loop/while/body/layer0/"
        "jvp(forward)/loop/while/body/layer0/checkpoint/"
        "rematted_computation/attention/attention/flash_fwd|"
        "custom-call.tpu_custom_call": [8, 0.08],
        "jit_step|transpose(jvp(forward))/loop/while/body/layer0/checkpoint/"
        "attention/attention/flash_bwd_dq|custom-call.tpu_custom_call":
            [8, 0.12],
        "jit_step|transpose(jvp(forward))/loop/while/body/layer0/checkpoint/"
        "attention/attention/flash_bwd_dkv|custom-call.tpu_custom_call":
            [8, 0.16],
        "jit_step|jvp(forward)/loop/while/body/layer0/feed_forward/w1|"
        "fusion.kOutput": [8, 0.36],
        "jit_step|jvp(forward)/exit_loss/while/body|fusion.kOutput":
            [16, 0.1],
        "jit_step|optimizer|fusion.kLoop": [90, 0.05],
        # an op that only holds others lasts as long as its body, which
        # the trace lists too: it counts on neither side of a share
        "jit_step|jvp(forward)/loop|while": [1, 0.6],
        "jit_step|transpose(jvp(forward))/exit_loss|while": [4, 0.3],
        "jit_step|unnamed|copy-done": [10, 0.05],
        "jit_other|loop|fusion.kLoop": [1, 5.0]}}
    m = common.load_json(common.HERE, "configs", "ouro_2p6b.json")
    run = {"facts": {"program": program}, "model": m, "counts": counts,
           "peaks": {"bf16_flops_per_s": 197e12}, "trace": None}

    def read(name):
        spec = common.load_json(common.HERE, "metrics", name + ".json")
        mod, fn = spec["reader"].split(".")
        assert mod == "looped"
        return getattr(readers, fn)(spec, run)

    assert read("loop_device_pct.train") == pytest.approx(80.0)
    assert read("exit_loss_device_pct.train") == pytest.approx(10.0)
    assert read("recompute_device_pct.train") == pytest.approx(8.0)
    one = 2.0 * (4096 * 4097 / 2) * 128 * 16 * 2
    want = 100.0 * (2 * 16 + 3 * 8 + 4 * 8) * one / 197e12 / 0.44
    assert read("flash_train_roofline") == pytest.approx(want)
    # a program without the names (the parent's), or another driver's
    # facts: nothing to read, and no error
    for facts in ({}, {"program": None}, {"program": {"scopes": {}}}):
        run["facts"] = facts
        for name in ("loop_device_pct.train", "flash_train_roofline"):
            assert read(name) is None


def test_selfcheck_has_no_mismatch_with_the_new_entries(capsys):
    import selfcheck
    del selfcheck.FAILS[:]
    selfcheck.counts()
    selfcheck.files()
    assert selfcheck.FAILS == []
    bench = common.load_json(common.REPO, "BENCHMARK.json")
    cell = common.load_cell(CELL)
    assert cell["model"]["family"] == "looped_decoder"
    assert cell["traffic_params"]["driver"] == "train_tokens"
    assert {m["name"] for m in cell["end_to_end"]} == \
        {"train_step_ms", "setup_s"}
    assert {m["name"] for m in cell["per_layer"]} == {
        "device_idle_pct.train", "model_mfu_pct.train",
        "loop_device_pct.train", "exit_loss_device_pct.train",
        "recompute_device_pct.train", "flash_train_roofline"} | {
        # read from the program's own record of its start, in every cell
        "import_s.setup", "state_s.setup", "step_trace_s.setup",
        "step_compile_s.setup", "step_programs.setup"}
    assert len(json.dumps(bench)) < 64 * 1024
    # the library's constructor is the file: published keys, one cut
    from builders.looped_decoder import _FIELDS
    from mxnet_tpu.models import ouro_2p6b_config
    m = cell["model"]
    lib = ouro_2p6b_config()
    assert {f: getattr(lib, f) for f in _FIELDS} == \
        {f: m[k] for f, k in _FIELDS.items()}
    assert lib.n_layers == m["published"]["num_hidden_layers"]
    assert m["num_hidden_layers"] == 8 and m["published"] == \
        {"num_hidden_layers": 48} and m["reduced"] == ["num_hidden_layers"]
    assert (m["hidden_size"], m["intermediate_size"], m["head_dim"],
            m["num_attention_heads"], m["num_key_value_heads"],
            m["vocab_size"], m["total_ut_steps"]) == \
        (2048, 5632, 128, 16, 16, 49152, 4)
