"""The program's own names: ``mx.*`` host spans on the profiler's clock,
named scopes on the device's ops, per-token times in the request record.

One ``jax.profiler`` session at a time in a process: every session here
is opened inside a test (never at import), and the tests that open one
live in this one file so that a single xdist worker runs them in turn.
The host plane of the CPU profile is read with ``jax.profiler.ProfileData``
alone.
"""
import glob
import os
import re
import time

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon, parallel, profiler, serve
from mxnet_tpu import telemetry as tel
from mxnet_tpu.gluon.model_zoo import vision
from mxnet_tpu.models import TransformerLM, tiny_config


# ----------------------------------------------------------------------
# helpers
# ----------------------------------------------------------------------
def _mx_spans(trace_dir):
    """``[(name, start ns, end ns, args)]`` of every ``mx.*`` event on the
    host plane of the one profile under ``trace_dir``, by start."""
    paths = glob.glob(os.path.join(str(trace_dir), "plugins", "profile",
                                   "*", "*.xplane.pb"))
    assert len(paths) == 1, paths
    data = jax.profiler.ProfileData.from_file(paths[0])
    out = []
    for plane in data.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("mx."):
                    out.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns,
                                {k: v for k, v in e.stats}))
    return sorted(out, key=lambda s: (s[1], -s[2]))


def _children(spans, parent):
    return [s for s in spans if s is not parent
            and parent[1] <= s[1] and s[2] <= parent[2]]


def _tiny_step(seed=0):
    mx.np.random.seed(seed)
    net = vision.resnet18_v1(classes=10, thumbnail=True)
    net.initialize()
    x = mx.np.array(onp.random.RandomState(seed).randn(2, 3, 32, 32)
                    .astype("float32"))
    y = mx.np.array(onp.array([1, 7], "int32"))
    net(x)
    step = parallel.TrainStep(
        net, gluon.loss.SoftmaxCrossEntropyLoss(),
        mx.optimizer.SGD(learning_rate=0.1, momentum=0.9), mesh=None)
    return step, x, y


def _tiny_server(**kw):
    net = TransformerLM(tiny_config())
    net.initialize()
    args = dict(slots=3, page_size=8, pages=24, ladder=(16, 32),
                max_new=10, cache_dir=None, int8=False)
    args.update(kw)
    return serve.Server(net, serve.ServeConfig(**args))


def _pump(srv, rids, limit=200):
    """``engine_step`` by hand until every request is terminal; returns
    the number of calls."""
    done = [srv._done[r] for r in rids]
    for calls in range(1, limit + 1):
        srv.engine_step()
        if all(ev.is_set() for ev in done):
            return calls
    raise AssertionError("requests not finished in %d steps" % limit)


@pytest.fixture(scope="module")
def lowered_step():
    step, x, y = _tiny_step()
    return step.lower(x, y)


@pytest.fixture(scope="module")
def step_names(lowered_step):
    return re.findall(r'loc\("(jit\(step\)/[^"]*)"',
                      lowered_step.as_text(debug_info=True))


# ----------------------------------------------------------------------
# (1) device names of the training step
# ----------------------------------------------------------------------
@pytest.mark.parametrize("phase", ["jvp(forward)/",
                                   "transpose(jvp(forward))/",
                                   "optimizer/"])
def test_train_step_ops_carry_their_phase(step_names, phase):
    assert any(n.startswith("jit(step)/" + phase) for n in step_names)


def test_train_step_ops_carry_the_blocks_registered_names(step_names):
    # a Sequential's child is numbered: <index>_<Type>; an attribute
    # keeps its name (``features``, ``body``)
    bn = [n for n in step_names if "/1_BatchNorm/" in n]
    assert any(n.startswith("jit(step)/jvp(forward)/features/") for n in bn)
    assert any(n.startswith("jit(step)/transpose(jvp(forward))/features/")
               for n in bn)
    assert any("/0_BasicBlockV1/body/0_Conv2D/" in n for n in step_names)
    assert any("jvp(forward)/output/" in n for n in step_names)


def test_batch_norm_sums_lie_under_their_block_in_both_phases(step_names):
    # batch norm is a custom_vjp: its forward sums (and rsqrt) and its
    # backward's sums still carry the block's path, which is what
    # ``bn_device_pct.train`` matches; no wrapper's name is put between
    def blocks(phase, prim):
        return {m.group(1) for m in (
            re.fullmatch(r"jit\(step\)/%s/(.*/\d+_BatchNorm)/%s"
                         % (re.escape(phase), prim), n)
            for n in step_names) if m}
    fwd = blocks("jvp(forward)", "reduce_sum")
    assert len(fwd) == 19    # 16 in the blocks' bodies, 3 downsamples
    assert fwd == blocks("jvp(forward)", "rsqrt")
    assert fwd == blocks("transpose(jvp(forward))", "reduce_sum")


def test_train_step_program_keeps_its_name(lowered_step, step_names):
    # the benchmark's match rules name the program jit_step
    assert re.search(r"HloModule jit_step\b",
                     lowered_step.compile().as_text())
    scoped = sum(n.startswith(("jit(step)/jvp(forward)/",
                               "jit(step)/transpose(jvp(forward))/",
                               "jit(step)/optimizer/"))
                 for n in step_names)
    assert scoped >= 0.95 * len(step_names)


@pytest.fixture(scope="module")
def looped_names():
    """Op names of the compiled looped training step (the passes are a
    scan, whose body's names are whole only in the compiled text), the
    flash kernels interpreted (``MXNET_PALLAS_INTERPRET``'s switch, set
    for this lowering alone): 128 tokens, heads of 64."""
    from mxnet_tpu.models import LoopedLM
    from mxnet_tpu.ndarray.ndarray import NDArray
    from mxnet_tpu.ops import pallas_ops
    cfg = tiny_config(dim=128, n_heads=2, n_kv_heads=2, hidden_dim=256,
                      n_layers=2, vocab_size=256, max_seq_len=128,
                      sandwich_norm=True, passes=4)
    net = LoopedLM(cfg)
    net.initialize()
    tok = NDArray(jnp.zeros((1, 128), jnp.int32))
    step = parallel.TrainStep(
        net, None, mx.optimizer.AdamW(learning_rate=1e-3), mesh=None,
        forward_fn=lambda net, t, l: net.loss(t, l, chunk=64))
    was = pallas_ops._INTERPRET
    pallas_ops._INTERPRET = True
    try:
        text = step.lower(tok, tok).compile().as_text()
    finally:
        pallas_ops._INTERPRET = was
    return set(re.findall(r'op_name="(jit\(step\)/[^"]*)"', text))


def test_looped_step_carries_loop_exit_loss_and_block_names(looped_names):
    fwd = "jit(step)/jvp(forward)/"
    bwd = "jit(step)/transpose(jvp(forward))/"
    # the passes are a scan under ``loop``; the blocks' registered names
    # lie beneath it, forward and backward
    for phase in (fwd, bwd):
        assert any(n.startswith(phase + "loop/") and "/layer1/" in n
                   and "/feed_forward/w2/" in n for n in looped_names)
        assert any(n.startswith(phase + "exit_loss/") for n in looped_names)
    assert any(n.startswith(fwd + "loop/") and "/attention_post_norm/" in n
               for n in looped_names)
    assert any(n.startswith(fwd + "loop/while/body/")
               and n.endswith("/norm/rsqrt") for n in looped_names)
    assert any(n.startswith(fwd + "exit_loss/exit_gate/")
               for n in looped_names)
    # the loss forms the head's and the hidden states' gradients in the
    # forward, chunk by chunk, under a scope of their own; what is left
    # under the backward's exit_loss is the gate's and the exit
    # distribution's backward and two scalings: no product of the head
    grads = {n for n in looped_names if "/head_grad/" in n}
    assert grads and all(n.startswith(fwd + "exit_loss/while/body/")
                         for n in grads)
    assert any(n.endswith("/head_grad/dot_general") for n in grads)
    assert any(n.startswith(fwd + "exit_loss/while/body/")
               and n.endswith("/dot_general") and n not in grads
               for n in looped_names)          # the chunk's logits
    assert not any(n.startswith(bwd + "exit_loss/while/")
                   for n in looped_names)
    assert any(n.startswith(bwd + "exit_loss/exit_gate/")
               for n in looped_names)
    scoped = sum(n.startswith((fwd + "loop/", bwd + "loop/",
                               fwd + "exit_loss/", bwd + "exit_loss/",
                               fwd + "tok_embeddings/",
                               bwd + "tok_embeddings/",
                               "jit(step)/optimizer/"))
                 for n in looped_names)
    assert scoped >= 0.9 * len(looped_names)


def test_looped_step_tells_recomputed_ops_from_first_time_ops(looped_names):
    # jax writes checkpoint/rematted_computation into a recomputed op's
    # path; the backward of a marked block carries checkpoint alone, its
    # first forward neither (``recompute_device_pct.train`` reads this)
    again = {n for n in looped_names if "/rematted_computation/" in n}
    assert again and all("/checkpoint/rematted_computation/" in n
                         and n.startswith("jit(step)/transpose(")
                         for n in again)
    first = {n for n in looped_names
             if n.startswith("jit(step)/jvp(forward)/loop/")}
    assert first and not any("checkpoint" in n for n in first)
    assert any("/checkpoint/feed_forward/" in n for n in looped_names)


@pytest.mark.parametrize("kernel,phase,there", [
    ("flash_fwd", "jit(step)/jvp(forward)/loop/", True),
    ("flash_fwd", "/checkpoint/rematted_computation/", False),
    ("flash_bwd_dq", "/checkpoint/attention/", True),
    ("flash_bwd_dkv", "/checkpoint/attention/", True)],
    ids=["forward", "recomputed", "dq", "dkv"])
def test_looped_step_keeps_the_flash_kernels_names(looped_names, kernel,
                                                   phase, there):
    # the kernel's ``name=`` is a component of the path under the
    # block's ``attention``, the tile it runs the one right above it
    # (interpreted here, the kernel's own ops lie beneath it; on the
    # chip it is .../tiles_q<bq>_k<bk>/<kernel>/pallas_call).  A marked
    # block keeps the forward kernel's output and row sums, so no
    # kernel is among the recomputed ops
    hits = [n for n in looped_names
            if re.search(r"/layer\d/[^ ]*attention/tiles_q\d+_k\d+/%s\)*/"
                         % kernel, n)
            and phase in n and "/loop/while/body/" in n]
    assert bool(hits) == there, (kernel, phase, hits[:3])


@pytest.fixture(scope="module")
def eva_names():
    """Op names of the compiled EvaByte training step, the EVA kernels
    interpreted: three windows of 256 bytes, chunks of 2, heads of 64."""
    from mxnet_tpu.models import EvaByteLM, evabyte_6p5b_config
    from mxnet_tpu.ndarray.ndarray import NDArray
    from mxnet_tpu.ops import pallas_ops
    cfg = evabyte_6p5b_config(dim=128, n_layers=2, n_heads=2, n_kv_heads=2,
                              hidden_dim=256, window_size=256, chunk_size=2,
                              max_seq_len=1024, dtype="float32")
    net = EvaByteLM(cfg)
    net.initialize()
    tok = NDArray(jnp.zeros((1, 768), jnp.int32))
    lab = NDArray(jnp.zeros((1, 768, 8), jnp.int32))
    step = parallel.TrainStep(
        net, None, mx.optimizer.AdamW(learning_rate=1e-3), mesh=None,
        forward_fn=lambda net, t, l: net.loss(t, l, heads=True))
    was = pallas_ops._INTERPRET
    pallas_ops._INTERPRET = True
    try:
        text = step.lower(tok, lab).compile().as_text()
    finally:
        pallas_ops._INTERPRET = was
    return set(re.findall(r'op_name="(jit\(step\)/[^"]*)"', text))


@pytest.mark.parametrize("scope,phase", [
    ("eva_prep", "first forward"), ("eva_prep", "backward"),
    ("eva_flash", "first forward"), ("eva_flash", "backward")])
def test_eva_step_carries_the_attentions_four_parts(eva_names, scope, phase):
    # both parts lie under ``eva`` under the block's ``attention``, in
    # the first forward and in the backward (``eva_attn_device_pct.train``
    # reads ``eva``, ``eva_remote_device_pct.train`` ``eva_prep``, the
    # pooling); the parts the fused kernel ended are gone
    if phase == "first forward":
        where = "jit(step)/jvp(forward)/layer1/attention/eva/%s/" % scope
        assert any(n.startswith(where) for n in eva_names), scope
    else:
        assert any(n.startswith("jit(step)/transpose(jvp(forward))/")
                   and "/layer1/checkpoint/attention/eva/%s/" % scope in n
                   for n in eva_names), scope
    # nothing of the attention lies outside ``eva`` but projections and
    # rotary
    assert not any("/%s/" % scope in n and "/eva/" not in n
                   for n in eva_names)
    assert not any(re.search(r"/eva_(local|remote|merge)/", n)
                   for n in eva_names)


def test_eva_step_carries_mbp_loss(eva_names):
    fwd = "jit(step)/jvp(forward)/mbp_loss/"
    assert any(n.startswith(fwd) and "dot_general" in n for n in eva_names)
    assert any(n.startswith("jit(step)/transpose(jvp(forward))/mbp_loss/")
               for n in eva_names)
    assert not any("/mbp_loss/" in n and "/layer" in n for n in eva_names)


@pytest.mark.parametrize("kernel,phase,there", [
    ("eva_flash_fwd", "jit(step)/jvp(forward)/layer", True),
    ("eva_flash_fwd", "/rematted_computation/", False),
    ("eva_flash_bwd_dq", "/checkpoint/attention/", True),
    ("eva_flash_bwd_dkv", "/checkpoint/attention/", True),
    ("eva_flash_bwd_dq", "/rematted_computation/", False),
    ("eva_flash_bwd_dkv", "/rematted_computation/", False)])
def test_eva_step_keeps_the_flash_kernels_names(eva_names, kernel, phase,
                                                there):
    # the kernels are named under the tiles they run (query, key and
    # summary), under the fused part that calls them; a marked block
    # keeps the forward's output and row sums, so none is among the
    # recomputed ops
    hits = [n for n in eva_names
            if re.search(r"/attention/eva/eva_flash/tiles_q\d+_k\d+_s\d+/"
                         r"%s\)*/" % kernel, n) and phase in n]
    assert bool(hits) == there, (kernel, phase, hits[:3])
    again = {n for n in eva_names if "/rematted_computation/" in n}
    assert again and any("/feed_forward/" in n for n in again)


@pytest.fixture(scope="module")
def dsa_step():
    """Op names of the compiled training step of a sparse-attention MoE
    decoder (``keye_vl2_30b_a3b_config`` at toy widths), every block
    marked for recomputation, the kernels interpreted: 512 tokens, so
    that the index kernel's tiles and the grouped matmul's rows run and
    the backward walks the causal tiles under the selection's bits; and
    the ``sparse_attn::`` counters the step's trace moved."""
    from mxnet_tpu.models import TransformerLM, keye_vl2_30b_a3b_config
    from mxnet_tpu.ndarray.ndarray import NDArray
    from mxnet_tpu.ops import pallas_ops
    cfg = keye_vl2_30b_a3b_config(
        vocab_size=256, dim=128, n_layers=2, n_heads=4, n_kv_heads=2,
        head_dim=128, index_heads=2, index_head_dim=64, index_topk=64,
        moe_num_experts=8, moe_top_k=2, moe_hidden_dim=128, moe_held=4,
        max_seq_len=1024)
    net = TransformerLM(cfg)
    net.initialize()
    for blk in net.layers:
        blk.recompute()
    tok = NDArray(jnp.zeros((1, 512), jnp.int32))
    step = parallel.TrainStep(
        net, None, mx.optimizer.AdamW(learning_rate=1e-3), mesh=None,
        forward_fn=lambda net, t, l: net.loss(t, l, chunk=256))
    was = pallas_ops._INTERPRET
    pallas_ops._INTERPRET = True
    before = profiler.get_counters()
    try:
        text = step.lower(tok, tok).compile().as_text()
    finally:
        pallas_ops._INTERPRET = was
    moved = {n: c - before.get(n, 0) for n, c in profiler.get_counters()
             .items() if n.startswith("sparse_attn::")
             and c != before.get(n, 0)}
    return set(re.findall(r'op_name="(jit\(step\)/[^"]*)"', text)), moved


@pytest.fixture(scope="module")
def dsa_names(dsa_step):
    return dsa_step[0]


FIRST = "jit(step)/jvp(forward)/layer1/"
BACK = "jit(step)/transpose(jvp(forward))/"


def _scopes(path):
    """``path`` as a pattern in which the routed experts' scopes may sit
    in the backward's own transform of a buffer
    (``experts/transpose(jvp(backward))/gmm/``)."""
    return re.escape(path).replace(
        "experts/", r"experts/(transpose\(jvp\(backward\)\)/)?")


@pytest.mark.parametrize("path,backward", [
    ("attention/indexer/", False), ("attention/indexer/indexer_loss/", True),
    ("attention/sparse_attn/", False), ("attention/sparse_attn/", True),
    ("feed_forward/experts/router/", False),
    ("feed_forward/experts/dispatch/", True),
    ("feed_forward/experts/gmm/", False), ("feed_forward/experts/gmm/", True),
    ("feed_forward/experts/combine/", False),
    ("feed_forward/experts/combine/", True)])
def test_dsa_moe_step_carries_indexer_sparse_attn_and_experts(
        dsa_names, path, backward):
    # dsa_indexer_device_pct.train, sparse_attn_device_pct.train and
    # experts_device_pct.train read these scopes
    if backward:
        assert any(n.startswith(BACK) and re.search(
            _scopes("/layer1/checkpoint/" + path), n) for n in dsa_names), path
    else:
        assert any(re.match(_scopes(FIRST + path), n)
                   for n in dsa_names), path


@pytest.mark.parametrize("kernel,where,there", [
    ("dsa_index", FIRST + "attention/indexer/", True),
    ("dsa_fwd", FIRST + "attention/sparse_attn/", True),
    ("dsa_masked_dq", BACK + "layer1/jvp(forward)/layer1/checkpoint/"
     "attention/sparse_attn/", True),
    ("dsa_masked_dkv", BACK + "layer1/jvp(forward)/layer1/checkpoint/"
     "attention/sparse_attn/", True),
    ("dsa_bwd", "/checkpoint/attention/sparse_attn/", False),
    ("dsa_index", "/rematted_computation/", False),
    ("dsa_fwd", "/rematted_computation/", False),
    ("dsa_masked_dq", "/rematted_computation/", False),
    ("dsa_bwd", "/rematted_computation/", False)])
def test_dsa_moe_step_keeps_its_kernels_names(dsa_names, kernel, where,
                                              there):
    # the sparse kernels under the tile they run (the forward: queries a
    # grid step, keys a query; the masked backward: its tile of queries
    # and keys); a marked block keeps the selection, its bits and the
    # forward kernel's output, so neither the scoring nor the forward
    # kernel is among the recomputed ops; the backward walks the causal
    # tiles under the bits, and the row kernel does not run
    tile = r"tiles_q\d+_k\d+/" if kernel != "dsa_index" else ""
    hits = [n for n in dsa_names
            if re.search(r"%s%s\)*/" % (tile, kernel), n) and where in n]
    assert bool(hits) == there, (kernel, where, hits[:3])
    # the block made again routes again; its experts' buffers are built
    # in their own backward, not here
    again = {n for n in dsa_names if "/rematted_computation/" in n}
    assert any("/feed_forward/experts/router/" in n for n in again)
    assert not any("/feed_forward/experts/" in n and "/gmm/" in n
                   for n in again)
    assert not any("/indexer/" in n and "top_k" in n for n in again)


def test_dsa_moe_step_counts_its_backward_path(dsa_step):
    # one traced backward a layer on the masked walk (PERF.md section 3)
    assert dsa_step[1] == {"sparse_attn::masked_bwd": 2}


def _laguna_step_names(n_experts):
    """Op names of the compiled training step of a window/full-attention
    MoE decoder (``laguna_s21_config``'s first two layers, a full dense
    and a sliding MoE one, at toy widths, 4 of ``n_experts`` held, top
    2), every block marked for recomputation, the kernels interpreted:
    512 tokens, a window of 128."""
    import dataclasses
    from mxnet_tpu.models import laguna_s21_config
    from mxnet_tpu.ndarray.ndarray import NDArray
    from mxnet_tpu.ops import pallas_ops
    cfg = laguna_s21_config(
        n_layers=2, vocab_size=256, dim=128, n_kv_heads=1, head_dim=128,
        hidden_dim=256, moe_num_experts=n_experts, moe_top_k=2,
        moe_hidden_dim=128, moe_held=4, max_seq_len=1024)
    cfg.layers = tuple(dataclasses.replace(
        s, n_heads=s.n_heads // 24, window=s.window and 128,
        shared_hidden_dim=128) for s in cfg.layers)
    net = TransformerLM(cfg)
    net.initialize()
    for blk in net.layers:
        blk.recompute()
    tok = NDArray(jnp.zeros((1, 512), jnp.int32))
    step = parallel.TrainStep(
        net, None, mx.optimizer.AdamW(learning_rate=1e-3), mesh=None,
        forward_fn=lambda net, t, l: net.loss(t, l, chunk=256))
    was = pallas_ops._INTERPRET
    pallas_ops._INTERPRET = True
    try:
        text = step.lower(tok, tok).compile().as_text()
    finally:
        pallas_ops._INTERPRET = was
    return set(re.findall(r'op_name="(jit\(step\)/[^"]*)"', text))


@pytest.fixture(scope="module")
def laguna_names():
    """8 experts: a bound at every pair, so one buffer of them all."""
    return _laguna_step_names(8)


@pytest.fixture(scope="module")
def laguna_bounded_names():
    """64 experts: a bound of 512 rows of the 1,024 pairs, as the cell
    holds 8 of 256 — the bounded buffer, and more of them in a loop
    under ``overflow`` for a call whose held pairs exceed it."""
    return _laguna_step_names(64)


@pytest.mark.parametrize("path,layer,backward", [
    ("attention/window_attn/", 1, False), ("attention/window_attn/", 1, True),
    ("attention/attn_gate/", 0, False), ("attention/attn_gate/", 1, True),
    ("feed_forward/shared_expert/", 1, False),
    ("feed_forward/shared_expert/", 1, True),
    ("feed_forward/experts/gmm/", 1, False)])
def test_laguna_step_carries_window_gate_and_shared_expert(
        laguna_names, path, layer, backward):
    # window_attn_device_pct.train and shared_expert_device_pct.train read
    # these scopes; the shared expert lies beside the routed experts, so
    # experts_device_pct.train keeps meaning the routed ones
    inner = _scopes("/layer%d/checkpoint/" % layer + path)
    if backward:
        assert any(n.startswith(BACK) and re.search(inner, n)
                   for n in laguna_names)
    else:
        assert any(re.match(_scopes("jit(step)/jvp(forward)/layer%d/%s"
                                    % (layer, path)), n)
                   for n in laguna_names)
    assert not any("/experts/" in n and "/shared_expert/" in n
                   for n in laguna_names)
    # the full layer has no window
    assert not any("/layer0/" in n and "/window_attn/" in n
                   for n in laguna_names)


@pytest.mark.parametrize("kernel,where,there", [
    ("swa_fwd", "jit(step)/jvp(forward)/layer1/attention/window_attn/",
     True),
    ("swa_bwd_dq", "/layer1/checkpoint/attention/window_attn/", True),
    ("swa_bwd_dkv", "/layer1/checkpoint/attention/window_attn/", True),
    ("flash_fwd", "jit(step)/jvp(forward)/layer0/attention/", True),
    ("swa_fwd", "/rematted_computation/", False),
    ("flash_fwd", "/rematted_computation/", False),
    ("swa_fwd", "/layer0/", False)])
def test_laguna_step_keeps_its_kernels_names(laguna_names, kernel, where,
                                             there):
    # the window kernels right under the tile they run, under the sliding
    # layer's window_attn; a marked block keeps the forward kernel's
    # output and row sums, so it is not among the recomputed ops
    hits = [n for n in laguna_names
            if re.search(r"tiles_q\d+_k\d+/%s\)*/" % kernel, n)
            and where in n]
    assert bool(hits) == there, (kernel, where, hits[:3])


EXPERTS = "/layer1/feed_forward/experts/"


@pytest.mark.parametrize("scope", ["router", "dispatch", "gmm", "combine"])
@pytest.mark.parametrize("backward", [False, True])
def test_laguna_step_keeps_its_experts_scopes_on_the_bounded_buffer(
        laguna_bounded_names, scope, backward):
    # experts_device_pct.train reads the experts scope, experts_roofline
    # the gmm scope inside it: the first bounded buffer carries both,
    # forward and backward, outside overflow
    if backward:
        where = [n for n in laguna_bounded_names if n.startswith(BACK)
                 and "/layer1/checkpoint/feed_forward/experts/" in n]
    else:
        where = [n for n in laguna_bounded_names
                 if n.startswith("jit(step)/jvp(forward)" + EXPERTS)]
    assert any("/%s/" % scope in n and "/overflow/" not in n
               for n in where), (scope, backward)


@pytest.mark.parametrize("scope", ["dispatch", "gmm", "combine"])
def test_laguna_step_takes_an_overflow_in_more_bounded_buffers(
        laguna_bounded_names, scope):
    for phase in ("jit(step)/jvp(forward)", BACK):
        assert any(n.startswith(phase) and re.search(
            r"/feed_forward/experts/overflow/while/body/(.*/)?%s/" % scope,
            n) for n in laguna_bounded_names), (phase, scope)
    # a block made again routes and sorts; nothing reads its experts'
    # output, so no buffer is built there
    again = [n for n in laguna_bounded_names
             if "/rematted_computation/" in n and "/experts/" in n]
    assert again and not any(re.search("/(gmm|combine|overflow)/", n)
                             for n in again)


def test_block_scope_names():
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(4, in_units=3), gluon.nn.Activation("relu"))
    assert [c._scope_name for c in net] == ["0_Dense", "1_Activation"]

    class Pair(gluon.HybridBlock):
        def __init__(self):
            super().__init__()
            self.left = gluon.nn.Dense(2, in_units=3)

        def forward(self, x):
            return self.left(x)

    pair = Pair()
    assert pair.left._scope_name == "left" and pair._scope_name is None
    pair.initialize()
    text = jax.jit(lambda a: pair(mx.np.array(a))._data).lower(
        jnp.ones((1, 3))).as_text(debug_info=True)
    assert "/Pair/left/" in text       # the type's name at the root


# ----------------------------------------------------------------------
# (2) spans of the serving engine
# ----------------------------------------------------------------------
ORDER = ["mx.serve.schedule", "mx.serve.decode.dispatch", "mx.serve.admit",
         "mx.serve.readback", "mx.serve.commit"]


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """A tiny server pumped by hand under one profiler session: the
    spans, what ``begin_step`` returned each step, and the records."""
    tmp = tmp_path_factory.mktemp("serve_trace")
    srv = _tiny_server()
    begin, snaps = srv.sched.begin_step, []

    def watched():
        snap = begin()
        snaps.append(snap)
        return snap

    srv.sched.begin_step = watched
    with jax.profiler.trace(str(tmp)):
        a = srv.submit([1, 2, 3, 4, 5], max_new=4)
        b = srv.submit(list(range(1, 20)), max_new=3)
        calls = _pump(srv, (a, b))
        srv.engine_step()           # an idle step has its span too
        calls += 1
    records = {r: srv.result(r, timeout=5) for r in (a, b)}
    return {"spans": _mx_spans(tmp), "snaps": snaps, "calls": calls,
            "records": records, "rids": (a, b)}


def test_engine_step_has_one_step_span_per_call(served):
    steps = [s for s in served["spans"] if s[0] == "mx.serve.step"]
    assert len(steps) == served["calls"]
    assert [s[3]["step_num"] for s in steps] == \
        list(range(1, served["calls"] + 1))


def test_engine_step_children_are_nested_in_order(served):
    spans = served["spans"]
    steps = [s for s in spans if s[0] == "mx.serve.step"]
    inner = [s for s in spans if s[0] != "mx.serve.step"
             and s[0].startswith("mx.serve.")]
    assert inner
    claimed = 0
    for step in steps:
        kids = [s for s in _children(spans, step)
                if s[0] in ORDER]       # prefill nests one deeper
        claimed += len(_children(spans, step))
        ranks = [ORDER.index(s[0]) for s in kids]
        assert ranks == sorted(ranks), [s[0] for s in kids]
        assert kids[0][0] == "mx.serve.schedule"
        assert kids[-1][0] == "mx.serve.commit"
    assert claimed == len(inner)        # no engine span outside a step
    busy = [s for s in steps if s[3]["active"]]
    assert busy
    for step in busy:
        names = [s[0] for s in _children(spans, step)]
        assert "mx.serve.decode.dispatch" in names
        assert "mx.serve.readback" in names


def test_prefill_span_carries_the_admission(served):
    spans = served["spans"]
    admits = [s for s in spans if s[0] == "mx.serve.admit"]
    prefills = [s for s in spans if s[0] == "mx.serve.prefill"]
    assert len(admits) == len(prefills) == 2
    want = {served["rids"][0]: (16, 5), served["rids"][1]: (32, 19)}
    for admit, prefill in zip(admits, prefills):
        assert prefill in _children(spans, admit)
        args = prefill[3]
        assert (args["padded"], args["true_len"]) == want[args["rid"]]
        assert args["start"] == 0
        assert admit[3]["rid"] == args["rid"]


def test_step_span_counts_what_begin_step_returned(served):
    steps = [s for s in served["spans"] if s[0] == "mx.serve.step"]
    assert len(steps) == len(served["snaps"])
    for step, snap in zip(steps, served["snaps"]):
        assert step[3]["active"] == len(snap)
        assert step[3]["context_tokens"] == sum(e["len"] + 1 for e in snap)
    assert max(s[3]["active"] for s in steps) == 2


def test_warm_pool_compiles_sit_under_compile_spans(tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        _tiny_server(prefix_cache=False)
    programs = [s[3]["program"] for s in _mx_spans(tmp_path)
                if s[0] == "mx.serve.compile"]
    assert programs == ["decode", "prefill16", "prefill32", "copy"]
    # build-path spans: on the host plane too, no mx.profiler running
    assert [s["args"]["program"] for s in profiler.build_spans()
            if s["name"] == "mx.serve.compile"][-4:] == programs


# ----------------------------------------------------------------------
# (3) spans of the training step
# ----------------------------------------------------------------------
def test_train_step_spans(tmp_path):
    step, x, y = _tiny_step()
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(3):
            step(x, y)
    spans = _mx_spans(tmp_path)
    steps = [s for s in spans if s[0] == "mx.train.step"]
    assert [s[3]["step_num"] for s in steps] == [1, 2, 3]
    builds = [s for s in spans if s[0] == "mx.train.step.build"]
    assert len(builds) == 1 and builds[0] in _children(spans, steps[0])
    dispatches = [s for s in spans if s[0] == "mx.train.step.dispatch"]
    assert len(dispatches) == 3
    for s, d in zip(steps, dispatches):
        assert d in _children(spans, s)
    assert dispatches[0] in _children(spans, builds[0])   # the compile
    # jit traces, lowers and compiles inside the first dispatch
    (trace,) = [s for s in spans if s[0] == "mx.train.step.trace"]
    assert trace in _children(spans, dispatches[0])
    assert trace[3]["program"] == "step"
    assert builds[0][3]["signature"] == "2x3x32x32:float32 2:int32"
    assert float(builds[0][3]["trace_s"]) > 0
    assert int(builds[0][3]["compiles"]) \
        + int(builds[0][3]["cache_loads"]) == 1


def test_train_step_plan_span(tmp_path, monkeypatch):
    # a step with marked blocks on a device that reports its memory
    # (the CPU does not: the numbers are given) plans which of them are
    # made again under mx.train.step.plan, inside the build span of the
    # first call with batches of a signature and before its dispatch;
    # the span's arguments are the plan's record
    from mxnet_tpu.models import TransformerLM
    from mxnet_tpu.ndarray.ndarray import NDArray
    from mxnet_tpu.parallel import train_step as ts
    monkeypatch.setattr(ts, "_device_memory", lambda device: (
        (1 << 40) + ts._RESERVE_BYTES, (1 << 40) - (1 << 30)))
    net = TransformerLM(tiny_config(dim=64, n_heads=2, n_kv_heads=2,
                                    hidden_dim=96, n_layers=2,
                                    vocab_size=256, max_seq_len=32))
    for blk in net.layers:
        blk.recompute()
    net.initialize()
    tok = NDArray(jnp.zeros((1, 32), jnp.int32))
    wide = NDArray(jnp.zeros((2, 32), jnp.int32))
    step = parallel.TrainStep(
        net, gluon.loss.SoftmaxCrossEntropyLoss(),
        mx.optimizer.AdamW(learning_rate=1e-3), mesh=None)
    with jax.profiler.trace(str(tmp_path)):
        for batch in (tok, tok, wide, tok):
            step(batch, batch)
    spans = _mx_spans(tmp_path)
    builds = [s for s in spans if s[0] == "mx.train.step.build"]
    plans = [s for s in spans if s[0] == "mx.train.step.plan"]
    dispatches = [s for s in spans if s[0] == "mx.train.step.dispatch"]
    # a plan a signature, none for the shape that came back
    assert len(builds) == len(plans) == 2 and len(dispatches) == 4
    for build, plan, dispatch in zip(builds, plans,
                                     (dispatches[0], dispatches[2])):
        assert plan in _children(spans, build)
        assert dispatch in _children(spans, build)
        assert plan[2] <= dispatch[1]           # planned, then run
    traces = [s for s in spans if s[0] == "mx.train.step.trace"]
    compiles = [s for s in spans if s[0] == "mx.train.step.compile"]
    assert len(traces) == len(compiles) == 4        # two tries a plan
    for plan in plans:
        inside = _children(spans, plan)
        assert [s[0] for s in inside] == ["mx.train.step.trace",
                                         "mx.train.step.compile"] * 2
        assert [s[3]["program"] for s in inside] \
            == ["step.spare0"] * 2 + ["step.spare2"] * 2
    assert all("from_cache" in c[3] for c in compiles)
    record = step.recompute_plan
    assert record["spared"] == ["layer0", "layer1"]
    args = plans[1][3]
    assert args["spared"] == "layer0 layer1"
    assert not args.get("made_again")       # (an empty argument is not kept)
    for key in ("free_bytes", "temp_bytes_rung0", "temp_bytes", "compiles"):
        assert int(args[key]) == record[key], key
    assert str(args["from_file"]) in ("False", "0")
    assert "refused" not in dispatches[0][3]
    # a step with no marked block plans nothing
    assert not [s for s in _mx_spans_of(_tiny_step, tmp_path / "plain")
                if s[0] == "mx.train.step.plan"]


def _mx_spans_of(make, trace_dir):
    step, x, y = make()
    with jax.profiler.trace(str(trace_dir)):
        step(x, y)
    return _mx_spans(trace_dir)


@pytest.mark.parametrize("name", ["mx.gluon.initialize", "mx.gluon.cast",
                                  "mx.train.step.init"])
def test_build_path_spans_are_annotations_in_a_session(tmp_path, name):
    with jax.profiler.trace(str(tmp_path)):
        step, x, y = _tiny_step()
        step.net.cast("float32")
    (span,) = [s for s in _mx_spans(tmp_path) if s[0] == name]
    assert int(span[3]["params"]) == 98
    key = "state_bytes" if name.endswith("init") else "bytes"
    assert int(span[3][key]) > 40e6
    # and on the host plane all the same
    assert [s for s in profiler.build_spans() if s["name"] == name]
    profiler.reset()


def test_data_loader_spans(tmp_path):
    data = gluon.data.ArrayDataset(onp.arange(12, dtype="float32")
                                   .reshape(6, 2))
    loader = gluon.data.DataLoader(data, batch_size=2)
    with jax.profiler.trace(str(tmp_path)):
        batches = list(loader)
    assert len(batches) == 3
    names = [s[0] for s in _mx_spans(tmp_path)]
    assert names.count("mx.data.h2d") == 3
    assert names.count("mx.data.next") == 4     # the last finds the end


def test_telemetry_span_reaches_the_device_timeline(tmp_path):
    tel.set_step_context(rank=2, step=9, gen=1)
    with jax.profiler.trace(str(tmp_path)):
        with tel.span("mx.test.fleet"):
            pass
    (span,) = _mx_spans(tmp_path)
    assert span[0] == "mx.test.fleet"
    assert (span[3]["rank"], span[3]["step"], span[3]["gen"]) == (2, 9, 1)


# ----------------------------------------------------------------------
# (4) per-token times
# ----------------------------------------------------------------------
def test_t_tokens_follow_the_tokens(served):
    for rid, rec in served["records"].items():
        assert rec["state"] == "done"
        tt = rec["t_tokens"]
        assert len(tt) == len(rec["tokens"]) > 1
        assert list(tt) == sorted(tt)
        assert tt[0] == rec["t_first"]
        assert tt[-1] == rec["t_done"]
        assert rec["t_submit"] <= rec["t_admit"] <= tt[0]


def test_t_tokens_survive_a_preemption():
    s = serve.SlotScheduler(2, 5, 2, 4)
    a = s.submit(4, 6)
    b = s.submit(4, 6)
    for _ in range(2):
        s.commit_prefill(s.admit_next(), 5)
    first = s.request(b)["t_tokens"]
    assert len(first) == 1 and first[0] == s.request(b)["t_first"]
    time.sleep(0.002)
    snap = s.begin_step()               # page pressure: b is preempted
    assert [e["rid"] for e in snap] == [a]
    assert s.request(b)["state"] == "waiting"
    assert s.request(b)["t_tokens"] == first
    s.commit_step(snap, [(6, False)])
    assert len(s.request(a)["t_tokens"]) == 2
    s.cancel(a)                         # room for b again
    plan = s.admit_next()
    assert plan["rid"] == b and plan["ntok"] == 1
    s.commit_prefill(plan, 7)
    rec = s.request(b)
    assert rec["tokens"] == (5, 7) and rec["preempts"] == 1
    assert rec["t_tokens"][0] == first[0] == rec["t_first"]
    assert rec["t_tokens"][1] > first[0]


# ----------------------------------------------------------------------
# (5) with no profiler session: nothing recorded, nothing changed
# ----------------------------------------------------------------------
def test_span_records_nothing_while_the_profiler_is_off():
    assert profiler.state() == "stop"
    before = len(profiler._state["events"])
    agg = dict(profiler._state["agg"])
    with profiler.span("mx.test.off", rid=1) as s:
        s.set(active=2)
    with profiler.step_span("mx.test.off.step", 3):
        pass
    assert len(profiler._state["events"]) == before
    assert dict(profiler._state["agg"]) == agg


def test_span_feeds_the_host_plane_while_mx_profiler_runs(tmp_path):
    profiler.set_config(filename=str(tmp_path / "p.json"))
    profiler.set_state("run")
    try:
        with profiler.step_span("mx.test.on", 4, kind="x") as s:
            s.set(active=2)
            with profiler.span("mx.test.on.child", rid=8):
                pass
    finally:
        profiler.set_state("stop")
    events = {e[1]: e for e in profiler._state["events"]
              if e[0] == "X" and e[1].startswith("mx.test.on")}
    assert events["mx.test.on"][6] == {"kind": "x", "active": 2,
                                       "step_num": 4}
    assert events["mx.test.on.child"][6] == {"rid": 8}
    assert profiler._state["agg"]["mx.test.on"][0] >= 1
    profiler.reset()


def test_annotate_keeps_feeding_the_aggregate_table():
    profiler.reset()
    with profiler.annotate("user_scope"):
        pass
    assert profiler._state["agg"]["user_scope"][0] == 1
    assert "user_scope" in profiler.dumps()
    profiler.reset()


def test_outputs_do_not_depend_on_a_profiler_session(tmp_path):
    """Bitwise the same losses and tokens with a session open and with
    none: a span changes nothing it encloses."""
    def losses():
        step, x, y = _tiny_step(seed=3)
        return [float(step(x, y)) for _ in range(3)]

    def tokens():
        mx.np.random.seed(5)
        srv = _tiny_server()
        rids = [srv.submit([3, 1, 4, 1, 5], max_new=5,
                           sampling={"seed": 11, "temperature": 0.8}),
                srv.submit([2, 7, 1, 8], max_new=4)]
        _pump(srv, rids)
        return [srv.result(r, timeout=5)["tokens"] for r in rids]

    plain = losses(), tokens()
    with jax.profiler.trace(str(tmp_path)):
        traced = losses(), tokens()
    assert plain == traced
    assert all(len(t) >= 4 for t in plain[1])
