"""SPMD parallel layer tests on the 8-device CPU mesh (SURVEY.md §4's
multi-process-on-one-host trick, TPU edition)."""
import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon
from mxnet_tpu.gluon import nn
from mxnet_tpu import parallel
from mxnet_tpu.parallel import P
from mxnet_tpu.test_utils import assert_almost_equal

pytestmark = pytest.mark.skipif(len(jax.devices()) < 8,
                                reason="needs 8 virtual devices")


def test_create_mesh():
    mesh = parallel.create_mesh(dp=2, tp=4)
    assert mesh.shape == {"dp": 2, "tp": 4}
    mesh2 = parallel.create_mesh(dp=-1, tp=2)
    assert mesh2.shape["dp"] == 4


def test_shard_params():
    mesh = parallel.create_mesh(dp=2, tp=4)
    net = nn.Dense(16, in_units=8)
    net.initialize()
    shardings = parallel.shard_params(net, mesh,
                                      rules=[("weight", ("tp", None))])
    w = net.weight.data()._data
    assert w.sharding.spec == P("tp", None)


def test_train_step_dp():
    mesh = parallel.create_mesh(dp=8)
    net = nn.HybridSequential()
    net.add(nn.Dense(16, activation="relu"), nn.Dense(1))
    net.initialize()
    net(mx.np.ones((8, 4)))  # materialize
    opt = mx.optimizer.SGD(learning_rate=0.3)
    step = parallel.TrainStep(net, gluon.loss.L2Loss(), opt, mesh=mesh)
    onp.random.seed(0)
    X = onp.random.normal(0, 1, (32, 4)).astype("float32")
    w_true = onp.random.normal(0, 1, (4, 1)).astype("float32")
    y = X @ w_true
    losses = []
    for _ in range(50):
        losses.append(float(step(mx.np.array(X), mx.np.array(y))))
    assert losses[-1] < losses[0] * 0.1


def test_train_step_matches_single_device():
    # dp-sharded step must compute the same update as unsharded
    net1 = nn.Dense(2, in_units=3)
    net1.initialize(init=mx.init.One())
    net2 = nn.Dense(2, in_units=3)
    net2.initialize(init=mx.init.One())
    X = mx.np.array(onp.arange(24, dtype="float32").reshape(8, 3) / 10)
    y = mx.np.array(onp.ones((8, 2), dtype="float32"))
    opt1 = mx.optimizer.SGD(learning_rate=0.5)
    opt2 = mx.optimizer.SGD(learning_rate=0.5)
    mesh = parallel.create_mesh(dp=8)
    s1 = parallel.TrainStep(net1, gluon.loss.L2Loss(), opt1, mesh=mesh)
    s2 = parallel.TrainStep(net2, gluon.loss.L2Loss(), opt2, mesh=None)
    l1 = float(s1(X, y))
    l2 = float(s2(X, y))
    assert abs(l1 - l2) < 1e-5
    assert_almost_equal(net1.weight.data(), net2.weight.data(), rtol=1e-5,
                        atol=1e-6)


def test_train_step_zero1():
    mesh = parallel.create_mesh(dp=8)
    net = nn.Dense(8, in_units=16)
    net.initialize()
    opt = mx.optimizer.Adam(learning_rate=0.01)
    step = parallel.TrainStep(net, gluon.loss.L2Loss(), opt, mesh=mesh,
                              zero1=True)
    x = mx.np.random.normal(0, 1, (16, 16))
    y = mx.np.random.normal(0, 1, (16, 8))
    l0 = float(step(x, y))
    l5 = l0
    for _ in range(5):
        l5 = float(step(x, y))
    assert l5 < l0
    # states sharded over dp on dim 0 (16 % 8 == 0)
    st = step._states["weight"]
    assert st[0].sharding.spec == P("dp", None)


def test_train_step_zero1_matches_unsharded():
    """The ZeRO-1 overlap restructure (grads pinned to the dp-sharded
    state spec before the update) is numerically invisible: the sharded
    step reproduces the unsharded trajectory and weights exactly."""
    def mk(mesh, zero1):
        mx.np.random.seed(5)
        net = nn.Dense(8, in_units=16)
        net.initialize()
        opt = mx.optimizer.Adam(learning_rate=0.01)
        return net, parallel.TrainStep(net, gluon.loss.L2Loss(), opt,
                                       mesh=mesh, zero1=zero1)

    n1, s1 = mk(parallel.create_mesh(dp=8), True)
    n2, s2 = mk(None, False)
    x = mx.np.random.normal(0, 1, (16, 16))
    y = mx.np.random.normal(0, 1, (16, 8))
    for i in range(5):
        l1, l2 = float(s1(x, y)), float(s2(x, y))
        assert abs(l1 - l2) < 1e-5, (i, l1, l2)
    onp.testing.assert_allclose(n1.weight.data().asnumpy(),
                                n2.weight.data().asnumpy(),
                                rtol=1e-5, atol=1e-6)


def test_ring_attention_matches_dense():
    mesh = parallel.create_mesh(cp=8)
    B, H, T, D = 2, 4, 64, 16
    onp.random.seed(1)
    q = jnp.asarray(onp.random.normal(0, 1, (B, H, T, D)), jnp.float32)
    k = jnp.asarray(onp.random.normal(0, 1, (B, H, T, D)), jnp.float32)
    v = jnp.asarray(onp.random.normal(0, 1, (B, H, T, D)), jnp.float32)
    from mxnet_tpu.ops.nn import dot_product_attention
    for causal in (False, True):
        ref = dot_product_attention(q, k, v, causal=causal)
        ring = parallel.ring_attention_sharded(q, k, v, mesh, axis_name="cp",
                                               causal=causal)
        assert_almost_equal(onp.asarray(ring), onp.asarray(ref), rtol=2e-4,
                            atol=2e-4)


def test_ring_attention_grads():
    mesh = parallel.create_mesh(cp=4)
    B, H, T, D = 1, 2, 32, 8
    onp.random.seed(2)
    q = jnp.asarray(onp.random.normal(0, 1, (B, H, T, D)), jnp.float32)
    k = jnp.asarray(onp.random.normal(0, 1, (B, H, T, D)), jnp.float32)
    v = jnp.asarray(onp.random.normal(0, 1, (B, H, T, D)), jnp.float32)
    from mxnet_tpu.ops.nn import dot_product_attention

    def f_ring(q, k, v):
        return parallel.ring_attention_sharded(q, k, v, mesh, "cp",
                                               causal=True).sum()

    def f_ref(q, k, v):
        return dot_product_attention(q, k, v, causal=True).sum()

    g_ring = jax.grad(f_ring, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for gr, gf in zip(g_ring, g_ref):
        assert_almost_equal(onp.asarray(gr), onp.asarray(gf), rtol=5e-4,
                            atol=5e-4)


def test_ring_double_buffer_matches_single_and_dense():
    """The overlap rewrite is a pure re-schedule: double-buffered ring
    (fused K/V permute + hand-written ring VJP) == the legacy
    single-buffered autodiff ring == dense attention, forward AND
    gradients, causal and non-causal."""
    from mxnet_tpu.ops.nn import dot_product_attention

    mesh = parallel.create_mesh(cp=8)
    B, H, T, D = 2, 2, 64, 16
    rs = onp.random.RandomState(11)
    q = jnp.asarray(rs.normal(0, 1, (B, H, T, D)), jnp.float32)
    k = jnp.asarray(rs.normal(0, 1, (B, H, T, D)), jnp.float32)
    v = jnp.asarray(rs.normal(0, 1, (B, H, T, D)), jnp.float32)
    for causal in (False, True):
        ref = dot_product_attention(q, k, v, causal=causal)

        def loss(qq, kk, vv, db):
            o = parallel.ring_attention_sharded(
                qq, kk, vv, mesh, "cp", causal=causal, double_buffer=db)
            return o.sum(), o

        grads = {}
        for db in (True, False):
            (_, o), g = jax.value_and_grad(
                lambda *a: loss(*a, db), argnums=(0, 1, 2),
                has_aux=True)(q, k, v)
            assert_almost_equal(onp.asarray(o), onp.asarray(ref),
                                rtol=2e-4, atol=2e-4)
            grads[db] = g
        g_ref = jax.grad(lambda *a: dot_product_attention(
            *a, causal=causal).sum(), argnums=(0, 1, 2))(q, k, v)
        for db in (True, False):
            for got, want in zip(grads[db], g_ref):
                assert_almost_equal(onp.asarray(got), onp.asarray(want),
                                    rtol=5e-4, atol=5e-4)


def test_ring_double_buffer_gqa_grads_match_dense():
    """The ring-native VJP handles grouped-query K/V: dk/dv accumulate
    over the query-head groups exactly as the repeated-kv dense
    gradient does."""
    from mxnet_tpu.ops.nn import dot_product_attention

    mesh = parallel.create_mesh(cp=4)
    B, H, Hkv, T, D = 1, 4, 2, 32, 8
    rs = onp.random.RandomState(12)
    q = jnp.asarray(rs.normal(0, 1, (B, H, T, D)), jnp.float32)
    k = jnp.asarray(rs.normal(0, 1, (B, Hkv, T, D)), jnp.float32)
    v = jnp.asarray(rs.normal(0, 1, (B, Hkv, T, D)), jnp.float32)
    rep = H // Hkv

    def f_ring(q, k, v):
        return parallel.ring_attention_sharded(q, k, v, mesh, "cp",
                                               causal=True).sum()

    def f_ref(q, k, v):
        return dot_product_attention(q, jnp.repeat(k, rep, 1),
                                     jnp.repeat(v, rep, 1),
                                     causal=True).sum()

    g_ring = jax.grad(f_ring, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for got, want in zip(g_ring, g_ref):
        assert_almost_equal(onp.asarray(got), onp.asarray(want),
                            rtol=5e-4, atol=5e-4)


def test_pipeline_forward():
    mesh = parallel.create_mesh(pp=4)
    # 4 identical-shape stages: y = relu(x @ w)
    onp.random.seed(3)
    D = 8
    ws = jnp.asarray(onp.random.normal(0, 0.5, (4, D, D)), jnp.float32)

    def stage(w, x):
        return jax.nn.relu(x @ w)

    x = jnp.asarray(onp.random.normal(0, 1, (8, D)), jnp.float32)
    out = parallel.pipeline.pipeline_apply(stage, ws, x, mesh,
                                           num_microbatches=4)
    # reference: sequential application
    ref = x
    for i in range(4):
        ref = jax.nn.relu(ref @ ws[i])
    assert_almost_equal(onp.asarray(out), onp.asarray(ref), rtol=1e-5,
                        atol=1e-5)


def test_pipeline_apply_schedules_match_sequential():
    """Forward-only pipeline under every schedule == sequential stage
    application (interleaved runs 2 virtual stages per device)."""
    mesh = parallel.create_mesh(pp=4)
    D = 8
    rs = onp.random.RandomState(21)
    x = jnp.asarray(rs.normal(0, 1, (16, D)), jnp.float32)

    def stage(w, a):
        return jax.nn.relu(a @ w)

    for sched, v in (("gpipe", 1), ("1f1b", 1), ("interleaved", 2)):
        ws = jnp.asarray(rs.normal(0, 0.5, (4 * v, D, D)), jnp.float32)
        ref = x
        for i in range(4 * v):
            ref = jax.nn.relu(ref @ ws[i])
        out = parallel.pipeline_apply(stage, ws, x, mesh,
                                      num_microbatches=4,
                                      schedule=sched, virtual_stages=v)
        assert_almost_equal(onp.asarray(out), onp.asarray(ref),
                            rtol=1e-5, atol=1e-5)


def test_pipeline_vjp_schedules_match_reference():
    """The training schedules produce identical outputs AND gradients:
    1F1B and interleaved == GPipe == jax.vjp of the sequential stack
    (params, inputs, and the pipelined output all match)."""
    mesh = parallel.create_mesh(pp=4)
    D, M = 8, 8
    rs = onp.random.RandomState(22)
    x = jnp.asarray(rs.normal(0, 1, (16, D)), jnp.float32)
    gy = jnp.asarray(rs.normal(0, 1, (16, D)), jnp.float32)

    def stage(w, a):
        return jax.nn.relu(a @ w)

    for sched, v in (("gpipe", 1), ("1f1b", 1), ("interleaved", 2)):
        ws = jnp.asarray(rs.normal(0, 0.5, (4 * v, D, D)), jnp.float32)

        def seq(ws_, x_):
            h = x_
            for i in range(4 * v):
                h = jax.nn.relu(h @ ws_[i])
            return h

        y_ref, vjp = jax.vjp(seq, ws, x)
        dws_ref, dx_ref = vjp(gy)
        y, dx, dws = parallel.pipeline_vjp(
            stage, ws, x, gy, mesh, num_microbatches=M, schedule=sched,
            virtual_stages=v)
        for got, want in ((y, y_ref), (dx, dx_ref), (dws, dws_ref)):
            assert_almost_equal(onp.asarray(got), onp.asarray(want),
                                rtol=1e-4, atol=1e-5)


def test_pipeline_schedule_info_pins_the_claims():
    """The chip-independent schedule facts the PR stands on: 1F1B keeps
    the SAME bubble as GPipe but drops the activation stash from M to n
    microbatches; interleaving (v=2) cuts the bubble further."""
    from mxnet_tpu.parallel.pipeline import schedule_info

    n, M = 4, 8
    gp = schedule_info("gpipe", n, M)
    fb = schedule_info("1f1b", n, M)
    il = schedule_info("interleaved", n, M, virtual_stages=2)
    assert gp["act_buf"] == M and gp["max_inflight"] == M
    assert fb["act_buf"] == n and fb["max_inflight"] == n
    assert fb["slots"] == gp["slots"] == 2 * (M + n - 1)
    assert abs(fb["bubble_fraction"] - gp["bubble_fraction"]) < 1e-9
    assert il["bubble_fraction"] < fb["bubble_fraction"]


def test_pipeline_vjp_1f1b_stash_is_smaller_in_the_program():
    """The 1F1B memory claim holds in the LOWERED program, not just the
    simulator: the activation stash buffer carried through the loop is
    (v, n, mb...) under 1F1B vs (v, M, mb...) under GPipe."""
    mesh = parallel.create_mesh(pp=4)
    D, M, mbs = 8, 8, 2
    ws = jnp.zeros((4, D, D), jnp.float32)
    x = jnp.zeros((M * mbs, D), jnp.float32)

    def stage(w, a):
        return jax.nn.relu(a @ w)

    def lower(sched):
        def f(w, xx, gg):
            return parallel.pipeline_vjp(stage, w, xx, gg, mesh, M,
                                         schedule=sched)
        return jax.jit(f).lower(ws, x, x).as_text()

    # stash shape appears as tensor<1x{depth}x{mbs}x{D}xf32>
    assert "tensor<1x4x%dx%dxf32>" % (mbs, D) in lower("1f1b")
    assert "tensor<1x8x%dx%dxf32>" % (mbs, D) in lower("gpipe")


def test_kvstore_trainer_on_mesh_batch():
    # classic reference-style DP loop: split_and_load over 'device' list
    ctxs = [mx.cpu(0)]
    net = nn.Dense(2, in_units=4)
    net.initialize(ctx=ctxs[0])
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1}, kvstore="device")
    X = mx.np.ones((8, 4))
    y = mx.np.zeros((8, 2))
    parts = gluon.utils.split_and_load(X, ctxs)
    with mx.autograd.record():
        losses = [gluon.loss.L2Loss()(net(p), y) for p in parts]
    for L in losses:
        L.backward()
    trainer.step(8)


def test_pipeline_output_replicated():
    """gpipe's final collective must be a true broadcast: every device's
    shard of the replicated output equals the last stage's result
    (ADVICE.md r1: ppermute ring-shift only reached device 0)."""
    mesh = parallel.create_mesh(pp=4)
    onp.random.seed(7)
    D = 4
    ws = jnp.asarray(onp.random.normal(0, 0.5, (4, D, D)), jnp.float32)
    x = jnp.asarray(onp.random.normal(0, 1, (8, D)), jnp.float32)

    def stage(w, a):
        return jax.nn.relu(a @ w)

    from mxnet_tpu.parallel.pipeline import gpipe_forward
    from mxnet_tpu.parallel.ring import _shard_map
    xm = x.reshape(4, 2, D)
    # out_specs=P('pp') keeps every device's copy visible instead of
    # collapsing to one shard — all 4 copies must match the reference
    out = _shard_map(
        lambda p, xmb: gpipe_forward(stage, p, xmb)[None],
        mesh, (P("pp"), P()), P("pp"))(ws, xm)
    ref = x
    for i in range(4):
        ref = jax.nn.relu(ref @ ws[i])
    ref = ref.reshape(4, 2, D)
    for dev in range(4):
        assert_almost_equal(onp.asarray(out[dev]).reshape(8 // 4 * 4, D)
                            .reshape(4, 2, D), onp.asarray(ref),
                            rtol=1e-5, atol=1e-5)


def test_train_step_param_rules_applied():
    """TrainStep(param_rules=...) must actually shard matching params
    (ADVICE.md r1: rules were silently dropped)."""
    mesh = parallel.create_mesh(dp=2, tp=4)
    net = nn.Dense(16, in_units=8)
    net.initialize()
    net(mx.np.ones((2, 8)))
    step = parallel.TrainStep(
        net, gluon.loss.L2Loss(), mx.optimizer.SGD(learning_rate=0.1),
        mesh=mesh, param_rules=[("weight", ("tp", None))])
    w = net.weight.data()._data
    assert w.sharding.spec == P("tp", None), w.sharding.spec
    # and the step still runs sharded
    loss = step(mx.np.ones((8, 8)), mx.np.ones((8, 16)))
    assert onp.isfinite(float(loss))


@pytest.mark.parametrize("marked", ["net", "first_child"])
def test_train_step_remat_matches_plain(marked):
    """``Block.recompute()`` runs the marked blocks again in the
    backward; losses must match the plain step over several steps,
    whether the whole net or only its first child is marked."""
    import mxnet_tpu as mx
    from mxnet_tpu import gluon, parallel

    def build():
        mx.np.random.seed(0)
        net = gluon.nn.HybridSequential()
        net.add(gluon.nn.Dense(16, activation="relu"), gluon.nn.Dense(4))
        net.initialize()
        net(mx.np.zeros((4, 8)))
        return net

    x = mx.np.random.uniform(-1, 1, (4, 8))
    y = mx.np.random.randint(0, 4, (4,), dtype="int32")
    loss = gluon.loss.SoftmaxCrossEntropyLoss()
    plain = parallel.TrainStep(build(), loss,
                               mx.optimizer.SGD(learning_rate=0.1),
                               mesh=None)
    net = build()
    (net if marked == "net" else net[0]).recompute()
    ck = parallel.TrainStep(net, loss, mx.optimizer.SGD(learning_rate=0.1),
                            mesh=None)
    assert "rematted_computation" in ck.lower(x, y).as_text(debug_info=True)
    for _ in range(3):
        l1 = float(plain(x, y))
        l2 = float(ck(x, y))
        assert abs(l1 - l2) < 1e-6, (l1, l2)


def test_dp_tp_trajectory_matches_single_device():
    """dp x tp sharded training must reproduce the single-device loss
    TRAJECTORY, not merely run (VERDICT r3 weak #8: the reference's dist
    tests assert exact arithmetic, reference dist_sync_kvstore.py)."""
    from mxnet_tpu.models import TransformerLM, tiny_config

    def build():
        mx.np.random.seed(0)
        cfg = tiny_config(n_heads=4, n_kv_heads=2, dim=64, hidden_dim=128,
                          n_layers=2, vocab_size=64)
        net = TransformerLM(cfg)
        net.initialize()
        return net, cfg

    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()

    def fwd(net, tokens, labels):
        logits = net.forward(tokens)
        return loss_fn(logits.reshape(-1, logits.shape[-1]),
                       labels.reshape(-1)).mean()

    onp.random.seed(3)
    B, T = 4, 16
    # one fixed batch repeated: equality must hold step-by-step AND the
    # memorizing trajectory must descend
    t0 = mx.np.array(onp.random.randint(0, 64, (B, T)).astype("int32"))
    l0 = mx.np.array(onp.random.randint(0, 64, (B, T)).astype("int32"))
    toks = [t0] * 5
    labs = [l0] * 5

    net1, _ = build()
    s_single = parallel.TrainStep(net1, None,
                                  mx.optimizer.AdamW(learning_rate=1e-2),
                                  mesh=None, forward_fn=fwd)
    single = [float(s_single(t, l)) for t, l in zip(toks, labs)]

    net2, _ = build()
    mesh = parallel.create_mesh(dp=2, tp=4)
    with parallel.mesh_scope(mesh):
        s_shard = parallel.TrainStep(net2, None,
                                     mx.optimizer.AdamW(learning_rate=1e-2),
                                     mesh=mesh, forward_fn=fwd)
        sharded = [float(s_shard(t, l)) for t, l in zip(toks, labs)]

    for i, (a, b) in enumerate(zip(single, sharded)):
        assert abs(a - b) < 5e-3 * max(1.0, abs(a)), \
            "step %d: single %.6f vs dp x tp %.6f" % (i, a, b)
    # and the trajectory must actually descend
    assert sharded[-1] < sharded[0]


def test_switch_moe_matches_per_token_reference():
    """Dense einsum dispatch must equal the obvious per-token loop
    (beyond-parity EP capability; SURVEY lists MoE as absent upstream)."""
    rs = onp.random.RandomState(0)
    T, D, H, E = 16, 8, 12, 4
    x = jnp.asarray(rs.normal(0, 1, (T, D)), jnp.float32)
    gate_w = jnp.asarray(rs.normal(0, 0.5, (D, E)), jnp.float32)
    w1 = jnp.asarray(rs.normal(0, 0.5, (E, D, H)), jnp.float32)
    w2 = jnp.asarray(rs.normal(0, 0.5, (E, H, D)), jnp.float32)
    out, aux = parallel.switch_moe(x, gate_w, w1, w2,
                                   capacity_factor=100.0)  # no drops
    probs = onp.asarray(jax.nn.softmax(x @ gate_w, axis=-1))
    want = onp.zeros((T, D), "float32")
    for t in range(T):
        e = int(probs[t].argmax())
        h = onp.maximum(onp.asarray(x)[t] @ onp.asarray(w1)[e], 0)
        want[t] = (h @ onp.asarray(w2)[e]) * probs[t, e]
    onp.testing.assert_allclose(onp.asarray(out), want, rtol=1e-4,
                                atol=1e-5)
    assert float(aux) > 0


def test_switch_moe_capacity_drops_tokens():
    rs = onp.random.RandomState(1)
    T, D, H, E = 16, 8, 12, 2
    x = jnp.asarray(rs.normal(0, 1, (T, D)), jnp.float32)
    # zero gate logits: argmax tie-breaks to expert 0 for EVERY token
    gate_w = jnp.zeros((D, E), jnp.float32)
    w1 = jnp.asarray(rs.normal(0, 0.5, (E, D, H)), jnp.float32)
    w2 = jnp.asarray(rs.normal(0, 0.5, (E, H, D)), jnp.float32)
    out, _ = parallel.switch_moe(x, gate_w, w1, w2,
                                 capacity_factor=0.5)  # C = 4 of 16
    nz = (onp.abs(onp.asarray(out)).sum(axis=1) > 1e-7).sum()
    assert nz == 4  # only capacity-many tokens produce output


def test_switch_moe_ep_sharded_matches_single():
    mesh = parallel.create_mesh(ep=8)
    from jax.sharding import NamedSharding
    rs = onp.random.RandomState(2)
    T, D, H, E = 32, 8, 16, 8
    x = jnp.asarray(rs.normal(0, 1, (T, D)), jnp.float32)
    gate_w = jnp.asarray(rs.normal(0, 0.5, (D, E)), jnp.float32)
    w1 = jnp.asarray(rs.normal(0, 0.5, (E, D, H)), jnp.float32)
    w2 = jnp.asarray(rs.normal(0, 0.5, (E, H, D)), jnp.float32)
    want, aux_w = parallel.switch_moe(x, gate_w, w1, w2)
    spec = parallel.moe_param_specs()
    w1s = jax.device_put(w1, NamedSharding(mesh, spec["w1"]))
    w2s = jax.device_put(w2, NamedSharding(mesh, spec["w2"]))

    @jax.jit
    def step(xx, gw, a, b):
        return parallel.switch_moe(xx, gw, a, b, mesh=mesh)

    got, aux_s = step(x, gate_w, w1s, w2s)
    onp.testing.assert_allclose(onp.asarray(got), onp.asarray(want),
                                rtol=1e-5, atol=1e-6)
    onp.testing.assert_allclose(float(aux_s), float(aux_w), rtol=1e-5)


def test_switch_moe_bf16_no_position_overflow():
    """Routing bookkeeping must be exact beyond 256 tokens per expert even
    with bf16 activations (bf16 cumsum cannot represent ints > 256)."""
    rs = onp.random.RandomState(3)
    T, D, H = 1024, 8, 8
    x = jnp.asarray(rs.normal(0, 1, (T, D)), jnp.bfloat16)
    gate_w = jnp.zeros((D, 1), jnp.bfloat16)  # everything to expert 0
    w1 = jnp.asarray(rs.normal(0, 0.5, (1, D, H)), jnp.bfloat16)
    w2 = jnp.asarray(rs.normal(0, 0.5, (1, H, D)), jnp.bfloat16)
    out, _ = parallel.switch_moe(x, gate_w, w1, w2, capacity_factor=1.0)
    produced = (onp.abs(onp.asarray(out, dtype=onp.float32))
                .sum(axis=1) > 1e-6).sum()
    assert produced == T, "%d/%d tokens produced output" % (produced, T)


def test_param_spec_missing_axis_replicates():
    """A tp-annotated model on a dp-only mesh must replicate the
    tp-sharded params, not crash (specs are declarative; the mesh
    decides what is realized)."""
    mesh = parallel.create_mesh(dp=8)
    net = nn.Dense(16, in_units=8)
    net.initialize()
    net.weight.shard(("tp", None))  # axis not in this mesh
    shardings = parallel.shard_params(net, mesh)
    w = net.weight.data()._data
    assert w.sharding.spec == P(None, None)
    # and a TrainStep over the same mesh runs
    step = parallel.TrainStep(net, gluon.loss.L2Loss(),
                              mx.optimizer.SGD(learning_rate=0.1),
                              mesh=mesh)
    loss = float(step(mx.np.ones((8, 8)), mx.np.zeros((8, 16))))
    assert onp.isfinite(loss)


def test_param_spec_partial_composite_axis():
    """fsdp-style ('dp','tp') composite specs keep the PRESENT sub-axes
    when the mesh lacks one (partial sharding, not full replication)."""
    from mxnet_tpu.parallel.sharding import _valid_spec
    mesh = parallel.create_mesh(dp=8)
    spec = _valid_spec((("dp", "tp"), None), (16, 4), mesh)
    assert spec == P("dp", None)
    mesh2 = parallel.create_mesh(dp=2, tp=4)
    spec2 = _valid_spec((("dp", "tp"), None), (16, 4), mesh2)
    assert spec2 == P(("dp", "tp"), None)


def test_valid_spec_drop_warns_once(caplog):
    """VERDICT r4 weak #4: silently replicating a parameter because its
    spec axis was dropped must be LOUD — once per (param, axis)."""
    import logging

    from mxnet_tpu.parallel.sharding import _valid_spec, _warned_drops

    mesh = parallel.create_mesh(dp=8)
    _warned_drops.clear()
    logger = "mxnet_tpu.parallel.sharding"
    with caplog.at_level(logging.WARNING, logger=logger):
        spec = _valid_spec(P("tp", None), (8, 8), mesh, param_name="w")
    assert spec == P(None, None)
    assert any("no axis 'tp'" in r.message and "w" in r.message
               and "REPLICATED" in r.message for r in caplog.records)

    caplog.clear()
    with caplog.at_level(logging.WARNING, logger=logger):
        spec = _valid_spec(P("dp"), (6,), mesh, param_name="w2")
    assert spec == P(None)
    assert any("not divisible" in r.message for r in caplog.records)

    # once-per-param: the same drop again is silent
    caplog.clear()
    with caplog.at_level(logging.WARNING, logger=logger):
        _valid_spec(P("dp"), (6,), mesh, param_name="w2")
        _valid_spec(P("tp", None), (8, 8), mesh, param_name="w")
    assert not caplog.records


def test_ring_attention_gqa_matches_dense():
    """Context parallelism composes with grouped-query kv: ring over a
    cp mesh with H_kv < H heads == dense attention over repeated kv
    (the ring shards only the sequence axis; the per-chunk kernel maps
    query heads to kv groups natively)."""
    from mxnet_tpu.ops.nn import dot_product_attention
    from mxnet_tpu.parallel.ring import ring_attention_sharded

    B, H, Hkv, T, D = 1, 4, 2, 64, 16
    rs = onp.random.RandomState(0)
    q = jnp.asarray(rs.normal(0, 1, (B, H, T, D)), jnp.float32)
    k = jnp.asarray(rs.normal(0, 1, (B, Hkv, T, D)), jnp.float32)
    v = jnp.asarray(rs.normal(0, 1, (B, Hkv, T, D)), jnp.float32)
    mesh = parallel.create_mesh(cp=4)
    o = ring_attention_sharded(q, k, v, mesh, axis_name="cp", causal=True)
    rep = H // Hkv
    ref = dot_product_attention(q, jnp.repeat(k, rep, 1),
                                jnp.repeat(v, rep, 1), causal=True)
    assert float(jnp.abs(o - ref).max()) < 1e-5


def test_sharded_checkpoint_reshard_roundtrip(tmp_path):
    """save_checkpoint on a dp x tp mesh, load_checkpoint onto a
    DIFFERENT topology (dp-only), continue training: the trajectory
    matches the uninterrupted run exactly.  The orbax-style sharded
    checkpoint/resume of SURVEY §5 (reference analog:
    Trainer.save_states + save_parameters, which cannot reshard)."""
    def make_step(mesh, rules):
        mx.np.random.seed(123)
        net = nn.HybridSequential()
        net.add(nn.Dense(16, in_units=8, activation="relu"),
                nn.Dense(4, in_units=16))
        net.initialize()
        opt = mx.optimizer.SGD(learning_rate=0.1, momentum=0.9)
        return net, parallel.TrainStep(
            net, gluon.loss.SoftmaxCrossEntropyLoss(), opt,
            mesh=mesh, param_rules=rules)

    def batch(seed):
        rs = onp.random.RandomState(seed)
        return (mx.np.array(rs.normal(0, 1, (8, 8)).astype("float32")),
                mx.np.array(rs.randint(0, 4, (8,)).astype("int32")))

    rules_tp = [("weight", ("tp", None))]
    mesh_a = parallel.create_mesh(dp=2, tp=4)
    net_a, step_a = make_step(mesh_a, rules_tp)
    for s in range(3):
        step_a(*batch(s))
    ck = str(tmp_path / "ckpt")
    step_a.save_checkpoint(ck)

    # uninterrupted reference: two more steps on the same step object
    ref_losses = [float(step_a(*batch(10 + s))) for s in range(2)]

    # restore onto a different topology: dp-only mesh, no tp sharding
    mesh_b = parallel.create_mesh(dp=8)
    net_b, step_b = make_step(mesh_b, None)
    step_b.load_checkpoint(ck)
    assert step_b._t == 3
    got_losses = [float(step_b(*batch(10 + s))) for s in range(2)]
    onp.testing.assert_allclose(got_losses, ref_losses, rtol=1e-5)
    # and the restored weights landed in mesh_b shardings
    w = net_b[0].weight.data()._data
    assert w.sharding.mesh.shape == {"dp": 8}


def test_sharded_checkpoint_to_single_device(tmp_path):
    """Mesh-saved checkpoint restores onto a single-device step."""
    mesh = parallel.create_mesh(dp=2, tp=4)
    mx.np.random.seed(7)
    net = nn.Dense(4, in_units=8)
    net.initialize()
    opt = mx.optimizer.SGD(learning_rate=0.05)
    step = parallel.TrainStep(net, gluon.loss.L2Loss(), opt, mesh=mesh,
                              param_rules=[("weight", ("tp", None))])
    x = mx.np.random.uniform(-1, 1, (8, 8))
    y = mx.np.random.uniform(-1, 1, (8, 4))
    step(x, y)
    ck = str(tmp_path / "ck1")
    step.save_checkpoint(ck)
    w_saved = net.weight.data().asnumpy()

    mx.np.random.seed(7)
    net2 = nn.Dense(4, in_units=8)
    net2.initialize()
    step2 = parallel.TrainStep(net2, gluon.loss.L2Loss(),
                               mx.optimizer.SGD(learning_rate=0.05),
                               mesh=None)
    step2.load_checkpoint(ck)
    onp.testing.assert_allclose(net2.weight.data().asnumpy(), w_saved,
                                rtol=1e-6)
    assert step2._t == 1


def test_compiled_step_carries_expected_collectives():
    """Compiled-artifact evidence for the comm design (SURVEY §2.3: one
    mechanism, XLA collectives): the dp-sharded step's gradient sync is
    an all-reduce inserted by GSPMD; with zero1 the optimizer-state
    sharding additionally introduces reduce-scatter/all-gather traffic.
    On real chips the same program rides ICI."""
    def build(zero1):
        mx.np.random.seed(0)
        net = nn.Dense(16, in_units=32)
        net.initialize()
        mesh = parallel.create_mesh(dp=8)
        step = parallel.TrainStep(net, gluon.loss.L2Loss(),
                                  mx.optimizer.SGD(learning_rate=0.1,
                                                   momentum=0.9),
                                  mesh=mesh, zero1=zero1)
        x = mx.np.random.uniform(-1, 1, (16, 32))
        y = mx.np.random.uniform(-1, 1, (16, 16))
        return step.lower(x, y).compile().as_text()

    plain = build(zero1=False)
    assert "all-reduce" in plain, "dp grad sync must be an all-reduce"
    z1 = build(zero1=True)
    assert ("reduce-scatter" in z1) or ("all-gather" in z1), \
        "zero1 sharded states must introduce reduce-scatter/all-gather"


def test_sharded_checkpoint_bf16_params(tmp_path):
    """bf16 params + fp32 optimizer moments round-trip through the
    orbax sharded checkpoint (mixed-precision training state)."""
    mx.np.random.seed(31)
    net = nn.Dense(8, in_units=16)
    net.cast("bfloat16")
    net.initialize()
    mesh = parallel.create_mesh(dp=8)
    step = parallel.TrainStep(net, gluon.loss.L2Loss(),
                              mx.optimizer.Adam(learning_rate=1e-3),
                              mesh=mesh)
    x = mx.np.random.uniform(-1, 1, (8, 16)).astype("bfloat16")
    y = mx.np.random.uniform(-1, 1, (8, 8)).astype("bfloat16")
    step(x, y)
    ck = str(tmp_path / "bf16ck")
    step.save_checkpoint(ck)
    w_ref = net.weight.data().asnumpy().astype("float32")

    mx.np.random.seed(31)
    net2 = nn.Dense(8, in_units=16)
    net2.cast("bfloat16")
    net2.initialize()
    step2 = parallel.TrainStep(net2, gluon.loss.L2Loss(),
                               mx.optimizer.Adam(learning_rate=1e-3),
                               mesh=None)
    step2.load_checkpoint(ck)
    assert str(net2.weight.data().dtype) == "bfloat16"
    onp.testing.assert_array_equal(
        net2.weight.data().asnumpy().astype("float32"), w_ref)
    # moments restored in fp32
    m = step2._states["weight"][0]
    assert str(m.dtype) == "float32"
    float(step2(x, y))  # and the step continues


# ----------------------------------------------------------------------
# striped causal layout + hierarchical (DCN x ICI) ring + seq_data
# ----------------------------------------------------------------------
@pytest.mark.parametrize("causal", [False, True])
def test_ring_striped_matches_roundrobin_and_dense(causal):
    """The striped layout is a pure re-balancing: striped == roundrobin
    == dense attention, forward AND gradients, with and without the
    causal mask (non-causal the layouts are mathematically identical;
    causal is where the stripe changes which (rank, block) pairs are
    masked and must still sum to the same attention)."""
    from mxnet_tpu.ops.nn import dot_product_attention

    mesh = parallel.create_mesh(cp=8)
    B, H, T, D = 1, 2, 64, 8
    rs = onp.random.RandomState(41)
    q = jnp.asarray(rs.normal(0, 1, (B, H, T, D)), jnp.float32)
    k = jnp.asarray(rs.normal(0, 1, (B, H, T, D)), jnp.float32)
    v = jnp.asarray(rs.normal(0, 1, (B, H, T, D)), jnp.float32)
    o_ref = dot_product_attention(q, k, v, causal=causal)
    g_ref = jax.grad(lambda *a: dot_product_attention(
        *a, causal=causal).sum(), argnums=(0, 1, 2))(q, k, v)

    for layout in ("striped", "roundrobin"):
        def loss(qq, kk, vv):
            o = parallel.ring_attention_sharded(
                qq, kk, vv, mesh, "cp", causal=causal, layout=layout)
            return o.sum(), o

        (_, o), g = jax.value_and_grad(loss, argnums=(0, 1, 2),
                                       has_aux=True)(q, k, v)
        assert_almost_equal(onp.asarray(o), onp.asarray(o_ref),
                            rtol=2e-5, atol=2e-5)
        for got, want in zip(g, g_ref):
            assert_almost_equal(onp.asarray(got), onp.asarray(want),
                                rtol=5e-5, atol=5e-5)


def test_ring_striped_gqa_grads_match_dense():
    """Striped layout composes with grouped-query K/V: the ring VJP's
    group-summed dk/dv still match the repeated-kv dense gradient when
    the mask offsets come from the stripe."""
    from mxnet_tpu.ops.nn import dot_product_attention

    mesh = parallel.create_mesh(cp=4)
    B, H, Hkv, T, D = 1, 4, 2, 32, 8
    rs = onp.random.RandomState(42)
    q = jnp.asarray(rs.normal(0, 1, (B, H, T, D)), jnp.float32)
    k = jnp.asarray(rs.normal(0, 1, (B, Hkv, T, D)), jnp.float32)
    v = jnp.asarray(rs.normal(0, 1, (B, Hkv, T, D)), jnp.float32)
    rep = H // Hkv

    def f_ring(q, k, v):
        return parallel.ring_attention_sharded(
            q, k, v, mesh, "cp", causal=True, layout="striped").sum()

    def f_ref(q, k, v):
        return dot_product_attention(q, jnp.repeat(k, rep, 1),
                                     jnp.repeat(v, rep, 1),
                                     causal=True).sum()

    g_ring = jax.grad(f_ring, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for got, want in zip(g_ring, g_ref):
        assert_almost_equal(onp.asarray(got), onp.asarray(want),
                            rtol=5e-5, atol=5e-5)


@pytest.mark.parametrize("causal,layout", [(True, "striped"),
                                           (True, "roundrobin"),
                                           (False, "roundrobin")])
def test_ring2_hierarchical_matches_flat_and_dense(causal, layout):
    """The 2-level (2 slices x 4) DCN x ICI ring == the flat 8-ring ==
    dense attention, forward and gradients: the outer-superblock /
    inner-sweep decomposition visits every block exactly once, so only
    the logsumexp merge ORDER differs from the flat ring."""
    from mxnet_tpu.ops.nn import dot_product_attention

    mesh_flat = parallel.create_mesh(cp=8)
    mesh2 = parallel.create_mesh(dcn=2, cp=4)
    B, H, T, D = 1, 2, 64, 8
    rs = onp.random.RandomState(43)
    q = jnp.asarray(rs.normal(0, 1, (B, H, T, D)), jnp.float32)
    k = jnp.asarray(rs.normal(0, 1, (B, H, T, D)), jnp.float32)
    v = jnp.asarray(rs.normal(0, 1, (B, H, T, D)), jnp.float32)

    def run(mesh, axis):
        def loss(qq, kk, vv):
            o = parallel.ring_attention_sharded(
                qq, kk, vv, mesh, axis_name=axis, causal=causal,
                layout=layout)
            return o.sum(), o

        (_, o), g = jax.value_and_grad(loss, argnums=(0, 1, 2),
                                       has_aux=True)(q, k, v)
        return o, g

    o2, g2 = run(mesh2, ("dcn", "cp"))
    of, gf = run(mesh_flat, "cp")
    o_ref = dot_product_attention(q, k, v, causal=causal)
    g_ref = jax.grad(lambda *a: dot_product_attention(
        *a, causal=causal).sum(), argnums=(0, 1, 2))(q, k, v)
    assert_almost_equal(onp.asarray(o2), onp.asarray(of), rtol=2e-5,
                        atol=2e-5)
    assert_almost_equal(onp.asarray(o2), onp.asarray(o_ref), rtol=2e-5,
                        atol=2e-5)
    for got, flat, want in zip(g2, gf, g_ref):
        assert_almost_equal(onp.asarray(got), onp.asarray(flat),
                            rtol=5e-5, atol=5e-5)
        assert_almost_equal(onp.asarray(got), onp.asarray(want),
                            rtol=5e-5, atol=5e-5)


def test_ring_prestriped_inputs_skip_the_permutation():
    """``permute_inputs=False`` is the production million-token
    contract: data arrives already striped (the seq_data layout), the
    output STAYS striped (position-aligned with q), and un-striping it
    recovers the dense result exactly as the permuting entry does."""
    from mxnet_tpu.ops.nn import dot_product_attention
    from mxnet_tpu.parallel import ring

    mesh = parallel.create_mesh(dcn=2, cp=4)
    B, H, T, D = 1, 2, 64, 8
    rs = onp.random.RandomState(44)
    q = jnp.asarray(rs.normal(0, 1, (B, H, T, D)), jnp.float32)
    k = jnp.asarray(rs.normal(0, 1, (B, H, T, D)), jnp.float32)
    v = jnp.asarray(rs.normal(0, 1, (B, H, T, D)), jnp.float32)
    qs, ks, vs = (ring.stripe_sequence(a, 8) for a in (q, k, v))
    # roundtrip sanity of the permutation pair itself
    onp.testing.assert_array_equal(
        onp.asarray(ring.unstripe_sequence(qs, 8)), onp.asarray(q))

    out_s = parallel.ring_attention_sharded(
        qs, ks, vs, mesh, axis_name=("dcn", "cp"), causal=True,
        layout="striped", permute_inputs=False)
    out_nat = parallel.ring_attention_sharded(
        q, k, v, mesh, axis_name=("dcn", "cp"), causal=True,
        layout="striped")
    ref = dot_product_attention(q, k, v, causal=True)
    got = ring.unstripe_sequence(out_s, 8)
    assert_almost_equal(onp.asarray(got), onp.asarray(out_nat),
                        rtol=1e-6, atol=1e-6)
    assert_almost_equal(onp.asarray(got), onp.asarray(ref), rtol=2e-5,
                        atol=2e-5)


def test_causal_balance_striped_near_one_roundrobin_skewed():
    """The chip-independent balance claim the striped layout stands on:
    striped keeps every ring step's max/mean block work ~1.0 (flat AND
    2-level), while the contiguous roundrobin layout's critical path
    grows toward ~2x as rank 0 idles."""
    from mxnet_tpu.parallel import ring

    for inner, outer in ((8, 1), (4, 2)):
        st = ring.causal_balance("striped", inner, outer)
        rr = ring.causal_balance("roundrobin", inner, outer)
        assert st["critical_path_x"] <= 1.05, st
        assert max(st["per_step_max_over_mean"]) <= 1.05, st
        assert rr["critical_path_x"] >= 1.5, rr
        assert rr["critical_path_x"] > st["critical_path_x"] * 1.4
        # zigzag is scored only: flat, no better than striped by 1%,
        # which is why it never grew an execution path
        zz = ring.causal_balance("zigzag", inner, outer)
        assert zz["critical_path_x"] <= st["critical_path_x"], zz
        assert st["critical_path_x"] - zz["critical_path_x"] < 0.01
    with pytest.raises(ValueError):
        ring.causal_balance("diagonal", 8)


def test_seq_data_shard_indices_are_the_stripe_contract():
    """``shard_token_indices`` IS the layout contract: striped shard r
    of n holds tokens r, r+n, r+2n, ... (exactly ring.stripe_permutation
    order), roundrobin the contiguous slab — and the full plan covers
    every token exactly once."""
    from mxnet_tpu.parallel import ring, seq_data

    T, n = 64, 8
    perm = onp.asarray(ring.stripe_permutation(T, n))
    for s in range(n):
        off, stride, count = seq_data.shard_token_indices(s, n, T,
                                                          "striped")
        onp.testing.assert_array_equal(
            off + stride * onp.arange(count),
            perm[s * (T // n):(s + 1) * (T // n)])
        off, stride, count = seq_data.shard_token_indices(s, n, T,
                                                          "roundrobin")
        assert (off, stride, count) == (s * 8, 1, 8)
    plan = seq_data.token_shards(n, T, "striped")
    seen = sorted(p for (_, off, stride, count) in plan
                  for p in range(off, off + stride * count, stride))
    assert seen == list(range(T))
    with pytest.raises(ValueError):
        seq_data.shard_token_indices(0, 8, 60, "striped")
    with pytest.raises(ValueError):
        seq_data.shard_token_indices(0, 8, 64, "zigzag")


@pytest.mark.parametrize("axis", ["cp", ("dcn", "cp")])
def test_seq_data_assembles_shards_no_full_sequence_read(axis):
    """``make_sequence_array`` builds the striped global array from
    per-shard reads alone: no single read ever covers more than one
    shard's tokens, the assembled array is the striped permutation of
    the underlying sequence, and feeding it straight to the ring with
    ``permute_inputs=False`` matches dense attention on the natural
    order."""
    from mxnet_tpu.ops.nn import dot_product_attention
    from mxnet_tpu.parallel import ring, seq_data

    mesh = parallel.create_mesh(cp=8) if axis == "cp" \
        else parallel.create_mesh(dcn=2, cp=4)
    B, H, T, D = 1, 2, 64, 8
    rs = onp.random.RandomState(45)
    full = {w: rs.normal(0, 1, (B, H, T, D)).astype("float32")
            for w in "qkv"}
    max_read = [0]

    def reader(w):
        def f(idx):
            max_read[0] = max(max_read[0], len(idx))
            return full[w][:, :, idx, :]
        return f

    q, k, v = (seq_data.make_sequence_array(
        reader(w), (B, H, T, D), mesh, axis_name=axis, layout="striped")
        for w in "qkv")
    assert max_read[0] == T // 8          # never a full-sequence read
    onp.testing.assert_array_equal(
        onp.asarray(q), onp.asarray(ring.stripe_sequence(
            jnp.asarray(full["q"]), 8)))

    out = parallel.ring_attention_sharded(
        q, k, v, mesh, axis_name=axis, causal=True, layout="striped",
        permute_inputs=False)
    ref = dot_product_attention(*(jnp.asarray(full[w]) for w in "qkv"),
                                causal=True)
    assert_almost_equal(onp.asarray(ring.unstripe_sequence(out, 8)),
                        onp.asarray(ref), rtol=2e-5, atol=2e-5)


def test_seq_shard_loader_iterates_per_step_reads():
    """SeqShardLoader yields one sharded array per step, each assembled
    from (step, indices) reads only; bad layouts fail at construction."""
    from mxnet_tpu.parallel import seq_data

    mesh = parallel.create_mesh(dcn=2, cp=4)
    B, H, T, D = 1, 1, 32, 4
    calls = []

    def read(step, idx):
        calls.append((step, len(idx)))
        rs = onp.random.RandomState((step, int(idx[0])))
        return rs.normal(0, 1, (B, H, len(idx), D)).astype("float32")

    loader = seq_data.SeqShardLoader(read, (B, H, T, D), mesh,
                                     axis_name=("dcn", "cp"), steps=3)
    arrs = list(loader)
    assert len(arrs) == 3
    assert all(a.shape == (B, H, T, D) for a in arrs)
    assert {c[0] for c in calls} == {0, 1, 2}
    assert all(c[1] == T // 8 for c in calls)
    # determinism: reloading a step reproduces the same global array
    onp.testing.assert_array_equal(onp.asarray(loader.load(1)),
                                   onp.asarray(arrs[1]))
    with pytest.raises(ValueError):
        seq_data.SeqShardLoader(read, (B, H, 30, D), mesh,
                                axis_name=("dcn", "cp"))


# ----------------------------------------------------------------------
# EpochPlan: resize-aware, exactly-once epoch reads
# ----------------------------------------------------------------------
def test_epoch_plan_exactly_once_under_random_resizes():
    """The elastic-data contract, as a property: for random (total,
    world, batch, layout) with world changes of random +/-k injected at
    random step boundaries, every global index is visited EXACTLY once
    — no sample dropped, none double-read."""
    rng = onp.random.RandomState(7)
    for trial in range(40):
        total = int(rng.randint(1, 200))
        world = int(rng.randint(1, 6))
        per = int(rng.randint(1, 5))
        layout = ("striped", "roundrobin")[trial % 2]
        plan = parallel.EpochPlan(total, world, per, layout=layout)
        seen = []
        while not plan.done():
            if rng.rand() < 0.3:
                k = int(rng.randint(-2, 3))
                plan.resize(max(1, plan.world + k))
            shards = plan.step_indices()
            assert len(shards) == plan.world
            seen.extend(onp.concatenate(shards).tolist())
        assert sorted(seen) == list(range(total)), \
            "trial %d (%s): dropped/doubled samples" % (trial, layout)


def test_epoch_plan_layouts_window_contracts():
    # striped: rank r reads cursor + r + world*k; roundrobin: slabs
    s = parallel.EpochPlan(100, 3, 2, layout="striped").step_indices()
    assert [x.tolist() for x in s] == [[0, 3], [1, 4], [2, 5]]
    r = parallel.EpochPlan(100, 3, 2, layout="roundrobin").step_indices()
    assert [x.tolist() for x in r] == [[0, 1], [2, 3], [4, 5]]
    # ragged tail: the first window%world ranks read one extra
    t = parallel.EpochPlan(4, 3, 2).step_indices()
    assert [len(x) for x in t] == [2, 1, 1]


def test_epoch_plan_3_2_3_trajectory_and_joiner_reconstruction():
    """The chaos-grow data story: 3 ranks -> a preemption shrinks to 2
    mid-epoch -> a replacement joins back to 3.  The joiner rebuilds
    the fleet's plan from the committed consumed-prefix and must then
    produce IDENTICAL per-rank reads; the epoch stays exactly-once
    end to end."""
    total, per = 60, 2
    plan = parallel.EpochPlan(total, 3, per)
    seen = []
    for _ in range(3):                      # world 3
        seen.extend(onp.concatenate(plan.step_indices()).tolist())
    plan.resize(2)                          # rank lost mid-epoch
    for _ in range(4):                      # world 2
        seen.extend(onp.concatenate(plan.step_indices()).tolist())
    committed = plan.cursor                 # the grow commit's boundary
    plan.resize(3)                          # replacement folded
    joiner = parallel.EpochPlan(total, 3, per, start=committed)
    while not plan.done():
        mine, theirs = plan.step_indices(), joiner.step_indices()
        for r in range(3):
            onp.testing.assert_array_equal(mine[r], theirs[r])
        seen.extend(onp.concatenate(mine).tolist())
    assert joiner.done()
    assert sorted(seen) == list(range(total))


def test_epoch_plan_validates():
    with pytest.raises(ValueError):
        parallel.EpochPlan(10, 2, 2, layout="zigzag")
    with pytest.raises(ValueError):
        parallel.EpochPlan(10, 0, 2)
    with pytest.raises(ValueError):
        parallel.EpochPlan(10, 2, 2, start=11)
    plan = parallel.EpochPlan(10, 2, 2)
    with pytest.raises(ValueError):
        plan.next_for(2)
