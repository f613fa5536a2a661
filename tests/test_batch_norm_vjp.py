"""Batch norm's training path is one op with its own backward
(``ops/nn.py`` ``batch_norm_train``: both moments in one pass, the
closed-form gradient).  Held here to the formulation it replaced, which
lives on in this file only as the reference: ``jnp.mean`` then
``jnp.var`` in float32, differentiated by ``jax.vjp``.
"""
import jax
import jax.numpy as jnp
import numpy as onp
import pytest
from jax import lax

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, parallel
from mxnet_tpu.gluon import nn
from mxnet_tpu.ops import nn as ops_nn


def two_pass(x, gamma, beta, eps=1e-5, axis=1):
    """The formulation ``batch_norm_train`` had up to PR 26."""
    axis = axis % x.ndim
    axes = tuple(i for i in range(x.ndim) if i != axis)
    shape = [1] * x.ndim
    shape[axis] = -1
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=axes)
    var = jnp.var(xf, axis=axes)
    inv = lax.rsqrt(var + eps).reshape(shape)
    out = (xf - mean.reshape(shape)) * inv \
        * gamma.astype(jnp.float32).reshape(shape) \
        + beta.astype(jnp.float32).reshape(shape)
    return out.astype(x.dtype), mean.astype(gamma.dtype), \
        var.astype(gamma.dtype)


SHAPES = {2: (48, 6), 4: (6, 5, 4, 3), 5: (4, 3, 2, 5, 4)}
# a dtype's own rounding: what one rounding of an O(1) value to it costs,
# with room for the few that an output or a gradient passes through
TOL = {"float32": 1e-5, "bfloat16": 2 ** -6, "float16": 2 ** -9}


def _case(rank, axis, dtype, seed=0, shift=0.0):
    shape = SHAPES[rank]
    c = shape[axis]
    rs = onp.random.RandomState(seed)
    x = jnp.asarray(shift + rs.normal(0, 1, shape), dtype)
    gamma = jnp.asarray(rs.uniform(0.5, 1.5, c), dtype)
    beta = jnp.asarray(rs.normal(0, 0.5, c), dtype)
    cts = (jnp.asarray(rs.normal(0, 1, shape), dtype),
           jnp.asarray(rs.normal(0, 1, c), dtype),
           jnp.asarray(rs.normal(0, 1, c), dtype))
    return (x, gamma, beta), cts


def _close(got, want, tol, what):
    got = onp.asarray(got, onp.float32)
    want = onp.asarray(want, onp.float32)
    scale = max(1.0, float(onp.abs(want).max()))
    assert onp.abs(got - want).max() <= tol * scale, \
        "%s: off by %g of %g" % (what, onp.abs(got - want).max(), scale)


@pytest.mark.parametrize("cotangents", ["out", "out+mean+var", "mean+var"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16", "float16"])
@pytest.mark.parametrize("axis", [1, -1])
@pytest.mark.parametrize("rank", [2, 4, 5])
def test_outputs_and_gradients_match_the_two_pass_formulation(
        rank, axis, dtype, cotangents):
    args, cts = _case(rank, axis, dtype)
    zero = [jnp.zeros_like(c) for c in cts]
    cts = tuple(c if name in cotangents else z
                for name, c, z in zip(("out", "mean", "var"), cts, zero))
    got, got_vjp = jax.vjp(
        lambda *a: ops_nn.batch_norm_train(*a, axis=axis), *args)
    want, want_vjp = jax.vjp(lambda *a: two_pass(*a, axis=axis), *args)
    tol = TOL[dtype]
    for name, g, w in zip(("out", "mean", "var"), got, want):
        assert g.dtype == w.dtype and g.shape == w.shape, name
        _close(g, w, tol, name)
    for name, g, w in zip(("dx", "dgamma", "dbeta"), got_vjp(cts),
                          want_vjp(cts)):
        assert g.dtype == w.dtype and g.shape == w.shape, name
        _close(g, w, tol, name)


@pytest.mark.parametrize("axis", [1, -1])
def test_training_step_gradient_traces_no_term_for_mean_and_var(axis):
    """Where ``mean`` and ``var`` only feed the running statistics their
    cotangents are symbolic zeros: the backward holds the closed form's
    two sums and nothing for the other two outputs."""
    args, _ = _case(4, axis, "float32")

    def loss(x, gamma, beta):
        out, mean, var = ops_nn.batch_norm_train(x, gamma, beta, axis=axis)
        return jnp.sum(out * out), (mean, var)

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2),
                                    has_aux=True))(*args)
    sums = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "reduce_sum"
            and e.invars[0].aval.ndim == 4 and e.outvars[0].aval.ndim == 1]
    assert len(sums) == 4, jaxpr      # two forward, two backward
    got = jax.grad(loss, argnums=(0, 1, 2), has_aux=True)(*args)[0]
    want = jax.grad(lambda *a: jnp.sum(two_pass(*a, axis=axis)[0] ** 2),
                    argnums=(0, 1, 2))(*args)
    for name, g, w in zip(("dx", "dgamma", "dbeta"), got, want):
        _close(g, w, 1e-5, name)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_fix_gamma_drops_gammas_gradient_in_the_caller(dtype):
    """``fix_gamma``: the caller hands the op a constant gamma of ones
    and drops its cotangent; x and beta get the gradient of a batch
    norm whose gamma is one."""
    (xa, gamma_a, beta_a), (dout, _, _) = _case(4, 1, dtype)
    x, gamma, beta = (mx.nd.NDArray(a) for a in (xa, gamma_a, beta_a))
    rm, rv = (mx.nd.NDArray(jnp.zeros_like(gamma_a)) for _ in range(2))
    for a in (x, gamma, beta):
        a.attach_grad()
    with autograd.record():
        out = mx.npx.batch_norm(x, gamma, beta, rm, rv, fix_gamma=True)
    out.backward(mx.nd.NDArray(dout))
    ones = jnp.ones_like(gamma_a)
    want_out, vjp = jax.vjp(two_pass, xa, ones, beta_a)
    dx, _, dbeta = vjp((dout, jnp.zeros_like(ones), jnp.zeros_like(ones)))
    _close(out._data, want_out[0], TOL[dtype], "out")
    _close(x.grad._data, dx, TOL[dtype], "dx")
    _close(beta.grad._data, dbeta, TOL[dtype], "dbeta")
    assert float(jnp.abs(gamma.grad._data.astype(jnp.float32)).max()) == 0


@pytest.mark.parametrize("axis", [1, -1])
def test_one_pass_variance_holds_under_a_shifted_mean(axis):
    """``x = 10 + N(0, 1)`` in float32: ``s2/N - mean**2`` loses
    ``mean**2 / var`` = 100 of float32's 1.2e-7, far inside 1e-3; the
    clamp keeps a constant channel, whose difference may round below
    zero, at exactly zero."""
    (x, gamma, beta), _ = _case(4, axis, "float32", seed=3, shift=10.0)
    _, mean, var = ops_nn.batch_norm_train(x, gamma, beta, axis=axis)
    _, want_mean, want_var = two_pass(x, gamma, beta, axis=axis)
    onp.testing.assert_allclose(onp.asarray(var), onp.asarray(want_var),
                                rtol=1e-3)
    onp.testing.assert_allclose(onp.asarray(mean), onp.asarray(want_mean),
                                rtol=1e-6)
    flat = jnp.full(SHAPES[4], 1000.1, jnp.float32)
    out, _, var = ops_nn.batch_norm_train(flat, gamma, beta, axis=axis)
    assert float(jnp.min(var)) >= 0.0
    assert bool(jnp.all(jnp.isfinite(out)))


def test_reverse_over_reverse():
    """The backward is plain jax, so a second reverse pass goes through
    it (``autograd.grad(..., create_graph=True)``)."""
    (x, gamma, beta), _ = _case(4, 1, "float32", seed=5)

    def second(fn):
        first = jax.grad(lambda x, g: jnp.sum(fn(x, g, beta)[0] ** 3),
                         argnums=(0, 1))
        return jax.grad(lambda x, g: sum(jnp.sum(d * d)
                                         for d in first(x, g)),
                        argnums=(0, 1))(x, gamma)

    for name, g, w in zip(("d2x", "d2gamma"),
                          second(ops_nn.batch_norm_train), second(two_pass)):
        _close(g, w, 1e-4, name)

    xn = mx.nd.NDArray(x)
    xn.attach_grad()
    layer = nn.BatchNorm(in_channels=SHAPES[4][1])
    layer.initialize()
    with autograd.record():
        y = (layer(xn) ** 3).sum()
        dx = autograd.grad(y, [xn], create_graph=True)[0]
        z = (dx * dx).sum()
    z.backward()
    ones, zeros = jnp.ones(SHAPES[4][1]), jnp.zeros(SHAPES[4][1])
    want = jax.grad(lambda x: jnp.sum(jax.grad(
        lambda x: jnp.sum(two_pass(x, ones, zeros)[0] ** 3))(x) ** 2))(x)
    _close(xn.grad._data, want, 1e-4, "tape d2x")


def _bn_net(layout):
    net = nn.HybridSequential()
    axis = -1 if layout == "NHWC" else 1
    net.add(nn.Conv2D(8, 3, padding=1, in_channels=3, layout=layout),
            nn.BatchNorm(axis=axis, in_channels=8),
            nn.Activation("relu"),
            nn.Conv2D(8, 3, padding=1, in_channels=8, layout=layout),
            nn.BatchNorm(axis=axis, in_channels=8),
            nn.Activation("relu"),
            nn.GlobalAvgPool2D(layout=layout), nn.Flatten(),
            nn.Dense(4, in_units=8))
    return net


def _sgd_gradients(net, step, x, y, lr):
    """The gradient one plain SGD step applied: ``(before - after)/lr``,
    and the running statistics it left."""
    params = net.collect_params()
    before = {k: onp.array(p.data().asnumpy(), onp.float64)
              for k, p in params.items()}
    loss = float(step(x, y))
    grads, stats = {}, {}
    for k, p in params.items():
        after = onp.array(p.data().asnumpy(), onp.float64)
        if p.grad_req == "null":
            stats[k] = after
        else:
            grads[k] = (before[k] - after) / lr
    return loss, grads, stats


@pytest.mark.parametrize("layout", ["NCHW", "NHWC"])
def test_imperative_tape_equals_train_step(layout):
    """``autograd.record`` → ``_tape.record_op`` → ``jax.vjp`` of the op,
    against ``TrainStep``'s fused gradient of the same network."""
    mx.np.random.seed(11)
    net = _bn_net(layout)
    net.initialize()
    rs = onp.random.RandomState(11)
    shape = (8, 6, 6, 3) if layout == "NHWC" else (8, 3, 6, 6)
    x = mx.np.array(rs.normal(0, 1, shape).astype("float32"))
    y = mx.np.array(rs.randint(0, 4, (8,)).astype("int32"))
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    net(x)
    params = net.collect_params()
    start = {k: p.data().asnumpy().copy() for k, p in params.items()}

    with autograd.record():
        loss = loss_fn(net(x), y).mean()
    loss.backward()
    tape = {k: p.grad().asnumpy() for k, p in params.items()
            if p.grad_req != "null"}
    tape_stats = {k: p.data().asnumpy() for k, p in params.items()
                  if p.grad_req == "null"}

    for k, p in params.items():
        p.set_data(mx.np.array(start[k]))
    lr = 0.5
    step = parallel.TrainStep(net, loss_fn,
                              mx.optimizer.SGD(learning_rate=lr), mesh=None)
    step_loss, grads, stats = _sgd_gradients(net, step, x, y, lr)
    assert abs(step_loss - float(loss)) < 1e-5
    assert set(grads) == set(tape) and any("gamma" in k for k in grads)
    for k in grads:
        onp.testing.assert_allclose(grads[k], tape[k], rtol=2e-4, atol=2e-6,
                                    err_msg=k)
    for k in stats:
        onp.testing.assert_allclose(stats[k], tape_stats[k], rtol=1e-5,
                                    atol=1e-6, err_msg=k)


class _ToyResNet(gluon.HybridBlock):
    """Stem, one residual block, head: three batch norms, one of them
    closing a residual branch."""

    def __init__(self):
        super().__init__()
        self.stem = nn.HybridSequential()
        self.stem.add(nn.Conv2D(8, 3, padding=1, in_channels=3),
                      nn.BatchNorm(in_channels=8), nn.Activation("relu"))
        self.body = nn.HybridSequential()
        self.body.add(nn.Conv2D(8, 3, padding=1, in_channels=8),
                      nn.BatchNorm(in_channels=8), nn.Activation("relu"),
                      nn.Conv2D(8, 3, padding=1, in_channels=8),
                      nn.BatchNorm(in_channels=8))
        self.head = nn.HybridSequential()
        self.head.add(nn.GlobalAvgPool2D(), nn.Flatten(),
                      nn.Dense(4, in_units=8))

    def forward(self, x):
        x = self.stem(x)
        return self.head(mx.npx.relu(x + self.body(x)))


def test_dp2_mesh_step_equals_the_single_device_step():
    """Under a ``dp`` mesh the batch is sharded and the op's sums become
    all-reduces: the statistics are the whole batch's, and the step is
    the single-device step."""
    rs = onp.random.RandomState(7)
    x = mx.np.array(rs.normal(0, 1, (8, 3, 8, 8)).astype("float32"))
    y = mx.np.array(rs.randint(0, 4, (8,)).astype("int32"))
    lr = 0.5
    runs = []
    for mesh in (None, parallel.create_mesh(dp=2)):
        mx.np.random.seed(7)
        net = _ToyResNet()
        net.initialize()
        net(x)
        step = parallel.TrainStep(
            net, gluon.loss.SoftmaxCrossEntropyLoss(),
            mx.optimizer.SGD(learning_rate=lr), mesh=mesh)
        runs.append(_sgd_gradients(net, step, x, y, lr))
    (loss1, grads1, stats1), (loss2, grads2, stats2) = runs
    assert abs(loss1 - loss2) < 1e-5
    assert sum("gamma" in k for k in grads1) == 3
    for k in grads1:
        onp.testing.assert_allclose(grads2[k], grads1[k], rtol=2e-4,
                                    atol=2e-6, err_msg=k)
    for k in stats1:
        onp.testing.assert_allclose(stats2[k], stats1[k], rtol=1e-5,
                                    atol=1e-6, err_msg=k)
