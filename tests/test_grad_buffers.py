"""A parameter's gradient buffer takes device memory only once somebody
reads or writes it (ROADMAP D16): a network that is only ever stepped by
``parallel.TrainStep``, which differentiates inside its own program,
never holds one; the tape, ``grad()``, ``Trainer`` and ``zero_grad``
behave as they did."""
import gc

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, parallel
from mxnet_tpu.gluon import nn


def _mlp(dtype="float32"):
    mx.np.random.seed(4)
    net = nn.HybridSequential()
    net.add(nn.Dense(256, activation="relu", in_units=128),
            nn.Dense(10, in_units=256))
    net.initialize()
    if dtype != "float32":
        net.cast(dtype)
    return net


def _batch():
    rs = onp.random.RandomState(0)
    return (mx.np.array(rs.randn(8, 128).astype("float32")),
            mx.np.array(rs.randint(0, 10, 8).astype("int32")))


def _live_bytes():
    # (a TrainStep and its jitted step refer to each other: what earlier
    # tests of this process left is freed only when the collector runs)
    gc.collect()
    return sum(a.nbytes for a in jax.live_arrays())


def _param_bytes(net):
    return sum(p.data()._data.nbytes for p in net.collect_params().values())


def test_a_net_stepped_by_train_step_holds_no_gradient_buffer():
    x, y = _batch()
    before = _live_bytes()
    net = _mlp()
    params = _param_bytes(net)
    step = parallel.TrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                              mx.optimizer.SGD(learning_rate=0.1),
                              mesh=None)
    first = float(step(x, y))
    for _ in range(3):
        last = float(step(x, y))
    assert last < first
    ps = net.collect_params()
    assert all(p._grad is not None and not p._grad.live
               for p in ps.values())
    # parameters (SGD without momentum keeps no state), nothing a
    # parameter's size beside them
    held = _live_bytes() - before
    assert params <= held < 1.5 * params, (held, params)
    # reading the gradients makes them, a parameter's size in all
    grads = [p.grad()._data for p in ps.values()]
    assert all(p._grad.live for p in ps.values())
    assert _live_bytes() - before - held == params
    assert all(float(jnp.abs(g).max()) == 0 for g in grads)


def test_the_tape_and_the_trainer_still_read_fresh_gradients():
    """The same net, first through ``TrainStep`` (no buffer made), then
    through ``autograd.record`` / ``backward`` / ``Trainer.step``: the
    buffers appear with the first backward and carry its gradient."""
    x, y = _batch()
    net = _mlp()
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    parallel.TrainStep(net, loss_fn, mx.optimizer.SGD(learning_rate=0.1),
                       mesh=None)(x, y)
    ps = net.collect_params()
    assert not any(p._grad.live for p in ps.values())
    assert not any(p._fresh_grad for p in ps.values())
    trainer = gluon.Trainer(ps, "sgd", {"learning_rate": 0.1})
    before = {n: jnp.copy(p.data()._data) for n, p in ps.items()}
    with autograd.record():
        loss = loss_fn(net(x), y).mean()
    loss.backward()
    assert all(p._grad.live and p._fresh_grad for p in ps.values())

    def ref_loss(arrays):
        h = jnp.maximum(x._data @ arrays["0.weight"].T
                        + arrays["0.bias"], 0)
        logits = h @ arrays["1.weight"].T + arrays["1.bias"]
        logp = jax.nn.log_softmax(logits)
        return -jnp.take_along_axis(logp, y._data[:, None], 1).mean()

    want = jax.grad(ref_loss)(before)
    for n, p in ps.items():
        onp.testing.assert_allclose(p.grad()._data, want[n], rtol=1e-4,
                                    atol=1e-6)
    trainer.step(1)
    assert not any(p._fresh_grad for p in ps.values())
    for n, p in ps.items():
        onp.testing.assert_allclose(p.data()._data,
                                    before[n] - 0.1 * want[n], rtol=1e-4,
                                    atol=1e-6)
    # a second backward writes the same handles
    handles = {n: p._grad for n, p in ps.items()}
    with autograd.record():
        loss_fn(net(x), y).mean().backward()
    assert all(ps[n]._grad is h and h._fresh for n, h in handles.items())


def test_an_unmade_buffer_answers_for_its_shape_and_dtype():
    net = _mlp()
    w = net[0].weight
    g = w.grad()
    assert not g.live
    assert g.shape == (256, 128) and g.dtype == onp.float32
    assert not g.live                        # asking made nothing
    net.cast("bfloat16")                     # nor does a cast ...
    assert w.grad() is g and not g.live and g.dtype == jnp.bfloat16
    net.zero_grad()                          # ... nor zeroing zeros
    assert not g.live
    assert g.asnumpy().shape == (256, 128)   # a reader does
    assert g.live and g._data.dtype == jnp.bfloat16
    net.cast("float32")
    assert g._data.dtype == jnp.float32 and w.grad() is g


def test_grad_req_add_accumulates_from_the_unmade_zeros():
    x, y = _batch()
    net = _mlp()
    for p in net.collect_params().values():
        p.grad_req = "add"
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    for _ in range(2):
        with autograd.record():
            loss = loss_fn(net(x), y).mean()
        loss.backward()
    twice = net[1].weight.grad()._data
    net.zero_grad()
    assert float(jnp.abs(net[1].weight.grad()._data).max()) == 0
    with autograd.record():
        loss = loss_fn(net(x), y).mean()
    loss.backward()
    onp.testing.assert_allclose(twice, 2 * net[1].weight.grad()._data,
                                rtol=1e-5, atol=1e-7)


def test_a_buffer_first_read_inside_a_trace_is_concrete():
    net = _mlp()
    w = net[0].weight

    @jax.jit
    def peek(a):
        return a + w.grad()._data.sum()

    assert float(peek(jnp.float32(1.0))) == 1.0
    assert not isinstance(w.grad()._data, jax.core.Tracer)
