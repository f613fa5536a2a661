"""INT8 quantized convolution + entropy-KL calibration tests.

Reference parity: ``src/operator/quantization/quantized_conv.cc:1``
(int8 conv), ``src/operator/quantization/calibrate.cc:88`` (KL threshold
search), ``python/mxnet/contrib/quantization.py`` (quantize_net flow).
"""
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon
from mxnet_tpu.contrib import quantization as q
from mxnet_tpu.gluon import nn


def test_optimal_threshold_clean_distribution():
    """With no outliers the best threshold keeps ~all of the range."""
    rs = onp.random.RandomState(0)
    arr = rs.normal(0, 1, 100000)
    th = float(onp.abs(arr).max())
    hist, edges = onp.histogram(arr, bins=1001, range=(-th, th))
    t, div = q.optimal_threshold(hist, edges, num_quantized_bins=255)
    assert t > 0.5 * th
    assert onp.isfinite(div)


def test_optimal_threshold_clips_outlier():
    """A single extreme outlier must be clipped by entropy calibration
    (the whole point of KL over minmax)."""
    rs = onp.random.RandomState(1)
    arr = onp.concatenate([rs.normal(0, 1, 100000), [100.0]])
    th = float(onp.abs(arr).max())
    hist, edges = onp.histogram(arr, bins=8001, range=(-th, th))
    t, _ = q.optimal_threshold(hist, edges, num_quantized_bins=255)
    assert t < 0.15 * th  # threshold stays near the gaussian mass
    # and the resulting scale is far tighter than minmax
    assert q._entropy_scale(arr) < 0.15 * (th / 127.0)


def test_optimal_threshold_is_an_edge():
    rs = onp.random.RandomState(2)
    arr = rs.normal(0, 2, 20000)
    th = float(onp.abs(arr).max())
    hist, edges = onp.histogram(arr, bins=511, range=(-th, th))
    t, _ = q.optimal_threshold(hist, edges, num_quantized_bins=255)
    assert onp.isclose(edges, t).any()


def test_smooth_distribution_matches_reference_semantics():
    p = onp.array([0.0, 2.0, 0.0, 2.0])
    s = q._smooth_distribution(p, eps=1e-4)
    assert onp.isclose(s.sum(), p.sum())
    assert (s > 0).all()
    assert q._smooth_distribution(onp.zeros(4)) is None


def test_quantized_conv2d_close_to_fp():
    rs = onp.random.RandomState(3)
    conv = nn.Conv2D(8, 3, strides=2, padding=1, in_channels=4,
                     use_bias=True)
    conv.initialize()
    x = mx.np.array(rs.normal(0, 1, (2, 4, 12, 12)).astype(onp.float32))
    conv(x)  # materialize
    want = conv(x).asnumpy()
    qc = q.QuantizedConv2D(conv, act_scale=q._minmax_scale(x.asnumpy()))
    got = qc(x).asnumpy()
    err = onp.abs(got - want).max() / (onp.abs(want).max() + 1e-9)
    assert err < 0.05, err


def test_quantized_conv_grouped():
    rs = onp.random.RandomState(4)
    conv = nn.Conv2D(8, 3, padding=1, groups=2, in_channels=4)
    conv.initialize()
    x = mx.np.array(rs.normal(0, 1, (1, 4, 8, 8)).astype(onp.float32))
    want = conv(x).asnumpy()
    qc = q.QuantizedConv2D(conv, act_scale=q._minmax_scale(x.asnumpy()))
    got = qc(x).asnumpy()
    err = onp.abs(got - want).max() / (onp.abs(want).max() + 1e-9)
    assert err < 0.05, err


def _small_cnn():
    net = nn.HybridSequential()
    net.add(nn.Conv2D(8, 3, padding=1, activation="relu"),
            nn.MaxPool2D(2),
            nn.Conv2D(16, 3, padding=1, activation="relu"),
            nn.GlobalAvgPool2D(),
            nn.Dense(10))
    return net


@pytest.mark.parametrize("calib_mode", ["naive", "entropy"])
def test_quantize_net_cnn_end_to_end(calib_mode):
    mx.np.random.seed(5)
    net = _small_cnn()
    net.initialize()
    x = mx.np.random.normal(0, 1, (8, 3, 16, 16))
    ref = net(x).asnumpy()
    q.quantize_net(net, calib_data=[x], calib_mode=calib_mode)
    # both conv layers and the dense layer must have been swapped
    kinds = [type(c).__name__ for c in net._children.values()]
    assert kinds.count("QuantizedConv2D") == 2
    assert kinds.count("QuantizedDense") == 1
    out = net(x).asnumpy()
    agree = (out.argmax(-1) == ref.argmax(-1)).mean()
    assert agree >= 0.75, agree
    rel = onp.abs(out - ref).max() / (onp.abs(ref).max() + 1e-9)
    assert rel < 0.25, rel


def test_quantize_resnet18_top1_parity():
    """CNN INT8 flagship case at CI scale: quantized ResNet-18 keeps
    argmax agreement with fp32 on synthetic calibration."""
    from mxnet_tpu.gluon.model_zoo import vision
    mx.np.random.seed(6)
    net = vision.resnet18_v1()
    net.initialize()
    x = mx.np.random.normal(0, 0.5, (4, 3, 64, 64))
    ref = net(x).asnumpy()
    q.quantize_net(net, calib_data=[x], calib_mode="naive")
    n_qconv = sum(1 for b in _walk_blocks(net)
                  if type(b).__name__ == "QuantizedConv2D")
    assert n_qconv >= 15, n_qconv
    out = net(x).asnumpy()
    agree = (out.argmax(-1) == ref.argmax(-1)).mean()
    assert agree >= 0.75, agree


def _walk_blocks(block):
    yield block
    for c in block._children.values():
        yield from _walk_blocks(c)


def test_quantized_net_hybridizes():
    """The INT8 deployment path: quantize then hybridize(static_alloc) must
    trace the int8 convs into one compiled program."""
    from mxnet_tpu.gluon.model_zoo import vision
    mx.np.random.seed(8)
    net = vision.resnet18_v1()
    net.initialize()
    x = mx.np.random.uniform(0, 1, (2, 3, 64, 64))
    ref = net(x).asnumpy()
    q.quantize_net(net, calib_data=[x], calib_mode="naive")
    net.hybridize(static_alloc=True, static_shape=True)
    out = net(x).asnumpy()
    assert (out.argmax(-1) == ref.argmax(-1)).mean() >= 0.5
    out2 = net(x).asnumpy()  # cached path identical
    onp.testing.assert_allclose(out, out2, rtol=1e-6)


# -- round-4: quantized elemwise-add / concat + int8 accuracy ---------------
def test_quantized_elemwise_add_matches_float():
    from mxnet_tpu.contrib import quantization as q
    rs = onp.random.RandomState(0)
    a = rs.uniform(-3, 3, (4, 8)).astype("float32")
    b = rs.uniform(-1, 1, (4, 8)).astype("float32")
    a_q = mx.np.array(q.quantize_array(a, 3.0 / 127.0))
    b_q = mx.np.array(q.quantize_array(b, 1.0 / 127.0))
    out, omin, omax = q.quantized_elemwise_add(
        a_q, b_q, -3.0, 3.0, -1.0, 1.0)
    assert out.asnumpy().dtype == onp.int8
    o_scale = float(omax.asnumpy()) / 127.0
    got = out.asnumpy().astype("float32") * o_scale
    # max error ~ one output step + the input quantization steps
    tol = o_scale + 3.0 / 127.0 + 1.0 / 127.0
    assert onp.abs(got - (a + b)).max() <= tol


def test_quantized_concat_matches_float():
    from mxnet_tpu.contrib import quantization as q
    rs = onp.random.RandomState(1)
    a = rs.uniform(-2, 2, (2, 3)).astype("float32")
    b = rs.uniform(-8, 8, (2, 5)).astype("float32")
    a_q = mx.np.array(q.quantize_array(a, 2.0 / 127.0))
    b_q = mx.np.array(q.quantize_array(b, 8.0 / 127.0))
    out, omin, omax = q.quantized_concat(a_q, -2.0, 2.0, b_q, -8.0, 8.0,
                                         dim=1)
    assert out.shape == (2, 8)
    assert out.asnumpy().dtype == onp.int8
    o_scale = float(omax.asnumpy()) / 127.0
    assert abs(o_scale - 8.0 / 127.0) < 1e-6  # widest input range wins
    got = out.asnumpy().astype("float32") * o_scale
    want = onp.concatenate([a, b], axis=1)
    assert onp.abs(got - want).max() <= 2 * o_scale + 8.0 / 127.0


def test_int8_accuracy_within_bound():
    """quantize -> predict: int8 top-1 must track fp32 top-1 (the
    trust-establishing accuracy check the reference quantization examples
    run; bounded top-1 delta)."""
    from mxnet_tpu.contrib import quantization as q
    mx.np.random.seed(0)
    onp.random.seed(0)
    # separable 3-class blobs rendered as 1x8x8 "images"
    n_per, ncls = 60, 3
    xs, ys = [], []
    for c in range(ncls):
        base = onp.zeros((8, 8), "float32")
        base[c * 2:c * 2 + 3, c * 2:c * 2 + 3] = 1.0
        for _ in range(n_per):
            img = base + onp.random.normal(0, 0.2, (8, 8))
            xs.append(img[None])
            ys.append(c)
    X = mx.np.array(onp.stack(xs).astype("float32"))
    Y = mx.np.array(onp.asarray(ys, "int32"))

    net = nn.HybridSequential()
    net.add(nn.Conv2D(8, 3, padding=1, activation="relu"),
            nn.Conv2D(8, 3, padding=1, activation="relu"),
            nn.GlobalAvgPool2D(), nn.Dense(ncls))
    net.initialize()
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": 1e-2})
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    for _ in range(80):
        with mx.autograd.record():
            loss = loss_fn(net(X), Y).mean()
        loss.backward()
        trainer.step(1)

    fp32_pred = net(X).asnumpy().argmax(1)
    fp32_acc = (fp32_pred == onp.asarray(ys)).mean()
    assert fp32_acc > 0.8, fp32_acc  # the float model must actually work

    q.quantize_net(net, calib_data=[X], calib_mode="naive")
    int8_pred = net(X).asnumpy().argmax(1)
    int8_acc = (int8_pred == onp.asarray(ys)).mean()
    assert fp32_acc - int8_acc <= 0.05, (fp32_acc, int8_acc)
    assert (int8_pred == fp32_pred).mean() >= 0.9
