"""Plain reference of ResNet-50 training (He et al. 2015,
arXiv:1512.03385, Table 1, the 50-layer column, as Goyal et al. 2017,
arXiv:1706.02677, section 5.1, train it: the stride of a stage's first
block on its 3x3 convolution, "v1.5"): forward, softmax
cross-entropy, gradients and SGD with momentum in float32 ``jax.numpy``
at ``highest`` precision.  No layout tricks, no fused step, nothing of
the program.

Departures from the publication, each as the configuration's file states
it: the stride of a stage's first block sits on its 3x3 convolution
("v1.5", which is what 4.089 GMAC an image counts); batch normalisation
uses the batch's own biased variance with eps 1e-5; parameters are held
in the configuration's ``param_dtype`` between steps (the update is
computed in float32 and rounded once when stored), momentum in float32;
weight decay falls on every trainable leaf.

Each bottleneck block is recomputed in the backward pass
(``jax.checkpoint``), so that float32 activations of a batch of 256 fit
on one chip once the program's state is freed.
"""
import math

import jax
import jax.numpy as jnp

from .precision import contraction

STAGES = ((3, 256), (4, 512), (6, 1024), (3, 2048))


def leaf_specs(model):
    """``{name: {"kind", "scale", "shape", "dtype"}}`` of every leaf, in
    forward order.  Convolutions and the classifier are He-normal
    (He et al. 2015b, arXiv:1502.01852); beta and bias 0; gamma 1, but
    ``residual_gamma`` in the batch normalisation that ends a residual
    branch (Goyal et al. 2017 start that one at 0, so that a block
    starts as the identity and the untrained network is well
    conditioned; see the configuration's ``assumed``)."""
    dt = model["param_dtype"]
    specs = {}

    def conv(name, cout, cin, k):
        specs[name + ".w"] = {"kind": "normal", "shape": [cout, cin, k, k],
                              "scale": math.sqrt(2.0 / (cin * k * k)),
                              "dtype": dt}
        gamma = model["residual_gamma"] if name.endswith(".c3") else 1.0
        specs[name + ".gamma"] = {"kind": "const", "scale": gamma,
                                  "shape": [cout], "dtype": dt}
        specs[name + ".beta"] = {"kind": "const", "scale": 0.0,
                                 "shape": [cout], "dtype": dt}

    conv("stem", 64, 3, 7)
    cin = 64
    for s, (blocks, cout) in enumerate(STAGES):
        for b in range(blocks):
            p = "s%d.b%d." % (s, b)
            conv(p + "c1", cout // 4, cin, 1)
            conv(p + "c2", cout // 4, cout // 4, 3)
            conv(p + "c3", cout, cout // 4, 1)
            if b == 0:
                conv(p + "down", cout, cin, 1)
            cin = cout
    n = model["num_classes"]
    specs["fc.w"] = {"kind": "normal", "shape": [n, 2048], "dtype": dt,
                     "scale": math.sqrt(2.0 / 2048)}
    specs["fc.b"] = {"kind": "const", "scale": 0.0, "shape": [n],
                     "dtype": dt}
    return specs


def _forward(params, x, precision, eps):
    def conv_bn(x, p, name, stride, pad, relu=True):
        y = contraction(precision, lambda x, w: jax.lax.conv_general_dilated(
            x, w, (stride, stride), [(pad, pad), (pad, pad)],
            dimension_numbers=("NCHW", "OIHW", "NCHW")))(x, p[name + ".w"])
        mean = jnp.mean(y, (0, 2, 3), keepdims=True)
        var = jnp.mean(jnp.square(y - mean), (0, 2, 3), keepdims=True)
        y = (y - mean) * jax.lax.rsqrt(var + eps) \
            * p[name + ".gamma"].reshape(1, -1, 1, 1) \
            + p[name + ".beta"].reshape(1, -1, 1, 1)
        return jax.nn.relu(y) if relu else y

    def block(x, p, stride, down):
        y = conv_bn(x, p, "c1", 1, 0)
        y = conv_bn(y, p, "c2", stride, 1)
        y = conv_bn(y, p, "c3", 1, 0, relu=False)
        if down:
            x = conv_bn(x, p, "down", stride, 0, relu=False)
        return jax.nn.relu(y + x)

    x = conv_bn(x, params, "stem", 2, 3)
    x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 1, 3, 3),
                              (1, 1, 2, 2),
                              [(0, 0), (0, 0), (1, 1), (1, 1)])
    for s, (blocks, _) in enumerate(STAGES):
        for b in range(blocks):
            pre = "s%d.b%d." % (s, b)
            sub = {k[len(pre):]: v for k, v in params.items()
                   if k.startswith(pre)}
            stride = 2 if (b == 0 and s > 0) else 1
            x = jax.checkpoint(
                lambda x, p, stride=stride, down=(b == 0):
                block(x, p, stride, down))(x, sub)
    x = jnp.mean(x, (2, 3))
    return contraction(precision, lambda x, w: jnp.matmul(x, w.T))(
        x, params["fc.w"]) + params["fc.b"]


def make_step(model, precision="f32", rows=None):
    """``step(params, mom, x, y) -> (loss, grads, params', mom')`` of one
    SGD-momentum step; ``params`` in ``param_dtype``, ``mom`` float32,
    ``x`` (B, 3, H, W) any float type, ``y`` (B,) int.  ``rows``
    restricts the step to the first ``rows`` of the batch, the mean taken
    over them (the planted fault of a batch half left out)."""
    opt = model["optimizer"]
    lr, mu, wd = opt["learning_rate"], opt["momentum"], opt["wd"]
    eps = model["bn_eps"]

    def loss_of(p32, x, y):
        logits = _forward(p32, x.astype(jnp.float32), precision, eps)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, y[:, None], 1))

    @jax.jit
    def step(params, mom, x, y):
        if rows is not None:
            x, y = x[:rows], y[:rows]
        with jax.default_matmul_precision("highest"):
            p32 = {k: v.astype(jnp.float32) for k, v in params.items()}
            loss, grads = jax.value_and_grad(loss_of)(p32, x, y)
        new_p, new_m = {}, {}
        for k, w in p32.items():
            g = grads[k] + wd * w
            new_m[k] = mu * mom[k] - lr * g
            new_p[k] = (w + new_m[k]).astype(params[k].dtype)
        return loss, grads, new_p, new_m

    return step
