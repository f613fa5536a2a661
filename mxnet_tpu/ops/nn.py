"""Neural-network ops as pure JAX functions (NCHW layouts, MXNet semantics).

Reference parity (behavior, not implementation):
- convolution/deconvolution: ``src/operator/nn/convolution.cc``,
  ``deconvolution.cc`` (NCHW default, groups, dilation)
- pooling: ``src/operator/nn/pooling.cc`` (max/avg/lp, global, valid/full)
- batch/layer/group/instance norm: ``src/operator/nn/batch_norm.cc``,
  ``layer_norm.cc``, ``group_norm.cc``, ``instance_norm.cc``
- softmax family: ``src/operator/nn/softmax.cc``
- fully_connected: ``src/operator/nn/fully_connected.cc:251``
- dropout: ``src/operator/nn/dropout.cc``
- activations: ``src/operator/nn/activation.cc``, ``leaky_relu.cc``

All functions take/return ``jax.Array`` and are jit/vjp-safe (static python
control flow only).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax


# ----------------------------------------------------------------------
# dense / linear algebra
# ----------------------------------------------------------------------
def fully_connected(x, weight, bias=None, flatten=True):
    """MXNet FullyConnected: y = x @ W.T + b; optionally flattens trailing
    dims (fully_connected.cc:251 semantics)."""
    if flatten and x.ndim > 2:
        x = x.reshape(x.shape[0], -1)
    y = jnp.matmul(x, weight.T)
    if bias is not None:
        y = y + bias
    return y


def dense(x, weight, bias=None):
    """Gluon Dense on trailing dim (no flatten): y = x @ W.T + b."""
    y = jnp.matmul(x, weight.T)
    if bias is not None:
        y = y + bias
    return y


# ----------------------------------------------------------------------
# convolution
# ----------------------------------------------------------------------
def channels_last(layout):
    """True for NWC/NHWC/NDHWC — the MXU-friendly layouts on TPU.

    The reference supports these on GPU only (``convolution-inl.h:107``);
    here they are first-class because XLA:TPU tiles channels-last convs
    without the re-layout passes NCHW needs (PERF.md lever 1).  This is the
    single source of truth for layout classification — gluon layers and the
    model zoo import it."""
    return layout in ("NWC", "NHWC", "NDHWC")


def _conv_dim_numbers(ndim, layout=None):
    # Default NC+spatial io layout with OIHW kernels; channels-last uses
    # O+spatial+I kernels, matching the reference's ConvertLayout of
    # (O, C/g, *k) into the data layout (convolution.cc:156-163).
    if channels_last(layout):
        if ndim == 3:
            return ("NWC", "OWI", "NWC")
        if ndim == 4:
            return ("NHWC", "OHWI", "NHWC")
        if ndim == 5:
            return ("NDHWC", "ODHWI", "NDHWC")
    else:
        if ndim == 3:
            return ("NCH", "OIH", "NCH")
        if ndim == 4:
            return ("NCHW", "OIHW", "NCHW")
        if ndim == 5:
            return ("NCDHW", "OIDHW", "NCDHW")
    raise ValueError("conv supports 1/2/3 spatial dims")


def convolution(x, weight, bias=None, stride=None, pad=None, dilate=None,
                num_group=1, layout=None, preferred_element_type=None):
    """Grouped, strided, dilated ND convolution (NC+spatial or
    channels-last layout).  ``preferred_element_type`` sets the
    accumulator dtype (int32 for the int8 quantized path)."""
    nsp = x.ndim - 2
    stride = tuple(stride or (1,) * nsp)
    pad = tuple(pad or (0,) * nsp)
    dilate = tuple(dilate or (1,) * nsp)
    dn = lax.conv_dimension_numbers(x.shape, weight.shape,
                                    _conv_dim_numbers(x.ndim, layout))
    y = lax.conv_general_dilated(
        x, weight, window_strides=stride,
        padding=[(p, p) for p in pad],
        lhs_dilation=(1,) * nsp,
        rhs_dilation=dilate,
        dimension_numbers=dn,
        feature_group_count=num_group,
        preferred_element_type=preferred_element_type)
    if bias is not None:
        bshape = (1,) * (x.ndim - 1) + (-1,) if channels_last(layout) \
            else (1, -1) + (1,) * nsp
        y = y + bias.reshape(bshape)
    return y


def deconvolution(x, weight, bias=None, stride=None, pad=None, dilate=None,
                  num_group=1, adj=None, target_shape=None):
    """Transposed convolution (gradient of conv w.r.t. input).

    weight layout matches the reference: (in_channels, out_channels/g, *k).
    """
    nsp = x.ndim - 2
    stride = tuple(stride or (1,) * nsp)
    pad = tuple(pad or (0,) * nsp)
    dilate = tuple(dilate or (1,) * nsp)
    adj = tuple(adj or (0,) * nsp)
    dn = lax.conv_dimension_numbers(
        x.shape,
        (weight.shape[1] * num_group, weight.shape[0] // num_group) + weight.shape[2:],
        _conv_dim_numbers(x.ndim))
    # express as lhs-dilated conv with transposed kernel
    w = weight
    if num_group > 1:
        w = w.reshape((num_group, w.shape[0] // num_group) + w.shape[1:])
        w = jnp.swapaxes(w, 1, 2)
        w = w.reshape((w.shape[0] * w.shape[1],) + w.shape[2:])
    else:
        w = jnp.swapaxes(w, 0, 1)
    w = jnp.flip(w, axis=tuple(range(2, w.ndim)))
    k_eff = [(w.shape[2 + i] - 1) * dilate[i] + 1 for i in range(nsp)]
    padding = [(k_eff[i] - 1 - pad[i], k_eff[i] - 1 - pad[i] + adj[i])
               for i in range(nsp)]
    y = lax.conv_general_dilated(
        x, w, window_strides=(1,) * nsp,
        padding=padding,
        lhs_dilation=stride,
        rhs_dilation=dilate,
        dimension_numbers=dn,
        feature_group_count=num_group)
    if bias is not None:
        y = y + bias.reshape((1, -1) + (1,) * nsp)
    return y


# ----------------------------------------------------------------------
# pooling
# ----------------------------------------------------------------------
def pooling(x, kernel, pool_type="max", stride=None, pad=None,
            global_pool=False, count_include_pad=True, layout=None):
    nsp = x.ndim - 2
    last = channels_last(layout)
    if global_pool:
        kernel = x.shape[1:-1] if last else x.shape[2:]
        stride = (1,) * nsp
        pad = (0,) * nsp
    kernel = tuple(kernel)
    stride = tuple(stride or kernel)
    pad = tuple(pad or (0,) * nsp)
    if last:
        window = (1,) + kernel + (1,)
        strides = (1,) + stride + (1,)
        pads = ((0, 0),) + tuple((p, p) for p in pad) + ((0, 0),)
    else:
        window = (1, 1) + kernel
        strides = (1, 1) + stride
        pads = ((0, 0), (0, 0)) + tuple((p, p) for p in pad)
    if pool_type == "max":
        init = -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else \
            jnp.iinfo(x.dtype).min
        return lax.reduce_window(x, init, lax.max, window, strides, pads)
    if pool_type in ("avg", "sum"):
        s = lax.reduce_window(x, 0.0 if jnp.issubdtype(x.dtype, jnp.floating)
                              else 0, lax.add, window, strides, pads)
        if pool_type == "sum":
            return s
        if count_include_pad or all(p == 0 for p in pad):
            denom = 1
            for k in kernel:
                denom *= k
            return s / denom
        ones = jnp.ones(x.shape, x.dtype)
        cnt = lax.reduce_window(ones, 0.0, lax.add, window, strides, pads)
        return s / cnt
    if pool_type == "lp":
        p = 2.0
        s = lax.reduce_window(jnp.abs(x) ** p, 0.0, lax.add, window, strides,
                              pads)
        return s ** (1.0 / p)
    raise ValueError("unknown pool_type %r" % pool_type)


def adaptive_avg_pool2d(x, output_size):
    if isinstance(output_size, int):
        output_size = (output_size, output_size)
    n, c, h, w = x.shape
    oh, ow = output_size
    if h % oh == 0 and w % ow == 0:
        x = x.reshape(n, c, oh, h // oh, ow, w // ow)
        return x.mean(axis=(3, 5))
    # general case: integral-image average (static shapes)
    ys = jnp.linspace(0, h, oh + 1).astype(jnp.int32)
    xs = jnp.linspace(0, w, ow + 1).astype(jnp.int32)
    cum = jnp.cumsum(jnp.cumsum(x, axis=2), axis=3)
    cum = jnp.pad(cum, ((0, 0), (0, 0), (1, 0), (1, 0)))
    out = (cum[:, :, ys[1:], :][:, :, :, xs[1:]]
           - cum[:, :, ys[:-1], :][:, :, :, xs[1:]]
           - cum[:, :, ys[1:], :][:, :, :, xs[:-1]]
           + cum[:, :, ys[:-1], :][:, :, :, xs[:-1]])
    area = ((ys[1:] - ys[:-1])[:, None] * (xs[1:] - xs[:-1])[None, :])
    return out / area


# ----------------------------------------------------------------------
# normalization
# ----------------------------------------------------------------------
def _bn_param_shape(ndim, axis):
    shape = [1] * ndim
    shape[axis] = -1
    return tuple(shape)


def _bn_geometry(x, axis):
    """(axes reduced, the per-channel broadcast shape, elements a channel)."""
    axes = tuple(i for i in range(x.ndim) if i != axis)
    return axes, _bn_param_shape(x.ndim, axis), x.size // x.shape[axis]


def _bn_forward(x, gamma, beta, eps, axis):
    """((out, mean, var), residuals of the backward)."""
    axes, shape, n = _bn_geometry(x, axis)
    xf = x.astype(jnp.float32)
    # both moments from one read of x: two sums with no dependence between
    # them, so XLA can carry both in the epilogue of the op that produced x
    mean = jnp.sum(xf, axis=axes) / n
    var = jnp.maximum(jnp.sum(xf * xf, axis=axes) / n - mean * mean, 0.0)
    inv = lax.rsqrt(var + eps)
    out = (xf - mean.reshape(shape)) * inv.reshape(shape) \
        * gamma.astype(jnp.float32).reshape(shape) \
        + beta.astype(jnp.float32).reshape(shape)
    outs = (out.astype(x.dtype), mean.astype(gamma.dtype),
            var.astype(gamma.dtype))
    # beta rides along for its dtype only
    return outs, (x, mean, inv, gamma, beta)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _batch_norm_train(x, gamma, beta, eps, axis):
    return _bn_forward(x, gamma, beta, eps, axis)[0]


def _batch_norm_train_fwd(x, gamma, beta, eps, axis):
    # ``symbolic_zeros`` hands the differentiable arguments over wrapped
    return _bn_forward(x.value, gamma.value, beta.value, eps, axis)


def _batch_norm_train_bwd(eps, axis, res, cts):
    x, mean, inv, gamma, beta = res
    dout, dmean, dvar = cts
    axes, shape, n = _bn_geometry(x, axis)
    zero = jax.custom_derivatives.SymbolicZero
    g = jnp.zeros(x.shape, jnp.float32) if isinstance(dout, zero) \
        else dout.astype(jnp.float32)
    xc = x.astype(jnp.float32) - mean.reshape(shape)
    xhat = xc * inv.reshape(shape)
    dbeta = jnp.sum(g, axis=axes)
    dgamma = jnp.sum(g * xhat, axis=axes)
    dx = (gamma.astype(jnp.float32) * inv).reshape(shape) * (
        g - (dbeta / n).reshape(shape) - xhat * (dgamma / n).reshape(shape))
    # mean and var are outputs too.  In a training step they only feed the
    # running statistics: their cotangents are symbolic zeros and these
    # terms are never traced.
    if not isinstance(dmean, zero):
        dx = dx + (dmean.astype(jnp.float32) / n).reshape(shape)
    if not isinstance(dvar, zero):
        dx = dx + xc * (dvar.astype(jnp.float32) * (2.0 / n)).reshape(shape)
    return dx.astype(x.dtype), dgamma.astype(gamma.dtype), \
        dbeta.astype(beta.dtype)


_batch_norm_train.defvjp(_batch_norm_train_fwd, _batch_norm_train_bwd,
                         symbolic_zeros=True)


def batch_norm_train(x, gamma, beta, eps=1e-5, axis=1):
    """Training-mode BN over ``axis``; returns (out, batch_mean, batch_var).

    One op with its own backward (``jax.custom_vjp``), one formulation for
    every caller.  Statistics are float32 whatever the input's dtype (at
    bf16 x b256 the variance loses ~3 decimal digits otherwise; reference
    BN uses fp32 accumulators, ``src/operator/nn/batch_norm.cc``), and any
    ``axis`` is reduced in place (no transpose), so channels-last layouts
    stay re-layout-free.

    Forward: ``s1 = sum(x)``, ``s2 = sum(x*x)`` in one pass, ``mean = s1/N``,
    ``var = max(s2/N - mean**2, 0)`` (the biased variance, as XLA's
    BatchNormExpander and flax's ``use_fast_variance`` compute it),
    ``out = (x - mean) * rsqrt(var + eps) * gamma + beta`` cast to
    ``x.dtype``.  ``jnp.var`` would centre on the mean and read x again.

    Backward, from the residuals (x as stored, mean, inv, gamma), with
    ``xhat = (x - mean) * inv`` and ``g = dout`` in float32:
    ``dbeta = sum(g)``, ``dgamma = sum(g * xhat)`` (one pass, two sums),
    ``dx = gamma * inv * (g - dbeta/N - xhat * dgamma/N)``, plus
    ``dmean/N + dvar * 2 * (x - mean)/N`` where ``mean`` / ``var`` carry a
    cotangent.  It has its own backward because autodiff of the forward
    transposes the mean *inside* the variance into one more full pass over
    the activation that sums ``c * (x - mean)``: zero but for rounding.
    """
    return _batch_norm_train(x, gamma, beta, eps, axis % x.ndim)


def batch_norm_inference(x, gamma, beta, moving_mean, moving_var, eps=1e-5,
                         axis=1):
    shape = _bn_param_shape(x.ndim, axis % x.ndim)
    inv = lax.rsqrt(moving_var + eps).reshape(shape)
    return (x - moving_mean.reshape(shape)) * inv * gamma.reshape(shape) \
        + beta.reshape(shape)


def layer_norm(x, gamma, beta, axis=-1, eps=1e-5):
    mean = jnp.mean(x, axis=axis, keepdims=True)
    var = jnp.var(x, axis=axis, keepdims=True)
    out = (x - mean) * lax.rsqrt(var + eps)
    shape = [1] * x.ndim
    shape[axis] = x.shape[axis]
    return out * gamma.reshape(shape) + beta.reshape(shape)


def rms_norm(x, gamma, axis=-1, eps=1e-6, unit_offset=False,
             out_dtype=None):
    """``x / rms(x) * gamma``.  With ``unit_offset`` the stored ``gamma``
    is the gain's distance from one (the gain is ``1 + gamma``, summed
    in float32); ``out_dtype`` casts the result (a float32 residual
    stream normed into the dtype of the matmuls that follow)."""
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=axis, keepdims=True)
    out = x * lax.rsqrt(var + eps).astype(x.dtype)
    shape = [1] * x.ndim
    shape[axis] = x.shape[axis]
    if unit_offset:
        gamma = 1.0 + gamma.astype(jnp.float32)
    out = out * gamma.reshape(shape)
    return out if out_dtype is None else out.astype(out_dtype)


def group_norm(x, gamma, beta, num_groups, eps=1e-5):
    n, c = x.shape[:2]
    g = num_groups
    xr = x.reshape((n, g, c // g) + x.shape[2:])
    axes = tuple(range(2, xr.ndim))
    mean = jnp.mean(xr, axis=axes, keepdims=True)
    var = jnp.var(xr, axis=axes, keepdims=True)
    xr = (xr - mean) * lax.rsqrt(var + eps)
    out = xr.reshape(x.shape)
    shape = (1, c) + (1,) * (x.ndim - 2)
    return out * gamma.reshape(shape) + beta.reshape(shape)


def instance_norm(x, gamma, beta, eps=1e-5):
    axes = tuple(range(2, x.ndim))
    mean = jnp.mean(x, axis=axes, keepdims=True)
    var = jnp.var(x, axis=axes, keepdims=True)
    out = (x - mean) * lax.rsqrt(var + eps)
    shape = (1, -1) + (1,) * (x.ndim - 2)
    return out * gamma.reshape(shape) + beta.reshape(shape)


def l2_normalization(x, eps=1e-10, mode="instance"):
    if mode == "instance":
        axes = tuple(range(1, x.ndim))
    elif mode == "channel":
        axes = (1,)
    elif mode == "spatial":
        axes = tuple(range(2, x.ndim))
    else:
        raise ValueError(mode)
    norm = jnp.sqrt(jnp.sum(jnp.square(x), axis=axes, keepdims=True) + eps)
    return x / norm


# ----------------------------------------------------------------------
# softmax family / activations
# ----------------------------------------------------------------------
def softmax(x, axis=-1, temperature=None, length=None):
    if temperature is not None and temperature != 1.0:
        x = x / temperature
    if length is not None:
        mask = jnp.arange(x.shape[axis]) < jnp.expand_dims(length, -1)
        shape = [1] * x.ndim
        shape[axis] = x.shape[axis]
        shape[0] = x.shape[0]
        x = jnp.where(mask.reshape([x.shape[0]] + [1] * (x.ndim - 2) +
                                   [x.shape[axis]]) if axis in (-1, x.ndim - 1)
                      else mask, x, -jnp.inf)
    return jax.nn.softmax(x, axis=axis)


def log_softmax(x, axis=-1, temperature=None):
    if temperature is not None and temperature != 1.0:
        x = x / temperature
    return jax.nn.log_softmax(x, axis=axis)


def _chunk_logits(h, head):
    return lax.dot_general(h, head, (((1,), (1,)), ((), ())),
                           preferred_element_type=jnp.float32)


def _chunks(x, n, chunk):
    """``x`` (N, ...) as (n, chunk, ...), zero rows appended to fill."""
    pad = n * chunk - x.shape[0]
    if pad:
        x = jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1))
    return x.reshape((n, chunk) + x.shape[1:])


def _chunk_ce(logits, y):
    """A chunk's cross-entropies and row logsumexps from its logits."""
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, y[:, None], axis=1)[:, 0]
    return lse - picked, lse


def _chunk_dlogits(logits, lse, y, gc, dtype):
    """``(softmax - onehot) * gc`` of a chunk from its float32 logits,
    row logsumexps and row cotangents ``gc``, cast to ``dtype``."""
    p = jnp.exp(logits - lse[:, None])
    hit = lax.broadcasted_iota(jnp.int32, p.shape, 1) == y[:, None]
    return ((p - hit.astype(p.dtype)) * gc[:, None]).astype(dtype)


def _chunk_head_grads(dlogits, h, head):
    """What a chunk gives the rows' gradient, ``dlogits @ head``, and
    the head's, ``dlogits.T @ h`` in float32."""
    dh = jnp.matmul(dlogits, head,
                    preferred_element_type=jnp.float32).astype(h.dtype)
    dw = lax.dot_general(dlogits, h, (((0,), (0,)), ((), ())),
                         preferred_element_type=jnp.float32)
    return dh, dw


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _chunked_ce(hidden, head, labels, chunk):
    return _chunked_ce_fwd(hidden, head, labels, chunk)[0]


def _chunked_ce_fwd(hidden, head, labels, chunk):
    N = hidden.shape[0]
    n = -(-N // chunk)

    def one(args):
        h, y = args
        return _chunk_ce(_chunk_logits(h, head), y)

    ce, lse = lax.map(one, (_chunks(hidden, n, chunk),
                            _chunks(labels, n, chunk)))
    return ce.reshape(-1)[:N], (hidden, head, labels, lse)


def _chunked_ce_bwd(chunk, res, g):
    hidden, head, labels, lse = res
    N = hidden.shape[0]
    n = lse.shape[0]

    def one(dw, args):
        h, y, l, gc = args
        # the chunk's logits again: the only copy alive in the backward
        dlogits = _chunk_dlogits(_chunk_logits(h, head), l, y, gc, h.dtype)
        dh, dw_chunk = _chunk_head_grads(dlogits, h, head)
        return dw + dw_chunk, dh

    dw, dh = lax.scan(one, jnp.zeros(head.shape, jnp.float32),
                      (_chunks(hidden, n, chunk), _chunks(labels, n, chunk),
                       lse, _chunks(g.astype(jnp.float32), n, chunk)))
    return dh.reshape(-1, hidden.shape[1])[:N], dw.astype(head.dtype), None


_chunked_ce.defvjp(_chunked_ce_fwd, _chunked_ce_bwd)


def chunked_softmax_cross_entropy(hidden, head, labels, chunk=2048):
    """Per-token cross-entropy of ``softmax(hidden @ head.T)`` without the
    whole logits: ``hidden`` (N, d), ``head`` (vocab, d), ``labels`` (N,)
    -> (N,) float32.  The tokens go a ``chunk`` at a time; a chunk's
    (chunk, vocab) logits are float32, live only while that chunk is
    worked on, and are computed again in the backward (which keeps the
    per-token logsumexp and sums the head's gradient in float32).
    Equals ``SoftmaxCrossEntropyLoss`` on the whole logits; ``chunk`` need
    not divide N.

    For a caller that needs the per-token values under cotangents of its
    own, which only the backward knows: four vocabulary-wide products a
    chunk.  A loss that is a weighted sum of them with weights it has
    before the head runs calls
    :func:`weighted_chunked_softmax_cross_entropy`, which needs three."""
    return _chunked_ce(hidden, head, labels.astype(jnp.int32),
                       int(min(chunk, hidden.shape[0])))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _weighted_ce(hidden, head, labels, weights, chunk):
    ce = _chunked_ce_fwd(hidden, head, labels, chunk)[0]
    return jnp.sum(weights * ce), ce


def _weighted_ce_fwd(hidden, head, labels, weights, chunk):
    N = hidden.shape[0]
    n = -(-N // chunk)

    def one(dw, args):
        h, y, w = args
        logits = _chunk_logits(h, head)
        ce, lse = _chunk_ce(logits, y)
        # the rows' cotangents are the weights (times the sum's own, a
        # scalar the backward applies): the gradients are formed here,
        # while the chunk's logits are alive.  dlogits is written once:
        # left to itself XLA computes it in the prologue of both
        # products, tile after tile, from the float32 logits
        with jax.named_scope("head_grad"):
            dlogits = lax.optimization_barrier(
                _chunk_dlogits(logits, lse, y, w, h.dtype))
            dh, dw_chunk = _chunk_head_grads(dlogits, h, head)
        return dw + dw_chunk, (ce, dh)

    dw, (ce, dh) = lax.scan(
        one, jnp.zeros(head.shape, jnp.float32),
        (_chunks(hidden, n, chunk), _chunks(labels, n, chunk),
         _chunks(weights, n, chunk)))
    ce = ce.reshape(-1)[:N]
    dh = dh.reshape(-1, hidden.shape[1])[:N]
    return (jnp.sum(weights * ce), ce), (dh, dw.astype(head.dtype), ce)


def _weighted_ce_bwd(chunk, res, cts):
    dh, dw, ce = res
    g = cts[0]      # the per-token output carries no gradient
    return (g * dh).astype(dh.dtype), (g * dw).astype(dw.dtype), None, g * ce


_weighted_ce.defvjp(_weighted_ce_fwd, _weighted_ce_bwd)


def weighted_chunked_softmax_cross_entropy(hidden, head, labels, weights,
                                           chunk=2048):
    """``(sum_n weights[n] * CE_n, CE)`` of ``softmax(hidden @ head.T)``
    without the whole logits: ``hidden`` (N, d), ``head`` (vocab, d),
    ``labels`` (N,), float32 ``weights`` (N,) -> a float32 scalar and the
    per-token cross-entropies (N,) as a value that carries no gradient
    (for logging).  The arithmetic, chunk by chunk, is
    :func:`chunked_softmax_cross_entropy`'s.

    For a loss that is linear in the cross-entropies with weights known
    before the head runs (``LoopedLM.loss``: the exit probabilities over
    the number of tokens).  A row's cotangent is then its weight, so a
    differentiated call forms ``dlogits = (softmax - onehot) * weights``
    and from it the gradients to ``hidden`` and ``head`` in the forward,
    from the chunk's logits while they are alive: three vocabulary-wide
    products a chunk where the per-token function's forward and backward
    make four.  The backward scales the two by the sum's cotangent; the
    gradient to ``weights`` is that cotangent times the cross-entropies.
    An undifferentiated call runs the per-token function's forward."""
    total, ce = _weighted_ce(hidden, head, labels.astype(jnp.int32),
                             weights.astype(jnp.float32),
                             int(min(chunk, hidden.shape[0])))
    return total, lax.stop_gradient(ce)


def masked_softmax(x, mask, axis=-1, temperature=1.0):
    if temperature != 1.0:
        x = x / temperature
    neg = jnp.finfo(x.dtype).min if jnp.issubdtype(x.dtype, jnp.floating) \
        else -1e9
    x = jnp.where(mask.astype(bool), x, neg)
    out = jax.nn.softmax(x, axis=axis)
    return jnp.where(mask.astype(bool), out, 0.0)


def leaky_relu(x, act_type="leaky", slope=0.25, gamma=None,
               lower_bound=0.125, upper_bound=0.334, rng=None):
    if act_type == "leaky":
        return jnp.where(x >= 0, x, slope * x)
    if act_type == "prelu":
        return jnp.where(x >= 0, x, gamma * x)
    if act_type == "elu":
        return jnp.where(x >= 0, x, slope * jnp.expm1(x))
    if act_type == "selu":
        alpha, scale = 1.6732632423543772, 1.0507009873554805
        return scale * jnp.where(x >= 0, x, alpha * jnp.expm1(x))
    if act_type == "gelu":
        return jax.nn.gelu(x, approximate=False)
    if act_type == "rrelu":
        if rng is None:  # inference: mean slope
            return jnp.where(x >= 0, x, (lower_bound + upper_bound) / 2 * x)
        s = jax.random.uniform(rng, x.shape, x.dtype, lower_bound, upper_bound)
        return jnp.where(x >= 0, x, s * x)
    raise ValueError("unknown leaky_relu act_type %r" % act_type)


def activation(x, act_type):
    if act_type == "relu":
        return jax.nn.relu(x)
    if act_type == "sigmoid":
        return jax.nn.sigmoid(x)
    if act_type == "log_sigmoid":
        return jax.nn.log_sigmoid(x)
    if act_type == "tanh":
        return jnp.tanh(x)
    if act_type == "softrelu":
        return jax.nn.softplus(x)
    if act_type == "softsign":
        return jax.nn.soft_sign(x)
    if act_type == "mish":
        return x * jnp.tanh(jax.nn.softplus(x))
    if act_type == "silu" or act_type == "swish":
        return jax.nn.silu(x)
    if act_type == "gelu":
        return jax.nn.gelu(x, approximate=False)
    if act_type == "gelu_tanh":
        return jax.nn.gelu(x, approximate=True)
    raise ValueError("unknown activation %r" % act_type)


def dropout(x, rng, p=0.5, axes=None):
    """Training-mode dropout with inverted scaling (dropout.cc semantics)."""
    if p <= 0.0:
        return x
    shape = list(x.shape)
    if axes:
        for ax in range(len(shape)):
            if ax not in axes:
                shape[ax] = 1
    keep = jax.random.bernoulli(rng, 1.0 - p, tuple(shape))
    return jnp.where(keep, x / (1.0 - p), 0.0).astype(x.dtype)


# ----------------------------------------------------------------------
# embedding / indexing
# ----------------------------------------------------------------------
def embedding(indices, weight, sparse_grad=False):
    return jnp.take(weight, indices.astype(jnp.int32), axis=0)


def one_hot(indices, depth, on_value=1.0, off_value=0.0, dtype="float32"):
    return jax.nn.one_hot(indices.astype(jnp.int32), depth,
                          dtype=jnp.dtype(dtype)) * (on_value - off_value) \
        + off_value


def pick(x, index, axis=-1, keepdims=False, mode="clip"):
    index = index.astype(jnp.int32)
    if mode == "clip":
        index = jnp.clip(index, 0, x.shape[axis] - 1)
    else:
        index = jnp.mod(index, x.shape[axis])
    picked = jnp.take_along_axis(x, jnp.expand_dims(index, axis), axis=axis)
    return picked if keepdims else jnp.squeeze(picked, axis=axis)


def gather_nd(data, indices):
    idx = tuple(indices[i].astype(jnp.int32) for i in range(indices.shape[0]))
    return data[idx]


def sequence_mask(data, length=None, use_sequence_length=False, value=0.0,
                  axis=0):
    if not use_sequence_length or length is None:
        return data
    steps = jnp.arange(data.shape[axis])
    bshape = [1] * data.ndim
    bshape[axis] = data.shape[axis]
    batch_axis = 1 if axis == 0 else 0
    lshape = [1] * data.ndim
    lshape[batch_axis] = data.shape[batch_axis]
    mask = steps.reshape(bshape) < length.reshape(lshape)
    return jnp.where(mask, data, value)


def sequence_last(data, length=None, use_sequence_length=False, axis=0):
    if not use_sequence_length or length is None:
        return jnp.take(data, data.shape[axis] - 1, axis=axis)
    idx = (length - 1).astype(jnp.int32)
    batch_axis = 1 if axis == 0 else 0
    data_bf = jnp.moveaxis(data, axis, 0)  # (T, B, ...)
    if batch_axis != 1 and data.ndim > 1:
        pass
    return jnp.take_along_axis(
        data_bf, idx.reshape((1, -1) + (1,) * (data_bf.ndim - 2)), axis=0
    )[0]


def sequence_reverse(data, length=None, use_sequence_length=False, axis=0):
    if not use_sequence_length or length is None:
        return jnp.flip(data, axis=axis)
    T = data.shape[axis]
    steps = jnp.arange(T)
    data_bf = jnp.moveaxis(data, axis, 0)
    lens = length.astype(jnp.int32).reshape((1, -1) + (1,) * (data_bf.ndim - 2))
    rev_idx = jnp.where(steps.reshape((-1,) + (1,) * (data_bf.ndim - 1)) < lens,
                        lens - 1 - steps.reshape((-1,) + (1,) * (data_bf.ndim - 1)),
                        steps.reshape((-1,) + (1,) * (data_bf.ndim - 1)))
    out = jnp.take_along_axis(data_bf, jnp.broadcast_to(rev_idx, data_bf.shape),
                              axis=0)
    return jnp.moveaxis(out, 0, axis)


# ----------------------------------------------------------------------
# attention (XLA path; Pallas flash kernel in ops/pallas_ops.py)
# ----------------------------------------------------------------------
def dot_product_attention(q, k, v, mask=None, scale=None, causal=False):
    """(B, H, T, D) attention, bf16-friendly, fp32 softmax accumulation."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        Tq, Tk = q.shape[-2], k.shape[-2]
        cmask = jnp.tril(jnp.ones((Tq, Tk), bool), k=Tk - Tq)
        scores = jnp.where(cmask, scores, -jnp.inf)
    if mask is not None:
        scores = jnp.where(mask.astype(bool), scores, -jnp.inf)
    w = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", w, v)


def smooth_l1(x, scalar=1.0):
    s2 = scalar * scalar
    return jnp.where(jnp.abs(x) < 1.0 / s2, 0.5 * s2 * x * x,
                     jnp.abs(x) - 0.5 / s2)
