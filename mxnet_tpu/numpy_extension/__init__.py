"""``mx.npx`` — MXNet extensions to the NumPy namespace.

Reference parity: ``python/mxnet/numpy_extension/`` (npx: softmax, conv,
batch_norm, embedding, pick, topk...) whose ops live in ``src/operator/nn/``
and ``src/operator/numpy_extension/``.  Each function routes the pure-JAX
implementation in ``mxnet_tpu.ops.nn`` through ``apply_op``.
"""
from __future__ import annotations

import builtins as _b

import jax
import jax.numpy as jnp
import numpy as _onp

from ..ndarray.ndarray import NDArray, apply_op
from ..ops import nn as _nn
from .. import _tape
from ..numpy import random as _random

__all__ = [
    "set_np", "reset_np", "is_np_array", "is_np_shape", "use_np", "softmax",
    "log_softmax", "masked_softmax", "masked_log_softmax", "activation",
    "relu", "sigmoid", "leaky_relu", "gelu", "fully_connected", "convolution",
    "deconvolution", "pooling", "batch_norm", "layer_norm", "group_norm",
    "instance_norm", "rms_norm", "l2_normalization", "dropout", "embedding",
    "one_hot", "pick", "topk", "gather_nd", "sequence_mask", "reshape_like",
    "shape_array", "cast", "arange_like", "broadcast_like", "smooth_l1",
    "erf", "erfinv", "gamma", "gammaln", "digamma", "slice", "slice_axis",
    "slice_like", "clip_global_norm", "multi_sum_sq", "flash_attention",
    "chunked_softmax_cross_entropy",
]


# --- np-mode shims (the TPU build is always "numpy semantics") ----------
def set_np(shape=True, array=True, dtype=False):
    return None


def reset_np():
    return None


def is_np_array():
    return True


def is_np_shape():
    return True


def is_np_default_dtype():
    return False


def use_np(func):
    return func


use_np_array = use_np
use_np_shape = use_np


def current_device():
    from ..context import current_context
    return current_context()


def num_gpus():
    from ..context import num_gpus as _n
    return _n()


def waitall():
    from ..ndarray import waitall as _w
    _w()


# --- nn ops -------------------------------------------------------------
def softmax(data, axis=-1, length=None, temperature=None, use_length=False,
            dtype=None):
    if use_length and length is not None:
        return apply_op(
            lambda x, l: _nn.softmax(x, axis=axis, temperature=temperature,
                                     length=l),
            [data, length], name="softmax")
    out = apply_op(lambda x: _nn.softmax(x, axis=axis,
                                         temperature=temperature),
                   [data], name="softmax")
    return out.astype(dtype) if dtype is not None else out


def log_softmax(data, axis=-1, temperature=None, dtype=None):
    out = apply_op(lambda x: _nn.log_softmax(x, axis=axis,
                                             temperature=temperature),
                   [data], name="log_softmax")
    return out.astype(dtype) if dtype is not None else out


def chunked_softmax_cross_entropy(hidden, head, label, chunk=2048):
    """Per-token cross-entropy of ``softmax(hidden @ head.T)`` a chunk of
    tokens at a time (``ops.nn.chunked_softmax_cross_entropy``)."""
    return apply_op(
        lambda h, w, y: _nn.chunked_softmax_cross_entropy(h, w, y, chunk),
        [hidden, head, label], name="chunked_softmax_cross_entropy")


def masked_softmax(data, mask, axis=-1, temperature=1.0):
    return apply_op(lambda x, m: _nn.masked_softmax(x, m, axis, temperature),
                    [data, mask], name="masked_softmax")


def masked_log_softmax(data, mask, axis=-1, temperature=1.0):
    return apply_op(
        lambda x, m: jnp.where(m.astype(bool),
                               jax.nn.log_softmax(
                                   jnp.where(m.astype(bool), x,
                                             jnp.finfo(x.dtype).min),
                                   axis=axis),
                               -jnp.inf),
        [data, mask], name="masked_log_softmax")


def activation(data, act_type="relu"):
    return apply_op(lambda x: _nn.activation(x, act_type), [data],
                    name="activation_" + act_type)


def relu(data):
    return apply_op(jax.nn.relu, [data], name="relu")


def sigmoid(data):
    return apply_op(jax.nn.sigmoid, [data], name="sigmoid")


def leaky_relu(data, gamma=None, act_type="leaky", slope=0.25,
               lower_bound=0.125, upper_bound=0.334):
    if act_type == "prelu" and gamma is not None:
        return apply_op(lambda x, g: _nn.leaky_relu(x, "prelu", gamma=g),
                        [data, gamma], name="prelu")
    if act_type == "rrelu" and _tape.is_training():
        k = _random.new_key()
        return apply_op(lambda x: _nn.leaky_relu(
            x, "rrelu", lower_bound=lower_bound, upper_bound=upper_bound,
            rng=k), [data], name="rrelu")
    return apply_op(lambda x: _nn.leaky_relu(
        x, act_type, slope=slope, lower_bound=lower_bound,
        upper_bound=upper_bound), [data], name=act_type)


def gelu(data, approximate=False):
    return apply_op(lambda x: jax.nn.gelu(x, approximate=approximate),
                    [data], name="gelu")


def fully_connected(x, weight, bias=None, num_hidden=None, no_bias=False,
                    flatten=True):
    if no_bias or bias is None:
        return apply_op(lambda a, w: _nn.fully_connected(a, w, None, flatten),
                        [x, weight], name="fully_connected")
    return apply_op(lambda a, w, b: _nn.fully_connected(a, w, b, flatten),
                    [x, weight, bias], name="fully_connected")


def convolution(data=None, weight=None, bias=None, kernel=None, stride=None,
                dilate=None, pad=None, num_filter=None, num_group=1,
                no_bias=False, layout=None):
    if no_bias or bias is None:
        return apply_op(
            lambda x, w: _nn.convolution(x, w, None, stride, pad, dilate,
                                         num_group, layout),
            [data, weight], name="convolution")
    return apply_op(
        lambda x, w, b: _nn.convolution(x, w, b, stride, pad, dilate,
                                        num_group, layout),
        [data, weight, bias], name="convolution")


def deconvolution(data=None, weight=None, bias=None, kernel=None, stride=None,
                  dilate=None, pad=None, adj=None, num_filter=None,
                  num_group=1, no_bias=False, target_shape=None, layout=None):
    if no_bias or bias is None:
        return apply_op(
            lambda x, w: _nn.deconvolution(x, w, None, stride, pad, dilate,
                                           num_group, adj, target_shape),
            [data, weight], name="deconvolution")
    return apply_op(
        lambda x, w, b: _nn.deconvolution(x, w, b, stride, pad, dilate,
                                          num_group, adj, target_shape),
        [data, weight, bias], name="deconvolution")


def pooling(data, kernel=(1, 1), stride=None, pad=None, pool_type="max",
            global_pool=False, count_include_pad=True, pooling_convention="valid",
            layout=None):
    return apply_op(
        lambda x: _nn.pooling(x, kernel, pool_type, stride, pad, global_pool,
                              count_include_pad, layout),
        [data], name="pooling")


def batch_norm(x, gamma, beta, running_mean, running_var, eps=1e-5,
               momentum=0.9, fix_gamma=False, use_global_stats=False,
               output_mean_var=False, axis=1):
    """Functional BN.  In training mode returns (out, batch_mean, batch_var)
    when output_mean_var; the Gluon layer handles the running-stat update
    (the reference op mutates aux states in-place: batch_norm.cc)."""
    training = _tape.is_training() and not use_global_stats
    if fix_gamma:
        gamma = NDArray(jnp.ones_like(gamma._data))
    if training:
        outs = apply_op(
            lambda a, g, b: _nn.batch_norm_train(a, g, b, eps, axis),
            [x, gamma, beta], n_out=3, name="batch_norm")
        out, mean, var = outs
        if output_mean_var:
            return out, mean, var
        return out
    out = apply_op(
        lambda a, g, b, m, v: _nn.batch_norm_inference(a, g, b, m, v, eps,
                                                       axis),
        [x, gamma, beta, running_mean, running_var], name="batch_norm")
    if output_mean_var:
        return out, running_mean, running_var
    return out


def layer_norm(data, gamma, beta, axis=-1, eps=1e-5):
    return apply_op(lambda x, g, b: _nn.layer_norm(x, g, b, axis, eps),
                    [data, gamma, beta], name="layer_norm")


def group_norm(data, gamma, beta, num_groups=1, eps=1e-5):
    return apply_op(lambda x, g, b: _nn.group_norm(x, g, b, num_groups, eps),
                    [data, gamma, beta], name="group_norm")


def instance_norm(data, gamma, beta, eps=1e-5):
    return apply_op(lambda x, g, b: _nn.instance_norm(x, g, b, eps),
                    [data, gamma, beta], name="instance_norm")


def rms_norm(data, gamma, axis=-1, eps=1e-6, unit_offset=False,
             out_dtype=None):
    return apply_op(lambda x, g: _nn.rms_norm(x, g, axis, eps, unit_offset,
                                              out_dtype),
                    [data, gamma], name="rms_norm")


def l2_normalization(data, eps=1e-10, mode="instance"):
    return apply_op(lambda x: _nn.l2_normalization(x, eps, mode), [data],
                    name="l2_normalization")


def dropout(data, p=0.5, axes=None, mode="training"):
    if not _tape.is_training() and mode != "always":
        return data
    k = _random.new_key()
    return apply_op(lambda x: _nn.dropout(x, k, p, axes), [data],
                    name="dropout")


def embedding(data, weight, input_dim=None, output_dim=None, dtype=None,
              sparse_grad=False):
    return apply_op(lambda i, w: _nn.embedding(i, w), [data, weight],
                    name="embedding")


def one_hot(data, depth, on_value=1.0, off_value=0.0, dtype="float32"):
    return apply_op(lambda i: _nn.one_hot(i, depth, on_value, off_value,
                                          dtype), [data], name="one_hot")


def pick(data, index, axis=-1, keepdims=False, mode="clip"):
    return apply_op(lambda x, i: _nn.pick(x, i, axis, keepdims, mode),
                    [data, index], name="pick")


def topk(data, axis=-1, k=1, ret_typ="indices", is_ascend=False, dtype="float32"):
    def g(x):
        xm = jnp.moveaxis(x, axis, -1)
        vals, idx = jax.lax.top_k(-xm if is_ascend else xm, k)
        if is_ascend:
            vals = -vals
        vals = jnp.moveaxis(vals, -1, axis)
        idx = jnp.moveaxis(idx, -1, axis)
        if ret_typ == "value":
            return vals
        if ret_typ == "indices":
            return idx.astype(jnp.dtype(dtype))
        if ret_typ == "both":
            return vals, idx.astype(jnp.dtype(dtype))
        if ret_typ == "mask":
            m = jnp.zeros(xm.shape, jnp.int32)
            m = jnp.put_along_axis(m, idx, 1, axis=-1, inplace=False)
            return jnp.moveaxis(m, -1, axis)
        raise ValueError(ret_typ)
    if ret_typ == "both":
        return list(apply_op(lambda x: tuple(g(x)), [data], n_out=2,
                             name="topk"))
    return apply_op(g, [data], name="topk")


def gather_nd(data, indices):
    return apply_op(lambda d, i: _nn.gather_nd(d, i), [data, indices],
                    name="gather_nd")


def sequence_mask(data, sequence_length=None, use_sequence_length=False,
                  value=0.0, axis=0):
    if sequence_length is None:
        return apply_op(lambda x: _nn.sequence_mask(x, None, False, value,
                                                    axis),
                        [data], name="sequence_mask")
    return apply_op(
        lambda x, l: _nn.sequence_mask(x, l, use_sequence_length, value, axis),
        [data, sequence_length], name="sequence_mask")


def reshape_like(lhs, rhs):
    shp = rhs.shape
    return apply_op(lambda x: jnp.reshape(x, shp), [lhs], name="reshape_like")


def shape_array(data):
    return NDArray(jnp.asarray(data.shape, dtype=jnp.int64))


def cast(data, dtype):
    return data.astype(dtype)


def arange_like(data, start=0.0, step=1.0, repeat=1, axis=None):
    if axis is None:
        n = data.size
    else:
        n = data.shape[axis]
    a = jnp.arange(start, start + step * n, step, dtype="float32")[:n]
    if axis is None:
        a = a.reshape(data.shape)
    return NDArray(a)


def broadcast_like(lhs, rhs, lhs_axes=None, rhs_axes=None):
    shp = rhs.shape
    return apply_op(lambda x: jnp.broadcast_to(x, shp), [lhs],
                    name="broadcast_like")


def smooth_l1(data, scalar=1.0):
    return apply_op(lambda x: _nn.smooth_l1(x, scalar), [data],
                    name="smooth_l1")


# special functions
def erf(data):
    return apply_op(jax.scipy.special.erf, [data], name="erf")


def erfinv(data):
    return apply_op(jax.scipy.special.erfinv, [data], name="erfinv")


def gamma(data):
    return apply_op(lambda x: jnp.exp(jax.scipy.special.gammaln(x)), [data],
                    name="gamma")


def gammaln(data):
    return apply_op(jax.scipy.special.gammaln, [data], name="gammaln")


def digamma(data):
    return apply_op(jax.scipy.special.digamma, [data], name="digamma")


# slicing (legacy npx.slice family)
def slice(data, begin, end, step=None):  # noqa: A001
    nd = data.ndim
    begin = tuple(begin) + (None,) * (nd - len(begin))
    end = tuple(end) + (None,) * (nd - len(end))
    step = tuple(step) + (None,) * (nd - len(step)) if step else (None,) * nd
    key = tuple(_builtins_slice(b, e, s) for b, e, s in zip(begin, end, step))
    return apply_op(lambda x: x[key], [data], name="slice")


_builtins_slice = _b.slice


def slice_axis(data, axis, begin, end):
    key = [_builtins_slice(None)] * data.ndim
    key[axis] = _builtins_slice(begin, end)
    key = tuple(key)
    return apply_op(lambda x: x[key], [data], name="slice_axis")


def slice_like(data, shape_like, axes=None):
    shp = list(data.shape)
    like = shape_like.shape
    ax = axes if axes is not None else range(min(len(shp), len(like)))
    key = [_builtins_slice(None)] * data.ndim
    for a in ax:
        key[a] = _builtins_slice(0, like[a])
    key = tuple(key)
    return apply_op(lambda x: x[key], [data], name="slice_like")


def multi_sum_sq(*arrays, num_arrays=None):
    if len(arrays) == 1 and isinstance(arrays[0], (list, tuple)):
        arrays = tuple(arrays[0])
    return apply_op(lambda *xs: tuple(jnp.sum(jnp.square(x)) for x in xs),
                    list(arrays), n_out=len(arrays), name="multi_sum_sq")


def clip_global_norm(arrays, max_norm, check_isfinite=True):
    """Gluon utils parity (gluon/utils.py clip_global_norm)."""
    total = jnp.sqrt(_builtins_sum(
        jnp.sum(jnp.square(a._data.astype(jnp.float32))) for a in arrays))
    scale = jnp.minimum(1.0, max_norm / (total + 1e-12))
    for a in arrays:
        a._set_data((a._data.astype(jnp.float32) * scale).astype(a.dtype))
    return float(total)


_builtins_sum = _b.sum


def flash_attention(query, key, value, causal=False, scale=None,
                    block_q=None, block_k=None):
    """Fused online-softmax attention over ``(B, H, S, D)`` tensors.

    On TPU with 128-aligned sequence and D in {64, 128, 256} this runs
    the Pallas flash kernels (fwd + dq + dkv, GQA-native: kv may carry
    fewer heads than query, mapped as ``h -> h // (Hq // Hkv)`` without
    materializing repeated K/V); elsewhere it transparently computes the
    same values with dense XLA attention.  Differentiable under
    ``autograd.record()`` either way.  Under ``parallel.mesh_scope`` the
    kernels run per ``dp`` shard of the batch and ``tp`` shard of the
    heads (``parallel.kernel_shard``).

    The TPU-native successor to the reference's fused attention matmuls
    (``src/operator/contrib/transformer.cc``,
    ``_contrib_interleaved_matmul_selfatt_*`` — also provided under
    their legacy names in this namespace).
    """
    from ..ops.pallas_ops import flash_attention as _fa
    from ..parallel.sharding import kernel_shard
    return apply_op(
        lambda q, k, v: _fa(q, k, v, causal=causal, scale=scale,
                            block_q=block_q, block_k=block_k,
                            shard=kernel_shard(q.shape[0], k.shape[1])),
        [query, key, value], name="flash_attention")


# checkpoint IO (npx.save/savez/load) implemented in utils.serialization
from .control_flow import cond, foreach, while_loop  # noqa: E402
from .contrib import (roi_align, roi_pooling, box_iou, box_nms,  # noqa: E402
                      interleaved_matmul_selfatt_qk,
                      interleaved_matmul_selfatt_valatt,
                      interleaved_matmul_encdec_qk,
                      interleaved_matmul_encdec_valatt)


def save(file, arr):
    from ..utils import serialization
    serialization.save(file, arr)


def savez(file, *args, **kwargs):
    from ..utils import serialization
    serialization.savez(file, *args, **kwargs)


def load(file):
    from ..utils import serialization
    return serialization.load(file)


# ----------------------------------------------------------------------
# round-2 op tail (VERDICT probes)
# ----------------------------------------------------------------------

def batch_dot(lhs, rhs, transpose_a=False, transpose_b=False,
              forward_stype=None):
    """Batched matmul (reference ``_npx_batch_dot``,
    src/operator/tensor/dot.cc)."""
    def g(a, b):
        if transpose_a:
            a = jnp.swapaxes(a, -1, -2)
        if transpose_b:
            b = jnp.swapaxes(b, -1, -2)
        return jnp.matmul(a, b)
    return apply_op(g, [lhs, rhs], name="batch_dot")


def scatter_nd(data, indices, shape):
    """Scatter ``data`` into zeros of ``shape`` at ``indices`` (reference
    ``scatter_nd``, src/operator/tensor/indexing_op.cc:874; indices is
    (M, N): M leading output dims, N updates)."""
    def g(d, idx):
        idx = idx.astype(jnp.int32)
        return jnp.zeros(shape, d.dtype).at[tuple(idx)].set(d)
    return apply_op(g, [data, indices], name="scatter_nd")


def rnn(data=None, parameters=None, state=None, state_cell=None, mode="lstm",
        state_size=None, num_layers=1, bidirectional=False, p=0.0,
        state_outputs=False, projection_size=None, **kwargs):
    """Fused multi-layer RNN on packed parameters (reference ``_npx_rnn``,
    src/operator/rnn.cc) — same packed layout as ``mx.nd.RNN``."""
    if projection_size is not None:
        raise NotImplementedError(
            "npx.rnn: projection_size (LSTMP) is not supported; the packed "
            "parameter layout differs — use gluon.rnn cells instead")
    from ..ndarray.legacy_ops import RNN as _RNN
    return _RNN(data, parameters, state, state_cell=state_cell, mode=mode,
                state_size=state_size, num_layers=num_layers,
                bidirectional=bidirectional, p=p,
                state_outputs=state_outputs, **kwargs)


def seed(seed_state, ctx="all"):
    """Seed the device RNG streams (reference npx.seed)."""
    _random.seed(seed_state, ctx)


def bernoulli(prob=None, logit=None, size=None, dtype=None, ctx=None,
              device=None, out=None):
    """Bernoulli sampling from prob or logit (reference
    ``_npx_bernoulli``, python/mxnet/ndarray/numpy_extension/random.py:26)."""
    if (prob is None) == (logit is None):
        raise ValueError("pass exactly one of prob or logit")
    base = prob if prob is not None else logit
    bj = base._data if isinstance(base, NDArray) else jnp.asarray(base)
    shape = tuple(size) if isinstance(size, (list, tuple)) else \
        ((size,) if size is not None else bj.shape)
    k = _random.new_key()
    p = jax.nn.sigmoid(bj) if logit is not None else bj
    r = jax.random.bernoulli(k, p, shape if shape else None)
    return NDArray(r.astype(dtype or "float32"))


def _sample_n(sampler, name):
    def f(a=0.0, b=1.0, batch_shape=None, dtype=None, ctx=None, device=None):
        aj = a._data if isinstance(a, NDArray) else jnp.asarray(a, jnp.float32)
        bj = b._data if isinstance(b, NDArray) else jnp.asarray(b, jnp.float32)
        event = jnp.broadcast_shapes(aj.shape, bj.shape)
        bshape = tuple(batch_shape) if batch_shape is not None else ()
        k = _random.new_key()
        r = sampler(k, bshape + event, aj, bj)
        return NDArray(r.astype(dtype or "float32"))
    f.__name__ = name
    f.__doc__ = ("npx.%s — batch_shape-prefixed sampling (reference "
                 "ndarray/numpy_extension/random.py)" % name)
    return f


uniform_n = _sample_n(
    lambda k, s, lo, hi: jax.random.uniform(k, s) * (hi - lo) + lo,
    "uniform_n")
normal_n = _sample_n(
    lambda k, s, loc, sc: jax.random.normal(k, s) * sc + loc, "normal_n")


def multibox_prior(data, sizes=(1.0,), ratios=(1.0,), clip=False,
                   steps=(-1.0, -1.0), offsets=(0.5, 0.5)):
    """Generate SSD prior (anchor) boxes from a (B, C, H, W) feature map
    (reference ``_npx_multibox_prior``,
    src/operator/contrib/multibox_prior.cc:30 MultiBoxPriorForward)."""
    sizes = tuple(float(s) for s in (sizes if isinstance(sizes, (list, tuple))
                                     else (sizes,)))
    ratios = tuple(float(r) for r in (ratios if isinstance(
        ratios, (list, tuple)) else (ratios,)))

    def g(x):
        in_h, in_w = x.shape[-2], x.shape[-1]
        step_y = steps[0] if steps[0] > 0 else 1.0 / in_h
        step_x = steps[1] if steps[1] > 0 else 1.0 / in_w
        cy = (jnp.arange(in_h, dtype=jnp.float32) + offsets[0]) * step_y
        cx = (jnp.arange(in_w, dtype=jnp.float32) + offsets[1]) * step_x
        # anchor (w/2, h/2) list: all sizes at ratios[0], then sizes[0] at
        # each remaining ratio (multibox_prior.cc:47-70)
        r0 = float(ratios[0]) ** 0.5 if ratios else 1.0
        whs = [(s * in_h / in_w * r0 / 2.0, s / r0 / 2.0) for s in sizes]
        whs += [(sizes[0] * in_h / in_w * (r ** 0.5) / 2.0,
                 sizes[0] / (r ** 0.5) / 2.0) for r in ratios[1:]]
        wh = jnp.asarray(whs, jnp.float32)  # (A, 2)
        cxg, cyg = jnp.meshgrid(cx, cy)     # (H, W)
        centers = jnp.stack([cxg, cyg], -1)[:, :, None, :]  # (H, W, 1, 2)
        half = wh[None, None, :, :]                          # (1, 1, A, 2)
        mins = centers - half
        maxs = centers + half
        boxes = jnp.concatenate([mins, maxs], -1)  # (H, W, A, 4)
        if clip:
            boxes = jnp.clip(boxes, 0.0, 1.0)
        return boxes.reshape(1, -1, 4)
    return apply_op(g, [data], name="multibox_prior")


def _iou_matrix(anchors, gts):
    """IoU between (A, 4) anchors and (G, 4) gt corner boxes."""
    ix1 = _onp.maximum(anchors[:, None, 0], gts[None, :, 0])
    iy1 = _onp.maximum(anchors[:, None, 1], gts[None, :, 1])
    ix2 = _onp.minimum(anchors[:, None, 2], gts[None, :, 2])
    iy2 = _onp.minimum(anchors[:, None, 3], gts[None, :, 3])
    inter = _onp.maximum(0, ix2 - ix1) * _onp.maximum(0, iy2 - iy1)
    area_a = (anchors[:, 2] - anchors[:, 0]) * (anchors[:, 3] - anchors[:, 1])
    area_g = (gts[:, 2] - gts[:, 0]) * (gts[:, 3] - gts[:, 1])
    union = area_a[:, None] + area_g[None, :] - inter
    return _onp.where(union <= 0, 0.0, inter / _onp.maximum(union, 1e-12))


def multibox_target(anchor, label, cls_pred, overlap_threshold=0.5,
                    ignore_label=-1, negative_mining_ratio=-1,
                    negative_mining_thresh=0.5, minimum_negative_samples=0,
                    variances=(0.1, 0.1, 0.2, 0.2)):
    """SSD training target assignment (reference ``_npx_multibox_target``,
    src/operator/contrib/multibox_target.cc:72 MultiBoxTargetForward):
    greedy bipartite matching then overlap-threshold matching; returns
    (loc_target (B, A*4), loc_mask (B, A*4), cls_target (B, A)).

    Host (eager) op — sequential matching, data-pipeline scale.
    """
    anchors = anchor.asnumpy().reshape(-1, 4)
    labels = label.asnumpy()
    cls_preds = cls_pred.asnumpy()
    B = labels.shape[0]
    A = anchors.shape[0]
    vx, vy, vw, vh = variances
    loc_t = _onp.zeros((B, A * 4), "float32")
    loc_m = _onp.zeros((B, A * 4), "float32")
    cls_t = _onp.zeros((B, A), "float32")
    for n in range(B):
        lab = labels[n]
        valid = []
        for row in lab:
            if row[0] == -1:
                break
            valid.append(row)
        if not valid:
            continue
        gts = _onp.asarray(valid, "float32")
        overlaps = _iou_matrix(anchors, gts[:, 1:5])
        matches = _onp.full(A, -1, _onp.int64)
        anchor_state = _onp.full(A, -1, _onp.int64)  # -1 ignore, 0 neg, 1 pos
        # greedy bipartite: repeatedly take global argmax
        ov = overlaps.copy()
        for _ in range(len(gts)):
            j, k = _onp.unravel_index(_onp.argmax(ov), ov.shape)
            if ov[j, k] < 1e-6:
                break
            matches[j] = k
            anchor_state[j] = 1
            ov[j, :] = -1
            ov[:, k] = -1
        # threshold matching for the rest
        if overlap_threshold > 0:
            for j in range(A):
                if anchor_state[j] == 1:
                    continue
                k = int(_onp.argmax(overlaps[j]))
                if overlaps[j, k] >= overlap_threshold:
                    matches[j] = k
                    anchor_state[j] = 1
                else:
                    anchor_state[j] = 0
        else:
            anchor_state[anchor_state != 1] = 0
        # negative mining (multibox_target.cc: negatives are drawn only
        # from anchors whose best IoU < negative_mining_thresh; the rest
        # of the unmatched anchors are ignored)
        if negative_mining_ratio > 0:
            maxiou = overlaps.max(axis=1)
            unmatched = anchor_state == 0
            eligible = _onp.where(unmatched &
                                  (maxiou < negative_mining_thresh))[0]
            anchor_state[unmatched] = -1
            num_pos = int((anchor_state == 1).sum())
            max_neg = max(int(negative_mining_ratio * num_pos),
                          int(minimum_negative_samples))
            if len(eligible):
                # hardness: low background prob (cls_preds: (B, C+1, A))
                bg = cls_preds[n, 0, eligible]
                order = _onp.argsort(bg)
                anchor_state[eligible[order[:max_neg]]] = 0
        for j in range(A):
            if anchor_state[j] == 1:
                k = matches[j]
                cls_t[n, j] = gts[k, 0] + 1
                al, at_, ar, ab = anchors[j]
                gl, gt_, gr, gb = gts[k, 1:5]
                aw, ah = ar - al, ab - at_
                ax, ay = (al + ar) / 2, (at_ + ab) / 2
                gw, gh = gr - gl, gb - gt_
                gx, gy = (gl + gr) / 2, (gt_ + gb) / 2
                loc_t[n, j * 4:(j + 1) * 4] = [
                    (gx - ax) / aw / vx, (gy - ay) / ah / vy,
                    _onp.log(gw / aw) / vw, _onp.log(gh / ah) / vh]
                loc_m[n, j * 4:(j + 1) * 4] = 1.0
            elif anchor_state[j] == -1:
                cls_t[n, j] = ignore_label
    return (NDArray(jnp.asarray(loc_t)), NDArray(jnp.asarray(loc_m)),
            NDArray(jnp.asarray(cls_t)))


def multibox_detection(cls_prob, loc_pred, anchor, clip=True,
                       threshold=0.01, background_id=0,
                       nms_threshold=0.5, force_suppress=False,
                       variances=(0.1, 0.1, 0.2, 0.2), nms_topk=-1):
    """SSD detection decode + NMS (reference ``_npx_multibox_detection``,
    src/operator/contrib/multibox_detection.cc:82
    MultiBoxDetectionForward).  Returns (B, A, 6) rows
    [class_id, score, xmin, ymin, xmax, ymax], suppressed rows -1.

    Host (eager) op — sequential NMS, inference post-processing scale.
    """
    probs = cls_prob.asnumpy()     # (B, C, A)
    locs = loc_pred.asnumpy()      # (B, A*4)
    anchors = anchor.asnumpy().reshape(-1, 4)
    B, C, A = probs.shape
    vx, vy, vw, vh = variances
    out = _onp.full((B, A, 6), -1.0, "float32")
    aw = anchors[:, 2] - anchors[:, 0]
    ah = anchors[:, 3] - anchors[:, 1]
    ax = (anchors[:, 0] + anchors[:, 2]) / 2
    ay = (anchors[:, 1] + anchors[:, 3]) / 2
    fg_rows = [c for c in range(C) if c != background_id]
    for n in range(B):
        scores = probs[n, fg_rows, :]      # skip the background row
        rows = _onp.asarray(fg_rows)[scores.argmax(axis=0)]
        # 0-based foreground class id: original row with the background
        # row's slot removed (reference convention: id - 1 when bg is 0)
        ids = _onp.where(rows > background_id, rows - 1, rows)
        conf = scores.max(axis=0)
        keep = conf >= threshold
        lp = locs[n].reshape(A, 4)
        ox = lp[:, 0] * vx * aw + ax
        oy = lp[:, 1] * vy * ah + ay
        ow = _onp.exp(lp[:, 2] * vw) * aw / 2
        oh = _onp.exp(lp[:, 3] * vh) * ah / 2
        boxes = _onp.stack([ox - ow, oy - oh, ox + ow, oy + oh], -1)
        if clip:
            boxes = _onp.clip(boxes, 0.0, 1.0)
        valid = _onp.where(keep)[0]
        order = valid[_onp.argsort(-conf[valid])]
        if nms_topk > 0:
            order = order[:nms_topk]
        kept = []
        for i in order:
            ok = True
            for j in kept:
                if force_suppress or ids[i] == ids[j]:
                    if _iou_matrix(boxes[i:i + 1], boxes[j:j + 1])[0, 0] \
                            > nms_threshold:
                        ok = False
                        break
            if ok:
                kept.append(i)
        for slot, i in enumerate(kept):
            out[n, slot] = [ids[i], conf[i], *boxes[i]]
    return NDArray(jnp.asarray(out))


def custom(*inputs, op_type=None, **kwargs):
    """Invoke an op registered by a loaded extension (reference
    ``mx.nd.Custom(..., op_type=...)`` over ``src/operator/custom/custom.cc``
    and lib_api.h REGISTER_OP; here ops come from ``mx.library.load``)."""
    if op_type is None:
        raise ValueError("custom requires op_type=")
    from .. import library
    return library.custom(op_type, *inputs, **kwargs)


__all__.append("custom")


def ctc_loss(data, label, data_lengths=None, label_lengths=None,
             use_data_lengths=False, use_label_lengths=False,
             blank_label="first"):
    """CTC loss (reference ``src/operator/nn/ctc_loss.cc:51``,
    ``_npx_ctc_loss`` alias).  data: (T, B, C) unnormalized activations;
    label: (B, L); returns (B,) losses."""
    from ..ops.ctc import ctc_loss as _ctc
    if blank_label not in ("first", "last"):
        raise ValueError("blank_label must be 'first' or 'last'")
    ins = [data, label]
    if use_data_lengths:
        ins.append(data_lengths)
    if use_label_lengths:
        ins.append(label_lengths)

    def g(d, l, *rest):
        it = iter(rest)
        dl = next(it) if use_data_lengths else None
        ll = next(it) if use_label_lengths else None
        d = jnp.transpose(d, (1, 0, 2))  # (B, T, C)
        if blank_label == "last":
            # move the blank channel to 0 and shift labels to 1-based;
            # padding (-1) maps to 0, which _ctc's default length
            # derivation already treats as padding
            d = jnp.concatenate([d[..., -1:], d[..., :-1]], axis=-1)
            l = jnp.maximum(jnp.where(l < 0, -1, l + 1), 0)
        return _ctc(d, l, dl, ll)

    return apply_op(g, ins, name="ctc_loss")


def im2col(data, kernel, stride=None, dilate=None, pad=None):
    """Sliding blocks (reference ``src/operator/nn/im2col.cc:84``)."""
    from ..ops import sliding as _sl
    return apply_op(lambda x: _sl.im2col(x, kernel, stride, dilate, pad),
                    [data], name="im2col")


def col2im(data, output_size, kernel, stride=None, dilate=None, pad=None):
    """Adjoint of im2col (reference ``src/operator/nn/im2col.cc:168``)."""
    from ..ops import sliding as _sl
    return apply_op(
        lambda x: _sl.col2im(x, output_size, kernel, stride, dilate, pad),
        [data], name="col2im")


def deformable_convolution(data=None, offset=None, weight=None, bias=None,
                           kernel=None, stride=None, pad=None, dilate=None,
                           num_filter=None, num_group=1,
                           num_deformable_group=1, no_bias=False,
                           layout=None):
    """Deformable convolution v1 (reference
    ``src/operator/deformable_convolution.cc``)."""
    from ..ops import sliding as _sl
    ins = [data, offset, weight]
    if not (no_bias or bias is None):
        ins.append(bias)

    def g(x, off, w, *b):
        return _sl.deformable_convolution(
            x, off, w, b[0] if b else None, kernel=tuple(kernel),
            stride=stride, pad=pad, dilate=dilate,
            num_deformable_group=num_deformable_group, num_group=num_group)

    return apply_op(g, ins, name="deformable_convolution")


__all__ += ["ctc_loss", "im2col", "col2im", "deformable_convolution"]


def index_add(A, ind, val):
    """A with val scatter-added at coordinate columns ``ind``
    (reference ``src/operator/contrib/index_add.cc``, ``_npx_index_add``):
    ind is (K, N) — K index dims, N sites."""
    def g(a, i, v):
        i = i.astype(jnp.int32)
        coords = tuple(i[k] for k in range(i.shape[0]))
        return a.at[coords].add(v)
    return apply_op(g, [A, ind, val], name="index_add")


def index_update(A, ind, val):
    """A with val scattered (overwrite) at coordinate columns ``ind``
    (``_npx_index_update``)."""
    def g(a, i, v):
        i = i.astype(jnp.int32)
        coords = tuple(i[k] for k in range(i.shape[0]))
        return a.at[coords].set(v)
    return apply_op(g, [A, ind, val], name="index_update")


def constraint_check(data, msg="Constraint violated!"):
    """Raise if any element is falsy; returns the validated input cast to
    bool-ish 1.0 (reference ``_npx_constraint_check``,
    ``src/operator/numpy/np_constraint_check.cc``).  Synchronous check
    (DELTAS.md #10: dispatch errors raise early here)."""
    import numpy as _onp
    arr = data.asnumpy() if hasattr(data, "asnumpy") else _onp.asarray(data)
    if not bool(arr.all()):
        raise ValueError(msg)
    return apply_op(lambda x: jnp.ones((), jnp.bool_), [data],
                    name="constraint_check")


__all__ += ["index_add", "index_update", "constraint_check"]


def sldwin_atten_score(query, key, dilation, w=1, symmetric=True):
    """Longformer sliding-window attention score (reference registers the
    ``_npx_sldwin_atten_score`` alias, ``contrib/transformer.cc:906``)."""
    from ..ndarray import contrib as _ndc
    return _ndc.sldwin_atten_score(query, key, dilation, w, symmetric)


def sldwin_atten_context(score, value, dilation, w=1, symmetric=True):
    from ..ndarray import contrib as _ndc
    return _ndc.sldwin_atten_context(score, value, dilation, w, symmetric)


def sldwin_atten_mask_like(score, dilation, valid_length, w=1,
                           symmetric=True):
    from ..ndarray import contrib as _ndc
    return _ndc.sldwin_atten_mask_like(score, dilation, valid_length, w,
                                       symmetric)


__all__ += ["sldwin_atten_score", "sldwin_atten_context",
            "sldwin_atten_mask_like"]
