"""Basic Gluon layers.

Reference parity: ``python/mxnet/gluon/nn/basic_layers.py`` (Dense, Dropout,
BatchNorm, Embedding, Flatten, LayerNorm, GroupNorm, InstanceNorm, Lambda,
Sequential...).  Every layer is a HybridBlock whose forward routes through
``mx.npx`` functional ops, so eager and hybridized execution share one path.
"""
from __future__ import annotations

import jax.numpy as jnp

from ... import initializer as init_mod
from ... import numpy_extension as npx
from ... import _tape
from ...ndarray.ndarray import NDArray
from ..block import Block, HybridBlock
from ..parameter import Parameter


class Sequential(Block):
    """Stack of blocks executed sequentially (basic_layers.py Sequential)."""

    def __init__(self, *blocks):
        super().__init__()
        for b in blocks:
            self.add(b)

    def add(self, *blocks):
        for block in blocks:
            self.register_child(block)

    def forward(self, x, *args):
        for block in self._children.values():
            x = block(x, *args)
            args = []
            if isinstance(x, (tuple, list)):
                args = x[1:]
                x = x[0]
        if args:
            return (x,) + tuple(args)
        return x

    def __getitem__(self, key):
        children = list(self._children.values())
        if isinstance(key, slice):
            net = type(self)()
            net.add(*children[key])
            return net
        return children[key]

    def __len__(self):
        return len(self._children)

    def __iter__(self):
        return iter(self._children.values())


class HybridSequential(HybridBlock):
    def __init__(self, *blocks):
        super().__init__()
        for b in blocks:
            self.add(b)

    def add(self, *blocks):
        for block in blocks:
            self.register_child(block)

    def forward(self, x, *args):
        for block in self._children.values():
            x = block(x, *args)
            args = []
            if isinstance(x, (tuple, list)):
                args = x[1:]
                x = x[0]
        if args:
            return (x,) + tuple(args)
        return x

    def __getitem__(self, key):
        children = list(self._children.values())
        if isinstance(key, slice):
            net = type(self)()
            net.add(*children[key])
            return net
        return children[key]

    def __len__(self):
        return len(self._children)

    def __iter__(self):
        return iter(self._children.values())


class Dense(HybridBlock):
    """Fully-connected layer: y = act(x W^T + b).

    Reference: basic_layers.py Dense over FullyConnected
    (src/operator/nn/fully_connected.cc:251).  ``flatten=True`` collapses
    trailing dims like the reference default.
    """

    def __init__(self, units, activation=None, use_bias=True, flatten=True,
                 dtype="float32", weight_initializer=None,
                 bias_initializer="zeros", in_units=0):
        super().__init__()
        self._units = units
        self._flatten = flatten
        self._activation = activation
        self.weight = Parameter(shape=(units, in_units), dtype=dtype,
                                init=weight_initializer,
                                allow_deferred_init=True, name="weight")
        self.bias = Parameter(shape=(units,), dtype=dtype,
                              init=bias_initializer,
                              allow_deferred_init=True, name="bias") \
            if use_bias else None

    def forward(self, x):
        if self.weight._data is None:
            in_units = 1
            if self._flatten:
                for d in x.shape[1:]:
                    in_units *= d
            else:
                in_units = x.shape[-1]
            self.weight._finish_deferred_init((self._units, in_units))
            if self.bias is not None:
                self.bias._finish_deferred_init((self._units,))
        out = npx.fully_connected(x, self.weight.data(),
                                  self.bias.data() if self.bias is not None
                                  else None,
                                  num_hidden=self._units,
                                  no_bias=self.bias is None,
                                  flatten=self._flatten)
        if self._activation is not None:
            out = npx.activation(out, self._activation)
        return out

    def __repr__(self):
        return "Dense(%s -> %d, %s)" % (
            self.weight.shape[1] if self.weight.shape else None,
            self._units, self._activation or "linear")


class Dropout(HybridBlock):
    def __init__(self, rate, axes=()):
        super().__init__()
        self._rate = rate
        self._axes = axes

    def forward(self, x):
        if self._rate == 0 or not _tape.is_training():
            return x
        return npx.dropout(x, p=self._rate, axes=self._axes)

    def __repr__(self):
        return "Dropout(p = %s, axes=%s)" % (self._rate, self._axes)


class Embedding(HybridBlock):
    def __init__(self, input_dim, output_dim, dtype="float32",
                 weight_initializer=None, sparse_grad=False):
        super().__init__()
        self._input_dim = input_dim
        self._output_dim = output_dim
        self.weight = Parameter(shape=(input_dim, output_dim), dtype=dtype,
                                init=weight_initializer, name="weight",
                                grad_stype="row_sparse" if sparse_grad
                                else "default")

    def forward(self, x):
        return npx.embedding(x, self.weight.data(), self._input_dim,
                             self._output_dim)

    def __repr__(self):
        return "Embedding(%d -> %d)" % (self._input_dim, self._output_dim)


class Flatten(HybridBlock):
    def forward(self, x):
        return x.flatten()

    def __repr__(self):
        return "Flatten"


class Identity(HybridBlock):
    def forward(self, x):
        return x


class _NormBase(HybridBlock):
    pass


class BatchNorm(_NormBase):
    """Batch normalization with running-stat aux state.

    Reference: basic_layers.py BatchNorm over src/operator/nn/batch_norm.cc.
    The running stats update is a functional handle-swap; under hybridize it
    becomes an extra traced output written back each step (see block.py
    _CachedGraph).
    """

    def __init__(self, axis=1, momentum=0.9, epsilon=1e-5, center=True,
                 scale=True, use_global_stats=False, beta_initializer="zeros",
                 gamma_initializer="ones",
                 running_mean_initializer="zeros",
                 running_variance_initializer="ones", in_channels=0,
                 **kwargs):
        super().__init__()
        self._axis = axis
        self._momentum = momentum
        self._epsilon = epsilon
        self._center = center
        self._scale = scale
        self._use_global_stats = use_global_stats
        self.gamma = Parameter(shape=(in_channels,), init=gamma_initializer,
                               allow_deferred_init=True, name="gamma",
                               differentiable=scale)
        self.beta = Parameter(shape=(in_channels,), init=beta_initializer,
                              allow_deferred_init=True, name="beta",
                              differentiable=center)
        self.running_mean = Parameter(shape=(in_channels,),
                                      init=running_mean_initializer,
                                      allow_deferred_init=True,
                                      name="running_mean",
                                      differentiable=False)
        self.running_var = Parameter(shape=(in_channels,),
                                     init=running_variance_initializer,
                                     allow_deferred_init=True,
                                     name="running_var",
                                     differentiable=False)

    def forward(self, x):
        ch = x.shape[self._axis]
        for p in (self.gamma, self.beta, self.running_mean, self.running_var):
            if p._data is None:
                p._finish_deferred_init((ch,))
        training = _tape.is_training() and not self._use_global_stats
        if training:
            out, mean, var = npx.batch_norm(
                x, self.gamma.data(), self.beta.data(),
                self.running_mean.data(), self.running_var.data(),
                eps=self._epsilon, momentum=self._momentum,
                fix_gamma=not self._scale, output_mean_var=True,
                axis=self._axis)
            m = self._momentum
            rm, rv = self.running_mean.data(), self.running_var.data()
            rm._data = m * rm._data + (1 - m) * mean._data
            rv._data = m * rv._data + (1 - m) * var._data
            return out
        return npx.batch_norm(
            x, self.gamma.data(), self.beta.data(),
            self.running_mean.data(), self.running_var.data(),
            eps=self._epsilon, momentum=self._momentum,
            fix_gamma=not self._scale, use_global_stats=True,
            axis=self._axis)

    def __repr__(self):
        return "BatchNorm(axis=%d, eps=%s, momentum=%s)" % (
            self._axis, self._epsilon, self._momentum)


class SyncBatchNorm(BatchNorm):
    """Cross-device synchronized BN (reference contrib SyncBatchNorm).

    On a sharded mesh the batch axis is global: XLA computes the reduction
    over the full sharded batch automatically under pjit, so the plain BN
    math *is* synchronized.  For explicit multi-process use the stats are
    psum-ed via mxnet_tpu.parallel collectives.
    """

    def __init__(self, in_channels=0, num_devices=None, **kwargs):
        super().__init__(in_channels=in_channels, **kwargs)


class LayerNorm(HybridBlock):
    def __init__(self, axis=-1, epsilon=1e-5, center=True, scale=True,
                 beta_initializer="zeros", gamma_initializer="ones",
                 in_channels=0):
        super().__init__()
        self._axis = axis
        self._epsilon = epsilon
        self.gamma = Parameter(shape=(in_channels,), init=gamma_initializer,
                               allow_deferred_init=True, name="gamma",
                               differentiable=scale)
        self.beta = Parameter(shape=(in_channels,), init=beta_initializer,
                              allow_deferred_init=True, name="beta",
                              differentiable=center)

    def forward(self, x):
        ch = x.shape[self._axis]
        for p in (self.gamma, self.beta):
            if p._data is None:
                p._finish_deferred_init((ch,))
        return npx.layer_norm(x, self.gamma.data(), self.beta.data(),
                              axis=self._axis, eps=self._epsilon)

    def __repr__(self):
        return "LayerNorm(axis=%d, eps=%s)" % (self._axis, self._epsilon)


class RMSNorm(HybridBlock):
    """RMS normalization (TPU-native extension for LLM blocks).  With
    ``unit_offset`` ``gamma`` holds the gain's distance from one
    (initially 0); ``out_dtype`` is the dtype of the result where it is
    not the input's (a float32 stream normed for bf16 matmuls)."""

    def __init__(self, axis=-1, epsilon=1e-6, gamma_initializer=None,
                 in_channels=0, unit_offset=False, out_dtype=None):
        super().__init__()
        self._axis = axis
        self._epsilon = epsilon
        self._unit_offset = unit_offset
        self._out_dtype = out_dtype
        self.gamma = Parameter(
            shape=(in_channels,), allow_deferred_init=True, name="gamma",
            init=gamma_initializer or ("zeros" if unit_offset else "ones"))

    def forward(self, x):
        if self.gamma._data is None:
            self.gamma._finish_deferred_init((x.shape[self._axis],))
        return npx.rms_norm(x, self.gamma.data(), axis=self._axis,
                            eps=self._epsilon,
                            unit_offset=self._unit_offset,
                            out_dtype=self._out_dtype)


class GroupNorm(HybridBlock):
    def __init__(self, num_groups=1, epsilon=1e-5, center=True, scale=True,
                 beta_initializer="zeros", gamma_initializer="ones",
                 in_channels=0):
        super().__init__()
        self._num_groups = num_groups
        self._epsilon = epsilon
        self.gamma = Parameter(shape=(in_channels,), init=gamma_initializer,
                               allow_deferred_init=True, name="gamma",
                               differentiable=scale)
        self.beta = Parameter(shape=(in_channels,), init=beta_initializer,
                              allow_deferred_init=True, name="beta",
                              differentiable=center)

    def forward(self, x):
        ch = x.shape[1]
        for p in (self.gamma, self.beta):
            if p._data is None:
                p._finish_deferred_init((ch,))
        return npx.group_norm(x, self.gamma.data(), self.beta.data(),
                              num_groups=self._num_groups, eps=self._epsilon)


class InstanceNorm(HybridBlock):
    def __init__(self, axis=1, epsilon=1e-5, center=True, scale=True,
                 beta_initializer="zeros", gamma_initializer="ones",
                 in_channels=0):
        super().__init__()
        self._axis = axis
        self._epsilon = epsilon
        self.gamma = Parameter(shape=(in_channels,), init=gamma_initializer,
                               allow_deferred_init=True, name="gamma",
                               differentiable=scale)
        self.beta = Parameter(shape=(in_channels,), init=beta_initializer,
                              allow_deferred_init=True, name="beta",
                              differentiable=center)

    def forward(self, x):
        ch = x.shape[self._axis]
        for p in (self.gamma, self.beta):
            if p._data is None:
                p._finish_deferred_init((ch,))
        if self._axis != 1:
            x = x.swapaxes(1, self._axis)
        out = npx.instance_norm(x, self.gamma.data(), self.beta.data(),
                                eps=self._epsilon)
        if self._axis != 1:
            out = out.swapaxes(1, self._axis)
        return out


class Lambda(Block):
    def __init__(self, function):
        super().__init__()
        if isinstance(function, str):
            from ... import numpy as mnp
            function = getattr(mnp, function)
        self._func = function

    def forward(self, *args):
        return self._func(*args)


class HybridLambda(HybridBlock):
    """Reference ``basic_layers.py:926``: a callable must conform to
    ``def function(F, data, *args)`` — F is the op namespace (the
    reference passes nd/sym; here the ``mx.nd`` facade, whose ops trace
    cleanly)."""

    def __init__(self, function):
        super().__init__()
        self._takes_F = not isinstance(function, str)
        if isinstance(function, str):
            from ... import numpy as mnp
            function = getattr(mnp, function)
        self._func = function

    def forward(self, *args):
        if self._takes_F:
            from ... import ndarray as F
            return self._func(F, *args)
        return self._func(*args)


class Concatenate(Sequential):
    """Run children on the same input, concat outputs (basic_layers.py)."""

    def __init__(self, axis=-1):
        super().__init__()
        self.axis = axis

    def forward(self, x):
        from ... import numpy as mnp
        out = [block(x) for block in self._children.values()]
        return mnp.concatenate(out, axis=self.axis)


class HybridConcatenate(HybridSequential):
    def __init__(self, axis=-1):
        super().__init__()
        self.axis = axis

    def forward(self, x):
        from ... import numpy as mnp
        out = [block(x) for block in self._children.values()]
        return mnp.concatenate(out, axis=self.axis)
