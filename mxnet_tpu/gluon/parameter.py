"""Gluon ``Parameter`` — deferred-init trainable tensor.

Reference parity: ``python/mxnet/gluon/parameter.py:47``.  A Parameter owns
one NDArray per device list; here the device story is a jax.Array (possibly
sharded over a Mesh), so a single handle suffices — ``list_data()`` etc.
return one-element lists for API compatibility.  Deferred init (shape with
0/-1 dims resolved at first forward) is preserved: layers call
``_finish_deferred_init`` once the input shape is known.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as onp

from .. import initializer as init_mod
from ..context import Context, current_context
from ..initializer import InitDesc
from ..ndarray.ndarray import NDArray
from .. import _tape


class DeferredInitializationError(RuntimeError):
    """Parameter accessed before shape was inferred (parameter.py raises the
    same)."""


class _GradBuffer(NDArray):
    """A parameter's gradient buffer: zeros that take device memory only
    once somebody reads or writes them.  The tape holds this handle from
    the moment the weight is marked (``_tape.mark_variable``), so the
    handle exists at once; the array behind it is made by the first
    ``backward`` that writes it or the first reader (``grad().asnumpy()``,
    ``Trainer``, an accumulation under ``grad_req='add'``).  A network that
    is only ever stepped by ``parallel.TrainStep``, which differentiates
    inside its own program, never makes one."""

    __slots__ = ("_aval",)
    _slot = NDArray._data   # the storage this class's property guards

    def __init__(self, shape, dtype):
        self._aval = (tuple(shape), jnp.dtype(dtype))
        self._ag = None
        self._fresh = False

    @property
    def live(self):
        """Whether the buffer holds an array yet."""
        try:
            _GradBuffer._slot.__get__(self)
        except AttributeError:
            return False
        return True

    @property
    def _data(self):
        if not self.live:
            # concrete even when first read while something is traced
            with jax.ensure_compile_time_eval():
                _GradBuffer._slot.__set__(self, jnp.zeros(*self._aval))
        return _GradBuffer._slot.__get__(self)

    @_data.setter
    def _data(self, value):
        _GradBuffer._slot.__set__(self, value)

    @property
    def shape(self):
        return tuple(self._data.shape) if self.live else self._aval[0]

    @property
    def dtype(self):
        return onp.dtype(self._data.dtype if self.live else self._aval[1])

    def retype(self, dtype):
        """The buffer in another dtype, in place (the tape holds this
        very object)."""
        if self.live:
            self._data = self._data.astype(dtype)
        self._aval = (self._aval[0], jnp.dtype(dtype))


class Parameter:
    def __init__(self, shape=None, dtype="float32", lr_mult=1.0, wd_mult=1.0,
                 init=None, allow_deferred_init=False, differentiable=True,
                 stype="default", grad_stype="default", grad_req="write",
                 name=None):
        self._name = name or "param"
        self._shape = tuple(shape) if shape is not None else None
        self.dtype = dtype
        self.lr_mult = lr_mult
        self.wd_mult = wd_mult
        self.init = init
        self.allow_deferred_init = allow_deferred_init
        self._differentiable = differentiable
        if not differentiable:
            grad_req = "null"
        self._grad_req = grad_req
        self._data = None   # NDArray
        self._grad = None   # NDArray
        self._deferred_init = None  # (init, ctx, default_init)
        self._sharding_spec = None  # parallel: PartitionSpec-like tuple
        self._var = None

    # -- identity ---------------------------------------------------------
    @property
    def name(self):
        return self._name

    def __repr__(self):
        return "Parameter %s (shape=%s, dtype=%s)" % (self._name, self._shape,
                                                      self.dtype)

    # -- shape ------------------------------------------------------------
    @property
    def shape(self):
        return self._shape

    @shape.setter
    def shape(self, new_shape):
        if self._shape is None:
            self._shape = tuple(new_shape)
            return
        unknown = any(d in (0, -1) for d in self._shape)
        if not unknown:
            if tuple(new_shape) != self._shape:
                raise AssertionError(
                    "Expected shape %s is incompatible with given shape %s "
                    "for Parameter %s" % (new_shape, self._shape, self._name))
            return
        if len(new_shape) != len(self._shape):
            raise AssertionError("shape rank mismatch for %s" % self._name)
        for old, new in zip(self._shape, new_shape):
            if old not in (0, -1) and old != new:
                raise AssertionError(
                    "Expected shape %s is incompatible with given shape %s"
                    % (self._shape, new_shape))
        self._shape = tuple(new_shape)

    @property
    def grad_req(self):
        return self._grad_req

    @grad_req.setter
    def grad_req(self, req):
        if req not in ("write", "add", "null"):
            raise ValueError("grad_req must be write/add/null")
        if not self._differentiable:
            req = "null"
        if self._grad_req == req:
            return
        self._grad_req = req
        if self._data is not None:
            if req == "null":
                self._grad = None
                self._data._ag = None
            else:
                self._init_grad()

    # -- initialization ---------------------------------------------------
    def initialize(self, init=None, device=None, ctx=None,
                   default_init=None, force_reinit=False):
        ctx = device if device is not None else ctx
        default_init = default_init or init_mod.Uniform()
        if self._data is not None and not force_reinit:
            return
        if self._shape is None or any(d in (0, -1) for d in (self._shape or ())):
            if self.allow_deferred_init:
                self._deferred_init = (init, ctx, default_init)
                return
            raise ValueError(
                "Cannot initialize Parameter %s because it has invalid shape "
                "%s and deferred init is disallowed." % (self._name,
                                                         self._shape))
        self._finish_init(init, ctx, default_init)

    def _finish_init(self, init, ctx, default_init):
        if isinstance(ctx, (list, tuple)):
            ctx = ctx[0] if ctx else None
        arr = NDArray(jnp.zeros(self._shape, self.dtype), ctx=ctx)
        initializer = init or self.init or default_init
        if isinstance(initializer, str):
            initializer = init_mod.create(initializer)
        # a param-specific initializer (Parameter(init=...) or the
        # layer's *_initializer kwarg) must fire even on bias/gamma/...
        # suffixed names — carried via the __init__ attr exactly like
        # the reference's Variable-attr path.  Pass the RESOLVED
        # instance (one construction, one code path); plain callables
        # like Mixed are not suffix-dispatched to begin with, so they
        # need no override.
        attrs = {}
        explicit = init or self.init
        if explicit is not None and isinstance(initializer,
                                               init_mod.Initializer):
            attrs["__init__"] = initializer
        initializer(InitDesc(self._name, attrs=attrs), arr)
        self._data = arr
        self._deferred_init = None
        if self._grad_req != "null":
            self._init_grad()

    def _finish_deferred_init(self, inferred_shape=None):
        if self._data is not None:
            if inferred_shape is not None:
                self.shape = inferred_shape  # validates compatibility
            return
        if inferred_shape is not None:
            self.shape = inferred_shape
        if self._deferred_init is None:
            raise DeferredInitializationError(
                "Parameter %s was not initialized (call .initialize())"
                % self._name)
        init, ctx, default_init = self._deferred_init
        self._finish_init(init, ctx, default_init)

    def _init_grad(self):
        self._grad = _GradBuffer(self._data.shape, self._data.dtype)
        _tape.mark_variable(self._data, self._grad, self._grad_req)

    # -- access -----------------------------------------------------------
    def _check_initialized(self):
        if self._data is not None:
            return
        if self._deferred_init is not None:
            raise DeferredInitializationError(
                "Parameter %s has not been initialized yet because "
                "initialization was deferred. Run a forward pass first."
                % self._name)
        raise RuntimeError(
            "Parameter %s has not been initialized. You should initialize "
            "parameters with Block.initialize()." % self._name)

    def data(self, ctx=None, device=None):
        self._check_initialized()
        return self._data

    def list_data(self):
        self._check_initialized()
        return [self._data]

    def grad(self, ctx=None, device=None):
        self._check_initialized()
        if self._grad is None:
            raise RuntimeError(
                "Cannot get gradient array for Parameter %s because "
                "grad_req='null'" % self._name)
        return self._grad

    @property
    def _fresh_grad(self):
        """True once backward has written this parameter's grad buffer
        since the last consuming step (reference ``Parameter._fresh_grad``
        backing the Trainer's stale-gradient protocol).  Lives on the
        grad-buffer handle, so re-marking the weight (set_data and
        friends) cannot orphan it."""
        return bool(self._grad is not None and self._grad._fresh)

    @_fresh_grad.setter
    def _fresh_grad(self, value):
        if self._grad is not None:
            self._grad._fresh = bool(value)

    def list_grad(self):
        return [self.grad()]

    def list_ctx(self):
        self._check_initialized()
        return [self._data.context]

    list_device = list_ctx

    def set_data(self, data):
        if not isinstance(data, NDArray):
            data = NDArray(jnp.asarray(data))
        if self._data is None:
            if self._deferred_init is not None:
                self.shape = data.shape
                self._finish_deferred_init()
            else:
                self.shape = data.shape
                src = data._data.astype(self.dtype)
                if src is data._data:
                    src = jnp.copy(src)
                self._data = NDArray(src)
                if self._grad_req != "null":
                    self._init_grad()
                return
        # COPY like the reference's ``arr[:] = data``: aliasing the
        # caller's array would let a later donated optimizer update
        # delete the buffer out from under the other holder.  astype to
        # a different dtype already yields a fresh buffer; copy only
        # when it was a no-op.
        src = data._data.astype(self.dtype)
        if src is data._data:
            src = jnp.copy(src)
        self._data._set_data(src)
        # re-mark: _set_data clears autograd info.  Grad freshness needs
        # no bookkeeping here — it lives on the (untouched) grad buffer.
        if self._grad is not None:
            _tape.mark_variable(self._data, self._grad, self._grad_req)

    def zero_grad(self):
        if self._grad is not None and self._grad.live:
            self._grad._data = jnp.zeros_like(self._grad._data)

    def reset_ctx(self, ctx):
        if self._data is not None:
            self._data = self._data.as_in_context(ctx)
            if self._grad is not None:
                # in-place device move, same buffer object: a record-
                # time tape holds this exact object as its grad_buf
                # (see cast)
                c = Context(ctx) if not isinstance(ctx, Context) else ctx
                if self._grad.live:
                    self._grad._data = jax.device_put(self._grad._data,
                                                      c.jax_device)
                self._grad._ag = None
                _tape.mark_variable(self._data, self._grad, self._grad_req)

    reset_device = reset_ctx

    def cast(self, dtype):
        self.dtype = dtype
        if self._data is not None:
            self._data = self._data.astype(dtype)
            if self._grad is not None:
                # mutate the grad buffer IN PLACE: a record-time tape
                # holds this exact object as its grad_buf — replacing it
                # would orphan both the gradient and its freshness mark
                self._grad.retype(dtype)
                self._grad._ag = None
                _tape.mark_variable(self._data, self._grad, self._grad_req)

    # -- sharding annotation (TPU-native extension) -----------------------
    def shard(self, spec):
        """Annotate with a PartitionSpec-like tuple for pjit sharding
        (consumed by mxnet_tpu.parallel); e.g. ``('tp', None)``."""
        self._sharding_spec = tuple(spec)
        return self

    @property
    def sharding_spec(self):
        return self._sharding_spec


class Constant(Parameter):
    """Non-updating parameter holding a constant (gluon/parameter.py
    Constant)."""

    def __init__(self, value, name=None):
        if not isinstance(value, NDArray):
            value = NDArray(jnp.asarray(value))
        self._value = value
        super().__init__(shape=value.shape, dtype=value.dtype,
                         grad_req="null", differentiable=False, name=name,
                         init="zeros")

    def initialize(self, init=None, device=None, ctx=None, default_init=None,
                   force_reinit=False):
        self._data = NDArray(self._value._data)
        self._deferred_init = None
