"""``mx.np.random`` — stateful RNG frontend over ``jax.random``.

Reference parity: ``python/mxnet/numpy/random.py`` + ``src/operator/random/``
(per-device RNG ``random_generator.h``).  The TPU build keeps MXNet's
*stateful* seed semantics (``mx.np.random.seed(n)`` makes subsequent calls
deterministic) by threading a split-on-use PRNG key — the counter-based
analog of the reference's per-device generator state.

Samplers with differentiable parameters (``normal``/``uniform``'s loc/scale)
are expressed as ``loc + scale * standard_sample`` so gradients flow to the
parameters through the tape (pathwise derivative).
"""
from __future__ import annotations

import threading

import jax
import jax.numpy as jnp
import numpy as _onp

from ..ndarray.ndarray import NDArray, apply_op
from ..context import current_context


class _RNGState(threading.local):
    """Lazy per-thread key: creating a key initializes the XLA backend, so
    it must not happen at import (jax.distributed.initialize must be able
    to run first in multi-process jobs)."""

    def __init__(self):
        self._key = None
        self.trace_stack = []

    @property
    def key(self):
        if self._key is None:
            self._key = jax.random.key(
                _onp.random.SeedSequence().entropy % (2**32))
        return self._key

    @key.setter
    def key(self, k):
        self._key = k


_STATE = _RNGState()


class trace_scope:
    """While tracing (hybridize), RNG keys derive deterministically from a
    traced base key by fold_in, so each compiled call gets fresh randomness
    from the key argument rather than baking one sample into the graph."""

    def __init__(self, base_key):
        self._base = base_key

    def __enter__(self):
        _STATE.trace_stack.append([self._base, 0])
        return self

    def __exit__(self, *exc):
        _STATE.trace_stack.pop()


def seed(seed_state=None, ctx="all"):
    if seed_state is None:
        seed_state = _onp.random.SeedSequence().entropy % (2**32)
    _STATE.key = jax.random.key(int(seed_state))


def new_key():
    """Split off a fresh PRNG key (also used by Dropout etc.)."""
    if _STATE.trace_stack:
        entry = _STATE.trace_stack[-1]
        entry[1] += 1
        return jax.random.fold_in(entry[0], entry[1])
    _STATE.key, sub = jax.random.split(_STATE.key)
    return sub


def _size_to_shape(size):
    if size is None:
        return ()
    if isinstance(size, int):
        return (size,)
    return tuple(size)


def _is_t(x):
    return isinstance(x, (NDArray, jax.Array))


def _default_int():
    """numpy/reference integer-sampler default is int64; canonicalize so
    the x64-off default resolves to int32 without a per-call truncation
    warning (int64 mode still yields real int64)."""
    return jax.dtypes.canonicalize_dtype(jnp.int64)


def uniform(low=0.0, high=1.0, size=None, dtype=None, ctx=None, device=None,
            out=None):
    shape = _size_to_shape(size)
    dt = jnp.dtype(dtype or "float32")
    k = new_key()
    if _is_t(low) or _is_t(high):
        def g(lo, hi):
            bshape = shape or jnp.broadcast_shapes(jnp.shape(lo), jnp.shape(hi))
            u = jax.random.uniform(k, bshape, dt)
            return lo + u * (hi - lo)
        return apply_op(g, [low, high], name="uniform", out=out)
    r = NDArray(jax.random.uniform(k, shape, dt, low, high),
                ctx=ctx or device or current_context())
    if out is not None:
        out._assign(r)
        return out
    return r


def normal(loc=0.0, scale=1.0, size=None, dtype=None, ctx=None, device=None,
           out=None):
    shape = _size_to_shape(size)
    dt = jnp.dtype(dtype or "float32")
    k = new_key()
    if _is_t(loc) or _is_t(scale):
        def g(mu, sig):
            bshape = shape or jnp.broadcast_shapes(jnp.shape(mu),
                                                   jnp.shape(sig))
            z = jax.random.normal(k, bshape, dt)
            return mu + sig * z
        return apply_op(g, [loc, scale], name="normal", out=out)
    r = NDArray(loc + scale * jax.random.normal(k, shape, dt),
                ctx=ctx or device or current_context())
    if out is not None:
        out._assign(r)
        return out
    return r


def randn(*size, dtype=None, ctx=None):
    return normal(0.0, 1.0, size=size or None, dtype=dtype, ctx=ctx)


def rand(*size):
    return uniform(0.0, 1.0, size=size or None)


def standard_normal(size=None, dtype=None):
    return normal(0.0, 1.0, size=size, dtype=dtype)


def randint(low, high=None, size=None, dtype=None, ctx=None, device=None,
            out=None):
    if high is None:
        low, high = 0, low
    dt = jnp.dtype(dtype) if dtype is not None else _default_int()
    r = NDArray(jax.random.randint(new_key(), _size_to_shape(size), low, high,
                                   dt), ctx=ctx or device or current_context())
    if out is not None:
        out._assign(r)
        return out
    return r


def choice(a, size=None, replace=True, p=None, ctx=None, out=None):
    shape = _size_to_shape(size)
    if isinstance(a, NDArray):
        arr = a._data
    elif isinstance(a, int):
        arr = jnp.arange(a)
    else:
        arr = jnp.asarray(a)
    pv = p._data if isinstance(p, NDArray) else (jnp.asarray(p) if p is not None
                                                 else None)
    r = NDArray(jax.random.choice(new_key(), arr, shape, replace=replace, p=pv))
    if out is not None:
        out._assign(r)
        return out
    return r


def permutation(x):
    if isinstance(x, int):
        return NDArray(jax.random.permutation(new_key(), x))
    return NDArray(jax.random.permutation(new_key(),
                                          x._data if isinstance(x, NDArray)
                                          else jnp.asarray(x)))


def shuffle(x):
    """In-place shuffle along axis 0 (handle swap)."""
    x._set_data(jax.random.permutation(new_key(), x._data, axis=0,
                                       independent=False))


def beta(a, b, size=None, dtype=None, ctx=None, device=None):
    av = a._data if isinstance(a, NDArray) else a
    bv = b._data if isinstance(b, NDArray) else b
    return NDArray(jax.random.beta(new_key(), av, bv, _size_to_shape(size)
                                   or None).astype(dtype or "float32"))


def gamma(shape, scale=1.0, size=None, dtype=None, ctx=None, device=None,
          out=None):
    sv = shape._data if isinstance(shape, NDArray) else shape
    sc = scale._data if isinstance(scale, NDArray) else scale
    r = NDArray((jax.random.gamma(new_key(), sv, _size_to_shape(size) or None)
                 * sc).astype(dtype or "float32"))
    if out is not None:
        out._assign(r)
        return out
    return r


def exponential(scale=1.0, size=None, ctx=None, device=None, out=None):
    sc = scale._data if isinstance(scale, NDArray) else scale
    r = NDArray(jax.random.exponential(new_key(), _size_to_shape(size)) * sc)
    if out is not None:
        out._assign(r)
        return out
    return r


def poisson(lam=1.0, size=None, ctx=None, device=None, out=None):
    lv = lam._data if isinstance(lam, NDArray) else lam
    r = NDArray(jax.random.poisson(new_key(), lv, _size_to_shape(size)
                                   or None).astype(_default_int()),
                ctx=ctx or device or current_context())
    if out is not None:
        out._assign(r)
        return out
    return r


def _multinomial_counts(key, n, pv, batch=()):
    """Multinomial counts of ``n`` draws over the last axis of ``pv``
    (probabilities, broadcast over ``batch``)."""
    return jax.random.multinomial(
        key, n, pv,
        shape=(tuple(batch) + pv.shape[-1:]) if batch else None)


def multinomial(n, pvals, size=None):
    pv = pvals._data if isinstance(pvals, NDArray) else jnp.asarray(pvals)
    shape = _size_to_shape(size)
    counts = _multinomial_counts(new_key(), n, pv,
                                 batch=(shape or ()) + pv.shape[:-1])
    return NDArray(counts.astype(_default_int()))


def multivariate_normal(mean, cov, size=None, check_valid=None, tol=None):
    mv = mean._data if isinstance(mean, NDArray) else jnp.asarray(mean)
    cv = cov._data if isinstance(cov, NDArray) else jnp.asarray(cov)
    return NDArray(jax.random.multivariate_normal(
        new_key(), mv, cv, _size_to_shape(size) or None))


def bernoulli(prob=None, logit=None, size=None, dtype=None, ctx=None,
              device=None, out=None):
    if prob is None:
        prob = jax.nn.sigmoid(logit._data if isinstance(logit, NDArray)
                              else jnp.asarray(logit))
    else:
        prob = prob._data if isinstance(prob, NDArray) else prob
    r = NDArray(jax.random.bernoulli(new_key(), prob,
                                     _size_to_shape(size) or None)
                .astype(dtype or "float32"))
    if out is not None:
        out._assign(r)
        return out
    return r


def laplace(loc=0.0, scale=1.0, size=None, dtype=None, ctx=None, out=None):
    lv = loc._data if isinstance(loc, NDArray) else loc
    sv = scale._data if isinstance(scale, NDArray) else scale
    r = NDArray((lv + sv * jax.random.laplace(new_key(), _size_to_shape(size)))
                .astype(dtype or "float32"))
    if out is not None:
        out._assign(r)
        return out
    return r


def logistic(loc=0.0, scale=1.0, size=None, ctx=None, out=None):
    lv = loc._data if isinstance(loc, NDArray) else loc
    sv = scale._data if isinstance(scale, NDArray) else scale
    r = NDArray(lv + sv * jax.random.logistic(new_key(), _size_to_shape(size)))
    if out is not None:
        out._assign(r)
        return out
    return r


def gumbel(loc=0.0, scale=1.0, size=None, ctx=None, out=None):
    lv = loc._data if isinstance(loc, NDArray) else loc
    sv = scale._data if isinstance(scale, NDArray) else scale
    r = NDArray(lv + sv * jax.random.gumbel(new_key(), _size_to_shape(size)))
    if out is not None:
        out._assign(r)
        return out
    return r


def lognormal(mean=0.0, sigma=1.0, size=None, ctx=None, out=None):
    r = normal(mean, sigma, size=size)
    r = NDArray(jnp.exp(r._data))
    if out is not None:
        out._assign(r)
        return out
    return r


def rayleigh(scale=1.0, size=None, ctx=None, out=None):
    sv = scale._data if isinstance(scale, NDArray) else scale
    u = jax.random.uniform(new_key(), _size_to_shape(size), minval=1e-12)
    r = NDArray(sv * jnp.sqrt(-2.0 * jnp.log(u)))
    if out is not None:
        out._assign(r)
        return out
    return r


def weibull(a, size=None, ctx=None, out=None):
    av = a._data if isinstance(a, NDArray) else a
    u = jax.random.uniform(new_key(), _size_to_shape(size), minval=1e-12)
    r = NDArray(jnp.power(-jnp.log(u), 1.0 / av))
    if out is not None:
        out._assign(r)
        return out
    return r


def pareto(a, size=None, ctx=None, out=None):
    av = a._data if isinstance(a, NDArray) else a
    u = jax.random.uniform(new_key(), _size_to_shape(size), minval=1e-12)
    r = NDArray(jnp.power(u, -1.0 / av) - 1.0)
    if out is not None:
        out._assign(r)
        return out
    return r


def power(a, size=None, ctx=None, out=None):
    av = a._data if isinstance(a, NDArray) else a
    u = jax.random.uniform(new_key(), _size_to_shape(size), minval=1e-12)
    r = NDArray(jnp.power(u, 1.0 / av))
    if out is not None:
        out._assign(r)
        return out
    return r


def chisquare(df, size=None, dtype=None, ctx=None):
    dv = df._data if isinstance(df, NDArray) else df
    return NDArray((2.0 * jax.random.gamma(
        new_key(), dv / 2.0, _size_to_shape(size) or None))
        .astype(dtype or "float32"))


def f(dfnum, dfden, size=None, ctx=None):
    n = chisquare(dfnum, size=size)._data / dfnum
    d = chisquare(dfden, size=size)._data / dfden
    return NDArray(n / d)


def binomial(n, p, size=None, dtype=None, ctx=None):
    shape = _size_to_shape(size)
    nv = int(n) if not isinstance(n, NDArray) else int(n.asscalar())
    pv = p._data if isinstance(p, NDArray) else p
    draws = jax.random.bernoulli(new_key(), pv, (nv,) + (shape or ()))
    return NDArray(jnp.sum(draws, axis=0).astype(dtype or _default_int()),
                   ctx=ctx or current_context())


def negative_binomial(n, p, size=None, ctx=None):
    g = jax.random.gamma(new_key(), n, _size_to_shape(size) or None) \
        * (1 - p) / p
    return NDArray(jax.random.poisson(new_key(), g).astype(_default_int()),
                   ctx=ctx or current_context())
