"""Mesh construction and scoping.

The mesh plays the role of the reference's "kvstore type + device list"
pair: axis sizes define how many ways each parallelism strategy splits the
job (`kvstore.cc:42-85` transport selection → axis layout selection).
Axis order follows the scaling-book convention: fastest-varying (innermost,
highest-bandwidth ICI neighbors) last — put ``tp`` innermost.
"""
from __future__ import annotations

import threading
from contextlib import contextmanager

import jax
import numpy as _onp
from jax.sharding import Mesh

_STATE = threading.local()


def create_mesh(axes=None, devices=None, **axis_sizes):
    """Create a ``jax.sharding.Mesh``.

    ``create_mesh(dp=2, tp=4)`` or ``create_mesh({'dp': 2, 'tp': 4})``.
    An axis size of -1 absorbs the remaining devices.
    """
    if isinstance(axes, dict):
        axis_sizes = axes
    elif axes is not None and not axis_sizes:
        # sequence of (name, size)
        axis_sizes = dict(axes)
    devices = list(devices if devices is not None else jax.devices())
    names = list(axis_sizes.keys())
    sizes = list(axis_sizes.values())
    n = len(devices)
    if -1 in sizes:
        known = 1
        for s in sizes:
            if s != -1:
                known *= s
        sizes[sizes.index(-1)] = n // known
    total = 1
    for s in sizes:
        total *= s
    if total > n:
        raise ValueError("mesh %s needs %d devices, have %d"
                         % (dict(zip(names, sizes)), total, n))
    dev_array = _onp.array(devices[:total]).reshape(sizes)
    return Mesh(dev_array, names)


def shrink_mesh(mesh, devices=None, axis=None):
    """Rebuild ``mesh``'s axis layout over a (smaller) surviving device
    set — the mesh half of an elastic resize (``mx.fault.elastic``).

    ``axis`` (default the FIRST axis — conventionally the data-parallel
    one) absorbs the change: its size is recomputed from the surviving
    device count; every other axis keeps its size (they encode the
    model-parallel layout the checkpoint reshard preserves).  Devices
    beyond the largest multiple of the fixed-axes product are dropped —
    a ragged survivor count costs up to ``product-1`` idle devices, not
    a crash."""
    devices = list(devices if devices is not None else jax.devices())
    names = list(mesh.axis_names)
    sizes = dict(zip(names, mesh.devices.shape))
    axis = names[0] if axis is None else axis
    if axis not in sizes:
        raise ValueError("mesh has no axis %r (axes: %s)" % (axis, names))
    fixed = 1
    for nm, s in sizes.items():
        if nm != axis:
            fixed *= s
    if len(devices) < fixed:
        raise ValueError(
            "cannot shrink mesh %s onto %d device(s): the non-%s axes "
            "alone need %d" % (sizes, len(devices), axis, fixed))
    sizes[axis] = len(devices) // fixed
    return create_mesh(sizes, devices=devices)


def grow_mesh(mesh, devices=None, axis=None):
    """:func:`shrink_mesh`'s counterpart — rebuild ``mesh``'s axis
    layout over a (larger) device set after an elastic GROW (a joined
    replacement rank brings its devices back).  Same recompute: the
    named (default first, conventionally data-parallel) axis absorbs
    the growth, every other axis keeps its size, and devices beyond the
    largest multiple of the fixed-axes product idle rather than crash.
    ``TrainStep.resize``'s orbax restore reshards any checkpoint onto
    the result, so shrink→grow round-trips are lossless."""
    devices = list(devices if devices is not None else jax.devices())
    names = list(mesh.axis_names)
    sizes = dict(zip(names, mesh.devices.shape))
    axis = names[0] if axis is None else axis
    if axis not in sizes:
        raise ValueError("mesh has no axis %r (axes: %s)" % (axis, names))
    fixed = 1
    for nm, s in sizes.items():
        if nm != axis:
            fixed *= s
    if len(devices) < fixed:
        raise ValueError(
            "cannot grow mesh %s onto %d device(s): the non-%s axes "
            "alone need %d" % (sizes, len(devices), axis, fixed))
    sizes[axis] = len(devices) // fixed
    return create_mesh(sizes, devices=devices)


def local_mesh(*names):
    """One-axis-per-name mesh over all local devices (first axis gets all)."""
    if not names:
        names = ("dp",)
    sizes = {names[0]: -1}
    for nm in names[1:]:
        sizes[nm] = 1
    return create_mesh(sizes)


def current_mesh():
    return getattr(_STATE, "mesh", None)


@contextmanager
def mesh_scope(mesh):
    """Make ``mesh`` the one :func:`current_mesh` answers with (and
    jax's own context mesh) inside the block.  ``mesh_scope(None)`` is a
    no-op, so callers with an optional mesh need no branch."""
    if mesh is None:
        yield None
        return
    prev = getattr(_STATE, "mesh", None)
    _STATE.mesh = mesh
    try:
        with mesh:
            yield mesh
    finally:
        _STATE.mesh = prev
