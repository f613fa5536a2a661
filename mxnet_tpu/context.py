"""Device contexts for the TPU-native MXNet capability surface.

Reference parity: ``python/mxnet/context.py`` (``Context`` at context.py:297,
``cpu()/gpu()/cpu_pinned()``).  The TPU build maps contexts onto JAX devices:
``tpu(i)`` is the i-th accelerator, ``gpu(i)`` is an alias for ``tpu(i)`` so
reference scripts run with a one-line (or zero-line) change, and ``cpu()`` is
the JAX CPU backend.  There is no ``cpu_pinned`` distinction on TPU (host
memory is host memory); it aliases ``cpu()`` and the delta is documented.
"""
from __future__ import annotations

import threading

import jax

__all__ = [
    "Context",
    "cpu",
    "gpu",
    "tpu",
    "cpu_pinned",
    "num_gpus",
    "num_tpus",
    "current_context",
    "current_device",
    "Device",
    "device",
]


_devtype_names = {1: "cpu", 2: "gpu", 3: "cpu_pinned", 4: "cpu_shared", 5: "tpu"}
_devtype_ids = {v: k for k, v in _devtype_names.items()}
# gpu is an alias for the accelerator backend on this build.
_JAX_BACKEND_FOR = {"cpu": "cpu", "cpu_pinned": "cpu", "cpu_shared": "cpu",
                    "gpu": None, "tpu": None}


def _accelerator_platform():
    """The default backend's platform name.  A backend that fails to
    initialize raises here: an accelerator that cannot start is never
    the CPU."""
    return jax.default_backend()


class Context:
    """A device context, API-compatible with ``mx.Context``.

    Parameters
    ----------
    device_type : str or Context
        'cpu', 'gpu', 'tpu', 'cpu_pinned', 'cpu_shared'.
    device_id : int
        Device ordinal.
    """

    _default_ctx = threading.local()

    def __init__(self, device_type, device_id=0):
        if isinstance(device_type, Context):
            self.device_typeid = device_type.device_typeid
            self.device_id = device_type.device_id
        else:
            if device_type not in _devtype_ids:
                raise ValueError("unknown device type %r" % (device_type,))
            self.device_typeid = _devtype_ids[device_type]
            self.device_id = device_id

    @property
    def device_type(self):
        return _devtype_names[self.device_typeid]

    def __hash__(self):
        return hash((self.device_typeid, self.device_id))

    def __eq__(self, other):
        return (
            isinstance(other, Context)
            and self.device_typeid == other.device_typeid
            and self.device_id == other.device_id
        )

    def __str__(self):
        return "%s(%d)" % (self.device_type, self.device_id)

    def __repr__(self):
        return "Context(%s)" % str(self)

    def __enter__(self):
        if not hasattr(Context._default_ctx, "value"):
            Context._default_ctx.value = Context("cpu", 0)
        self._old_ctx = Context._default_ctx.value
        Context._default_ctx.value = self
        return self

    def __exit__(self, ptype, value, trace):
        Context._default_ctx.value = self._old_ctx

    # --- JAX mapping -----------------------------------------------------
    @property
    def jax_device(self):
        """The concrete ``jax.Device`` this context denotes.

        Always a process-LOCAL device: in multi-process (dist kvstore)
        jobs, ``jax.devices()`` is global but data placement must target
        addressable devices (reference analog: each worker only touches
        its own GPUs)."""
        dtype = self.device_type
        if dtype in ("cpu", "cpu_pinned", "cpu_shared"):
            if _accelerator_platform() != "cpu":
                devs = [d for d in jax.local_devices(backend="cpu")]
            else:
                devs = jax.local_devices()
            return devs[min(self.device_id, len(devs) - 1)]
        # gpu/tpu -> default accelerator backend
        devs = jax.local_devices()
        if self.device_id >= len(devs):
            raise ValueError(
                "context %s out of range: %d local device(s) visible"
                % (self, len(devs)))
        return devs[self.device_id]

    def empty_cache(self):
        """Reference: ``Context.empty_cache`` (context.py) — release the
        memory pool.  XLA manages device memory; this is a no-op hook."""

    # numpy-style alias used by mx 2.x
    @property
    def index(self):
        return self.device_id


# mx 2.x names `Device` as well
Device = Context


def cpu(device_id=0):
    return Context("cpu", device_id)


def cpu_pinned(device_id=0):
    return Context("cpu_pinned", device_id)


def tpu(device_id=0):
    return Context("tpu", device_id)


def gpu(device_id=0):
    """Alias of :func:`tpu` — reference GPU scripts run unchanged."""
    return Context("gpu", device_id)


def device(dev_type, device_id=0):
    return Context(dev_type, device_id)


def num_gpus():
    """Number of visible accelerator chips (parity with ``mx.context.num_gpus``)."""
    if _accelerator_platform() == "cpu":
        return 0
    return len(jax.devices())


def num_tpus():
    return num_gpus()


def current_context():
    """The ambient default context (``with mx.tpu(0): ...`` scoped)."""
    if not hasattr(Context._default_ctx, "value"):
        # default to the accelerator when present, else cpu — this is the
        # "one-line context swap" promise: on a TPU host everything lands
        # on-chip by default.
        if _accelerator_platform() != "cpu":
            Context._default_ctx.value = Context("tpu", 0)
        else:
            Context._default_ctx.value = Context("cpu", 0)
    return Context._default_ctx.value


current_device = current_context
