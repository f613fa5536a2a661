"""mxnet_tpu — a TPU-native deep-learning framework with the capability
surface of Apache MXNet 2.0, built from scratch on JAX/XLA/pjit/Pallas.

Import as ``import mxnet_tpu as mx`` — the namespace mirrors ``mxnet``:
``mx.np``, ``mx.npx``, ``mx.nd``, ``mx.autograd``, ``mx.gluon``,
``mx.optimizer``, ``mx.kv``, ``mx.context``/``mx.cpu()/mx.gpu()/mx.tpu()``.

Architecture (see SURVEY.md for the full mapping):
- MXNet's threaded dependency engine (src/engine/) -> JAX async dispatch;
  NDArray is a mutable handle over immutable jax.Arrays.
- nnvm graph + CachedOp (src/imperative/cached_op.cc) -> hybridize() traces
  to a jaxpr and compiles with jax.jit (XLA does fusion/memory planning).
- src/operator/ CUDA kernels -> jax.numpy/lax ops (XLA HLO is the native
  TPU path) + Pallas kernels for attention.
- KVStore transports (ps-lite/NCCL) -> XLA collectives over ICI/DCN via
  jax.sharding meshes.
"""
from __future__ import annotations

import time as _time

_IMPORT_T0 = _time.monotonic()    # mx.start.import, recorded below

__version__ = "2.0.0.tpu0"

import os as _os

if _os.environ.get("MXNET_INT64_TENSOR_SIZE", "0") not in (
        "", "0", "false", "False"):  # env_bool truthiness (utils/config.py)
    # Large-tensor / int64 mode (reference: the USE_INT64_TENSOR_SIZE build
    # flag, tests/nightly/test_large_array.py).  Must be set before any jax
    # array is created; widens index/shape arithmetic past 2^31.
    import jax as _jax

    _jax.config.update("jax_enable_x64", True)

from . import context
from .context import Context, Device, cpu, gpu, tpu, cpu_pinned, num_gpus, \
    num_tpus, current_context, current_device, device
from . import ndarray
from . import ndarray as nd
from .ndarray import NDArray, waitall
from . import numpy as np  # noqa: A004
from . import numpy_extension as npx
from . import autograd
from . import ops

# subsystems below import lazily to keep `import mxnet_tpu` light and to
# tolerate partial builds while the framework grows.
from . import base  # noqa: E402
from .util import is_np_array, is_np_shape, set_np, use_np  # noqa: E402
from . import profiler  # noqa: E402  (ndarray has imported it already)

profiler.record_build_span("mx.start.import", _IMPORT_T0, module=__name__)


def __getattr__(name):
    import importlib
    _lazy = {
        "gluon": ".gluon",
        "optimizer": ".optimizer",
        "initializer": ".initializer",
        "init": ".initializer",
        "lr_scheduler": ".lr_scheduler",
        "kvstore": ".kvstore",
        "kv": ".kvstore",
        "io": ".io",
        "parallel": ".parallel",
        "amp": ".amp",
        "telemetry": ".telemetry",
        "flightrec": ".flightrec",
        "fault": ".fault",
        "analysis": ".analysis",
        "metric": ".gluon.metric",
        "monitor": ".monitor",
        "mon": ".monitor",
        "test_utils": ".test_utils",
        "random": ".numpy.random",
        "recordio": ".recordio",
        "image": ".image",
        "runtime": ".runtime",
        "serve": ".serve",
        "engine": ".engine",
        "models": ".models",
        "sym": ".symbol",
        "symbol": ".symbol",
        "callback": ".callback",
        "model": ".model",
        "visualization": ".visualization",
        "viz": ".visualization",
        "library": ".library",
        "contrib": ".contrib",
        "rtc": ".rtc",
        "subgraph": ".subgraph",
    }
    if name in _lazy:
        mod = importlib.import_module(_lazy[name], __name__)
        globals()[name] = mod
        return mod
    if name == "AttrScope":  # class, not module (reference mx.AttrScope)
        from .symbol import AttrScope
        globals()[name] = AttrScope
        return AttrScope
    raise AttributeError("module %r has no attribute %r" % (__name__, name))
