"""``mx.gluon`` — the user-facing NN API (reference: ``python/mxnet/gluon/``)."""
import time as _time

_IMPORT_T0 = _time.monotonic()    # mx.start.import, recorded below

from . import loss, utils
from .block import Block, HybridBlock
from .parameter import Constant, Parameter, DeferredInitializationError
from .symbol_block import SymbolBlock
from .trainer import Trainer
from . import nn
from . import rnn
from .. import profiler as _profiler

_profiler.record_build_span("mx.start.import", _IMPORT_T0,
                            module=__name__)


def __getattr__(name):
    import importlib
    lazy = {"data": ".data", "model_zoo": ".model_zoo", "metric": ".metric",
            "contrib": ".contrib", "probability": ".probability"}
    if name in lazy:
        import sys
        mod = importlib.import_module(lazy[name], __name__)
        globals()[name] = mod
        return mod
    raise AttributeError("module %r has no attribute %r" % (__name__, name))
