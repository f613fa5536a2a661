"""Gluon ``Block`` / ``HybridBlock`` — the user-facing NN module system.

Reference parity: ``python/mxnet/gluon/block.py`` (``Block:203``,
``HybridBlock:998``, ``hybridize:1419``, ``export:1514``).

TPU-native hybridize: the reference traces ``forward`` once via deferred
compute into an nnvm Symbol and executes it with CachedOp
(``block.py:1101/1135/1251``, ``src/imperative/cached_op.cc:776``).  Here the
trace target is a jaxpr: ``hybridize()`` swaps parameter handles for tracers,
runs ``forward`` once per input signature, and compiles the whole graph with
``jax.jit`` — XLA performs the fusion/CSE/memory-planning that CachedOp's
graph passes (pointwise_fusion_pass.cc, plan_memory.cc) did by hand.  The
compiled callable is recorded on the autograd tape as a *single* node, so
backward is one fused XLA program too (the analog of CachedOp::Backward).

Mutable layer state (BatchNorm running stats) is detected at trace time:
parameters whose handle was written during tracing become extra outputs of
the compiled function and are written back after each call — the functional
equivalent of the reference's in-place aux-state update.
"""
from __future__ import annotations

import contextlib
import re
import threading
from collections import OrderedDict

import jax
import jax.numpy as jnp

from .. import _tape
from .. import initializer as init_mod
from .. import profiler as _profiler
from ..context import current_context
from ..ndarray.ndarray import NDArray, apply_op
from ..numpy import random as _random
from ..ops.pallas_ops import ATTENTION_KERNEL_OUT
from ..utils import serialization
from .parameter import Parameter, DeferredInitializationError


@contextlib.contextmanager
def swapped_params(handles, arrays):
    """Inside, each parameter handle of ``handles`` (a Parameter's
    ``_data``) holds the array given for it — a tracer of the function
    being traced — and on leaving what it held before.  Yields a list
    that, once left, holds ``(index, value)`` of every handle written
    inside (running statistics)."""
    originals = [h._data for h in handles]
    for h, a in zip(handles, arrays):
        h._data = a
    written = []
    try:
        yield written
    finally:
        for i, (h, a, orig) in enumerate(zip(handles, arrays, originals)):
            if h._data is not a:
                written.append((i, h._data))
            h._data = orig


class _Keeping(threading.local):
    """What the trace of a training step says of its marked blocks, set
    by whoever drives the trace (:func:`keeping`) and read by
    ``Block._forward_recomputed``."""
    kept = frozenset()  # ids of the marked blocks that are not made again
    seen = None         # {id(block): [applied in the step's own trace?]}
    trace = None        # the trace the step's forward runs in


_KEEPING = _Keeping()


@contextlib.contextmanager
def keeping(kept=(), seen=None):
    """Round the forward of a training step as ``parallel.TrainStep``
    traces it: inside, a marked block whose id is in ``kept`` is not
    made again (its forward runs as an unmarked block's would, with the
    random keys it would have drawn under the checkpoint), every other
    marked block is.  Only a block applied in the trace this is opened
    in can be kept: one under a ``lax.scan`` or another block's
    checkpoint would hold its interior once a trip, so it is made again
    whatever ``kept`` says.  With ``seen`` (a dict) every application
    of a marked block notes under ``id(block)``, in the order of the
    forward, whether it stood in that trace: what a plan is made
    from."""
    was = _KEEPING.kept, _KEEPING.seen, _KEEPING.trace
    _KEEPING.kept, _KEEPING.seen = frozenset(kept), seen
    _KEEPING.trace = jax.core.get_opaque_trace_state()
    try:
        yield
    finally:
        _KEEPING.kept, _KEEPING.seen, _KEEPING.trace = was


def _placed_bytes(params):
    """Bytes of the parameters of ``params`` that hold an array."""
    return sum(p._data._data.nbytes for p in params.values()
               if p._data is not None)


class Block:
    """Base class for all neural network layers and models."""

    #: the name this block's device ops carry (``jax.named_scope`` in
    #: ``__call__``): the name its parent registered it under — the
    #: attribute name, or ``<index>_<Type>`` for a child a container
    #: numbered — and the type's name at the root
    _scope_name = None

    #: set by :meth:`recompute`
    _recompute = False

    #: the values a marked block keeps beside its inputs, by the name
    #: the op that makes them gives them (``checkpoint_name``): what is
    #: dear to make again for its size.  Filled by the ops that mark.
    _recompute_keeps = (ATTENTION_KERNEL_OUT,)

    def __init__(self):
        self._children = OrderedDict()
        self._reg_params = OrderedDict()
        self._forward_hooks = OrderedDict()
        self._forward_pre_hooks = OrderedDict()
        self._hook_id = 0

    # -- attribute registration (block.py __setattr__) --------------------
    def __setattr__(self, name, value):
        if isinstance(value, Block):
            existing = self.__dict__.get("_children")
            if existing is not None:
                existing[name] = value
                value.__dict__["_scope_name"] = name
        elif isinstance(value, Parameter):
            existing = self.__dict__.get("_reg_params")
            if existing is not None:
                existing[name] = value
                if value._name in (None, "param"):
                    value._name = name
        super().__setattr__(name, value)

    def __delattr__(self, name):
        self._children.pop(name, None)
        self._reg_params.pop(name, None)
        super().__delattr__(name)

    def register_child(self, block, name=None):
        index = str(len(self._children))
        block.__dict__["_scope_name"] = name or "%s_%s" % (
            index, type(block).__name__)
        name = name or index
        self._children[name] = block
        super().__setattr__("_child_" + name, block)

    # -- params -----------------------------------------------------------
    @property
    def params(self):
        return self._reg_params

    def collect_params(self, select=None):
        """Structural-path-keyed dict of all Parameters (2.0 semantics:
        block.py collect_params with regex select)."""
        ret = OrderedDict()
        pattern = re.compile(select) if select else None

        def walk(block, prefix):
            for name, p in block._reg_params.items():
                key = prefix + name if prefix else name
                if pattern is None or pattern.match(key):
                    ret[key] = p
            for cname, child in block._children.items():
                walk(child, prefix + cname + ".")

        walk(self, "")
        return ret

    def initialize(self, init=None, device=None, ctx=None, verbose=False,
                   force_reinit=False):
        default_init = init or init_mod.Uniform()
        params = self.collect_params()
        with _profiler.build_span("mx.gluon.initialize") as span:
            for name, p in params.items():
                if p._name in ("param",):
                    p._name = name
                p.initialize(init=p.init, ctx=device if device is not None
                             else ctx, default_init=default_init,
                             force_reinit=force_reinit)
            span.set(params=len(params), bytes=_placed_bytes(params))

    def hybridize(self, active=True, **kwargs):
        """Plain Blocks cascade to children (reference ``block.py``
        Block.hybridize: non-hybrid containers like ``Sequential``
        activate tracing on every hybridizable descendant)."""
        for child in self._children.values():
            child.hybridize(active, **kwargs)

    def recompute(self, active=True):
        """Mark this block as one that may be made again: inside a traced
        training step (``parallel.TrainStep``, a hybridized parent under
        ``autograd.record``) its forward runs under ``jax.checkpoint``
        and what the backward needs of its interior is computed a second
        time.  Kept are its inputs and what an op names as dear to make
        again (``_recompute_keeps``): the flash attention kernel's
        output and row sums, so that the kernel runs once.  Whether the
        block *is* made again is ``TrainStep``'s to say, from the memory
        the device has left beside the compiled step: as many of the
        marked blocks as fit, the last of the forward first, are not
        checkpointed at all and hold their interior like unmarked ones
        (:func:`keeping`).  Without such a plan — a hybridized parent,
        a device that does not report its memory, a block applied under
        a ``lax.scan`` — every marked block is made again.  Values,
        gradients and random draws do not change with the plan.  Outside
        a trace, and in inference, the mark does nothing."""
        self._recompute = bool(active)
        return self

    def zero_grad(self):
        for p in self.collect_params().values():
            p.zero_grad()

    def reset_ctx(self, ctx):
        for p in self.collect_params().values():
            p.reset_ctx(ctx)

    reset_device = reset_ctx

    def cast(self, dtype):
        params = self.collect_params()
        with _profiler.build_span("mx.gluon.cast") as span:
            for p in params.values():
                p.cast(dtype)
            span.set(params=len(params), bytes=_placed_bytes(params))
        return self

    def apply(self, fn):
        for child in self._children.values():
            child.apply(fn)
        fn(self)
        return self

    def setattr(self, name, value):
        """Set an attribute on all Parameters (e.g. grad_req)."""
        for p in self.collect_params().values():
            setattr(p, name, value)

    def share_parameters(self, shared):
        own = self.collect_params()
        for k, v in shared.items():
            if k in own:
                self._set_param_by_path(k, v)
        return self

    def _set_param_by_path(self, path, param):
        parts = path.split(".")
        blk = self
        for part in parts[:-1]:
            blk = blk._children[part]
        blk._reg_params[parts[-1]] = param
        object.__setattr__(blk, parts[-1], param)

    # -- hooks ------------------------------------------------------------
    def register_forward_hook(self, hook):
        self._hook_id += 1
        self._forward_hooks[self._hook_id] = hook
        return _HookHandle(self._forward_hooks, self._hook_id)

    def register_forward_pre_hook(self, hook):
        self._hook_id += 1
        self._forward_pre_hooks[self._hook_id] = hook
        return _HookHandle(self._forward_pre_hooks, self._hook_id)

    # -- save / load ------------------------------------------------------
    def save_parameters(self, filename, deduplicate=False):
        """block.py:341 — parameter file (npz container, bf16-safe)."""
        params = self.collect_params()
        arg_dict = {}
        seen = {}
        for name, p in params.items():
            if p._data is None:
                continue
            if deduplicate and id(p) in seen:
                continue
            seen[id(p)] = name
            arg_dict[name] = p.data()
        serialization.save_params(filename, arg_dict)
        self._refresh_manifest_entry(filename)

    @staticmethod
    def _refresh_manifest_entry(filename):
        """A sibling checksum manifest (written by CheckpointHandler /
        ``mx.fault``) would go stale when this file is overwritten
        directly, poisoning every future verified load — update its
        entry for this file in place."""
        import os as _os
        if not isinstance(filename, str):
            return
        stem = filename[:-len(".params")] \
            if filename.endswith(".params") else filename
        manifest = stem + ".manifest.json"
        if not _os.path.exists(manifest):
            return
        import json as _json
        from .. import fault as _fault
        try:
            with open(manifest, "rb") as f:
                data = _json.loads(f.read().decode())
            entries = data["files"]
        except (OSError, ValueError, KeyError, UnicodeDecodeError):
            # unreadable manifest: remove it rather than let it reject
            # the fresh file forever
            try:
                _os.remove(manifest)
            except OSError:
                pass
            return
        base = _os.path.dirname(_os.path.abspath(manifest))
        rel = _os.path.relpath(_os.path.abspath(filename), base)
        if rel in entries:
            # a hash/write failure here must propagate, NOT delete the
            # manifest — it still correctly covers the other files
            entries[rel] = {"sha256": _fault.file_sha256(filename),
                            "bytes": _os.path.getsize(filename)}
            _fault._atomic_write_bytes(
                manifest, _json.dumps(data, indent=1).encode())

    def load_parameters(self, filename, device=None, ctx=None,
                        allow_missing=False, ignore_extra=False,
                        cast_dtype=False, dtype_source="current"):
        """block.py:379.  When a ``<filename>.manifest.json`` checksum
        manifest sits next to the file (written by CheckpointHandler or
        ``mx.fault``), it is verified first so a torn file raises
        :class:`mxnet_tpu.fault.CorruptCheckpointError` before any
        parameter is touched — callers can fall back to an older
        checkpoint with the net state unmodified."""
        import os as _os
        if isinstance(filename, str):
            stem = filename[:-len(".params")] \
                if filename.endswith(".params") else filename
            manifest = stem + ".manifest.json"
            if _os.path.exists(manifest):
                from .. import fault as _fault
                # verify only this file's entry: the manifest may list
                # trainer states a params-only deployment never copied
                ok, bad = _fault.verify_manifest(
                    manifest, only=[_os.path.basename(filename)])
                if not ok:
                    raise _fault.CorruptCheckpointError(
                        "checkpoint %s failed manifest verification: %s"
                        % (filename, ", ".join(bad)))
        loaded = serialization.load_params(filename)
        params = self.collect_params()
        if not allow_missing:
            for name in params:
                if name not in loaded:
                    raise AssertionError(
                        "Parameter %s is missing in file %s" % (name, filename))
        if not ignore_extra:
            for name in loaded:
                if name not in params:
                    raise AssertionError(
                        "Parameter %s loaded from file %s is not present in "
                        "this block" % (name, filename))
        for name, p in params.items():
            if name in loaded:
                val = loaded[name]
                if cast_dtype and dtype_source == "current" and p._data is not None:
                    val = val.astype(p.dtype)
                elif cast_dtype and dtype_source == "saved":
                    p.dtype = val.dtype
                p.set_data(val)

    def load_dict(self, param_dict, device=None, allow_missing=False,
                  ignore_extra=False, cast_dtype=False):
        params = self.collect_params()
        for name, p in params.items():
            if name in param_dict:
                p.set_data(param_dict[name])
            elif not allow_missing:
                raise AssertionError("Parameter %s missing" % name)

    # -- summary ----------------------------------------------------------
    def summary(self, *inputs):
        lines = ["-" * 64,
                 "%-28s %-24s %s" % ("Layer", "Param shape", "#Params"),
                 "=" * 64]
        total = 0
        for name, p in self.collect_params().items():
            n = 1
            for d in (p.shape or ()):
                n *= max(d, 0)
            total += n
            lines.append("%-28s %-24s %d" % (name, str(p.shape), n))
        lines.append("=" * 64)
        lines.append("Total params: %d" % total)
        print("\n".join(lines))

    # -- call -------------------------------------------------------------
    def __call__(self, *args, **kwargs):
        for hook in self._forward_pre_hooks.values():
            hook(self, args)
        with jax.named_scope(self._scope_name or type(self).__name__):
            if self._recompute and _tape.is_training() and any(
                    isinstance(a, NDArray)
                    and isinstance(a._data, jax.core.Tracer) for a in args):
                out = self._forward_recomputed(args, kwargs)
            else:
                out = self.forward(*args, **kwargs)
        for hook in self._forward_hooks.values():
            hook(self, args, out)
        return out

    def forward(self, *args, **kwargs):
        raise NotImplementedError

    def _forward_recomputed(self, args, kwargs):
        """``forward`` as a pure function of this block's parameter
        arrays and its NDArray arguments, under ``jax.checkpoint``, which
        keeps the values named in ``_recompute_keeps`` and no other.  The
        parameters' handles hold the enclosing trace's values; inside
        the checkpointed function they hold that function's own
        arguments (``swapped_params``), so that nothing of the block's
        interior is closed over.  A handle written inside
        (running statistics) comes out as an output and is written
        back.  Random keys derive from one key drawn outside.  A block
        the trace's driver keeps (:func:`keeping`) runs ``forward`` bare,
        under the key it would have been given."""
        key = _random.new_key() if _random._STATE.trace_stack else None
        if _KEEPING.trace is not None:
            own = jax.core.get_opaque_trace_state() == _KEEPING.trace
            if _KEEPING.seen is not None:
                _KEEPING.seen.setdefault(id(self), []).append(own)
            if own and id(self) in _KEEPING.kept:
                with _random.trace_scope(key) if key is not None \
                        else contextlib.nullcontext():
                    return self.forward(*args, **kwargs)
        handles = list({id(p._data): p._data
                        for p in self.collect_params().values()
                        if p._data is not None}.values())
        where = [i for i, a in enumerate(args) if isinstance(a, NDArray)]
        meta = {}

        def pure(arrays, key, *xs):
            call = list(args)
            for i, x in zip(where, xs):
                call[i] = NDArray(x)
            with swapped_params(handles, arrays) as written, \
                    _random.trace_scope(key) if key is not None \
                    else contextlib.nullcontext():
                out = self.forward(*call, **kwargs)
            outs, meta["tree"] = _flatten_out(out)
            if not all(isinstance(o, NDArray) for o in outs):
                raise TypeError("a recomputed block returns NDArrays; %s "
                                "returned %r" % (type(self).__name__, outs))
            meta["written"] = [i for i, _ in written]
            return (tuple(o._data for o in outs),
                    tuple(v for _, v in written))

        outs, written = jax.checkpoint(
            pure, policy=jax.checkpoint_policies.save_only_these_names(
                *self._recompute_keeps))(
            [h._data for h in handles], key,
            *[args[i]._data for i in where])
        for i, v in zip(meta["written"], written):
            handles[i]._data = v
        return _unflatten_out([NDArray(o) for o in outs], meta["tree"])

    def __repr__(self):
        s = "{name}(\n{modstr}\n)"
        modstr = "\n".join("  ({key}): {block}".format(
            key=key, block=_indent(repr(block), 2))
            for key, block in self._children.items())
        if not self._children:
            return self.__class__.__name__ + "()"
        return s.format(name=self.__class__.__name__, modstr=modstr)


class _HookHandle:
    def __init__(self, hooks, hid):
        self._hooks = hooks
        self._id = hid

    def detach(self):
        self._hooks.pop(self._id, None)


def _indent(s, num):
    lines = s.split("\n")
    return ("\n" + " " * num).join(lines)


class _CachedGraph:
    """The jit-compiled trace of one HybridBlock — the CachedOp analog
    (src/imperative/cached_op.cc:776).  One instance per (input signature,
    train_mode) pair."""

    def __init__(self, block, params, mutated_idx, jitted, n_out, out_tree):
        self.block = block
        self.params = params          # list[(name, Parameter)]
        self.mutated_idx = mutated_idx  # indices into params written at trace
        self.jitted = jitted          # jit fn(key, param_arrays, *inputs)
        self.n_out = n_out
        self.out_tree = out_tree


class HybridBlock(Block):
    """A Block that can be traced and compiled (``hybridize()``)."""

    def __init__(self):
        super().__init__()
        self._active = False
        self._cached_graphs = {}
        self._flags = {}
        self._partition_backend = None

    def hybridize(self, active=True, backend=None, clear=True, **kwargs):
        """block.py:1419 — enable traced/compiled execution.

        ``static_alloc``/``static_shape`` are accepted for compatibility;
        XLA always allocates statically for a traced graph.
        """
        self._active = active
        self._flags.update(kwargs)
        self._partition_backend = backend
        if clear:
            self._cached_graphs.clear()
        for child in self._children.values():
            if isinstance(child, HybridBlock):
                child.hybridize(active=False if not active else False,
                                clear=clear)
        # note: only the outermost hybridized block compiles; children run
        # inside its trace (matches reference: inner CachedOps are inlined).
        self._active = active

    def optimize_for(self, x, *args, backend=None, clear=True, **kwargs):
        """block.py optimize_for — partition/compile for a backend.  XLA is
        the only backend; equivalent to hybridize + one warmup call."""
        self.hybridize(True, backend=backend, clear=clear, **kwargs)
        return self(x, *args)

    def infer_shape(self, *args):
        """Trigger deferred parameter shape inference without running a full
        forward (uses jax.eval_shape under the hood)."""
        self._infer_shapes_eagerly(args)

    def _infer_shapes_eagerly(self, args):
        with _tape.suspend_recording():
            self.forward(*args)

    # -- tracing ----------------------------------------------------------
    def _signature(self, args, kwargs):
        sig = [_tape.is_training(), _tape.is_recording()]
        for a in args:
            if isinstance(a, NDArray):
                sig.append(("nd", a.shape, str(a.dtype)))
            else:
                sig.append(("py", a if not isinstance(a, (list, tuple))
                            else tuple(a)))
        for k in sorted(kwargs):
            v = kwargs[k]
            sig.append((k, v.shape if isinstance(v, NDArray) else v))
        return tuple(sig)

    def _build_cache(self, args, kwargs):
        # materialize deferred params first (the reference's shape-inference
        # pass inside _build_cache, block.py:1135)
        if any(p._data is None for p in self.collect_params().values()):
            with _tape.suspend_recording():
                self.forward(*args, **kwargs)

        params = list(self.collect_params().items())
        block = self
        meta = {}

        def jit_body(key, param_list, *xs):
            with swapped_params([p._data for _, p in params],
                                param_list) as mutated, \
                    _tape.suspend_recording(), _random.trace_scope(key):
                out = block.forward(*[NDArray(a) for a in xs], **kwargs)
            outs, tree = _flatten_out(out)
            meta["out_tree"] = tree
            meta["n_out"] = len(outs)
            meta["mut_idx"] = tuple(i for i, _ in mutated)
            return tuple(o._data if isinstance(o, NDArray) else o
                         for o in outs) + tuple(v for _, v in mutated)

        body = jit_body
        if self._partition_backend:
            from ..subgraph import get_backend
            transform = get_backend(self._partition_backend)
            if transform is not None:
                body = transform(jit_body, self)
        jitted = jax.jit(body)
        key0 = _random.new_key()
        param_arrays = [p._data._data for _, p in params]
        in_arrays = [a._data if isinstance(a, NDArray) else a for a in args]
        jitted(key0, param_arrays, *in_arrays)  # compile + discover meta
        graph = _CachedGraph(self, params, meta["mut_idx"], jitted,
                             meta["n_out"], meta["out_tree"])
        return graph

    def _call_cached(self, args, kwargs):
        sig = self._signature(args, kwargs)
        graph = self._cached_graphs.get(sig)
        if graph is None:
            graph = self._build_cache(args, kwargs)
            self._cached_graphs[sig] = graph
        params = graph.params
        key = _random.new_key()
        param_handles = [p._data for _, p in params]
        in_handles = [a for a in args if isinstance(a, NDArray)]

        if not _tape.is_recording():
            # fast inference path: no tape node, no handle wrapping —
            # the analog of CachedOp's bulked static path (cached_op.cc:546)
            flat_arrays = graph.jitted(key, [h._data for h in param_handles],
                                       *[a._data for a in in_handles])
            outs = [NDArray(a) for a in flat_arrays[:graph.n_out]]
            for j, pi in enumerate(graph.mutated_idx):
                param_handles[pi]._data = flat_arrays[graph.n_out + j]
            return _unflatten_out(outs, graph.out_tree)

        def run_fn(key_arr, *arrs):
            n_p = len(params)
            plist = list(arrs[:n_p])
            xs = arrs[n_p:]
            return graph.jitted(key_arr, plist, *xs)

        all_inputs = [NDArray(key)] + param_handles + in_handles
        flat = apply_op(run_fn, all_inputs,
                        n_out=graph.n_out + len(graph.mutated_idx),
                        name=type(self).__name__)
        if not isinstance(flat, (list, tuple)):
            flat = [flat]
        outs = flat[:graph.n_out]
        # write back mutated aux state (running stats) — detached
        for j, pi in enumerate(graph.mutated_idx):
            newval = flat[graph.n_out + j]
            handle = param_handles[pi]
            handle._data = newval._data
            # aux updates carry no gradient history
        return _unflatten_out(list(outs), graph.out_tree)

    def __call__(self, *args, **kwargs):
        if self._active:
            for hook in self._forward_pre_hooks.values():
                hook(self, args)
            with jax.named_scope(self._scope_name or type(self).__name__):
                out = self._call_cached(args, kwargs)
            for hook in self._forward_hooks.values():
                hook(self, args, out)
            return out
        return super().__call__(*args, **kwargs)

    # -- export -----------------------------------------------------------
    def export(self, path, epoch=0, remove_amp_cast=True,
               example_inputs=None):
        """block.py:1514 — serialize the compiled model.

        The reference writes ``-symbol.json`` (nnvm graph) + ``.params``;
        the TPU build writes a serialized StableHLO exported function
        (``jax.export``) + the same npz params.  Reload with
        ``SymbolBlock.imports``; the deserialized program runs without the
        original Python model code — the exact role of the reference's
        symbol JSON."""
        from jax import export as jax_export

        params = self.collect_params()
        param_file = "%s-%04d.params" % (path, epoch)
        serialization.save_params(
            param_file, {k: p.data() for k, p in params.items()
                         if p._data is not None})
        sym_file = "%s-symbol.stablehlo" % path
        if example_inputs is None:
            raise ValueError(
                "export requires example_inputs=(x, ...) to trace the "
                "deployment graph (the reference infers them from the "
                "cached graph; pass the same arrays you called the block "
                "with)")
        if not isinstance(example_inputs, (list, tuple)):
            example_inputs = (example_inputs,)
        names = list(params.keys())
        block = self

        def deploy_fn(param_list, *inputs):
            with swapped_params([params[n]._data for n in names],
                                param_list), _tape.suspend_recording():
                out = block.forward(*[NDArray(a) for a in inputs])
            outs, _ = _flatten_out(out)
            return tuple(o._data if isinstance(o, NDArray) else o
                         for o in outs)

        param_arrays = [params[n]._data._data for n in names]
        in_arrays = [a._data if isinstance(a, NDArray) else jnp.asarray(a)
                     for a in example_inputs]
        exported = jax_export.export(jax.jit(deploy_fn))(param_arrays,
                                                         *in_arrays)
        from ..utils.serialization import atomic_write
        with atomic_write(sym_file) as f:
            import json as _json
            header = _json.dumps({"param_names": names}).encode()
            f.write(len(header).to_bytes(8, "little") + header +
                    exported.serialize())
        return sym_file, param_file

    def reset_cache(self):
        self._cached_graphs.clear()


def _flatten_out(out):
    """Flatten forward output (NDArray | tuple/list/dict) to list + tree."""
    if isinstance(out, NDArray):
        return [out], None
    if isinstance(out, (tuple, list)):
        flat, trees = [], []
        for o in out:
            f, t = _flatten_out(o)
            flat.extend(f)
            trees.append((len(f), t))
        return flat, (type(out), trees)
    if isinstance(out, dict):
        flat, trees = [], []
        for k in out:
            f, t = _flatten_out(out[k])
            flat.extend(f)
            trees.append((k, len(f), t))
        return flat, (dict, trees)
    return [out], "leaf"


def _unflatten_out(flat, tree):
    if tree is None:
        return flat[0]
    if tree == "leaf":
        return flat[0]
    typ, trees = tree
    if typ is dict:
        out = {}
        i = 0
        for k, n, t in trees:
            out[k] = _unflatten_out(flat[i:i + n], t)
            i += n
        return out
    res = []
    i = 0
    for n, t in trees:
        res.append(_unflatten_out(flat[i:i + n], t))
        i += n
    return typ(res)
