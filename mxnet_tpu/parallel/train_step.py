"""Fused SPMD training step: forward + backward + optimizer update as ONE
compiled XLA program over a device mesh.

This is the TPU-native performance path that subsumes the reference's whole
step pipeline (SURVEY.md §3.4): Trainer._allreduce_grads (kvstore pushpull)
→ XLA inserts the gradient psum from shardings; priority-overlap of comm
and backward (``trainer.py:395,407``) → XLA's latency-hiding scheduler;
fused optimizer kernels (``multi_sgd_update`` etc.) → the update is fused
into the same program with donated buffers.

``TrainStep`` wraps a Gluon block + loss + mx optimizer.  The optimizer's
pure ``_rule`` is reused verbatim, so all 17 mx optimizers work sharded.
ZeRO-1 (``zero1=True``) shards optimizer states over ``dp`` — the analog of
the reference's server-side update sharding (``kvstore_dist_server.h:346``).
"""
from __future__ import annotations

import contextlib
import functools
import hashlib
import json
import os
import warnings

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from .. import _tape
from .. import fault as _fault
from .. import profiler as _profiler
from ..gluon.block import keeping, swapped_params
from ..ndarray.ndarray import NDArray
from ..numpy import random as _random
from ..utils import compile_cache
from ..utils.serialization import atomic_write
from .mesh import mesh_scope
from .sharding import _valid_spec, param_sharding

P = PartitionSpec

#: device memory a recomputation plan leaves unasked.  The step's
#: temporaries are one allocation of several GB that must find a
#: contiguous range in a heap the caller's arrays have been made in and
#: freed from, and whoever compiles the lowered step again beside the
#: running one (a memory report does) loads a second copy of its code.
_RESERVE_BYTES = 512 << 20
#: programs a plan may compile for one batch signature: the step with
#: every marked block made again (the reading the others are measured
#: from, and what runs when nothing more fits) and two tries.  Each is a
#: compile of the whole step, most of a minute at a model's real size.
_COMPILES = 3


#: what the runtime raises when a program does not fit the device: a v5e
#: refuses to load the step with a plain ``ValueError`` ("RESOURCE_EXHAUSTED:
#: Error loading program 'jit_step': Attempting to reserve 5.41G at the
#: bottom of memory ..."; chip run, PR 33), the compiler with jax's own
_DEVICE_ERRORS = (jax.errors.JaxRuntimeError, ValueError)


def _out_of_memory(error):
    return "RESOURCE_EXHAUSTED" in str(error)


def _is_ndarray(x):
    return isinstance(x, NDArray)


def _raw(x):
    return x._data if isinstance(x, NDArray) else x


def _signature(arrays):
    return tuple((tuple(a.shape), str(a.dtype)) for a in arrays)


def _marked_blocks(net):
    """``[(path, block)]`` of the blocks under ``net`` that carry
    ``Block.recompute()``'s mark, in the order of the tree."""
    found, met = [], set()

    def walk(block, path):
        if block._recompute and id(block) not in met:
            met.add(id(block))
            found.append((path or type(block).__name__, block))
        for name, child in block._children.items():
            walk(child, "%s.%s" % (path, name) if path else name)

    walk(net, "")
    return found


def _device_memory(device):
    """``(bytes_limit, bytes_in_use)`` of a device that reports them,
    else None (the CPU; a described topology has no device at all)."""
    stats = device.memory_stats()
    if not stats or "bytes_limit" not in stats \
            or "bytes_in_use" not in stats:
        return None
    return int(stats["bytes_limit"]), int(stats["bytes_in_use"])


def blocks_to_spare(readings, n, room):
    """The next number of blocks to try sparing, or None when the search
    is over.  A step has ``n`` marked blocks that can be spared the
    recomputation, the last of the forward first; ``readings[k]`` is
    what the compiled step that spares the last ``k`` needs for its
    temporaries and code (None where the compiler or the device refused
    it), ``readings[0]`` always there; ``room`` is what the device has
    for them.  Everything is tried first; after a try that does not fit,
    the number a straight line between the largest that fits and the
    smallest that does not puts at ``room`` (the midpoint where the
    latter gave no reading).  The first block spared costs least (it
    takes the place of what its own backward held anyway), so the line
    errs towards fewer."""
    if readings[0] is None or readings[0] > room:
        return None
    lo = max(k for k, need in readings.items()
             if need is not None and need <= room)
    over = [k for k in readings if k > lo]
    hi = min(over) if over else n + 1
    if hi - lo <= 1:
        return None
    if not over:
        return n
    if readings[hi] is None:
        return (lo + hi) // 2
    a_block = (readings[hi] - readings[lo]) / (hi - lo)
    return min(max(lo + int((room - readings[lo]) / a_block), lo + 1),
               hi - 1)


class _Planned:
    """The step as planned for batches of one signature."""

    def __init__(self, jitted, run, plan, candidates, readings):
        self.jitted = jitted            # what ``lower()`` lowers
        self.run = run                  # its executable, which runs
        self.plan = plan                # ``TrainStep.recompute_plan``
        self.candidates = candidates    # paths of the blocks it could spare
        self.readings = readings        # ``blocks_to_spare``'s
        self.ran = False        # the device has run a plan of this signature


class TrainStep:
    """Compile ``(params, states, batch) -> (loss, params', states')``.

    Parameters
    ----------
    net : HybridBlock (initialized)
    loss_fn : callable(out, label) -> per-sample loss NDArray
    optimizer : mx Optimizer instance
    mesh : jax.sharding.Mesh or None (single device)
    param_rules : [(regex, spec tuple)] parameter sharding rules
    batch_spec : PartitionSpec for each batch input (default P('dp'))
    zero1 : shard optimizer states over 'dp'
    forward_fn : optional callable(net, *batch)->scalar loss overriding the
        default ``loss_fn(net(x), y).mean()`` convention.  It may return
        ``(loss, aux)`` instead, ``aux`` any pytree of arrays computed on
        the way (per-exit losses, statistics to log): the step then
        returns ``(loss, aux)``, the aux not differentiated.

    A block marked with ``Block.recompute()`` may be made again in the
    backward: it then keeps its input and what its ops name as dear to
    make again (a flash attention kernel's output and row sums).
    Whether it is, is decided here, from what only the step can observe.
    On one device that reports its memory, the first call with batches
    of a signature compiles the step ahead of time with every marked
    block made again, reads the compiled step's temporaries and code
    and the device's ``bytes_limit`` and ``bytes_in_use``, and spares as
    many marked blocks as fit in what is left after ``_RESERVE_BYTES``,
    the last of the forward first (what a later block holds it holds
    for the shorter time): a spared block is not checkpointed at all.
    Candidates are the marked blocks the trace met in the step's own
    trace; one under a ``lax.scan`` is always made again.  How many fit
    is searched (``blocks_to_spare``) with each try compiled and its own
    ``memory_analysis()`` held to the room, ``_COMPILES`` programs at
    most; what runs is the largest try that fits, else the step first
    compiled.  The plan is kept beside the persistent compile cache
    (``mx_recompute_plan_<key>.json``, keyed by shapes, marked blocks,
    optimizer, device and jax version), so that a later start compiles
    or loads the planned step alone, and still verifies it; a plan is
    kept for every batch signature met, so a shape that comes back
    runs what it ran before.  A planned step the device refuses for
    want of memory, at any call, is planned again from the device's
    memory as it is then, while its arguments are still there (the plan
    file follows only where the refused step had never run).
    ``recompute_plan`` holds the last plan's record; ``lower()`` lowers
    the program that runs.  A step with no marked block, under a mesh,
    against a described topology or on a device without
    ``memory_stats()`` is built and compiled as if there were no plan.
    Parameters and optimizer states are donated to the step and updated
    in place.
    """

    def __init__(self, net, loss_fn, optimizer, mesh=None, param_rules=None,
                 batch_spec=None, zero1=False, forward_fn=None, aot=False):
        self.net = net
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        self.mesh = mesh
        self.param_rules = param_rules
        self.zero1 = zero1
        self.forward_fn = forward_fn
        # aot=True: ``mesh`` may be built from a PJRT *topology
        # description* (jax.experimental.topologies) instead of live
        # devices — params/states are never placed on the mesh, only
        # lowered/compiled against it.  This is the chips-free
        # compile path (maxtext-style AOT): ``lower()``/``compile()``
        # produce the exact TPU executable text a real slice would run,
        # which is what tools/hlo_snapshot.py pins; ``__call__`` raises.
        self.aot = aot
        self._t = 0
        self._batch_spec = batch_spec
        self._jitted = None     # every marked block made again: jit's own
        self._plan_device = None  # whose memory plans are made from, if any
        self._planned = {}      # batch signature -> _Planned
        #: how the last plan chose the blocks to spare (None: not planned)
        self.recompute_plan = None
        self._states = None
        self._shardings = None
        with _profiler.build_span("mx.train.step.init") as span:
            self._params = list(net.collect_params().items())
            for name, p in self._params:
                if p._data is None:
                    raise ValueError(
                        "TrainStep requires initialized parameters; %s is "
                        "not (run one forward or pass concrete shapes)"
                        % name)
            self._trainable = [name for name, p in self._params
                               if p.grad_req != "null"]
            self._setup()
            span.set(params=len(self._params), state_bytes=sum(
                a.nbytes for arrays in self._states.values()
                for a in arrays))

    # -- sharding & states -------------------------------------------------
    def _setup(self):
        params = dict(self._params)
        mesh = self.mesh
        if mesh is not None:
            self._shardings = param_sharding(
                params, mesh, rules=self.param_rules, default=P())
            if not self.aot:
                for name, p in self._params:
                    p._data._data = jax.device_put(p._data._data,
                                                   self._shardings[name])
        # optimizer states mirror param shapes (entries with other shapes —
        # e.g. Nadam's scalar momentum schedule — are replicated)
        self._states = {}
        for i, (name, p) in enumerate(self._params):
            if name not in self._trainable:
                continue
            st = self.optimizer.create_state(i, p.data())
            arrays = tuple(s._data for s in st)
            if mesh is not None and not self.aot:
                arrays = tuple(
                    jax.device_put(a, NamedSharding(
                        mesh, self._state_spec(name, p, a.shape)))
                    for a in arrays)
            self._states[name] = arrays

    def _state_spec(self, name, p, st_shape):
        """PartitionSpec for one optimizer-state entry."""
        if tuple(st_shape) != tuple(p.shape):
            return _valid_spec(P(), st_shape, self.mesh,
                               param_name=name + ".state")
        if self.zero1:
            return _valid_spec(P("dp"), st_shape, self.mesh,
                               param_name=name + ".state")
        return self._shardings[name].spec

    # -- the pure step -----------------------------------------------------
    def _build(self, batch_arrays, kept=(), seen=None):
        """The jitted step.  ``kept`` and ``seen`` are
        ``gluon.block.keeping``'s: the ids of the marked blocks that are
        not made again, and where the trace notes the marked blocks it
        met."""
        net, params, trainable = self.net, self._params, self._trainable
        opt = self.optimizer
        loss_fn, forward_fn = self.loss_fn, self.forward_fn
        name_to_idx = {name: i for i, (name, _) in enumerate(params)}

        def run_forward(all_arrays, key, batch):
            with swapped_params(
                    [p._data for _, p in params],
                    [all_arrays[name] for name, _ in params]) as written, \
                    _tape.suspend_recording(), _random.trace_scope(key), \
                    keeping(kept, seen):
                _tape.set_training(True)
                try:
                    aux = None
                    if forward_fn is not None:
                        loss = forward_fn(net, *[NDArray(b)
                                                 for b in batch])
                        if isinstance(loss, tuple):
                            loss, aux = loss
                    else:
                        data = NDArray(batch[0])
                        label = NDArray(batch[1])
                        # forward, not __call__: a hybridized net's
                        # cached program is not the step's; the root's
                        # own recompute mark is honoured here instead
                        out = net._forward_recomputed((data,), {}) \
                            if net._recompute else net.forward(data)
                        loss = loss_fn(out, label).mean()
                finally:
                    _tape.set_training(False)
            mutated = {params[i][0]: v for i, v in written}
            loss_arr, aux = jax.tree_util.tree_map(
                _raw, (loss, aux), is_leaf=_is_ndarray)
            return loss_arr, (mutated, aux)

        def step(param_arrays, opt_states, t, lr, key, *batch):
            # the body runs once, as jit traces it: with the step's
            # mesh in scope, so that what asks current_mesh() — the
            # blocks' activation constraints, the per-shard wrap of the
            # Pallas kernels — sees it without a mesh_scope of the
            # caller's
            with mesh_scope(self.mesh):
                return sharded_step(param_arrays, opt_states, t, lr, key,
                                    *batch)

        def sharded_step(param_arrays, opt_states, t, lr, key, *batch):
            train_sub = {n: param_arrays[n] for n in trainable}
            frozen = {n: a for n, a in param_arrays.items()
                      if n not in train_sub}

            def loss_of(tr):
                # device names: under value_and_grad jax writes this
                # scope's ops as jvp(forward)/... and their backward as
                # transpose(jvp(forward))/...
                with jax.named_scope("forward"):
                    return run_forward({**frozen, **tr}, key, batch)

            (loss, (mutated, aux)), grads = jax.value_and_grad(
                loss_of, has_aux=True)(train_sub)
            new_params = dict(frozen)
            new_states = {}
            tf = t.astype(jnp.int32)
            with jax.named_scope("optimizer"):
                for name in trainable:
                    i = name_to_idx[name]
                    w = param_arrays[name]
                    g = grads[name].astype(jnp.float32)
                    if self.zero1 and self.mesh is not None:
                        # ZeRO-1 comm/compute overlap: pin each param's
                        # grad to the dp-sharded state spec BEFORE the
                        # update.  The sharded update then lives in the
                        # PROGRAM, not in inferred propagation from the
                        # state out_shardings: each parameter's reduce
                        # chain is an independent op issuable as soon as
                        # that grad is ready (never one combined tail
                        # collective), the update runs on the 1/dp shard,
                        # and the only post-update traffic is the
                        # updated-param all-gather — which the TPU
                        # scheduler pairs into async start/done around
                        # remaining backward compute (asserted by
                        # hlo.check_collective_overlap /
                        # check_overlap_window on the AOT artifact).
                        # Partitioners with partial->tiled resharding
                        # lower the pinned reduce to a true
                        # reduce-scatter.
                        gspec = self._state_spec(name, params[i][1],
                                                 w.shape)
                        g = jax.lax.with_sharding_constraint(
                            g, NamedSharding(self.mesh, gspec))
                    if opt.clip_gradient is not None:
                        g = jnp.clip(g, -opt.clip_gradient,
                                     opt.clip_gradient)
                    wd = jnp.float32(opt._get_wd(i))
                    lr_i = lr * jnp.float32(
                        params[i][1].lr_mult
                        if hasattr(params[i][1], "lr_mult") else 1.0)
                    scalars = tuple(opt._scalar_args(i))
                    res = opt._rule(w, g, lr_i, wd, tf, scalars,
                                    opt_states.get(name, ()))
                    new_params[name] = res[0]
                    new_states[name] = res[1]
            # frozen params mutated in forward (BN stats) propagate
            for name, val in mutated.items():
                if name not in trainable:
                    new_params[name] = val
            if aux is not None:
                loss = (loss, aux)
            return loss, new_params, new_states

        in_shardings = None
        out_shardings = None
        if self.mesh is not None:
            pspec = {n: self._shardings[n].spec for n, _ in params}
            pdict = dict(params)
            st_spec = {n: tuple(
                self._state_spec(n, pdict[n], a.shape)
                for a in self._states[n]) for n in self._states}
            bspec = self._batch_spec or P("dp")
            bspecs = tuple(bspec if hasattr(b, "shape") and b.ndim > 0
                           else P() for b in batch_arrays)
            sh = lambda spec: NamedSharding(self.mesh, spec)  # noqa: E731
            in_shardings = (
                {n: sh(pspec[n]) for n, _ in params},
                {n: tuple(sh(s) for s in st_spec[n]) for n in self._states},
                sh(P()), sh(P()), sh(P()),
            ) + tuple(sh(s) for s in bspecs)
            out_shardings = (
                sh(P()),
                {n: sh(pspec[n]) for n, _ in params},
                {n: tuple(sh(s) for s in st_spec[n]) for n in self._states},
            )
        return jax.jit(step, donate_argnums=(0, 1),
                       in_shardings=in_shardings,
                       out_shardings=out_shardings)

    # -- public ------------------------------------------------------------
    def __call__(self, *batch):
        if self.aot:
            raise RuntimeError(
                "TrainStep(aot=True) compiles against a topology "
                "description — it cannot execute; use lower()/compile()")
        if _fault._DIST_HEARTBEAT is not None:
            # step-boundary peer health (mx.fault.dist): detect a hung
            # peer before launching the next cross-process program
            _fault._DIST_HEARTBEAT.beat(step=self._t)
        batch_arrays = self._batch_arrays(batch)
        # first call (where the step plans: with batches of a signature):
        # the program is built here and compiled in the dispatch the
        # build span encloses, or ahead of it under the plan span
        build = _profiler.build_span(
            "mx.train.step.build", signature=" ".join(
                "%s:%s" % ("x".join(map(str, shape)), dtype)
                for shape, dtype in _signature(batch_arrays))) \
            if self._built(batch_arrays) is None \
            else contextlib.nullcontext()
        with _profiler.step_span("mx.train.step", self._t + 1), build:
            loss = self._step(batch_arrays)
        return loss

    @staticmethod
    def _batch_arrays(batch):
        return tuple(b._data if isinstance(b, NDArray) else jnp.asarray(b)
                     for b in batch)

    def _args(self, batch_arrays, t):
        return ({name: p._data._data for name, p in self._params},
                self._states, jnp.int32(t),
                jnp.float32(self.optimizer.learning_rate),
                _random.new_key()) + batch_arrays

    def _built(self, batch_arrays):
        """What runs batches of these shapes, if it is built yet: the
        jitted step, which keeps a program a shape itself, or where the
        step plans, the plan of this signature."""
        if self._jitted is None or self._plan_device is None:
            return self._jitted
        return self._planned.get(_signature(batch_arrays))

    def _ensure_built(self, batch_arrays, args=None):
        if self._jitted is None:
            self._jitted = self._build(batch_arrays)
            self._plan_device = self._device_to_plan_for()
            if self._plan_device is None:
                return self._first_call
        built = self._built(batch_arrays)
        if built is None:
            built = self._plan(batch_arrays, args or self._args(
                batch_arrays, max(self._t, 1)))
        return built

    def _first_call(self, *args):
        """The step that does not plan, the first time: jit traces,
        lowers and compiles (or loads) inside this call."""
        with _profiler.build_span("mx.train.step.trace", program="step") \
                .compiles_as("mx.train.step.compile"):
            return self._jitted(*args)

    def _step(self, batch_arrays):
        self._t += 1
        self.optimizer.num_update = self._t
        args = self._args(batch_arrays, self._t)
        built = self._ensure_built(batch_arrays, args)
        with _profiler.span("mx.train.step.dispatch") as span:
            loss, new_params, new_states = self._dispatch(
                built, args, batch_arrays, span)
        for name, p in self._params:
            p._data._data = new_params[name]
        self._states = new_states
        return jax.tree_util.tree_map(NDArray, loss)

    def _dispatch(self, built, args, batch_arrays, span):
        if not isinstance(built, _Planned):
            return built(*args)
        try:
            out = built.run(*args)
            built.ran = True
            return out
        except _DEVICE_ERRORS as e:
            # memory_analysis() is the plan's reading of what the device
            # reserves for a program, and the caller may have put more on
            # the device since the plan was made.  A step refused for
            # want of memory fails before it runs, its arguments not yet
            # given up: then the device's word stands and the plan is
            # made again with fewer blocks spared, down to the step the
            # parent would have run, which raises what it raises.  Only
            # a plan refused before it ever ran is written to the plan
            # file: one that ran was a sound plan for the memory a start
            # finds.
            spared = built.plan["spared"]
            if not spared or not _out_of_memory(e) or any(
                    a.is_deleted() for a in
                    jax.tree_util.tree_leaves(args[:2])):
                raise
            warnings.warn(
                "the device refused the training step that spares %s the "
                "recomputation (%s); planning again"
                % (" ".join(spared), str(e).splitlines()[0][:200]))
        span.set(refused=len(spared))
        return self._dispatch(self._plan(batch_arrays, args, refused=built),
                              args, batch_arrays, span)

    # -- which marked blocks are made again ---------------------------------
    def _device_to_plan_for(self):
        """The device from whose memory this step plans, or None where
        nothing is known: no marked block, a mesh, a described topology,
        a device that does not report its memory."""
        if self.mesh is not None or self.aot \
                or not _marked_blocks(self.net):
            return None
        device = next(iter(self._params[0][1]._data._data.devices()))
        return device if _device_memory(device) is not None else None

    def _plan(self, batch_arrays, args, refused=None):
        """Choose the marked blocks to spare from the device's free
        memory (class docstring) and note the planned step, compiled,
        under the batch's signature.  ``refused`` is the plan of this
        signature that the device would not run."""
        limit, in_use = _device_memory(self._plan_device)
        # what a compiled step's temporaries and code may take
        room = limit - in_use - _RESERVE_BYTES
        blocks = dict(_marked_blocks(self.net))
        key = self._plan_key(blocks, batch_arrays, limit)
        where = compile_cache.cache_dir_in_force()
        file = where and os.path.join(
            where, "mx_recompute_plan_%s.json" % key[:32])
        compiles = 0

        def compiled(candidates, k, seen=None):
            """``(k, jitted, executable, temporaries + code)`` of the
            step that spares the last ``k`` of ``candidates``; without
            the last two where the compiler refuses it."""
            nonlocal compiles
            compiles += 1
            program = "step.spare%d" % k
            with _profiler.build_span("mx.train.step.trace",
                                      program=program):
                jitted = self._build(
                    batch_arrays, [id(blocks[path]) for path in
                                   candidates[len(candidates) - k:]], seen)
                with compile_cache.stable_locations():
                    lowered = jitted.lower(*args)
            try:
                with _profiler.build_span("mx.train.step.compile",
                                          program=program) as made:
                    executable = lowered.compile()
                    made.set(from_cache=bool(made.cache_loads))
            except _DEVICE_ERRORS as e:
                if not k or not _out_of_memory(e):
                    raise
                return k, jitted, None, None    # no room even to compile
            ma = executable.memory_analysis()
            return k, jitted, executable, int(
                ma.temp_size_in_bytes + ma.generated_code_size_in_bytes)

        def fits(tried):
            return tried[3] is not None and tried[3] <= room

        with _profiler.build_span("mx.train.step.plan") as span:
            candidates, readings, best = None, {}, None
            hint = None if refused else _read_plan(file, key, blocks)
            if refused:
                # the device's word stands over memory_analysis()'s
                candidates = refused.candidates
                readings = dict(refused.readings)
                readings[len(refused.plan["spared"])] = None
            elif hint is not None:
                # a start that finds a plan traces and loads that step
                # only; the file is a hint: the executable has to fit
                tried = compiled(hint["candidates"], len(hint["spared"]))
                candidates, readings = hint["candidates"], {tried[0]: tried[3]}
                if fits(tried) or not hint["spared"]:
                    best, readings = tried, {**hint["readings"], **readings}
                del tried
            if best is None:
                if 0 not in readings:
                    seen = {}
                    best = compiled([], 0, seen)
                    paths = {id(b): path for path, b in blocks.items()}
                    candidates = [paths[i] for i, own in seen.items()
                                  if i in paths and all(own)]
                    readings[0] = best[3]
                while compiles < _COMPILES:
                    k = blocks_to_spare(readings, len(candidates), room)
                    if k is None:
                        break
                    tried = compiled(candidates, k)
                    readings[k] = tried[3]
                    if fits(tried):
                        best = tried
                    del tried
                if best is None:    # refused, and no try of this round fits
                    best = compiled(candidates, max(
                        [k for k, need in readings.items()
                         if need is not None and need <= room] or [0]))
            k, jitted, executable, need = best
            spared = candidates[len(candidates) - k:]
            plan = {"free_bytes": room - readings[0],
                    "temp_bytes_rung0": readings[0], "temp_bytes": need,
                    "spared": spared,
                    "made_again": [p for p in blocks if p not in spared],
                    "compiles": compiles,
                    "from_file": hint is not None and compiles == 1}
            # (a span's arguments are joined by , and = in the trace)
            span.set(**dict(plan, spared=" ".join(spared),
                            made_again=" ".join(plan["made_again"])))
        if not plan["from_file"] and not (refused and refused.ran):
            _write_plan(file, {
                "key": key, "candidates": candidates, "spared": spared,
                "readings": readings, "plan": plan})
        self.recompute_plan = plan
        built = self._planned[_signature(batch_arrays)] = _Planned(
            jitted, executable, plan, candidates, readings)
        built.ran = bool(refused and refused.ran)
        return built

    def _plan_key(self, blocks, batch_arrays, limit):
        """Everything a plan depends on that is known before any trace,
        the library's own code among it: another version of a block
        keeps other values for its backward (PERF.md section 6, PR
        38), and two versions may share one cache directory."""
        return hashlib.sha256(json.dumps([
            _library_digest(),
            jax.__version__, self._plan_device.device_kind, limit,
            type(self.optimizer).__name__,
            [(n, tuple(p.shape), str(p.dtype)) for n, p in self._params],
            sorted((n, _signature(st)) for n, st in self._states.items()),
            _signature(batch_arrays), list(blocks),
        ]).encode()).hexdigest()

    def save_checkpoint(self, path):
        """Sharded checkpoint of the FULL training state — params,
        optimizer states, step counter — via orbax (SURVEY §5: the
        orbax-style sharded analog of ``Trainer.save_states`` +
        ``save_parameters``).  Each process writes only its addressable
        shards, so the same call is multi-host safe; ``load_checkpoint``
        reshards onto whatever mesh the restoring step uses."""
        import os

        import orbax.checkpoint as ocp
        tree = {
            "params": {n: p._data._data for n, p in self._params},
            "states": self._states,
            "t": jnp.int32(self._t),
        }
        ckptr = ocp.StandardCheckpointer()  # async writer
        # force: periodic checkpointing to a fixed path overwrites, like
        # the reference's Trainer.save_states
        ckptr.save(os.path.abspath(path), tree, force=True)
        ckptr.wait_until_finished()

    def load_checkpoint(self, path):
        """Restore a ``save_checkpoint`` tree onto THIS step's mesh:
        every array is loaded directly into this step's shardings
        (resharding from however it was saved — dp x tp to tp-only, to
        single device, ...)."""
        import os

        import orbax.checkpoint as ocp
        from jax.sharding import SingleDeviceSharding

        # EVERY restore leaf carries an explicit sharding: leaving one
        # out makes orbax fall back to the sharding saved in the
        # checkpoint, whose mesh/devices need not exist in the restoring
        # process (different topology / host count) — exactly the case
        # this method advertises
        if self.mesh is not None:
            repl = NamedSharding(self.mesh, P())
        else:
            repl = SingleDeviceSharding(
                next(iter(self._params[0][1]._data._data.devices()))
                if self._params else jax.devices()[0])
        pdict = dict(self._params)

        def _target(arr, name):
            sharding = self._shardings[name] if self.mesh is not None \
                else repl
            return jax.ShapeDtypeStruct(arr.shape, arr.dtype,
                                        sharding=sharding)

        def _state_target(name, arrays):
            if self.mesh is None:
                return tuple(jax.ShapeDtypeStruct(a.shape, a.dtype,
                                                  sharding=repl)
                             for a in arrays)
            return tuple(
                jax.ShapeDtypeStruct(
                    a.shape, a.dtype,
                    sharding=NamedSharding(
                        self.mesh,
                        self._state_spec(name, pdict[name], a.shape)))
                for a in arrays)

        target = {
            "params": {n: _target(p._data._data, n)
                       for n, p in self._params},
            "states": {n: _state_target(n, arrs)
                       for n, arrs in self._states.items()},
            "t": jax.ShapeDtypeStruct((), jnp.int32, sharding=repl),
        }
        tree = ocp.StandardCheckpointer().restore(
            os.path.abspath(path), target)
        for name, p in self._params:
            p._data._data = tree["params"][name]
        self._states = {n: tuple(arrs)
                        for n, arrs in tree["states"].items()}
        self._t = int(tree["t"])
        self.optimizer.num_update = self._t
        return self

    def resize(self, mesh, checkpoint=None):
        """Rebind this step to a NEW (typically smaller) mesh — the
        reshard entry point of the elastic resize protocol
        (``mx.fault.elastic``): drop the compiled program, re-place
        params and optimizer states on the new mesh, then restore the
        full training state from ``checkpoint`` — saved on ANY topology;
        :meth:`load_checkpoint`'s orbax path reshards it onto this one.

        Without ``checkpoint`` the params keep their current values but
        the optimizer states are re-created FRESH (momentum restarts) —
        pass the last good checkpoint unless you mean that.
        """
        self.mesh = mesh
        self._jitted = None
        self._planned = {}
        self._setup()
        if checkpoint is not None:
            self.load_checkpoint(checkpoint)
        return self

    def compile(self, *batch):
        """Warm the compile cache without stepping."""
        self._ensure_built(self._batch_arrays(batch))
        return self

    def lower(self, *batch):
        """Lower the full step to StableHLO without executing.

        Returns a ``jax.stages.Lowered``: ``.as_text()`` is the exact
        program handed to XLA (layout/transpose evidence), and
        ``.compile().cost_analysis()`` / ``.memory_analysis()`` give the
        backend's FLOP count and buffer sizes — the chip-independent perf
        evidence used by ``tests/test_hlo_perf.py`` and PERF.md.  The
        reference's analog is its per-op profiler dump
        (``src/profiler/profiler.cc``); here the whole train step is one
        XLA program, so the compiled artifact itself is inspectable.
        It is the program ``__call__`` runs: where the step plans what
        its marked blocks keep, the planned one.
        """
        batch_arrays = self._batch_arrays(batch)
        built = self._ensure_built(batch_arrays)
        args = self._args(batch_arrays, max(self._t, 1))
        if isinstance(built, _Planned):
            with compile_cache.stable_locations():   # as it was compiled
                return built.jitted.lower(*args)
        if self.aot:
            # topology-mesh lowering: hand jit avals, not host-placed
            # arrays (a compile-only client has no buffers to match the
            # in_shardings' memory kinds against)
            args = jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(jnp.shape(a), a.dtype),
                args)
        return self._jitted.lower(*args)


@functools.cache
def _library_digest():
    """sha256 of the package's Python sources, path and content."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    digest = hashlib.sha256()
    for where, _, files in sorted(os.walk(root)):
        for name in sorted(f for f in files if f.endswith(".py")):
            path = os.path.join(where, name)
            digest.update(os.path.relpath(path, root).encode())
            with open(path, "rb") as f:
                digest.update(f.read())
    return digest.hexdigest()


def _read_plan(path, key, blocks):
    """The plan file's content if it is there, whole, of this key and of
    these marked blocks."""
    if not path:
        return None
    try:
        with open(path) as f:
            hint = json.load(f)
        if hint["key"] != key:
            return None
        candidates, spared = hint["candidates"], hint["spared"]
        if any(path not in blocks for path in candidates) \
                or spared != candidates[len(candidates) - len(spared):]:
            return None
        hint["readings"] = {int(k): need
                            for k, need in hint["readings"].items()}
        hint["readings"][0] + 0     # the reading everything starts from
        return hint
    except (OSError, ValueError, KeyError, TypeError):
        return None


def _write_plan(path, content):
    if not path:
        return
    try:
        with atomic_write(path, "w") as f:
            json.dump(content, f)
    except OSError:
        pass   # a cache that cannot be written is a cache that misses
