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

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from .. import _tape
from .. import fault as _fault
from .. import profiler as _profiler
from ..gluon.block import swapped_params
from ..ndarray.ndarray import NDArray
from ..numpy import random as _random
from .mesh import mesh_scope
from .sharding import _valid_spec, param_sharding

P = PartitionSpec


def _is_ndarray(x):
    return isinstance(x, NDArray)


def _raw(x):
    return x._data if isinstance(x, NDArray) else x


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

    A block marked with ``Block.recompute()`` runs again in the backward
    and keeps its input and what its ops name as dear to make again (a
    flash attention kernel's output and row sums), nothing else of its
    interior.  Parameters and optimizer states are donated to the step
    and updated in place.
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
        self._params = list(net.collect_params().items())
        for name, p in self._params:
            if p._data is None:
                raise ValueError(
                    "TrainStep requires initialized parameters; %s is not "
                    "(run one forward or pass concrete shapes)" % name)
        self._trainable = [name for name, p in self._params
                           if p.grad_req != "null"]
        self._t = 0
        self._batch_spec = batch_spec
        self._jitted = None
        self._cold = True   # the next call builds and compiles
        self._states = None
        self._shardings = None
        self._setup()

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
    def _build(self, batch_arrays):
        net, params, trainable = self.net, self._params, self._trainable
        opt = self.optimizer
        loss_fn, forward_fn = self.loss_fn, self.forward_fn
        name_to_idx = {name: i for i, (name, _) in enumerate(params)}

        def run_forward(all_arrays, key, batch):
            with swapped_params(
                    [p._data for _, p in params],
                    [all_arrays[name] for name, _ in params]) as written, \
                    _tape.suspend_recording(), _random.trace_scope(key):
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
        # first call: the program is built here and compiled in the
        # dispatch the build span encloses
        build = _profiler.span("mx.train.step.build") if self._cold \
            else contextlib.nullcontext()
        with _profiler.step_span("mx.train.step", self._t + 1), build:
            loss = self._step(batch)
        self._cold = False
        return loss

    def _step(self, batch):
        batch_arrays = tuple(b._data if isinstance(b, NDArray)
                             else jnp.asarray(b) for b in batch)
        if self._jitted is None:
            self._jitted = self._build(batch_arrays)
        self._t += 1
        self.optimizer.num_update = self._t
        lr = jnp.float32(self.optimizer.learning_rate)
        key = _random.new_key()
        param_arrays = {name: p._data._data for name, p in self._params}
        with _profiler.span("mx.train.step.dispatch"):
            loss, new_params, new_states = self._jitted(
                param_arrays, self._states, jnp.int32(self._t), lr, key,
                *batch_arrays)
        for name, p in self._params:
            p._data._data = new_params[name]
        self._states = new_states
        return jax.tree_util.tree_map(NDArray, loss)

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
        self._cold = True
        self._setup()
        if checkpoint is not None:
            self.load_checkpoint(checkpoint)
        return self

    def compile(self, *batch):
        """Warm the compile cache without stepping."""
        batch_arrays = tuple(b._data if isinstance(b, NDArray)
                             else jnp.asarray(b) for b in batch)
        if self._jitted is None:
            self._jitted = self._build(batch_arrays)
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
        """
        batch_arrays = tuple(b._data if isinstance(b, NDArray)
                             else jnp.asarray(b) for b in batch)
        if self._jitted is None:
            self._jitted = self._build(batch_arrays)
        param_arrays = {name: p._data._data for name, p in self._params}
        lr = jnp.float32(self.optimizer.learning_rate)
        args = (param_arrays, self._states, jnp.int32(max(self._t, 1)),
                lr, _random.new_key()) + batch_arrays
        if self.aot:
            # topology-mesh lowering: hand jit avals, not host-placed
            # arrays (a compile-only client has no buffers to match the
            # in_shardings' memory kinds against)
            args = jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(jnp.shape(a), a.dtype),
                args)
        return self._jitted.lower(*args)
