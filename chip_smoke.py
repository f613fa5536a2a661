#!/usr/bin/env python3
"""chip_smoke.py — does the system still start on the chip?

One process drives the two main paths once, through the entry points a
user calls, at published widths on one TPU v5e:

- *train*: ``gluon`` ResNet-50 (bf16, batch 256 x 3 x 224 x 224, SGD
  momentum) through ``parallel.TrainStep`` — five steps on a seeded batch;
- *serve*: ``mx.serve.Server`` over ``TransformerLM`` at Llama-3-8B widths
  (depth cut 32 -> 8 so weights and KV pool fit 16 GB) — six requests
  through ``submit()``/``result()`` on the engine thread, the Pallas
  kernels required in the compiled programs, and prefill + paged-decode
  logits held to the same net's dense full-sequence forward.

``--chips 4`` runs, instead, only what exists across chips: the dp2 x tp2
ZeRO-1 train step against its one-device replay, ``Server(mesh=tp2)``
against the unsharded ``Server``, and four replicas behind
``serve_router.ReplicaGroup`` each on its own chip.

Every phase prints one JSON line (seconds split into compile and run,
whether its compiles came from the persistent cache, HBM, what was
checked).  Any failed check or exception exits non-zero at once; a
process that finds no TPU fails in the *device* phase — no retry, no
probe subprocess, no CPU.  The last line of a passing run is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.

Weights and inputs are random, made from ``--seed``.  Nothing here is a
benchmark: the seconds are bring-up observations.
"""
import argparse
import gc
import json
import statistics
import threading
import time

#: |logit| reaches ~6 at these widths, where one bf16 ulp is 2**-5: the
#: stated tolerance between two implementations of the same bf16 forward
#: (flash + paged kernels vs dense; tp=2 vs one device) is eight of them.
BF16_LOGIT_TOL = 0.25
#: loss of the dp x tp x ZeRO-1 step against its one-device replay.
#: __graft_entry__.py holds the fp32 pair to rtol 2e-5 (170 fp32 ulps of
#: 2**-23); bf16 carries 2**-8, and the loss is an fp32 mean over
#: thousands of tokens, so the same trajectory in bf16 is held to 1e-2.
BF16_LOSS_RTOL = 1e-2
#: "substantial" device memory: well past anything but the weights
MIN_BYTES_IN_USE = 256 << 20


class SmokeFailure(RuntimeError):
    """A check of the smoke did not hold."""


def check(ok, what):
    if not ok:
        raise SmokeFailure(what)


def emit(phase, **fields):
    print(json.dumps({"phase": phase, **fields}), flush=True)


def hbm(devices):
    """Per-device ``bytes_in_use`` now and ``peak_bytes_in_use`` so far
    (the peak is the process's, not the phase's: it never resets)."""
    stats = [d.memory_stats() or {} for d in devices]
    return {"hbm_bytes_in_use": [s.get("bytes_in_use") for s in stats],
            "hbm_peak_bytes": [s.get("peak_bytes_in_use") for s in stats]}


class CacheWatch:
    """Did a phase's compiles come out of the persistent cache?"""

    def __init__(self):
        import jax

        from mxnet_tpu.utils import compile_cache
        self._entries = compile_cache.cache_entries
        self._dir = jax.config.jax_compilation_cache_dir
        self._before = self._entries(self._dir)

    def verdict(self):
        new = self._entries(self._dir) - self._before
        return {"cache": "warm" if new == 0 else "cold",
                "cache_new_entries": new}


# ----------------------------------------------------------------------
# one chip
# ----------------------------------------------------------------------
def phase_device(count):
    import jax
    devices = jax.devices()
    d = devices[0]
    check(d.platform == "tpu",
          "no accelerator: jax.devices() is %r" % (devices,))
    check(len(devices) >= count,
          "need %d chip(s), jax.devices() has %d" % (count, len(devices)))
    emit("device", platform=d.platform, kind=d.device_kind,
         count=len(devices))
    return devices[:count]


def phase_train(device, seed, net_fn=None, batch=256, image=224, steps=5):
    """ResNet-50 v1 in bf16 with SGD momentum through
    ``parallel.TrainStep``, ``steps`` steps."""
    import numpy as onp

    import mxnet_tpu as mx
    from mxnet_tpu import gluon, parallel
    from mxnet_tpu.gluon.model_zoo import vision

    watch = CacheWatch()
    mx.np.random.seed(seed)
    net = (net_fn or vision.resnet50_v1)()
    net.cast("bfloat16")
    net.initialize()
    x = mx.np.random.uniform(0, 1, (batch, 3, image, image)) \
        .astype("bfloat16")
    y = mx.np.random.randint(0, 1000, (batch,), dtype="int32")
    net(x[:1])  # materialize deferred shapes at batch 1
    opt = mx.optimizer.SGD(learning_rate=0.1, momentum=0.9, wd=1e-4)
    step = parallel.TrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                              opt, mesh=None)
    trainable = [p for p in net.collect_params().values()
                 if p.grad_req != "null"]
    before = [onp.asarray(p.data()._data, onp.float32)
              for p in trainable[:4]]
    losses, walls = [], []
    for _ in range(steps):
        t0 = time.perf_counter()
        losses.append(float(step(x, y)))  # float() waits for the device
        walls.append(time.perf_counter() - t0)
    check(all(onp.isfinite(v) for v in losses),
          "non-finite loss: %r" % (losses,))
    check(any(not onp.array_equal(b, onp.asarray(p.data()._data,
                                                 onp.float32))
              for b, p in zip(before, trainable)),
          "parameters did not change in %d steps" % steps)
    homes = set().union(*(p.data()._data.devices()
                          for p in net.collect_params().values()))
    check(homes == {device},
          "parameters live on %r, not on %r" % (homes, device))
    check(step._jitted._cache_size() == 1,
          "the step compiled %d times" % step._jitted._cache_size())
    check(max(walls[1:]) < 0.5 * walls[0],
          "steps 2-%d are no small fraction of step 1 (the only one "
          "that compiles): %r" % (steps, walls))
    steady = statistics.median(walls[1:])
    emit("train", model="resnet50_v1 bf16" if net_fn is None else "test",
         batch=batch, steps=steps,
         compile_s=round(walls[0] - steady, 3),
         run_s=round(sum(walls[1:]) + steady, 3),
         step_s=[round(w, 4) for w in walls], **watch.verdict(),
         **hbm([device]), losses=losses,
         checked=["finite losses", "parameters changed",
                  "parameters on the device", "one compile"])


def _forward_logits(net):
    """``forward(params, tokens, cache=None)`` -> fp32 logits of ``net``
    run on ``params`` (the warm pool's programs sample in-graph and
    return only tokens, so the logits checks trace the model's cached
    forward themselves, the way ``serve``'s program builders do)."""
    import jax.numpy as jnp

    from mxnet_tpu import _tape, serve
    from mxnet_tpu.ndarray.ndarray import NDArray
    ps = net.collect_params()

    def forward(params, tokens, cache=None):
        with _tape.suspend_recording(), serve._swapped_params(ps, params):
            return net.forward(NDArray(tokens), cache=cache)._data \
                .astype(jnp.float32)

    return forward


def _cached_logits_fns(net, page_size):
    """Jitted prefill and decode of the cached forward, returning
    logits and the updated pools."""
    import jax

    from mxnet_tpu.models import CacheView
    forward = _forward_logits(net)

    @jax.jit
    def prefill(params, k, v, page_row, tokens, true_len):
        view = CacheView("prefill", k, v, page_size, page_row=page_row,
                         true_len=true_len)
        return forward(params, tokens, view), view.k, view.v

    @jax.jit
    def decode(params, k, v, page_table, lengths, active, tokens):
        view = CacheView("decode", k, v, page_size, page_table=page_table,
                         lengths=lengths, active=active)
        return forward(params, tokens, view), view.k, view.v

    return prefill, decode


def _dense_reference(net, pad_to):
    """The reference: ``ref(params, tokens)`` -> fp32 logits of the
    full-sequence forward with XLA dense attention, on the device.
    ``tokens`` are padded to ``pad_to`` so that every call of a phase is
    one program (causal: padding behind a position cannot reach it)."""
    import jax
    import jax.numpy as jnp
    import numpy as onp
    forward = jax.jit(_forward_logits(net))

    def ref(params, tokens):
        padded = onp.zeros((1, pad_to), onp.int32)
        padded[0, :len(tokens)] = tokens
        impl, net.cfg.attn_impl = net.cfg.attn_impl, "dense"
        try:
            return forward(params, jnp.asarray(padded))[0, :len(tokens)]
        finally:
            net.cfg.attn_impl = impl

    return ref


def _engine_vs_dense_tokens(dense, params, prompts, tokens, tol):
    """Every token the engine returned against the greedy argmax of the
    dense reference run on that request's own prompt + tokens, so each
    step is judged on the context the engine really had.  A token that
    is not the argmax but within ``tol`` of it in logit is a near-tie
    that the kernels' rounding can flip: reported with its margin.
    Anything further is the engine's fault — slots, page tables,
    sampling or requests mixed up — and fails.  Returns the near-ties
    and request 0's reference logits."""
    import numpy as onp
    near_ties, first = [], None
    for i, (prompt, toks) in enumerate(zip(prompts, tokens)):
        ref = dense(params, list(prompt) + list(toks[:-1]))
        if first is None:
            first = ref
        rows = onp.asarray(ref[len(prompt) - 1:])  # row j chose toks[j]
        for j, (tok, top) in enumerate(zip(toks, rows.argmax(-1))):
            if tok == top:
                continue
            margin = float(rows[j, top] - rows[j, tok])
            check(margin <= tol,
                  "request %d, step %d: the engine returned token %d, the "
                  "dense forward of the same context picks %d, %.4g apart "
                  "in logit (tolerance %.4g) — no near-tie"
                  % (i, j, tok, int(top), margin, tol))
            near_ties.append({"request": i, "step": j,
                              "tokens": [int(tok), int(top)],
                              "margin": margin})
    return near_ties, first


def _cached_vs_dense_logits(srv, net, prompt, generated, decode_steps, ref):
    """Max |difference| between the cached path's logits — prefill of
    ``prompt`` at its ladder rung (flash kernel), then ``decode_steps``
    paged-decode steps feeding the tokens the server generated — and
    ``ref``, the dense full-sequence forward of the same tokens."""
    import jax.numpy as jnp
    import numpy as onp

    from mxnet_tpu.models import init_pools
    pool, spec = srv.pool, srv.pool.spec
    params = pool.params
    prefill, decode = _cached_logits_fns(net, spec.page_size)
    L, T = len(prompt), pool.ladder_fit(len(prompt))
    seq = list(prompt) + list(generated[:decode_steps])
    ref = onp.asarray(ref[:len(seq)])

    k, v = init_pools(spec)
    MP = spec.max_pages_per_slot
    row = onp.arange(1, MP + 1, dtype=onp.int32)
    padded = onp.zeros((1, T), onp.int32)
    padded[0, :L] = prompt
    got, k, v = prefill(params, k, v, jnp.asarray(row),
                        jnp.asarray(padded), jnp.int32(L))
    diffs = [onp.abs(onp.asarray(got)[0, :L] - ref[:L]).max()]
    table = onp.zeros((spec.slots, MP), onp.int32)
    table[0] = row
    lengths = onp.zeros((spec.slots,), onp.int32)
    lengths[0] = L
    active = onp.zeros((spec.slots,), bool)
    active[0] = True
    for t in range(L, len(seq)):
        toks = onp.zeros((spec.slots, 1), onp.int32)
        toks[0, 0] = seq[t]
        got, k, v = decode(params, k, v, jnp.asarray(table),
                           jnp.asarray(lengths), jnp.asarray(active),
                           jnp.asarray(toks))
        diffs.append(onp.abs(onp.asarray(got)[0, 0] - ref[t]).max())
        lengths[0] += 1
    return float(max(diffs)), float(onp.abs(ref).max())


def _inference_net(cfg, seed):
    """The LM with seeded random weights and no gradient buffers."""
    import mxnet_tpu as mx
    from mxnet_tpu.models import TransformerLM
    mx.np.random.seed(seed)
    net = TransformerLM(cfg)
    net.setattr("grad_req", "null")
    net.initialize(init=mx.init.Normal(0.02))
    return net


def _requests(rng, vocab, n, lo, hi):
    return [[int(t) for t in rng.randint(1, vocab, int(rng.randint(lo, hi)))]
            for _ in range(n)]


def _serve_all(srv, prompts, max_new):
    rids = [srv.submit(p) for p in prompts]
    out = [srv.result(r, timeout=600) for r in rids]
    for i, res in enumerate(out):
        check(res is not None and res["state"] == "done"
              and len(res["tokens"]) == max_new,
              "request %d did not complete with %d tokens: %r"
              % (i, max_new, res))
    return [list(res["tokens"]) for res in out]


def _kernels_in(programs, marker):
    for name, compiled in programs.items():
        check(marker in compiled.as_text(),
              "%s holds no %s: a dense stand-in was compiled where the "
              "Pallas kernel belongs" % (name, marker))
    return sorted(programs)


def phase_serve(device, seed, cfg=None, serve_cfg=None, n_requests=6,
                prompt_range=(100, 900), decode_steps=4,
                kernel_marker="tpu_custom_call"):
    """``mx.serve.Server`` over the flagship LM on one chip."""
    import numpy as onp

    from mxnet_tpu import serve
    from mxnet_tpu.models import llama3_8b_config

    watch = CacheWatch()
    reduced = None
    if cfg is None:
        cfg = llama3_8b_config(n_layers=8)
        reduced = {"n_layers": "32->8"}
    if serve_cfg is None:
        # pages for 8 slots of 1024 + 32 tokens, plus the trash page
        serve_cfg = serve.ServeConfig(slots=8, page_size=128,
                                      pages=8 * 9 + 1, ladder=(512, 1024),
                                      max_new=32, int8=False,
                                      temperature=0.0)
    t0 = time.perf_counter()
    net = _inference_net(cfg, seed)
    t_init = time.perf_counter() - t0
    peak_after_init = hbm([device])["hbm_peak_bytes"]
    srv = serve.Server(net, serve_cfg)
    kernels = None
    if kernel_marker:
        pool = srv.pool
        kernels = _kernels_in(
            {"decode": pool._decode,
             **{"prefill[%d]" % T: p for T, p in pool._prefill.items()}},
            kernel_marker)
    prompts = _requests(onp.random.RandomState(seed), cfg.vocab_size,
                        n_requests, *prompt_range)
    t0 = time.perf_counter()
    with srv:
        tokens = _serve_all(srv, prompts, serve_cfg.max_new)
    t_run = time.perf_counter() - t0
    check(srv._error is None and not any(
        t.name == "mxserve-engine" for t in threading.enumerate()),
          "the engine thread did not shut down cleanly: %r" % srv._error)
    tol = BF16_LOGIT_TOL if cfg.dtype == "bfloat16" else 1e-4
    t0 = time.perf_counter()
    dense = _dense_reference(net, srv.pool.spec.max_context)
    near_ties, ref = _engine_vs_dense_tokens(dense, srv.pool.params,
                                             prompts, tokens, tol)
    t_tokens = time.perf_counter() - t0
    diff, ref_max = _cached_vs_dense_logits(srv, net, prompts[0],
                                            tokens[0], decode_steps, ref)
    t_logits = time.perf_counter() - t0 - t_tokens
    check(diff <= tol,
          "prefill + paged decode logits differ from the dense full "
          "forward by %.4g (tolerance %.4g, max |logit| %.3g)"
          % (diff, tol, ref_max))
    homes = set().union(*(a.devices() for a in srv.pool.params.values()))
    check(homes == {device},
          "weights live on %r, not on %r" % (homes, device))
    emit("serve", model="llama3-8b widths" if reduced else "test",
         **({"reduced": reduced} if reduced else {}),
         params=int(net.num_params()), requests=n_requests,
         prompt_lens=[len(p) for p in prompts],
         max_new=serve_cfg.max_new, init_s=round(t_init, 3),
         compile_s=srv.pool.stats["compile_s"], run_s=round(t_run, 3),
         tokens_check_s=round(t_tokens, 3),
         logits_check_s=round(t_logits, 3), **watch.verdict(),
         **hbm([device]), hbm_peak_after_init_bytes=peak_after_init,
         kernels_in=kernels,
         tokens_equal_dense_argmax=not near_ties, near_tie_tokens=near_ties,
         max_abs_logit_diff=diff, logit_tolerance=tol,
         max_abs_logit=ref_max,
         checked=["all requests done with max_new tokens",
                  "Pallas kernels in decode and prefill programs",
                  "every engine token vs the dense forward's argmax "
                  "(near-ties reported)",
                  "cached logits vs dense full forward",
                  "clean shutdown"])


# ----------------------------------------------------------------------
# four chips: only what exists across chips, and what it is compared with
# ----------------------------------------------------------------------
def _mesh_lm_config(cfg):
    from mxnet_tpu.models import llama3_8b_config
    if cfg is not None:
        return cfg, None
    # depth sized so that the one-device replay of (a) and the unsharded
    # server of (b) also fit one chip's 16 GB beside the sharded copy
    return llama3_8b_config(n_layers=2), {"n_layers": "32->2"}


def _mesh_serve_config(serve_cfg):
    from mxnet_tpu import serve
    if serve_cfg is not None:
        return serve_cfg
    # One rung and no chunk ladder: every serving program carries the
    # in-graph sampler, whose sort over the 128256-entry vocabulary is
    # ~40 s of TPU compile, and a program is compiled per device — the
    # four-chip phases build six servers.
    return serve.ServeConfig(slots=4, page_size=128, pages=4 * 9 + 1,
                             ladder=(1024,), max_new=16, int8=False,
                             temperature=0.0, prefix_cache=False)


def _substantial(devices, floor):
    if floor is None:  # the CPU suite: its backend keeps no memory_stats
        return
    used = [(d.memory_stats() or {}).get("bytes_in_use") for d in devices]
    check(all(u is not None and u >= floor for u in used),
          "a device of %r holds no substantial memory: bytes_in_use %r"
          % (devices, used))


def phase_mesh_train(devices, seed, cfg=None, batch=4, seq=256, steps=3,
                     kernel_marker="tpu_custom_call",
                     min_bytes=MIN_BYTES_IN_USE):
    """(a) dp2 x tp2 ZeRO-1 ``TrainStep`` vs its one-device replay."""
    import numpy as onp

    import mxnet_tpu as mx
    from mxnet_tpu import gluon, parallel
    from mxnet_tpu.models import TransformerLM

    cfg, reduced = _mesh_lm_config(cfg)
    watch = CacheWatch()
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()

    def fwd(net, tokens, labels):
        logits = net.forward(tokens)
        return loss_fn(logits.reshape(-1, logits.shape[-1]),
                       labels.reshape(-1)).mean()

    def run(mesh):
        mx.np.random.seed(seed)
        net = TransformerLM(cfg)
        net.initialize(init=mx.init.Normal(0.02))
        toks = mx.np.random.randint(0, cfg.vocab_size, (batch, seq + 1),
                                    dtype="int32")
        opt = mx.optimizer.SGD(learning_rate=0.05, momentum=0.9)
        step = parallel.TrainStep(net, None, opt, mesh=mesh,
                                  forward_fn=fwd, zero1=mesh is not None)
        inputs, labels = toks[:, :-1], toks[:, 1:]
        kernels = None
        t0 = time.perf_counter()
        if kernel_marker:
            text = step.lower(inputs, labels).compile().as_text()
            kernels = text.count(kernel_marker)
            check(kernels > 0, "the %s train step holds no %s"
                  % ("dp x tp" if mesh is not None else "one-device",
                     kernel_marker))
        losses, walls = [], []
        for _ in range(steps):
            t1 = time.perf_counter()
            losses.append(float(step(inputs, labels)))
            walls.append(time.perf_counter() - t1)
        total = time.perf_counter() - t0
        used = mesh.devices.flatten().tolist() if mesh is not None \
            else devices[:1]
        _substantial(used, min_bytes)
        mem = hbm(devices)
        run_s = sum(walls[1:])
        return losses, kernels, round(total - run_s, 3), round(run_s, 3), mem

    mesh = parallel.create_mesh(dp=2, tp=2, devices=devices)
    sharded, k_mesh, c_mesh, r_mesh, mem_mesh = run(mesh)
    gc.collect()
    replay, k_one, c_one, r_one, mem_one = run(None)
    gc.collect()
    check(all(onp.isfinite(v) for v in sharded + replay),
          "non-finite loss: %r / %r" % (sharded, replay))
    rtol = BF16_LOSS_RTOL if cfg.dtype == "bfloat16" else 2e-5
    worst = max(abs(a - b) / abs(b) for a, b in zip(sharded, replay))
    check(worst <= rtol,
          "dp2 x tp2 ZeRO-1 losses %r leave the one-device replay %r by "
          "%.3g relative (tolerance %.3g)" % (sharded, replay, worst, rtol))
    emit("mesh_train", mesh={"dp": 2, "tp": 2}, zero1=True,
         **({"reduced": reduced} if reduced else {}), batch=batch, seq=seq,
         steps=steps, losses=sharded, replay_losses=replay,
         max_rel_loss_diff=worst, loss_rtol=rtol,
         kernels_in_step=k_mesh, kernels_in_replay=k_one,
         compile_s=c_mesh, run_s=r_mesh, replay_compile_s=c_one,
         replay_run_s=r_one, **watch.verdict(), **mem_mesh,
         replay_hbm=mem_one,
         checked=["losses equal the one-device replay",
                  "Pallas kernels in the dp x tp step",
                  "memory in use on all four devices"])


def _first_step_logits(net, pool, prompt):
    """Logits at the last prompt position from ``pool``'s own (possibly
    tp-sharded) weights, through the prefill path at the ladder rung."""
    import jax
    import jax.numpy as jnp
    import numpy as onp

    from mxnet_tpu import parallel
    from mxnet_tpu.models import init_pools
    spec = pool.spec
    prefill, _ = _cached_logits_fns(net, spec.page_size)
    k, v = init_pools(spec)
    if pool.mesh is not None:
        k = jax.device_put(k, pool.k_pages.sharding)
        v = jax.device_put(v, pool.v_pages.sharding)
    L, T = len(prompt), pool.ladder_fit(len(prompt))
    padded = onp.zeros((1, T), onp.int32)
    padded[0, :L] = prompt
    row = onp.arange(1, spec.max_pages_per_slot + 1, dtype=onp.int32)
    put = pool._put
    with parallel.mesh_scope(pool.mesh):
        got, _, _ = prefill(pool.params, k, v, put(jnp.asarray(row)),
                            put(jnp.asarray(padded)), put(jnp.int32(L)))
    return onp.asarray(got)[0, L - 1]


def phase_mesh_serve(devices, seed, cfg=None, serve_cfg=None, n_requests=4,
                     prompt_range=(100, 900),
                     kernel_marker="tpu_custom_call",
                     min_bytes=MIN_BYTES_IN_USE):
    """(b) ``Server(mesh=tp2)`` vs the unsharded ``Server``."""
    import numpy as onp

    from mxnet_tpu import parallel, serve

    cfg, reduced = _mesh_lm_config(cfg)
    watch = CacheWatch()
    serve_cfg = _mesh_serve_config(serve_cfg)
    net = _inference_net(cfg, seed)
    prompts = _requests(onp.random.RandomState(seed), cfg.vocab_size,
                        n_requests, *prompt_range)
    mesh = parallel.create_mesh(tp=2, devices=devices[:2])
    tol = BF16_LOGIT_TOL if cfg.dtype == "bfloat16" else 1e-4
    dense = _dense_reference(net, serve_cfg.max_pages_per_slot
                             * serve_cfg.page_size)
    near_ties = []

    out = {}
    for name, m in (("one_device", None), ("tp2", mesh)):
        srv = serve.Server(net, serve_cfg, mesh=m)
        if kernel_marker:
            _kernels_in({"%s decode" % name: srv.pool._decode,
                         **{"%s prefill[%d]" % (name, T): p
                            for T, p in srv.pool._prefill.items()}},
                        kernel_marker)
        t0 = time.perf_counter()
        with srv:
            toks = _serve_all(srv, prompts, serve_cfg.max_new)
        run_s = time.perf_counter() - t0
        check(srv._error is None, "%s engine died: %r" % (name, srv._error))
        if m is None:
            # the two servers run one engine, so agreeing with each other
            # is not enough: the unsharded one is held to the dense forward
            near_ties, _ = _engine_vs_dense_tokens(
                dense, srv.pool.params, prompts, toks, tol)
        logits = [_first_step_logits(net, srv.pool, p) for p in prompts]
        _substantial(devices[:2] if m is not None else devices[:1],
                     min_bytes)
        out[name] = (toks, logits, srv.pool.stats["compile_s"],
                     round(run_s, 3), hbm(devices))
        del srv
        gc.collect()

    (t_one, l_one, c_one, r_one, _), (t_tp, l_tp, c_tp, r_tp, mem) = \
        out["one_device"], out["tp2"]
    diff = max(float(onp.abs(a - b).max()) for a, b in zip(l_one, l_tp))
    check(diff <= tol,
          "first-step logits of tp=2 differ from one device by %.4g "
          "(tolerance %.4g)" % (diff, tol))
    # greedy tokens must be equal; where a sequence parts, the step's
    # top-2 margin on the one-device logits says whether bf16 rounding
    # can explain it — a near-tie is reported with its margin, a real
    # divergence fails
    flips = []
    params = {k: p.data()._data for k, p in net.collect_params().items()}
    for i, (a, b) in enumerate(zip(t_one, t_tp)):
        if a == b:
            continue
        j = next(n for n, (x, y) in enumerate(zip(a, b)) if x != y)
        last = onp.asarray(dense(params, prompts[i] + a[:j])[-1])
        margin = float(abs(last[a[j]] - last[b[j]]))
        flips.append({"request": i, "step": j, "tokens": [a[j], b[j]],
                      "margin": margin})
        check(margin <= tol,
              "request %d: tp=2 picked token %d where one device picked "
              "%d at step %d, %.4g apart in logit — no near-tie"
              % (i, b[j], a[j], j, margin))
    emit("mesh_serve", mesh={"tp": 2},
         **({"reduced": reduced} if reduced else {}), requests=n_requests,
         max_new=serve_cfg.max_new, max_abs_first_logit_diff=diff,
         logit_tolerance=tol, tokens_equal=not flips, near_tie_flips=flips,
         one_device_tokens_equal_dense_argmax=not near_ties,
         one_device_near_tie_tokens=near_ties,
         compile_s=c_tp, run_s=r_tp, one_device_compile_s=c_one,
         one_device_run_s=r_one, **watch.verdict(), **mem,
         checked=["one-device engine tokens vs the dense forward's argmax",
                  "first-step logits vs one device",
                  "greedy tokens equal (near-ties reported)",
                  "Pallas kernels in tp=2 decode and prefill programs",
                  "memory in use on both devices"])


def phase_replicas(devices, seed, cfg=None, serve_cfg=None, n_requests=12,
                   prompt_range=(100, 900), min_bytes=MIN_BYTES_IN_USE):
    """(c) four replicas behind the router, replica i on chip i."""
    import numpy as onp

    from mxnet_tpu import serve_router

    cfg, reduced = _mesh_lm_config(cfg)
    watch = CacheWatch()
    serve_cfg = _mesh_serve_config(serve_cfg)
    net = _inference_net(cfg, seed)
    prompts = _requests(onp.random.RandomState(seed), cfg.vocab_size,
                        n_requests, *prompt_range)
    t0 = time.perf_counter()
    group = serve_router.ReplicaGroup.build(net, serve_cfg,
                                            replicas=len(devices))
    t_build = time.perf_counter() - t0
    homes = [set(srv.pool.k_pages.devices()) for srv in group.servers]
    check(homes == [{d} for d in devices],
          "replicas live on %r, not one on each of %r" % (homes, devices))
    t0 = time.perf_counter()
    with group:
        gids = [group.submit(p) for p in prompts]
        served = {}
        for i, gid in enumerate(gids):
            placed = group.requests()[gid]["replica"]
            res = group.result(gid, timeout=600)
            check(res is not None and res["state"] == "done"
                  and len(res["tokens"]) == serve_cfg.max_new,
                  "request %d did not complete: %r" % (i, res))
            served[placed] = served.get(placed, 0) + 1
    t_run = time.perf_counter() - t0
    check(all(srv._error is None for srv in group.servers),
          "a replica's engine died: %r"
          % [srv._error for srv in group.servers])
    check(group.stats()["failovers"] == 0, "a replica failed over")
    _substantial(devices, min_bytes)
    emit("replicas", replicas=len(devices),
         **({"reduced": reduced} if reduced else {}), requests=n_requests,
         max_new=serve_cfg.max_new, dispatched_to=served,
         compile_s=round(t_build, 3), run_s=round(t_run, 3),
         **watch.verdict(), **hbm(devices),
         checked=["replica i on device i", "all requests done",
                  "no failover", "memory in use on all four devices"])


# ----------------------------------------------------------------------
def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1 (default): the train and serve phases on one "
                    "chip; 4: only the dp x tp, tp-serving and replica "
                    "phases and what each is compared with")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import mxnet_tpu  # noqa: F401 — nothing of the repo, nothing to smoke
    from mxnet_tpu.utils import compile_cache
    compile_cache.place_compile_cache()

    devices = phase_device(args.chips)
    if args.chips == 1:
        phase_train(devices[0], args.seed)
        gc.collect()
        phase_serve(devices[0], args.seed)
    else:
        phase_mesh_train(devices, args.seed)
        gc.collect()
        phase_mesh_serve(devices, args.seed)
        gc.collect()
        phase_replicas(devices, args.seed)
    d = devices[0]
    print(json.dumps({"ok": True,
                      "device": {"platform": d.platform,
                                 "kind": d.device_kind,
                                 "count": len(devices)}}),
          flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
