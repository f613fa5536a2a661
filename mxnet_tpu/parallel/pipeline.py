"""Pipeline parallelism: stage-sharded microbatch loop.

The reference has no pipeline subsystem (SURVEY.md §2.3 — PP "Absent").
This module provides schedule-driven pipelines over a ``pp`` mesh axis
using ``shard_map`` + ``ppermute``: each device owns one (or ``v``
interleaved virtual) stage's parameters; a microbatch's activations hop
stage-to-stage over ICI neighbors, cotangents hop back.

Three schedules share one SPMD loop body (the schedule is a set of
host-built slot tables, not a separate code path):

- ``"gpipe"``  — all forwards, flush, all backwards.  In-flight
  activations per stage = M (every microbatch stashed until the flush).
- ``"1f1b"``   — PipeDream-flush/Megatron steady state: one forward,
  one backward per stage per cycle.  Same bubble as GPipe
  ((n-1)/(M+n-1) per pass) but in-flight activations drop from M to
  <= n - stage, so the stash buffer shrinks from (M, ...) to (n, ...).
- ``"interleaved"`` — v virtual stages per device (device d owns global
  stages d, n+d, 2n+d, ...), cutting the warm-up/cool-down bubble by
  ~1/v at the cost of v× more (but v× smaller per-hop wait) neighbor
  exchanges.

``pipeline_apply`` keeps its forward-only contract; ``pipeline_vjp`` is
the training entry: explicit forward AND backward micro-steps under the
chosen schedule, per-stage ``jax.vjp`` with recompute-from-stash (only
stage *inputs* are stored), gradient accumulation across microbatches.
The stage functions must be shape-preserving across hops (same
activation shape between stages), the common transformer case.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from .. import fault as _fault

SCHEDULES = ("gpipe", "1f1b", "interleaved")


def gpipe_forward(stage_fn, params_stacked, x_microbatches, axis_name="pp"):
    """Run under shard_map over ``pp``: device i applies stage i.

    stage_fn(params_i, x) -> y (same shape as x)
    params_stacked: pytree with leading stage axis, sharded over pp
    x_microbatches: (M, ...) microbatch-major input (replicated)
    Returns final-stage outputs (M, ...).
    """
    n = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    my_params = jax.tree_util.tree_map(lambda a: a[0], params_stacked)
    M = x_microbatches.shape[0]
    steps = M + n - 1
    perm = [(i, (i + 1) % n) for i in range(n)]

    out = jnp.zeros_like(x_microbatches)
    carry = jnp.zeros_like(x_microbatches[0])

    def body(t, state):
        out, carry = state
        # stage 0 ingests microbatch t (if in range); others take carry
        mb = jnp.clip(t, 0, M - 1)
        inp = jnp.where(idx == 0,
                        x_microbatches[mb],
                        carry)
        y = stage_fn(my_params, inp)
        # last stage writes result for microbatch (t - n + 1)
        done = t - (n - 1)
        ok = jnp.logical_and(idx == n - 1,
                             jnp.logical_and(done >= 0, done < M))
        out = lax.cond(
            ok,
            lambda o: o.at[jnp.clip(done, 0, M - 1)].set(y),
            lambda o: o,
            out)
        carry = lax.ppermute(y, axis_name, perm)
        return out, carry

    out, _ = lax.fori_loop(0, steps, body, (out, carry))
    # only the last stage holds real outputs; broadcast them so every device
    # holds the last stage's outs (a ppermute ring-shift would only reach one
    # neighbor — ADVICE.md round 1).  All other stages contribute zeros, so a
    # psum over the pp axis is an exact broadcast.
    if n > 1:
        out = lax.psum(jnp.where(idx == n - 1, out, jnp.zeros_like(out)),
                       axis_name)
    return out


# ----------------------------------------------------------------------
# schedule simulation (host-side, pure python ints)
# ----------------------------------------------------------------------
@functools.lru_cache(maxsize=64)
def _simulate(schedule, n, M, v=1, with_backward=True):
    """Event-driven slot simulation of ``schedule`` over ``n`` devices ×
    ``v`` virtual stages × ``M`` microbatches.  One op (F or B) per
    device per slot; an activation/cotangent produced at slot t is
    consumable by the neighbor from slot t+1 (one-hop latency).  Returns
    the per-slot op tables the SPMD loop body indexes, the receive
    tables (what arrives at each device each slot), the stash buffer
    depths, and the bubble statistics — so gpipe/1f1b/interleaved are
    DATA handed to one shared loop body, not three code paths."""
    if schedule not in SCHEDULES:
        raise ValueError("unknown schedule %r (one of %s)"
                         % (schedule, ", ".join(SCHEDULES)))
    L = n * v
    f_done = [[None] * M for _ in range(L)]
    b_done = [[None] * M for _ in range(L)]
    next_f = [0] * L
    next_b = [0] * L
    f_tab, fv_tab, b_tab, bv_tab = [], [], [], []
    done_ops, total_ops = 0, L * M * (2 if with_backward else 1)
    limit = 16 * (L + M + 4)
    t = 0
    while done_ops < total_ops:
        if t >= limit:
            raise AssertionError(
                "schedule %r (n=%d M=%d v=%d) did not converge"
                % (schedule, n, M, v))
        frow, fvrow = [-1] * n, [-1] * n
        brow, bvrow = [-1] * n, [-1] * n
        for d in range(n):
            cand_b = None
            if with_backward:
                for j in range(v):
                    s = j * n + d
                    m = next_b[s]
                    if m >= M or m >= next_f[s]:
                        continue
                    if f_done[s][m] is None or f_done[s][m] >= t:
                        continue
                    if schedule == "gpipe" and next_f[s] < M:
                        continue  # classic flush: backward after all F
                    if s < L - 1 and (b_done[s + 1][m] is None
                                      or b_done[s + 1][m] + 1 > t):
                        continue
                    if cand_b is None or m < cand_b[1]:
                        cand_b = (s, m)
            cand_f = None
            for j in range(v):
                s = j * n + d
                m = next_f[s]
                if m >= M:
                    continue
                if with_backward and schedule != "gpipe" \
                        and next_f[s] - next_b[s] >= L - s:
                    continue  # 1F1B in-flight cap: B catches up first
                if s > 0 and (f_done[s - 1][m] is None
                              or f_done[s - 1][m] + 1 > t):
                    continue
                if cand_f is None or (m, j) < (cand_f[1],
                                               cand_f[0] // n):
                    cand_f = (s, m)
            if cand_b is not None:  # backward has priority (1F1B)
                s, m = cand_b
                brow[d], bvrow[d] = m, s // n
                b_done[s][m] = t
                next_b[s] += 1
                done_ops += 1
            elif cand_f is not None:
                s, m = cand_f
                frow[d], fvrow[d] = m, s // n
                f_done[s][m] = t
                next_f[s] += 1
                done_ops += 1
        f_tab.append(frow)
        fv_tab.append(fvrow)
        b_tab.append(brow)
        bv_tab.append(bvrow)
        t += 1
    T = t

    # receive tables: the activation/cotangent arriving at device d at
    # slot t (sent by its neighbor at t-1)
    rf_mb = [[-1] * n for _ in range(T)]
    rf_vs = [[-1] * n for _ in range(T)]
    rb_mb = [[-1] * n for _ in range(T)]
    rb_vs = [[-1] * n for _ in range(T)]
    for s in range(L - 1):
        for m in range(M):
            slot = f_done[s][m] + 1
            if slot < T:
                rf_mb[slot][(s + 1) % n] = m
                rf_vs[slot][(s + 1) % n] = (s + 1) // n
    if with_backward:
        for s in range(1, L):
            for m in range(M):
                slot = b_done[s][m] + 1
                if slot < T:
                    rb_mb[slot][(s - 1) % n] = m
                    rb_vs[slot][(s - 1) % n] = (s - 1) // n

    def _window(write, free):
        """Max span of simultaneously-live microbatch indices -> minimal
        safe ring-buffer depth for ``m % depth`` indexing."""
        best = 1
        for s in range(L):
            lives = [(write(s, m), free(s, m)) for m in range(M)
                     if write(s, m) is not None]
            for i, (w1, f1) in enumerate(lives):
                for j in range(i + 1, len(lives)):
                    w2, f2 = lives[j]
                    if w1 <= f2 and w2 <= f1:  # overlap
                        best = max(best, j - i + 1)
        return best

    if with_backward:
        act_buf = _window(
            lambda s, m: f_done[s][m] if s == 0
            else f_done[s - 1][m] + 1,
            lambda s, m: b_done[s][m])
        cot_buf = _window(
            lambda s, m: None if s >= L - 1 else b_done[s + 1][m] + 1,
            lambda s, m: b_done[s][m])
    else:
        act_buf = _window(
            lambda s, m: f_done[s][m] if s == 0
            else f_done[s - 1][m] + 1,
            lambda s, m: f_done[s][m])
        cot_buf = 1
    max_inflight = max(
        (next_f[s] if not with_backward else
         max((sum(1 for m in range(M)
                  if f_done[s][m] <= tt and (b_done[s][m] is None
                                             or b_done[s][m] > tt))
              for tt in range(T)), default=0))
        for s in range(L))
    return {
        "f_mb": f_tab, "f_vs": fv_tab, "b_mb": b_tab, "b_vs": bv_tab,
        "rf_mb": rf_mb, "rf_vs": rf_vs, "rb_mb": rb_mb, "rb_vs": rb_vs,
        "slots": T, "act_buf": act_buf, "cot_buf": cot_buf,
        "max_inflight": max_inflight,
        "bubble_fraction": 1.0 - total_ops / float(T * n),
    }


def schedule_info(schedule, n, num_microbatches, virtual_stages=1,
                  with_backward=True):
    """Analytic schedule statistics (slots, bubble fraction, stash
    depths, peak in-flight microbatches) for a pipeline of ``n`` devices
    × ``virtual_stages`` running ``num_microbatches`` — the numbers the
    1F1B memory claim is asserted against
    (``tests/test_parallel.py``)."""
    sim = _simulate(schedule, n, num_microbatches, virtual_stages,
                    with_backward)
    return {k: sim[k] for k in ("slots", "act_buf", "cot_buf",
                                "max_inflight", "bubble_fraction")}


def _stage_order(n, v):
    """Device-major placement for interleaving: device d's chunk j holds
    global stage j*n + d (so every forward hop is the d->d+1 neighbor
    exchange).  Returns (placement order, inverse) index lists."""
    order = [j * n + d for d in range(n) for j in range(v)]
    inv = [(s % n) * v + (s // n) for s in range(n * v)]
    return order, inv


def _scheduled_pipeline(stage_fn, params_dev, xm, gym, sim, n, v,
                        axis_name, with_backward):
    """Shared SPMD loop body for every schedule: runs under shard_map,
    one slot per fori_loop step.  Per slot each device (1) stores the
    activation/cotangent that arrived from its neighbor, (2) performs
    the schedule table's op — a stage forward, a stage backward
    (``jax.vjp`` with recompute from the stage-input stash), or nothing
    (bubble) — and (3) exchanges the produced payloads: activations ride
    the d->d+1 ring, cotangents the d->d-1 ring.  The collectives are
    UNCONDITIONAL (outside the op conds) so every device always joins
    the same exchanges — idle slots send zeros."""
    M = xm.shape[0]
    mb_shape = xm.shape[1:]
    dtype = xm.dtype
    L = n * v
    A = sim["act_buf"]
    C = sim["cot_buf"]
    T = sim["slots"]
    tab = lambda key: jnp.asarray(sim[key], jnp.int32)  # noqa: E731
    f_mb, f_vs = tab("f_mb"), tab("f_vs")
    b_mb, b_vs = tab("b_mb"), tab("b_vs")
    rf_mb, rf_vs = tab("rf_mb"), tab("rf_vs")
    rb_mb, rb_vs = tab("rb_mb"), tab("rb_vs")
    perm_fwd = [(i, (i + 1) % n) for i in range(n)]
    perm_bwd = [(i, (i - 1) % n) for i in range(n)]
    idx = lax.axis_index(axis_name)
    zero_mb = jnp.zeros(mb_shape, dtype)
    tree = jax.tree_util.tree_map

    def body(t, carry):
        acts, cots, outs, dxs, dparams, fmsg, bmsg = carry
        # 1. file the neighbor payloads that arrived this slot
        rfm = rf_mb[t, idx]
        acts = lax.cond(
            rfm >= 0,
            lambda a: a.at[rf_vs[t, idx],
                           jnp.remainder(rfm, A)].set(fmsg),
            lambda a: a, acts)
        if with_backward:
            rbm = rb_mb[t, idx]
            cots = lax.cond(
                rbm >= 0,
                lambda c: c.at[rb_vs[t, idx],
                               jnp.remainder(rbm, C)].set(bmsg),
                lambda c: c, cots)
        fm, fv = f_mb[t, idx], f_vs[t, idx]
        bm, bv = b_mb[t, idx], b_vs[t, idx]
        state = (acts, cots, outs, dxs, dparams)

        def do_fwd(st):
            acts, cots, outs, dxs, dparams = st
            m = jnp.clip(fm, 0, M - 1)
            s = fv * n + idx  # global stage
            inp = jnp.where(s == 0, xm[m],
                            acts[fv, jnp.remainder(m, A)])
            # stage 0 stashes its own input for the backward replay;
            # elsewhere this rewrites the arrival in place
            acts = acts.at[fv, jnp.remainder(m, A)].set(inp)
            y = stage_fn(tree(lambda a: a[fv], params_dev), inp)
            outs = lax.cond(s == L - 1,
                            lambda o: o.at[m].set(y), lambda o: o, outs)
            return (acts, cots, outs, dxs, dparams), y, zero_mb

        def do_bwd(st):
            acts, cots, outs, dxs, dparams = st
            m = jnp.clip(bm, 0, M - 1)
            s = bv * n + idx
            inp = acts[bv, jnp.remainder(m, A)]
            g_in = jnp.where(s == L - 1, gym[m],
                             cots[bv, jnp.remainder(m, C)])
            _, vjp = jax.vjp(stage_fn,
                             tree(lambda a: a[bv], params_dev), inp)
            dp, dx = vjp(g_in.astype(dtype))
            dparams = tree(lambda acc, g: acc.at[bv].add(g),
                           dparams, dp)
            dxs = lax.cond(s == 0,
                           lambda o: o.at[m].set(dx), lambda o: o, dxs)
            return (acts, cots, outs, dxs, dparams), zero_mb, dx

        def do_idle(st):
            return st, zero_mb, zero_mb

        if with_backward:
            state, fpay, bpay = lax.cond(
                fm >= 0, do_fwd,
                lambda st: lax.cond(bm >= 0, do_bwd, do_idle, st),
                state)
        else:
            state, fpay, bpay = lax.cond(fm >= 0, do_fwd, do_idle,
                                         state)
        acts, cots, outs, dxs, dparams = state
        # 2. uniform neighbor exchanges (every device, every slot)
        fmsg = lax.ppermute(fpay, axis_name, perm_fwd)
        if with_backward:
            bmsg = lax.ppermute(bpay, axis_name, perm_bwd)
        return acts, cots, outs, dxs, dparams, fmsg, bmsg

    acts0 = jnp.zeros((v, A) + mb_shape, dtype)
    outs0 = jnp.zeros((M,) + mb_shape, dtype)
    if with_backward:
        cots0 = jnp.zeros((v, C) + mb_shape, dtype)
        dxs0 = jnp.zeros((M,) + mb_shape, dtype)
        dparams0 = tree(jnp.zeros_like, params_dev)
        bmsg0 = zero_mb
    else:  # scalar placeholders: the fwd-only loop never touches them
        cots0 = dxs0 = dparams0 = bmsg0 = jnp.zeros((), dtype)
    carry = (acts0, cots0, outs0, dxs0, dparams0, zero_mb, bmsg0)
    _, _, outs, dxs, dparams, _, _ = lax.fori_loop(0, T, body, carry)
    # only the last stage holds real outputs / stage 0 the input grads;
    # the psum over one-hot contributions is an exact broadcast
    outs = lax.psum(jnp.where(idx == n - 1, outs, jnp.zeros_like(outs)),
                    axis_name)
    if not with_backward:
        return outs
    dxs = lax.psum(jnp.where(idx == 0, dxs, jnp.zeros_like(dxs)),
                   axis_name)
    return outs, dxs, dparams


def _resolve_stages(schedule, virtual_stages, params_stacked, n):
    """Validate schedule/virtual_stages against the stage stack; returns
    the effective v."""
    if schedule not in SCHEDULES:
        raise ValueError("unknown schedule %r (one of %s)"
                         % (schedule, ", ".join(SCHEDULES)))
    v = int(virtual_stages)
    if v > 1 and schedule != "interleaved":
        raise ValueError("virtual_stages=%d requires "
                         "schedule='interleaved'" % v)
    leaves = jax.tree_util.tree_leaves(params_stacked)
    L = leaves[0].shape[0]
    if L != n * v:
        raise ValueError(
            "stage stack has %d stages but mesh axis is %d devices x "
            "%d virtual stages" % (L, n, v))
    return v


def _launch(attempt, mutating, _comm, _gen):
    """The shared pipeline fault seam (same protocol as kvstore/ring):
    multi-process launches ride ``coordinated_call`` — after any failed
    attempt every worker votes and re-issues together, and a mid-op
    failure of a mutating step aborts everywhere; single-process is
    plain ``retry_call``, never a per-attempt timeout (an abandoned
    attempt thread would issue a second identical collective
    concurrently on the same mesh)."""
    if _comm is not None or jax.process_count() > 1:
        from .. import fault_dist as _fdist
        # the production path (ambient comm/gen) opts into step-lease
        # mode: an ACTIVE lease covers the launch with the step-boundary
        # aggregate vote instead of a per-op round.  Test seams that
        # drive explicit comms/gens stay on per-op voting — their round
        # accounting is the thing under test.
        return _fdist.coordinated_call(attempt, op="pipeline",
                                       mutating=mutating, comm=_comm,
                                       gen=_gen,
                                       lease=(_comm is None and
                                              _gen is None) or None)
    policy = _fault.entry_only_policy() if mutating \
        else _fault.mutating_policy()
    # mxlint: disable=R3 -- the mutating branch right above selects
    # entry_only_policy(); the pure forward/vjp retries any transient
    return _fault.retry_call(attempt, op="pipeline", policy=policy)


def pipeline_vjp(stage_fn, params_stacked, x, gy, mesh, num_microbatches,
                 axis_name="pp", schedule="1f1b", virtual_stages=1,
                 mutating=False, _comm=None, _gen=None):
    """Forward AND backward of a pp-sharded stage stack under an
    explicit pipeline schedule — the training path.

    x: (B, ...) inputs, gy: (B, ...) output cotangent (same shape by the
    shape-preserving-stage contract).  Returns ``(y, dx, dparams)``:
    stage outputs, input cotangent, and per-stage parameter gradients
    (summed over microbatches — stages must be batch-row-independent,
    the same assumption GPipe's microbatching already makes).

    ``schedule="1f1b"`` (default) holds at most ``n - stage`` microbatch
    activations in flight (the stash buffer is (v, n_buf<=n, ...)
    instead of GPipe's (v, M, ...)); ``"interleaved"`` with
    ``virtual_stages=v`` additionally cuts the warm-up/cool-down bubble
    by ~1/v.  ``"gpipe"`` reproduces the classic flush schedule on the
    same loop body.  Backward recomputes each stage's forward from the
    stashed stage INPUT inside ``jax.vjp`` (activations-in-backward are
    never stored).  Collectives launch through the same fault seam as
    :func:`pipeline_apply` (``collective_check("pipeline")`` +
    coordinated/retry call; ``mutating=True`` aborts every worker on a
    mid-op failure instead of re-running the mutation).
    """
    n = mesh.shape[axis_name]
    v = _resolve_stages(schedule, virtual_stages, params_stacked, n)
    B = x.shape[0]
    M = num_microbatches
    assert B % M == 0
    xm = x.reshape((M, B // M) + x.shape[1:])
    gym = gy.reshape(xm.shape)
    sim = _simulate(schedule, n, M, v, with_backward=True)
    order, inv = _stage_order(n, v)
    tree = jax.tree_util.tree_map
    params_dev = tree(lambda a: a[jnp.asarray(order)], params_stacked)
    pspec = tree(lambda _: P(axis_name), params_stacked)

    def body(params, xmb, gymb):
        return _scheduled_pipeline(stage_fn, params, xmb, gymb, sim, n,
                                   v, axis_name, with_backward=True)

    def attempt():
        _fault.collective_check("pipeline")
        return jax.shard_map(
            body, mesh=mesh, in_specs=(pspec, P(), P()),
            out_specs=(P(), P(), pspec),
            check_vma=False)(params_dev, xm, gym)

    outs, dxs, dparams = _launch(attempt, mutating, _comm, _gen)
    y = outs.reshape((B,) + outs.shape[2:])
    dx = dxs.reshape((B,) + dxs.shape[2:])
    # gathered dparams are device-major; un-permute to stage order
    dparams = tree(lambda a: a[jnp.asarray(inv)], dparams)
    return y, dx, dparams


def pipeline_apply(stage_fn, params_stacked, x, mesh, num_microbatches,
                   axis_name="pp", mutating=False, _comm=None, _gen=None,
                   schedule="gpipe", virtual_stages=1):
    """Forward a batch through a pp-sharded stage stack.

    x: (B, ...); split into ``num_microbatches`` along axis 0.
    params_stacked: pytree whose leaves have leading dim = pp size.

    The stage-transfer collectives (``ppermute``/``psum`` inside
    :func:`gpipe_forward`) launch through the same fault seam as
    kvstore/ring (``mx.fault.dist.coordinated_call``): in a multi-process
    job every worker votes after a failed attempt and re-issues the
    pipeline step together — a solo re-entry against peers still parked
    in the original ``ppermute`` ring would deadlock the mesh.  Pass
    ``mutating=True`` when ``stage_fn`` mutates host state (e.g. an
    in-place stats update in a training integration): a mid-op failure
    then aborts every worker instead of re-running the mutation.
    Single-process, the launch is plain ``mx.fault.retry_call`` (the
    forward is pure, so re-execution is safe); never a per-attempt
    timeout — an abandoned attempt thread would issue a second identical
    collective concurrently on the same mesh.  ``_comm``/``_gen`` are
    test seams mirroring ``coordinated_call``'s parameters.

    ``schedule`` selects the pipeline schedule (``"gpipe"`` default —
    byte-identical lowering to the pre-schedule code; forward-only
    ``"1f1b"`` shares GPipe's timing by construction and exists so the
    training schedule's lowering is pinnable; ``"interleaved"`` +
    ``virtual_stages=v`` runs v virtual stages per device).  The
    training path with a real 1F1B steady state is
    :func:`pipeline_vjp`.
    """
    n = mesh.shape[axis_name]
    v = _resolve_stages(schedule, virtual_stages, params_stacked, n)
    B = x.shape[0]
    M = num_microbatches
    assert B % M == 0
    xm = x.reshape((M, B // M) + x.shape[1:])
    tree = jax.tree_util.tree_map
    pspec = tree(lambda _: P(axis_name), params_stacked)

    if schedule == "gpipe":
        def body(params, xmb):
            return gpipe_forward(stage_fn, params, xmb, axis_name)
        args = (params_stacked, xm)
    else:
        sim = _simulate(schedule, n, M, v, with_backward=False)
        order, _ = _stage_order(n, v)
        params_dev = tree(lambda a: a[jnp.asarray(order)],
                          params_stacked)

        def body(params, xmb):
            return _scheduled_pipeline(stage_fn, params, xmb, None, sim,
                                       n, v, axis_name,
                                       with_backward=False)
        args = (params_dev, xm)

    def attempt():
        _fault.collective_check("pipeline")
        return jax.shard_map(body, mesh=mesh, in_specs=(pspec, P()),
                             out_specs=P(), check_vma=False)(*args)

    out = _launch(attempt, mutating, _comm, _gen)
    return out.reshape((B,) + out.shape[2:])
