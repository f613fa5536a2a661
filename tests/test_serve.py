"""mx.serve — continuous-batching decode runtime (tier-1 unit tests).

Decode correctness is the load-bearing half: prefill + N decode steps
through the paged KV cache must reproduce the full-sequence forward's
logits (the tiny config is fp32: to a few ulp, XLA being free to order
a (1, T0) and a (1, T) reduction differently, and the greedy token
exactly), paged and contiguous layouts must agree bit-for-bit, and the lowered decode program must be
host-transfer-free with every KV buffer at the fixed pool shape (the
O(1)-in-generated-length property).  The scheduler half mirrors how
the fault runtime is tested: protocol unit tests plus the mxverify
scenario family and the mxrace confirmation scenario, each with its
liveness mutation.
"""
import os
import threading

import numpy as onp
import pytest

import jax.numpy as jnp

import mxnet_tpu as mx  # noqa: F401 — namespace init
from mxnet_tpu import _tape, serve
from mxnet_tpu.models import (CacheSpec, CacheView, TransformerLM,
                              init_pools, tiny_config)
from mxnet_tpu.ndarray.ndarray import NDArray


def _net(cfg=None):
    cfg = cfg or tiny_config()
    net = TransformerLM(cfg)
    net.initialize()
    return cfg, net


def _full_logits(net, toks):
    with _tape.suspend_recording():
        return net.forward(NDArray(jnp.asarray(toks)))._data


def _prefill(net, spec, k, v, page_row, toks, true_len):
    view = CacheView("prefill", k, v, spec.page_size,
                     page_row=jnp.asarray(page_row, jnp.int32),
                     true_len=jnp.int32(true_len))
    with _tape.suspend_recording():
        logits = net.forward(NDArray(jnp.asarray(toks)), cache=view)._data
    return logits, view.k, view.v


def _decode(net, spec, k, v, page_table, lengths, active, toks):
    view = CacheView("decode", k, v, spec.page_size,
                     page_table=jnp.asarray(page_table, jnp.int32),
                     lengths=jnp.asarray(lengths, jnp.int32),
                     active=jnp.asarray(active, bool))
    with _tape.suspend_recording():
        logits = net.forward(NDArray(jnp.asarray(toks)), cache=view)._data
    return logits, view.k, view.v


def _spec(cfg, page_size=4, slots=2, pages=12, mp=6):
    return CacheSpec(n_layers=cfg.n_layers, n_kv_heads=cfg.n_kv_heads,
                     head_dim=cfg.dim // cfg.n_heads, slots=slots,
                     pages=pages, page_size=page_size,
                     max_pages_per_slot=mp, dtype="float32")


# ----------------------------------------------------------------------
# decode correctness
# ----------------------------------------------------------------------
def _assert_same_logits(got, want, what):
    """fp32 logits of O(1) magnitude equal to a few ulp (2**-23 each),
    and the greedy token they pick exactly."""
    onp.testing.assert_allclose(got, want, rtol=0, atol=16 * 2.0 ** -23,
                                err_msg=what)
    assert onp.array_equal(got.argmax(-1), want.argmax(-1)), what


def test_prefill_plus_decode_matches_full_forward_exactly():
    """The parity criterion: prefill(T0) + (T-T0) paged decode steps
    produce, token by token, the full-sequence forward's logits — GQA
    heads, per-slot RoPE offsets, page-crossing writes and all.  fp32
    tiny config: equal to a few ulp, greedy tokens exactly equal."""
    cfg, net = _net()
    spec = _spec(cfg)
    rng = onp.random.RandomState(0)
    T, T0 = 14, 5
    toks = rng.randint(0, cfg.vocab_size, (1, T)).astype(onp.int32)
    full = onp.asarray(_full_logits(net, toks))

    k, v = init_pools(spec)
    row = onp.array([1, 2, 3, 4, 5, 6], onp.int32)
    pre, k, v = _prefill(net, spec, k, v, row, toks[:, :T0], T0)
    _assert_same_logits(onp.asarray(pre)[0, :T0], full[0, :T0],
                        "prefill diverged from the full forward")

    page_table = onp.zeros((2, spec.max_pages_per_slot), onp.int32)
    page_table[0] = row
    lengths = onp.array([T0, 0], onp.int32)
    active = onp.array([True, False])
    for t in range(T0, T):
        step = onp.array([[toks[0, t]], [0]], onp.int32)
        logits, k, v = _decode(net, spec, k, v, page_table, lengths,
                               active, step)
        _assert_same_logits(
            onp.asarray(logits)[0, 0], full[0, t],
            "decode step %d diverged from the full forward" % t)
        lengths = lengths + active.astype(onp.int32)


def test_paged_equals_contiguous_bit_for_bit():
    """The same request decoded through 4-token pages scattered across
    the pool and through one slot-sized page (the contiguous layout)
    must produce identical bits — paging is a pure layout change."""
    cfg, net = _net()
    rng = onp.random.RandomState(1)
    T, T0 = 12, 4
    toks = rng.randint(0, cfg.vocab_size, (1, T)).astype(onp.int32)

    outs = []
    for page_size, row in ((4, [5, 1, 9]), (64, [1])):
        spec = _spec(cfg, page_size=page_size, slots=2, pages=12,
                     mp=len(row))
        k, v = init_pools(spec)
        _, k, v = _prefill(net, spec, k, v,
                           onp.asarray(row, onp.int32), toks[:, :T0], T0)
        page_table = onp.zeros((2, len(row)), onp.int32)
        page_table[0] = row
        lengths = onp.array([T0, 0], onp.int32)
        active = onp.array([True, False])
        got = []
        for t in range(T0, T):
            step = onp.array([[toks[0, t]], [0]], onp.int32)
            logits, k, v = _decode(net, spec, k, v, page_table,
                                   lengths, active, step)
            got.append(onp.asarray(logits)[0, 0])
            lengths = lengths + active.astype(onp.int32)
        outs.append(onp.stack(got))
    assert onp.array_equal(outs[0], outs[1])


def test_paged_attention_kernel_matches_dense_fallback():
    """The Pallas page-table kernel (interpret mode on CPU) against the
    XLA dense-gather fallback on GQA shapes with ragged lengths,
    including an empty slot."""
    from mxnet_tpu.ops import pallas_ops as po
    prev = po._INTERPRET
    po._INTERPRET = True
    try:
        S, H, Hkv, D, psz, P, MP = 3, 8, 2, 64, 128, 7, 3
        rng = onp.random.RandomState(2)
        q = jnp.asarray(rng.randn(S, H, D).astype(onp.float32))
        kp = jnp.asarray(rng.randn(P, Hkv, psz, D).astype(onp.float32))
        vp = jnp.asarray(rng.randn(P, Hkv, psz, D).astype(onp.float32))
        pt = jnp.asarray(rng.randint(1, P, (S, MP)).astype(onp.int32))
        lens = jnp.asarray(onp.array([5, 3 * psz, 0], onp.int32))
        dense = po._paged_dense(q, kp, vp, pt, lens, D ** -0.5)
        kern = po._paged_kernel_call(q, kp, vp, pt, lens, D ** -0.5)
        onp.testing.assert_allclose(onp.asarray(kern),
                                    onp.asarray(dense), atol=2e-5)
    finally:
        po._INTERPRET = prev


def test_decode_program_fixed_kv_shapes_and_no_host_transfers():
    """The O(1)-decode criterion on the ARTIFACT: every KV buffer in
    the lowered decode program has the fixed pool shape (nothing scales
    with generated length — the same program serves step 1 and step
    10k), and the program is host-transfer-free (analysis.hlo), the
    same verdict tools/hlo_snapshot.py ratchets in CI."""
    from mxnet_tpu.analysis import hlo
    lowered, info = serve.lower_decode_program()
    txt = lowered.as_text()
    res = hlo.check_no_host_transfers(txt)
    assert res.ok, res.details
    pool = "x".join(str(d) for d in info["pool_shape"])
    assert "tensor<%sx" % pool in txt  # the KV pools, pool-shaped
    # nothing in the program may carry a sequence-length axis beyond
    # the pool's own: the largest tensors are exactly the two pools
    import re
    dims = [tuple(int(d) for d in m.group(1).split("x"))
            for m in re.finditer(r"tensor<([0-9x]+)x[a-z]", txt)]
    pool_elems = 1
    for d in info["pool_shape"]:
        pool_elems *= d
    assert max(onp.prod(d) for d in dims) <= pool_elems


# ----------------------------------------------------------------------
# scheduler protocol
# ----------------------------------------------------------------------
def _sched(**kw):
    args = dict(slots=2, pages=9, page_size=2, max_pages_per_slot=4)
    args.update(kw)
    return serve.SlotScheduler(**args)


def test_scheduler_lifecycle_and_conservation():
    s = _sched()
    rid = s.submit(3, 2)
    plan = s.admit_next()
    assert plan["rid"] == rid and plan["prefill_len"] == 3
    assert s.commit_prefill(plan, 7) is None
    snap = s.begin_step()
    assert [e["rid"] for e in snap] == [rid]
    assert s.commit_step(snap, [(9, False)]) == [rid]
    req = s.request(rid)
    assert req["state"] == "done" and req["tokens"] == (7, 9)
    assert s.check_conservation() == []
    assert s.stats()["free_pages"] == 8


def test_scheduler_stale_commit_dropped_by_epoch_check():
    """The TOCTOU the mxverify scenario hunts, as a unit test: cancel
    mid-flight, reassign the slot, then commit the stale snapshot —
    the epoch check must drop it (no token crosses requests)."""
    s = _sched(slots=1)
    a = s.submit(3, 3)
    b = s.submit(3, 3)
    plan = s.admit_next()
    s.commit_prefill(plan, 7)
    snap = s.begin_step()          # decode in flight for A
    assert s.cancel(a)             # client gone: slot freed NOW
    plan_b = s.admit_next()        # B takes the same slot, new epoch
    assert plan_b["rid"] == b and plan_b["slot"] == snap[0]["slot"]
    assert plan_b["epoch"] != snap[0]["epoch"]
    s.commit_prefill(plan_b, 20)
    s.commit_step(snap, [(("stale", a), False)])  # the in-flight result
    assert s.request(b)["tokens"] == (20,)  # nothing crossed
    assert s.request(a)["state"] == "cancelled"
    assert s.check_conservation() == []


def test_scheduler_preempts_youngest_under_page_pressure():
    s = _sched(slots=2, pages=5, page_size=2, max_pages_per_slot=4)
    a = s.submit(4, 6)             # 2 pages now, grows
    b = s.submit(4, 6)
    for _ in range(2):
        plan = s.admit_next()
        s.commit_prefill(plan, 5)
    assert s.stats()["free_pages"] == 0
    # both slots need a page at position 4 -> the YOUNGER (b) is
    # preempted back to the queue front, freeing pages for a
    snap = s.begin_step()
    assert [e["rid"] for e in snap] == [a]
    assert s.request(b)["state"] == "waiting"
    assert s.stats()["preemptions"] >= 1
    assert s.check_conservation() == []


def test_scheduler_random_ops_conserve_pages():
    rng = onp.random.RandomState(3)
    s = _sched(slots=3, pages=11, page_size=2, max_pages_per_slot=4)
    live = []
    for it in range(300):
        op = rng.randint(0, 5)
        if op == 0:
            live.append(s.submit(int(rng.randint(1, 7)),
                                 int(rng.randint(1, 5))))
        elif op == 1 and live:
            s.cancel(live[rng.randint(len(live))])
        elif op == 2:
            plan = s.admit_next()
            if plan is not None and rng.rand() < 0.9:
                s.commit_prefill(plan, it)
        else:
            snap = s.begin_step()
            s.commit_step(snap, [(it, rng.rand() < 0.2)
                                 for _ in snap])
        assert s.check_conservation() == [], "iteration %d" % it


def test_scheduler_cancel_of_failed_request_stays_failed():
    """Terminal states are terminal: cancelling a request that already
    FAILED (regrew past the per-slot page budget) must not rewrite it
    to 'cancelled' — the client would lose the real failure."""
    s = _sched(slots=1, pages=13, page_size=2, max_pages_per_slot=4)
    rid = s.submit(9, 2)           # 9 tokens -> 5 pages > budget of 4
    assert s.admit_next() is None  # unservable: marked failed
    assert s.request(rid)["state"] == "failed"
    assert s.cancel(rid) is False  # already terminal
    assert s.request(rid)["state"] == "failed"


def test_scheduler_failed_head_does_not_block_admission():
    """An unservable head-of-queue request is failed AND skipped in the
    same admit_next call — it must not head-of-line-block the
    admissible request queued behind it."""
    s = _sched(slots=1, pages=13, page_size=2, max_pages_per_slot=4)
    big = s.submit(9, 2)           # 5 pages > budget: unservable
    ok = s.submit(3, 2)
    plan = s.admit_next()
    assert plan is not None and plan["rid"] == ok
    assert s.request(big)["state"] == "failed"
    assert s.check_conservation() == []


def test_scheduler_purge_bounds_request_state():
    """Terminal records are purgeable (the Server does this after
    delivery) so per-request scheduler state — copied per _set_req —
    stays bounded by LIVE requests; a live request refuses to purge."""
    s = _sched()
    rid = s.submit(3, 1)
    assert s.purge(rid) is None    # live: refused
    plan = s.admit_next()
    assert s.commit_prefill(plan, 7) == rid   # max_new=1: done
    purged = s.purge(rid)
    assert purged["state"] == "done" and purged["tokens"] == (7,)
    assert s.request(rid) is None and s.stats()["requests"] == 0
    assert s.purge(rid) is None    # idempotent
    assert s.check_conservation() == []


def test_scheduler_cap_filling_prompt_terminates():
    """A prompt that exactly fills the slot's page budget leaves no
    cache position for a decode write: the request must finish at the
    prefill commit (one generated token), never sit in 'running' with
    its pages leaked."""
    s = _sched(slots=1, pages=9, page_size=2, max_pages_per_slot=4)
    rid = s.submit(8, 4)           # 8 tokens == 4 pages * 2 == cap
    plan = s.admit_next()
    assert plan["prefill_len"] == 8
    assert s.commit_prefill(plan, 7) == rid   # terminal at the commit
    req = s.request(rid)
    assert req["state"] == "done" and req["tokens"] == (7,)
    assert s.begin_step() == ()    # nothing left running
    assert s.check_conservation() == []
    assert s.stats()["free_slots"] == 1


# ----------------------------------------------------------------------
# server end-to-end
# ----------------------------------------------------------------------
def _serve_cfg(**kw):
    args = dict(slots=3, page_size=8, pages=24, ladder=(16, 32),
                max_new=10, cache_dir=None, int8=False)
    args.update(kw)
    return serve.ServeConfig(**args)


def test_server_continuous_batch_matches_solo_generation():
    """Seven concurrent requests through the continuous batcher must
    produce EXACTLY the tokens each request gets when served alone —
    batching and slot placement cannot leak into the math (greedy
    decode, fp32)."""
    cfg, net = _net()
    rng = onp.random.RandomState(4)
    prompts = [list(rng.randint(1, cfg.vocab_size,
                                int(rng.randint(3, 14))))
               for _ in range(7)]
    budgets = [3 + (i % 5) for i in range(7)]
    srv = serve.Server(net, _serve_cfg())
    with srv:
        rids = [srv.submit(p, max_new=m)
                for p, m in zip(prompts, budgets)]
        batched = [srv.result(r, timeout=120)["tokens"] for r in rids]
    assert srv.sched.check_conservation() == []
    assert all(len(t) == m for t, m in zip(batched, budgets))

    solo_srv = serve.Server(net, _serve_cfg(slots=1))
    with solo_srv:
        for i in (0, 3, 6):
            solo = solo_srv.result(
                solo_srv.submit(prompts[i], max_new=budgets[i]),
                timeout=120)["tokens"]
            assert solo == batched[i]


def test_server_preemption_under_page_pressure_completes_all():
    cfg, net = _net()
    rng = onp.random.RandomState(5)
    srv = serve.Server(net, _serve_cfg(slots=3, page_size=4, pages=10,
                                       ladder=(8, 16), max_new=12))
    prompts = [list(rng.randint(1, cfg.vocab_size, 7))
               for _ in range(4)]
    with srv:
        rids = [srv.submit(p, max_new=10) for p in prompts]
        res = [srv.result(r, timeout=180) for r in rids]
    assert all(r["state"] == "done" and len(r["tokens"]) == 10
               for r in res)
    assert srv.sched.check_conservation() == []
    # delivered requests were purged: scheduler state stays bounded
    assert srv.sched.stats()["requests"] == 0


def test_server_cancel_mid_run_frees_and_completes_rest():
    cfg, net = _net()
    rng = onp.random.RandomState(6)
    srv = serve.Server(net, _serve_cfg())
    with srv:
        keep = srv.submit(list(rng.randint(1, cfg.vocab_size, 6)),
                          max_new=8)
        drop = srv.submit(list(rng.randint(1, cfg.vocab_size, 6)),
                          max_new=8)
        srv.cancel(drop)
        res_drop = srv.result(drop, timeout=120)
        res_keep = srv.result(keep, timeout=120)
    assert res_keep["state"] == "done" and len(res_keep["tokens"]) == 8
    assert res_drop["state"] in ("cancelled", "done")
    assert srv.sched.check_conservation() == []


def test_server_rejects_empty_prompt_and_zero_max_new():
    cfg, net = _net()
    srv = serve.Server(net, _serve_cfg())
    with pytest.raises(ValueError, match="empty prompt"):
        srv.submit([])
    with pytest.raises(ValueError, match="max_new"):
        srv.submit([1, 2], max_new=0)


@pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning")
def test_server_engine_death_fails_waiters_fast():
    """A dying engine thread must not strand blocked result() callers:
    every live waiter wakes and re-raises the engine's error, and new
    submits are refused."""
    cfg, net = _net()
    srv = serve.Server(net, _serve_cfg())
    boom = RuntimeError("injected engine fault")

    def _dead_step():
        raise boom

    srv.engine_step = _dead_step
    with srv:
        try:
            rid = srv.submit([1, 2, 3], max_new=4)
        except RuntimeError:
            rid = None  # engine died before the submit: also correct
        if rid is not None:
            with pytest.raises(RuntimeError) as ei:
                srv.result(rid, timeout=30)
            assert ei.value.__cause__ is boom
    with pytest.raises(RuntimeError):
        srv.submit([1], max_new=1)


def test_server_stop_wakes_blocked_result_waiters():
    """An orderly stop() must not strand a blocked result() caller:
    live waiters wake and read their request's honest non-terminal
    state."""
    cfg, net = _net()
    srv = serve.Server(net, _serve_cfg())   # engine never started
    rid = srv.submit([1, 2, 3], max_new=4)
    out = {}

    def waiter():
        out["req"] = srv.result(rid, timeout=30)

    t = threading.Thread(target=waiter)
    t.start()
    t.join(timeout=0.3)
    assert t.is_alive()                     # genuinely blocked
    srv.stop()
    t.join(timeout=10)
    assert not t.is_alive(), "stop() left the waiter stranded"
    assert out["req"]["state"] == "waiting"  # honest: never served


def test_server_result_is_single_delivery_and_store_bounded():
    cfg, net = _net()
    srv = serve.Server(net, _serve_cfg())
    with srv:
        rid = srv.submit([1, 2, 3], max_new=3)
        res = srv.result(rid, timeout=120)
        assert res["state"] == "done" and len(res["tokens"]) == 3
        assert srv.result(rid, timeout=1) is None  # evicted on delivery
    assert srv._results == {} and srv._prompts == {}
    assert srv.sched.stats()["requests"] == 0


def test_warm_pool_persistent_cache_hit(tmp_path):
    """The cold-start-free replica claim: a second WarmPool over the
    same persistent cache dir compiles every program out of the cache
    (zero new entries -> stats['cache_hit'])."""
    cfg, net = _net()
    scfg = _serve_cfg(slots=2, ladder=(16,), max_new=6,
                      cache_dir=str(tmp_path / "cache"))
    cold = serve.WarmPool(net, scfg)
    assert cold.stats["cache_hit"] is False
    assert cold.stats["cache_new_entries"] > 0
    warm = serve.WarmPool(net, scfg)
    assert warm.stats["cache_hit"] is True
    assert warm.stats["cache_new_entries"] == 0


def test_int8_weight_path_rides_decode_program():
    cfg, net = _net()
    q, scales = serve.quantize_weights(
        {k: p.data()._data for k, p in net.collect_params().items()})
    # every 2-D weight quantized to int8 within its per-tensor scale
    assert any(v.dtype == jnp.int8 for v in q.values())
    for name, scale in scales.items():
        orig = onp.asarray(net.collect_params()[name].data()._data)
        deq = onp.asarray(q[name]).astype(onp.float32) * scale
        assert onp.abs(orig - deq).max() <= scale * 0.5 + 1e-7
    srv = serve.Server(net, _serve_cfg(int8=True, max_new=5))
    rng = onp.random.RandomState(7)
    with srv:
        res = srv.result(srv.submit(
            list(rng.randint(1, cfg.vocab_size, 6)), max_new=5),
            timeout=120)
    assert res["state"] == "done" and len(res["tokens"]) == 5


# ----------------------------------------------------------------------
# checker integration (the gate's scenarios, at test budget)
# ----------------------------------------------------------------------
def test_mxverify_serve_scenario_green_and_mutation_caught():
    from mxnet_tpu.analysis import modelcheck as mc
    budget = mc.Budget(schedules=150, seconds=6)
    rep = mc.verify_scenario("serve_sched", budget=budget)
    assert rep.ok, rep.counterexample and rep.counterexample.format()
    with mc.mutations("serve_stale_commit"):
        rep = mc.verify_scenario("serve_sched",
                                 budget=mc.Budget(schedules=300,
                                                  seconds=10))
    assert not rep.ok, "checker went blind to serve_stale_commit"
    assert rep.counterexample.oracle == "serve_no_cross_delivery"
    with mc.mutations("skip_cow_copy"):
        rep = mc.verify_scenario("serve_sched",
                                 budget=mc.Budget(schedules=400,
                                                  seconds=10))
    assert not rep.ok, "checker went blind to skip_cow_copy"
    assert rep.counterexample.oracle == "serve_shared_no_cross_delivery"


def test_mxrace_serve_scenario_clean_and_drop_lock_confirmed():
    from mxnet_tpu.analysis import racecheck as rc
    clean = rc.confirm("serve_sched", seeds=(0, 1))
    assert not clean.racy, clean.summary()
    with rc.mutations("drop_sched_lock"):
        racy = rc.confirm("serve_sched", seeds=(0, 1))
    assert racy.racy, "harness went blind to drop_sched_lock"


def test_serve_config_env_knobs(monkeypatch):
    monkeypatch.setenv("MXNET_SERVE_SLOTS", "5")
    monkeypatch.setenv("MXNET_SERVE_PAGE_SIZE", "32")
    monkeypatch.setenv("MXNET_SERVE_LADDER", "32,64")
    monkeypatch.setenv("MXNET_SERVE_MAX_NEW", "16")
    c = serve.ServeConfig()
    assert (c.slots, c.page_size, c.ladder, c.max_new) == \
        (5, 32, (32, 64), 16)
    assert c.max_pages_per_slot == -(-(64 + 16) // 32)


# ----------------------------------------------------------------------
# elastic replicas: drain through the ordinary preemption path
# ----------------------------------------------------------------------
def test_scheduler_preempt_all_drains_and_requeues():
    """An elastic resize drains EVERY occupied slot in one lock
    transaction: pages freed, requests back at the queue FRONT in slot
    order, nothing dropped — then ordinary admission resumes them."""
    from mxnet_tpu import profiler
    s = _sched(slots=2, pages=9)
    a = s.submit(3, 2)
    b = s.submit(3, 2)
    for _ in range(2):
        s.commit_prefill(s.admit_next(), 7)
    snap = s.begin_step()               # decode in flight for both
    before = profiler.get_counter("serve::elastic_drains")
    assert s.preempt_all(reason="test resize") == 2
    assert profiler.get_counter("serve::elastic_drains") == before + 2
    assert s.stats()["free_pages"] == 8   # full pool (1 trash page)
    assert s.request(a)["state"] == s.request(b)["state"] == "waiting"
    assert s.check_conservation() == []
    # the in-flight snapshot commits stale: the epoch check drops it —
    # earned tokens survive the drain, the stale 99 never lands
    s.commit_step(snap, [(99, False), (99, False)])
    assert s.request(a)["tokens"] == (7,)
    assert s.request(b)["tokens"] == (7,)
    # both re-admit (re-prefilling prompt + earned tokens) and finish
    # their budget — nothing was lost
    for _ in range(2):
        s.commit_prefill(s.admit_next(), 8)
    assert s.request(a)["tokens"] == (7, 8)
    assert s.request(b)["tokens"] == (7, 8)
    assert s.request(a)["state"] == s.request(b)["state"] == "done"
    assert s.preempt_all() == 0         # empty drain is a no-op
    assert s.check_conservation() == []


# ----------------------------------------------------------------------
# prefix cache (scheduler protocol)
# ----------------------------------------------------------------------
def test_scheduler_prefix_partial_hit_cows_and_conserves():
    """The load-bearing COW case: B's prompt covers A's deeper cached
    block only partially, so B's table must hold a PRIVATE copy of that
    page (B's decode appends into it) while the cached original keeps
    serving the trie."""
    s = _sched(slots=2, pages=9)            # psz=2, mp=4
    a = s.submit(4, 2, prompt=(7, 8, 9, 10))
    plan_a = s.admit_next()
    assert plan_a["prefill_start"] == 0 and plan_a["cow"] is None
    s.commit_prefill(plan_a, 100)
    snap = s.begin_step()
    s.commit_step(snap, [(101, False)])     # max_new=2: A done, slot
    assert s.request(a)["state"] == "done"  # freed, blocks 0+1 cached
    assert s.stats()["cached_pages"] == 2
    assert s.check_refcounts() == [] and s.check_conservation() == []

    b = s.submit(3, 2, prompt=(7, 8, 9))
    plan_b = s.admit_next()
    # block 0 fully shared; block 1 matches 1 of 2 tokens -> covered 3,
    # prefill resumes at position 2 and the ext page is COWed
    assert plan_b["prefill_start"] == 2
    assert plan_b["cow"] is not None
    src, dst = plan_b["cow"]
    assert src != dst and dst in plan_b["pages"]
    assert src not in plan_b["pages"]       # the shared page left B's
    assert s.check_refcounts() == []        # table at the COW
    s.commit_prefill(plan_b, 200)
    snap = s.begin_step()
    s.commit_step(snap, [(201, False)])
    assert s.request(b)["tokens"] == (200, 201)
    assert s.stats()["prefix_hits"] >= 1
    assert s.check_refcounts() == [] and s.check_conservation() == []


def test_scheduler_prefix_full_hit_cows_last_block():
    """A prompt IDENTICAL to a cached one still re-prefills its last
    token (the decode program needs its logits), so the final cached
    block is COWed even on a full match — and the write is bitwise
    idempotent, which is why transparency holds."""
    s = _sched(slots=2, pages=9)
    a = s.submit(4, 1, prompt=(7, 8, 9, 10))
    s.commit_prefill(s.admit_next(), 100)   # max_new=1: done at commit
    assert s.request(a)["state"] == "done"
    b = s.submit(4, 2, prompt=(7, 8, 9, 10))
    plan_b = s.admit_next()
    assert plan_b["prefill_start"] == 3     # plen-1: recompute last tok
    assert plan_b["cow"] is not None
    s.commit_prefill(plan_b, 200)
    assert s.check_refcounts() == [] and s.check_conservation() == []


def test_scheduler_prefix_eviction_only_at_zero_refs_when_dry():
    """Cached pages stay resident until the allocator runs dry, then
    zero-ref trie pages are evicted deepest-first; pages a live slot
    still holds shared survive."""
    s = _sched(slots=2, pages=9)
    a = s.submit(4, 2, prompt=(7, 8, 9, 10))
    s.commit_prefill(s.admit_next(), 100)
    s.commit_step(s.begin_step(), [(101, False)])
    assert s.stats()["cached_pages"] == 2   # blocks (7,8) and (9,10)
    # 6 free pages left; two concurrent 4-page prompts need 8 — the
    # second admission must evict the zero-ref cached pages to fit
    big = s.submit(7, 2)
    big2 = s.submit(7, 2)
    s.commit_prefill(s.admit_next(), 300)   # big: 4 pages, running
    assert s.request(big)["state"] == "running"
    s.commit_prefill(s.admit_next(), 301)   # big2: needed eviction
    assert s.request(big2)["state"] == "running"
    assert s.stats()["prefix_evictions"] >= 1
    assert s.check_refcounts() == [] and s.check_conservation() == []


def test_scheduler_random_prefix_ops_conserve_pages_and_refs():
    """The conservation fuzz, prefix edition: random submits drawn
    from a small prompt alphabet (lots of shared prefixes), cancels,
    admissions and steps — the 3-way partition (free / cached /
    slot-private) and the refcount invariants must hold at every
    step."""
    rng = onp.random.RandomState(13)
    s = _sched(slots=3, pages=13, page_size=2, max_pages_per_slot=4)
    base = (3, 1, 4, 1, 5, 9)
    live = []
    for it in range(300):
        op = rng.randint(0, 5)
        if op == 0:
            plen = int(rng.randint(1, 7))
            prompt = (base[:plen] if rng.rand() < 0.7 else
                      tuple(int(x) for x in
                            rng.randint(1, 50, plen)))
            live.append(s.submit(plen, int(rng.randint(1, 5)),
                                 prompt=prompt))
        elif op == 1 and live:
            s.cancel(live[rng.randint(len(live))])
        elif op == 2:
            plan = s.admit_next()
            if plan is not None and rng.rand() < 0.9:
                s.commit_prefill(plan, it)
        else:
            snap = s.begin_step()
            s.commit_step(snap, [(it, rng.rand() < 0.2)
                                 for _ in snap])
        assert s.check_conservation() == [], "iteration %d" % it
        assert s.check_refcounts() == [], "iteration %d" % it


# ----------------------------------------------------------------------
# sampling (in-graph, per-request seeds)
# ----------------------------------------------------------------------
def test_sampling_deterministic_per_seed_and_batched_matches_solo():
    """Same seed => same tokens across fresh servers, and a sampled
    request inside a full batch produces EXACTLY its solo tokens —
    the per-slot gumbel-max sampling is vmapped lanewise, so batching
    cannot leak across requests (fp32, bitwise)."""
    cfg, net = _net()
    rng = onp.random.RandomState(8)
    prompts = [list(rng.randint(1, cfg.vocab_size, 6))
               for _ in range(3)]
    sp = {"temperature": 0.9, "top_k": 20, "top_p": 0.9}
    runs = []
    for _ in range(2):
        srv = serve.Server(net, _serve_cfg())
        with srv:
            rids = [srv.submit(p, max_new=8,
                               sampling=dict(sp, seed=40 + i))
                    for i, p in enumerate(prompts)]
            runs.append([srv.result(r, timeout=120)["tokens"]
                         for r in rids])
    assert runs[0] == runs[1], "same seeds must reproduce bitwise"
    solo_srv = serve.Server(net, _serve_cfg(slots=1))
    with solo_srv:
        for i, p in enumerate(prompts):
            solo = solo_srv.result(
                solo_srv.submit(p, max_new=8,
                                sampling=dict(sp, seed=40 + i)),
                timeout=120)["tokens"]
            assert solo == runs[0][i], "batched != solo for seed %d" % i


def test_sampling_distinct_seeds_in_one_batch_decorrelate():
    """Two requests with the SAME prompt and different seeds in one
    batch must produce different streams (seeded smoke — fully
    deterministic, no statistics), and the greedy default still rides
    the same decode program."""
    cfg, net = _net()
    prompt = [5, 9, 2, 14, 3]
    sp = {"temperature": 1.0, "top_k": 0, "top_p": 1.0}
    srv = serve.Server(net, _serve_cfg())
    with srv:
        ra = srv.submit(prompt, max_new=10, sampling=dict(sp, seed=1))
        rb = srv.submit(prompt, max_new=10, sampling=dict(sp, seed=2))
        rg = srv.submit(prompt, max_new=10)          # greedy default
        ta = srv.result(ra, timeout=120)["tokens"]
        tb = srv.result(rb, timeout=120)["tokens"]
        tg = srv.result(rg, timeout=120)["tokens"]
    assert len(ta) == len(tb) == len(tg) == 10
    assert ta != tb, "distinct seeds produced identical streams"


# ----------------------------------------------------------------------
# prefix cache + chunk prefill (server end-to-end) and sharded decode
# ----------------------------------------------------------------------
def test_server_prefix_cache_bitwise_transparent():
    """Shared-system-prompt workload with the prefix cache ON vs OFF:
    token streams must match bitwise (the cache is a pure prefill
    saving — COW plus chunk prefill reconstruct exactly the state a
    full prefill would have written), and the ON run must actually
    hit the trie."""
    cfg, net = _net()
    rng = onp.random.RandomState(9)
    sys_prompt = list(rng.randint(1, cfg.vocab_size, 10))
    prompts = [sys_prompt + list(rng.randint(1, cfg.vocab_size,
                                             int(rng.randint(2, 6))))
               for _ in range(4)]
    outs = {}
    for on in (True, False):
        srv = serve.Server(net, _serve_cfg(page_size=8,
                                           ladder=(8, 16, 32),
                                           prefix_cache=on))
        with srv:
            rids = [srv.submit(p, max_new=6) for p in prompts]
            outs[on] = [srv.result(r, timeout=120)["tokens"]
                        for r in rids]
        if on:
            st = srv.sched.stats()
            assert st["prefix_hits"] >= 1, "cache never engaged"
        assert srv.sched.check_refcounts() == []
        assert srv.sched.check_conservation() == []
    assert outs[True] == outs[False], \
        "prefix cache changed the served tokens"


def test_sharded_decode_matches_replicated_and_warm_spinup(tmp_path):
    """A tp=2 serving replica (weights sharded by annotation, KV pools
    split over Hkv) must serve EXACTLY the replicated replica's tokens,
    and a second sharded pool over the same persistent cache must come
    up compile-free — the fleet spin-up claim."""
    from mxnet_tpu import parallel
    cfg, net = _net()
    mesh = parallel.create_mesh(tp=2)
    rng = onp.random.RandomState(10)
    prompts = [list(rng.randint(1, cfg.vocab_size, 6))
               for _ in range(3)]
    scfg = _serve_cfg(slots=2, ladder=(16,), max_new=6,
                      cache_dir=str(tmp_path / "cache_tp"))
    srv_rep = serve.Server(net, _serve_cfg(slots=2, ladder=(16,),
                                           max_new=6))
    with srv_rep:
        want = [srv_rep.result(srv_rep.submit(p, max_new=6),
                               timeout=120)["tokens"] for p in prompts]
    srv_tp = serve.Server(net, scfg, mesh=mesh)
    with srv_tp:
        got = [srv_tp.result(srv_tp.submit(p, max_new=6),
                             timeout=120)["tokens"] for p in prompts]
    assert got == want, "sharding changed the served tokens"
    warm = serve.WarmPool(net, scfg, mesh=mesh)
    assert warm.stats["sharded"] is True
    assert warm.stats["cache_hit"] is True, \
        "warm sharded spin-up recompiled"
    assert warm.stats["cache_new_entries"] == 0


def test_server_attach_elastic_drains_on_resize_and_completes():
    """A Server riding an ElasticRunner: firing the runner's on_resize
    mid-decode drains the slots, the engine re-admits, and every
    request still completes with its full budget (the drain requeues,
    never drops).  The previous on_resize hook stays chained."""
    import time
    import types
    cfg, net = _net()
    rng = onp.random.RandomState(11)
    srv = serve.Server(net, _serve_cfg(slots=2, max_new=12))
    chained = []
    runner = types.SimpleNamespace(on_resize=chained.append)
    assert srv.attach_elastic(runner) is runner
    assert runner.on_resize is not chained.append   # wrapped
    prompts = [list(rng.randint(1, cfg.vocab_size, 5))
               for _ in range(3)]
    with srv:
        rids = [srv.submit(p, max_new=8) for p in prompts]
        time.sleep(0.2)                  # some decode in flight
        info = types.SimpleNamespace(gen=2, world=2)
        runner.on_resize(info)           # the resize seam
        res = [srv.result(r, timeout=120) for r in rids]
    assert chained == [info]             # prior hook still fired
    assert all(r["state"] == "done" and len(r["tokens"]) == 8
               for r in res)
    assert srv.sched.check_conservation() == []
