"""mx.serve — continuous-batching decode runtime over ``TransformerLM``.

The ROADMAP's "millions of users" direction: the repo could train,
export, and quantize, but nothing *served* — every inference token paid
O(T) full-sequence recompute and requests could not share a batch.
This module is the serving half, three layers deep:

1. **Incremental decode** (``models.kv_cache`` + the transformer's
   ``forward(tokens, cache=...)`` split): a paged KV cache over fixed
   batch-slot x page-budget shapes, so one decode step is O(1) in
   generated length and the decode program never recompiles as
   requests come and go.
2. **Continuous batching** (:class:`SlotScheduler` + :class:`Server`):
   an admission/eviction/preemption state machine where new requests
   join the running batch at any step and finished requests free their
   pages immediately — no batch-boundary barriers.  The scheduler is
   the most thread-heavy host code in the repo, so it lands the way
   PRs 10-13 taught: every shared-state access rides ``_lock``
   (mxrace's ``serve_sched`` scenario confirms the discipline, its
   ``drop_sched_lock`` mutation proves the checker sees a violation),
   and the plan/commit protocol is model-checked (mxverify's
   ``serve_sched`` scenario family; the ``serve_stale_commit``
   mutation reintroduces the commit-after-reassign TOCTOU the epoch
   check exists for).
3. **Compiled-program warm pool** (:class:`WarmPool`): the prefill
   shape ladder and THE decode program are AOT-compiled at startup
   behind jax's persistent compile cache, so a replica spin-up on a
   warm cache does zero compilation (``stats["cache_hit"]``); the
   int8 weight path from ``contrib.quantization`` rides the same
   decode program for memory-bound decode (int8 HBM reads, in-register
   dequantize).

Knobs (environment, all optional)::

    MXNET_SERVE_SLOTS        batch slots                     (8)
    MXNET_SERVE_PAGE_SIZE    tokens per KV page              (128)
    MXNET_SERVE_PAGES        page-pool budget incl. trash    (64)
    MXNET_SERVE_LADDER       prefill pad lengths, csv        (64,128,256)
    MXNET_SERVE_MAX_NEW      default per-request output cap  (64)
    MXNET_SERVE_INT8         int8 weight path                (0)
    MXNET_SERVE_TEMP         default sampling temperature    (0 = greedy)
    MXNET_SERVE_TOP_K        default top-k cutoff            (0 = off)
    MXNET_SERVE_TOP_P        default nucleus mass            (1.0 = off)
    MXNET_SERVE_PREFIX_CACHE refcounted prompt-prefix reuse  (1)
    MXNET_SERVE_DEADLINE_MS  default per-request deadline, ms (0 = off)

Sampling is compiled INTO the decode/prefill programs: every slot
carries (seed, step, temperature, top_k, top_p) operands, the RNG key
is ``fold_in(PRNGKey(seed), step)`` with ``step`` = tokens generated so
far, and ``temperature <= 0`` reduces to the bitwise-greedy argmax.
Same seed ⇒ same tokens; a batched slot samples bitwise-identically to
a solo run (per-slot lanes are independent under vmap); a preempted
request re-prefills and resumes at the same step indices, so even its
continuation is reproducible.  No host round-trip per token.

The prefix cache shares KV pages across requests with a common prompt
prefix: the :class:`SlotScheduler` keeps a trie keyed on FULL token
blocks (one page each) plus per-page refcounts; a request that matches
``m`` blocks (optionally extended by a partial cover from a deeper
cached block) prefills only its uncovered suffix through the chunk
program.  **Copy-on-write rule**: any write landing in a shared page —
the recomputed last prompt token of a fully-covered prompt, or decode
appends into a partially-covered block — first allocates a private
page and copies the shared one (``skip_cow_copy`` reintroduces the
corruption, caught by the ``serve_shared_no_cross_delivery`` oracle).
Cached pages with zero slot owners stay resident and are evicted
(deepest chain first) only when the allocator runs dry.

Protocol notes (the part mxverify checks): the engine OVERLAPS
admission/prefill with the in-flight decode, so a slot freed by a
cancel can be reassigned while a decode launched against its old
occupant is still in flight.  Every slot assignment therefore carries
an **epoch**; ``commit_step``/``commit_prefill`` drop results whose
(slot, epoch) no longer match — without that check a stale decode
result is delivered into the WRONG request (the
``serve_stale_commit`` mutation, caught by the
``serve_no_cross_delivery`` oracle).  Stale device writes are harmless
by construction: every attended cache position is written by its own
request's prefill/decode before it becomes visible (write-before-read),
so the page allocator never needs to quiesce the device.
"""
from __future__ import annotations

import contextlib
import logging
import os
import threading
import time

from . import fault as _fault
from . import flightrec as _flightrec
from . import profiler as _profiler
from . import telemetry as _telemetry
from .utils import compile_cache as _ccache

log = logging.getLogger("mxnet_tpu.serve")

__all__ = ["ServeConfig", "SlotScheduler", "WarmPool", "Server",
           "DeadlineExceededError", "OverloadedError",
           "quantize_weights", "lower_decode_program"]

#: deliberately reintroducible protocol bugs, armed ONLY by
#: analysis.modelcheck.mutations() (checker-liveness proofs).  Empty in
#: production; the branches testing it are dead outside the checker.
_TEST_MUTATIONS = set()


class DeadlineExceededError(TimeoutError):
    """The request's deadline expired before it finished: it was
    cancelled *through* the scheduler (pages and radix refcounts
    released), and :meth:`Server.result` raises this instead of
    hanging.  A ``TimeoutError`` subclass so callers treating any
    timeout uniformly keep working."""


class OverloadedError(RuntimeError):
    """The admission queue is full and the shed policy rejected this
    request — the typed backpressure signal (retry elsewhere/later)
    that keeps admitted-request p99 bounded instead of letting the
    queue grow without bound."""


def _env_int(name, default):
    return int(os.environ.get(name, str(default)))


def _norm_sampling(sampling, rid):
    """Normalize a per-request sampling dict against greedy defaults.
    The seed defaults to the rid so distinct requests in one batch
    decorrelate even when the client never thinks about seeds."""
    sp = dict(sampling or {})
    return {"seed": int(sp.get("seed", rid)),
            "temperature": float(sp.get("temperature", 0.0)),
            "top_k": int(sp.get("top_k", 0)),
            "top_p": float(sp.get("top_p", 1.0))}


class ServeConfig:
    """Serving-replica shape: batch slots x page budget x prefill
    ladder.  Fixed at startup — these ARE the compiled shapes."""

    def __init__(self, slots=None, page_size=None, pages=None,
                 ladder=None, max_new=None, eos_id=None, cache_dir=None,
                 int8=None, temperature=None, top_k=None, top_p=None,
                 prefix_cache=None, deadline_ms=None):
        env = os.environ
        self.slots = _env_int("MXNET_SERVE_SLOTS", 8) if slots is None \
            else int(slots)
        self.page_size = _env_int("MXNET_SERVE_PAGE_SIZE", 128) \
            if page_size is None else int(page_size)
        self.pages = _env_int("MXNET_SERVE_PAGES", 64) if pages is None \
            else int(pages)
        if ladder is None:
            ladder = tuple(int(t) for t in env.get(
                "MXNET_SERVE_LADDER", "64,128,256").split(",") if t)
        self.ladder = tuple(sorted(set(int(t) for t in ladder)))
        self.max_new = _env_int("MXNET_SERVE_MAX_NEW", 64) \
            if max_new is None else int(max_new)
        self.eos_id = eos_id
        self.cache_dir = cache_dir
        self.int8 = (env.get("MXNET_SERVE_INT8", "0") not in
                     ("", "0", "false", "False")) if int8 is None \
            else bool(int8)
        # replica-default sampling knobs (per-request ``sampling=`` on
        # submit overrides); temperature 0 is bitwise greedy
        self.temperature = float(env.get("MXNET_SERVE_TEMP", "0")) \
            if temperature is None else float(temperature)
        self.top_k = _env_int("MXNET_SERVE_TOP_K", 0) if top_k is None \
            else int(top_k)
        self.top_p = float(env.get("MXNET_SERVE_TOP_P", "1.0")) \
            if top_p is None else float(top_p)
        self.prefix_cache = (env.get("MXNET_SERVE_PREFIX_CACHE", "1")
                             not in ("", "0", "false", "False")) \
            if prefix_cache is None else bool(prefix_cache)
        # default per-request deadline; 0 = none (requests may wait
        # forever unless submit(deadline=) says otherwise)
        self.deadline_ms = _env_int("MXNET_SERVE_DEADLINE_MS", 0) \
            if deadline_ms is None else int(deadline_ms)
        self.max_pages_per_slot = -(-(max(self.ladder) + self.max_new)
                                    // self.page_size)

    def default_sampling(self):
        """Replica-default sampling params (the per-request shape
        :meth:`SlotScheduler.submit` normalizes against)."""
        return {"temperature": self.temperature, "top_k": self.top_k,
                "top_p": self.top_p}

    def default_deadline(self):
        """Replica-default per-request deadline in SECONDS (None when
        the knob is off)."""
        return self.deadline_ms / 1000.0 if self.deadline_ms > 0 \
            else None

    def cache_spec(self, cfg):
        """CacheSpec for a model config (import deferred: the scheduler
        half of this module must stay importable without jax)."""
        from .models.kv_cache import CacheSpec
        return CacheSpec(
            n_layers=cfg.n_layers, n_kv_heads=cfg.n_kv_heads,
            head_dim=cfg.head_dim or cfg.dim // cfg.n_heads,
            slots=self.slots,
            pages=self.pages, page_size=self.page_size,
            max_pages_per_slot=self.max_pages_per_slot, dtype=cfg.dtype)


# ----------------------------------------------------------------------
# the admission/eviction/preemption state machine (pure host, no jax)
# ----------------------------------------------------------------------
class SlotScheduler:
    """Continuous-batching control plane over fixed slots x pages.

    All shared state lives in ONE dict (``_s``) with immutable values,
    every access under ``_lock`` — the same single-variable shape
    ``StepLease`` uses, so the dynamic race harness can instrument the
    whole state as one named variable.  ``_sim`` is the modelcheck
    seam: scenario builders install a cooperative scheduler so the
    transaction boundaries become explorable schedule points (seams sit
    OUTSIDE the locked regions — each locked transaction is atomic,
    interleavings are explored between them).  ``audit`` records
    allocator-invariant breaches (double-allocated or double-freed
    pages) for the model checker's conservation oracle.

    Request lifecycle::

        submit -> waiting -> [admit_next/commit_prefill] -> running
        running -> done        (eos / max_new / context cap)
        running -> waiting     (preempted: pages freed, requeued FRONT)
        any     -> cancelled   (client gone; running slots freed NOW)
    """

    #: mirrors models.kv_cache.TRASH_PAGE (not imported: the scheduler
    #: half of this module must stay importable without jax)
    TRASH_PAGE = 0

    def __init__(self, slots, pages, page_size, max_pages_per_slot,
                 sim=None, prefix_cache=True, ladder=None):
        TRASH_PAGE = SlotScheduler.TRASH_PAGE
        self._lock = threading.Lock()
        self.page_size = int(page_size)
        # prefill ladder, when known: partial-extension hits are only
        # taken when they shrink the chunk rung — a few shared tokens
        # that leave the rung unchanged cost a page copy (and the
        # chunk program, pricier than plain prefill at equal T) for
        # zero compute saved.  None (sims, unit harnesses) keeps the
        # unconditional extension so COW stays exercised.
        self.ladder = tuple(sorted(set(int(t) for t in ladder))) \
            if ladder else None
        self.max_pages_per_slot = int(max_pages_per_slot)
        self.slots = int(slots)
        self.num_pages = int(pages)
        self.prefix_cache = bool(prefix_cache)
        self.audit = []
        self._sim = sim
        self._s = {
            # page 0 is the trash page — never allocated
            "free_pages": tuple(p for p in range(pages)
                                if p != TRASH_PAGE),
            "free_slots": tuple(range(slots)),
            "queue": (),
            "reqs": {},
            "slots": {},
            "next_rid": 0,
            "next_epoch": 0,
            "preemptions": 0,
            # prefix cache: trie keyed on FULL token blocks (the key is
            # the prompt's first i*page_size tokens, the value the page
            # holding block i) + per-page slot-owner refcounts.  A page
            # in the trie is never in free_pages; refcount 0 means
            # "cached, evictable".
            "prefix": {},
            "refs": {},
            "prefix_hits": 0,
            "prefix_evictions": 0,
        }

    # -- seams ----------------------------------------------------------
    def _point(self, kind, detail=""):
        # every scheduler transaction is already named here for the
        # model checker — the flight recorder rides the same seam (the
        # record is lock-free w.r.t. the scheduler: _point is called
        # before/outside the _lock'd transaction body)
        _flightrec.record(kind, detail=detail)
        sim = self._sim
        if sim is not None:
            sim.point(kind, obj=("sched", id(self)), write=True,
                      detail=detail)

    def _pages_for(self, tokens):
        return max(1, -(-int(tokens) // self.page_size))

    # -- allocator primitives (called ONLY under _lock) -----------------
    def _alloc(self, s, n):
        free = s["free_pages"]
        if len(free) < n:
            # allocator dry: zero-owner cached prefix pages are the
            # reclaimable reserve — evict before giving up
            self._evict_prefix(s, n - len(free))
            free = s["free_pages"]
            if len(free) < n:
                return None
        got, rest = free[:n], free[n:]
        owned = [p for sl in s["slots"].values() for p in sl["pages"]]
        for p in got:
            if p in owned:
                self.audit.append("page %d allocated while owned" % p)
        s["free_pages"] = rest
        return got

    def _free(self, s, pages):
        for p in pages:
            if p in s["free_pages"]:
                self.audit.append("page %d freed while free" % p)
        s["free_pages"] = s["free_pages"] + tuple(pages)

    def _evict_prefix(self, s, n):
        """Free up to ``n`` cached prefix pages with ZERO slot owners,
        deepest key first (evicting a deep block never strands a live
        shallower one — a chain is only walkable up to its first
        missing block anyway).  Called under ``_lock`` when the
        allocator runs dry."""
        if n <= 0 or not s["prefix"]:
            return
        prefix = dict(s["prefix"])
        refs = dict(s["refs"])
        freed = []
        for key in sorted(prefix, key=lambda k: (-prefix[k][1], k)):
            if len(freed) >= n:
                break
            page = prefix[key][0]
            if refs.get(page, 0) == 0:
                del prefix[key]
                refs.pop(page, None)
                freed.append(page)
        if freed:
            s["prefix"] = prefix
            s["refs"] = refs
            self._free(s, freed)
            s["prefix_evictions"] = s["prefix_evictions"] + len(freed)

    def _release_slot(self, s, slot):
        ent = s["slots"].pop(slot)
        held = set(ent.get("shared", ()))
        if held:
            # drop this slot's refs; the pages stay cached (refcount 0
            # = evictable), they are NOT freed here
            refs = dict(s["refs"])
            for p in held:
                n = refs.get(p, 0) - 1
                if n < 0:
                    self.audit.append("page %d refcount underflow" % p)
                    n = 0
                refs[p] = n
            s["refs"] = refs
        self._free(s, [p for p in ent["pages"] if p not in held])
        s["free_slots"] = s["free_slots"] + (slot,)
        return ent

    def _set_req(self, s, rid, **updates):
        reqs = dict(s["reqs"])
        req = dict(reqs[rid])
        req.update(updates)
        reqs[rid] = req
        s["reqs"] = reqs
        return req

    # -- client side ----------------------------------------------------
    def submit(self, prompt_len, max_new, prompt=None, sampling=None):
        """Enqueue one request; returns its rid (thread-safe).
        ``prompt`` (the actual token tuple) opts the request into
        prefix-cache sharing — without it the scheduler has no content
        to key the trie on and the request prefills from scratch.
        ``sampling`` overrides the greedy defaults per request
        ({seed, temperature, top_k, top_p}; seed defaults to rid)."""
        self._point("sched.submit")
        with self._lock:
            s = self._s
            rid = s["next_rid"]
            s["next_rid"] = rid + 1
            reqs = dict(s["reqs"])
            # t_* phase timestamps are the request's SLO lifecycle
            # (telemetry.request_lifecycle consumes them at terminal
            # delivery); they purge with the record — no per-request
            # state survives past the result handoff
            reqs[rid] = {"rid": rid, "prompt_len": int(prompt_len),
                         "max_new": int(max_new), "state": "waiting",
                         "tokens": (), "slot": None, "epoch": None,
                         "prompt": (None if prompt is None
                                    else tuple(int(t) for t in prompt)),
                         "sampling": _norm_sampling(sampling, rid),
                         "t_submit": time.monotonic(), "t_admit": None,
                         "t_first": None, "t_done": None, "preempts": 0,
                         # one monotonic time per entry of "tokens":
                         # t_tokens[0] == t_first; a preempted request
                         # keeps the times of the tokens it had
                         "t_tokens": ()}
            s["reqs"] = reqs
            s["queue"] = s["queue"] + (rid,)
        _telemetry.bump("serve::submitted")
        return rid

    def cancel(self, rid):
        """Drop a request (client disconnect).  A waiting request
        leaves the queue; a running one frees its slot and pages NOW —
        an in-flight step against it is dropped by the epoch check at
        commit.  Returns True when the request was still live."""
        self._point("sched.cancel", "rid %s" % rid)
        with self._lock:
            s = self._s
            req = s["reqs"].get(rid)
            if req is None or req["state"] in ("done", "cancelled",
                                               "failed"):
                return False  # terminal states stay terminal
            if req["state"] == "waiting":
                s["queue"] = tuple(r for r in s["queue"] if r != rid)
            elif req["state"] == "running":
                s["slots"] = dict(s["slots"])
                self._release_slot(s, req["slot"])
            self._set_req(s, rid, state="cancelled", slot=None,
                          epoch=None, t_done=time.monotonic())
        _telemetry.bump("serve::cancelled")
        return True

    # -- engine side ----------------------------------------------------
    def admit_next(self):
        """Admit the head-of-queue request when a slot and its prompt's
        pages are available; returns the admission plan (the prefill's
        inputs) or None.  Allocation + state flip are ONE transaction —
        the plan's (slot, epoch) identity is what ``commit_prefill``
        later checks against.

        Prefix-cache walk (``prompt`` known): the longest chain of
        cached FULL token blocks, optionally extended by the best
        partial cover from one block deeper (max common prefix of the
        next block; lexicographic tie-break keeps the walk
        deterministic).  The plan's ``prefill_start`` is the first
        position the engine must actually compute; ``cow`` names the
        (shared src, private dst) page pair to copy first when that
        position lands inside a shared page."""
        self._point("sched.admit")
        with self._lock:
            s = self._s
            if not s["queue"] or not s["free_slots"]:
                return None
            rid, need = None, 0
            while s["queue"]:
                rid = s["queue"][0]
                req = s["reqs"][rid]
                # a preempted request re-prefills prompt + tokens so far
                plen = req["prompt_len"] + len(req["tokens"])
                need = self._pages_for(plen)
                if need <= self.max_pages_per_slot:
                    break
                # unservable head: fail it and keep admitting — it must
                # not head-of-line-block the admissible request behind
                s["queue"] = s["queue"][1:]
                self._set_req(s, rid, state="failed",
                              t_done=time.monotonic())
                rid = None
            if rid is None:
                return None
            psz = self.page_size
            seq = ()
            if self.prefix_cache and req.get("prompt") is not None \
                    and len(req["prompt"]) == req["prompt_len"]:
                seq = req["prompt"] + tuple(req["tokens"])
            chain, ext = [], None
            if seq:
                # radix walk: node key = (parent page, token block) so
                # key size — and the hashing/allocation per admission —
                # is O(prompt), not O(prompt^2 / page_size) the way
                # cumulative-prefix keys would be
                prefix = s["prefix"]
                parent = 0  # root sentinel: the trash page id
                while (len(chain) + 1) * psz <= plen:
                    k = len(chain) * psz
                    val = prefix.get((parent, seq[k:k + psz]))
                    if val is None:
                        break
                    chain.append(val[0])
                    parent = val[0]
                m = len(chain)
                rem = seq[m * psz:]
                if rem:
                    # one block deeper: a cached block whose content
                    # partially covers our next block still saves its
                    # prefix positions (COW makes the tail writable)
                    for key, val in prefix.items():
                        if key[0] != parent:
                            continue
                        blk = key[1]
                        lcp = 0
                        while lcp < len(rem) and lcp < psz \
                                and blk[lcp] == rem[lcp]:
                            lcp += 1
                        if lcp and (ext is None or lcp > ext[1]
                                    or (lcp == ext[1]
                                        and key < ext[2])):
                            ext = (val[0], lcp, key)
            if ext is not None and self.ladder is not None:
                # rung-shrink gate: the chunk prefill pads to a ladder
                # rung, so a partial hit that leaves the rung unchanged
                # saves nothing — it only buys a COW page copy and the
                # chunk program.  Take it only when the shorter suffix
                # drops to a smaller rung (this also kills spurious
                # few-token matches between unrelated prompts).
                def _fit(n):
                    for T_ in self.ladder:
                        if T_ >= n:
                            return T_
                    return None
                c0 = len(chain) * psz
                r0 = _fit(plen - max(0, min(c0, plen - 1)))
                r1 = _fit(plen - max(0, min(c0 + ext[1], plen - 1)))
                if r0 is None or r1 is None or r1 >= r0:
                    ext = None
            shared_chain = chain + ([ext[0]] if ext else [])
            covered = len(chain) * psz + (ext[1] if ext else 0)
            # at least the last prompt position is recomputed — its
            # logits seed the first generated token
            start = max(0, min(covered, plen - 1))
            b0 = start // psz
            cow = None
            table_head = list(shared_chain)
            if b0 < len(shared_chain):
                # first uncached write lands in a shared page:
                # copy-on-write.  The private copy takes the page's
                # table position; the shared src stays refcounted (so
                # eviction can't free it before the engine's copy).
                src = shared_chain[b0]
                if _TEST_MUTATIONS and "skip_cow_copy" \
                        in _TEST_MUTATIONS:
                    pass  # mutation: write INTO the shared page
                else:
                    table_head = table_head[:b0]
                    cow = (src, None)
            s["slots"] = dict(s["slots"])
            got = self._alloc(s, need - len(table_head))
            if got is None:
                return None
            if cow is not None:
                cow = (cow[0], got[0])
            table = tuple(table_head) + tuple(got)
            held = set(shared_chain)
            if held:
                refs = dict(s["refs"])
                for p in held:
                    refs[p] = refs.get(p, 0) + 1
                s["refs"] = refs
                s["prefix_hits"] = s["prefix_hits"] + 1
            # FULL blocks this prefill completes, publishable into the
            # trie at commit (existing keys are skipped there); each
            # key names its parent PAGE, so depth i's parent is this
            # very table's page i-1 (block b0's parent may be shared)
            insert = tuple((((table[i - 1] if i else 0),
                             seq[i * psz:(i + 1) * psz]), i)
                           for i in range(b0, plen // psz)) if seq \
                else ()
            slot = s["free_slots"][0]
            s["free_slots"] = s["free_slots"][1:]
            s["queue"] = s["queue"][1:]
            epoch = s["next_epoch"]
            s["next_epoch"] = epoch + 1
            s["slots"][slot] = {"rid": rid, "epoch": epoch,
                                "pages": table, "len": plen,
                                "last_tok": None,
                                "shared": tuple(sorted(held))}
            # first admission stamps the queued->running boundary; a
            # re-admission after preemption keeps it (queued time is
            # the CLIENT-visible wait, not the last requeue's)
            self._set_req(s, rid, state="running", slot=slot,
                          epoch=epoch,
                          t_admit=req.get("t_admit")
                          or time.monotonic())
        _telemetry.bump("serve::admitted")
        return {"rid": rid, "slot": slot, "epoch": epoch,
                "pages": table, "prefill_len": plen,
                "prefill_start": start if seq else 0,
                "shared": tuple(sorted(held)), "cow": cow,
                "insert": insert,
                "sampling": dict(req["sampling"]),
                "ntok": len(req["tokens"])}

    def commit_prefill(self, plan, first_token, done=False):
        """Record the prefill's first generated token.  Epoch-checked:
        a cancel may have freed (and admission reassigned) the slot
        while the prefill was in flight — a stale commit is dropped."""
        self._point("sched.commit_prefill", "rid %s" % plan["rid"])
        with self._lock:
            s = self._s
            ent = s["slots"].get(plan["slot"])
            if ent is None or ent["epoch"] != plan["epoch"]:
                return None  # reassigned/cancelled mid-prefill: drop
            rid = ent["rid"]
            req = s["reqs"][rid]
            s["slots"] = dict(s["slots"])
            # publish this prefill's freshly-written FULL blocks into
            # the prefix trie.  Keys another request cached first are
            # skipped (our page stays private); published pages become
            # shared with THIS slot as first owner — ent["shared"]
            # must grow BEFORE the terminal release below so the
            # refcount is decremented exactly once either way.
            if self.prefix_cache and plan.get("insert"):
                prefix, refs = dict(s["prefix"]), dict(s["refs"])
                held = set(ent.get("shared", ()))
                grown = False
                for key, idx in plan["insert"]:
                    page = ent["pages"][idx]
                    if key in prefix or page in held:
                        continue
                    prefix[key] = (page, idx)
                    refs[page] = 1
                    held.add(page)
                    grown = True
                if grown:
                    s["prefix"], s["refs"] = prefix, refs
                    ent = dict(ent, shared=tuple(sorted(held)))
                    s["slots"][plan["slot"]] = ent
            tokens = req["tokens"] + (first_token,)
            # a prompt that exactly fills the slot leaves no cache
            # position for a decode write: terminal here, or no
            # snapshot would ever carry it to commit_step
            capped = ent["len"] >= self.max_pages_per_slot \
                * self.page_size
            fin = done or len(tokens) >= req["max_new"] or capped
            now = time.monotonic()
            t_first = req.get("t_first") or now
            t_tokens = req["t_tokens"] + (now,)
            if fin:
                self._release_slot(s, plan["slot"])
                self._set_req(s, rid, state="done", tokens=tokens,
                              t_tokens=t_tokens, slot=None, epoch=None,
                              t_first=t_first, t_done=now)
            else:
                s["slots"][plan["slot"]] = dict(
                    ent, last_tok=first_token)
                self._set_req(s, rid, tokens=tokens, t_tokens=t_tokens,
                              t_first=t_first)
        return rid if fin else None

    def fail(self, plan):
        """Terminal failure of an admitted-but-unprefillable request
        (a preempted request regrown past the ladder): free the plan's
        slot and pages, mark the request failed.  Epoch-checked like
        every other commit."""
        self._point("sched.fail", "rid %s" % plan["rid"])
        with self._lock:
            s = self._s
            ent = s["slots"].get(plan["slot"])
            if ent is None or ent["epoch"] != plan["epoch"]:
                return
            s["slots"] = dict(s["slots"])
            self._release_slot(s, plan["slot"])
            self._set_req(s, ent["rid"], state="failed", slot=None,
                          epoch=None, t_done=time.monotonic())

    def begin_step(self):
        """Snapshot the decode batch: every running slot with one more
        token of page capacity.  A slot crossing a page boundary
        allocates here; when the pool is dry the YOUNGEST other running
        slot is preempted (pages freed, request requeued at the FRONT
        to re-prefill later) — continuous batching's page-pressure
        valve.  Returns a tuple of per-slot dicts (slot, rid, epoch,
        len, last_tok) — the identity ``commit_step`` validates."""
        self._point("sched.begin")
        with self._lock:
            s = self._s
            s["slots"] = dict(s["slots"])
            snap = []
            for slot in sorted(s["slots"]):
                ent = s["slots"].get(slot)
                if ent is None or ent["last_tok"] is None:
                    continue
                pos = ent["len"]  # this step writes cache position len
                if pos >= self.max_pages_per_slot * self.page_size:
                    # no decode headroom (commit_prefill finishes this
                    # case; defense): a skipped slot would never reach
                    # commit_step again — terminal NOW, not leaked
                    self._release_slot(s, slot)
                    self._set_req(s, ent["rid"], state="done",
                                  slot=None, epoch=None,
                                  t_done=time.monotonic())
                    continue
                need_page = pos // self.page_size >= len(ent["pages"])
                if need_page:
                    got = self._alloc(s, 1)
                    while got is None:
                        victim = self._pick_victim(s, exclude=slot)
                        if victim is None:
                            break
                        self._preempt(s, victim)
                        got = self._alloc(s, 1)
                    if got is None:
                        # not even preemption helped: requeue this one
                        self._preempt(s, slot)
                        continue
                    ent = dict(ent, pages=ent["pages"] + tuple(got))
                    s["slots"][slot] = ent
                req = s["reqs"][ent["rid"]]
                snap.append({"slot": slot, "rid": ent["rid"],
                             "epoch": ent["epoch"], "len": pos,
                             "pages": ent["pages"],
                             "last_tok": ent["last_tok"],
                             # sampling operands: the decode program
                             # folds step (= tokens generated so far)
                             # into the request's seed, so a resumed
                             # request replays the same token stream
                             "sampling": dict(req.get("sampling")
                                              or _norm_sampling(
                                                  None, ent["rid"])),
                             "step": len(req["tokens"])})
        return tuple(snap)

    def _pick_victim(self, s, exclude):
        """Youngest (highest-epoch) running slot other than
        ``exclude`` — the cheapest recompute to throw away."""
        best = None
        for slot, ent in s["slots"].items():
            if slot == exclude:
                continue
            if best is None or ent["epoch"] > s["slots"][best]["epoch"]:
                best = slot
        return best

    def _preempt(self, s, slot):
        ent = self._release_slot(s, slot)
        req = s["reqs"][ent["rid"]]
        self._set_req(s, ent["rid"], state="waiting", slot=None,
                      epoch=None, preempts=req.get("preempts", 0) + 1)
        s["queue"] = (ent["rid"],) + s["queue"]
        s["preemptions"] = s["preemptions"] + 1
        _telemetry.bump("serve::preemptions")

    def commit_step(self, snapshot, results):
        """Apply one decode step's results: ``results`` pairs each
        snapshot entry with its generated token (and the engine's
        done flag, e.g. EOS).  The (slot, epoch) identity from the
        snapshot is re-validated — admissions ran WHILE the decode was
        in flight, so a slot may now belong to a different request;
        the ``serve_stale_commit`` mutation skips this check and the
        ``serve_no_cross_delivery`` oracle catches the resulting
        cross-request token leak.  Returns the rids finished by this
        step."""
        self._point("sched.commit")
        finished = []
        with self._lock:
            s = self._s
            s["slots"] = dict(s["slots"])
            now = time.monotonic()
            for entry, (token, done) in zip(snapshot, results):
                slot, epoch = entry["slot"], entry["epoch"]
                ent = s["slots"].get(slot)
                if ent is None:
                    continue  # freed mid-flight (cancel): drop
                if ent["epoch"] != epoch and not (
                        _TEST_MUTATIONS
                        and "serve_stale_commit" in _TEST_MUTATIONS):
                    # reassigned mid-flight: this result belongs to the
                    # slot's PREVIOUS occupant — deliverable to no one
                    continue
                rid = ent["rid"]
                req = s["reqs"][rid]
                tokens = req["tokens"] + (token,)
                t_tokens = req["t_tokens"] + (now,)
                new_len = ent["len"] + 1
                capped = new_len + 1 > self.max_pages_per_slot \
                    * self.page_size
                fin = done or len(tokens) >= req["max_new"] or capped
                if fin:
                    self._release_slot(s, slot)
                    self._set_req(s, rid, state="done", tokens=tokens,
                                  t_tokens=t_tokens, slot=None,
                                  epoch=None, t_done=now)
                    finished.append(rid)
                else:
                    s["slots"][slot] = dict(ent, len=new_len,
                                            last_tok=token)
                    self._set_req(s, rid, tokens=tokens,
                                  t_tokens=t_tokens)
        if finished:
            _telemetry.bump("serve::finished", len(finished))
        return finished

    def preempt_all(self, reason="elastic"):
        """Drain EVERY occupied slot through the ordinary preemption
        path — pages freed, each request requeued at the FRONT of the
        queue to re-prefill later — and return the number of slots
        drained.  This is the elastic-resize valve: when the replica's
        :class:`~mxnet_tpu.fault_elastic.ElasticRunner` reshards (a
        peer died or a replacement joined), the compiled decode
        program's mesh is about to change, so in-flight decode state is
        recomputable-but-not-portable; no request is dropped, only its
        KV cache.  One transaction under the scheduler lock — an
        ``engine_step`` racing this call sees either the old world
        (its stale-epoch commits are discarded) or the drained one."""
        with self._lock:
            s = self._s
            s["slots"] = dict(s["slots"])
            drained = 0
            for slot in sorted(s["slots"]):
                self._preempt(s, slot)
                drained += 1
        if drained:
            _telemetry.bump("serve::elastic_drains", drained)
            log.info("serve: drained %d slot(s) (%s)", drained, reason)
        return drained

    def purge(self, rid):
        """Drop a TERMINAL request's record and return it (None when
        the rid is unknown or still live).  The scheduler's per-request
        state must stay bounded by LIVE requests, not by every rid ever
        submitted: ``_set_req`` copies the reqs dict per update, so a
        long-running replica that never purged would pay an
        O(total-requests-ever) copy per generated token.  The Server
        calls this once a terminal record has been handed to its own
        result store; direct scheduler drivers (tests, the checker
        scenarios) may ignore it."""
        with self._lock:
            s = self._s
            req = s["reqs"].get(rid)
            if req is None or req["state"] not in ("done", "cancelled",
                                                   "failed"):
                return None
            reqs = dict(s["reqs"])
            del reqs[rid]
            s["reqs"] = reqs
            return dict(req)

    # -- introspection --------------------------------------------------
    def request(self, rid):
        with self._lock:
            req = self._s["reqs"].get(rid)
            return dict(req) if req else None

    def stats(self):
        with self._lock:
            s = self._s
            return {
                "waiting": len(s["queue"]),
                "running": len(s["slots"]),
                "free_slots": len(s["free_slots"]),
                "free_pages": len(s["free_pages"]),
                "preemptions": s["preemptions"],
                "requests": len(s["reqs"]),
                "cached_pages": len(s["prefix"]),
                "prefix_hits": s["prefix_hits"],
                "prefix_evictions": s["prefix_evictions"],
            }

    def check_conservation(self):
        """Allocator invariant for tests and the mxverify oracle:
        every page is free, cached in the prefix trie, or privately
        owned by exactly one slot — a three-way partition; audit
        empty.  (A shared page appears in MANY slots' tables; it is
        accounted once, as cached.)"""
        with self._lock:
            s = self._s
            vals = [v[0] for v in s["prefix"].values()]
            cached = sorted(set(vals))
            owned = [p for ent in s["slots"].values()
                     for p in ent["pages"]
                     if p not in set(ent.get("shared", ()))]
            free = list(s["free_pages"])
        problems = list(self.audit)
        if len(set(vals)) != len(vals):
            problems.append("trie maps two keys to one page")
        allp = owned + free + cached
        if len(set(allp)) != len(allp):
            problems.append("page owned/free/cached more than once: %s"
                            % sorted(allp))
        if len(allp) != self.num_pages - 1:  # trash page never pooled
            problems.append("page leak: %d accounted of %d"
                            % (len(allp), self.num_pages - 1))
        return problems

    def check_refcounts(self):
        """Prefix-cache refcount invariant (the second serve oracle's
        hook): every cached page's refcount equals the number of slots
        holding it shared; refs never negative; no ref without a cache
        entry; no cached page simultaneously free."""
        with self._lock:
            s = self._s
            cached = set(v[0] for v in s["prefix"].values())
            refs = dict(s["refs"])
            free = set(s["free_pages"])
            holders = {}
            for ent in s["slots"].values():
                for p in set(ent.get("shared", ())):
                    holders[p] = holders.get(p, 0) + 1
        problems = []
        for p in sorted(cached & free):
            problems.append("cached page %d is also free" % p)
        for p in sorted(set(holders) - cached):
            problems.append("ref held on non-cached page %d" % p)
        for p in sorted(cached):
            have = refs.get(p, 0)
            want = holders.get(p, 0)
            if have != want:
                problems.append("page %d refcount %d != %d holder(s)"
                                % (p, have, want))
        for p, n in sorted(refs.items()):
            if n < 0:
                problems.append("page %d refcount negative" % p)
            elif n and p not in cached:
                problems.append("refcount on evicted page %d" % p)
        return problems


# ----------------------------------------------------------------------
# int8 weight path
# ----------------------------------------------------------------------
def quantize_weights(params, exclude=("tok_embeddings", "gamma")):
    """Per-tensor int8 weight quantization for memory-bound decode
    (``contrib.quantization``'s minmax scheme on the LM's 2-D mats):
    returns (int8 params dict, {name: python-float scale}).  The decode
    program dequantizes in-register (``int8 * scale`` fused into the
    consuming matmul's input), so HBM reads — the decode bottleneck —
    shrink 2x vs bf16.  Embeddings and norm gains stay in the compute
    dtype."""
    import numpy as onp

    import jax.numpy as jnp

    from .contrib.quantization import _minmax_scale
    q, scales = {}, {}
    for name, arr in params.items():
        a = onp.asarray(arr)
        if a.ndim != 2 or any(t in name for t in exclude):
            q[name] = arr
            continue
        scale = _minmax_scale(a.astype(onp.float32))
        q[name] = jnp.clip(jnp.round(
            jnp.asarray(a, jnp.float32) / scale), -127, 127) \
            .astype(jnp.int8)
        scales[name] = float(scale)
    return q, scales


def _dequant(params, scales, dtype):
    import jax.numpy as jnp
    if not scales:
        return params
    return {k: (v.astype(dtype) * jnp.asarray(scales[k], dtype)
                if k in scales else v)
            for k, v in params.items()}


# ----------------------------------------------------------------------
# in-graph sampling (compiled into the decode/prefill programs)
# ----------------------------------------------------------------------
def _sample_one(logits, seed, step, temp, top_k, top_p):
    """Sample ONE token from (V,) float32 logits, fully in-graph.

    The key is ``fold_in(PRNGKey(seed), step)`` with ``step`` = tokens
    generated so far, so the whole stream is a pure function of
    (seed, logits history): same seed replays the same tokens, and a
    preempted request resumes at the same step indices it would have
    hit uninterrupted.  ``temp <= 0`` returns the bitwise-greedy
    argmax; ``top_k <= 0`` disables the rank cutoff; ``top_p >= 1``
    keeps all mass.  Top-p masks on cumulative-mass-EXCLUDING-self so
    the top-1 token always survives.  Gumbel-max over the masked,
    temperature-scaled logits keeps everything argmax-shaped (no
    host round-trip, no categorical divide)."""
    import jax
    import jax.numpy as jnp
    V = logits.shape[-1]
    greedy = jnp.argmax(logits).astype(jnp.int32)
    order = jnp.argsort(-logits)            # descending, stable
    sl = logits[order]
    t = jnp.maximum(temp, 1e-6).astype(jnp.float32)
    keep = jnp.where(top_k > 0, jnp.arange(V) < top_k, True)
    probs = jax.nn.softmax(sl / t)
    keep = keep & (jnp.cumsum(probs) - probs < top_p)
    masked = jnp.where(keep, sl / t, -jnp.inf)
    key = jax.random.fold_in(jax.random.PRNGKey(seed), step)
    pick = jnp.argmax(masked + jax.random.gumbel(key, (V,),
                                                 jnp.float32))
    sampled = order[pick].astype(jnp.int32)
    return jnp.where(temp <= 0.0, greedy, sampled)


def _sample_batch(logits, seeds, steps, temps, top_ks, top_ps):
    """Per-slot vmap of :func:`_sample_one` — lanes are independent
    (own key, own mask), so a batched slot samples bitwise-identically
    to a solo run of the same request."""
    import jax
    with jax.named_scope("sample"):
        return jax.vmap(_sample_one)(logits, seeds, steps, temps,
                                     top_ks, top_ps)


# ----------------------------------------------------------------------
# pure program builders (param-swap closures over the Gluon net)
# ----------------------------------------------------------------------
@contextlib.contextmanager
def _swapped_params(ps, arrays):
    from .ndarray.ndarray import NDArray
    prev = {k: p._data for k, p in ps.items()}
    for k, p in ps.items():
        p._data = NDArray(arrays[k])
    try:
        yield
    finally:
        for k, p in ps.items():
            p._data = prev[k]


def _build_decode_fn(net, ps, page_size, scales, dtype):
    import jax.numpy as jnp

    from . import _tape
    from .models.kv_cache import CacheView
    from .ndarray.ndarray import NDArray

    def decode(params, k_pages, v_pages, page_table, lengths, tokens,
               active, seeds, steps, temps, top_ks, top_ps):
        params = _dequant(params, scales, dtype)
        view = CacheView("decode", k_pages, v_pages, page_size,
                         page_table=page_table, lengths=lengths,
                         active=active)
        with _tape.suspend_recording(), _swapped_params(ps, params):
            logits = net.forward(NDArray(tokens[:, None]),
                                 cache=view)._data
        nxt = _sample_batch(logits[:, -1, :].astype(jnp.float32),
                            seeds, steps, temps, top_ks, top_ps)
        return nxt, view.k, view.v

    return decode


def _build_prefill_fn(net, ps, page_size, scales, dtype):
    import jax
    import jax.numpy as jnp

    from . import _tape
    from .models.kv_cache import CacheView
    from .ndarray.ndarray import NDArray

    def prefill(params, k_pages, v_pages, page_row, tokens, true_len,
                seed, step, temp, top_k, top_p):
        params = _dequant(params, scales, dtype)
        view = CacheView("prefill", k_pages, v_pages, page_size,
                         page_row=page_row, true_len=true_len)
        with _tape.suspend_recording(), _swapped_params(ps, params):
            logits = net.forward(NDArray(tokens), cache=view)._data
        last = logits[0, true_len - 1, :].astype(jnp.float32)
        with jax.named_scope("sample"):
            tok = _sample_one(last, seed, step, temp, top_k, top_p)
        return tok, view.k, view.v

    return prefill


def _build_chunk_fn(net, ps, page_size, scales, dtype):
    import jax
    import jax.numpy as jnp

    from . import _tape
    from .models.kv_cache import CacheView
    from .ndarray.ndarray import NDArray

    def chunk(params, k_pages, v_pages, page_row, tokens, true_len,
              start, seed, step, temp, top_k, top_p):
        params = _dequant(params, scales, dtype)
        view = CacheView("chunk", k_pages, v_pages, page_size,
                         page_row=page_row, true_len=true_len,
                         start=start)
        with _tape.suspend_recording(), _swapped_params(ps, params):
            logits = net.forward(NDArray(tokens), cache=view)._data
        last = logits[0, true_len - 1, :].astype(jnp.float32)
        with jax.named_scope("sample"):
            tok = _sample_one(last, seed, step, temp, top_k, top_p)
        return tok, view.k, view.v

    return chunk


def _build_copy_fn():
    """Pool page copy (the COW engine step): pools in, pools out —
    rides the same donate/thread-the-pools discipline as the decode
    and prefill programs."""
    def copy(k_pages, v_pages, src, dst):
        return (k_pages.at[:, dst].set(k_pages[:, src]),
                v_pages.at[:, dst].set(v_pages[:, src]))

    return copy


@contextlib.contextmanager
def _cache_at(cache_dir):
    """Point jax's persistent compile cache at ``cache_dir`` for the
    compiles inside, admitting sub-second serving programs, and put
    the process's settings back after — unrelated jit traffic must not
    inherit a zero-threshold cache in the serve directory.  No-op for
    ``cache_dir=None``."""
    if not cache_dir:
        yield
        return
    import jax
    from jax.experimental.compilation_cache import \
        compilation_cache as cc
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    restore = {k: getattr(jax.config, k) for k in keys}
    for k, v in zip(keys, (cache_dir, 0, 0)):
        jax.config.update(k, v)
    # the cache latches its settings at the process's first compile
    # (parameter init, usually): re-arm it for these programs, and
    # again after, so the next compile latches the restored ones
    cc.reset_cache()
    try:
        yield
    finally:
        for k, v in restore.items():
            jax.config.update(k, v)
        cc.reset_cache()


class WarmPool:
    """AOT-compile the serving programs for the fixed shape ladder at
    startup, behind jax's persistent compile cache.

    One decode program (slots x 1 token) plus one prefill program per
    ladder length — compiled via ``lower().compile()`` (the same
    topology-compile seam ``TrainStep(aot=True)`` rides, which is how
    ``tools/hlo_snapshot.py`` pins the decode program chip-free).  With
    ``cache_dir`` set the XLA executables persist across processes:
    ``stats["cache_hit"]`` is True when a replica start compiled
    everything out of the cache (zero new cache entries) — the
    cold-start-free spin-up the warm pool exists for."""

    def __init__(self, net, serve_cfg: ServeConfig, params=None,
                 scales=None, mesh=None):
        import jax
        import jax.numpy as jnp

        from .models.kv_cache import init_pools
        from .parallel.mesh import mesh_scope
        t0 = time.monotonic()
        cfg = net.cfg
        self.cfg = cfg
        self.serve_cfg = serve_cfg
        self.spec = serve_cfg.cache_spec(cfg)
        ps = net.collect_params()
        if params is None:
            params = {k: p.data()._data for k, p in ps.items()}
        scales = scales or {}
        if serve_cfg.int8 and not scales:
            params, scales = quantize_weights(params)
        self.params = params
        self.scales = scales
        # JAX_COMPILATION_CACHE_DIR, when set, is the cache: the pool
        # compiles into it and counts its hits there.  Only without it
        # does an explicit ServeConfig(cache_dir=) repoint the cache,
        # and only around this pool's own compiles
        own_dir = None if _ccache.placed_from_outside() \
            else serve_cfg.cache_dir
        cache_dir = own_dir or jax.config.jax_compilation_cache_dir
        before = _ccache.cache_entries(cache_dir)
        dtype = jnp.dtype(cfg.dtype)
        spec = self.spec
        self.k_pages, self.v_pages = init_pools(spec)
        # sharded replica: params by their Megatron TP annotations, KV
        # pools over the Hkv heads axis, tables/scalars replicated —
        # the same AOT .lower().compile() path below then emits ONE
        # GSPMD-partitioned decode program (pinned chip-free as
        # serve_decode_tp_* by tools/hlo_snapshot.py)
        self.mesh = mesh
        shard_p = shard_pool = shard_rep = None
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            from .parallel.sharding import _valid_spec, param_sharding
            shard_rep = NamedSharding(mesh, PartitionSpec())
            shard_p = param_sharding(ps, mesh)
            shard_pool = NamedSharding(mesh, _valid_spec(
                PartitionSpec(None, None, "tp", None, None),
                self.k_pages.shape, mesh, warn=False))
            self.k_pages = jax.device_put(self.k_pages, shard_pool)
            self.v_pages = jax.device_put(self.v_pages, shard_pool)
            params = {k: jax.device_put(v, shard_p[k])
                      for k, v in params.items()}
            self.params = params
        self._put = (lambda x: jax.device_put(x, shard_rep)) \
            if mesh is not None else (lambda x: x)

        def aval(shape, dt_, shard=None):
            if shard is not None:
                return jax.ShapeDtypeStruct(shape, dt_, sharding=shard)
            return jax.ShapeDtypeStruct(shape, dt_)

        pool_aval = aval(self.k_pages.shape, self.k_pages.dtype,
                         shard_pool)
        pav = {k: aval(v.shape, v.dtype,
                       shard_p[k] if shard_p is not None else None)
               for k, v in params.items()}
        i32 = lambda *shape: aval(shape, jnp.int32, shard_rep)  # noqa: E731
        f32 = lambda *shape: aval(shape, jnp.float32, shard_rep)  # noqa: E731

        def compiled(program, fn, donate, *avals):
            with _profiler.build_span("mx.serve.compile", program=program):
                return jax.jit(fn, donate_argnums=donate).lower(
                    *avals).compile()

        # the mesh is in scope while the programs trace, so the
        # Pallas kernels wrap themselves per tp shard
        with _cache_at(own_dir), mesh_scope(mesh):
            decode = _build_decode_fn(net, ps, spec.page_size, scales,
                                      dtype)
            S, MP = spec.slots, spec.max_pages_per_slot
            self._decode = compiled(
                "decode", decode, (1, 2),
                pav, pool_aval, pool_aval, i32(S, MP), i32(S), i32(S),
                aval((S,), jnp.bool_, shard_rep),
                i32(S), i32(S), f32(S), i32(S), f32(S))
            prefill = _build_prefill_fn(net, ps, spec.page_size,
                                        scales, dtype)
            samp = (i32(), i32(), f32(), i32(), f32())
            self._prefill = {}
            for T in serve_cfg.ladder:
                self._prefill[T] = compiled(
                    "prefill%d" % T, prefill, (1, 2),
                    pav, pool_aval, pool_aval, i32(MP), i32(1, T),
                    i32(), *samp)
            # the chunk ladder (prefix-cache suffix prefill) reuses
            # the same rungs; the plain prefill programs above stay
            # bitwise-unchanged for the start==0 path
            self._chunk = {}
            if serve_cfg.prefix_cache:
                chunk = _build_chunk_fn(net, ps, spec.page_size,
                                        scales, dtype)
                for T in serve_cfg.ladder:
                    self._chunk[T] = compiled(
                        "chunk%d" % T, chunk, (1, 2),
                        pav, pool_aval, pool_aval, i32(MP), i32(1, T),
                        i32(), i32(), *samp)
            # pool page copy — the COW step that makes a shared page
            # privately writable
            self._copy = compiled(
                "copy", _build_copy_fn(), (0, 1),
                pool_aval, pool_aval, i32(), i32())
        new = _ccache.cache_entries(cache_dir) - before
        self.stats = {
            "compile_s": round(time.monotonic() - t0, 3),
            "programs": 2 + len(self._prefill) + len(self._chunk),
            "sharded": mesh is not None,
            "cache_dir": cache_dir,
            "cache_new_entries": new if cache_dir else None,
            "cache_hit": (new == 0) if cache_dir else None,
            "int8": bool(scales),
        }
        log.info("serve warm pool ready: %d programs in %.2fs%s",
                 self.stats["programs"], self.stats["compile_s"],
                 " (persistent-cache hit)" if self.stats["cache_hit"]
                 else "")

    def ladder_fit(self, n):
        """Smallest ladder length holding an n-token prompt (None when
        the prompt exceeds the ladder)."""
        for T in self.serve_cfg.ladder:
            if n <= T:
                return T
        return None

    # -- program invocations (the caller threads the pools) -------------
    def run_prefill(self, tokens_padded, page_row, true_len, start=0,
                    sampling=None, step=0):
        """Prefill ``true_len`` real tokens (ladder-padded input).
        ``start > 0`` routes through the chunk program: the tokens are
        the prompt SUFFIX from absolute position ``start``, earlier
        positions read from cached pages.  ``sampling``/``step`` feed
        the in-graph sampler (defaults: greedy, step 0)."""
        import jax.numpy as jnp
        put = self._put
        T = int(tokens_padded.shape[-1])
        sp = _norm_sampling(sampling, 0)
        samp = (put(jnp.asarray(sp["seed"], jnp.int32)),
                put(jnp.asarray(step, jnp.int32)),
                put(jnp.asarray(sp["temperature"], jnp.float32)),
                put(jnp.asarray(sp["top_k"], jnp.int32)),
                put(jnp.asarray(sp["top_p"], jnp.float32)))
        row = put(jnp.asarray(page_row, jnp.int32))
        toks = put(jnp.asarray(tokens_padded, jnp.int32).reshape(1, T))
        tl = put(jnp.asarray(true_len, jnp.int32))
        if start:
            tok, self.k_pages, self.v_pages = self._chunk[T](
                self.params, self.k_pages, self.v_pages, row, toks,
                tl, put(jnp.asarray(start, jnp.int32)), *samp)
        else:
            tok, self.k_pages, self.v_pages = self._prefill[T](
                self.params, self.k_pages, self.v_pages, row, toks,
                tl, *samp)
        return tok

    def run_decode(self, page_table, lengths, tokens, active,
                   sampling=None):
        """One decode step.  ``sampling`` is a dict of per-slot arrays
        (seeds, steps, temps, top_ks, top_ps); None means greedy."""
        import jax.numpy as jnp
        put = self._put
        S = self.spec.slots
        sp = sampling or {}
        nxt, self.k_pages, self.v_pages = self._decode(
            self.params, self.k_pages, self.v_pages,
            put(jnp.asarray(page_table, jnp.int32)),
            put(jnp.asarray(lengths, jnp.int32)),
            put(jnp.asarray(tokens, jnp.int32)),
            put(jnp.asarray(active, bool)),
            put(jnp.asarray(sp.get("seeds",
                                   [0] * S), jnp.int32)),
            put(jnp.asarray(sp.get("steps",
                                   [0] * S), jnp.int32)),
            put(jnp.asarray(sp.get("temps",
                                   [0.0] * S), jnp.float32)),
            put(jnp.asarray(sp.get("top_ks",
                                   [0] * S), jnp.int32)),
            put(jnp.asarray(sp.get("top_ps",
                                   [1.0] * S), jnp.float32)))
        return nxt

    def copy_page(self, src, dst):
        """COW: copy page ``src``'s K/V (all layers) into ``dst`` —
        runs BEFORE the chunk prefill that writes into ``dst``."""
        import jax.numpy as jnp
        put = self._put
        self.k_pages, self.v_pages = self._copy(
            self.k_pages, self.v_pages,
            put(jnp.asarray(src, jnp.int32)),
            put(jnp.asarray(dst, jnp.int32)))


class Server:
    """The serving replica: a :class:`WarmPool`, a
    :class:`SlotScheduler`, and one engine thread running the
    continuous-batching loop.  Clients call :meth:`submit` /
    :meth:`result` (or the one-shot :meth:`generate`) from any thread.

    Engine iteration (the protocol the mxverify scenario explores)::

        snapshot = sched.begin_step()      # capacity, preemption
        launch decode(snapshot)            # async dispatch
        while plan := sched.admit_next():  # admissions OVERLAP decode
            first = prefill(plan)
            sched.commit_prefill(plan, first)   # epoch-checked
        sched.commit_step(snapshot, results)    # epoch-checked
    """

    def __init__(self, net, serve_cfg=None, mesh=None, **kw):
        self.cfg = serve_cfg or ServeConfig(**kw)
        self.pool = WarmPool(net, self.cfg, mesh=mesh)
        spec = self.pool.spec
        self.sched = SlotScheduler(spec.slots, spec.pages,
                                   spec.page_size,
                                   spec.max_pages_per_slot,
                                   prefix_cache=self.cfg.prefix_cache,
                                   ladder=self.cfg.ladder)
        self._lock = threading.Lock()   # guards _prompts/_done/_live
        self._prompts = {}              # rid -> list[int] prompt tokens
        self._done = {}                 # rid -> threading.Event
        self._live = frozenset()        # rids not yet terminal
        self._results = {}              # rid -> terminal request dict
        self._deadlines = {}            # rid -> monotonic expiry time
        self._expired = set()           # rids cancelled by the sweep
        self._stop = threading.Event()
        self._work = threading.Event()
        self._thread = None
        self._steps = 0                 # engine iterations (span number)
        self._error = None              # engine-thread death, if any
        # streaming SLO sketches, fed at terminal delivery — mergeable
        # across replicas, O(buckets) to ship on the heartbeat
        self.slo = _telemetry.ServeSLO()

    # -- client API -----------------------------------------------------
    def submit(self, prompt_tokens, max_new=None, sampling=None,
               deadline=None):
        """Enqueue a request.  ``sampling`` overrides the replica's
        default knobs per request ({seed, temperature, top_k, top_p});
        the seed defaults to the rid, so two identical prompts still
        decorrelate unless the client pins a seed.  ``deadline`` is a
        per-request budget in SECONDS (default: the replica's
        ``MXNET_SERVE_DEADLINE_MS`` knob); an expired request is
        cancelled through the scheduler — pages and radix refcounts
        released — and :meth:`result` raises
        :class:`DeadlineExceededError`."""
        prompt = [int(t) for t in prompt_tokens]
        if not prompt:
            raise ValueError("empty prompt")
        if max_new is None:
            max_new = self.cfg.max_new
        if max_new < 1:
            raise ValueError("max_new must be >= 1, got %r"
                             % (max_new,))
        if self.pool.ladder_fit(len(prompt)) is None:
            raise ValueError(
                "prompt of %d tokens exceeds the prefill ladder %s"
                % (len(prompt), self.cfg.ladder))
        sp = dict(self.cfg.default_sampling())
        sp.update(sampling or {})
        if deadline is None:
            deadline = self.cfg.default_deadline()
        # sched.submit runs INSIDE our lock (one-way Server->sched
        # nesting, never reversed) so the engine can never admit a rid
        # whose prompt/event aren't registered yet
        with self._lock:
            if self._error is not None:
                raise RuntimeError("serve engine thread died") \
                    from self._error
            rid = self.sched.submit(len(prompt), max_new,
                                    prompt=prompt, sampling=sp)
            self._prompts[rid] = prompt
            self._done[rid] = threading.Event()
            self._live = self._live | {rid}
            if deadline is not None:
                self._deadlines[rid] = (time.monotonic()
                                        + float(deadline))
        self._work.set()
        return rid

    def cancel(self, rid):
        ok = self.sched.cancel(rid)
        # the engine sweep is the SOLE notifier (setting the event here
        # would race its _results migration and deliver a record the
        # sweep then re-stores forever); wake it so the cancelled
        # waiter is released within one iteration
        self._work.set()
        return ok

    def _pop_result(self, rid):
        """Pop the terminal record AND the deadline-expiry verdict for
        ``rid`` under one lock acquisition (a two-step read would race
        the sweep)."""
        with self._lock:
            res = self._results.pop(rid, None)
            expired = rid in self._expired
            self._expired.discard(rid)
        return res, expired

    def result(self, rid, timeout=None):
        """Block for the request's terminal state; returns the request
        dict (state done|cancelled|failed, generated ``tokens``).
        Single-delivery: the record is evicted from the result store
        on return (Server memory stays bounded by UNDELIVERED
        requests) — a second call for the same rid returns None.

        Timeout semantics (cancel-and-evict): a caller that gives up
        OWNS the give-up — the request is cancelled through the
        scheduler (pages/refcounts released) and its record evicted,
        so an abandoned request cannot pin slots or Server memory
        waiting for a collector that never comes.  A request whose
        DEADLINE expired raises :class:`DeadlineExceededError`
        instead."""
        with self._lock:
            ev = self._done.get(rid)
        if ev is not None and not ev.wait(timeout):
            # cancel-and-evict: nobody is coming back for this rid
            self.cancel(rid)
            with self._lock:
                self._live = self._live - {rid}
                self._done.pop(rid, None)
                self._prompts.pop(rid, None)
                self._results.pop(rid, None)
                self._deadlines.pop(rid, None)
                self._expired.discard(rid)
            self.sched.purge(rid)
            raise TimeoutError(
                "request %d not finished within %.3fs — cancelled and "
                "evicted" % (rid, timeout))
        res, expired = self._pop_result(rid)
        if expired:
            raise DeadlineExceededError(
                "request %d exceeded its deadline (cancelled, pages "
                "released)" % rid)
        if res is not None:
            return res
        req = self.sched.request(rid)  # in flight (death/stop paths)
        if req is None:
            # the sweep moved it between our two reads: it is in the
            # result store NOW (stored before the scheduler purge)
            res, expired = self._pop_result(rid)
            if expired:
                raise DeadlineExceededError(
                    "request %d exceeded its deadline (cancelled, "
                    "pages released)" % rid)
            return res
        if req["state"] not in ("done", "cancelled", "failed"):
            with self._lock:
                err = self._error
            if err is not None:
                raise RuntimeError(
                    "serve engine thread died with request %d "
                    "in flight" % rid) from err
        return req

    def generate(self, prompt_tokens, max_new=None, timeout=None,
                 sampling=None, deadline=None):
        """One-shot submit+result.  ``timeout`` follows
        :meth:`result`'s cancel-and-evict semantics; ``deadline`` is
        the request's own budget (typed
        :class:`DeadlineExceededError`)."""
        rid = self.submit(prompt_tokens, max_new=max_new,
                          sampling=sampling, deadline=deadline)
        return self.result(rid, timeout=timeout)

    def slo_snapshot(self):
        """Live serving SLOs: p50/p95/p99 latency, TTFT and queue-time
        sketches plus tokens/s — computed from the streaming histograms
        (no per-request state is retained past delivery)."""
        return self.slo.snapshot()

    def attach_telemetry(self, sess=None):
        """Register this replica's load gauges (queue depth, running
        slots, free pages) on a telemetry session so they ride the
        fleet heartbeat — the serving-side load signal the ROADMAP's
        elastic policy layer consumes.  Returns the session."""
        sess = sess or _telemetry.session()
        sched = self.sched
        sess.register_gauge("serve::queue_depth",
                            lambda: sched.stats()["waiting"])
        sess.register_gauge("serve::running",
                            lambda: sched.stats()["running"])
        sess.register_gauge("serve::free_pages",
                            lambda: sched.stats()["free_pages"])
        return sess

    def attach_elastic(self, runner):
        """Ride an :class:`~mxnet_tpu.fault_elastic.ElasticRunner`:
        chain onto its ``on_resize`` so every topology change (a peer
        preempted, a replacement joined) drains this replica's slots
        through :meth:`SlotScheduler.preempt_all` — requests survive in
        the queue and re-prefill on the resharded program; only KV
        state is recomputed.  A JOINED replica needs no drain at all:
        its scheduler starts empty and its first requests warm-spin
        from the :class:`WarmPool`'s AOT-compiled ladder (the pool was
        built before the join, so the first prefill pays zero compile).
        Returns the runner for chaining."""
        prev = runner.on_resize
        sched = self.sched

        def _drain(info, _prev=prev):
            gen = getattr(info.gen, "value", info.gen)
            sched.preempt_all(reason="resize gen=%s world=%s"
                              % (gen, info.world))
            self._work.set()   # engine re-admits on the new program
            if _prev is not None:
                _prev(info)
        runner.on_resize = _drain
        return runner

    # -- engine ---------------------------------------------------------
    def start(self):
        if self._thread is None:
            self._stop.clear()
            self._thread = threading.Thread(target=self._engine_loop,
                                            daemon=True,
                                            name="mxserve-engine")
            self._thread.start()
        return self

    def stop(self):
        self._stop.set()
        self._work.set()
        if self._thread is not None:
            self._thread.join(timeout=30.0)
            self._thread = None
        # an orderly stop must not strand blocked result() callers any
        # more than a crash may: wake every live waiter — their
        # requests read back in their honest non-terminal state
        with self._lock:
            evs = [self._done[r] for r in self._live
                   if r in self._done]
        for ev in evs:
            ev.set()

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()
        return False

    def _finish_terminal(self):
        """Fire the completion event of every request that reached a
        terminal state — the single notification path (finish, cancel,
        preempt-to-failure), so no commit path can forget one.  The
        terminal record moves to ``_results`` and is PURGED from the
        scheduler (whose per-request state must stay bounded by live
        requests — see :meth:`SlotScheduler.purge`); the record is
        stored before the purge so a concurrently-woken ``result()``
        always finds it in one place or the other."""
        with self._lock:
            live = self._live
        done = {}
        for rid in live:
            req = self.sched.request(rid)
            if req is not None and req["state"] in ("done", "cancelled",
                                                    "failed"):
                done[rid] = req
        if not done:
            return
        with self._lock:
            # re-filter against the CURRENT live set: a concurrent
            # timeout-eviction (result's cancel-and-evict) may have
            # disowned a rid after our snapshot — re-storing it would
            # leak the record forever
            done = {rid: req for rid, req in done.items()
                    if rid in self._live}
            if not done:
                return
            self._live = self._live - frozenset(done)
            self._results.update(done)
            evs = [self._done.pop(rid, None) for rid in done]
            for rid in done:
                self._prompts.pop(rid, None)
                self._deadlines.pop(rid, None)
        for rid, req in done.items():
            # lifecycle spans + SLO samples are cut from the record's
            # phase timestamps HERE, before the purge — per-request
            # telemetry state dies with the request
            _telemetry.request_lifecycle(req, slo=self.slo)
            self.sched.purge(rid)
        for ev in evs:
            if ev is not None:
                ev.set()

    def _sweep_deadlines(self):
        """Cancel every request whose deadline passed — through the
        scheduler, so pages and radix refcounts are released like any
        other cancel; :meth:`result` turns the cancellation into a
        typed :class:`DeadlineExceededError` via ``_expired``.  Runs
        on the engine thread each iteration (deadline resolution is
        one engine step, plenty for second-scale budgets)."""
        with self._lock:
            if not self._deadlines:
                return
            now = time.monotonic()
            due = sorted(rid for rid, t in self._deadlines.items()
                         if now >= t)
        for rid in due:
            cancelled = self.sched.cancel(rid)
            with self._lock:
                self._deadlines.pop(rid, None)
                if cancelled and rid in self._live:
                    self._expired.add(rid)
            if cancelled:
                _telemetry.bump("serve::deadline_exceeded")
                _flightrec.record("serve.deadline",
                                  detail="rid %d expired" % rid)

    def _engine_loop(self):
        try:
            while not self._stop.is_set():
                if not self.engine_step():
                    # idle: park until a submit pokes us (bounded
                    # wait = cheap insurance against a lost wake)
                    self._work.wait(0.25)
                    self._work.clear()
        except BaseException as e:
            # a dying engine must not strand blocked result()
            # callers: record the error, wake every live waiter
            # (result() re-raises it), refuse new submits
            with self._lock:
                self._error = e
                evs = [self._done[r] for r in self._live
                       if r in self._done]
            log.exception("serve engine thread died")
            _flightrec.note_terminal("serve_engine", exc=e)
            for ev in evs:
                ev.set()
            raise

    def engine_step(self):
        """One engine iteration; returns False when idle.  Public so
        tests (and single-threaded drivers) can pump the engine without
        the background thread."""
        self._steps += 1
        with _profiler.step_span("mx.serve.step", self._steps) as step:
            return self._engine_step(step)

    def _engine_step(self, step):
        import numpy as onp
        sched, pool = self.sched, self.pool
        spec = pool.spec
        eos = self.cfg.eos_id
        with _profiler.span("mx.serve.schedule"):
            # chaos seam: serve_engine_kill fires here, on the engine
            # thread — the replica-death offense ReplicaGroup fails over
            _fault.serve_engine_check("engine_step")
            self._sweep_deadlines()
            snapshot = sched.begin_step()
            step.set(active=len(snapshot),
                     context_tokens=sum(e["len"] + 1 for e in snapshot))
            if snapshot:
                S, MP = spec.slots, spec.max_pages_per_slot
                page_table = onp.zeros((S, MP), onp.int32)
                lengths = onp.zeros((S,), onp.int32)
                tokens = onp.zeros((S,), onp.int32)
                active = onp.zeros((S,), bool)
                seeds = onp.zeros((S,), onp.int32)
                steps = onp.zeros((S,), onp.int32)
                temps = onp.zeros((S,), onp.float32)
                top_ks = onp.zeros((S,), onp.int32)
                top_ps = onp.ones((S,), onp.float32)
                for e in snapshot:
                    row = list(e["pages"])[:MP]
                    page_table[e["slot"], :len(row)] = row
                    lengths[e["slot"]] = e["len"]
                    tokens[e["slot"]] = e["last_tok"]
                    active[e["slot"]] = True
                    sp = e.get("sampling") or {}
                    seeds[e["slot"]] = sp.get("seed", 0)
                    steps[e["slot"]] = e.get("step", 0)
                    temps[e["slot"]] = sp.get("temperature", 0.0)
                    top_ks[e["slot"]] = sp.get("top_k", 0)
                    top_ps[e["slot"]] = sp.get("top_p", 1.0)
        toks = None
        if snapshot:
            # async dispatch: the device crunches the decode while the
            # host runs admissions/prefills below (their programs chain
            # on the pool arrays, so ordering is functional, not timed)
            with _profiler.span("mx.serve.decode.dispatch"):
                toks = pool.run_decode(page_table, lengths, tokens,
                                       active,
                                       sampling={"seeds": seeds,
                                                 "steps": steps,
                                                 "temps": temps,
                                                 "top_ks": top_ks,
                                                 "top_ps": top_ps})
        admitted = False
        while True:
            plan = sched.admit_next()
            if plan is None:
                break
            admitted = True
            with self._lock:
                prompt = self._prompts.get(plan["rid"])
            if prompt is None:
                # a timeout-eviction disowned the rid between admit
                # and here; its cancel already freed the slot, and any
                # commit against this plan is epoch-dropped
                continue
            prompt = list(prompt)
            req = sched.request(plan["rid"])
            prompt = prompt + [int(t) for t in (req or {}).get(
                "tokens", ())]  # preempted: re-prefill generated tail
            start = int(plan.get("prefill_start", 0))
            chunk = prompt[start:]
            # the prefix-cache win: only the UNCOVERED suffix rides
            # the ladder, so a mostly-shared prompt fits a smaller
            # rung (prefill compute scales with the padded length)
            T = pool.ladder_fit(len(chunk))
            if T is None:
                # a preempted request regrew past the ladder: terminal
                sched.fail(plan)
                continue
            what = dict(rid=plan["rid"], padded=T, true_len=len(chunk),
                        start=start)
            with _profiler.span("mx.serve.admit", **what):
                if plan.get("cow"):
                    # the first computed position lands in a shared
                    # page: privatize it before any write can touch it
                    pool.copy_page(*plan["cow"])
                padded = onp.zeros((T,), onp.int32)
                padded[:len(chunk)] = chunk
                row = onp.zeros((spec.max_pages_per_slot,), onp.int32)
                row[:len(plan["pages"])] = plan["pages"]
                # the prefill blocks: int() waits for its token
                with _profiler.span("mx.serve.prefill", **what):
                    first = int(pool.run_prefill(
                        padded, row, len(chunk), start=start,
                        sampling=plan.get("sampling"),
                        step=plan.get("ntok", 0)))
                sched.commit_prefill(plan, first,
                                     done=(eos is not None
                                           and first == eos))
        if snapshot:
            try:
                _fault.serve_decode_check()
                # the host waits here for the decode it dispatched
                with _profiler.span("mx.serve.readback"):
                    out = onp.asarray(toks)
            except Exception as exc:  # noqa: BLE001 -- classification filter
                from . import fault_dist as _fdist
                if _fdist.classify_xla_error(exc) != "transient":
                    raise  # fatal or unclassified: honest engine death
                # transient decode failure: NOTHING was committed, page
                # writes are write-before-read, and sampling is pure in
                # (seed, step) — dropping the step and redoing it next
                # iteration is bitwise identical to never having failed
                _telemetry.bump("serve::decode_retries")
                _flightrec.record("serve.decode_retry",
                                  error=type(exc).__name__)
                log.warning("serve: transient decode failure — step "
                            "dropped for deterministic replay: %s", exc)
                self._finish_terminal()
                return True
        with _profiler.span("mx.serve.commit"):
            if snapshot:
                results = [(int(out[e["slot"]]),
                            eos is not None
                            and int(out[e["slot"]]) == eos)
                           for e in snapshot]
                sched.commit_step(snapshot, results)
            self._finish_terminal()
        return bool(snapshot) or admitted


# ----------------------------------------------------------------------
# chip-free AOT seam (tools/hlo_snapshot.py)
# ----------------------------------------------------------------------
def lower_decode_program(cfg=None, serve_cfg=None, mesh=None,
                         dtype=None):
    """Lower THE decode program without materializing parameters —
    the serving analog of ``TrainStep(aot=True)``: abstract params +
    pool avals (optionally sharded onto a PJRT *topology* mesh, no
    chips), so ``tools/hlo_snapshot.py`` can pin the compiled decode
    artifact's host-transfer count and KV buffer shapes in CI.

    Returns ``(lowered, info)`` where ``info`` names the pool shape
    the O(1)-decode assertion checks against."""
    import jax
    import jax.numpy as jnp

    from .models import TransformerLM, tiny_config
    from .parallel.mesh import mesh_scope
    cfg = cfg or tiny_config()
    serve_cfg = serve_cfg or ServeConfig(slots=4, page_size=128,
                                         pages=16, ladder=(128,),
                                         max_new=128, cache_dir=None,
                                         int8=False)
    net = TransformerLM(cfg)
    ps = net.collect_params()
    spec = serve_cfg.cache_spec(cfg)
    dt = jnp.dtype(dtype or cfg.dtype)
    pool_shape = (spec.n_layers, spec.pages, spec.n_kv_heads,
                  spec.page_size, spec.head_dim)
    shard_rep = shard_pool = None
    shard_p = {}
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec
        shard_rep = NamedSharding(mesh, PartitionSpec())
        shard_pool = shard_rep
        shard_p = {k: shard_rep for k in ps}
        if "tp" in mesh.axis_names:
            # tensor-parallel replica: params by their Megatron
            # annotations, pools over the Hkv heads axis, control
            # tables replicated — the serve_decode_tp_* artifacts
            from .parallel.sharding import _valid_spec, param_sharding
            shard_p = param_sharding(ps, mesh)
            shard_pool = NamedSharding(mesh, _valid_spec(
                PartitionSpec(None, None, "tp", None, None),
                pool_shape, mesh, warn=False))

    def av(shape, dtype, shard=None):
        kw = {"sharding": shard} if shard is not None else {}
        return jax.ShapeDtypeStruct(shape, dtype, **kw)

    pool_aval = av(pool_shape, dt, shard_pool)
    pav = {k: av(tuple(p.shape), dt, shard_p.get(k))
           for k, p in ps.items()}
    S, MP = spec.slots, spec.max_pages_per_slot
    decode = _build_decode_fn(net, ps, spec.page_size, {}, dt)
    i32 = lambda *shape: av(shape, jnp.int32, shard_rep)  # noqa: E731
    f32 = lambda *shape: av(shape, jnp.float32, shard_rep)  # noqa: E731
    with mesh_scope(mesh):
        lowered = jax.jit(decode, donate_argnums=(1, 2)).lower(
            pav, pool_aval, pool_aval, i32(S, MP), i32(S), i32(S),
            av((S,), jnp.bool_, shard_rep),
            i32(S), i32(S), f32(S), i32(S), f32(S))
    info = {"pool_shape": pool_shape, "slots": S,
            "max_pages_per_slot": MP}
    if shard_pool is not None:
        info["pool_spec"] = str(getattr(shard_pool, "spec", None))
    return lowered, info
