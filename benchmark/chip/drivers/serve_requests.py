"""Serving cells: requests from the generator's plan through
``Server.submit()`` / ``result()`` with the engine thread running.

Closed loop: ``clients`` threads, each sending its next request when the
last is delivered.  Open loop: one thread submits on the plan's schedule
whether or not earlier requests finished, one collects; a request is
timed from when it was due.  The warm-up runs the traffic itself, so the
window opens on a steady engine.  Arrivals stop when the window closes;
requests in flight then are drained and enter the tails with their true
times, while the token rate counts only the tokens that came out inside
the window.  Once everything is delivered and the replica is freed, the
plain reference runs over a sample of the served requests.
"""
import gc
import math
import queue
import threading
import time

import common
import generator

RESULT_TIMEOUT_S = 300.0


class Load:
    """Offers the plan to the server and keeps every request's record."""

    def __init__(self, server, plan, mix, t_base, t_stop):
        self.server, self.plan, self.mix = server, plan, mix
        self.t_base, self.t_stop = t_base, t_stop
        self.records, self.errors = [], []
        self._next, self._lock = 0, threading.Lock()
        self.threads = []

    def _take(self):
        with self._lock:
            if self._next >= len(self.plan):
                raise RuntimeError("the plan ran out of requests")
            i, self._next = self._next, self._next + 1
        return self.plan[i]

    def _collect(self, req, rid, due, late):
        rec = {"due": due, "late": late, "prompt": req["prompt"],
               "out": req["out"], "state": "lost", "tokens": ()}
        try:
            res = self.server.result(rid, timeout=RESULT_TIMEOUT_S)
            if res is not None:
                rec.update({k: res[k] for k in (
                    "state", "tokens", "t_submit", "t_admit", "t_first",
                    "t_done", "preempts")})
        except Exception as e:  # noqa: BLE001 -- a lost request is counted, not raised
            rec["error"] = repr(e)
        with self._lock:
            self.records.append(rec)

    def _client(self):
        try:
            while time.monotonic() < self.t_stop:
                req = self._take()
                due = time.monotonic()
                rid = self.server.submit(req["prompt"], max_new=req["out"])
                self._collect(req, rid, due, 0.0)
        except Exception as e:  # noqa: BLE001 -- reported by the driver
            self.errors.append(e)

    def _schedule(self, handoff):
        try:
            while True:
                req = self._take()
                due = self.t_base + req["due"]
                if due >= self.t_stop:
                    break
                wait = due - time.monotonic()
                if wait > 0:
                    time.sleep(wait)
                late = max(0.0, time.monotonic() - due)
                rid = self.server.submit(req["prompt"], max_new=req["out"])
                handoff.put((req, rid, due, late))
        except Exception as e:  # noqa: BLE001 -- reported by the driver
            self.errors.append(e)
        finally:
            handoff.put(None)

    def _drain(self, handoff):
        while True:
            item = handoff.get()
            if item is None:
                return
            self._collect(*item)

    def start(self):
        arr = self.mix["arrivals"]
        if arr["process"] == "closed":
            self.threads = [threading.Thread(target=self._client,
                                             name="bench-client-%d" % i)
                            for i in range(arr["clients"])]
        else:
            handoff = queue.Queue()
            self.threads = [
                threading.Thread(target=self._schedule, args=(handoff,),
                                 name="bench-schedule"),
                threading.Thread(target=self._drain, args=(handoff,),
                                 name="bench-collect")]
        for t in self.threads:
            t.start()

    def join(self):
        for t in self.threads:
            t.join(RESULT_TIMEOUT_S + 60)
        alive = [t.name for t in self.threads if t.is_alive()]
        if alive:
            raise RuntimeError("load threads did not end: %s" % alive)
        if self.errors:
            raise self.errors[0]


def tokens_inside(rec, w0, w1):
    """How many of a delivered request's tokens came out inside
    [w0, w1).  The record holds the first token's time and the last's;
    the ones between are placed evenly, as a steady decode emits them
    (per-token times are not in the program's record yet)."""
    n, t0, t1 = len(rec["tokens"]), rec["t_first"], rec["t_done"]
    if n == 1 or t1 <= t0:
        return n if w0 <= t1 < w1 else 0
    gap = (t1 - t0) / (n - 1)
    # token j at t0 + j gap: count j in [0, n) with w0 <= t_j < w1
    lo = max(0, math.ceil((w0 - t0) / gap))
    hi = min(n, math.ceil((w1 - t0) / gap))
    return max(0, hi - lo)


def warm_rungs(server, plan):
    """One short request through every prefill rung the plan's prompts
    use, and through decode, before the traffic starts: the host-side
    helpers jax compiles on a shape's first use (a reshape, a cast) are
    then compiled, so that nothing compiles inside the window whichever
    lengths the warm-up's traffic happens to bring."""
    fit = server.pool.ladder_fit
    longest = {}
    for req in plan:
        rung = fit(len(req["prompt"]))
        if len(req["prompt"]) > len(longest.get(rung, ())):
            longest[rung] = req["prompt"]
    rids = [server.submit(p, max_new=2) for p in longest.values()]
    for rid in rids:
        server.result(rid, timeout=RESULT_TIMEOUT_S)


def window_metrics(records, w0, w1):
    """End-to-end numbers of the window [w0, w1) from the request
    records.  Rates count what was delivered inside the window; tails are
    over every request due in it, a failed one counting as the worst."""
    due = [r for r in records if w0 <= r["due"] < w1]
    ok = [r for r in due if r["state"] == "done"
          and len(r["tokens"]) == r["out"]]
    failed = len(due) - len(ok)
    delivered = sum(tokens_inside(r, w0, w1) for r in records
                    if r["state"] == "done")
    ttft = [(r["t_first"] - r["due"]) * 1e3 for r in ok]
    tpot = [(r["t_done"] - r["t_first"]) / (len(r["tokens"]) - 1) * 1e3
            for r in ok if len(r["tokens"]) > 1]
    if failed and ok:
        ttft += [max(ttft)] * failed
        tpot += [max(tpot)] * failed
    return {"attempted": len(due), "failed": failed,
            "serve_tokens_per_s": delivered / (w1 - w0),
            "ttft_p95_ms": common.percentile(ttft, 95),
            "ttft_p50_ms": common.percentile(ttft, 50),
            "tpot_p95_ms": common.percentile(tpot, 95),
            "tpot_p50_ms": common.percentile(tpot, 50),
            "queue_wait_p95_ms": common.percentile(
                [(r["t_admit"] - r["t_submit"]) * 1e3 for r in ok], 95),
            "late_p95_ms": common.percentile(
                [r["late"] * 1e3 for r in due], 95),
            "preempts": sum(r.get("preempts", 0) for r in ok)}


def sample_served(records, w0, w1, seed, n):
    """``n`` requests that finished in the window, the longest among
    them, the rest drawn from the seed."""
    import numpy as onp
    done = [r for r in records if r["state"] == "done"
            and w0 <= r["t_done"] < w1]
    done.sort(key=lambda r: (r["t_done"], len(r["prompt"])))
    if not done:
        return []
    longest = max(done, key=lambda r: len(r["prompt"]) + len(r["tokens"]))
    rest = [r for r in done if r is not longest]
    pick = onp.random.RandomState(seed % (2 ** 32)).permutation(len(rest))
    return [longest] + [rest[i] for i in pick[:n - 1]]


def served_gap(ref, model, weights, sample, pad_to, precision="f32",
               against=None):
    """The widest gap, over every served token of the sample, by which
    the token's reference logit lies below the reference's best at that
    position.  With ``precision`` below float32 the reference stands in
    the program's place (the control): the token judged at each position
    is the one that precision puts first, its gap read in ``against``,
    the float32 logits.  Returns (gap, tokens compared, float32 logits)."""
    import jax
    import jax.numpy as jnp
    import numpy as onp
    rows = onp.zeros((len(sample), pad_to), onp.int32)
    nxt = onp.zeros((len(sample), pad_to), onp.int32)
    mask = onp.zeros((len(sample), pad_to), bool)
    for i, r in enumerate(sample):
        seq = list(r["prompt"]) + list(r["tokens"])
        if len(seq) - 1 > pad_to:
            raise ValueError("a served request of %d tokens does not fit "
                             "the check's pad_to %d" % (len(seq), pad_to))
        rows[i, :len(seq) - 1] = seq[:-1]
        lo = len(r["prompt"]) - 1     # row lo chose tokens[0]
        nxt[i, lo:len(seq) - 1] = r["tokens"]
        mask[i, lo:len(seq) - 1] = True
    logits = ref.logits(model, weights, rows, precision=precision)

    @jax.jit
    def widest(full, tok, mask):
        at = jnp.take_along_axis(full, tok[..., None], -1)[..., 0]
        return jnp.max(jnp.where(mask, jnp.max(full, -1) - at, 0.0))

    if against is None:
        gap = widest(logits, jnp.asarray(nxt), jnp.asarray(mask))
    else:
        gap = widest(against, jnp.argmax(logits, -1), jnp.asarray(mask))
    return float(gap), int(mask.sum()), logits


def run(ctx):
    cell, mix, model = ctx["cell"], ctx["cell"]["traffic_params"], \
        ctx["cell"]["model"]
    ref = common.module("reference", model["family"])
    builder = common.module("builders", model["family"])
    specs = ref.leaf_specs(model)
    built = builder.ServeCell(model, common.make_weights(ctx["seed"], specs),
                              **ctx.get("builder_args", {}))
    server = built.server
    seconds = min(ctx["seconds"], mix["trace_s"]) if ctx["trace"] \
        else ctx["seconds"]
    plan = generator.request_plan(mix, ctx["seed"], model["vocab_size"],
                                  seconds)
    spans = ctx["tracer"].watch_server(server) if ctx["trace"] else None

    server.start()
    warm_rungs(server, plan)
    t_base = time.monotonic()
    w0 = t_base + mix["warmup_s"]
    w1 = w0 + seconds
    load = Load(server, plan, mix, t_base, w1)
    load.start()
    time.sleep(max(0.0, w0 - time.monotonic()))
    compiles = ctx["compiles"].n
    if ctx["trace"]:
        ctx["tracer"].start()
    ctx["setup_s"] = time.monotonic() - ctx["t_start"]
    w0 = time.monotonic()
    time.sleep(max(0.0, w1 - time.monotonic()))
    w1 = time.monotonic()
    trace = ctx["tracer"].stop() if ctx["trace"] else None
    compiled_in_window = ctx["compiles"].n - compiles
    load.join()
    sched_stats = server.sched.stats()
    server.stop()
    if server._error is not None:
        raise RuntimeError("the engine thread died") from server._error

    memory = common.peak_bytes(ctx["devices"]) + built.temp_bytes
    built.free()
    del built, server
    gc.collect()

    m = window_metrics(load.records, w0, w1)
    sample = sample_served(load.records, w0, w1, ctx["seed"],
                           mix["check"]["requests"])

    def weights(names):
        return common.make_weights(ctx["seed"], specs, only=names)

    gap, compared, full = served_gap(ref, model, weights, sample,
                                     mix["check"]["pad_to"])
    values = {"served_logit_gap": gap, "failed_requests": m["failed"],
              "compiled_in_window": compiled_in_window}
    control_values = {
        name: {"served_logit_gap": served_gap(
            ref, model, weights, sample, mix["check"]["pad_to"],
            against=full, **how)[0]}
        for name, how in ctx["controls"].items()}
    del full
    print("serve: %d due, %d failed, %.1f tokens/s, ttft p50/p95 %.1f/%.1f "
          "ms, tpot p50/p95 %.2f/%.2f ms, queue wait p95 %.1f ms, generator "
          "late p95 %.2f ms, %d preemptions, compared %d tokens of %d "
          "requests, scheduler %s"
          % (m["attempted"], m["failed"], m["serve_tokens_per_s"],
             m["ttft_p50_ms"] or -1, m["ttft_p95_ms"] or -1,
             m["tpot_p50_ms"] or -1, m["tpot_p95_ms"] or -1,
             m["queue_wait_p95_ms"] or -1, m["late_p95_ms"] or 0,
             m["preempts"], compared, len(sample), sched_stats), flush=True)
    e2e = {k: m[k] for k in ("serve_tokens_per_s", "ttft_p95_ms",
                             "tpot_p95_ms")}
    e2e["setup_s"] = ctx["setup_s"]
    return {"attempted": m["attempted"], "failed": m["failed"],
            "end_to_end": e2e, "values": values,
            "control_values": control_values,
            "memory_peak_bytes": memory, "trace": trace,
            "facts": {"records": load.records, "w0": w0, "w1": w1,
                      "spans": spans, "model": model,
                      "queue_wait_p95_ms": m["queue_wait_p95_ms"]}}
