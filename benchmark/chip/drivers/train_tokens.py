"""Token training cells: a ring of seeded token batches already on the
device, cycled through the program's compiled step back to back, the
loss read every ``read_loss_every``-th step as a training loop logs it.

Set-up builds the one step object, drives it through its first steps and
hands the same object to the window.  Every number the comparison reads
is that step's own: its loss and the exits' mean cross-entropies and
probabilities (the step's aux outputs), the first gradient from Adam's
first moment after one step, ``g = m1 / (1 - beta1)``, and how far that
step moved the parameters.  Once the window has closed and the step's
state is freed, the plain reference follows the same steps from the same
weights and batches.

In the traced run the program's own names are reduced too
(``program_trace.reduce_program``) and handed to the readers as
``facts["program"]``: the device's ops by named-scope path.
"""
import gc
import os
import time

import common
import program_trace


def token_ring(mix, seed, vocab):
    """``(tokens, labels)``, each (ring, sequences, seq_len) int32 made
    on the device in one jitted call: ids uniform over the vocabulary,
    the label of a position the id that follows it."""
    import jax
    import jax.numpy as jnp
    import numpy as onp
    shape = (mix["ring"], mix["sequences"], mix["seq_len"] + 1)

    @jax.jit
    def make(s):
        ids = jax.random.randint(jax.random.key(s), shape, 0, vocab,
                                 jnp.int32)
        return ids[..., :-1], ids[..., 1:]

    return make(onp.uint32(int(seed) % (2 ** 32)))


def _norm(a):
    import jax.numpy as jnp
    return float(jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)))))


def first_steps(cell, weights, x, y, n, keep):
    """What the comparison reads of the program's first ``n`` steps:
    every step's loss; of the first step the exits' ``parts``, the
    gradient's norm leaf by leaf and, of the ``keep`` leaves, the
    gradient itself (on the host: the window's device memory is the
    deployment's), and the norm of each leaf's move from ``weights``."""
    import jax
    seen = {"loss": []}
    for i in range(n):
        loss, parts = cell.step(*cell.wrap(x[i], y[i]))
        seen["loss"].append(float(loss))
        if i == 0:
            seen["parts"] = {k: [float(v) for v in
                                 jax.device_get(parts[k]._data)]
                             for k in ("ce", "p")}
            scale = 1.0 / (1.0 - cell.beta1)
            seen["grad"] = {k: v * scale for k, v in
                            cell.first_moment_norms().items()}
            seen["g1"] = {k: jax.device_get(v) * scale for k, v in
                          cell.first_moment(keep).items()}
            seen["update"] = cell.update_norms(weights)
    return seen


def follow(make_step, model, weights, x, y, n, keep, **how):
    """The reference's (or a control's) first ``n`` steps, read the same
    way; the last step needs no gradient."""
    import jax.numpy as jnp
    step = make_step(model, **how)
    params, state = dict(weights), None
    out = {"loss": []}
    for i in range(n):
        if i == n - 1 and i > 0:
            loss, _ = step.loss(params, x[i], y[i])
            out["loss"].append(float(loss))
            break
        loss, parts, grads, params, state = step(params, state, x[i], y[i])
        out["loss"].append(float(loss))
        if i == 0:
            out["parts"] = {k: [float(v) for v in parts[k]] for k in parts}
            out["grad"] = {k: _norm(g) for k, g in grads.items()}
            out["g1"] = {k: grads[k] for k in keep}
            out["update"] = {k: _norm(params[k].astype(jnp.float32)
                                      - weights[k].astype(jnp.float32))
                             for k in params}
        if i + 2 >= n:
            state = None        # no later gradient step reads the moments
        del grads
    return out


def _padded(a, b):
    """Two lists of exits made as long as the longer; an exit that one
    side lacks reads 0 there."""
    n = max(len(a), len(b))
    return list(a) + [0.0] * (n - len(a)), list(b) + [0.0] * (n - len(b))


def compare(got, want, head, gate):
    """The cell's compared numbers from two sets of first-step readings.
    ``ce_gap``: the widest gap of an exit's mean cross-entropy in the
    first step, over the reference's; ``mean_p_gap``: the widest
    difference of an exit's mean probability; ``loss_gap``: the widest
    gap of a checked step's loss over the reference's (the second
    step's is computed from the parameters the first step wrote);
    ``head_grad_diff``, ``gate_grad_diff``: the first gradient of those
    leaves, taken together, as the norm of its difference from the
    reference's over the reference's norm; ``grad_norm_gap.median``:
    the median leaf's gap of the first gradient's norm, against the
    reference's norm of that leaf or of the median leaf, whichever is
    larger;
    ``update_norm_gap.median``: the same of the norm of the parameters'
    move in the first step (a state left unchanged reads 1)."""
    import jax.numpy as jnp
    ce_g, ce_w = _padded(got["parts"]["ce"], want["parts"]["ce"])
    p_g, p_w = _padded(got["parts"]["p"], want["parts"]["p"])

    def median_gap(name):
        floor = common.median(list(want[name].values()))
        return common.median([abs(got[name][k] - w) / max(w, floor)
                              for k, w in want[name].items()])

    def diff(leaves):
        # over the leaves together: the gate's bias is one number, and
        # its gradient alone can lie near 0 (PERF.md, PR 28)
        apart = sum(_norm(jnp.asarray(got["g1"][k], jnp.float32)
                          - want["g1"][k].astype(jnp.float32)) ** 2
                    for k in leaves)
        return (apart / sum(want["grad"][k] ** 2 for k in leaves)) ** 0.5

    return {
        "ce_gap": max(abs(g - w) / max(w, 1e-6)
                      for g, w in zip(ce_g, ce_w)),
        "mean_p_gap": max(abs(g - w) for g, w in zip(p_g, p_w)),
        "loss_gap": max(abs(g - w) / abs(w)
                        for g, w in zip(got["loss"], want["loss"])),
        "head_grad_diff": diff(head),
        "gate_grad_diff": diff(gate),
        "grad_norm_gap.median": median_gap("grad"),
        "update_norm_gap.median": median_gap("update")}


def run(ctx):
    import jax
    cell, mix, model = ctx["cell"], ctx["cell"]["traffic_params"], \
        ctx["cell"]["model"]
    if (mix["sequences"], mix["seq_len"]) != (model["sequences"],
                                              model["seq_len"]):
        raise ValueError("the traffic's batch is not the configuration's")
    if ctx["trace"]:
        # op names are metadata, which jax leaves out of the persistent
        # cache's key: a traced run that is to show scopes must not be
        # handed a program compiled under other names (PR 26)
        jax.config.update("jax_compilation_cache_include_metadata_in_key",
                          True)
    ref = common.module("reference", model["family"])
    builder = common.module("builders", model["family"])
    specs = ref.leaf_specs(model)
    weights = common.make_weights(ctx["seed"], specs)
    built = builder.TrainCell(model, weights, **ctx["builder_args"])
    x, y = token_ring(mix, ctx["seed"], model["vocab_size"])
    n_check, ring = mix["check"]["steps"], mix["ring"]
    if ring < n_check:
        raise ValueError("the ring holds fewer batches than are checked")
    head, gate = mix["check"]["head_leaves"], mix["check"]["gate_leaves"]
    seen = first_steps(built, weights, x, y, n_check, set(head + gate))
    del weights
    step = built.step
    batches = [built.wrap(x[i], y[i]) for i in range(ring)]
    t_warm = time.monotonic()
    for i in range(mix["warm_steps"]):
        loss = step(*batches[i % ring])[0]
    float(loss)
    dt = (time.monotonic() - t_warm) / mix["warm_steps"]

    seconds = min(ctx["seconds"], mix["trace_s"]) if ctx["trace"] \
        else ctx["seconds"]
    every = mix["read_loss_every"]
    compiles = ctx["compiles"].n
    kept = ctx["tracer"].out_dir + ".kept.xplane.pb"
    if ctx["trace"]:
        ctx["tracer"].keep = kept
        ctx["tracer"].start()
    t0 = time.monotonic()
    ctx["setup_s"] = t0 - ctx["t_start"]
    # A step takes seconds and the host runs several dispatches ahead of
    # the device: a step is dispatched only if the device should finish
    # it inside the window, reckoned from the last loss read (when host
    # and device met) and the steps' own time so far.
    n, losses, met, done = 0, [], 0.0, 0
    while n == 0 or met + (n + 1 - done) * dt < seconds:
        if ctx["trace"]:
            with jax.profiler.TraceAnnotation("bench.train_step", n=n):
                loss = step(*batches[n % ring])[0]
        else:
            loss = step(*batches[n % ring])[0]
        n += 1
        if n % every == 0:
            losses.append(float(loss))
            met, done = time.monotonic() - t0, n
            dt = met / n
    jax.block_until_ready(loss._data)
    window = time.monotonic() - t0
    trace, facts = None, {}
    if ctx["trace"]:
        trace = ctx["tracer"].stop()
        if os.path.exists(kept):
            facts["program"] = program_trace.reduce_program(kept)
            os.remove(kept)
    compiled_in_window = ctx["compiles"].n - compiles

    live = max((d.memory_stats() or {}).get("bytes_in_use", 0)
               for d in ctx["devices"])
    memory = max(common.peak_bytes(ctx["devices"]),
                 live + built.temp_bytes(*batches[0]))
    built.free()
    del built, step, batches, loss
    gc.collect()

    weights = common.make_weights(ctx["seed"], specs)
    keep = head + gate
    want = follow(ref.make_step, model, weights, x, y, n_check, keep)
    values = compare(seen, want, head, gate)
    values["compiled_in_window"] = compiled_in_window
    values["nonfinite_losses"] = sum(
        1 for v in losses + seen["loss"] if v != v or abs(v) == float("inf"))
    control_values = {
        name: compare(follow(ref.make_step, model, weights, x, y, n_check,
                             keep, **how), want, head, gate)
        for name, how in ctx["controls"].items()}
    step_ms = window / n * 1e3
    tokens = model["sequences"] * model["seq_len"]
    print("train: %d steps in %.3f s; %.1f tokens/s; losses %s (reference "
          "%s); exits ce %s p %s (reference %s %s) -> %s"
          % (n, window, tokens * n / window, seen["loss"], want["loss"],
             seen["parts"]["ce"], seen["parts"]["p"], want["parts"]["ce"],
             want["parts"]["p"], losses[-3:]), flush=True)
    return {"attempted": n, "failed": 0,
            "end_to_end": {"train_step_ms": step_ms,
                           "setup_s": ctx["setup_s"]},
            "values": values, "control_values": control_values,
            "memory_peak_bytes": memory, "trace": trace, "facts": facts}
