"""Byte training cells: a ring of seeded byte batches already on the
device, each with the labels of the model's several byte predictors,
cycled through the program's compiled step back to back, the loss read
every ``read_loss_every``-th step as a training loop logs it.

As in ``train_tokens.py`` (whose ``follow`` and ``_norm`` this file
uses), set-up builds the one step object, drives it through its first
steps and hands the same object to the window; every number the
comparison reads is that step's own: its loss and the heads' mean
cross-entropies (the step's aux output), the first gradient from Adam's
first moment after one step, ``g = m1 / (1 - beta1)``, and how far that
step moved the parameters.  Once the window has closed and the step's
state is freed, the plain reference follows the same steps from the same
weights and batches.  What differs from the token cells is here: labels
at ``heads`` offsets, no exit gate, and the comparison (the pooling
vectors' gradient in the gate's place).

In the traced run the program's own names are reduced too
(``program_trace.reduce_program``) and handed to the readers as
``facts["program"]``: the device's ops by named-scope path.
"""
import gc
import os
import time

import common
import program_trace
from drivers.train_tokens import _norm, follow


def byte_ring(mix, seed, vocab, heads):
    """``(bytes, labels)``: (ring, sequences, seq_len) and (ring,
    sequences, seq_len, heads) int32 made on the device in one jitted
    call: ids uniform over the vocabulary, ``seq_len + heads`` of them a
    sequence; predictor ``k``'s label at ``t`` is the id at ``t + 1 +
    k``."""
    import jax
    import jax.numpy as jnp
    import numpy as onp
    T = mix["seq_len"]
    shape = (mix["ring"], mix["sequences"], T + heads)

    @jax.jit
    def make(s):
        ids = jax.random.randint(jax.random.key(s), shape, 0, vocab,
                                 jnp.int32)
        return ids[..., :T], jnp.stack(
            [ids[..., 1 + k:T + 1 + k] for k in range(heads)], axis=-1)

    return make(onp.uint32(int(seed) % (2 ** 32)))


def first_steps(cell, weights, x, y, n, keep):
    """What the comparison reads of the program's first ``n`` steps:
    every step's loss; of the first step the heads' cross-entropies, the
    gradient's norm leaf by leaf and, of the ``keep`` leaves, the
    gradient itself (on the host: the window's device memory is the
    deployment's), and the norm of each leaf's move from ``weights``."""
    import jax
    seen = {"loss": []}
    for i in range(n):
        loss, parts = cell.step(*cell.wrap(x[i], y[i]))
        seen["loss"].append(float(loss))
        if i == 0:
            seen["parts"] = {"ce": [float(v) for v in
                                    jax.device_get(parts["ce"]._data)]}
            scale = 1.0 / (1.0 - cell.beta1)
            seen["grad"] = {k: v * scale for k, v in
                            cell.first_moment_norms().items()}
            seen["g1"] = {k: jax.device_get(v) * scale for k, v in
                          cell.first_moment(keep).items()}
            seen["update"] = cell.update_norms(weights)
    return seen


def compare(got, want, head, summary):
    """The cell's compared numbers from two sets of first-step readings.
    ``ce_gap``: the widest gap of a head's mean cross-entropy in the
    first step, over the reference's; ``loss_gap``: the widest gap of a
    checked step's loss over the reference's (the second step's is
    computed from the parameters the first step wrote);
    ``head_grad_diff``, ``summary_grad_diff``: the first gradient of
    those leaves, taken together, as the norm of its difference from the
    reference's over the reference's norm (the pooling vectors of every
    layer are the only leaves that see nothing but the summaries);
    ``grad_norm_gap.median``: the median leaf's gap of the first
    gradient's norm, against the reference's norm of that leaf or of
    the median leaf, whichever is larger; ``update_norm_gap.median``:
    the same of the norm of the parameters' move in the first step (a
    state left unchanged reads 1)."""
    import jax.numpy as jnp

    def median_gap(name):
        floor = common.median(list(want[name].values()))
        return common.median([abs(got[name][k] - w) / max(w, floor)
                              for k, w in want[name].items()])

    def diff(leaves):
        apart = sum(_norm(jnp.asarray(got["g1"][k], jnp.float32)
                          - want["g1"][k].astype(jnp.float32)) ** 2
                    for k in leaves)
        return (apart / sum(want["grad"][k] ** 2 for k in leaves)) ** 0.5

    return {
        "ce_gap": max(abs(g - w) / max(w, 1e-6) for g, w in
                      zip(got["parts"]["ce"], want["parts"]["ce"])),
        "loss_gap": max(abs(g - w) / abs(w)
                        for g, w in zip(got["loss"], want["loss"])),
        "head_grad_diff": diff(head),
        "summary_grad_diff": diff(summary),
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

    def seeded_weights():
        return ref.clamp(specs, common.make_weights(ctx["seed"], specs))

    weights = seeded_weights()
    built = builder.TrainCell(model, weights, **ctx["builder_args"])
    x, y = byte_ring(mix, ctx["seed"], model["vocab_size"],
                     model["num_pred_heads"])
    n_check, ring = mix["check"]["steps"], mix["ring"]
    if ring < n_check:
        raise ValueError("the ring holds fewer batches than are checked")
    head = mix["check"]["head_leaves"]
    summary = [k for k in specs
               if k.split(".")[-1] in mix["check"]["summary_leaves"]]
    keep = head + summary
    seen = first_steps(built, weights, x, y, n_check, set(keep))
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
    # the host runs several dispatches ahead of the device: a step is
    # dispatched only if the device should finish it inside the window,
    # reckoned from the last loss read (when host and device met) and
    # the steps' own time so far
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

    weights = seeded_weights()
    # the checked steps end in a forward alone: the last gradient step's
    # moments are read by nobody
    last = {"drop_state_at": n_check - 1}
    want = follow(ref.make_step, model, weights, x, y, n_check, keep,
                  **last)
    values = compare(seen, want, head, summary)
    values["compiled_in_window"] = compiled_in_window
    values["nonfinite_losses"] = sum(
        1 for v in losses + seen["loss"] if v != v or abs(v) == float("inf"))
    control_values = {
        name: compare(follow(ref.make_step, model, weights, x, y, n_check,
                             keep, **dict(how, **last)), want, head, summary)
        for name, how in ctx["controls"].items()}
    step_ms = window / n * 1e3
    tokens = model["sequences"] * model["seq_len"]
    print("train: %d steps in %.3f s; %.1f bytes/s; losses %s (reference "
          "%s); heads ce %s (reference %s) -> %s"
          % (n, window, tokens * n / window, seen["loss"], want["loss"],
             seen["parts"]["ce"], want["parts"]["ce"], losses[-3:]),
          flush=True)
    return {"attempted": n, "failed": 0,
            "end_to_end": {"train_step_ms": step_ms,
                           "setup_s": ctx["setup_s"]},
            "values": values, "control_values": control_values,
            "memory_peak_bytes": memory, "trace": trace, "facts": facts}
