"""Training cells of a window/full-attention MoE decoder: a ring of seeded
token batches already on the device, cycled through the program's
compiled step back to back, the loss read every ``read_loss_every``-th
step as a training loop logs it.

As in ``train_dsa_moe.py``, less its selection, set-up builds the one
step object, drives it through its first
steps and hands the same object to the window; every number the
comparison reads is that step's own: its loss and aux output (the mean
cross-entropy and the router term), the first gradient from Adam's first
moment after one step, ``g = m1 / (1 - beta1)``, and how far that step
moved the parameters.  Once the window has closed and the step's state is
freed, the plain reference follows the same steps from the same weights
and batches.  The gradients compared leaf for leaf are the head's, the
routers' and the head gates'; the pairs routed to the experts held here
are read of every step of the window (``facts["held_pairs"]``).

In the traced run the program's own names are reduced too
(``program_trace.reduce_program``) and handed to the readers as
``facts["program"]``: the device's ops by named-scope path.
"""
import gc
import os
import time

import common
import program_trace
from drivers.train_tokens import _norm, token_ring

PARTS = ("ce", "router_loss")


def _scalar(a):
    import jax
    return float(jax.device_get(a).reshape(-1)[0])


def first_steps(cell, weights, x, y, n, keep):
    """What the comparison reads of the program's first ``n`` steps:
    every step's loss; of the first step its aux terms, the gradient's
    norm leaf by leaf and, of the ``keep`` leaves, the gradient itself
    (on the host), and the norm of each leaf's move from ``weights``."""
    import jax
    seen = {"loss": []}
    for i in range(n):
        loss, parts = cell.step(*cell.wrap(x[i], y[i]))
        seen["loss"].append(float(loss))
        if i == 0:
            seen["parts"] = {k: _scalar(parts[k]._data) for k in PARTS}
            scale = 1.0 / (1.0 - cell.beta1)
            seen["grad"] = {k: v * scale for k, v in
                            cell.first_moment_norms().items()}
            seen["g1"] = {k: jax.device_get(v) * scale for k, v in
                          cell.first_moment(keep).items()}
            seen["update"] = cell.update_norms(weights)
    return seen


def follow(step, weights, x, y, n, keep):
    """The reference's (or a control's) first ``n`` steps, read the same
    way; the last step needs no gradient."""
    import jax.numpy as jnp
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
            out["parts"] = {k: _scalar(parts[k]) for k in PARTS}
            out["grad"] = {k: _norm(g) for k, g in grads.items()}
            out["g1"] = {k: grads[k] for k in keep}
            out["update"] = {k: _norm(params[k].astype(jnp.float32)
                                      - weights[k].astype(jnp.float32))
                             for k in params}
        del grads
    return out


def checked_leaves(specs, check):
    """``{kind: [leaf]}`` of the gradients compared leaf for leaf: the
    traffic's ``head_leaves`` and every layer's leaves of the names
    ``router_leaves`` and ``gate_leaves`` give."""
    leaves = {"head": check["head_leaves"]}
    for kind in ("router", "gate"):
        leaves[kind] = [k for k in specs
                        if k.split(".")[-1] in check[kind + "_leaves"]]
    return leaves


def compare(got, want, leaves):
    """The cell's compared numbers from two sets of first-step readings.
    ``loss_gap``: the widest gap of a checked step's loss
    (cross-entropy and router term) over the reference's (the second
    step's is computed from the parameters the first step wrote);
    ``<kind>_grad_diff`` for each ``leaves`` kind (head, router, gate):
    the first gradient of those leaves, taken together, as the norm of
    its difference from the reference's over the reference's norm;
    ``grad_norm_gap.median``: the median leaf's gap of the first
    gradient's norm, against the reference's norm of that leaf or of the
    median leaf, whichever is larger; ``update_norm_gap.median``: the
    same of the norm of the parameters' move in the first step (a state
    left unchanged reads 1)."""
    import jax.numpy as jnp

    def median_gap(name):
        floor = common.median(list(want[name].values()))
        return common.median([abs(got[name][k] - w) / max(w, floor)
                              for k, w in want[name].items()])

    def diff(names):
        apart = sum(_norm(jnp.asarray(got["g1"][k], jnp.float32)
                          - want["g1"][k].astype(jnp.float32)) ** 2
                    for k in names)
        whole = sum(want["grad"][k] ** 2 for k in names)
        return (apart / whole) ** 0.5 if whole else float("inf")

    out = {"loss_gap": max(abs(g - w) / abs(w)
                           for g, w in zip(got["loss"], want["loss"]))}
    for kind, names in leaves.items():
        out[kind + "_grad_diff"] = diff(names)
    out["grad_norm_gap.median"] = median_gap("grad")
    out["update_norm_gap.median"] = median_gap("update")
    return out


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
        # handed a program compiled under other names
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
    leaves = checked_leaves(specs, mix["check"])
    keep = set(sum(leaves.values(), []))
    seen = first_steps(built, weights, x, y, n_check, keep)
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
    n, losses, pairs, met, done = 0, [], [], 0.0, 0
    while n == 0 or met + (n + 1 - done) * dt < seconds:
        if ctx["trace"]:
            with jax.profiler.TraceAnnotation("bench.train_step", n=n):
                loss, parts = step(*batches[n % ring])
        else:
            loss, parts = step(*batches[n % ring])
        pairs.append(parts["held_pairs"]._data)
        n += 1
        if n % every == 0:
            losses.append(float(loss))
            met, done = time.monotonic() - t0, n
            dt = met / n
    jax.block_until_ready(loss._data)
    window = time.monotonic() - t0
    trace, facts = None, {"held_pairs": [int(p) for p in
                                         jax.device_get(pairs)]}
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
    del built, step, batches, loss, parts
    gc.collect()

    weights = common.make_weights(ctx["seed"], specs)
    # the checked steps end in a forward alone: the last gradient step's
    # moments are read by nobody
    last = n_check - 1
    want = follow(ref.make_step(model, drop_state_at=last), weights, x, y,
                  n_check, keep)
    values = compare(seen, want, leaves)
    values["compiled_in_window"] = compiled_in_window
    values["nonfinite_losses"] = sum(
        1 for v in losses + seen["loss"] if v != v or abs(v) == float("inf"))
    control_values = {
        name: compare(follow(ref.make_step(model, drop_state_at=last, **how),
                             weights, x, y, n_check, keep), want, leaves)
        for name, how in ctx["controls"].items()}
    step_ms = window / n * 1e3
    tokens = model["sequences"] * model["seq_len"]
    print("train: %d steps in %.3f s; %.1f tokens/s; losses %s (reference "
          "%s); parts %s (reference %s); held pairs %s -> %s"
          % (n, window, tokens * n / window, seen["loss"], want["loss"],
             seen["parts"], want["parts"], facts["held_pairs"][:4],
             losses[-3:]), flush=True)
    return {"attempted": n, "failed": 0,
            "end_to_end": {"train_step_ms": step_ms,
                           "setup_s": ctx["setup_s"]},
            "values": values, "control_values": control_values,
            "memory_peak_bytes": memory, "trace": trace, "facts": facts}
