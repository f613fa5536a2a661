"""Training cells: a ring of seeded batches already on the device, cycled
through the program's compiled step back to back, the loss read every
``read_loss_every``-th step as a training loop logs it.

Set-up builds the one step object, drives it through its first steps on
rows that all differ, and hands the same object to the window.  Once the
window has closed and the step's state is freed, the plain reference
follows the first three steps from the same weights and batches.
"""
import gc
import time

import common
import generator


def _leaf_norms(tree):
    import jax.numpy as jnp
    return {k: float(jnp.sqrt(jnp.sum(jnp.square(
        v.astype(jnp.float32))))) for k, v in tree.items()}


def leaf_gaps(got, want, skip=()):
    """Per leaf, the gap between the leaf's norm as the program has it
    and as the reference has it, against the reference's norm of that
    leaf or of the median leaf, whichever is larger."""
    floor = common.median(list(want.values()))
    return {k: abs(got[k] - want[k]) / max(want[k], floor)
            for k in want if k not in skip}


def median_gap(got, want, skip=()):
    """The median leaf's gap.  (The worst leaf's is the noise of one
    small leaf, a gamma whose three steps move it by less than bf16
    holds at 1, and has no upper reading: PERF.md, PR 25.)"""
    return common.median(list(leaf_gaps(got, want, skip).values()))


def first_steps(cell, x, y, n):
    """What the comparison reads of the program's first ``n`` steps."""
    import jax.numpy as jnp
    step, seen = cell.step, {"loss": []}
    seen["p0"] = cell.params()
    for i in range(n):
        seen["loss"].append(float(step(*cell.wrap(x[i], y[i]))))
        if i == 0:
            seen["m1"] = cell.momentum()
    seen["pn"] = cell.params()
    seen["update"] = _leaf_norms(
        {k: seen["pn"][k].astype(jnp.float32)
         - seen["p0"][k].astype(jnp.float32) for k in seen["p0"]})
    return seen


def follow(make_step, model, weights, x, y, n, **how):
    """The reference's (or a control's) first ``n`` steps: losses, the
    first gradient with its leaf norms, the leaf norms of the parameters'
    change."""
    import jax.numpy as jnp
    step = make_step(model, **how)
    params = dict(weights)
    mom = {k: jnp.zeros(v.shape, jnp.float32) for k, v in params.items()}
    out = {"loss": []}
    for i in range(n):
        loss, grads, params, mom = step(params, mom, x[i], y[i])
        out["loss"].append(float(loss))
        if i == 0:
            out["grad"], out["g1"] = _leaf_norms(grads), grads
        del grads
    out["update"] = _leaf_norms(
        {k: params[k].astype(jnp.float32) - weights[k].astype(jnp.float32)
         for k in params})
    return out


def compare(got, want, head):
    """The cell's compared numbers from two sets of first-step readings:
    the first gradient of the ``head`` leaves, those next to the loss,
    as the norm of its difference from the reference's over the
    reference's norm (the one number an fp8 step fails); the median
    leaf's gap of the first gradient's norm, and of the norm of the
    parameters' change, leaves whose reference gradient is under a
    thousandth of the median leaf's left out of the change."""
    floor = 1e-3 * common.median(list(want["grad"].values()))
    still = [k for k, g in want["grad"].items() if g < floor]
    diff = _leaf_norms({k: got["g1"][k] - want["g1"][k] for k in head})
    return {"head_grad_diff": max(diff[k] / want["grad"][k] for k in head),
            "grad_norm_gap.median": median_gap(got["grad"], want["grad"]),
            "update_norm_gap.median": median_gap(got["update"],
                                                 want["update"], still)}


def run(ctx):
    import jax
    import jax.numpy as jnp
    cell, mix, model = ctx["cell"], ctx["cell"]["traffic_params"], \
        ctx["cell"]["model"]
    ref = common.module("reference", model["family"])
    builder = common.module("builders", model["family"])
    specs = ref.leaf_specs(model)
    built = builder.TrainCell(model, common.make_weights(ctx["seed"], specs))
    x, y = generator.image_ring(mix, ctx["seed"], model["batch_size"],
                                model["image_size"], model["num_classes"],
                                model["param_dtype"])
    n_check = mix["check"]["steps"]
    if mix["ring"] < n_check:
        raise ValueError("the ring holds fewer batches than are checked")
    seen = first_steps(built, x, y, n_check)
    opt = model["optimizer"]
    # the first gradient as the optimizer got it: m1 = -lr (g + wd w0)
    seen["g1"] = {k: -seen["m1"][k] / opt["learning_rate"]
                  - opt["wd"] * seen["p0"][k].astype(jnp.float32)
                  for k in seen["m1"]}
    seen["grad"] = _leaf_norms(seen["g1"])
    for k in ("p0", "m1", "pn"):
        del seen[k]
    step, ring = built.step, mix["ring"]
    batches = [built.wrap(x[i], y[i]) for i in range(ring)]
    for i in range(mix["warm_steps"]):
        loss = step(*batches[i % ring])
    float(loss)

    seconds = min(ctx["seconds"], mix["trace_s"]) if ctx["trace"] \
        else ctx["seconds"]
    every = mix["read_loss_every"]
    compiles = ctx["compiles"].n
    if ctx["trace"]:
        ctx["tracer"].start()
    t0 = time.monotonic()
    ctx["setup_s"] = t0 - ctx["t_start"]
    n, losses = 0, []
    while time.monotonic() - t0 < seconds:
        if ctx["trace"]:
            with jax.profiler.TraceAnnotation("bench.train_step", n=n):
                loss = step(*batches[n % ring])
        else:
            loss = step(*batches[n % ring])
        n += 1
        if n % every == 0:
            losses.append(float(loss))
    jax.block_until_ready(loss._data)
    window = time.monotonic() - t0
    trace = ctx["tracer"].stop() if ctx["trace"] else None
    compiled_in_window = ctx["compiles"].n - compiles

    live = max((d.memory_stats() or {}).get("bytes_in_use", 0)
               for d in ctx["devices"])
    memory = max(common.peak_bytes(ctx["devices"]),
                 live + built.temp_bytes(*batches[0]))
    built.free()
    del built, step, batches, loss
    gc.collect()

    weights = common.make_weights(ctx["seed"], specs)
    want = follow(ref.make_step, model, weights, x, y, n_check)
    head = mix["check"]["head_leaves"]
    values = compare(seen, want, head)
    values["compiled_in_window"] = compiled_in_window
    values["nonfinite_losses"] = sum(
        1 for v in losses + seen["loss"] if v != v or abs(v) == float("inf"))
    control_values = {
        name: compare(follow(ref.make_step, model, weights, x, y, n_check,
                             **how), want, head)
        for name, how in ctx["controls"].items()}
    step_ms = window / n * 1e3
    print("train: %d steps in %.3f s; %.1f rows/s; losses %s (reference %s) "
          "-> %s" % (n, window, model["batch_size"] * n / window,
                     seen["loss"], want["loss"], losses[-3:]), flush=True)
    return {"attempted": n, "failed": 0,
            "end_to_end": {"train_step_ms": step_ms,
                           "setup_s": ctx["setup_s"]},
            "values": values, "control_values": control_values,
            "memory_peak_bytes": memory, "trace": trace,
            "facts": {}}
