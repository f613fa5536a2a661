#!/usr/bin/env python3
"""Where the router's gradient gap of a window/full MoE cell comes from,
on the chip at the cell's own size:

    python benchmark/chip/tests/router_flips.py --workload <cell> \\
        --seeds 1,2 --out <file.jsonl>

Each seed builds the cell's step, weights and batch as
``drivers/train_window_moe.py`` does and takes the program's first step,
its router wrapped so that every MoE layer's top-k ids reach the host
(``jax.debug.callback``).  One JSON line a seed:

- ``plan``: the step's ``TrainStep.recompute_plan``, the blocks spared
  and made again and the bytes free and asked;
- ``executions``: how often each MoE layer's router ran in the step (2
  where the block is made again for the backward), and
  ``again_differ``: the share of tokens whose set a later execution
  changed;
- ``differ``: a MoE layer's share of tokens whose top-k set in the
  program is not the float32 reference's; ``held_differ``: the share
  whose experts held here are not;
- ``own``: the compared numbers of the first step against the reference
  as the cell has it;
- ``pinned``: the same against the reference made to take the program's
  own selection in every MoE layer: what is left of a gap there is the
  rounding of the products, with the selection the same.

Not part of a benchmark run.
"""
import argparse
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(HERE))))

import common  # noqa: E402
from drivers import train_window_moe as driver  # noqa: E402
from drivers.train_tokens import token_ring  # noqa: E402

_SELECTED = {}
# the layer whose routed experts are being traced, and each MoE block's
# layer by the block's identity
_NOW, _LAYER_OF = [None], {}


def capture():
    """Wraps the library's routed experts and sigmoid router so that each
    layer's top-k ids of every execution land in ``_SELECTED[layer]``, in
    order; ``watch`` says which block is which layer."""
    import jax
    import numpy as onp
    from mxnet_tpu.models import experts
    route, forward = experts.sigmoid_route, experts.RoutedExperts.forward

    def traced(self, x):
        _NOW[0] = _LAYER_OF[id(self)]
        return forward(self, x)

    def recorded(x, router_w, top_k, scale):
        probs, top_e, gates = route(x, router_w, top_k, scale)
        layer = _NOW[0]
        jax.debug.callback(
            lambda e: _SELECTED.setdefault(layer, []).append(
                onp.asarray(e)), top_e)
        return probs, top_e, gates

    experts.RoutedExperts.forward = traced
    experts.sigmoid_route = recorded


def watch(net):
    _SELECTED.clear()
    _LAYER_OF.clear()
    _LAYER_OF.update({id(blk.feed_forward): i
                      for i, blk in enumerate(net.layers)})


def _differ(a, b, keep=None):
    """Share of rows whose sets of ids differ, of those ``keep`` holds
    where it is given."""
    import numpy as onp
    if keep is not None:
        a, b = onp.where(keep(a), a, -1), onp.where(keep(b), b, -1)
    return float(onp.mean(onp.any(onp.sort(a, -1) != onp.sort(b, -1), -1)))


def one(workload, seed):
    import jax
    import jax.numpy as jnp
    import numpy as onp
    cell = common.load_cell(workload)
    model, mix = cell["model"], cell["traffic_params"]
    ref = common.module("reference", model["family"])
    builder = common.module("builders", model["family"])
    specs = ref.leaf_specs(model)
    leaves = driver.checked_leaves(specs, mix["check"])
    keep = set(sum(leaves.values(), []))
    x, y = token_ring(mix, seed, model["vocab_size"])

    weights = common.make_weights(seed, specs)
    built = builder.TrainCell(model, weights)
    watch(built.net)
    seen = driver.first_steps(built, weights, x, y, 1, keep)
    jax.effects_barrier()
    plan = built.step.recompute_plan
    built.free()
    del built, weights
    gc.collect()

    weights = common.make_weights(seed, specs)
    B, T = x.shape[1:]
    picked = [{i: jnp.asarray(e[0].reshape(B, T, -1)[r])
               for i, e in _SELECTED.items()} for r in range(B)]
    again = {i: max([_differ(e[0], later) for later in e[1:]] or [0.0])
             for i, e in _SELECTED.items()}
    own = ref.selections(model, weights, x[0])
    first, n = model["first_expert_held"], model["num_experts"]

    def held(e):
        return (e >= first) & (e < first + n)

    differ = {i: _differ(onp.asarray(own[0][i]), onp.asarray(picked[0][i]))
              for i in own[0]}
    held_differ = {i: _differ(onp.asarray(own[0][i]),
                              onp.asarray(picked[0][i]), held)
                   for i in own[0]}
    if set(differ) != set(_SELECTED):
        raise RuntimeError("the program routed in layers %s, the reference "
                           "in %s" % (sorted(_SELECTED), sorted(differ)))
    want = driver.follow(ref.make_step(model, drop_state_at=1), weights,
                         x, y, 1, keep)
    pinned = driver.follow(ref.make_step(model, drop_state_at=1,
                                         selection=picked),
                           weights, x, y, 1, keep)
    return {"seed": seed, "plan": plan,
            "executions": {i: len(e) for i, e in _SELECTED.items()},
            "again_differ": again, "differ": differ,
            "held_differ": held_differ,
            "own": driver.compare(seen, want, leaves),
            "pinned": driver.compare(seen, pinned, leaves)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--out", required=True)
    a = ap.parse_args()
    os.makedirs(os.path.dirname(os.path.abspath(a.out)), exist_ok=True)
    common.require_chips(1, False)
    common.place_cache()
    capture()
    for seed in (int(s) for s in a.seeds.split(",")):
        t0 = time.monotonic()
        row = dict(one(a.workload, seed), seconds=time.monotonic() - t0)
        line = json.dumps(row, default=str)
        with open(a.out, "a") as f:
            f.write(line + "\n")
        print(line, flush=True)


if __name__ == "__main__":
    main()
