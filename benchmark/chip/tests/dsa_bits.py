#!/usr/bin/env python3
"""Whether the sparse attention's two halves see one selection, on the
chip at a DSA cell's own size:

    python benchmark/chip/tests/dsa_bits.py --workload <cell> \\
        --seeds 1,2 --out <file.jsonl>

The forward gathers each query's keys by ``idx``; the backward, where it
walks the causal tiles, masks them by the bits ``models/dsa.select``
packs from the score rows, the top-k's last score and its tie rule
(ties to the lower position).  Each seed builds the cell's step, weights
and batch as ``drivers/train_dsa_moe.py`` does and takes the program's
first step, ``select`` wrapped so that every layer's ``idx``,
``n_valid``, ``vals`` and bits reach the host (``jax.debug.callback``).
One JSON line a seed, and in it one entry a layer's execution:

- ``queries``: the queries checked;
- ``popcount_off``: queries whose bits do not count ``n_valid``;
- ``slots_unset``: valid slots whose key's bit is not set;
- ``tied_at_tau``: full queries with two or more slots at the top-k's
  last score, the rows where the tie rule decides a bit.

Both of the first two read 0 where the halves agree.  Not part of a
benchmark run.
"""
import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(HERE))))

import common  # noqa: E402
from drivers.train_tokens import token_ring  # noqa: E402

_SEEN = []
#: the layers whose selection the step traced, in order
_TRACED = []


def disagreement(idx, n_valid, vals, bits):
    """The counts above of one sequence's selection, on the host."""
    import numpy as onp
    idx, n_valid, vals, bits = (onp.asarray(a) for a in
                                (idx, n_valid, vals, bits))
    T, K = idx.shape
    valid = onp.arange(K)[None, :] < n_valid[:, None]
    row = 8 * (idx // 256) + idx % 8
    got = (bits[row, onp.arange(T)[:, None]] >> ((idx % 256) // 8)
           .astype(onp.uint32)) & 1
    count = onp.bitwise_count(bits).sum(axis=0, dtype=onp.int64)
    full = n_valid == K
    return {"queries": int(T),
            "popcount_off": int(onp.sum(count != n_valid)),
            "slots_unset": int(onp.sum(valid & (got == 0))),
            "tied_at_tau": int(onp.sum(
                full & (onp.sum(vals == vals[:, -1:], axis=1) > 1)))}


def capture():
    """Wraps ``dsa.select`` so that each call's selection is checked on
    the host, in the order the step traced the layers."""
    import jax
    from mxnet_tpu.models import dsa
    select = dsa.select

    def checked(qi, ki, w, topk, *a, **kw):
        out = select(qi, ki, w, topk, *a, **kw)
        if out[3] is not None:
            layer = len(_TRACED)
            _TRACED.append(layer)
            jax.debug.callback(
                lambda *s: _SEEN.append(dict(disagreement(*s), layer=layer)),
                out[0], out[2], out[1], out[3])
        return out

    dsa.select = checked


def one(workload, seed):
    import jax
    cell = common.load_cell(workload)
    model, mix = cell["model"], cell["traffic_params"]
    ref = common.module("reference", model["family"])
    builder = common.module("builders", model["family"])
    weights = common.make_weights(seed, ref.leaf_specs(model))
    built = builder.TrainCell(model, weights)
    del weights
    x, y = token_ring(mix, seed, model["vocab_size"])
    _SEEN.clear()
    _TRACED.clear()
    loss = built.step(*built.wrap(x[0], y[0]))[0]
    float(loss)
    jax.effects_barrier()
    built.free()
    return {"seed": seed, "layers_traced": len(_TRACED),
            "executions": list(_SEEN)}


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
        line = json.dumps(row)
        with open(a.out, "a") as f:
            f.write(line + "\n")
        print(line, flush=True)


if __name__ == "__main__":
    main()
