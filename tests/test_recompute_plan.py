"""Which marked blocks are made again is sized to the device's memory
(``Block.recompute``, ``gluon.block.keeping``, ``parallel.TrainStep``'s
plan): a spared block against a checkpointed one, the search as a pure
function, the plan made from a device's reported memory and the
compiled step's own ``memory_analysis()``, a plan a batch signature,
the plan file beside the compile cache, and the device's refusal.  All
on the CPU, which reports no memory: the tests give
``train_step._device_memory`` the numbers a device would."""
import json
import math
import warnings
import os
import sys

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon, parallel
from mxnet_tpu.gluon import nn
from mxnet_tpu.models import (EvaByteLM, LoopedLM, TransformerLM,
                              evabyte_6p5b_config, tiny_config)
from mxnet_tpu.ndarray.ndarray import NDArray
from mxnet_tpu.parallel import train_step as ts
from mxnet_tpu.utils import compile_cache

CHIP = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark", "chip")
if CHIP not in sys.path:
    sys.path.insert(0, CHIP)

import common  # noqa: E402


# ----------------------------------------------------------------------
# three toy steps, each with marked blocks
# ----------------------------------------------------------------------
def _tokens(shape, seed=0, high=256):
    return NDArray(jnp.asarray(onp.random.RandomState(seed).randint(
        0, high, shape), jnp.int32))


def _plain(layers=1):
    mx.np.random.seed(5)
    net = TransformerLM(tiny_config(dim=64, n_heads=2, n_kv_heads=2,
                                    hidden_dim=96, n_layers=layers,
                                    vocab_size=256, max_seq_len=32))
    for blk in net.layers:
        blk.recompute()
    net.initialize()
    tok = _tokens((2, 32))
    step = parallel.TrainStep(
        net, gluon.loss.SoftmaxCrossEntropyLoss(),
        mx.optimizer.SGD(learning_rate=0.1, momentum=0.9), mesh=None)
    return step, tok, tok


def _looped(layers=1, T=32, **over):
    args = dict(dim=64, n_heads=2, n_kv_heads=2, hidden_dim=96,
                n_layers=layers, vocab_size=256, max_seq_len=T,
                sandwich_norm=True, passes=4, dtype="float32")
    args.update(over)
    mx.np.random.seed(5)
    net = LoopedLM(tiny_config(**args))
    net.initialize()
    tok = _tokens((1, T))
    step = parallel.TrainStep(
        net, None, mx.optimizer.AdamW(learning_rate=1e-3), mesh=None,
        forward_fn=lambda net, t, l: net.loss(t, l, chunk=T // 2))
    return step, tok, tok


def _eva(layers=1, window=16, **over):
    args = dict(dim=64, n_layers=layers, n_heads=2, n_kv_heads=2,
                hidden_dim=96, window_size=window, chunk_size=2,
                max_seq_len=4 * window, dtype="float32")
    args.update(over)
    mx.np.random.seed(5)
    net = EvaByteLM(evabyte_6p5b_config(**args))
    net.initialize()
    tok = _tokens((1, 3 * window), high=320)
    lab = _tokens((1, 3 * window, 8), seed=1, high=320)
    step = parallel.TrainStep(
        net, None, mx.optimizer.AdamW(learning_rate=1e-3), mesh=None,
        forward_fn=lambda net, t, l: net.loss(t, l))
    return step, tok, lab


class _Cell(gluon.HybridBlock):
    """Dense, ReLU, dropout, Dense: a marked block with a random op."""

    def __init__(self, dim, rate):
        super().__init__()
        self.up = nn.Dense(2 * dim, flatten=False, in_units=dim)
        self.drop = nn.Dropout(rate)
        self.down = nn.Dense(dim, flatten=False, in_units=2 * dim)

    def forward(self, x):
        return x + self.down(self.drop(mx.npx.relu(self.up(x))))


class _Stack(gluon.HybridBlock):
    def __init__(self, layers, dim, rate):
        super().__init__()
        self.layers = []
        for i in range(layers):
            self.layers.append(_Cell(dim, rate).recompute())
            setattr(self, "layer%d" % i, self.layers[-1])

    def forward(self, x):
        for cell in self.layers:
            x = cell(x)
        return x


def _dropout(layers=3, dim=16, batch=8):
    mx.np.random.seed(5)
    net = _Stack(layers, dim, 0.5)
    net.initialize()
    x = NDArray(jnp.asarray(onp.random.RandomState(0).randn(batch, dim),
                            jnp.float32))
    step = parallel.TrainStep(
        net, gluon.loss.L2Loss(),
        mx.optimizer.SGD(learning_rate=0.1, momentum=0.9), mesh=None)
    return step, x, x


STEPS = {"plain": _plain, "looped": _looped, "eva": _eva,
         "dropout": _dropout}


def _args(step, x, y):
    return step._args((x._data, y._data), 1)


def _products(jaxpr, under=""):
    """``(contraction length, name stack)`` of every ``dot_general`` in
    ``jaxpr`` and the jaxprs its equations hold."""
    out = []
    for e in jaxpr.eqns:
        name = "%s/%s" % (under, e.source_info.name_stack)
        if e.primitive is jax.lax.dot_general_p:
            (contracted, _), _ = e.params["dimension_numbers"]
            out.append((math.prod(e.invars[0].aval.shape[d]
                                  for d in contracted), name))
        for v in e.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    out += _products(sub, name)
    return out


def _made_again(step, x, y, spared, seen=None):
    """The names of the marked blocks whose products the step that
    spares the blocks ``spared`` makes a second time."""
    blocks = dict(ts._marked_blocks(step.net))
    jaxpr = jax.make_jaxpr(step._build(
        (x._data, y._data), [id(blocks[p]) for p in spared], seen))(
        *_args(step, x, y))
    again = [name for _, name in _products(jaxpr.jaxpr)
             if "rematted_computation" in name]
    return sorted(p for p in blocks
                  if any("/%s/" % p.split(".")[-1] in n for n in again)), \
        jaxpr


# ----------------------------------------------------------------------
# (1) a mark has two states: made again, or spared
# ----------------------------------------------------------------------
@pytest.mark.parametrize("kind", ["plain", "eva", "dropout"])
def test_a_spared_block_is_not_made_again_and_the_others_are(kind):
    step, x, y = STEPS[kind](3)
    paths = [p for p, _ in ts._marked_blocks(step.net)]
    assert len(paths) == 3
    seen = {}
    again, _ = _made_again(step, x, y, [], seen)
    assert again == paths
    # the trace met every marked block once, in its own trace and in the
    # order of the forward
    blocks = dict(ts._marked_blocks(step.net))
    assert list(seen) == [id(blocks[p]) for p in paths]
    assert all(own == [True] for own in seen.values())
    assert _made_again(step, x, y, paths[2:])[0] == paths[:2]
    assert _made_again(step, x, y, paths[1:])[0] == paths[:1]
    # ... whichever blocks are named, not only trailing ones
    assert _made_again(step, x, y, paths[:1])[0] == paths[1:]
    again, jaxpr = _made_again(step, x, y, paths)
    assert again == []
    assert "remat" not in str(jaxpr) and "checkpoint" not in str(jaxpr)


def test_a_block_under_a_scan_is_made_again_whatever_the_plan_says():
    step, x, y = _looped(2)
    paths = [p for p, _ in ts._marked_blocks(step.net)]
    seen = {}
    again, _ = _made_again(step, x, y, [], seen)
    assert again == paths and list(seen.values()) == [[False], [False]]
    assert _made_again(step, x, y, paths)[0] == paths
    blocks = dict(ts._marked_blocks(step.net))
    assert step._build((x._data, y._data), [id(b) for b in blocks.values()]) \
        .lower(*_args(step, x, y)).as_text() == step._build(
            (x._data, y._data)).lower(*_args(step, x, y)).as_text()
    # the same blocks in a single pass stand in the step's own trace
    flat, fx, fy = _looped(2, passes=1)
    seen = {}
    assert _made_again(flat, fx, fy, paths[1:], seen)[0] == paths[:1]
    assert list(seen.values()) == [[True], [True]]


def test_a_marked_block_inside_a_marked_block_is_the_outer_ones_to_keep():
    step, x, y = _dropout(2)
    for cell in step.net.layers:
        cell.up.recompute()
    paths = [p for p, _ in ts._marked_blocks(step.net)]
    assert paths == ["layer0", "layer0.up", "layer1", "layer1.up"]
    seen = {}
    _made_again(step, x, y, [], seen)
    blocks = dict(ts._marked_blocks(step.net))
    assert [seen[id(blocks[p])] for p in paths] == [
        [True], [False], [True], [False]]


def test_outside_a_training_step_a_marked_block_is_made_again():
    # a hybridized parent under autograd.record: no driver, no plan
    step, x, y = _dropout(2)
    blocks = dict(ts._marked_blocks(step.net))

    def f(x):
        from mxnet_tpu import _tape
        _tape.set_training(True)
        try:
            return step.net(NDArray(x))._data.sum()
        finally:
            _tape.set_training(False)
    jaxpr = jax.make_jaxpr(jax.grad(f))(x._data)
    assert str(jaxpr).count("prevent_cse") >= len(blocks)


# ----------------------------------------------------------------------
# (2) values and random draws do not change with the plan
# ----------------------------------------------------------------------
def _device_with(monkeypatch, room):
    """A device that leaves the step's temporaries and code ``room``
    bytes (limit - in use - reserve)."""
    monkeypatch.setattr(ts, "_device_memory", lambda device: (
        (1 << 40) + ts._RESERVE_BYTES, (1 << 40) - room))


def _run(make, n=2):
    step, x, y = make()
    losses = [float(step(x, y)) for _ in range(n)]
    return losses, step._states, {
        k: p.data()._data for k, p in step.net.collect_params().items()}, \
        step


@pytest.mark.parametrize("kind,fit", [
    ("eva", "some"), ("eva", "all"), ("plain", "some"), ("plain", "all"),
    ("dropout", "all"), ("looped", "all")])
def test_every_plan_gives_the_unplanned_step_to_the_bit(
        monkeypatch, interpret_kernels, kind, fit):
    def make():
        if kind in ("plain", "dropout"):
            return STEPS[kind](3)
        return _looped(2, T=128, dim=128) if kind == "looped" \
            else _eva(3, window=128, dim=128)
    loss0, states0, params0, step0 = _run(make)
    assert step0.recompute_plan is None and step0._planned == {}
    room = 1 << 30
    _device_with(monkeypatch, room)
    loss, states, params, step = _run(make)
    plan = step.recompute_plan
    paths = [p for p, _ in ts._marked_blocks(step.net)]
    if kind == "looped":
        # nothing under the scan can be spared, and nothing is compiled
        # to find that out
        assert plan["spared"] == [] and plan["compiles"] == 1
        assert plan["made_again"] == paths
    else:
        # everything fits: no block is made again
        assert plan["spared"] == paths and plan["compiles"] == 2
    if fit == "some":
        # room for less than the step that spares every block reads
        room = (plan["temp_bytes_rung0"] + plan["temp_bytes"]) // 2
        _device_with(monkeypatch, room)
        loss, states, params, step = _run(make)
        plan = step.recompute_plan
        assert plan["spared"] == paths[len(paths) - len(plan["spared"]):]
        assert 0 < len(plan["spared"]) < 3 == plan["compiles"]
        assert plan["made_again"] == paths[:len(paths) - len(plan["spared"])]
    assert plan["temp_bytes"] <= room      # as compiled
    assert plan["free_bytes"] == room - plan["temp_bytes_rung0"]

    def same(a, b, name):
        if kind in ("plain", "dropout"):
            # XLA fuses a block it is free to schedule whole otherwise:
            # the same operations and the same masks, to rounding
            onp.testing.assert_allclose(a, b, rtol=2e-5, atol=1e-7,
                                        err_msg=name)
        else:
            assert bool(jnp.all(a == b)), name

    same(jnp.asarray(loss), jnp.asarray(loss0), "loss")
    for name, st in states0.items():
        for a, b in zip(st, states[name]):
            same(a, b, name)
    for name, a in params0.items():
        same(a, params[name], name)


class _Mask(gluon.HybridBlock):
    """Adds ``scale`` times a dropout mask of ones: the masks of a stack
    of these can be read off its output digit by digit."""

    def __init__(self, scale):
        super().__init__()
        self.scale, self.drop = scale, nn.Dropout(0.5)

    def forward(self, x):
        return x + self.scale * self.drop(mx.np.ones_like(x))


def test_a_spared_block_draws_the_keys_it_would_have_drawn():
    from mxnet_tpu import _tape
    from mxnet_tpu.gluon.block import keeping
    from mxnet_tpu.numpy import random as _random
    cells = [_Mask(4 ** i).recompute() for i in range(3)]

    def masks(spared):
        def forward(key):
            with _random.trace_scope(key), keeping(
                    [id(cells[i]) for i in spared]):
                _tape.set_training(True)
                try:
                    x = NDArray(jnp.zeros((4, 32)) + key[0] * 0)
                    for cell in cells:
                        x = cell(x)
                    # a draw after the blocks: the stream they leave
                    return x._data, mx.np.random.uniform(size=(3,))._data
                finally:
                    _tape.set_training(False)
        total, after = jax.jit(forward)(jax.random.PRNGKey(7))
        digits = onp.asarray(total).astype(int) // 2    # a kept one is 2
        return [(digits // 4 ** i) % 4 for i in range(3)] + [
            onp.asarray(after)]
    want = masks([])
    assert all(set(onp.unique(m)) == {0, 1} for m in want[:3])
    assert not (want[0] == want[1]).all()
    for spared in ([2], [1, 2], [0, 1, 2], [0]):
        for a, b in zip(want, masks(spared)):
            assert (a == b).all(), spared


# ----------------------------------------------------------------------
# (3) the search, a pure function of (readings, candidates, room)
# ----------------------------------------------------------------------
@pytest.mark.parametrize("readings,n,room,want", [
    ({0: 10}, 4, 9, None),                  # no room: nothing is tried
    ({0: 10}, 4, 10, 4),                    # everything first
    ({0: 10}, 0, 100, None),                # nothing that could be spared
    ({0: None}, 4, 100, None),
    ({0: 10, 4: 90}, 4, 100, None),         # it fits: done
    ({0: 10, 4: 210}, 4, 100, 1),           # 50 a block: 1.8 of them
    ({0: 10, 4: 130}, 4, 100, 3),           # never what was read already
    ({0: 10, 4: 210, 1: 20}, 4, 100, 2),    # from the largest that fits
    ({0: 10, 4: 210, 1: 20, 2: 101}, 4, 100, None),
    ({0: 10, 4: None}, 4, 100, 2),          # no reading: the midpoint
    ({0: 10, 4: None, 2: 50}, 4, 100, 3),
    ({0: 10, 4: None, 2: 150}, 4, 100, 1),
    ({0: 10, 1: 8, 2: 300}, 2, 100, None),  # the first block spared is free
    # evabyte_6p5b_train_1x8192 (v5e compile, PR 33)
    ({0: 4376931328}, 4, 6513000000, 4),
    ({0: 4376931328, 4: 6890836000}, 4, 6513000000, 3),
    ({0: 4376931328, 4: 6890836000, 3: 6084797000}, 4, 6513000000, None)])
def test_the_search_tries_everything_then_what_a_straight_line_says(
        readings, n, room, want):
    assert ts.blocks_to_spare(readings, n, room) == want


@pytest.mark.parametrize("seed", range(6))
def test_the_search_ends_within_its_compiles_on_what_fits(seed):
    rng = onp.random.RandomState(seed)
    n = int(rng.randint(1, 40))
    need = onp.concatenate([[100.0], 100 + onp.cumsum(
        onp.sort(rng.uniform(0, 30, n)))])     # dearer block by block
    room = float(rng.uniform(90, need[-1] + 20))
    readings = {0: need[0]}
    while len(readings) < ts._COMPILES:
        k = ts.blocks_to_spare(readings, n, room)
        if k is None:
            break
        assert 0 < k <= n and k not in readings
        readings[k] = need[k]
    fit = [k for k, v in readings.items() if v <= room]
    if need[0] > room:
        assert list(readings) == [0]
    elif need[-1] <= room:
        assert max(fit) == n and len(readings) == 2
    else:
        assert fit and need[max(fit)] <= room


# ----------------------------------------------------------------------
# (4) where nothing is known, nothing changes
# ----------------------------------------------------------------------
def _no_plan(monkeypatch):
    def refuse(*_):
        raise AssertionError("a plan was made")
    monkeypatch.setattr(parallel.TrainStep, "_plan", refuse)


def test_a_step_with_no_marked_block_is_not_planned(monkeypatch):
    def make():
        mx.np.random.seed(3)
        step, x, y = _plain(2)
        for blk in step.net.layers:
            blk.recompute(False)
        return step, x, y
    step, x, y = make()
    text = step.lower(x, y).as_text()
    _device_with(monkeypatch, 1 << 30)      # a device that would report
    _no_plan(monkeypatch)
    step, x, y = make()
    assert step.lower(x, y).as_text() == text
    step(x, y)
    assert step.recompute_plan is None and step._planned == {}
    assert step._jitted._cache_size() == 1   # compiled by its first call
    wide = _tokens((4, 32))
    step(wide, wide)
    step(x, y)
    assert step._jitted._cache_size() == 2   # jit's own program a shape


@pytest.mark.parametrize("kind", ["looped", "eva"])
def test_a_device_without_memory_stats_is_not_planned_for(monkeypatch, kind):
    # the CPU reports nothing: every marked block is made again
    assert ts._device_memory(jax.devices()[0]) is None
    step, x, y = STEPS[kind](2)
    _no_plan(monkeypatch)
    text = step.lower(x, y).as_text()
    assert "optimization_barrier" in text
    step(x, y)
    assert step.recompute_plan is None and step._planned == {}
    # and the step a plan is measured from lowers to that text too
    other, x, y = STEPS[kind](2)
    assert other._build((x._data, y._data), (), {}).lower(
        *_args(other, x, y)).as_text() == text


def test_a_mesh_or_a_described_topology_is_not_planned_for(monkeypatch):
    _device_with(monkeypatch, 1 << 30)
    step, x, y = _eva(1)
    assert step._device_to_plan_for() is jax.devices()[0]
    step.mesh = parallel.create_mesh(dp=1, devices=jax.devices()[:1])
    assert step._device_to_plan_for() is None
    step.mesh, step.aot = None, True
    assert step._device_to_plan_for() is None


def test_stable_locations_leave_the_call_stack_out_and_put_it_back():
    name = "jax_traceback_in_locations_limit"
    was = getattr(jax.config, name)
    assert was != 1
    with compile_cache.stable_locations():
        assert getattr(jax.config, name) == 1
    assert getattr(jax.config, name) == was
    with pytest.raises(KeyError):
        with compile_cache.stable_locations():
            raise KeyError
    assert getattr(jax.config, name) == was
    # the scopes a trace is read by are not locations: they stay
    import re
    step, x, y = _plain(1)

    def names():
        return re.findall(r'op_name="([^"]*)"', step._build(
            (x._data, y._data)).lower(*_args(step, x, y)).compile().as_text())
    want = names()
    with compile_cache.stable_locations():
        assert names() == want
    assert any("/layer0/" in n for n in want)


# ----------------------------------------------------------------------
# (5) the plan file beside the compile cache
# ----------------------------------------------------------------------
@pytest.fixture()
def plan_dir(tmp_path, monkeypatch):
    monkeypatch.setattr(compile_cache, "cache_dir_in_force",
                        lambda: str(tmp_path))
    return tmp_path


def _plan_files(plan_dir):
    return sorted(p for p in os.listdir(str(plan_dir))
                  if p.startswith("mx_recompute_plan_"))


@pytest.mark.parametrize("kind", ["eva", "looped"])
def test_a_start_that_finds_a_fitting_plan_compiles_one_program(
        monkeypatch, plan_dir, kind):
    make = STEPS[kind]
    _device_with(monkeypatch, 1 << 30)
    counter = common.CompileCounter()
    _, x, y = make(2)               # the tokens' own small programs
    step, x, y = make(2)
    before = counter.n
    loss = float(step(x, y))
    cold = step.recompute_plan
    assert cold["from_file"] is False
    # a block under the scan cannot be spared: nothing is compiled for it
    assert cold["compiles"] == (2 if kind == "eva" else 1)
    assert counter.n - before == cold["compiles"]
    (name,) = _plan_files(plan_dir)
    stored = json.load(open(os.path.join(str(plan_dir), name)))
    assert stored["spared"] == cold["spared"] == (
        ["layer0", "layer1"] if kind == "eva" else [])
    assert name == "mx_recompute_plan_%s.json" % stored["key"][:32]

    step, x, y = make(2)
    before = counter.n
    assert float(step(x, y)) == loss
    warm = step.recompute_plan
    assert counter.n - before == 1 == warm["compiles"]
    assert warm["from_file"] is True and warm["spared"] == cold["spared"]
    assert warm["temp_bytes"] == cold["temp_bytes"]
    assert warm["free_bytes"] == cold["free_bytes"]
    float(step(x, y))
    assert counter.n - before == 1          # and no other afterwards
    assert _plan_files(plan_dir) == [name]

    # other shapes, another key: a plan of its own
    step, x, y = make(2, **({"window": 32} if kind == "eva" else {"T": 64}))
    step(x, y)
    assert step.recompute_plan["from_file"] is False
    assert len(_plan_files(plan_dir)) == 2


def test_a_plan_made_by_other_library_code_is_not_read(monkeypatch,
                                                      plan_dir):
    # two versions of the library may share one cache directory (a
    # parent and its change, run in turn): a block of the other keeps
    # other values, so its plan is no hint
    _device_with(monkeypatch, 1 << 30)
    step, x, y = STEPS["eva"](2)
    step(x, y)
    assert len(_plan_files(plan_dir)) == 1
    assert len(ts._library_digest()) == 64
    monkeypatch.setattr(ts, "_library_digest", lambda: "another")
    step, x, y = STEPS["eva"](2)
    step(x, y)
    assert step.recompute_plan["from_file"] is False
    assert len(_plan_files(plan_dir)) == 2


def test_the_plan_nests_its_tries_as_build_spans(monkeypatch, plan_dir):
    # with no profiler running: plan > a trace and a compile a step
    # tried, inside the build span of the first call with a signature
    from mxnet_tpu import profiler
    assert profiler.state() == "stop"
    _device_with(monkeypatch, 1 << 30)

    def start(*batches):
        profiler.reset()
        step, x, y = _eva(2)
        for _ in batches or (0,):
            step(x, y)
        spans = profiler.build_spans()
        return step, spans, lambda name: [s for s in spans
                                          if s["name"] == name]

    step, spans, named = start(0, 1)    # the second call builds nothing
    (build,), (plan,) = named("mx.train.step.build"), \
        named("mx.train.step.plan")
    assert spans[plan["parent"]] is build
    traces, compiles = named("mx.train.step.trace"), \
        named("mx.train.step.compile")
    assert [t["args"]["program"] for t in traces] \
        == [c["args"]["program"] for c in compiles] \
        == ["step.spare0", "step.spare2"]
    for t, c in zip(traces, compiles):
        assert spans[t["parent"]] is plan and spans[c["parent"]] is plan
        assert t["t1"] <= c["t0"]                   # lowered, then compiled
        assert t["args"]["trace_s"] > 0 and t["args"]["lower_s"] > 0
        assert t["args"]["compiles"] + t["args"]["cache_loads"] == 0
        assert c["args"]["compiles"] + c["args"]["cache_loads"] == 1
        assert c["args"]["from_cache"] == bool(c["args"]["cache_loads"])
    # the plan's own record stands in its arguments: the programs it
    # tried; what jax made of them is the build span's
    assert plan["args"]["compiles"] == step.recompute_plan["compiles"] == 2
    assert plan["args"]["from_file"] is False
    args = build["args"]
    assert args["compiles"] + args["cache_loads"] == 2
    assert args["trace_s"] == pytest.approx(
        sum(t["args"]["trace_s"] for t in traces))
    assert 0 < args["compile_s"] + args["cache_load_s"] \
        <= sum(c["t1"] - c["t0"] for c in compiles)

    # the next start finds the plan: one program
    step, spans, named = start()
    (plan,) = named("mx.train.step.plan")
    assert plan["args"]["from_file"] is True and plan["args"]["compiles"] == 1
    (trace,), (compiled,) = named("mx.train.step.trace"), \
        named("mx.train.step.compile")
    assert trace["args"]["program"] == "step.spare2"
    (build,) = named("mx.train.step.build")
    assert build["args"]["compiles"] + build["args"]["cache_loads"] == 1

    # a new signature appends one build, a signature that comes back none
    x, y = _tokens((2, 48), seed=1, high=320), _tokens((2, 48, 8), seed=2,
                                                       high=320)
    small = step._batch_arrays((_tokens((1, 48), high=320),
                                _tokens((1, 48, 8), high=320)))
    step(x, y)
    assert len([s for s in profiler.build_spans()
                if s["name"] == "mx.train.step.build"]) == 2
    step(*[NDArray(a) for a in small])
    step(x, y)
    builds = [s for s in profiler.build_spans()
              if s["name"] == "mx.train.step.build"]
    assert [b["args"]["signature"] for b in builds] \
        == ["1x48:int32 1x48x8:int32", "2x48:int32 2x48x8:int32"]
    profiler.reset()


def test_a_plan_that_no_longer_fits_is_made_again_and_overwritten(
        monkeypatch, plan_dir):
    _device_with(monkeypatch, 1 << 30)
    step, x, y = _eva(3)
    loss = float(step(x, y))
    roomy = step.recompute_plan
    (name,) = _plan_files(plan_dir)
    # the same device with less of it free: the key holds, the file's
    # plan does not fit the compiled step, a smaller one is made
    room = (roomy["temp_bytes_rung0"] + roomy["temp_bytes"]) // 2
    _device_with(monkeypatch, room)
    step, x, y = _eva(3)
    assert float(step(x, y)) == loss
    tight = step.recompute_plan
    assert tight["from_file"] is False and tight["compiles"] == 3
    assert tight["temp_bytes"] <= room < roomy["temp_bytes"]
    assert len(tight["spared"]) < len(roomy["spared"])
    assert _plan_files(plan_dir) == [name]
    stored = json.load(open(os.path.join(str(plan_dir), name)))
    assert stored["spared"] == tight["spared"]
    # a file that is not a plan is no hint, and is replaced; nor is one
    # that names blocks the step does not have
    for content in ("{", json.dumps(dict(stored, candidates=["gone"],
                                         spared=[]))):
        with open(os.path.join(str(plan_dir), name), "w") as f:
            f.write(content)
        step, x, y = _eva(3)
        step(x, y)
        assert step.recompute_plan["from_file"] is False
        assert json.load(open(os.path.join(str(plan_dir), name)))[
            "spared"] == tight["spared"]


def test_no_cache_directory_no_plan_file(monkeypatch, tmp_path):
    monkeypatch.setattr(compile_cache, "cache_dir_in_force", lambda: None)
    monkeypatch.chdir(tmp_path)
    _device_with(monkeypatch, 1 << 30)
    step, x, y = _eva(1)
    step(x, y)
    assert step.recompute_plan["spared"] == ["layer0"]
    assert os.listdir(str(tmp_path)) == []


# ----------------------------------------------------------------------
# (6) a plan a batch signature, each made once
# ----------------------------------------------------------------------
def test_a_shape_that_comes_back_runs_what_it_ran_before(monkeypatch):
    _device_with(monkeypatch, 1 << 30)
    monkeypatch.setattr(compile_cache, "cache_dir_in_force", lambda: None)
    counter = common.CompileCounter()
    step, a, la = _eva(2)
    b, lb = _tokens((2, 48), high=320), _tokens((2, 48, 8), seed=1, high=320)
    float(step(a, la)), float(step(b, lb))      # (and the losses' reads)
    spent = []
    for x, y in ((a, la), (b, lb), (a, la), (b, lb)):
        before = counter.n
        float(step(x, y))
        spent.append(counter.n - before)
    assert spent == [0, 0, 0, 0]
    assert sorted(step._planned) == sorted(
        ts._signature((x._data, y._data)) for x, y in ((a, la), (b, lb)))
    # from a fresh step: two compiles a shape, once
    step, a, la = _eva(2)
    spent = []
    for x, y in ((a, la), (b, lb), (a, la), (b, lb)):
        before = counter.n
        step(x, y)
        spent.append(counter.n - before)
    assert spent == [2, 2, 0, 0]
    assert [p.plan["compiles"] for p in step._planned.values()] == [2, 2]


def test_lower_lowers_the_program_that_runs(monkeypatch):
    _device_with(monkeypatch, 1 << 30)
    step, x, y = _eva(2)
    rung0 = step._build((x._data, y._data)).lower(
        *_args(step, x, y)).as_text()
    planned = step.lower(x, y)           # plans, as the first call would
    assert step.recompute_plan["spared"] == ["layer0", "layer1"]
    assert planned.as_text() != rung0
    ma = planned.compile().memory_analysis()
    assert ma.temp_size_in_bytes + ma.generated_code_size_in_bytes \
        == step.recompute_plan["temp_bytes"]
    made = step._planned[ts._signature((x._data, y._data))]
    step(x, y)                           # and the call runs that plan
    assert step._planned[ts._signature((x._data, y._data))] is made
    assert step.compile(x, y) is step


# ----------------------------------------------------------------------
# (7) the device has the last word
# ----------------------------------------------------------------------
def _refusing(run, times, error=ValueError):
    left = [times]

    def refuse(*args):
        if left[0]:
            left[0] -= 1
            # (a v5e's words, and its exception: a plain ValueError)
            raise error(
                "RESOURCE_EXHAUSTED: Error loading program 'jit_step': "
                "Attempting to reserve 5.41G at the bottom of memory. That "
                "was not possible. There are 5.03G free")
        return run(*args)
    return refuse


@pytest.mark.parametrize("calls_before,error", [
    (0, ValueError), (2, ValueError), (0, jax.errors.JaxRuntimeError)])
def test_a_planned_step_the_device_refuses_is_planned_again(
        monkeypatch, plan_dir, calls_before, error):
    # memory_analysis() is all the plan can read, and the caller may put
    # more on the device later; where the device refuses the program
    # (before it runs: the arguments are still there) fewer blocks are
    # spared, and the trace and a warning say so
    _device_with(monkeypatch, 1 << 30)
    ref, x, y = _eva(3)
    want = [float(ref(x, y)) for _ in range(calls_before + 2)]
    step, x, y = _eva(3)
    got = [float(step(x, y)) for _ in range(calls_before)]
    step.lower(x, y)                      # plans, if no call has
    sig = ts._signature((x._data, y._data))
    first = step._planned[sig]
    assert len(first.plan["spared"]) == 3
    first.run = _refusing(first.run, 1, error)
    spans = []
    real = ts._profiler.span

    class Span:
        def __init__(self, name):
            self.name, self.args, self.inner = name, {}, real(name)

        def __enter__(self):
            self.inner.__enter__()
            spans.append(self)
            return self

        def __exit__(self, *exc):
            return self.inner.__exit__(*exc)

        def set(self, **kw):
            self.args.update(kw)
    monkeypatch.setattr(ts._profiler, "span", Span)
    with pytest.warns(UserWarning, match="refused the training step"):
        got.append(float(step(x, y)))
    dispatch, = [s for s in spans if s.name == "mx.train.step.dispatch"]
    assert dispatch.args == {"refused": 3}
    plan = step.recompute_plan
    second = step._planned[sig]
    assert second is not first and second.readings[3] is None
    assert 0 < len(plan["spared"]) < 3 and plan["from_file"] is False
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        got.append(float(step(x, y)))
    onp.testing.assert_allclose(got, want, rtol=1e-6)
    (name,) = _plan_files(plan_dir)
    stored = json.load(open(os.path.join(str(plan_dir), name)))
    if calls_before:
        # it had run: the caller took the memory since, and the next
        # start finds the plan a start should find
        assert stored["spared"] == first.plan["spared"]
        assert stored["readings"]["3"] == first.readings[3]
    else:
        # it never ran: the reading was wrong, and the file says so
        assert stored["spared"] == plan["spared"]
        assert stored["readings"]["3"] is None
    # refused again and again it ends on the step every block is made
    # again in, and what that one raises is raised
    second.run = _refusing(second.run, 1 << 30)
    for planned in range(4):
        try:
            with warnings.catch_warnings():
                warnings.simplefilter("ignore")
                step(x, y)
        except ValueError:
            break
        step._planned[sig].run = _refusing(step._planned[sig].run, 1 << 30)
    assert step.recompute_plan["spared"] == []


def test_only_a_refusal_for_memory_with_the_arguments_there_is_caught(
        monkeypatch):
    _device_with(monkeypatch, 1 << 30)
    step, x, y = _eva(2)
    step(x, y)
    planned, = step._planned.values()

    for kind in (jax.errors.JaxRuntimeError, ValueError):
        def other(*args):
            raise kind("INTERNAL: something else")
        planned.run = other
        with pytest.raises(kind, match="INTERNAL"):
            step(x, y)

    def late(*args):
        for a in jax.tree_util.tree_leaves(args[:2]):
            a.delete()
        raise jax.errors.JaxRuntimeError("RESOURCE_EXHAUSTED: mid-run")
    planned.run = late
    with pytest.raises(jax.errors.JaxRuntimeError, match="mid-run"):
        step(x, y)
