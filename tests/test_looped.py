"""The looped decoder (``mx.models.LoopedLM``), recomputation by block
(``Block.recompute``) and the head whose logits are never whole
(``chunked_softmax_cross_entropy`` and, for a loss that is a weighted
sum, ``weighted_chunked_softmax_cross_entropy``), at toy widths on the
CPU, against
the plain reference the benchmark keeps
(``benchmark/chip/reference/looped_decoder.py``, which imports nothing of
``mxnet_tpu``)."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon, parallel
from mxnet_tpu.models import (LoopedLM, TransformerLM, exit_log_probs,
                              expected_exit_loss, ouro_2p6b_config,
                              tiny_config)
from mxnet_tpu.ndarray.ndarray import NDArray, apply_op
from mxnet_tpu.ops.nn import (chunked_softmax_cross_entropy,
                              weighted_chunked_softmax_cross_entropy)

CHIP = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark", "chip")
if CHIP not in sys.path:
    sys.path.insert(0, CHIP)

import common  # noqa: E402
from builders.looped_decoder import _program_name  # noqa: E402
from reference import looped_decoder as ref  # noqa: E402

MODEL = {"param_dtype": "float32", "init_std": 0.02, "hidden_size": 64,
         "intermediate_size": 128, "vocab_size": 256, "head_dim": 16,
         "num_attention_heads": 4, "num_key_value_heads": 4,
         "num_hidden_layers": 2, "rms_norm_eps": 1e-6, "rope_theta": 1e6,
         "total_ut_steps": 4, "beta": 0.05,
         "optimizer": {"learning_rate": 3e-4, "beta1": 0.9, "beta2": 0.95,
                       "epsilon": 1e-8, "wd": 0.1}}
B, T, CHUNK = 2, 32, 24          # 64 tokens in chunks of 24: 24, 24, 16


def _config(**over):
    args = dict(vocab_size=256, dim=64, n_layers=2, n_heads=4, n_kv_heads=4,
                hidden_dim=128, rope_theta=1e6, norm_eps=1e-6,
                sandwich_norm=True, passes=4, dtype="float32")
    args.update(over)
    return tiny_config(**args)


def _net(weights, cls=LoopedLM, **over):
    net = cls(_config(**over))
    ps = net.collect_params()
    for name, value in weights.items():
        ps[_program_name(name)].set_data(NDArray(value))
    return net


def _batch(seed=0):
    ids = onp.random.RandomState(seed).randint(0, 256, (B, T + 1))
    return jnp.asarray(ids[:, :-1]), jnp.asarray(ids[:, 1:])


def _adamw():
    o = MODEL["optimizer"]
    return mx.optimizer.AdamW(learning_rate=o["learning_rate"],
                              beta1=o["beta1"], beta2=o["beta2"],
                              epsilon=o["epsilon"], wd=o["wd"])


def _step(net, chunk=CHUNK, **kw):
    return parallel.TrainStep(
        net, None, _adamw(), mesh=None, forward_fn=lambda net, t, l:
        net.loss(t, l, beta=MODEL["beta"], chunk=chunk), **kw)


def _gradients(step):
    """Every parameter's gradient of a step's one call, read from Adam's
    first moment: ``m1 = (1 - beta1) g``."""
    return {n: st[0] / (1 - MODEL["optimizer"]["beta1"])
            for n, st in step._states.items()}


def _first_gradients(step, tok, lab):
    """The loss and every parameter's gradient of one step."""
    loss = float(step(NDArray(tok), NDArray(lab)))
    return loss, _gradients(step)


@pytest.fixture(scope="module")
def weights():
    return common.make_weights(7, ref.leaf_specs(MODEL))


@pytest.fixture(scope="module")
def reference_step(weights):
    tok, lab = _batch()
    return ref.make_step(MODEL)(weights, None, tok, lab)


# ----------------------------------------------------------------------
# the model against the plain reference, float32.  Tolerances: both
# sides compute in float32 but sum in different orders (flash-style
# dense attention against a masked softmax, chunked logsumexp against
# log_softmax, log-space exit probabilities against products), four
# passes deep: 1e-5 relative is ten float32 roundings of a unit value
# ----------------------------------------------------------------------
def test_every_pass_logits_match_the_reference(weights):
    tok, _ = _batch()
    got = jnp.stack([o._data for o in
                     _net(weights).exit_logits(NDArray(tok))], axis=1)
    want = ref.logits(MODEL, weights, tok)
    assert got.shape == want.shape == (B, 4, T, 256)
    onp.testing.assert_allclose(got, want, rtol=0, atol=2e-5)


def test_exit_parts_and_loss_match_the_reference(weights, reference_step):
    tok, lab = _batch()
    loss, parts, _, _, _ = reference_step
    net = _net(weights)
    ce, log_p = net.exit_parts(NDArray(tok), NDArray(lab), chunk=CHUNK)
    assert ce.shape == log_p.shape == (4, B * T)
    onp.testing.assert_allclose(ce._data.mean(1), parts["ce"], rtol=1e-5)
    onp.testing.assert_allclose(jnp.exp(log_p._data).mean(1), parts["p"],
                                rtol=1e-5)
    onp.testing.assert_allclose(jnp.exp(log_p._data).sum(0), 1.0, rtol=1e-6)
    got = net.loss(NDArray(tok), NDArray(lab), beta=MODEL["beta"],
                   chunk=CHUNK)
    onp.testing.assert_allclose(float(got), float(loss), rtol=1e-5)


def test_every_leaf_gradient_matches_the_reference(weights, reference_step):
    tok, lab = _batch()
    loss, _, grads, new_params, _ = reference_step
    net = _net(weights)
    got_loss, got = _first_gradients(_step(net), tok, lab)
    onp.testing.assert_allclose(got_loss, float(loss), rtol=1e-5)
    ps = net.collect_params()
    assert set(_program_name(k) for k in grads) == set(got)
    for name, want in grads.items():
        g = got[_program_name(name)]
        # a leaf's gradient as a whole: the norm of the difference over
        # the norm, 2e-5 for sums over 64 tokens x 4 passes in float32
        assert float(jnp.linalg.norm(g - want)
                     / jnp.linalg.norm(want)) < 2e-5, name
        # and AdamW's first step lands where the reference's does: a
        # step is lr = 3e-4 times g / (|g| + 1e-8), which float32
        # rounding moves by a hundredth of a step where |g| is near 1e-8
        onp.testing.assert_allclose(ps[_program_name(name)].data()._data,
                                    new_params[name], rtol=0, atol=5e-6)


def test_the_step_hands_the_exits_through_as_aux(weights):
    """``forward_fn`` may return ``(loss, aux)``: the step then returns
    both, the aux untouched by the gradient, and steps as it did."""
    tok, lab = _batch()
    plain, logged = _net(weights), _net(weights)
    want = float(_step(plain)(NDArray(tok), NDArray(lab)))
    ce, log_p = _net(weights).exit_parts(NDArray(tok), NDArray(lab),
                                         chunk=CHUNK)
    step = parallel.TrainStep(
        logged, None, _adamw(), mesh=None, forward_fn=lambda net, t, l:
        net.loss(t, l, beta=MODEL["beta"], chunk=CHUNK, exits=True))
    loss, exits = step(NDArray(tok), NDArray(lab))
    assert float(loss) == want
    assert isinstance(exits["ce"], NDArray) and exits["ce"].shape == (4,)
    onp.testing.assert_allclose(exits["ce"]._data, ce._data.mean(1),
                                rtol=1e-6)
    onp.testing.assert_allclose(exits["p"]._data,
                                jnp.exp(log_p._data).mean(1), rtol=1e-6)
    for (name, p), q in zip(plain.collect_params().items(),
                            logged.collect_params().values()):
        assert bool(jnp.all(p.data()._data == q.data()._data)), name


def _loss_from_parts(net, tokens, labels, exits):
    """The loss as its parts spell it: per-token cross-entropies whose
    cotangents the backward brings (the head's logits made again)."""
    ce, log_p = net.exit_parts(tokens, labels, chunk=CHUNK)
    loss = apply_op(lambda c, lp: expected_exit_loss(c, lp, MODEL["beta"]),
                    [ce, log_p])
    if not exits:
        return loss
    return loss, {"ce": apply_op(lambda c: c.mean(1), [ce]),
                  "p": apply_op(lambda lp: jnp.exp(lp).mean(1), [log_p])}


@pytest.mark.parametrize("exits", [False, True], ids=["loss", "with_exits"])
def test_the_loss_is_the_expected_exit_loss_of_its_parts(weights, exits):
    """``loss`` forms the head's gradients in the forward, from weights
    the gate has already made: value, aux and every leaf's gradient are
    those of ``expected_exit_loss(*exit_parts(...))``."""
    tok, lab = _batch()
    got, want = {}, {}
    for out, forward in (
            (got, lambda net, t, l: net.loss(
                t, l, beta=MODEL["beta"], chunk=CHUNK, exits=exits)),
            (want, lambda net, t, l: _loss_from_parts(net, t, l, exits))):
        step = parallel.TrainStep(_net(weights), None, _adamw(), mesh=None,
                                  forward_fn=forward)
        res = step(NDArray(tok), NDArray(lab))
        out["loss"], out["aux"] = res if exits else (res, {})
        out["grads"] = _gradients(step)
    onp.testing.assert_allclose(float(got["loss"]), float(want["loss"]),
                                rtol=1e-6)
    assert set(got["aux"]) == set(want["aux"]) == \
        ({"ce", "p"} if exits else set())
    for k, v in want["aux"].items():
        assert got["aux"][k].shape == (4,)
        onp.testing.assert_allclose(got["aux"][k]._data, v._data, rtol=1e-6)
    assert set(got["grads"]) == set(want["grads"])
    for name, v in want["grads"].items():
        # the same float32 products summed in another order
        assert float(jnp.linalg.norm(got["grads"][name] - v)
                     / jnp.linalg.norm(v)) < 1e-5, name


def test_the_eager_tape_gives_the_traced_steps_gradients(weights):
    tok, lab = _batch()
    loss, traced = _first_gradients(_step(_net(weights)), tok, lab)
    net = _net(weights)
    with mx.autograd.record():
        eager = net.loss(NDArray(tok), NDArray(lab), beta=MODEL["beta"],
                         chunk=CHUNK)
    eager.backward()
    onp.testing.assert_allclose(float(eager), loss, rtol=1e-6)
    for name, p in net.collect_params().items():
        assert float(jnp.linalg.norm(p.grad()._data - traced[name])
                     / jnp.linalg.norm(traced[name])) < 1e-5, name


def test_a_shared_weight_gets_the_sum_of_its_four_uses(weights):
    """The same network with the loop written out: four copies of the
    two layers, unshared.  Every copy's gradient, summed, is the shared
    weight's."""
    tok, lab = _batch()

    class Unrolled(LoopedLM):
        def hidden_states(self, tokens):
            h, states = self._embed(tokens), []
            for i, blk in enumerate(self.layers):
                h = blk(h)
                if i % 2 == 1:
                    h = self.norm(h)
                    states.append(h)
            return states

    copies = dict(weights)
    for t in range(1, 4):
        for k, v in weights.items():
            if k.startswith("layer"):
                layer, leaf = k.split(".")
                copies["layer%d.%s" % (2 * t + int(layer[5:]), leaf)] = v
    _, shared = _first_gradients(_step(_net(weights)), tok, lab)
    _, apart = _first_gradients(
        _step(_net(copies, cls=Unrolled, n_layers=8, passes=1)), tok, lab)
    for name, g in shared.items():
        if name.startswith("layer"):
            layer, leaf = name.split(".", 1)
            want = sum(apart["layer%d.%s" % (2 * t + int(layer[5:]), leaf)]
                       for t in range(4))
        else:
            want = apart[name]
        assert float(jnp.linalg.norm(g - want)
                     / jnp.linalg.norm(want)) < 1e-5, name


def test_parameters_do_not_depend_on_the_number_of_passes():
    shapes = [{n: p.shape for n, p in
               LoopedLM(_config(passes=k)).collect_params().items()}
              for k in (1, 4)]
    assert shapes[0] == shapes[1]
    assert shapes[0]["exit_gate.weight"] == (1, 64)
    assert "layer0.attention_post_norm.gamma" in shapes[0]


def test_one_pass_without_the_sandwich_is_transformer_lm_to_the_bit():
    cfg = _config(passes=1, sandwich_norm=False)
    mx.np.random.seed(3)
    plain = TransformerLM(cfg)
    plain.initialize()
    tok, lab = _batch()
    plain(NDArray(tok))
    looped = LoopedLM(cfg)
    looped.initialize()
    lp = looped.collect_params()
    for name, p in plain.collect_params().items():
        lp[name].set_data(p.data())
    assert set(lp) - set(plain.collect_params()) == \
        {"exit_gate.weight", "exit_gate.bias"}
    want = plain(NDArray(tok))._data
    assert bool(jnp.all(looped(NDArray(tok))._data == want))
    # one exit takes all the probability: the loss is the cross-entropy
    ce = gluon.loss.SoftmaxCrossEntropyLoss()(
        NDArray(want.reshape(-1, 256)), NDArray(lab.reshape(-1)))
    onp.testing.assert_allclose(
        float(looped.loss(NDArray(tok), NDArray(lab))),
        float(ce.mean()), rtol=1e-6)
    with pytest.raises(ValueError, match="LoopedLM"):
        TransformerLM(_config())


def test_the_published_configuration():
    cfg = ouro_2p6b_config(n_layers=8)
    assert (cfg.vocab_size, cfg.dim, cfg.n_layers, cfg.n_heads,
            cfg.n_kv_heads, cfg.hidden_dim, cfg.passes) == \
        (49152, 2048, 8, 16, 16, 5632, 4)
    assert cfg.dim // cfg.n_heads == 128 and cfg.sandwich_norm
    assert (cfg.rope_theta, cfg.norm_eps, cfg.max_seq_len) == \
        (1e6, 1e-6, 65536)
    assert ouro_2p6b_config().n_layers == 48


def test_exit_distribution_and_loss_by_hand():
    z = jnp.asarray([[0.0, 2.0], [0.0, -1.0], [5.0, 0.3]])
    lam = jax.nn.sigmoid(z)
    want = jnp.stack([lam[0], lam[1] * (1 - lam[0]),
                      (1 - lam[0]) * (1 - lam[1])])
    log_p = exit_log_probs(z)
    onp.testing.assert_allclose(jnp.exp(log_p), want, rtol=1e-6)
    ce = jnp.asarray([[3.0, 1.0], [2.0, 1.5], [1.0, 4.0]])
    by_hand = jnp.mean(jnp.sum(want * ce, 0)
                       + 0.1 * jnp.sum(want * jnp.log(want), 0))
    onp.testing.assert_allclose(expected_exit_loss(ce, log_p, 0.1), by_hand,
                                rtol=1e-6)


# ----------------------------------------------------------------------
# the head without whole logits
# ----------------------------------------------------------------------
@pytest.mark.parametrize("chunk", [24, 64, 100], ids=["ragged", "whole",
                                                      "larger"])
def test_chunked_cross_entropy_is_softmax_cross_entropy(chunk):
    rs = onp.random.RandomState(5)
    h = jnp.asarray(rs.randn(64, 32), jnp.float32)
    w = jnp.asarray(rs.randn(200, 32) * 0.3, jnp.float32)
    y = jnp.asarray(rs.randint(0, 200, 64))
    weight = jnp.asarray(rs.rand(64), jnp.float32)   # uneven cotangents
    whole = gluon.loss.SoftmaxCrossEntropyLoss()

    def by_chunks(h, w):
        return chunked_softmax_cross_entropy(h, w, y, chunk)

    def by_logits(h, w):
        return whole(NDArray(h @ w.T), NDArray(y))._data

    onp.testing.assert_allclose(by_chunks(h, w), by_logits(h, w),
                                rtol=1e-5, atol=1e-6)
    got = jax.grad(lambda h, w: (by_chunks(h, w) * weight).sum(),
                   (0, 1))(h, w)
    want = jax.grad(lambda h, w: (by_logits(h, w) * weight).sum(),
                    (0, 1))(h, w)
    for g, v in zip(got, want):
        onp.testing.assert_allclose(g, v, rtol=1e-4, atol=1e-6)


def _weighted_sum(h, w, y, chunk=256):
    return weighted_chunked_softmax_cross_entropy(
        h, w, y, jnp.ones(h.shape[:1], jnp.float32), chunk)[0]


@pytest.mark.parametrize("fn", [
    lambda h, w, y: chunked_softmax_cross_entropy(h, w, y, 256).sum(),
    _weighted_sum], ids=["per_token", "weighted_sum"])
def test_chunked_cross_entropy_holds_one_chunk_of_logits(fn):
    # 4,096 tokens over a 4,096-word vocabulary: whole float32 logits
    # are 67 MB, a chunk of 256 is 4 MB; forward and backward together
    # stay far under the whole logits (the weighted sum, which forms
    # the gradients in its forward, holds the head's summed gradient
    # and the rows' besides: 1 MB each here)
    h = jax.ShapeDtypeStruct((4096, 64), jnp.float32)
    w = jax.ShapeDtypeStruct((4096, 64), jnp.float32)
    y = jax.ShapeDtypeStruct((4096,), jnp.int32)

    def temp(fn):
        return jax.jit(jax.grad(fn, (0, 1))) \
            .lower(h, w, y).compile().memory_analysis().temp_size_in_bytes

    whole = temp(lambda h, w, y: -jnp.take_along_axis(
        jax.nn.log_softmax(h @ w.T), y[:, None], 1)[:, 0].sum())
    assert whole > 4096 * 4096 * 4
    assert temp(fn) < 4096 * 4096 * 4 / 4


@pytest.mark.parametrize("cotangent", [1.0, -2.5], ids=["one", "scaled"])
@pytest.mark.parametrize("chunk", [24, 64, 100], ids=["ragged", "whole",
                                                      "larger"])
def test_weighted_cross_entropy_is_the_weighted_sum_of_the_per_token(
        chunk, cotangent):
    rs = onp.random.RandomState(5)
    h = jnp.asarray(rs.randn(64, 32), jnp.float32)
    w = jnp.asarray(rs.randn(200, 32) * 0.3, jnp.float32)
    y = jnp.asarray(rs.randint(0, 200, 64))
    weight = jnp.asarray(rs.rand(64), jnp.float32)

    def early(h, w, weight):
        return cotangent * weighted_chunked_softmax_cross_entropy(
            h, w, y, weight, chunk)[0]

    def late(h, w, weight):
        return cotangent * (chunked_softmax_cross_entropy(h, w, y, chunk)
                            * weight).sum()

    total, ce = weighted_chunked_softmax_cross_entropy(h, w, y, weight,
                                                       chunk)
    onp.testing.assert_allclose(cotangent * total, late(h, w, weight),
                                rtol=1e-6)
    onp.testing.assert_allclose(
        ce, chunked_softmax_cross_entropy(h, w, y, chunk), rtol=1e-6)
    value, got = jax.value_and_grad(early, (0, 1, 2))(h, w, weight)
    onp.testing.assert_allclose(value, cotangent * total, rtol=1e-6)
    for g, v in zip(got, jax.grad(late, (0, 1, 2))(h, w, weight)):
        assert g.shape == v.shape
        onp.testing.assert_allclose(g, v, rtol=1e-5, atol=1e-6)
    # the per-token output is a value: nothing comes back through it
    through = jax.grad(lambda h: weighted_chunked_softmax_cross_entropy(
        h, w, y, weight, chunk)[1].sum())(h)
    assert not bool(jnp.any(through))


def _products(jaxpr, width):
    """``dot_general``s of a jaxpr, loops' bodies counted once, that
    have an operand or a result ``width`` wide."""
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general" and any(
                width in v.aval.shape for v in eqn.invars + eqn.outvars):
            n += 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n += _products(sub, width)
    return n


def test_weighted_cross_entropy_makes_a_chunks_logits_once():
    """Three vocabulary-wide products a chunk (logits, the rows'
    gradient, the head's), all in the forward rule; the per-token
    function makes four, three of them in its backward."""
    rs = onp.random.RandomState(1)
    h = jnp.asarray(rs.randn(64, 32), jnp.float32)
    w = jnp.asarray(rs.randn(200, 32), jnp.float32)
    y = jnp.asarray(rs.randint(0, 200, 64))
    weight = jnp.asarray(rs.rand(64), jnp.float32)

    def early(h, w, weight):
        return weighted_chunked_softmax_cross_entropy(h, w, y, weight, 16)[0]

    def late(h, w, weight):
        return (chunked_softmax_cross_entropy(h, w, y, 16) * weight).sum()

    for fn, whole, backward in ((early, 3, 0), (late, 4, 3)):
        assert _products(jax.make_jaxpr(jax.grad(fn, (0, 1, 2)))(
            h, w, weight).jaxpr, 200) == whole
        pullback = jax.vjp(fn, h, w, weight)[1]
        assert _products(jax.make_jaxpr(pullback)(jnp.float32(1)).jaxpr,
                         200) == backward


def test_npx_chunked_cross_entropy_records_on_the_tape():
    rs = onp.random.RandomState(2)
    h = mx.np.array(rs.randn(10, 8).astype("float32"))
    w = mx.np.array(rs.randn(12, 8).astype("float32"))
    y = mx.np.array(rs.randint(0, 12, 10).astype("int32"))
    h.attach_grad()
    with mx.autograd.record():
        loss = mx.npx.chunked_softmax_cross_entropy(h, w, y, chunk=4).sum()
    loss.backward()
    want = jax.grad(lambda a: -jnp.take_along_axis(jax.nn.log_softmax(
        a @ w._data.T), y._data[:, None], 1).sum())(h._data)
    onp.testing.assert_allclose(h.grad._data, want, rtol=1e-4, atol=1e-6)


# ----------------------------------------------------------------------
# recomputation by block
# ----------------------------------------------------------------------
def test_recomputation_leaves_loss_and_gradients_unchanged(weights):
    tok, lab = _batch()
    marked = _net(weights)
    plain = _net(weights)
    for blk in plain.layers:
        blk.recompute(False)
    assert all(b._recompute for b in marked.layers)
    assert not any(b._recompute for b in plain.layers)
    loss_a, grads_a = _first_gradients(_step(marked), tok, lab)
    loss_b, grads_b = _first_gradients(_step(plain), tok, lab)
    assert loss_a == loss_b
    for name, g in grads_a.items():
        # the same operations in the same order, once more: to rounding
        onp.testing.assert_allclose(g, grads_b[name], rtol=1e-5, atol=1e-9)


def _lowered(recompute, **over):
    cfg = _config(dim=128, n_heads=4, n_kv_heads=4, hidden_dim=512,
                  n_layers=4, passes=2, vocab_size=256, **over)
    net = LoopedLM(cfg)
    for blk in net.layers:
        blk.recompute(recompute)
    net.initialize()
    tok = NDArray(jnp.zeros((4, 256), jnp.int32))
    return _step(net, chunk=256).lower(tok, tok)


def test_recomputation_lowers_the_steps_temporaries():
    # 1,024 tokens x 8 block applications: the blocks' interiors
    # (scores, the SwiGLU's 512-wide products) dominate the step's
    # temporaries; with a block recomputed only its input is kept
    marked = _lowered(True).compile().memory_analysis().temp_size_in_bytes
    plain = _lowered(False).compile().memory_analysis().temp_size_in_bytes
    assert marked < 0.6 * plain, (marked, plain)


def test_an_unmarked_network_lowers_as_before():
    # no mark, no jax.checkpoint anywhere in the step; a mark taken off
    # again is no mark
    text = _lowered(False).as_text(debug_info=True)
    assert "checkpoint" not in text and "rematted" not in text
    # the one barrier left is the exit loss's own, round dlogits
    assert text.count("stablehlo.optimization_barrier") == 1
    assert 'loc("head_grad/optimization_barrier"' in text
    marked = _lowered(True).as_text(debug_info=True)
    assert "rematted_computation" in marked
    mx.np.random.seed(11)
    net = TransformerLM(tiny_config())
    net.initialize()
    tok = NDArray(jnp.zeros((2, 16), jnp.int32))
    net(tok)

    def lower():
        return parallel.TrainStep(
            net, gluon.loss.SoftmaxCrossEntropyLoss(),
            mx.optimizer.SGD(learning_rate=0.1), mesh=None) \
            .lower(tok, tok).as_text()

    before = lower()
    for blk in net.layers:
        blk.recompute()
    assert lower() != before
    for blk in net.layers:
        blk.recompute(False)
    assert lower() == before


def test_a_marked_block_is_plain_outside_a_training_trace():
    net = gluon.nn.Dense(3, in_units=4)
    net.initialize()
    net.recompute()
    x = mx.np.array(onp.ones((2, 4), "float32"))
    want = net.forward(x)._data
    assert bool(jnp.all(net(x)._data == want))            # eager
    text = jax.jit(lambda a: net(NDArray(a))._data).lower(x._data) \
        .as_text(debug_info=True)
    assert "checkpoint" not in text                         # inference trace


def test_recomputation_carries_written_state_and_random_keys():
    """A marked block that writes a running statistic and draws a
    dropout mask: the statistic comes out of the checkpointed call and
    is written back, and the recomputed forward draws the mask the
    first forward drew.  With ``loss = sum(drop(bn(x)) * w)`` the
    gradient to ``w`` is ``loss / w`` under one mask, and something else
    under two."""
    class Noisy(gluon.HybridBlock):
        def __init__(self):
            super().__init__()
            self.bn = gluon.nn.BatchNorm(in_channels=4)
            self.drop = gluon.nn.Dropout(0.5)
            self.w = gluon.Parameter(shape=(1,), init="ones", name="w")

        def forward(self, x):
            return self.drop(self.bn(x) + 3.0) * self.w.data()

    x = mx.np.array(onp.random.RandomState(1).randn(64, 4)
                    .astype("float32"))
    seen = {}
    for mark in (True, False):
        mx.np.random.seed(9)
        net = Noisy()
        net.initialize()
        net.recompute(mark)
        step = parallel.TrainStep(
            net, None, mx.optimizer.SGD(learning_rate=1.0), mesh=None,
            forward_fn=lambda net, x: net(x).sum())
        loss = float(step(x))
        ps = net.collect_params()
        grad_w = 1.0 - float(ps["w"].data()._data[0])     # w0 = 1, lr = 1
        onp.testing.assert_allclose(grad_w, loss, rtol=1e-5)
        seen[mark] = ps["bn.running_mean"].data()._data
    assert float(jnp.abs(seen[True]).sum()) > 0            # written back
    onp.testing.assert_allclose(seen[True], seen[False], rtol=1e-6)


# ----------------------------------------------------------------------
# what a marked block keeps: the flash kernel's output and row sums
# (pallas_ops.ATTENTION_KERNEL_OUT), so the kernel runs once.  The
# kernels run in the interpreter at the smallest shape they take (rows
# of 128, heads of 64); "bare" is the parent's form, jax.checkpoint
# with no policy.
# ----------------------------------------------------------------------
@pytest.fixture()
def bare_checkpoint(monkeypatch):
    """Returns a switch: called, every ``jax.checkpoint`` from then on
    drops its policy (what ``_forward_recomputed`` was before it kept
    anything)."""
    real = jax.checkpoint

    def switch():
        monkeypatch.setattr(jax, "checkpoint",
                            lambda fun, **kw: real(fun))
    return switch


def _kernel_config(**over):
    return _config(dim=128, n_heads=2, n_kv_heads=2, hidden_dim=256, **over)


class _TwoBlocks(gluon.HybridBlock):
    def __init__(self):
        super().__init__()
        from mxnet_tpu.models.transformer import TransformerBlock
        self.a = TransformerBlock(_kernel_config())
        self.b = TransformerBlock(_kernel_config())

    def forward(self, x):
        return self.b(self.a(x))


# (the parent marked, its two blocks marked, bare) -> flash_fwd calls in
# the gradient's jaxpr; dq and dkv are always one a block
@pytest.mark.parametrize("outer,inner,bare,forwards", [
    (False, False, False, 2),       # unmarked: one a block application
    (False, True, False, 2),        # marked: still one
    (False, True, True, 4),         # the parent's form: made again
    (True, False, False, 2),        # the root's mark alone
    (True, True, False, 2),         # a marked block in a marked parent
    (True, True, True, 5),          # bare, nested: again and again
])
def test_a_marked_block_runs_the_attention_kernel_once(
        interpret_kernels, bare_checkpoint, outer, inner, bare, forwards):
    net = _TwoBlocks()
    net.initialize()
    net.recompute(outer)
    net.a.recompute(inner)
    net.b.recompute(inner)
    if bare:
        bare_checkpoint()

    def loss(x):
        with mx.autograd.train_mode():
            return net(NDArray(x))._data.sum()

    text = str(jax.make_jaxpr(jax.grad(loss))(jnp.ones((1, 128, 128))))
    assert text.count("name=flash_fwd") == forwards
    assert text.count("name=flash_bwd_dq") == 2
    assert text.count("name=flash_bwd_dkv") == 2
    assert ("remat2" in text) == (outer or inner)


def test_keeping_the_kernels_output_changes_no_bit(interpret_kernels,
                                                   bare_checkpoint):
    """The looped step with the kernels inside, marked as ``LoopedLM``
    marks it, against the same step under the bare checkpoint: the kept
    ``o`` and ``lse`` are the bits a second run of the kernel makes, so
    the loss, both moments and every parameter after the step are the
    same to the bit."""
    ids = onp.random.RandomState(3).randint(0, 256, (B, 129))
    tok, lab = NDArray(jnp.asarray(ids[:, :-1])), \
        NDArray(jnp.asarray(ids[:, 1:]))

    def run():
        mx.np.random.seed(5)
        net = LoopedLM(_kernel_config())
        net.initialize()
        step = _step(net, chunk=96)
        text = step.lower(tok, lab).as_text()
        loss = float(step(tok, lab))
        return loss, step._states, {
            n: p.data()._data for n, p in net.collect_params().items()}, text

    loss_a, states_a, params_a, text_a = run()
    bare_checkpoint()
    loss_b, states_b, params_b, text_b = run()
    assert text_a != text_b                  # two programs, not one twice
    assert loss_a == loss_b
    assert states_a.keys() == states_b.keys() and len(params_a) > 10
    for name, st in states_a.items():
        for a, b in zip(st, states_b[name]):
            assert bool(jnp.all(a == b)), name
    for name, a in params_a.items():
        assert bool(jnp.all(a == params_b[name])), name


def _marked_mlp():
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(32, activation="relu", in_units=16),
            gluon.nn.Dense(8, in_units=32))
    x = mx.np.array(onp.ones((4, 16), "float32"))
    return net, net, x, mx.np.array(onp.zeros((4,), "int32"))


def _marked_resnet_features():
    from mxnet_tpu.gluon.model_zoo import vision
    net = vision.resnet18_v1(layout="NHWC")
    x = mx.np.array(onp.ones((2, 32, 32, 3), "float32"))
    return net, net.features, x, mx.np.array(onp.zeros((2,), "int32"))


@pytest.mark.parametrize("build", [_marked_mlp, _marked_resnet_features])
def test_a_marked_block_without_the_kernel_lowers_as_the_bare_checkpoint(
        bare_checkpoint, build):
    """No named value inside, nothing more kept: the step's text is the
    bare checkpoint's, letter for letter."""
    mx.np.random.seed(2)
    net, marked, x, y = build()
    net.initialize()
    net(x)
    marked.recompute()

    def lower():
        return parallel.TrainStep(
            net, gluon.loss.SoftmaxCrossEntropyLoss(),
            mx.optimizer.SGD(learning_rate=0.1), mesh=None) \
            .lower(x, y).as_text()

    with_policy = lower()
    assert "optimization_barrier" in with_policy
    bare_checkpoint()
    assert lower() == with_policy
