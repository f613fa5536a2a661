"""Model zoo + flagship transformer tests."""
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon, parallel
from mxnet_tpu.gluon.model_zoo import vision
from mxnet_tpu.models import TransformerLM, tiny_config
from mxnet_tpu.test_utils import assert_almost_equal


def test_resnet18_forward_and_hybrid():
    net = vision.resnet18_v1(classes=10)
    net.initialize()
    x = mx.np.random.normal(0, 1, (2, 3, 32, 32))
    out_eager = net(x)
    assert out_eager.shape == (2, 10)
    net.hybridize()
    out_hybrid = net(x)
    assert_almost_equal(out_eager, out_hybrid, rtol=1e-4, atol=1e-4)


def test_resnet_v2_forward():
    net = vision.resnet18_v2(classes=10)
    net.initialize()
    assert net(mx.np.random.normal(0, 1, (2, 3, 32, 32))).shape == (2, 10)


@pytest.mark.parametrize("name", ["alexnet", "vgg11", "squeezenet1.1",
                                  "mobilenet0.25", "mobilenetv2_0.25",
                                  "densenet121"])
def test_zoo_constructs_and_runs(name):
    net = vision.get_model(name, classes=7)
    net.initialize()
    size = 224
    out = net(mx.np.random.uniform(0, 1, (1, 3, size, size)))
    assert out.shape == (1, 7)


def test_get_model_unknown():
    with pytest.raises(ValueError):
        vision.get_model("resnet999")


def test_transformer_forward_and_train():
    cfg = tiny_config()
    net = TransformerLM(cfg)
    net.initialize()
    toks = mx.np.random.randint(0, cfg.vocab_size, (2, 16), dtype="int32")
    out = net(toks)
    assert out.shape == (2, 16, cfg.vocab_size)
    # quick training convergence on a repeated sequence
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    opt = mx.optimizer.AdamW(learning_rate=3e-3)

    def fwd(net, tokens, labels):
        logits = net.forward(tokens)
        return loss_fn(logits.reshape(-1, logits.shape[-1]),
                       labels.reshape(-1)).mean()

    step = parallel.TrainStep(net, None, opt, forward_fn=fwd)
    labels = toks
    l0 = float(step(toks, labels))
    l_last = l0
    for _ in range(10):
        l_last = float(step(toks, labels))
    assert l_last < l0


def test_transformer_tp_mesh():
    import jax
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 devices")
    cfg = tiny_config()
    net = TransformerLM(cfg)
    net.initialize()
    mesh = parallel.create_mesh(dp=2, tp=4)
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    opt = mx.optimizer.AdamW(learning_rate=1e-3)

    def fwd(net, tokens, labels):
        logits = net.forward(tokens)
        return loss_fn(logits.reshape(-1, logits.shape[-1]),
                       labels.reshape(-1)).mean()

    with parallel.mesh_scope(mesh):
        step = parallel.TrainStep(net, None, opt, mesh=mesh, forward_fn=fwd,
                                  zero1=True)
        toks = mx.np.random.randint(0, cfg.vocab_size, (4, 32), dtype="int32")
        loss = step(toks, toks)
        assert bool(mx.np.isfinite(loss))
    # qkv weights sharded over tp
    w = net.layers[0].attention.wq.weight.data()._data
    from mxnet_tpu.parallel import P
    assert w.sharding.spec == P("tp", None)


def test_graft_entry_dryrun():
    import jax
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 devices")
    import sys
    sys.path.insert(0, "/root/repo")
    import __graft_entry__ as g
    g.dryrun_multichip(8)


def test_transformer_lm_moe_trains_with_aux_loss():
    """MoE TransformerLM: moe_num_experts routes every moe_every-th block
    through the routed-expert FFN (top moe_top_k, dropless); the router's
    balance loss joins the training loss inside the same trace
    (``TransformerLM.loss``) and the model still learns."""
    from mxnet_tpu.models import TransformerLM, tiny_config
    mx.np.random.seed(0)
    cfg = tiny_config(n_layers=2, moe_num_experts=4, moe_every=2,
                      moe_top_k=2, vocab_size=64)
    net = TransformerLM(cfg)
    net.initialize()
    from mxnet_tpu.models.experts import RoutedExperts
    from mxnet_tpu.models.transformer import FeedForward
    kinds = [type(blk.feed_forward) for blk in net.layers]
    assert kinds == [RoutedExperts, FeedForward]

    onp.random.seed(0)
    toks = mx.np.array(onp.random.randint(0, 64, (4, 16)).astype("int32"))
    labs = mx.np.array(onp.random.randint(0, 64, (4, 16)).astype("int32"))
    step = parallel.TrainStep(net, None,
                              mx.optimizer.AdamW(learning_rate=1e-2),
                              mesh=None,
                              forward_fn=lambda net, x, y: net.loss(x, y))
    l0, parts = step(toks, labs)
    # every pair is routed to an expert held here: 4 x 16 tokens, top 2
    assert int(parts["held_pairs"]._data) == 4 * 16 * 2
    assert float(parts["router_loss"]._data) > 0
    for _ in range(8):
        ln, _ = step(toks, labs)
    l0, ln = float(l0), float(ln)
    assert onp.isfinite(l0) and onp.isfinite(ln)
    assert ln < l0  # memorizes the fixed batch
