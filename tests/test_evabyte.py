"""EvaByte (``mx.models.EvaByteLM``): EVA attention under one softmax
(the kernels, and the XLA stand-in), the norms' unit offset, the float32 residual stream and the
eight byte heads, at toy widths on the CPU (window 32, chunk 4, four
windows, two layers), against the plain reference the benchmark keeps
(``benchmark/chip/reference/eva_decoder.py``, which imports nothing of
``mxnet_tpu`` and computes the attention as one dense masked softmax)."""
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon, parallel
from mxnet_tpu.models import (EvaByteLM, TransformerLM, chunk_summaries,
                              eva_attention, evabyte_6p5b_config)
from mxnet_tpu.ndarray.ndarray import NDArray
from mxnet_tpu.ops.nn import dot_product_attention
from mxnet_tpu.ops.pallas_ops import eva_flash_attention

CHIP = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark", "chip")
if CHIP not in sys.path:
    sys.path.insert(0, CHIP)

import common  # noqa: E402
from builders.eva_decoder import _program_name  # noqa: E402
from reference import eva_decoder as ref  # noqa: E402

MODEL = {"param_dtype": "float32", "init_std": 0.02, "hidden_size": 64,
         "intermediate_size": 128, "vocab_size": 320,
         "num_attention_heads": 4, "num_key_value_heads": 4,
         "num_hidden_layers": 2, "rms_norm_eps": 1e-5, "rope_theta": 1e5,
         "window_size": 32, "chunk_size": 4, "num_pred_heads": 8,
         "optimizer": {"learning_rate": 3e-4, "beta1": 0.9, "beta2": 0.95,
                       "epsilon": 1e-8, "wd": 0.1}}
B, T, K = 2, 128, 8              # four windows of 32, 32 chunks of 4


def _config(**over):
    args = dict(dim=64, n_layers=2, n_heads=4, n_kv_heads=4, hidden_dim=128,
                window_size=32, chunk_size=4, max_seq_len=256,
                dtype="float32")
    args.update(over)
    return evabyte_6p5b_config(**args)


def _net(weights, cls=EvaByteLM, **over):
    net = cls(_config(**over))
    net.cast(over.get("dtype", "float32"))
    ps = net.collect_params()
    for name, value in weights.items():
        if _program_name(name) in ps:
            ps[_program_name(name)].set_data(NDArray(value))
    return net


def _batch(seed=0, t=T):
    ids = onp.random.RandomState(seed).randint(0, 320, (B, t + K))
    return jnp.asarray(ids[:, :t]), jnp.stack(
        [jnp.asarray(ids[:, 1 + k:t + 1 + k]) for k in range(K)], -1)


def _step(net):
    o = MODEL["optimizer"]
    opt = mx.optimizer.AdamW(learning_rate=o["learning_rate"],
                             beta1=o["beta1"], beta2=o["beta2"],
                             epsilon=o["epsilon"], wd=o["wd"])
    return parallel.TrainStep(
        net, None, opt, mesh=None,
        forward_fn=lambda net, t, l: net.loss(t, l, heads=True))


@pytest.fixture(scope="module")
def weights():
    """Seeded leaves; the norms' stored offsets are drawn too (0 as
    initialised would leave the unit offset untested)."""
    specs = ref.leaf_specs(MODEL)
    w = ref.clamp(specs, common.make_weights(7, specs))
    key = jax.random.key(1)
    for n in sorted(w):
        if n.endswith("norm"):
            key, sub = jax.random.split(key)
            w[n] = 0.1 * jax.random.normal(sub, w[n].shape)
    return w


@pytest.fixture(scope="module")
def reference_step(weights):
    tok, lab = _batch()
    return ref.make_step(MODEL)(weights, None, tok, lab)


# ----------------------------------------------------------------------
# the model against the plain reference.  float32: both sides compute in
# float32 but sum in different orders (windows of 32: the XLA stand-in's
# softmax over a window's keys and summaries against one masked softmax): 2e-5 is some tens of float32 roundings of a unit
# value.  bf16: parameters, matmuls and attention in bf16 over the
# float32 stream against the float32 reference on the same (bf16) leaves
# ----------------------------------------------------------------------
def test_logits_match_the_reference(weights):
    tok, _ = _batch()
    got = _net(weights)(NDArray(tok))._data
    want = ref.logits(MODEL, weights, tok)
    assert got.shape == want.shape == (B, T, K, 320)
    assert got.dtype == jnp.float32
    onp.testing.assert_allclose(got, want, rtol=0, atol=2e-5)


def test_loss_and_every_leaf_gradient_match_the_reference(weights,
                                                          reference_step):
    tok, lab = _batch()
    loss, parts, grads, new_params, _ = reference_step
    net = _net(weights)
    step = _step(net)
    got_loss, aux = step(NDArray(tok), NDArray(lab))
    onp.testing.assert_allclose(float(got_loss), float(loss), rtol=1e-5)
    assert aux["ce"].shape == (K,)
    onp.testing.assert_allclose(aux["ce"]._data, parts["ce"], rtol=1e-5)
    onp.testing.assert_allclose(float(got_loss), float(parts["ce"].mean()),
                                rtol=1e-5)
    ps = net.collect_params()
    assert set(_program_name(k) for k in grads) == set(step._states)
    for name, want in grads.items():
        g = step._states[_program_name(name)][0] \
            / (1 - MODEL["optimizer"]["beta1"])
        # a leaf's gradient as a whole: the norm of the difference over
        # the norm; the pooling vectors' among them
        assert float(jnp.linalg.norm(g - want)
                     / jnp.linalg.norm(want)) < 2e-5, name
        onp.testing.assert_allclose(ps[_program_name(name)].data()._data,
                                    new_params[name], rtol=0, atol=5e-6)


def test_bf16_model_follows_the_float32_reference(weights):
    tok, lab = _batch()
    low = {k: v.astype(jnp.bfloat16) for k, v in weights.items()}
    model = dict(MODEL, param_dtype="bfloat16")
    loss, parts, grads, _, _ = ref.make_step(model)(low, None, tok, lab)
    net = _net(low, dtype="bfloat16")
    assert net.layers[0].attention.wq.weight.data().dtype == jnp.bfloat16
    logits = net(NDArray(tok))._data
    assert logits.dtype == jnp.float32           # fp32_logits
    want = ref.logits(model, low, tok)
    # bf16 has 8 bits: logits of size 0.8 carry 2-3e-3 of rounding each
    # from two layers' branches
    assert float(jnp.abs(logits - want).max()) < 0.03
    step = _step(net)
    got_loss, aux = step(NDArray(tok), NDArray(lab))
    onp.testing.assert_allclose(float(got_loss), float(loss), rtol=5e-4)
    onp.testing.assert_allclose(aux["ce"]._data, parts["ce"], rtol=1e-3)
    for name in ("lm_head", "layer0.w_down", "layer1.adaptive_phi",
                 "layer0.adaptive_phi", "embed"):
        g = step._states[_program_name(name)][0] \
            / (1 - MODEL["optimizer"]["beta1"])
        assert float(jnp.linalg.norm(g - grads[name])
                     / jnp.linalg.norm(grads[name])) < 0.05, name


def test_the_stream_is_float32_and_the_branches_bf16(weights):
    low = {k: v.astype(jnp.bfloat16) for k, v in weights.items()}
    net = _net(low, dtype="bfloat16")
    tok, _ = _batch()
    h = net._embed(NDArray(tok))
    assert h.dtype == jnp.float32
    blk = net.layers[0]
    u = blk.attention_norm(h)
    assert u.dtype == jnp.bfloat16
    assert blk.attention(u).dtype == jnp.bfloat16
    assert blk(h).dtype == jnp.float32
    # the norm's gain is one plus the stored leaf
    g = blk.attention_norm.gamma.data()._data.astype(jnp.float32)
    x = h._data
    want = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-5) \
        * (1.0 + g)
    onp.testing.assert_allclose(u._data.astype(jnp.float32), want,
                                rtol=1e-2, atol=1e-3)
    fresh = EvaByteLM(_config())
    fresh.initialize()
    assert float(jnp.abs(fresh.norm.gamma.data()._data).max()) == 0.0


# ----------------------------------------------------------------------
# the attention alone: one softmax over a window's keys and the
# summaries before it, against ONE dense masked softmax over [tokens |
# summaries], values and all five gradients
# ----------------------------------------------------------------------
def _dense_eva(q, k, v, mu, phi, window, chunk):
    """One masked softmax over the concatenated key set, written out."""
    Bq, H, Tq, D = q.shape
    ks, vs = chunk_summaries(k, v, mu, phi, chunk)
    keys = jnp.concatenate([k, ks], axis=2)
    vals = jnp.concatenate([v, vs], axis=2)
    i = jnp.arange(Tq)
    tok = (i[:, None] // window == i[None, :] // window) \
        & (i[None, :] <= i[:, None])
    j = jnp.arange(Tq // chunk)
    summ = (j[None, :] * chunk) // window < i[:, None] // window
    mask = jnp.concatenate([tok, summ], axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, keys,
                   precision="highest") / math.sqrt(D)
    p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, vals, precision="highest")


def _qkv(shape, seed=0):
    Bq, H, Tq, D = shape
    rs = onp.random.RandomState(seed)
    q, k, v, ct = (jnp.asarray(rs.randn(*shape), jnp.float32)
                   for _ in range(4))
    mu, phi = (jnp.asarray(rs.randn(H, D) * D ** -0.5, jnp.float32)
               for _ in range(2))
    return (q, k, v, mu, phi), ct


def _check_against_dense(shape, window, chunk, tol, attention=None):
    """``attention(q, k, v, mu, phi)`` (``eva_attention`` by default)
    against ``_dense_eva``: the output to ``tol`` and each of the five
    gradients to ``tol`` of its norm."""
    attention = attention or (lambda *a: eva_attention(*a, window, chunk))
    args, ct = _qkv(shape)
    onp.testing.assert_allclose(attention(*args),
                                _dense_eva(*args, window, chunk), rtol=0,
                                atol=tol)
    g_got = jax.grad(lambda *a: (attention(*a) * ct).sum(), range(5))(*args)
    g_want = jax.grad(lambda *a: (_dense_eva(*a, window, chunk)
                                  * ct).sum(), range(5))(*args)
    for name, a, b in zip("q k v mu phi".split(), g_got, g_want):
        assert float(jnp.linalg.norm(a - b)) \
            <= tol * float(jnp.linalg.norm(b)), name
        # one window: no summary, so mu and phi have none
        assert float(jnp.linalg.norm(b)) > 0 or shape[2] == window, name


def test_merged_parts_are_one_dense_masked_softmax():
    # the XLA stand-in for the kernels (windows of 32 are under the
    # kernels' 128), float32: to 1e-5
    _check_against_dense((2, 3, 128, 16), 32, 4, 1e-5)


def test_merged_parts_through_the_kernels(interpret_kernels):
    # the kernels' own code in the interpreter, three windows of 256
    # and chunks of 2: 128 summaries a window, whole summary tiles; the
    # kernels feed the MXU in one bf16 pass on the chip, full float32
    # here
    _check_against_dense((1, 2, 768, 64), 256, 2, 1e-5)


def _with_tiles(window, chunk, block_q, block_k):
    def attention(q, k, v, mu, phi):
        T = q.shape[2]
        ks, vs = chunk_summaries(k[:, :, :T - window], v[:, :, :T - window],
                                 mu, phi, chunk)
        return eva_flash_attention(q, k, v, ks, vs, window,
                                   block_q=block_q, block_k=block_k)
    return attention


@pytest.mark.parametrize("shape,window,chunk,tiles", [
    # one window: the causal flash kernel, no summary
    ((1, 2, 128, 64), 128, 4, None),
    # the smallest row the kernels take: two windows of 128, 32
    # summaries, one tile of them
    ((1, 2, 256, 64), 128, 4, None),
    # four windows: 64 summaries a window, 192 in one tile masked by
    # the window that reads it
    ((1, 2, 512, 64), 128, 2, None),
    # three windows of 256 in tiles of 128: two query and key tiles a
    # window, the diagonal inside the window, 128 summaries a tile
    ((1, 2, 768, 64), 256, 2, (128, 128)),
    # tiles of unequal sides: 128 queries against 256 keys, and back
    ((2, 1, 768, 64), 256, 2, (128, 256)),
    ((1, 1, 1024, 128), 256, 2, (256, 128))])
def test_the_fused_kernels_are_one_dense_masked_softmax(
        interpret_kernels, shape, window, chunk, tiles):
    _check_against_dense(shape, window, chunk, 1e-5, tiles and _with_tiles(
        window, chunk, *tiles))


def test_the_fused_kernels_run_per_shard_under_a_mesh(interpret_kernels):
    # batch rows over dp, heads over tp: a head's summaries travel with
    # its keys, so every shard's softmax is whole
    mesh = parallel.create_mesh(dp=2, tp=2)
    args, ct = _qkv((2, 4, 256, 64))

    def loss(*a):
        shard = parallel.kernel_shard(2, 4)
        return (eva_attention(*a, 128, 4, shard=shard) * ct).sum()

    with parallel.mesh_scope(mesh):
        lowered = jax.jit(jax.value_and_grad(loss, range(5))).lower(*args)
        assert "sdy.manual_computation" in lowered.as_text()
        got, grads = lowered.compile()(*args)
    want, wants = jax.value_and_grad(lambda *a: (
        _dense_eva(*a, 128, 4) * ct).sum(), range(5))(*args)
    onp.testing.assert_allclose(got, want, rtol=1e-5)
    for name, a, b in zip("q k v mu phi".split(), grads, wants):
        assert float(jnp.linalg.norm(a - b)) \
            <= 1e-5 * float(jnp.linalg.norm(b)), name


def test_window_zero_is_plain_causal_attention():
    args, _ = _qkv((2, 3, 128, 16), seed=2)
    q, k, v = args[:3]
    got = eva_attention(*args, 32, 4)
    first = dot_product_attention(q[:, :, :32], k[:, :, :32], v[:, :, :32],
                                  causal=True)
    onp.testing.assert_allclose(got[:, :, :32], first, rtol=0, atol=1e-6)
    # and a later window is not: it sees the summaries
    second = dot_product_attention(q[:, :, 32:64], k[:, :, 32:64],
                                   v[:, :, 32:64], causal=True)
    assert float(jnp.abs(got[:, :, 32:64] - second).max()) > 1e-3
    # the last window's chunks are seen by nobody
    bumped = eva_attention(q, k.at[:, :, 96:].add(1.0) * 1.0, v, *args[3:],
                           32, 4)
    onp.testing.assert_allclose(bumped[:, :, :96], got[:, :, :96], atol=1e-6)
    with pytest.raises(ValueError, match="whole windows"):
        eva_attention(*(a[:, :, :100] for a in args[:3]), *args[3:], 32, 4)


def test_with_one_window_the_model_is_transformer_lm_with_eight_heads(
        weights):
    tok, lab = _batch(t=32)
    eva = _net(weights)
    plain = _net(weights, cls=TransformerLM, attn_impl="dense")
    missing = set(eva.collect_params()) - set(plain.collect_params())
    assert missing == {"layer%d.attention.%s" % (i, n) for i in (0, 1)
                       for n in ("adaptive_mu_k", "adaptive_phi")}
    want = plain(NDArray(tok))._data
    assert want.shape == (B, 32, K * 320)
    got = eva(NDArray(tok))._data
    onp.testing.assert_allclose(got, want.reshape(B, 32, K, 320), rtol=0,
                                atol=1e-6)
    # head k's loss is the cross-entropy of its slice of the logits
    ce = gluon.loss.SoftmaxCrossEntropyLoss()
    heads = [float(ce(NDArray(want[..., 320 * k:320 * (k + 1)]
                              .reshape(-1, 320)),
                      NDArray(lab[..., k].reshape(-1))).mean())
             for k in range(K)]
    loss, parts = eva.loss(NDArray(tok), NDArray(lab), heads=True)
    onp.testing.assert_allclose(parts["ce"]._data, heads, rtol=1e-6)
    onp.testing.assert_allclose(float(loss), sum(heads) / K, rtol=1e-6)


def test_the_published_configuration():
    cfg = evabyte_6p5b_config()
    assert (cfg.vocab_size, cfg.dim, cfg.n_layers, cfg.n_heads,
            cfg.n_kv_heads, cfg.hidden_dim) == (320, 4096, 32, 32, 32, 11008)
    assert cfg.dim // cfg.n_heads == 128
    assert (cfg.attn_impl, cfg.window_size, cfg.chunk_size,
            cfg.num_pred_heads) == ("eva", 2048, 16, 8)
    assert (cfg.rope_theta, cfg.norm_eps, cfg.max_seq_len) == \
        (1e5, 1e-5, 32768)
    assert cfg.norm_unit_offset and cfg.residual_dtype == "float32"
    net = EvaByteLM(_config())
    shapes = {n: p.shape for n, p in net.collect_params().items()}
    assert shapes["layer1.attention.adaptive_mu_k"] == (4, 16)
    assert shapes["output.weight"] == (K * 320, 64)
    assert all(b._recompute for b in net.layers)
    with pytest.raises(ValueError, match="n_kv_heads"):
        EvaByteLM(_config(n_kv_heads=2))
    with pytest.raises(NotImplementedError, match="cached path"):
        net.initialize()
        net.layers[0].attention(
            NDArray(jnp.zeros((1, 32, 64))), cache=object())


# ----------------------------------------------------------------------
# what a marked block keeps: the fused kernel's output and row sums, so
# it does not run again in the backward; one forward, dq and dkv call a
# block, and nothing merged or stacked
# ----------------------------------------------------------------------
@pytest.mark.parametrize("bare,forwards", [(False, 1), (True, 2)])
def test_a_marked_block_runs_each_attention_kernel_once(
        interpret_kernels, monkeypatch, bare, forwards):
    from mxnet_tpu.models.transformer import TransformerBlock
    cfg = _config(dim=128, n_heads=2, n_kv_heads=2, hidden_dim=256,
                  window_size=256, chunk_size=2)
    blk = TransformerBlock(cfg)
    blk.initialize()
    blk.recompute()
    if bare:       # jax.checkpoint with no policy: nothing is kept
        real = jax.checkpoint
        monkeypatch.setattr(jax, "checkpoint", lambda fun, **kw: real(fun))

    def loss(x):
        with mx.autograd.train_mode():
            return blk(NDArray(x))._data.sum()

    text = str(jax.make_jaxpr(jax.grad(loss))(jnp.ones((1, 768, 128))))
    # three windows, one call of each kernel
    assert text.count("name=eva_flash_fwd") == forwards
    assert text.count("name=eva_flash_bwd_dq") == 1
    assert text.count("name=eva_flash_bwd_dkv") == 1
    assert "name=flash_" not in text
    assert "remat2" in text
    # the attention alone stacks no windows (rotary's halves aside)
    args, _ = _qkv((1, 2, 768, 64))
    alone = str(jax.make_jaxpr(jax.grad(
        lambda *a: eva_attention(*a, 256, 2).sum(), range(5)))(*args))
    assert "name=eva_flash_bwd_dkv" in alone
    assert "concatenate" not in alone


def test_the_marked_steps_lowering_holds_no_kernel_under_the_recomputed_part(
        interpret_kernels):
    net = EvaByteLM(_config(dim=128, n_heads=2, n_kv_heads=2,
                            hidden_dim=256, window_size=256, chunk_size=2,
                            n_layers=1, max_seq_len=1024))
    net.initialize()
    tok = NDArray(jnp.zeros((1, 768), jnp.int32))
    lab = NDArray(jnp.zeros((1, 768, K), jnp.int32))
    text = _step(net).lower(tok, lab).as_text(debug_info=True)
    names = [line for line in text.splitlines() if "flash_" in line]
    assert any("eva/eva_flash/" in n and "eva_flash_fwd" in n
               for n in names)
    assert any("eva_flash_bwd_dkv" in n for n in names)
    assert "rematted_computation" in text
    assert not [n for n in names if "rematted_computation" in n]
