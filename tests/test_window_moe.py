"""Sliding-window attention (``ops/pallas_ops.py`` ``window_attention``:
the ``swa_fwd`` / ``swa_bwd_dq`` / ``swa_bwd_dkv`` kernels), YaRN partial
rotary, head-wise gates and sigmoid-scored experts beside a shared one
(``models/transformer.py``, ``models/experts.py``): Laguna-S-2.1's layers
against straightforward float32 ``jax.numpy`` and numpy at toy size on
the CPU."""
import dataclasses
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu.models import (LayerSpec, LlamaConfig, TransformerLM,
                              experts, laguna_s21_config, tiny_config)
from mxnet_tpu.models import transformer
from mxnet_tpu.ndarray.ndarray import NDArray
from mxnet_tpu.ops import pallas_ops

CHIP = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark", "chip")
if CHIP not in sys.path:
    sys.path.insert(0, CHIP)


def _normal(key, *shape):
    return jax.random.normal(jax.random.key(key), shape, jnp.float32)


def _dense_band(q, k, v, window):
    """Every query against every key, masked to t - window < s <= t, one
    softmax; (B, H, T, D) with grouped K/V repeated."""
    rep = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, rep, 1), jnp.repeat(v, rep, 1)
    T = q.shape[2]
    t = onp.arange(T)
    band = (t[None, :] <= t[:, None]) & (t[None, :] > t[:, None] - window)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(q.shape[-1])
    return jnp.einsum("bhqk,bhkd->bhqd",
                      jax.nn.softmax(jnp.where(band, s, -jnp.inf), -1), v)


@pytest.mark.parametrize("H,G,T,D,window,block", [
    (4, 2, 512, 64, 128, 128),     # the window one tile: two a query tile
    (9, 1, 512, 64, 200, 128),     # GQA 9:1, a window of no whole tile
    (2, 2, 768, 128, 300, 256),    # 300 over tiles of 256
    (2, 1, 512, 128, 512, 256),    # a tile inside the band, unmasked
    (2, 1, 256, 64, 1000, None)],  # a window past the row: causal
    ids=["one_tile", "gqa9_w200", "w300_t256", "interior", "wide"])
def test_window_kernels_match_the_dense_band(monkeypatch, H, G, T, D, window,
                                             block):
    monkeypatch.setattr(pallas_ops, "_INTERPRET", True)
    q, k, v = _normal(1, 1, H, T, D), _normal(2, 1, G, T, D), \
        _normal(3, 1, G, T, D)
    r = _normal(4, 1, H, T, D)

    def program(q, k, v):
        return jnp.sum(pallas_ops.window_attention(
            q, k, v, window, block_q=block, block_k=block) * r)

    def plain(q, k, v):
        return jnp.sum(_dense_band(q, k, v, window) * r)

    with jax.default_matmul_precision("highest"):
        got = pallas_ops.window_attention(q, k, v, window, block_q=block,
                                          block_k=block)
        assert float(jnp.max(jnp.abs(got - _dense_band(q, k, v, window)))) \
            < 1e-5
        g1 = jax.grad(program, (0, 1, 2))(q, k, v)
        g2 = jax.grad(plain, (0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", g1, g2):
        assert float(jnp.max(jnp.abs(a - b))) < 1e-4, name


def test_the_band_visits_the_tiles_it_needs_and_counts_them(monkeypatch):
    # at 8,192 tokens and a 512-token window on 512-wide tiles a query
    # tile visits the key tile before its own and its own: 2 a tile (1
    # for the first), where the causal walk visits 8.5 on average
    T, W, b = 8192, 512, 512
    assert pallas_ops._band_slots(T, W, b, b) == (2, 2)
    assert pallas_ops._band_visited(T, W, b, b) == 2 * 16 - 1
    assert pallas_ops._band_slots(T, W, 128, 128) == (5, 5)
    assert pallas_ops._swa_tiles(T, W) == (512, 512)
    assert pallas_ops._swa_tiles(768, 300) == (256, 256)
    monkeypatch.setattr(pallas_ops, "_INTERPRET", True)
    before = mx.profiler.get_counters()
    q = jnp.zeros((1, 4, 512, 64), jnp.float32)
    k = jnp.zeros((1, 2, 512, 64), jnp.float32)
    jax.jit(lambda q, k: pallas_ops.window_attention(q, k, k, 128)).lower(
        q, k)
    after = mx.profiler.get_counters()
    assert after["window_attn::calls"] - before.get(
        "window_attn::calls", 0) == 1
    # 4 query tiles of 128: 1 + 2 + 2 + 2 key tiles, over 4 heads
    assert after["window_attn::key_tiles"] - before.get(
        "window_attn::key_tiles", 0) == 4 * 7


def test_off_the_chip_the_band_is_a_dense_masked_softmax():
    q, k, v = _normal(1, 1, 4, 100, 16), _normal(2, 1, 2, 100, 16), \
        _normal(3, 1, 2, 100, 16)
    with jax.default_matmul_precision("highest"):
        got = pallas_ops.window_attention(q, k, v, 7)
        assert float(jnp.max(jnp.abs(got - _dense_band(q, k, v, 7)))) < 1e-5


def _yarn_numpy(D, fraction, theta, factor, original, fast, slow, mscale):
    """YaRN's inverse frequencies written out (Peng et al. 2023, section
    3.2 as transformers' _compute_yarn_parameters has it)."""
    rot = int(D * fraction)
    dims = onp.arange(0, rot, 2) / rot
    extrapolated = 1.0 / theta ** dims
    interpolated = extrapolated / factor

    def correction(turns):
        return rot * math.log(original / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(correction(fast)), 0)
    high = min(math.ceil(correction(slow)), rot - 1)
    ramp = onp.clip((onp.arange(rot // 2) - low) / max(high - low, 1e-3),
                    0, 1)
    return interpolated * ramp + extrapolated * (1 - ramp), mscale


def test_yarn_partial_rotary_is_the_formula():
    yarn = (128, 8192, 32, 1, 1.4852030263919618)
    inv, scale = transformer.rope_frequencies(128, 500000.0, 0.5, yarn)
    want, mscale = _yarn_numpy(128, 0.5, 500000.0, *yarn)
    assert inv.shape == (32,) and scale == mscale
    onp.testing.assert_allclose(inv, want, rtol=1e-6)
    # the fastest dims keep their frequency, the slowest are divided by
    # the factor
    assert inv[0] == pytest.approx(1.0) and inv[-1] == pytest.approx(
        want[-1]) and want[-1] < 1.0 / 500000 ** (62 / 64) / 100
    x = onp.asarray(_normal(5, 1, 6, 2, 128))
    got = onp.asarray(transformer._rope_scaled(jnp.asarray(x),
                                               jnp.arange(6), inv, scale))
    ang = onp.arange(6)[:, None] * want[None, :]
    cos, sin = onp.cos(ang)[None, :, None] * mscale, \
        onp.sin(ang)[None, :, None] * mscale
    a, b = x[..., :32], x[..., 32:64]
    onp.testing.assert_allclose(got[..., :32], a * cos - b * sin, atol=1e-4)
    onp.testing.assert_allclose(got[..., 32:64], a * sin + b * cos,
                                atol=1e-4)
    onp.testing.assert_array_equal(got[..., 64:], x[..., 64:])
    # a whole-head plain rotary is the model's old rotary
    inv, scale = transformer.rope_frequencies(64, 1e4)
    y = _normal(6, 1, 5, 3, 64)
    onp.testing.assert_allclose(
        transformer._rope_scaled(y, jnp.arange(5), inv, scale),
        transformer._rope(y, jnp.arange(5), 1e4), atol=1e-5)


def test_head_gate_scales_each_heads_output_by_its_sigmoid():
    o, g = _normal(1, 2, 5, 3 * 8), _normal(2, 2, 5, 3)
    got = transformer._gate_heads(o, g)
    want = onp.asarray(o).reshape(2, 5, 3, 8) \
        / (1 + onp.exp(-onp.asarray(g)))[..., None]
    onp.testing.assert_allclose(got, want.reshape(2, 5, 24), rtol=1e-5)


E = 64   # experts; 32 shares of 2 in the share test


def _moe_cfg(held, first, shared=16):
    cfg = tiny_config(dim=32, moe_num_experts=E, moe_top_k=4,
                      moe_hidden_dim=8, moe_held=held, moe_first_held=first)
    spec = LayerSpec(ffn="experts", router_score="sigmoid",
                     routed_scale=2.5, shared_hidden_dim=shared)
    return cfg, spec


def _moe_weights(seed=0):
    return {"r": _normal(seed + 1, E, 32) * 0.3,
            "w1": _normal(seed + 2, E, 32, 8) * 0.2,
            "w3": _normal(seed + 3, E, 32, 8) * 0.2,
            "w2": _normal(seed + 4, E, 8, 32) * 0.2,
            "s1": _normal(seed + 5, 16, 32) * 0.2,
            "s3": _normal(seed + 6, 16, 32) * 0.2,
            "s2": _normal(seed + 7, 32, 16) * 0.2}


def _layer(held, first, w):
    cfg, spec = _moe_cfg(held, first)
    ffn = experts.RoutedExperts(cfg, spec)
    ffn.initialize()
    ffn.router.weight.set_data(NDArray(w["r"]))
    for name, full in (("experts_w1", "w1"), ("experts_w3", "w3"),
                       ("experts_w2", "w2")):
        getattr(ffn, name).set_data(NDArray(w[full][first:first + held]))
    ffn.shared_expert.w1.weight.set_data(NDArray(w["s1"]))
    ffn.shared_expert.w3.weight.set_data(NDArray(w["s3"]))
    ffn.shared_expert.w2.weight.set_data(NDArray(w["s2"]))
    return ffn


def _plain_moe(x, w, held=range(E)):
    """Sigmoid scores, the top 4's gates normalised and times 2.5, the
    held experts' SwiGLU, plus the shared expert's."""
    s = 1 / (1 + onp.exp(-(x @ w["r"].T)))
    y = onp.zeros_like(x)
    for t in range(x.shape[0]):
        top = onp.argsort(-s[t])[:4]
        for e in top:
            if e in held:
                h = x[t] @ w["w1"][e]
                h = h / (1 + onp.exp(-h)) * (x[t] @ w["w3"][e])
                y[t] += 2.5 * s[t, e] / s[t, top].sum() * (h @ w["w2"][e])
    h = x @ w["s1"].T
    return y + (h / (1 + onp.exp(-h)) * (x @ w["s3"].T)) @ w["s2"].T


def test_sigmoid_scored_experts_beside_a_shared_one_are_the_formula():
    w = {k: onp.asarray(v, onp.float64) for k, v in _moe_weights().items()}
    x = onp.asarray(_normal(9, 1, 24, 32))
    with jax.default_matmul_precision("highest"):
        y, aux = _layer(E, 0, {k: jnp.asarray(v, jnp.float32)
                               for k, v in w.items()})(NDArray(x))
    onp.testing.assert_allclose(y.asnumpy()[0], _plain_moe(x[0], w),
                                atol=2e-5)
    assert int(aux["held_pairs"]) == 24 * 4
    assert float(aux["router_loss"]) > 0


def test_a_block_made_again_routes_its_backward_as_its_forward_did():
    """Under the recomputation policy of a marked block the selection is
    kept, so the backward runs no second top-k: scores made again may
    differ in the last bit and flip a near tie, and the gradient would
    then be of another routing than the loss's."""
    w = _moe_weights()
    x = _normal(9, 24, 32)

    def f(x, r):
        return jnp.sum(experts.sigmoid_route(x, r, 4, 2.5)[2] ** 2)

    again = jax.checkpoint(
        f, policy=jax.checkpoint_policies.save_only_these_names(
            *mx.gluon.Block._recompute_keeps))
    grad = jax.make_jaxpr(jax.grad(again, argnums=1))(x, w["r"])
    assert str(grad).count("top_k") == 1
    onp.testing.assert_allclose(jax.grad(again, argnums=1)(x, w["r"]),
                                jax.grad(f, argnums=1)(x, w["r"]),
                                rtol=1e-5, atol=1e-7)


def test_the_32_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """32 shares of 2 experts each, as 32 chips would hold them: the
    routed parts every share gives, with the shared expert (which every
    chip computes alike) counted once, add up to what the uncut layer
    gives; and a share's part is the plain formula over its own
    experts."""
    w = _moe_weights(10)
    x = _normal(19, 1, 24, 32)
    with jax.default_matmul_precision("highest"):
        whole, _ = _layer(E, 0, w)(NDArray(x))
        parts = [_layer(2, first, w)(NDArray(x))[0]
                 for first in range(0, E, 2)]
        shared = _layer(2, 0, w).shared_expert(NDArray(x))
    assert len(parts) == 32
    total = sum(p.asnumpy() for p in parts) - 31 * shared.asnumpy()
    # 32 float32 parts, each carrying the shared expert's O(1) output
    onp.testing.assert_allclose(total, whole.asnumpy(), atol=1e-4)
    wf = {k: onp.asarray(v, onp.float64) for k, v in w.items()}
    onp.testing.assert_allclose(
        parts[5].asnumpy()[0], _plain_moe(onp.asarray(x[0]), wf,
                                          range(10, 12)), atol=2e-5)


def test_laguna_config_is_the_published_pattern():
    cfg = laguna_s21_config()
    assert len(cfg.layers) == 48
    kinds = [(s.window, s.n_heads, s.ffn) for s in cfg.layers]
    assert kinds[:5] == [(0, 48, "dense"), (512, 72, "experts"),
                         (512, 72, "experts"), (512, 72, "experts"),
                         (0, 48, "experts")]
    assert kinds[4:8] == kinds[44:48]
    full, sliding = cfg.layers[0], cfg.layers[1]
    assert full.rope_yarn == (128, 8192, 32, 1, 1.4852030263919618) \
        and full.rope_fraction == 0.5 and full.rope_theta == 500000
    assert sliding.rope_yarn is None and sliding.rope_fraction == 1 \
        and sliding.rope_theta == 10000
    assert all(s.head_gate and s.shared_hidden_dim == 1024
               and s.routed_scale == 2.5 and s.router_score == "sigmoid"
               for s in cfg.layers)
    assert len(laguna_s21_config(n_layers=5).layers) == 5
    # the per-layer facts live in the one spec: the flat fields are the
    # ones every other model has, and a flat model's layers are the
    # default spec
    assert [f.name for f in dataclasses.fields(LlamaConfig)][-1] == "layers"
    assert LlamaConfig().layer_spec(3) == LayerSpec()
    with pytest.raises(ValueError, match="layer specs"):
        TransformerLM(dataclasses.replace(tiny_config(), layers=(
            LayerSpec(),)))


def _toy_laguna(dtype="float32"):
    from builders import window_moe_decoder as builder
    import common
    m = common.load_json(common.HERE, "configs", "laguna_s21.json")
    m.update({"hidden_size": 64, "num_key_value_heads": 2, "head_dim": 32,
              "num_attention_heads_per_layer": [4, 6, 6, 6, 4] + [4] * 43,
              "num_attention_heads": 4, "intermediate_size": 96,
              "moe_intermediate_size": 16,
              "shared_expert_intermediate_size": 16, "router_width": 16,
              "num_experts": 16, "first_expert_held": 0,
              "num_experts_per_tok": 4, "vocab_size": 80,
              "sliding_window": 20, "init_std": 0.05, "param_dtype": dtype,
              "seq_len": 64})
    return m, builder


def test_the_whole_model_is_the_reference_in_logits_and_loss():
    """Five Laguna layers at toy widths (a full dense layer, three
    sliding and one full MoE layer, every expert held) in float32:
    ``TransformerLM``'s logits and loss against the benchmark's plain
    reference, block by block, from the same seeded weights."""
    import common
    from reference import window_moe_decoder as ref
    m, builder = _toy_laguna()
    specs = ref.leaf_specs(m)
    weights = common.make_weights(5, specs)
    net = TransformerLM(builder.library_config(m))
    ps = net.collect_params()
    for name, value in weights.items():
        ps[builder._program_name(name)].set_data(NDArray(value))
    tokens = jax.random.randint(jax.random.key(3), (1, 65), 0, 80)
    x, y = tokens[:, :-1], tokens[:, 1:]
    how = {"attention": "window", "gate": True, "shared_expert": True}
    block, exits, _ = ref._pieces(m, "f32", how)
    with jax.default_matmul_precision("highest"):
        logits = net(NDArray(x)).asnumpy()
        loss, parts = net.loss(NDArray(x), NDArray(y), chunk=32)
        h, aux = weights["embed"][x[0]], 0.0
        for i, kind in enumerate(ref.layers(m)):
            h, a = block(h, ref._layer(weights, i, kind[2]), kind)
            aux = aux + a / 4
        z = ref._rms(h, weights["final_norm"], m["rms_norm_eps"])
        want = z @ weights["lm_head"].T
        want_loss = exits(h, weights["final_norm"], weights["lm_head"],
                          y[0], 1.0)[0] + aux
    onp.testing.assert_allclose(logits[0], want, atol=1e-4)
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-5)
    assert float(parts["router_loss"]) == pytest.approx(float(aux),
                                                        rel=1e-4)


def test_the_cached_path_refuses_sliding_layers():
    from mxnet_tpu.models.kv_cache import CacheSpec, CacheView, init_pools
    m, builder = _toy_laguna()
    net = TransformerLM(builder.library_config(m))
    net.initialize()
    spec = CacheSpec(n_layers=5, n_kv_heads=2, head_dim=32, slots=1,
                     pages=4, page_size=16, max_pages_per_slot=4,
                     dtype="float32")
    k, v = init_pools(spec)
    view = CacheView("prefill", k, v, spec.page_size,
                     page_row=jnp.arange(4, dtype=jnp.int32),
                     true_len=jnp.int32(8))
    with pytest.raises(NotImplementedError, match="ROADMAP N5"):
        net(NDArray(jnp.zeros((1, 8), jnp.int32)), cache=view)
