"""Chip-independent perf evidence for the TRANSFORMER path — the
flagship long-context capability (SURVEY.md §5, BASELINE ladder 5) —
mirroring tests/test_hlo_perf.py's compiled-artifact method for ResNet.

What determines transformer TPU throughput, asserted on the artifact:

1. The TPU lowering of the flash TransformerLM carries the Mosaic flash
   kernels — one ``tpu_custom_call`` per (fwd, dq, dkv) per layer.  The
   reference's answer to attention cost is fused CUDA matmuls
   (``src/operator/contrib/transformer.cc``,
   ``_contrib_interleaved_matmul_selfatt_*``); this pins the TPU-native
   answer (Pallas online-softmax kernels) into the emitted program, with
   zero devices.
2. XLA's ``cost_analysis`` of the compiled dense train step matches the
   analytic matmul FLOP count (fwd 2*P_mm*T + 4*H*Dh*T^2 per layer;
   train = 3x) — the roofline MFU denominators in PERF.md are honest.
3. The fused LM train step donates its param+optimizer buffers (in-place
   weight update, ~1x HBM footprint) exactly like the ResNet step.
"""
import re

import numpy as onp
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import gluon, parallel
from mxnet_tpu.models import TransformerLM
from mxnet_tpu.models.transformer import LlamaConfig

from _transformer_utils import abstract_params, lm_loss_fn

B, T = 1, 512
CFG = dict(vocab_size=1024, dim=256, n_layers=2, n_heads=4, n_kv_heads=4,
           hidden_dim=512, max_seq_len=T, dtype="bfloat16")


def _net_and_params(attn_impl):
    net = TransformerLM(LlamaConfig(attn_impl=attn_impl, **CFG))
    return net, net.collect_params()


def _abstract_args(ps):
    toks = jax.ShapeDtypeStruct((B, T), jnp.int32)
    return abstract_params(ps), toks


def test_flash_kernels_in_tpu_lowering(monkeypatch):
    """The fwd+bwd TPU program of the flash TransformerLM contains the
    three Mosaic kernels (fwd, dq, dkv) once per layer.  The runtime
    backend gate is bypassed because lowering FOR tpu from a chipless
    host is exactly the scenario this evidence covers."""
    from mxnet_tpu.ops import pallas_ops
    monkeypatch.setattr(pallas_ops, "_pallas_available", lambda: True)
    net, ps = _net_and_params("flash")
    params, toks = _abstract_args(ps)
    lowered = jax.jit(jax.grad(lm_loss_fn(net, ps))).trace(
        params, toks, toks).lower(lowering_platforms=("tpu",))
    txt = lowered.as_text()
    n_calls = txt.count("tpu_custom_call")
    n_layers = CFG["n_layers"]
    assert n_calls == 3 * n_layers, \
        "expected %d Mosaic kernel calls (fwd+dq+dkv x %d layers), " \
        "found %d" % (3 * n_layers, n_layers, n_calls)
    # and the kernels replaced the dense score path: score tensors are
    # (B, H, T, T) — that exact shape must not appear in the program
    score_shape = _score_shape_re()
    assert not score_shape.search(txt), \
        "dense (B,H,T,T) score tensor alongside the flash kernels"


def _score_shape_re():
    """Regex for the (B, H, T, T) attention-score tensor shape.  The
    dense lowering REALLY produces it (asserted below), so the flash
    test's not-present check cannot go vacuously green."""
    return re.compile(r"tensor<%dx%dx%dx%dx" %
                      (B, CFG["n_heads"], T, T))


def test_dense_lowering_does_contain_score_tensor():
    """Control for the flash assertion: the dense program carries the
    (B, H, T, T) score tensor this regex hunts — proving the pattern
    matches what XLA actually emits."""
    net, ps = _net_and_params("dense")
    params, toks = _abstract_args(ps)
    txt = jax.jit(jax.grad(lm_loss_fn(net, ps))).trace(
        params, toks, toks).lower(lowering_platforms=("tpu",)).as_text()
    assert _score_shape_re().search(txt), \
        "dense lowering lost its (B,H,T,T) score tensor — regex stale"


def _analytic_fwd_matmul_flops():
    """Hardware FLOPs (2/MAC) of every matmul in one forward pass."""
    D, L = CFG["dim"], CFG["n_layers"]
    H, Hkv = CFG["n_heads"], CFG["n_kv_heads"]
    Dh = D // H
    F, V = CFG["hidden_dim"], CFG["vocab_size"]
    per_layer = (
        2 * T * D * (H * Dh)          # wq
        + 2 * 2 * T * D * (Hkv * Dh)  # wk, wv
        + 2 * T * (H * Dh) * D        # wo
        + 4 * H * Dh * T * T          # QK^T + PV (full matrix; XLA
                                      # counts causal matmuls dense too)
        + 3 * 2 * T * D * F           # SwiGLU w1, w3, w2
    )
    return B * (L * per_layer + 2 * T * D * V)  # + lm head


def test_dense_train_flops_match_analytic():
    """cost_analysis of the compiled dense fwd+bwd = ~3x analytic fwd
    matmul FLOPs (bwd does 2x fwd matmul work; softmax/RMSNorm/rope add
    a few %).  A trace regression that duplicated the forward or
    repeated KV per query head would land far outside the band."""
    net, ps = _net_and_params("dense")
    params, toks = _abstract_args(ps)
    compiled = jax.jit(jax.grad(lm_loss_fn(net, ps))).trace(
        params, toks, toks).lower().compile()
    flops = compiled.cost_analysis()["flops"]
    ratio = flops / _analytic_fwd_matmul_flops()
    assert 2.7 <= ratio <= 3.6, \
        "train flops = %.2fx analytic fwd matmuls (expect ~3x)" % ratio


def test_lm_train_step_donates_buffers():
    """The fused LM train step aliases params + AdamW state in/out —
    weights update in place, like the ResNet step (test_hlo_perf.py)."""
    mx.np.random.seed(0)
    net = TransformerLM(LlamaConfig(attn_impl="dense", **CFG))
    net.initialize()
    toks = mx.np.random.randint(0, CFG["vocab_size"], (B, T),
                                dtype="int32")
    net(toks[:, :8])  # materialize params

    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()

    def fwd(net_, tokens, labels):
        logits = net_.forward(tokens)
        V = logits.shape[-1]
        return loss_fn(logits.reshape(-1, V), labels.reshape(-1)).mean()

    step = parallel.TrainStep(net, None, mx.optimizer.AdamW(
        learning_rate=1e-4), mesh=None, forward_fn=fwd)
    ma = step.lower(toks, toks).compile().memory_analysis()
    ps = net.collect_params()
    param_bytes = sum(2 * int(onp.prod(p.shape)) for _, p in ps.items())
    # bf16 params + 2x fp32 AdamW moments ~= 5x param_bytes aliased
    assert ma.alias_size_in_bytes >= 3 * param_bytes, \
        "aliased %.1f MB < 3x param bytes %.1f MB" % (
            ma.alias_size_in_bytes / 1e6, 3 * param_bytes / 1e6)


# ---------------------------------------------------------------------------
# the flash kernels' tile loop, read from the kernels' own jaxprs
# ---------------------------------------------------------------------------

def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        for j in (v if isinstance(v, (list, tuple)) else (v,)):
            j = getattr(j, "jaxpr", j)
            if hasattr(j, "eqns"):
                yield j


def _eqns(jaxpr):
    """Every equation of a jaxpr and of what it holds (loops, branches)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn):
            yield from _eqns(sub)


def _flash_kernel_jaxpr(kind, causal=True):
    """The kernel body of one flash kernel at 2 head-rows of 1,024 x 128
    bf16 with 256 x 256 tiles, and its (block, D) operand shapes."""
    from mxnet_tpu.ops import pallas_ops
    BH, T, D, blk = 2, 1024, 128, 256
    row = jax.ShapeDtypeStruct((BH, T, D), jnp.bfloat16)
    stat = jax.ShapeDtypeStruct((BH, 1, T), jnp.float32)
    off = jax.ShapeDtypeStruct((1,), jnp.int32)
    if kind == "fwd":
        def call(q, k, v, qo, ko):
            return pallas_ops._fwd_call(q, k, v, qo, ko, causal, 0.1,
                                        bq=blk, bk=blk)
        outer = jax.make_jaxpr(call)(row, row, row, off, off)
    else:
        fn = pallas_ops._bwd_dq_call if kind == "dq" \
            else pallas_ops._bwd_dkv_call

        def call(q, k, v, do, lse, delta, qo, ko):
            return fn(q, k, v, do, lse, delta, qo, ko, causal, 0.1,
                      bq=blk, bk=blk)
        outer = jax.make_jaxpr(call)(row, row, row, row, stat, stat, off,
                                     off)
    (pallas,) = [e for e in _eqns(outer.jaxpr)
                 if e.primitive.name == "pallas_call"]
    return pallas.params["jaxpr"], (blk, D)


@pytest.mark.parametrize("kind,products", [("fwd", 2), ("dq", 3),
                                           ("dkv", 4)])
def test_flash_kernel_feeds_the_mxu_as_stored(kind, products):
    """bf16 in: every product of every tile takes bf16 operands and
    yields float32, and no (block, D) operand tile is widened first."""
    kernel, tile = _flash_kernel_jaxpr(kind)
    dots = [e for e in _eqns(kernel) if e.primitive.name == "dot_general"]
    assert len(dots) == products
    for e in dots:
        assert [v.aval.dtype for v in e.invars] == [jnp.bfloat16] * 2, e
        assert e.outvars[0].aval.dtype == jnp.float32, e
    widened = [e for e in _eqns(kernel)
               if e.primitive.name == "convert_element_type"
               and e.params["new_dtype"] == jnp.float32
               and e.invars[0].aval.shape == tile]
    assert not widened, widened


@pytest.mark.parametrize("kind", ["fwd", "dq", "dkv"])
@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
def test_flash_kernel_has_one_tile_loop(kind, causal):
    """One loop over the opposite row with every product in its body;
    the mask (iota, compare, a select over the whole score tile) is
    built there when causal and not at all when not."""
    kernel, (blk, _) = _flash_kernel_jaxpr(kind, causal)
    # a fori_loop is a while where its bounds are traced, a scan where not
    (body,) = [list(_eqns(e.params["body_jaxpr" if e.primitive.name == "while"
                                   else "jaxpr"].jaxpr))
               for e in _eqns(kernel) if e.primitive.name in ("while", "scan")]
    names = {x.primitive.name for x in body}
    assert "dot_general" in names and "exp" in names
    on_tile = {x.primitive.name for x in body
               if x.primitive.name in ("select_n", "ge", "gt", "lt", "le")
               and x.outvars[0].aval.shape == (blk, blk)}
    assert bool(on_tile) == causal and ("iota" in names) == causal, names
