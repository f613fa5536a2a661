"""Llama-class transformer LM, TPU-native.

Design (scaling-book recipe): params carry Megatron-style TP sharding
annotations (consumed by ``mxnet_tpu.parallel``); activations get
``with_sharding_constraint`` hints for sequence parallelism; attention can
run dense (XLA), flash (Pallas, ``mxnet_tpu.ops.pallas_ops``) or ring
(context-parallel over a ``cp`` axis) — the long-context capability the
reference lacks (SURVEY.md §5).

Reference anchors (capability, not code): the reference's closest artifacts
are ``src/operator/contrib/transformer.cc`` (fused interleaved self-attn
matmuls) and the model-parallel LSTM doc; this block supersedes both.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from .. import numpy_extension as npx
from ..gluon.block import HybridBlock
from ..gluon.nn import Dense, Embedding, LayerNorm, RMSNorm
from ..gluon.parameter import Parameter
from ..initializer import Normal
from ..ndarray.ndarray import NDArray, apply_op


@dataclass(frozen=True)
class LayerSpec:
    """What one layer is, where a model's layers differ (``LlamaConfig.
    layers``).  A field left None takes the model-wide field of the same
    meaning."""
    # query heads (None: ``n_heads``)
    n_heads: int = None
    # 0: causal over every earlier key; w > 0: the query at t sees the
    # keys t - w < s <= t (``ops.pallas_ops.window_attention``)
    window: int = 0
    # rotary: theta (None: ``rope_theta``), the share of a head's dims
    # that rotate (its first ones), and YaRN's (factor, original context,
    # beta_fast, beta_slow, attention_factor) where the frequencies are
    # scaled (``rope_frequencies``)
    rope_theta: float = None
    rope_fraction: float = 1.0
    rope_yarn: tuple = None
    # each head's output times sigmoid of a projection of the layer's
    # input, before ``wo`` (head-wise gated attention)
    head_gate: bool = False
    # "dense" or "experts" (None: routed experts every ``moe_every``-th
    # layer where ``moe_num_experts``); the experts' router scores
    # ("softmax" or "sigmoid"), the factor on their gates, and the width
    # of a shared expert beside them (0: none)
    ffn: str = None
    router_score: str = "softmax"
    routed_scale: float = 1.0
    shared_hidden_dim: int = 0


_FLAT_LAYER = LayerSpec()


@dataclass
class LlamaConfig:
    vocab_size: int = 32000
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    hidden_dim: int = 14336
    max_seq_len: int = 8192
    rope_theta: float = 500000.0
    norm_eps: float = 1e-5
    dtype: str = "bfloat16"
    # a head's width where it is not dim // n_heads
    head_dim: int = None
    # an RMSNorm over each query and key head before the rotary
    qk_norm: bool = False
    # flash is the default: the Pallas kernel fires on TPU for
    # 128-aligned seq and D in {64,128,256}, and transparently falls
    # back to dense XLA attention elsewhere (ops/pallas_ops.py gating) —
    # so dense is never worse and long-seq TPU runs get the fused kernel
    # eva (``models/evabyte.py``): exact causal attention inside a window
    # of ``window_size`` tokens, one softmax shared with a learned summary
    # of every ``chunk_size``-token chunk of all earlier windows
    # dsa (``models/dsa.py``): each query attends to the ``index_topk``
    # earlier keys an indexer of ``index_heads`` heads of
    # ``index_head_dim`` scores highest
    attn_impl: str = "flash"  # dense | flash | ring | eva | dsa
    cp_axis: str = "cp"       # mesh axis for ring attention
    index_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    # routed experts (0 = dense FFN everywhere): every ``moe_every``-th
    # block's FFN is ``moe_top_k`` of ``moe_num_experts`` SwiGLU experts
    # of width ``moe_hidden_dim`` (``models/experts.py``), of which the
    # ``moe_held`` from ``moe_first_held`` are here (0: all); the
    # router's balance loss weighs ``moe_aux_coef``
    moe_num_experts: int = 0
    moe_every: int = 2
    moe_top_k: int = 1
    moe_hidden_dim: int = 0
    moe_held: int = 0
    moe_first_held: int = 0
    moe_aux_coef: float = 0.001
    # sandwich norms: each branch's output is normed too, before the
    # residual add (four RMSNorms a block, not two)
    sandwich_norm: bool = False
    # looped decoders (``models.looped.LoopedLM``): the stack of layers
    # runs this many times over the same weights
    passes: int = 1
    # attn_impl="eva": the window and the chunk one summary stands for
    window_size: int = 0
    chunk_size: int = 0
    # linear predictors over the one final hidden state; predictor k is
    # for the token at t + 1 + k (``models.evabyte.EvaByteLM``)
    num_pred_heads: int = 1
    # the norms store their gain as its distance from one
    norm_unit_offset: bool = False
    # the residual stream's dtype where it is not ``dtype``: branches
    # compute in ``dtype`` and are added to the stream in this one
    residual_dtype: str = None
    # one LayerSpec a layer, where the layers differ (None: every layer
    # is what the fields above make it)
    layers: tuple = None

    def layer_spec(self, i):
        return self.layers[i] if self.layers is not None else _FLAT_LAYER


def llama3_8b_config(**over):
    cfg = LlamaConfig(vocab_size=128256, dim=4096, n_layers=32, n_heads=32,
                      n_kv_heads=8, hidden_dim=14336, rope_theta=500000.0)
    for k, v in over.items():
        setattr(cfg, k, v)
    return cfg


def ouro_2p6b_config(**over):
    """Ouro-2.6B (ByteDance Seed, arXiv:2510.25741; ``config.json`` of
    ``ByteDance/Ouro-2.6B``): 48 shared layers run in 4 passes
    (``total_ut_steps``), sandwich norms, 16 heads of 128 with as many
    K/V heads, SwiGLU 5632, untied embedding and head.  For
    :class:`~.looped.LoopedLM`."""
    cfg = LlamaConfig(vocab_size=49152, dim=2048, n_layers=48, n_heads=16,
                      n_kv_heads=16, hidden_dim=5632, max_seq_len=65536,
                      rope_theta=1000000.0, norm_eps=1e-6,
                      sandwich_norm=True, passes=4)
    for k, v in over.items():
        setattr(cfg, k, v)
    return cfg


def evabyte_6p5b_config(**over):
    """EvaByte 6.5B (``config.json`` of ``EvaByte/EvaByte``), a
    tokenizer-free byte-level LM: 32 layers of EVA attention (Zheng et
    al., ICLR 2023, causal chunked form: window 2,048, chunk 16) with 32
    heads of 128, SwiGLU 11,008, 320 byte ids, eight byte-prediction
    heads, norm gains stored as an offset from one, a float32 residual
    stream.  For :class:`~.evabyte.EvaByteLM`."""
    cfg = LlamaConfig(vocab_size=320, dim=4096, n_layers=32, n_heads=32,
                      n_kv_heads=32, hidden_dim=11008, max_seq_len=32768,
                      rope_theta=100000.0, norm_eps=1e-5, attn_impl="eva",
                      window_size=2048, chunk_size=16, num_pred_heads=8,
                      norm_unit_offset=True, residual_dtype="float32")
    for k, v in over.items():
        setattr(cfg, k, v)
    return cfg


def keye_vl2_30b_a3b_config(**over):
    """Keye-VL-2.0-30B-A3B's language model (``config.json`` of
    ``Kwai-Keye/Keye-VL-2.0-30B-A3B``): 48 layers of DeepSeek Sparse
    Attention (32 query and 4 K/V heads of 128 with q/k norms; an indexer
    of 16 heads of 64 keeps 2,048 keys a query) and routed experts (top 8
    of 128 SwiGLU experts of 768, no shared expert), hidden 2,048, a
    151,936-id untied vocabulary.  Text tokens: its M-RoPE is 1-D rotary.
    For :class:`~.transformer.TransformerLM`."""
    cfg = LlamaConfig(vocab_size=151936, dim=2048, n_layers=48, n_heads=32,
                      n_kv_heads=4, head_dim=128, hidden_dim=6144,
                      max_seq_len=262144, rope_theta=10000000.0,
                      norm_eps=1e-6, qk_norm=True, attn_impl="dsa",
                      index_heads=16, index_head_dim=64, index_topk=2048,
                      moe_num_experts=128, moe_every=1, moe_top_k=8,
                      moe_hidden_dim=768, moe_aux_coef=0.001)
    for k, v in over.items():
        setattr(cfg, k, v)
    return cfg


def laguna_layers(layer_types, heads, mlp_types, window, rope,
                  shared_hidden_dim, routed_scale):
    """A Laguna model's LayerSpecs from its ``config.json``'s lists:
    ``layer_types`` (``full_attention`` / ``sliding_attention``), query
    heads a layer, ``mlp_layer_types`` (``dense`` / ``sparse``), the
    sliding window, ``rope_parameters`` by layer type; every head gated,
    sparse layers sigmoid-scored experts beside a shared one."""
    specs = []
    for kind, h, mlp in zip(layer_types, heads, mlp_types):
        r = rope[kind]
        yarn = (r["factor"], r["original_max_position_embeddings"],
                r["beta_fast"], r["beta_slow"], r["attention_factor"]) \
            if r["rope_type"] == "yarn" else None
        specs.append(LayerSpec(
            n_heads=h, window=window if kind == "sliding_attention" else 0,
            rope_theta=float(r["rope_theta"]),
            rope_fraction=r["partial_rotary_factor"], rope_yarn=yarn,
            head_gate=True, ffn="experts" if mlp == "sparse" else "dense",
            router_score="sigmoid", routed_scale=routed_scale,
            shared_hidden_dim=shared_hidden_dim))
    return tuple(specs)


def laguna_s21_config(**over):
    """Laguna-S-2.1 (``config.json`` of ``poolside/Laguna-S-2.1``): 48
    layers, a full-attention layer and then three of a 512-token sliding
    window, repeated; 48 query heads on full layers and 72 on sliding
    ones, 8 K/V heads of 128, every head's output gated; YaRN rotary
    (theta 5e5, factor 128 over 8,192) on half of each head's dims on
    full layers, plain rotary (theta 1e4) on all of them on sliding ones;
    a dense SwiGLU of 12,288 in layer 0 and in every other layer the top
    10 of 256 sigmoid-scored SwiGLU experts of 1,024, gates scaled by
    2.5, beside a shared expert of 1,024; hidden 3,072, a 100,352-id
    untied vocabulary.  ``n_layers`` keeps the first layers.  For
    :class:`~.transformer.TransformerLM`."""
    n = over.pop("n_layers", 48)
    kinds = ["full_attention"] + ["sliding_attention"] * 3
    layers = laguna_layers(
        (kinds * 12)[:n], ([48] + [72] * 3) * 12, ["dense"] + ["sparse"] * 47,
        512, {"full_attention": {
            "rope_type": "yarn", "rope_theta": 500000, "factor": 128,
            "original_max_position_embeddings": 8192, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.4852030263919618,
            "partial_rotary_factor": 0.5},
            "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                                  "partial_rotary_factor": 1}},
        1024, 2.5)
    cfg = LlamaConfig(vocab_size=100352, dim=3072, n_layers=n, n_heads=48,
                      n_kv_heads=8, head_dim=128, hidden_dim=12288,
                      max_seq_len=1048576, rope_theta=500000.0,
                      norm_eps=1e-6, moe_num_experts=256, moe_top_k=10,
                      moe_hidden_dim=1024, moe_aux_coef=0.001, layers=layers)
    for k, v in over.items():
        setattr(cfg, k, v)
    return cfg


def tiny_config(**over):
    cfg = LlamaConfig(vocab_size=256, dim=64, n_layers=2, n_heads=4,
                      n_kv_heads=2, hidden_dim=128, max_seq_len=128,
                      dtype="float32")
    for k, v in over.items():
        setattr(cfg, k, v)
    return cfg


def _rope(x, positions, theta):
    """Rotary embedding on (B, T, H, D).  ``positions`` is (T,) shared
    across the batch (full-sequence path) or (B, T) per-row — the
    decode path passes each slot's own cache length, so a batch of
    requests at different depths rotates correctly in one program."""
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    if positions.ndim == 1:
        positions = positions[None, :]
    angles = positions[..., None].astype(jnp.float32) * freqs  # (B, T, d/2)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                          axis=-1)
    return out.astype(x.dtype)


def rope_frequencies(head_dim, theta, fraction=1.0, yarn=None):
    """``(inverse frequencies (rot / 2,), scale on cos and sin)`` of a
    rotary over the first ``rot = head_dim * fraction`` dims of a head.
    With ``yarn = (factor, original context, beta_fast, beta_slow,
    attention_factor)`` the frequencies are YaRN's (Peng et al. 2023, as
    ``transformers``' ``_compute_yarn_parameters`` computes them: each
    blended between the original and the original over ``factor`` by a
    ramp over the dims whose wavelength lies between ``beta_fast`` and
    ``beta_slow`` turns of the original context, its ends truncated) and
    the scale is ``attention_factor``."""
    import numpy as onp
    rot = int(head_dim * fraction)
    inv = 1.0 / theta ** (onp.arange(0, rot, 2, dtype=onp.float64) / rot)
    if yarn is None:
        return inv.astype(onp.float32), 1.0
    factor, original, fast, slow, attention_factor = yarn

    def dim_of(turns):
        return rot * math.log(original / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(dim_of(fast)), 0)
    high = min(math.ceil(dim_of(slow)), rot - 1)
    ramp = onp.clip((onp.arange(rot // 2) - low)
                    / ((high - low) or 0.001), 0, 1)
    inv = inv / factor * ramp + inv * (1 - ramp)
    return inv.astype(onp.float32), float(attention_factor)


def _rope_scaled(x, positions, inv, scale):
    """``_rope`` over a head's first ``2 * len(inv)`` dims at the given
    inverse frequencies, cos and sin times ``scale``; the other dims pass
    through.  (B, T, H, D), ``positions`` (T,)."""
    rot = 2 * inv.shape[0]
    angles = positions.astype(jnp.float32)[:, None] * jnp.asarray(inv)
    cos = (jnp.cos(angles) * scale)[None, :, None, :]
    sin = (jnp.sin(angles) * scale)[None, :, None, :]
    xf = x.astype(jnp.float32)
    x1, x2, rest = xf[..., :rot // 2], xf[..., rot // 2:rot], xf[..., rot:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos, rest],
                          axis=-1)
    return out.astype(x.dtype)


def _gate_heads(o, g):
    """Head-wise gated attention (Qiu et al. 2025, arXiv:2505.06708): head
    h's output (B, T, H * D) times sigmoid of its gate logit ``g`` (B, T,
    H), in float32."""
    B, T, H = g.shape
    gated = o.reshape(B, T, H, -1).astype(jnp.float32) \
        * jax.nn.sigmoid(g.astype(jnp.float32))[..., None]
    return gated.reshape(o.shape).astype(o.dtype)


def _sp_constraint(x, spec):
    """Sequence-parallel activation hint, applied only when a mesh scope is
    active and the axes exist on it (axis filtering delegated to
    ``parallel.sharding._valid_spec`` — one implementation of the
    drop-missing/indivisible-axes rule)."""
    from ..parallel.mesh import current_mesh
    from ..parallel.sharding import _valid_spec
    from jax.sharding import NamedSharding
    mesh = current_mesh()
    if mesh is None:
        return x
    try:
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, _valid_spec(spec, x.shape, mesh,
                                               warn=False)))
    except Exception:
        return x


def _norm(cfg):
    """One of the model's RMSNorms: over a stream held in another dtype
    than the matmuls' it gives the matmuls' dtype."""
    return RMSNorm(epsilon=cfg.norm_eps, in_channels=cfg.dim,
                   unit_offset=cfg.norm_unit_offset,
                   out_dtype=cfg.dtype if cfg.residual_dtype else None)


class Indexer(HybridBlock):
    """DSA's lightning indexer (``models/dsa.py``): ``wq`` makes
    ``index_heads`` query heads of ``index_head_dim``, ``wk`` one key
    head (LayerNorm ``k_norm``), ``weights_proj`` a weight per head."""

    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        h, d = cfg.index_heads, cfg.index_head_dim
        self.wq = Dense(h * d, use_bias=False, flatten=False,
                        in_units=cfg.dim, dtype=cfg.dtype)
        self.wk = Dense(d, use_bias=False, flatten=False, in_units=cfg.dim,
                        dtype=cfg.dtype)
        self.k_norm = LayerNorm(epsilon=1e-6, in_channels=d)
        self.weights_proj = Dense(h, use_bias=False, flatten=False,
                                  in_units=cfg.dim, dtype=cfg.dtype)


class Attention(HybridBlock):
    def __init__(self, cfg: LlamaConfig, layer_idx=0):
        super().__init__()
        self.cfg = cfg
        self.layer_idx = layer_idx
        spec = cfg.layer_spec(layer_idx)
        self.spec = spec
        head_dim = cfg.head_dim or cfg.dim // cfg.n_heads
        self.head_dim = head_dim
        self.n_heads = nh = spec.n_heads or cfg.n_heads
        if spec.window and cfg.attn_impl != "flash":
            raise ValueError("a sliding-window layer runs the window "
                             "kernels: attn_impl must be flash, not %r"
                             % cfg.attn_impl)
        # Megatron TP: qkv column-parallel, out row-parallel
        self.wq = Dense(nh * head_dim, use_bias=False,
                        flatten=False, in_units=cfg.dim, dtype=cfg.dtype)
        self.wk = Dense(cfg.n_kv_heads * head_dim, use_bias=False,
                        flatten=False, in_units=cfg.dim, dtype=cfg.dtype)
        self.wv = Dense(cfg.n_kv_heads * head_dim, use_bias=False,
                        flatten=False, in_units=cfg.dim, dtype=cfg.dtype)
        self.wo = Dense(cfg.dim, use_bias=False, flatten=False,
                        in_units=nh * head_dim, dtype=cfg.dtype)
        self.wq.weight.shard(("tp", None))
        self.wk.weight.shard(("tp", None))
        self.wv.weight.shard(("tp", None))
        self.wo.weight.shard((None, "tp"))
        if spec.head_gate:
            self.head_gate = Dense(nh, use_bias=False, flatten=False,
                                   in_units=cfg.dim, dtype=cfg.dtype)
            self.head_gate.weight.shard(("tp", None))
        if cfg.qk_norm:
            self.q_norm = RMSNorm(epsilon=cfg.norm_eps, in_channels=head_dim)
            self.k_norm = RMSNorm(epsilon=cfg.norm_eps, in_channels=head_dim)
        if cfg.attn_impl == "dsa":
            self.indexer = Indexer(cfg)
        if cfg.attn_impl == "eva":
            # a head's two pooling vectors: a chunk's keys are weighed by
            # softmax(k . mu), its values by softmax(k . phi)
            if cfg.n_kv_heads != cfg.n_heads:
                raise ValueError("eva attention pools a head's own keys: "
                                 "n_kv_heads must equal n_heads")
            for name in ("adaptive_mu_k", "adaptive_phi"):
                setattr(self, name, Parameter(
                    shape=(cfg.n_heads, head_dim), dtype=cfg.dtype,
                    init=Normal(head_dim ** -0.5), name=name)
                    .shard(("tp", None)))

    def forward(self, x, cache=None):
        cfg = self.cfg
        B, T, _ = x.shape
        q = self.wq(x)
        k = self.wk(x)
        v = self.wv(x)
        hd, nh, nkv = self.head_dim, self.n_heads, cfg.n_kv_heads
        impl, theta, cp_axis = cfg.attn_impl, cfg.rope_theta, cfg.cp_axis
        spec, window = self.spec, self.spec.window
        if spec.rope_theta is not None:
            theta = spec.rope_theta
        scaled = spec.rope_fraction != 1.0 or spec.rope_yarn is not None
        if cfg.qk_norm:
            q = self.q_norm(q.reshape(B, T, nh, hd)).reshape(B, T, nh * hd)
            k = self.k_norm(k.reshape(B, T, nkv, hd)).reshape(B, T, nkv * hd)
        if cache is not None:
            if impl in ("eva", "dsa"):
                raise NotImplementedError(
                    "%s attention has no cached path: a slot's state "
                    "would be its window's K/V and the summaries of the "
                    "windows before it (eva, ROADMAP N7), or its keys' "
                    "index keys beside its K/V (dsa, ROADMAP N8)" % impl)
            if window or scaled or spec.head_gate or theta != cfg.rope_theta:
                raise NotImplementedError(
                    "layer %d has no cached path: the paged cache keeps "
                    "every key and rotates whole heads at the model's "
                    "theta, so sliding-window layers (whose cache is a "
                    "ring of the window's pages), a per-layer, scaled or "
                    "partial rotary and gated heads are the training "
                    "path's alone (ROADMAP N5)" % self.layer_idx)
            return self._forward_cached(x, q, k, v, cache)
        if impl == "dsa":
            return self._forward_dsa(x, q, k, v)
        if scaled:
            inv, rscale = rope_frequencies(hd, theta, spec.rope_fraction,
                                           spec.rope_yarn)

        def attn(q, k, v, *pool):
            q = q.reshape(B, T, nh, hd)
            k = k.reshape(B, T, nkv, hd)
            v = v.reshape(B, T, nkv, hd)
            pos = jnp.arange(T)
            if scaled:
                q = _rope_scaled(q, pos, inv, rscale)
                k = _rope_scaled(k, pos, inv, rscale)
            else:
                q = _rope(q, pos, theta)
                k = _rope(k, pos, theta)
            # GQA: the flash kernel reads kv groups natively (no HBM
            # materialization of repeated heads); dense/ring paths
            # repeat here
            if nkv != nh and impl != "flash":
                rep = nh // nkv
                k = jnp.repeat(k, rep, axis=2)
                v = jnp.repeat(v, rep, axis=2)
            q = jnp.swapaxes(q, 1, 2)  # (B, H, T, D)
            k = jnp.swapaxes(k, 1, 2)
            v = jnp.swapaxes(v, 1, 2)
            q = _sp_constraint(q, ("dp", "tp", None, None))
            k = _sp_constraint(k, ("dp", "tp", None, None))
            v = _sp_constraint(v, ("dp", "tp", None, None))
            if impl == "ring":
                from ..parallel.mesh import current_mesh
                from ..parallel.ring import ring_attention_local
                mesh = current_mesh()
                if mesh is not None and cp_axis in mesh.shape \
                        and mesh.shape[cp_axis] > 1:
                    # inside pjit: express ring attention directly; GSPMD
                    # partitions it. For explicit control use
                    # parallel.ring_attention_sharded outside jit.
                    from ..ops.nn import dot_product_attention
                    o = dot_product_attention(q, k, v, causal=True)
                else:
                    from ..ops.nn import dot_product_attention
                    o = dot_product_attention(q, k, v, causal=True)
            elif impl == "flash" and window:
                from ..ops.pallas_ops import window_attention
                from ..parallel.sharding import kernel_shard
                with jax.named_scope("window_attn"):
                    o = window_attention(q, k, v, window,
                                         shard=kernel_shard(B, nkv))
            elif impl == "flash":
                from ..ops.pallas_ops import flash_attention
                from ..parallel.sharding import kernel_shard
                o = flash_attention(q, k, v, causal=True,
                                    shard=kernel_shard(B, nkv))
            elif impl == "eva":
                from ..parallel.sharding import kernel_shard
                from .evabyte import eva_attention
                o = eva_attention(q, k, v, *pool, cfg.window_size,
                                  cfg.chunk_size,
                                  shard=kernel_shard(B, nkv))
            else:
                from ..ops.nn import dot_product_attention
                o = dot_product_attention(q, k, v, causal=True)
            o = jnp.swapaxes(o, 1, 2).reshape(B, T, nh * hd)
            return o

        pool = [self.adaptive_mu_k.data(), self.adaptive_phi.data()] \
            if impl == "eva" else []
        o = apply_op(attn, [q, k, v] + pool, name="attention")
        if spec.head_gate:
            with jax.named_scope("attn_gate"):
                o = apply_op(_gate_heads, [o, self.head_gate(x)],
                             name="attn_gate")
        return self.wo(o)

    def _index_inputs(self, x):
        """The indexer's ``(qi (B, T, heads, d), ki (B, T, d), w (B, T,
        heads))`` of ``x``, detached: its queries and key after their
        norm and half rotary, the weights with both of the scores' scales
        folded in (``models/dsa.py``)."""
        cfg, ix = self.cfg, self.indexer
        B, T, _ = x.shape
        ih, idim, theta = cfg.index_heads, cfg.index_head_dim, cfg.rope_theta
        xd = apply_op(jax.lax.stop_gradient, [x], name="indexer_input")

        def prep(qi, ki, wi, gamma, beta):
            from ..ops.nn import layer_norm
            from .dsa import rope_half
            pos = jnp.arange(T)
            qi = rope_half(qi.reshape(B, T, ih, idim), pos, theta)
            ki = layer_norm(ki.astype(jnp.float32), gamma.astype(jnp.float32),
                            beta.astype(jnp.float32), eps=1e-6)
            ki = rope_half(ki[:, :, None, :], pos, theta)[:, :, 0]
            wi = wi.astype(jnp.float32) * (ih * idim) ** -0.5
            return qi, ki.astype(qi.dtype), wi

        return apply_op(prep, [ix.wq(xd), ix.wk(xd), ix.weights_proj(xd),
                               ix.k_norm.gamma.data(), ix.k_norm.beta.data()],
                        n_out=3, name="indexer_inputs")

    def selection(self, x):
        """The keys DSA selects for each query of ``x`` (B, T, dim), the
        attention's normed input: ``idx`` (B, T, index_topk), query t's
        first ``min(index_topk, t + 1)`` slots (``models/dsa.select``)."""
        topk = self.cfg.index_topk

        def sel(qi, ki, wi):
            from .dsa import select
            return jnp.stack([select(jnp.swapaxes(qi[b], 0, 1), ki[b], wi[b],
                                     topk)[0] for b in range(qi.shape[0])])

        return apply_op(sel, list(self._index_inputs(x)), name="selection")

    def _forward_dsa(self, x, q, k, v):
        """DeepSeek Sparse Attention (``models/dsa.py``): gives ``(out,
        {"index_loss": the indexer's KL})``.  The indexer reads ``x``
        detached: its loss is its leaves' only gradient."""
        cfg = self.cfg
        B, T, _ = x.shape
        hd, nh, nkv = self.head_dim, self.n_heads, cfg.n_kv_heads
        theta, topk = cfg.rope_theta, cfg.index_topk

        def attn(q, k, v, qi, ki, wi):
            from .dsa import dsa_attention
            pos = jnp.arange(T)
            q = _rope(q.reshape(B, T, nh, hd), pos, theta)
            k = _rope(k.reshape(B, T, nkv, hd), pos, theta)
            o, loss = dsa_attention(q, k, v.reshape(B, T, nkv, hd), qi, ki,
                                    wi, topk)
            return o.reshape(B, T, nh * hd), loss

        o, loss = apply_op(attn, [q, k, v] + list(self._index_inputs(x)),
                           n_out=2, name="attention")
        return self.wo(o), {"index_loss": loss}

    def _forward_cached(self, x, q, k, v, cache):
        """Prefill/decode through a paged KV cache (``mx.serve``).

        Prefill: the prompt's attention is self-contained (causal over
        the K/V just computed — no cache read), and the post-RoPE,
        un-repeated GQA K/V are scattered into the slot's pages.
        Decode: ONE new token per slot — RoPE at each slot's own cache
        length, the token's K/V scattered at that position, then a
        paged attention read over the slot's whole cache
        (``ops.pallas_ops.paged_attention``: Pallas page-table kernel
        on TPU, dense gather fallback elsewhere).  Both paths are pure
        functional updates: the new pools land back on ``cache``.
        """
        cfg = self.cfg
        B, T, _ = x.shape
        hd, nh, nkv = self.head_dim, self.n_heads, cfg.n_kv_heads
        theta, layer = cfg.rope_theta, self.layer_idx
        psz, mode = cache.page_size, cache.mode
        from . import kv_cache as _kvc

        if mode == "prefill":
            def prefill(q, k, v, kp, vp, page_row, true_len):
                q = _rope(q.reshape(B, T, nh, hd), jnp.arange(T), theta)
                k = _rope(k.reshape(B, T, nkv, hd), jnp.arange(T), theta)
                v = v.reshape(B, T, nkv, hd)
                kp = _kvc.write_prompt(kp, layer, page_row, k[0],
                                       true_len, psz)
                vp = _kvc.write_prompt(vp, layer, page_row, v[0],
                                       true_len, psz)
                from ..ops.pallas_ops import flash_attention
                from ..parallel.sharding import kernel_shard
                with jax.named_scope("attention"):
                    o = flash_attention(jnp.swapaxes(q, 1, 2),
                                        jnp.swapaxes(k, 1, 2),
                                        jnp.swapaxes(v, 1, 2), causal=True,
                                        shard=kernel_shard(B, nkv))
                return jnp.swapaxes(o, 1, 2).reshape(B, T, nh * hd), kp, vp

            o, new_k, new_v = apply_op(
                prefill, [q, k, v, cache.k, cache.v, cache.page_row,
                          cache.true_len], n_out=3, name="attention_prefill")
        elif mode == "chunk":
            def chunk(q, k, v, kp, vp, page_row, true_len, start):
                # prefix-cache prefill: this call computes only the
                # prompt SUFFIX from absolute position ``start``; the
                # covered prefix is read straight out of the (possibly
                # shared) cached pages
                pos = start + jnp.arange(T)
                q = _rope(q.reshape(B, T, nh, hd), pos, theta)
                k = _rope(k.reshape(B, T, nkv, hd), pos, theta)
                v = v.reshape(B, T, nkv, hd)
                kp = _kvc.write_chunk(kp, layer, page_row, k[0],
                                      true_len, psz, start)
                vp = _kvc.write_chunk(vp, layer, page_row, v[0],
                                      true_len, psz, start)
                with jax.named_scope("attention"):
                    MP = page_row.shape[0]
                    # gather the slot's pages; row i covers absolute
                    # positions [i*psz, (i+1)*psz) so masking kpos < start
                    # keeps exactly the cached prefix (our own chunk
                    # writes and trash rows land at kpos >= start)
                    kpre = kp[layer, page_row].swapaxes(1, 2) \
                        .reshape(MP * psz, nkv, hd)
                    vpre = vp[layer, page_row].swapaxes(1, 2) \
                        .reshape(MP * psz, nkv, hd)
                    kk = jnp.concatenate([kpre, k[0]], axis=0)
                    vv = jnp.concatenate([vpre, v[0]], axis=0)
                    if nkv != nh:
                        rep = nh // nkv
                        kk = jnp.repeat(kk, rep, axis=1)
                        vv = jnp.repeat(vv, rep, axis=1)
                    qf = q[0].astype(jnp.float32)       # (T, nh, hd)
                    kf = kk.astype(jnp.float32)         # (N, nh, hd)
                    scores = jnp.einsum("tnd,snd->nts", qf, kf) \
                        / math.sqrt(hd)
                    kpos = jnp.arange(MP * psz)
                    qpos = pos[:, None]                 # (T, 1)
                    pmask = jnp.broadcast_to(kpos[None, :] < start,
                                             (T, MP * psz))
                    cmask = pos[None, :] <= qpos        # causal over chunk
                    mask = jnp.concatenate([pmask, cmask], axis=1)
                    scores = jnp.where(mask[None, :, :], scores, -1e30)
                    probs = jax.nn.softmax(scores, axis=-1)
                    o = jnp.einsum("nts,snd->tnd", probs,
                                   vv.astype(jnp.float32))
                return (o.astype(v.dtype).reshape(B, T, nh * hd),
                        kp, vp)

            o, new_k, new_v = apply_op(
                chunk, [q, k, v, cache.k, cache.v, cache.page_row,
                        cache.true_len, cache.start], n_out=3,
                name="attention_chunk")
        else:
            def decode(q, k, v, kp, vp, page_table, lengths, active):
                pos = lengths.astype(jnp.int32)[:, None]  # (S, 1)
                q = _rope(q.reshape(B, T, nh, hd), pos, theta)
                k = _rope(k.reshape(B, T, nkv, hd), pos, theta)
                v = v.reshape(B, T, nkv, hd)
                kp = _kvc.write_token(kp, layer, page_table, lengths,
                                      k[:, 0], active, psz)
                vp = _kvc.write_token(vp, layer, page_table, lengths,
                                      v[:, 0], active, psz)
                from ..ops.pallas_ops import paged_attention
                from ..parallel.sharding import kernel_shard
                ctx = jnp.where(active, lengths + 1, lengths)
                # any slot may read any page: the pools shard by head only
                with jax.named_scope("attention"):
                    o = paged_attention(
                        q[:, 0], kp[layer], vp[layer], page_table, ctx,
                        shard=kernel_shard(B, nkv, batch_axis=None))
                return o.reshape(B, T, nh * hd), kp, vp

            o, new_k, new_v = apply_op(
                decode, [q, k, v, cache.k, cache.v, cache.page_table,
                         cache.lengths, cache.active], n_out=3,
                name="attention_decode")
        cache.k = new_k._data
        cache.v = new_v._data
        return self.wo(o)


class FeedForward(HybridBlock):
    def __init__(self, cfg: LlamaConfig, hidden_dim=None):
        super().__init__()
        hidden = hidden_dim or cfg.hidden_dim
        self.w1 = Dense(hidden, use_bias=False, flatten=False,
                        in_units=cfg.dim, dtype=cfg.dtype)  # gate
        self.w3 = Dense(hidden, use_bias=False, flatten=False,
                        in_units=cfg.dim, dtype=cfg.dtype)  # up
        self.w2 = Dense(cfg.dim, use_bias=False, flatten=False,
                        in_units=hidden, dtype=cfg.dtype)  # down
        self.w1.weight.shard(("tp", None))
        self.w3.weight.shard(("tp", None))
        self.w2.weight.shard((None, "tp"))

    def forward(self, x):
        return self.w2(npx.activation(self.w1(x), "silu") * self.w3(x))


class TransformerBlock(HybridBlock):
    def __init__(self, cfg: LlamaConfig, layer_idx=0):
        super().__init__()
        self.attention_norm = _norm(cfg)
        self.attention = Attention(cfg, layer_idx=layer_idx)
        self.ffn_norm = _norm(cfg)
        spec = cfg.layer_spec(layer_idx)
        use_moe = spec.ffn == "experts" if spec.ffn else (
            cfg.moe_num_experts > 0
            and layer_idx % max(1, cfg.moe_every) == 0)
        if use_moe:
            from .experts import RoutedExperts
        self.feed_forward = RoutedExperts(cfg, spec) if use_moe \
            else FeedForward(cfg)
        self._sandwich = cfg.sandwich_norm
        if cfg.sandwich_norm:
            self.attention_post_norm = _norm(cfg)
            self.ffn_post_norm = _norm(cfg)

    def forward(self, x, cache=None):
        """The block's output; where its attention or FFN gives losses
        and counts of its own (DSA's indexer, routed experts) ``(out,
        {name: value})``."""
        a, aux = _split_aux(self.attention(self.attention_norm(x),
                                           cache=cache), {})
        x = x + (self.attention_post_norm(a) if self._sandwich else a)
        f, aux = _split_aux(self.feed_forward(self.ffn_norm(x)), aux)
        out = x + (self.ffn_post_norm(f) if self._sandwich else f)
        return (out, aux) if aux else out


def _split_aux(out, aux):
    """``(value, aux + what the layer gave beside its value)``."""
    if isinstance(out, tuple):
        out, more = out
        aux = dict(aux, **more)
    return out, aux


class TransformerLM(HybridBlock):
    """Decoder-only LM.  Input: (B, T) int tokens; output: (B, T, vocab)."""

    # whether ``forward`` runs the layers ``cfg.passes`` times
    _loops = False

    def __init__(self, cfg: LlamaConfig = None, **kwargs):
        super().__init__()
        if cfg is None:
            cfg = LlamaConfig(**kwargs)
        if cfg.passes != 1 and not self._loops:
            raise ValueError("%s runs its layers once; passes=%d needs "
                             "models.LoopedLM"
                             % (type(self).__name__, cfg.passes))
        if cfg.layers is not None and len(cfg.layers) != cfg.n_layers:
            raise ValueError("%d layer specs for %d layers"
                             % (len(cfg.layers), cfg.n_layers))
        self.cfg = cfg
        self.tok_embeddings = Embedding(cfg.vocab_size, cfg.dim,
                                        dtype=cfg.dtype)
        self.tok_embeddings.weight.shard((None, "tp"))
        self.layers = []
        for i in range(cfg.n_layers):
            blk = TransformerBlock(cfg, layer_idx=i)
            setattr(self, "layer%d" % i, blk)
            self.layers.append(blk)
        self.norm = _norm(cfg)
        # predictor k's rows are [k * vocab, (k + 1) * vocab)
        self.output = Dense(cfg.vocab_size * cfg.num_pred_heads,
                            use_bias=False, flatten=False,
                            in_units=cfg.dim, dtype=cfg.dtype)
        self.output.weight.shard(("tp", None))

    def forward(self, tokens, cache=None):
        """Full-sequence logits (``cache=None``), or the incremental
        serving path: with a :class:`~.kv_cache.CacheView` the call is
        a prefill (write the prompt's K/V into the view's pages) or a
        decode step (one token per slot, O(1) in generated length) —
        the view carries the updated pools back out."""
        return self.output(self.hidden(tokens, cache=cache))

    def hidden(self, tokens, cache=None):
        """The final norm's output (B, T, dim), what the head reads."""
        return self.hidden_with_aux(tokens, cache=cache)[0]

    def hidden_with_aux(self, tokens, cache=None):
        """``(hidden, aux)``: ``aux`` sums over the blocks what each gives
        beside its output (``{"index_loss", "router_loss", "held_pairs"}``
        where its layers have them; empty otherwise)."""
        h, aux = self._embed(tokens), {}
        for blk in self.layers:
            h, more = _split_aux(blk(h, cache=cache), {})
            for k, v in more.items():
                aux[k] = aux[k] + v if k in aux else v
        return self.norm(h), aux

    def loss(self, tokens, labels, chunk=2048):
        """The training loss of next-token prediction over ``labels`` (B,
        T): the mean cross-entropy (``ops.nn``'s chunked form: the logits
        are never whole), plus what the layers add: every DSA layer's
        indexer loss, and the routed experts' balance losses averaged over
        their layers.  Gives ``(loss, parts)``: ``ce`` (1,) and those
        terms, ``held_pairs`` the (token, expert) pairs routed to experts
        held here, summed over the layers (a step's aux output)."""
        from ..ops.nn import weighted_chunked_softmax_cross_entropy
        z, aux = self.hidden_with_aux(tokens)
        n_moe = sum(not isinstance(b.feed_forward, FeedForward)
                    for b in self.layers)
        with jax.named_scope("lm_loss"):
            def ce_of(z, head, y):
                N = y.size
                total, _ = weighted_chunked_softmax_cross_entropy(
                    z.reshape(N, -1), head, y.reshape(N),
                    jnp.full((N,), 1.0 / N, jnp.float32), chunk)
                return total

            ce = apply_op(ce_of, [z, self.output.weight.data(), labels],
                          name="lm_ce")
            loss = ce
            if "index_loss" in aux:
                loss = loss + aux["index_loss"]
            if "router_loss" in aux:
                aux["router_loss"] = aux["router_loss"] / n_moe
                loss = loss + aux["router_loss"]
        return loss, dict(aux, ce=ce.reshape(1))

    def _embed(self, tokens):
        h = self.tok_embeddings(tokens)
        if self.cfg.residual_dtype:
            h = h.astype(self.cfg.residual_dtype)
        return apply_op(lambda a: _sp_constraint(a, ("dp", "sp", None)), [h],
                        name="sp_shard")

    def num_params(self):
        total = 0
        for _, p in self.collect_params().items():
            if p.shape:
                n = 1
                for d in p.shape:
                    n *= d
                total += n
        return total
