"""BERT encoder (BASELINE.json config 3: "GluonNLP BERT-base pretrain
(hybridize -> XLA HLO)").

Reference anchors: the GluonNLP BERT built on the reference's
``contrib/transformer.cc`` fused attention ops and Gluon layers; here the
encoder uses the same npx ops with a fused attention path, post-LN
(original BERT), GELU FFN, and MLM/NSP heads for pretraining.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax.numpy as jnp

from .. import numpy_extension as npx
from ..gluon.block import HybridBlock
from ..gluon.nn import Dense, Dropout, Embedding, LayerNorm
from ..ndarray.ndarray import NDArray, apply_op


@dataclass
class BertConfig:
    vocab_size: int = 30522
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    intermediate_size: int = 3072
    max_position_embeddings: int = 512
    type_vocab_size: int = 2
    dropout: float = 0.1
    layer_norm_eps: float = 1e-12
    dtype: str = "float32"


def bert_base_config(**over):
    cfg = BertConfig()
    for k, v in over.items():
        setattr(cfg, k, v)
    return cfg


def bert_tiny_config(**over):
    cfg = BertConfig(vocab_size=1024, hidden_size=128, num_layers=2,
                     num_heads=4, intermediate_size=256,
                     max_position_embeddings=128)
    for k, v in over.items():
        setattr(cfg, k, v)
    return cfg


class BertSelfAttention(HybridBlock):
    def __init__(self, cfg):
        super().__init__()
        self.cfg = cfg
        h = cfg.hidden_size
        self.qkv = Dense(3 * h, flatten=False, in_units=h, dtype=cfg.dtype)
        self.out = Dense(h, flatten=False, in_units=h, dtype=cfg.dtype)
        self.qkv.weight.shard(("tp", None))
        self.out.weight.shard((None, "tp"))
        self.dropout = Dropout(cfg.dropout)

    def forward(self, x, mask=None):
        cfg = self.cfg
        B, T, H = x.shape
        nh = cfg.num_heads
        hd = H // nh
        qkv = self.qkv(x)

        def attn(qkv_a, *mask_a):
            q, k, v = jnp.split(qkv_a.reshape(B, T, 3, nh, hd), 3, axis=2)
            q = jnp.swapaxes(q[:, :, 0], 1, 2)  # (B, nh, T, hd)
            k = jnp.swapaxes(k[:, :, 0], 1, 2)
            v = jnp.swapaxes(v[:, :, 0], 1, 2)
            if mask_a:
                from ..ops.nn import dot_product_attention
                m = mask_a[0][:, None, None, :].astype(bool)  # (B,1,1,T)
                o = dot_product_attention(q, k, v, mask=m)
            else:
                # no padding mask: the fused kernel applies (full-batch
                # pretrain/inference); falls back to dense off-TPU or
                # for unaligned seq (ops/pallas_ops.py gating)
                from ..ops.pallas_ops import flash_attention
                from ..parallel.sharding import kernel_shard
                o = flash_attention(q, k, v, causal=False,
                                    shard=kernel_shard(B, nh))
            return jnp.swapaxes(o, 1, 2).reshape(B, T, H)

        ins = [qkv] + ([mask] if mask is not None else [])
        ctx = apply_op(attn, ins, name="bert_attention")
        return self.dropout(self.out(ctx))


class BertLayer(HybridBlock):
    def __init__(self, cfg):
        super().__init__()
        self.attention = BertSelfAttention(cfg)
        self.attn_norm = LayerNorm(epsilon=cfg.layer_norm_eps,
                                   in_channels=cfg.hidden_size)
        self.inter = Dense(cfg.intermediate_size, flatten=False,
                           in_units=cfg.hidden_size, dtype=cfg.dtype)
        self.output = Dense(cfg.hidden_size, flatten=False,
                            in_units=cfg.intermediate_size, dtype=cfg.dtype)
        self.inter.weight.shard(("tp", None))
        self.output.weight.shard((None, "tp"))
        self.out_norm = LayerNorm(epsilon=cfg.layer_norm_eps,
                                  in_channels=cfg.hidden_size)
        self.dropout = Dropout(cfg.dropout)

    def forward(self, x, mask=None):
        x = self.attn_norm(x + self.attention(x, mask))
        h = npx.gelu(self.inter(x))
        return self.out_norm(x + self.dropout(self.output(h)))


class BERTModel(HybridBlock):
    """Encoder returning (sequence_output, pooled_output)."""

    def __init__(self, cfg: BertConfig = None, **kwargs):
        super().__init__()
        if cfg is None:
            cfg = BertConfig(**kwargs)
        self.cfg = cfg
        self.word_embed = Embedding(cfg.vocab_size, cfg.hidden_size,
                                    dtype=cfg.dtype)
        self.token_type_embed = Embedding(cfg.type_vocab_size,
                                          cfg.hidden_size, dtype=cfg.dtype)
        self.position_embed = Embedding(cfg.max_position_embeddings,
                                        cfg.hidden_size, dtype=cfg.dtype)
        self.embed_norm = LayerNorm(epsilon=cfg.layer_norm_eps,
                                    in_channels=cfg.hidden_size)
        self.embed_dropout = Dropout(cfg.dropout)
        self.layers = []
        for i in range(cfg.num_layers):
            layer = BertLayer(cfg)
            setattr(self, "layer%d" % i, layer)
            self.layers.append(layer)
        self.pooler = Dense(cfg.hidden_size, activation="tanh",
                            flatten=False, in_units=cfg.hidden_size,
                            dtype=cfg.dtype)

    def forward(self, tokens, token_types=None, valid_length=None):
        B, T = tokens.shape
        pos = apply_op(
            lambda t: jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32),
                                       (B, T)), [tokens], name="positions")
        emb = self.word_embed(tokens) + self.position_embed(pos)
        if token_types is not None:
            emb = emb + self.token_type_embed(token_types)
        h = self.embed_dropout(self.embed_norm(emb))
        mask = None
        if valid_length is not None:
            mask = apply_op(
                lambda vl: (jnp.arange(T)[None, :] <
                            vl[:, None]).astype(jnp.float32),
                [valid_length], name="attn_mask")
        for layer in self.layers:
            h = layer(h, mask)
        pooled = self.pooler(h[:, 0])
        return h, pooled


class BERTForPretrain(HybridBlock):
    """MLM + NSP heads (the pretrain objective of config 3)."""

    def __init__(self, cfg: BertConfig = None, **kwargs):
        super().__init__()
        self.bert = BERTModel(cfg, **kwargs)
        cfg = self.bert.cfg
        self.mlm_transform = Dense(cfg.hidden_size, flatten=False,
                                   in_units=cfg.hidden_size, dtype=cfg.dtype)
        self.mlm_norm = LayerNorm(epsilon=cfg.layer_norm_eps,
                                  in_channels=cfg.hidden_size)
        self.mlm_decoder = Dense(cfg.vocab_size, flatten=False,
                                 in_units=cfg.hidden_size, dtype=cfg.dtype)
        self.nsp = Dense(2, flatten=False, in_units=cfg.hidden_size,
                         dtype=cfg.dtype)

    def forward(self, tokens, token_types=None, valid_length=None):
        seq, pooled = self.bert(tokens, token_types, valid_length)
        h = self.mlm_norm(npx.gelu(self.mlm_transform(seq)))
        mlm_logits = self.mlm_decoder(h)
        nsp_logits = self.nsp(pooled)
        return mlm_logits, nsp_logits
