"""EvaByte, a tokenizer-free byte-level LM (``config.json`` and modelling
code of ``EvaByte/EvaByte``): a Llama-class stack whose attention is EVA
(Zheng et al., "Efficient Attention via Control Variates", ICLR 2023) in
its causal chunked form, with a head of several byte predictors.

    chunk j = tokens [c j, c (j + 1)), in window floor(c j / W)
    k~_j = sum_m softmax_m(k_m . mu_h) k_m     m over the chunk, per head
    v~_j = sum_m softmax_m(k_m . phi_h) v_m
    query i in window w sees, under ONE softmax, the tokens m <= i of its
    own window exactly and the pairs (k~_j, v~_j) of every chunk of the
    windows before w.  Window 0 sees no summary.

    logits[t, k] = z_t W_head[k]   (float32), predictor k for byte t + 1 + k
    loss = mean over k of mean over t of CE(logits[t, k], byte[t + 1 + k])

The stack is :class:`~.transformer.TransformerBlock` as it is: the
configuration selects the attention (``attn_impl="eva"``), the norms'
unit offset and the float32 residual stream.  The attention is one
call of EVA's own kernels (``ops/pallas_ops.py``
``eva_flash_attention``): a query tile walks the summaries its window
sees and then its window's keys, under one running softmax; a single
window is the causal flash kernel.  Every block is marked as one that
may be made again (``Block.recompute``): a block that is keeps the
kernel's output and row sums (``Block._recompute_keeps``), and
``parallel.TrainStep`` spares as many blocks, the last first, as it
finds memory for on the device.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..ndarray.ndarray import apply_op
from ..ops.pallas_ops import eva_flash_attention, flash_attention
from .transformer import LlamaConfig, TransformerLM


def chunk_summaries(k, v, mu, phi, chunk):
    """``(k~, v~)``, each (B, H, T / chunk, D) in ``k``'s dtype: every
    chunk's keys pooled by ``softmax(k . mu)`` and its values by
    ``softmax(k . phi)`` over the chunk's tokens, softmax and sums in
    float32.  ``k``, ``v`` (B, H, T, D), ``k`` after RoPE; ``mu``, ``phi``
    (H, D).  The pooling logits carry no further scale."""
    B, H, T, D = k.shape
    kc = k.reshape(B, H, T // chunk, chunk, D)
    vc = v.reshape(B, H, T // chunk, chunk, D)

    def pooled(x, w):
        logits = jnp.einsum("bhncd,hd->bhnc", kc, w,
                            preferred_element_type=jnp.float32)
        p = jax.nn.softmax(logits, axis=-1)
        return jnp.sum(p[..., None] * x.astype(jnp.float32),
                       axis=3).astype(k.dtype)

    return pooled(kc, mu), pooled(vc, phi)


def eva_attention(q, k, v, mu, phi, window, chunk, shard=None):
    """EVA attention on (B, H, T, D), ``q`` and ``k`` after RoPE.  ``T``
    is at most ``window`` (one window: plain causal attention) or a
    multiple of it; ``window`` is a multiple of ``chunk``."""
    T = q.shape[2]
    if T <= window:
        window = T
    if T % window or window % chunk:
        raise ValueError(
            "eva attention: %d tokens are not whole windows of %d, or the "
            "window not whole chunks of %d" % (T, window, chunk))
    with jax.named_scope("eva"):
        if T == window:
            with jax.named_scope("eva_flash"):
                return flash_attention(q, k, v, causal=True, shard=shard)
        with jax.named_scope("eva_prep"):
            # the last window's chunks are seen by nobody
            ks, vs = chunk_summaries(k[:, :, :T - window],
                                     v[:, :, :T - window], mu, phi, chunk)
        with jax.named_scope("eva_flash"):
            return eva_flash_attention(q, k, v, ks, vs, window, shard=shard)


class EvaByteLM(TransformerLM):
    """Input (B, T) int byte ids.  ``forward`` gives the float32 logits
    (B, T, heads, vocab); ``loss`` the multi-byte prediction loss for
    ``TrainStep(forward_fn=...)``."""

    def __init__(self, cfg: LlamaConfig = None, **kwargs):
        super().__init__(cfg, **kwargs)
        for blk in self.layers:
            blk.recompute()

    def forward(self, tokens, cache=None):
        return self._logits(self.hidden(tokens, cache=cache))

    def _logits(self, z):
        heads, vocab = self.cfg.num_pred_heads, self.cfg.vocab_size

        def head(z, w):
            out = jnp.einsum("btd,vd->btv", z, w,
                             preferred_element_type=jnp.float32)
            return out.reshape(out.shape[:2] + (heads, vocab))

        return apply_op(head, [z, self.output.weight.data()],
                        name="byte_heads")

    def loss(self, tokens, labels, heads=False):
        """The mean over the predictors of each one's mean cross-entropy;
        ``labels`` (B, T, heads) hold, for predictor k, the byte at
        ``t + 1 + k``.  With ``heads`` also ``{"ce": (heads,)}``, every
        predictor's mean cross-entropy (``TrainStep`` hands it through
        as the step's aux)."""
        z = self.hidden(tokens)
        with jax.named_scope("mbp_loss"):
            logits = self._logits(z)

            def mean_ce(logits, y):
                logp = jax.nn.log_softmax(logits, axis=-1)
                picked = jnp.take_along_axis(
                    logp, y.astype(jnp.int32)[..., None], axis=-1)[..., 0]
                return -jnp.mean(picked, axis=(0, 1))

            ce = apply_op(mean_ce, [logits, labels], name="byte_mean_ce")
            loss = apply_op(jnp.mean, [ce], name="mbp_loss")
        return (loss, {"ce": ce}) if heads else loss
