"""DeepSeek Sparse Attention (DeepSeek-V3.2-Exp technical report, section
2.1): a lightning indexer scores every earlier key for every query, each
query keeps its ``topk`` best, and the attention runs over those alone.

    q^I_{t,j} = RoPE_half(W^I_q u_t)_j         (heads j of width d_I)
    k^I_s     = RoPE_half(LayerNorm(W^I_k u_s))  (one key head)
    w_{t,j}   = (W^I_w u_t)_j / sqrt(heads)
    I_{t,s}   = sum_j w_{t,j} relu(q^I_{t,j} . k^I_s / sqrt(d_I)),  s <= t
    S_t       = the min(topk, t + 1) positions s <= t of largest I_{t,s},
                exactly, ties to the lower position; one set for all heads
    o_{t,h}   = sum_{s in S_t} softmax_{S_t}(q_{t,h} . k_{s,g(h)} / sqrt(D))
                v_{s,g(h)}
    L^I       = mean_t KL(p_t || softmax_{S_t} I_t),
                p_t = stopgrad(mean_h of the attention's weights over S_t)

The indexer reads ``u`` detached, so ``L^I`` is the only gradient its
leaves get (the selection is discrete).  ``RoPE_half`` rotates the first
half of a head's width and leaves the rest.

Scoring and selection go 512 queries at a time (``select``): a chunk's
scores against every key up to its last query are one call of the
``dsa_index`` kernel (``ops.pallas_ops.index_scores``), and its top
``topk`` an exact ``lax.top_k`` — over segments of 8,192 keys and then
over their winners where the row is longer (the same set: a key in the
row's top ``topk`` is in its segment's).  Chunks whose rows end in the
same 8,192-key span are one ``lax.map``; the whole (T, T) score matrix
never exists.  Where the attention's backward walks the causal tiles
(``ops.pallas_ops.sparse_attention_takes_bits``: the sparse cell's
shape), the same chunk function also packs S_t as one bit a key, from
the chunk's score rows and the top-k's last score.  The attention is
``ops.pallas_ops.sparse_attention``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..ops.pallas_ops import (ATTENTION_KERNEL_OUT, index_scores,
                              index_scores_dense, pack_selection,
                              sparse_attention, sparse_attention_takes_bits)

#: queries a chunk of scoring, selection and the indexer loss
SCORE_CHUNK = 512
#: keys a segment of the first stage of a long row's top-k, and the span
#: that rows of one ``lax.map`` end in
SEGMENT = 8192


def exact_top_k(s, k, segment=SEGMENT):
    """``lax.top_k(s, k)`` over the last axis as a set (values and
    positions; ties to the lower position), in two stages where the row
    is whole segments and longer than one: each segment's top ``k``,
    then the top ``k`` of those, whose candidates lie segment by segment
    so that a tie still goes to the lower position."""
    L = s.shape[-1]
    n = L // segment
    if n < 2 or L % segment or k >= segment:
        return jax.lax.top_k(s, k)
    v, i = jax.lax.top_k(s.reshape(s.shape[:-1] + (n, segment)), k)
    i = i + (jnp.arange(n, dtype=i.dtype) * segment)[:, None]
    v = v.reshape(s.shape[:-1] + (n * k,))
    i = i.reshape(s.shape[:-1] + (n * k,))
    v, j = jax.lax.top_k(v, k)
    return v, jnp.take_along_axis(i, j, axis=-1)


def _spans(T, chunk, topk):
    """``[(first chunk, last chunk + 1, keys)]``: the chunks of ``chunk``
    queries whose causal rows end within the same ``SEGMENT`` span, and
    the keys their rows are cut to."""
    span = max(SEGMENT, topk)
    out, c = [], 0
    n = T // chunk
    while c < n:
        end = min(T, -(-(c + 1) * chunk // span) * span)
        hi = min(n, end // chunk)
        out.append((c, hi, end))
        c = hi
    return out


def _chunk_of(a, c, chunk, axis):
    return jax.lax.dynamic_slice_in_dim(a, c * chunk, chunk, axis=axis)


def _order(x):
    """float32 -> int32 in the total order ``lax.top_k`` sorts by (-0
    below +0)."""
    i = jax.lax.bitcast_convert_type(x, jnp.int32)
    return i ^ ((i >> 31) & 0x7FFFFFFF)


def _selected(s, vals, idx, q0):
    """A chunk's selection from its score rows ``s`` (queries at
    ``q0``): the earlier keys whose score beats the top-k's last, tau,
    or ties it at a position no later than the last tied key the top-k
    took (ties go to the lower position).  A query with fewer than topk
    earlier keys has tau -inf, its empty slots' indices future
    positions."""
    tau = _order(vals[:, -1:])
    last = jnp.max(jnp.where(_order(vals) == tau, idx, -1), axis=1,
                   keepdims=True)
    key = jnp.arange(s.shape[1])[None, :]
    o = _order(s)
    sel = ((o > tau) | ((o == tau) & (key <= last))) \
        & (key <= q0 + jnp.arange(s.shape[0])[:, None])
    return jnp.pad(sel, ((0, 0), (0, -s.shape[1] % 256)))


def select(qi, ki, w, topk, chunk=SCORE_CHUNK, bits=False):
    """One sequence's selection: ``(idx (T, topk) int32, vals (T, topk)
    float32, n_valid (T,) int32, bits)``, ``idx[t, :n_valid[t]]`` the
    positions of S_t and ``vals`` their scores (the empty slots of a
    query with fewer than ``topk`` earlier keys come last and read
    -inf).  ``bits``: the same sets one bit a (query, key), (T / 32, T)
    uint32 in ``pallas_ops.pack_selection``'s layout, made from each
    chunk's score rows beside its top-k (T whole bands of 256), else
    None.  ``qi`` (heads, T, d_I) and ``ki`` (T, d_I) after rotary, ``w``
    (T, heads) with both scales folded in."""
    T = ki.shape[0]
    chunk = min(chunk, T)
    if T % chunk or topk > T or (bits and T % 256):
        raise ValueError("select: %d tokens are not whole chunks of %d "
                         "(and, for bits, of 256) or fewer than topk %d"
                         % (T, chunk, topk))
    # the scores select; their gradient is the indexer loss's own
    qi, ki, w = (jax.lax.stop_gradient(a) for a in (qi, ki, w))
    vals, idx, words = [], [], []
    for lo, hi, keys in _spans(T, chunk, topk):
        def one(c, keys=keys):
            s = index_scores(_chunk_of(qi, c, chunk, 1), ki[:keys],
                             _chunk_of(w, c, chunk, 0), q0=c * chunk)
            v, i = exact_top_k(s, topk)
            if not bits:
                return v, i
            return v, i, pack_selection(_selected(s, v, i, c * chunk))

        out = jax.lax.map(one, jnp.arange(lo, hi))
        vals.append(out[0].reshape(-1, topk))
        idx.append(out[1].reshape(-1, topk))
        if bits:
            cols = jnp.moveaxis(out[2], 0, 1).reshape(out[2].shape[1], -1)
            words.append(jnp.pad(cols, ((0, T // 32 - cols.shape[0]),
                                        (0, 0))))
    n_valid = jnp.minimum(topk, jnp.arange(T, dtype=jnp.int32) + 1)
    return (jnp.concatenate(idx).astype(jnp.int32), jnp.concatenate(vals),
            n_valid, jnp.concatenate(words, axis=1) if bits else None)


def _kl_parts(vals, p, n_valid):
    valid = jnp.arange(vals.shape[1])[None, :] < n_valid[:, None]
    logq = jax.nn.log_softmax(jnp.where(valid, vals, -jnp.inf), axis=-1)
    return valid, jnp.where(valid, logq, 0.0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7,))
def _index_loss(qi, ki, w, idx, vals, p, n_valid, chunk):
    valid, logq = _kl_parts(vals, p, n_valid)
    kl = jnp.sum(jnp.where(valid, jax.scipy.special.xlogy(p, p)
                           - p * logq, 0.0), axis=-1)
    return jnp.mean(kl)


def _index_loss_fwd(qi, ki, w, idx, vals, p, n_valid, chunk):
    return _index_loss(qi, ki, w, idx, vals, p, n_valid, chunk), \
        (qi, ki, w, idx, vals, p, n_valid)


def _index_loss_bwd(chunk, res, g):
    """d/dI_{t,s} of the mean KL is (softmax_{S_t} I_t - p_t)_s / T on
    the selected pairs and 0 elsewhere: scattered into each chunk's row
    of scores and carried back through the scores' XLA form."""
    qi, ki, w, idx, vals, p, n_valid = res
    T = ki.shape[0]
    valid, logq = _kl_parts(vals, p, n_valid)
    gsel = jnp.where(valid, jnp.exp(logq) - p, 0.0) * (g / T)
    dq, dw, dk = [], [], jnp.zeros(ki.shape, jnp.float32)
    for lo, hi, keys in _spans(T, chunk, idx.shape[1]):
        def one(dk_keys, c, keys=keys):
            qc, wc = _chunk_of(qi, c, chunk, 1), _chunk_of(w, c, chunk, 0)
            dI = jnp.zeros((chunk, keys), jnp.float32).at[
                jnp.arange(chunk)[:, None], _chunk_of(idx, c, chunk, 0)
            ].add(_chunk_of(gsel, c, chunk, 0))
            _, back = jax.vjp(lambda a, b, e: index_scores_dense(
                a, b, e, causal=False), qc, ki[:keys], wc)
            dqc, dkc, dwc = back(dI)
            return dk_keys + dkc, (dqc, dwc)

        dk_keys, (dqc, dwc) = jax.lax.scan(
            one, jnp.zeros((keys, ki.shape[1]), jnp.float32),
            jnp.arange(lo, hi))
        dk = dk.at[:keys].add(dk_keys)
        dq.append(jnp.moveaxis(dqc, 0, 1).reshape(qi.shape[0], -1,
                                                  qi.shape[2]))
        dw.append(dwc.reshape(-1, w.shape[1]))
    import numpy as onp
    zero = onp.zeros(idx.shape, jax.dtypes.float0)
    return (jnp.concatenate(dq, axis=1).astype(qi.dtype),
            dk.astype(ki.dtype), jnp.concatenate(dw).astype(w.dtype), zero,
            jnp.zeros_like(vals), jnp.zeros_like(p),
            onp.zeros(n_valid.shape, jax.dtypes.float0))


_index_loss.defvjp(_index_loss_fwd, _index_loss_bwd)


def index_loss(qi, ki, w, idx, vals, p, n_valid, chunk=SCORE_CHUNK):
    """``L^I`` of one sequence, the mean over its queries of ``KL(p_t ||
    softmax over S_t of I_t)``: the value from the selection's own
    scores ``vals``, the gradient (to ``qi``, ``ki``, ``w`` alone)
    through the scores made again a chunk at a time.  ``p`` (T, topk) is
    the attention's head-mean weight of each slot."""
    return _index_loss(qi, ki, w, idx, jax.lax.stop_gradient(vals),
                       jax.lax.stop_gradient(p), n_valid,
                       min(chunk, ki.shape[0]))


def rope_half(x, positions, theta):
    """Rotary (rotate-half form) on the first half of the last axis,
    the rest as it is."""
    from .transformer import _rope
    h = x.shape[-1] // 2
    return jnp.concatenate([_rope(x[..., :h], positions, theta),
                            x[..., h:]], axis=-1)


def dsa_attention(q, k, v, qi, ki, w, topk, scale=None):
    """One batch of sequences through DSA: ``q`` (B, T, H, D), ``k``,
    ``v`` (B, T, G, D) after their norms and rotary; the indexer's
    ``qi`` (B, T, heads, d_I), ``ki`` (B, T, d_I) after theirs and ``w``
    (B, T, heads) float32 with 1 / sqrt(heads d_I) folded in.  Returns
    ``(o (B, T, H, D), L^I averaged over the sequences)``.  Named scopes:
    ``indexer`` (scoring, top-k, the selection's bits where the
    backward walks the causal tiles under them, ``indexer_loss``) and
    ``sparse_attn`` (the forward's gather of the selected rows, the
    kernels; the backward's scatter of dK/dV only where the sequence is
    too long for the bits)."""
    B, T, H, D = q.shape
    masked = sparse_attention_takes_bits(T, topk, D)
    sel = []
    with jax.named_scope("indexer"):
        for b in range(B):
            idx, vals, n_valid, bits = select(
                jnp.swapaxes(qi[b], 0, 1), ki[b], w[b], topk, bits=masked)
            sel.append((checkpoint_keep(idx), checkpoint_keep(vals),
                        n_valid, bits if bits is None
                        else checkpoint_keep(bits)))
    with jax.named_scope("sparse_attn"):
        idx = jnp.concatenate([s[0] + b * T for b, s in enumerate(sel)])
        n_valid = jnp.concatenate([s[2] for s in sel])
        bits = jnp.concatenate([s[3] for s in sel], axis=1) if masked \
            else None
        o, pbar = sparse_attention(q.reshape(B * T, H, D),
                                   k.reshape(B * T, -1, D),
                                   v.reshape(B * T, -1, D), idx, n_valid,
                                   scale=scale, bits=bits)
    with jax.named_scope("indexer"), jax.named_scope("indexer_loss"):
        loss = sum(index_loss(jnp.swapaxes(qi[b], 0, 1), ki[b], w[b],
                              sel[b][0], sel[b][1],
                              pbar[b * T:(b + 1) * T], sel[b][2])
                   for b in range(B)) / B
    return o.reshape(B, T, H, D), loss


def checkpoint_keep(x):
    """``x`` named as a value a recomputed block keeps (the selection:
    a top-k a block's backward would otherwise run again)."""
    from jax.ad_checkpoint import checkpoint_name
    return checkpoint_name(x, ATTENTION_KERNEL_OUT)
