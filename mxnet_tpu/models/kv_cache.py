"""Paged KV cache — the decode substrate for ``mx.serve``.

Full-sequence ``TransformerLM.forward(tokens)`` pays O(T) recompute per
generated token and cannot share a batch across requests.  This module
gives the model an incremental path: a **paged** KV cache (vLLM-shaped)
whose storage is a fixed pool of fixed-size pages holding the
*un-repeated* GQA KV blocks (H_kv heads, exactly what the Pallas
attention kernels consume), indexed per batch slot through a page
table.  Decode is then O(1) in generated length: every buffer in the
decode program has the pool shape, never a sequence-dependent one —
the property ``tests/test_serve.py`` pins on the lowered program.

Layout (single pool shared by all layers along a leading L axis)::

    k_pages, v_pages : (L, P, H_kv, page_size, D)   the pool
    page_table       : (S, MP) int32                 slot -> page ids
    lengths          : (S,) int32                    valid tokens/slot

Page 0 is the **trash page**: writes of padding tokens (prefill past
``true_len``) and of inactive decode slots are routed there, so a
fixed-shape scatter needs no host-side masking and a freed slot's
stale page-table row can never corrupt a live slot's pages.  The
allocator (``serve.SlotScheduler``) never hands out page 0.

Everything here is pure array code (functional updates — callers
thread the returned pools), so the whole prefill/decode step jits into
one program; the host-side scheduler owns the page table and lengths.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

#: page id every masked (padding / inactive-slot) write is routed to
TRASH_PAGE = 0


@dataclass
class CacheSpec:
    """Static shape of a paged cache pool (one serving replica)."""

    n_layers: int
    n_kv_heads: int
    head_dim: int
    slots: int            # batch slots (S)
    pages: int            # pool pages (P), page 0 reserved as trash
    page_size: int        # tokens per page
    max_pages_per_slot: int  # page-table width (MP)
    dtype: str = "float32"

    @property
    def max_context(self):
        return self.max_pages_per_slot * self.page_size

    def pages_for(self, tokens):
        """Pages needed to hold ``tokens`` cache entries."""
        return -(-int(tokens) // self.page_size)


def init_pools(spec: CacheSpec):
    """Zeroed (k_pages, v_pages) pools of the spec's fixed shape."""
    # heads OUTSIDE the (page_size, D) minor dims: the Pallas decode
    # kernel blocks one (page, head) tile at a time, and Mosaic wants
    # the blocked axes to be the two minor ones
    shape = (spec.n_layers, spec.pages, spec.n_kv_heads,
             spec.page_size, spec.head_dim)
    dt = jnp.dtype(spec.dtype)
    return jnp.zeros(shape, dt), jnp.zeros(shape, dt)


@jax.named_scope("kv_write")
def write_prompt(pages, layer, page_row, kv, true_len, page_size):
    """Scatter one prompt's per-layer K (or V) into its slot's pages.

    pages: (L, P, Hkv, psz, D) pool; page_row: (MP,) int32 page ids for
    the slot; kv: (T, Hkv, D) freshly computed (post-RoPE, un-repeated);
    token t lands in page ``page_row[t // psz]`` at offset ``t % psz``.
    Tokens at or past ``true_len`` (ladder padding) go to the trash
    page, so the scatter shape is static for the whole ladder entry.
    """
    T = kv.shape[0]
    t = jnp.arange(T, dtype=jnp.int32)
    dest = jnp.where(t < true_len, page_row[t // page_size],
                     jnp.int32(TRASH_PAGE))
    return pages.at[layer, dest, :, t % page_size].set(kv)


@jax.named_scope("kv_write")
def write_chunk(pages, layer, page_row, kv, true_len, page_size, start):
    """Scatter a prompt SUFFIX (chunk prefill — the prefix-cache path
    where positions below ``start`` already sit in cached pages).

    kv: (T, Hkv, D) for chunk tokens 0..T-1; chunk token t is absolute
    position ``start + t`` and lands in page
    ``page_row[(start + t) // psz]`` at offset ``(start + t) % psz``.
    Tokens at or past ``true_len`` (ladder padding) go to the trash
    page.  With ``start == 0`` this degenerates to
    :func:`write_prompt`; it is a separate function so the plain
    prefill program stays bitwise-unchanged."""
    T = kv.shape[0]
    t = jnp.arange(T, dtype=jnp.int32)
    pos = start + t
    idx = jnp.clip(pos // page_size, 0, page_row.shape[0] - 1)
    dest = jnp.where(t < true_len, page_row[idx],
                     jnp.int32(TRASH_PAGE))
    return pages.at[layer, dest, :, pos % page_size].set(kv)


@jax.named_scope("kv_write")
def write_token(pages, layer, page_table, lengths, kv, active, page_size):
    """Scatter one decode step's per-layer K (or V), one token per slot.

    kv: (S, Hkv, D); slot s's token lands at cache position
    ``lengths[s]`` (page ``page_table[s, lengths[s] // psz]``).
    Inactive slots write to the trash page — their page-table rows may
    be stale (freed and reassigned), so routing by ``active`` is a
    correctness rule, not an optimization.
    """
    pos = lengths.astype(jnp.int32)
    idx = jnp.clip(pos // page_size, 0, page_table.shape[1] - 1)
    dest = jnp.where(active,
                     jnp.take_along_axis(page_table, idx[:, None],
                                         axis=1)[:, 0],
                     jnp.int32(TRASH_PAGE))
    return pages.at[layer, dest, :, pos % page_size].set(kv)


class CacheView:
    """The cache as the model's forward sees it: one object threaded
    through the layer stack, holding the (traced) pools plus the
    slot/position metadata of the current call.  Each ``Attention``
    block rebinds ``.k``/``.v`` with its functional update — after the
    trace the caller reads the final pools back out.

    mode "prefill": one request, ``x`` is (1, T, dim); ``page_row``
    (MP,) and scalar ``true_len`` place the prompt.  mode "chunk": a
    prompt SUFFIX starting at absolute position ``start`` (the
    prefix-cache path — earlier positions are read from cached pages,
    shared ones unchanged); same metadata plus scalar ``start``.
    mode "decode": one token per slot, ``x`` is (S, 1, dim);
    ``page_table`` (S, MP), ``lengths`` (S,) and ``active`` (S,) bool
    drive per-slot RoPE offsets, the paged write, and the paged
    attention read.
    """

    def __init__(self, mode, k, v, page_size, page_row=None,
                 true_len=None, page_table=None, lengths=None,
                 active=None, start=None):
        if mode not in ("prefill", "chunk", "decode"):
            raise ValueError("CacheView mode must be "
                             "prefill|chunk|decode, got %r" % mode)
        self.mode = mode
        self.k = k
        self.v = v
        self.page_size = page_size
        self.page_row = page_row
        self.true_len = true_len
        self.page_table = page_table
        self.lengths = lengths
        self.active = active
        self.start = start
