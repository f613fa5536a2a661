"""Ring attention — context parallelism for long sequences.

The reference has NO equivalent (SURVEY.md §5: its longest-sequence tools
are fused RNN + ``_contrib_interleaved_matmul_selfatt_*``); this is the TPU
build's flagship new capability.  Q stays put, K/V blocks rotate around the
``cp`` mesh axis via ``lax.ppermute`` (ICI neighbor exchange), and the
per-step block attention is the Pallas flash kernel
(``ops/pallas_ops.flash_attention_with_lse``) with *global position
offsets* feeding its causal mask — so the (T×T) score matrix never
materializes, in forward **or** backward (the kernel's custom VJP is the
recompute-based blocked backward).  Partial results over disjoint key sets
are combined with logsumexp-weighted averaging, the mathematically exact
merge of normalized softmax attentions.

Two dimensions of scale live here:

**Causal layout.**  ``layout="striped"`` (default for causal) interleaves
tokens round the ring (rank r holds global tokens ``r, r+n, r+2n, …``,
Striped Attention, Brandon et al.): every (query-rank, key-block) pair
then does a near-identical half-triangle of causal work, so per ring step
the max/mean block work across ranks is ~1.0 instead of the contiguous
round-robin layout's ~2× critical path (rank 0 idles while rank n−1
computes full blocks — ``causal_balance`` quantifies both).  The striped
causal mask stays a *block-level offset*: with per-token striding, query
``i`` on rank ``my`` sees key ``j`` of owner ``ok`` iff ``i > j`` or
(``i == j`` and ``ok <= my``) — exactly the kernel's existing
``q_offset/k_offset`` interface with ``k_offset = (ok > my)``.
``layout="roundrobin"`` keeps the contiguous layout (A/B path; also what
non-causal attention always uses — without a mask the layouts are
mathematically identical and the stripe permutation would be pure cost).

**Hierarchical (DCN×ICI) ring.**  ``axis_name=("dcn", "cp")`` chains an
outer ring over the cross-slice DCN axis with the inner ICI ring: each
outer step moves one slice-sized K/V superblock over DCN (every rank
ppermutes its block along ``dcn`` in parallel) while the inner
double-buffered ring overlaps the transfer with a full slice's worth of
flash compute — the DCN exchange is issued *before* the inner sweep and
consumed only after it, so a slow cross-slice hop has ``n_inner``
kernel-invocations of window to hide in, instead of the single block a
flat ring would give it.  This is the only formulation where DCN-speed
hops are affordable, and is what takes the sequence beyond one slice
(ROADMAP "million-token context").
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from .. import fault as _fault
from ..ops.pallas_ops import (flash_attention_block_bwd,
                              flash_attention_with_lse)
from ..ops.pallas_ops import merge_attention_parts as _merge


def _shard_map(fn, mesh, in_specs, out_specs):
    """``jax.shard_map`` with the replication check off, as every
    per-shard body of this layer needs it (the seam the fault tests
    wrap to fail a launch)."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


LAYOUTS = ("striped", "roundrobin")

#: layouts :func:`causal_balance` can score.  "zigzag" (each rank
#: holds half-chunks ``r`` and ``2n-1-r`` of the sequence — the
#: megatron context-parallel layout) is analytic-only: its balance is
#: indistinguishable from striped's, so the ring never grew an
#: execution path for it (striped needs one permutation, zigzag two
#: half-chunk moves, for the same critical path).
BALANCE_LAYOUTS = LAYOUTS + ("zigzag",)


# ---------------------------------------------------------------------------
# striped layout: permutation + mask offsets + analytic balance
# ---------------------------------------------------------------------------

def stripe_permutation(T, n):
    """Indices such that ``x[..., perm, ...]`` is in striped order: the
    contiguous shard ``r`` of the permuted sequence holds the original
    tokens ``r, r+n, r+2n, …`` (token ``g`` lives on rank ``g % n`` at
    local position ``g // n``)."""
    if T % n:
        raise ValueError("sequence length %d not divisible by ring size %d"
                         % (T, n))
    return jnp.arange(T).reshape(T // n, n).T.reshape(-1)


def unstripe_permutation(T, n):
    """Inverse of :func:`stripe_permutation` (take with this to restore
    natural token order)."""
    if T % n:
        raise ValueError("sequence length %d not divisible by ring size %d"
                         % (T, n))
    return jnp.arange(T).reshape(n, T // n).T.reshape(-1)


def stripe_sequence(x, n, axis=2):
    """Reorder a naturally-ordered sequence axis into striped layout."""
    return jnp.take(x, stripe_permutation(x.shape[axis], n), axis=axis)


def unstripe_sequence(x, n, axis=2):
    """Undo :func:`stripe_sequence` on a striped sequence axis."""
    return jnp.take(x, unstripe_permutation(x.shape[axis], n), axis=axis)


def ring_axes(axis_name):
    """Normalize ``axis_name`` — one mesh axis or an (outer, inner)
    pair — to a validated tuple.  The single contract shared by the
    ring, the ``seq_data`` loader, and the example."""
    axes = tuple(axis_name) if isinstance(axis_name, (tuple, list)) \
        else (axis_name,)
    if len(axes) not in (1, 2):
        raise ValueError("axis_name must be one mesh axis or an "
                         "(outer, inner) pair, got %r" % (axis_name,))
    return axes


def ring_size(mesh, axis_name):
    """Total ring size: product of the mesh axes the sequence shards
    over."""
    n = 1
    for a in ring_axes(axis_name):
        n *= mesh.shape[a]
    return n


def _mask_offsets(layout, my, owner, T, Tk):
    """(q_offset, k_offset) feeding the flash kernel's causal mask for
    the block held at this ring step.

    roundrobin: global contiguous offsets — block ``owner``'s keys start
    at ``owner * Tk``.  striped: token ``i`` of rank ``my`` is global
    ``my + i*n`` vs key ``j`` of ``owner`` at ``owner + j*n``, so
    ``q >= k  ⟺  i > j or (i == j and owner <= my)`` — causal with the
    key side shifted by one exactly when the owner is a later rank."""
    if layout == "striped":
        return jnp.int32(0), (owner > my).astype(jnp.int32)
    return my * T, owner * Tk


def causal_balance(layout, inner, outer=1, block_tokens=128):
    """Analytic causal work balance of one full ring pass (host-side;
    ``tests/test_parallel.py`` holds it).  Work per (rank, step) is the number of
    unmasked score entries of that block in the given layout.  Returns
    per-step ``max/mean`` across ranks and the overall critical-path
    factor (sum of per-step maxima vs a perfectly balanced ring, 1.0 =
    every rank equally busy every step — striped ≈ 1.0, zigzag ≈ 1.0,
    roundrobin → ~2 as the ring grows)."""
    if layout not in BALANCE_LAYOUTS:
        raise ValueError("unknown layout %r" % (layout,))
    L = block_tokens
    n = inner * outer

    def work(my, owner):
        if layout == "roundrobin":
            if owner < my:
                return L * L
            return L * (L + 1) // 2 if owner == my else 0
        if layout == "zigzag":
            # each rank holds half-chunks (r, 2n-1-r) of L//2 tokens;
            # causal work at half-chunk granularity over the 2x2 pairs
            half = L // 2
            tri = half * (half + 1) // 2
            w = 0
            for cq in (my, 2 * n - 1 - my):
                for ck in (owner, 2 * n - 1 - owner):
                    if cq > ck:
                        w += half * half
                    elif cq == ck:
                        w += tri
            return w
        return L * (L + 1) // 2 if owner <= my else L * (L - 1) // 2

    steps = []
    for so in range(outer):
        for si in range(inner):
            w = []
            for o in range(outer):
                for i in range(inner):
                    owner = (((o - so) % outer) * inner
                             + (i - si) % inner)
                    w.append(work(o * inner + i, owner))
            steps.append(w)
    per_step = [max(w) * n / sum(w) for w in steps if sum(w)]
    total = sum(sum(w) for w in steps)
    crit = sum(max(w) for w in steps) * n / total
    return {"per_step_max_over_mean": [round(x, 4) for x in per_step],
            "critical_path_x": round(crit, 4)}


# ---------------------------------------------------------------------------
# flat (single-axis) double-buffered ring
# ---------------------------------------------------------------------------

def _ring_fwd_loop(q, k, v, axis_name, causal, scale, layout):
    """Double-buffered forward ring: ONE fused K/V buffer per step (half
    the collectives of the k/v-separate form), with the next block's
    exchange issued before the current block's flash kernel — the
    permute result has no consumer until the next iteration, so the TPU
    backend pairs it into async start/done with the kernel scheduled
    inside the window."""
    n = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    B, H, T, D = q.shape
    Tk = k.shape[2]
    perm = [(i, (i + 1) % n) for i in range(n)]
    acc_o = jnp.zeros((B, H, T, D), jnp.float32)
    acc_lse = jnp.full((B, H, T), -jnp.inf)

    def body(step, carry):
        acc_o, acc_lse, kv = carry
        kv_next = lax.ppermute(kv, axis_name, perm)
        owner = (my - step) % n  # whose K/V block we hold now
        q_off, k_off = _mask_offsets(layout, my, owner, T, Tk)
        o_s, lse_s = flash_attention_with_lse(
            q, kv[0], kv[1], causal=causal, scale=scale,
            q_offset=q_off, k_offset=k_off)
        acc_o, acc_lse = _merge(acc_o, acc_lse, o_s, lse_s)
        return acc_o, acc_lse, kv_next

    acc_o, acc_lse, _ = lax.fori_loop(
        0, n, body, (acc_o, acc_lse, jnp.stack((k, v))))
    return acc_o, acc_lse


def _ring_bwd_loop(q, k, v, o, lse, do, axis_name, causal, scale, layout):
    """Ring-native backward: re-rotate K/V around the ring a second
    time, accumulating dq locally while the (dk, dv) partials ride
    their own fused buffer one hop behind.  Per step the K/V prefetch
    is issued BEFORE the block's dq/dkv kernels (overlaps this step's
    compute) and the accumulated dkv hop after them (overlaps the NEXT
    step's compute) — every collective has a kernel-sized window.  The
    per-block gradients use the GLOBAL merged logsumexp
    (``flash_attention_block_bwd``), so the contributions sum exactly
    to the dense gradient."""
    n = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    B, H, T, D = q.shape
    Tk = k.shape[2]
    perm = [(i, (i + 1) % n) for i in range(n)]
    delta = jnp.sum(do.astype(jnp.float32) * o, axis=-1)
    kv0 = jnp.stack((k, v))
    dkv0 = jnp.zeros(kv0.shape, jnp.float32)
    dq0 = jnp.zeros((B, H, T, D), jnp.float32)

    def body(step, carry):
        dq, kv, dkv = carry
        kv_next = lax.ppermute(kv, axis_name, perm)
        owner = (my - step) % n
        q_off, k_off = _mask_offsets(layout, my, owner, T, Tk)
        dq_b, dk_b, dv_b = flash_attention_block_bwd(
            q, kv[0], kv[1], do, lse, delta, causal=causal, scale=scale,
            q_offset=q_off, k_offset=k_off)
        dq = dq + dq_b
        dkv = dkv + jnp.stack((dk_b, dv_b))
        dkv_next = lax.ppermute(dkv, axis_name, perm)
        return dq, kv_next, dkv_next

    dq, _, dkv = lax.fori_loop(0, n, body, (dq0, kv0, dkv0))
    # after n hops both buffers are home again: dkv holds THIS rank's
    # block gradients, accumulated by every rank that visited them
    return dq, dkv


# ---------------------------------------------------------------------------
# hierarchical (outer DCN ring × inner ICI ring)
# ---------------------------------------------------------------------------

def _ring2_fwd_loop(q, k, v, outer_axis, inner_axis, causal, scale,
                    layout):
    """Two-level forward ring.  Each outer step ppermutes the currently
    held K/V block along the (slow, cross-slice) outer axis — issued
    BEFORE the inner sweep and consumed only after it, so the DCN hop
    hides behind ``n_in`` flash kernels — while the inner sweep is the
    flat double-buffered ICI ring over the superblock currently
    resident in this slice (``n_in - 1`` neighbor hops + ``n_in``
    block kernels).  Visit order: at outer step ``so``, inner step
    ``si``, rank (o, i) holds the block of rank
    ((o−so) mod n_out, (i−si) mod n_in) — every block exactly once."""
    n_out = lax.axis_size(outer_axis)
    n_in = lax.axis_size(inner_axis)
    my_out = lax.axis_index(outer_axis)
    my_in = lax.axis_index(inner_axis)
    my = my_out * n_in + my_in
    B, H, T, D = q.shape
    Tk = k.shape[2]
    perm_out = [(i, (i + 1) % n_out) for i in range(n_out)]
    perm_in = [(i, (i + 1) % n_in) for i in range(n_in)]

    def compute(acc_o, acc_lse, kv, so, si):
        owner = ((my_out - so) % n_out) * n_in + (my_in - si) % n_in
        q_off, k_off = _mask_offsets(layout, my, owner, T, Tk)
        o_s, lse_s = flash_attention_with_lse(
            q, kv[0], kv[1], causal=causal, scale=scale,
            q_offset=q_off, k_offset=k_off)
        return _merge(acc_o, acc_lse, o_s, lse_s)

    def inner_sweep(so, acc_o, acc_lse, kv):
        def body(si, carry):
            acc_o, acc_lse, kv = carry
            kv_next = lax.ppermute(kv, inner_axis, perm_in)
            acc_o, acc_lse = compute(acc_o, acc_lse, kv, so, si)
            return acc_o, acc_lse, kv_next

        acc_o, acc_lse, kv = lax.fori_loop(0, n_in - 1, body,
                                           (acc_o, acc_lse, kv))
        acc_o, acc_lse = compute(acc_o, acc_lse, kv, so, n_in - 1)
        return acc_o, acc_lse

    acc_o = jnp.zeros((B, H, T, D), jnp.float32)
    acc_lse = jnp.full((B, H, T), -jnp.inf)
    kv0 = jnp.stack((k, v))

    def outer_body(so, carry):
        acc_o, acc_lse, kv = carry
        # DCN prefetch: no consumer until the next outer iteration —
        # the whole inner sweep is its overlap window
        kv_dcn = lax.ppermute(kv, outer_axis, perm_out)
        acc_o, acc_lse = inner_sweep(so, acc_o, acc_lse, kv)
        return acc_o, acc_lse, kv_dcn

    acc_o, acc_lse, kv = lax.fori_loop(0, n_out - 1, outer_body,
                                       (acc_o, acc_lse, kv0))
    # last outer step: no further DCN hop to issue
    acc_o, acc_lse = inner_sweep(n_out - 1, acc_o, acc_lse, kv)
    return acc_o, acc_lse


def _ring2_bwd_loop(q, k, v, o, lse, do, outer_axis, inner_axis, causal,
                    scale, layout):
    """Two-level ring-native backward.  The (dk, dv) partial buffer
    shadows K/V's trajectory: within an outer step it rides one inner
    hop behind the kernels, then completes its inner ring (one extra
    hop — re-aligning it with the superblock the DCN prefetch delivers)
    and crosses DCN after the slice's last contribution is in.  After
    ``n_out`` outer steps both buffers are home: dkv holds THIS rank's
    block gradients, accumulated by every rank that visited them."""
    n_out = lax.axis_size(outer_axis)
    n_in = lax.axis_size(inner_axis)
    my_out = lax.axis_index(outer_axis)
    my_in = lax.axis_index(inner_axis)
    my = my_out * n_in + my_in
    B, H, T, D = q.shape
    Tk = k.shape[2]
    perm_out = [(i, (i + 1) % n_out) for i in range(n_out)]
    perm_in = [(i, (i + 1) % n_in) for i in range(n_in)]
    delta = jnp.sum(do.astype(jnp.float32) * o, axis=-1)

    def compute(dq, kv, dkv, so, si):
        owner = ((my_out - so) % n_out) * n_in + (my_in - si) % n_in
        q_off, k_off = _mask_offsets(layout, my, owner, T, Tk)
        dq_b, dk_b, dv_b = flash_attention_block_bwd(
            q, kv[0], kv[1], do, lse, delta, causal=causal, scale=scale,
            q_offset=q_off, k_offset=k_off)
        return dq + dq_b, dkv + jnp.stack((dk_b, dv_b))

    def inner_sweep(so, dq, kv, dkv):
        def body(si, carry):
            dq, kv, dkv = carry
            kv_next = lax.ppermute(kv, inner_axis, perm_in)
            dq, dkv = compute(dq, kv, dkv, so, si)
            dkv_next = lax.ppermute(dkv, inner_axis, perm_in)
            return dq, kv_next, dkv_next

        dq, kv, dkv = lax.fori_loop(0, n_in - 1, body, (dq, kv, dkv))
        dq, dkv = compute(dq, kv, dkv, so, n_in - 1)
        # complete dkv's inner ring (n_in hops total): the buffer is
        # now aligned with the superblock position the outer prefetch
        # delivers, so kv and dkv cross DCN in lockstep
        dkv = lax.ppermute(dkv, inner_axis, perm_in)
        return dq, dkv

    kv0 = jnp.stack((k, v))
    dkv0 = jnp.zeros(kv0.shape, jnp.float32)
    dq0 = jnp.zeros((B, H, T, D), jnp.float32)

    def outer_body(so, carry):
        dq, kv, dkv = carry
        kv_dcn = lax.ppermute(kv, outer_axis, perm_out)
        dq, dkv = inner_sweep(so, dq, kv, dkv)
        dkv_dcn = lax.ppermute(dkv, outer_axis, perm_out)
        return dq, kv_dcn, dkv_dcn

    dq, kv, dkv = lax.fori_loop(0, n_out - 1, outer_body,
                                (dq0, kv0, dkv0))
    # last outer step: K/V has no further DCN hop to make (mirrors the
    # forward's epilogue — XLA cannot DCE a collective inside the loop,
    # so a full-trip-count loop would ship one discarded superblock
    # over the slowest link every backward); dkv still crosses DCN one
    # final time to arrive home
    dq, dkv = inner_sweep(n_out - 1, dq, kv, dkv)
    dkv = lax.ppermute(dkv, outer_axis, perm_out)
    return dq, dkv


# ---------------------------------------------------------------------------
# custom-vjp wrapper (O(local) residuals) + per-shard body
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _ring_db(q, k, v, axis_name, causal, scale, layout):
    acc_o, _ = _ring_db_fwd_loop(q, k, v, axis_name, causal, scale,
                                 layout)
    return acc_o.astype(q.dtype)


def _ring_db_fwd_loop(q, k, v, axis_name, causal, scale, layout):
    if isinstance(axis_name, tuple):
        return _ring2_fwd_loop(q, k, v, axis_name[0], axis_name[1],
                               causal, scale, layout)
    return _ring_fwd_loop(q, k, v, axis_name, causal, scale, layout)


def _ring_db_fwd(q, k, v, axis_name, causal, scale, layout):
    acc_o, acc_lse = _ring_db_fwd_loop(q, k, v, axis_name, causal, scale,
                                       layout)
    # O(local) residuals: q, the HOME K/V block, the merged output and
    # its logsumexp.  Autodiff of the loop would instead stash every
    # ROTATED K/V block it saw (n per device = the full sequence's K/V
    # on every rank — exactly the memory ring attention exists to
    # avoid) plus the per-block softmax internals on the XLA fallback.
    return acc_o.astype(q.dtype), (q, k, v, acc_o, acc_lse)


def _ring_db_bwd(axis_name, causal, scale, layout, res, do):
    q, k, v, o, lse = res
    if isinstance(axis_name, tuple):
        dq, dkv = _ring2_bwd_loop(q, k, v, o, lse, do, axis_name[0],
                                  axis_name[1], causal, scale, layout)
    else:
        dq, dkv = _ring_bwd_loop(q, k, v, o, lse, do, axis_name, causal,
                                 scale, layout)
    return (dq.astype(q.dtype), dkv[0].astype(k.dtype),
            dkv[1].astype(v.dtype))


_ring_db.defvjp(_ring_db_fwd, _ring_db_bwd)


def ring_attention_local(q, k, v, axis_name, causal=False, scale=None,
                         double_buffer=True, layout="roundrobin"):
    """Per-shard body (call under shard_map with sequence sharded on
    ``axis_name``).  q,k,v: (B, H, T_local, D).

    ``axis_name`` may be a single mesh axis or an ``(outer, inner)``
    pair — the hierarchical DCN×ICI ring (outer superblock exchange
    overlapped with a full inner sweep; see module docstring).

    ``double_buffer=True`` (default) is the communication/compute-overlap
    formulation: K and V are fused into ONE permuted buffer (half the
    collectives per ring step), the neighbor exchange of the *next*
    block is issued before the current block's flash kernel (the TPU
    backend pairs it into async ``collective-permute-start``/``done``
    with the kernel scheduled inside the window — asserted
    chip-independently by ``mx.analysis.hlo``'s overlap checks on the
    AOT-compiled artifact; see tools/hlo_snapshot.py), and the backward
    is the hand-written ring VJP: K/V re-rotate with O(local) residuals
    instead of autodiff stashing all n rotated blocks (the full
    sequence's K/V on every rank).
    ``double_buffer=False`` keeps the original two-collective autodiff
    formulation, the reference ``tests/test_parallel.py`` holds the
    fused form to; it exists for the flat ring only.

    ``layout`` names the token layout the causal mask assumes —
    "striped" expects the sequence axis already in striped order
    (:func:`stripe_sequence`); :func:`ring_attention_sharded` handles
    the permutation for natural-order callers.
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if layout not in LAYOUTS:
        raise ValueError("unknown layout %r" % (layout,))
    if isinstance(axis_name, (tuple, list)):
        axis_name = tuple(axis_name)
        if len(axis_name) == 1:
            axis_name = axis_name[0]
    if not double_buffer:
        if isinstance(axis_name, tuple):
            raise ValueError("double_buffer=False (the legacy A/B path) "
                             "supports the flat ring only")
        n = lax.axis_size(axis_name)
        my = lax.axis_index(axis_name)
        B, H, T, D = q.shape
        Tk = k.shape[2]

        acc_o = jnp.zeros((B, H, T, D), jnp.float32)
        acc_lse = jnp.full((B, H, T), -jnp.inf)
        perm = [(i, (i + 1) % n) for i in range(n)]

        def body(step, carry):
            acc_o, acc_lse, kk, vv = carry
            owner = (my - step) % n  # whose K/V block we hold at this step
            q_off, k_off = _mask_offsets(layout, my, owner, T, Tk)
            o_s, lse_s = flash_attention_with_lse(
                q, kk, vv, causal=causal, scale=scale,
                q_offset=q_off, k_offset=k_off)
            acc_o, acc_lse = _merge(acc_o, acc_lse, o_s, lse_s)
            kk = lax.ppermute(kk, axis_name, perm)
            vv = lax.ppermute(vv, axis_name, perm)
            return acc_o, acc_lse, kk, vv

        acc_o, acc_lse, _, _ = lax.fori_loop(
            0, n, body, (acc_o, acc_lse, k, v))
        return acc_o.astype(q.dtype)
    return _ring_db(q, k, v, axis_name, causal, scale, layout)


def ring_attention_sharded(q, k, v, mesh, axis_name="cp", causal=False,
                           scale=None, batch_axis=None, double_buffer=True,
                           layout=None, permute_inputs=None, _comm=None,
                           _gen=None):
    """Full ring attention via shard_map.

    q/k/v: (B, H, T, D) jax.Arrays (sequence dim will be sharded over
    ``axis_name``; batch over ``batch_axis`` if given).

    ``axis_name``: one mesh axis ("cp") for the flat ICI ring, or an
    ``("dcn", "cp")`` pair for the hierarchical two-level ring — the
    sequence shards over both axes (outer-major) and each outer step's
    cross-slice superblock exchange overlaps a full inner ICI sweep
    (module docstring).  ``double_buffer`` selects the overlap
    formulation; ``False`` is the pre-overlap two-collective flat form
    kept for A/B measurement.

    ``layout`` ("striped" default when causal, else "roundrobin")
    selects the causal block layout; striped balances per-step causal
    work across ranks (~1.0 max/mean vs roundrobin's ~2× critical
    path).  Non-causal attention always runs roundrobin — without a
    mask the layouts are mathematically identical and the stripe
    permutation would be pure cost.  ``permute_inputs`` (default True
    for striped) treats q/k/v as natural token order: they are striped
    on the way in and the output is un-striped on the way out.  Pass
    ``permute_inputs=False`` when the data is ALREADY striped — the
    production million-token path, where ``parallel.seq_data`` loads
    each shard pre-striped and no host ever holds (or permutes) the
    full sequence; the output then stays in striped order (position-
    aligned with q, so per-token losses compose unchanged).

    The collective launch is fault-guarded via ``mx.fault.retry_call``
    (the op is pure, so re-execution is always safe).  Retry covers
    errors classified as transient — injected ``collective_fail`` faults
    and anything a caller maps to ``mx.fault.TransientError``; raw XLA
    runtime errors are classified by ``mx.fault.dist.classify_xla_error``
    inside the coordinated path (a cross-slice DCN transient — connection
    reset, UNAVAILABLE, deadline exceeded — re-issues together; OOM and
    compile errors stay fatal).

    In a multi-process job the retry is generation-gated
    (``mx.fault.dist.coordinated_call``): after any failed attempt every
    process votes through the consensus barrier and re-issues the
    collective together — a solo re-entry against peers still parked in
    the original launch would deadlock the mesh.  This is the DCN seam
    of the two-level ring: the outer ``ppermute`` crosses slices, so a
    transient there surfaces on every process and the fleet re-enters
    the ring as one.  ``_comm``/``_gen`` are test seams mirroring
    ``coordinated_call``'s parameters.
    """
    axes = ring_axes(axis_name)
    n_total = ring_size(mesh, axis_name)
    if layout is None:
        layout = "striped" if causal else "roundrobin"
    if layout not in LAYOUTS:
        raise ValueError("unknown layout %r" % (layout,))
    if not causal:
        layout = "roundrobin"  # no mask -> identical math, skip the stripe
    if layout == "striped":
        if q.shape[2] != k.shape[2]:
            raise ValueError(
                "striped layout needs equal q/k sequence lengths, got "
                "%d vs %d" % (q.shape[2], k.shape[2]))
        if permute_inputs is None:
            permute_inputs = True
    else:
        permute_inputs = False
    if permute_inputs:
        perm = stripe_permutation(q.shape[2], n_total)
        q, k, v = (jnp.take(a, perm, axis=2) for a in (q, k, v))

    body_axis = axes[0] if len(axes) == 1 else axes
    spec = P(batch_axis, None, body_axis, None)
    fn = functools.partial(ring_attention_local, axis_name=body_axis,
                           causal=causal, scale=scale,
                           double_buffer=double_buffer, layout=layout)

    def attempt():
        _fault.collective_check("ring_attention")
        return _shard_map(fn, mesh, (spec, spec, spec), spec)(q, k, v)

    if _comm is not None or jax.process_count() > 1:
        from .. import fault_dist as _fdist
        # lease=True: with step-granularity consensus armed and ACTIVE
        # (mx.fault.dist.enable_step_lease) the success path skips the
        # per-op vote — the launch is covered by the step-boundary
        # aggregate vote; otherwise per-op voting as before.  Test
        # seams that drive explicit comms/gens stay on per-op voting.
        out = _fdist.coordinated_call(attempt, op="ring_attention",
                                      comm=_comm, gen=_gen,
                                      lease=(_comm is None and
                                             _gen is None) or None)
    else:
        # no per-attempt timeout: an abandoned attempt thread would
        # issue a second identical collective concurrently on the same
        # mesh
        out = _fault.retry_call(attempt, op="ring_attention",
                                policy=_fault.mutating_policy())
    if permute_inputs:
        out = jnp.take(out, unstripe_permutation(out.shape[2], n_total),
                       axis=2)
    return out
