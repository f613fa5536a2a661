"""Pallas TPU kernels — the hand-scheduled hot ops.

Reference analog: the reference hand-writes CUDA for its hot ops
(``src/operator/contrib/transformer.cc`` fused attention matmuls, NVRTC
``fusion/``); on TPU, XLA fuses pointwise chains already, so Pallas is
reserved for attention, where manual VMEM blocking beats materializing the
(T×T) score matrix in HBM.

``flash_attention``: online-softmax blocked attention, forward AND
backward as Pallas kernels — the backward is recompute-based (FlashAttention
-2 style): the forward stashes only O and the per-row logsumexp; the
backward re-forms each (block_q × block_k) score tile in VMEM to produce
dq/dk/dv, so training memory stays O(T) like the forward.  The tile is
chosen per kernel from the shape (``_pick_tiles``: 512 × 512 where the
row divides by it and the resident rows leave the room — a 128 × 128
tile's fixed cost a step, not its arithmetic, held the kernels at a
fifth of the MXU); ``block_q`` / ``block_k`` only override it.

``flash_attention_with_lse`` additionally returns the logsumexp and takes
dynamic *global position offsets* for the causal mask — the building block
``parallel/ring.py`` calls per ring step, where the K/V block's global
offset is only known at runtime (it rotates around the mesh).  The custom
VJP propagates cotangents of the lse output too (the ring combine
arithmetic differentiates through lse): d/ds of lse folds into the standard
dS = P∘(dP - Δ) recurrence as Δ := rowsum(dO∘O) - dlse.

On non-TPU backends everything falls back to XLA dense attention (with an
identical lse), so tests run anywhere; set MXNET_PALLAS_INTERPRET=1 to run
the actual kernels in interpret mode on CPU.

GSPMD cannot partition a Mosaic kernel, so under a device mesh the
caller of ``flash_attention`` / ``paged_attention`` says how the kernel
is split (``shard=``, from ``parallel.sharding.kernel_shard``): the
kernel then runs per shard inside a ``shard_map`` over the mesh axes
that shard batch rows and heads.  Both are independent, so the
per-shard kernel is exact.
"""
from __future__ import annotations

import functools
import logging
import os

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from .nn import dot_product_attention

_INTERPRET = os.environ.get("MXNET_PALLAS_INTERPRET", "0") == "1"

#: the name the flash forward's ``o`` and ``lse`` carry for
#: ``jax.checkpoint`` policies: a block marked with ``Block.recompute``
#: keeps them (``gluon.Block._recompute_keeps``), so its backward
#: does not run the forward kernel a second time.  Outside a checkpoint
#: the name is an identity and lowers to nothing.
ATTENTION_KERNEL_OUT = "attention_kernel_out"
_logger = logging.getLogger(__name__)
_warned_whole = set()  # mesh shapes already told about, once per process
NEG_INF = float("-inf")

#: VMEM the TPU compiler grants one kernel unasked, and the most these
#: kernels ask for.  A v5e core has 128 MiB; the rest is left to the
#: compiler's own scratch.  tests/test_chip_compile.py compiles the
#: longest rows this admits (forward and backward) for the described chip.
_VMEM_DEFAULT = 16 << 20
_VMEM_MAX = 100 << 20
#: what the smallest tile (128 x 128) may take on top of the resident
#: rows.  It bounds the longest row (``_max_row``); a larger tile is
#: chosen only where the rows leave it room (``_pick_tiles``).
_VMEM_TILE_MIN = 4 << 20


def _pallas_available():
    """True where the kernels run: compiled on a TPU, interpreted on
    the CPU under MXNET_PALLAS_INTERPRET=1.  On a TPU a Pallas the
    installed JAX cannot import raises here instead of turning every
    attention into the dense stand-in."""
    backend = jax.default_backend()
    if _INTERPRET:
        if backend != "cpu":
            raise RuntimeError(
                "MXNET_PALLAS_INTERPRET=1 runs the kernels in the Pallas "
                "interpreter, which is for the CPU platform; unset it on "
                "%r" % backend)
        return True
    if backend != "tpu":
        return False
    from jax.experimental.pallas import tpu  # noqa: F401
    return True


def _shapes_ok(q, k):
    T, D = q.shape[-2], q.shape[-1]
    Tk = k.shape[-2]
    return (T >= 128 and Tk >= 128 and T % 128 == 0 and Tk % 128 == 0
            and D in (64, 128, 256))


def _row_bytes(D, dtype, stats):
    """VMEM per token of a kernel's two resident (T, D) rows, double-
    buffered; ``stats`` adds dkv's lse and delta rows — a (1, T) fp32 row
    pads to 8 sublanes: 2 rows x 2 buffers x 32 T."""
    return 4 * D * jnp.dtype(dtype).itemsize + (128 if stats else 0)


def _max_row(D, dtype, stats):
    """The longest row (tokens, a multiple of 128) a kernel takes, at
    the smallest tile: at D=128 bf16, 98,304 in the forward and dq
    kernels and 87,296 in dkv — so 87,296 wherever the backward runs."""
    return (_VMEM_MAX - _VMEM_TILE_MIN) // _row_bytes(D, dtype, stats) \
        // 128 * 128


def _tile_bytes(kind, bq, bk, D):
    """VMEM a kernel's own (bq, bk) tile loop may take beside the
    resident rows: the float32 score-sized tiles alive at once (forward:
    s, p, p as stored and the mask; backward: s, p, dp, ds and the two
    casts besides), the blocks the pipeline double-buffers with
    their float32 accumulators, and 1 MiB for the compiler.  An upper
    estimate: it only sets the kernel's ``vmem_limit_bytes``."""
    tiles = 6 if kind == "fwd" else 8
    return tiles * bq * bk * 4 + 8 * max(bq, bk) * D * 4 + (1 << 20)


def _tile_target(kind, loop_row):
    """The (block_q, block_k) a kernel would run if every row divided by
    it and VMEM had the room, by the length of the row its loop walks —
    what the sweep on the chip found (PERF.md section 6, PR 29; bf16,
    head_dim 64 and 128, rows of 128 to 16,384): a row of up to 1,024
    tokens is one tile (no loop is left), a longer one takes 512 x 512,
    and from 8,192 tokens the backward kernels take 1,024 x 1,024."""
    if loop_row <= 1024 or (kind != "fwd" and loop_row >= 8192):
        return 1024, 1024
    return 512, 512


def _pick_tiles(kind, T, Tk, D, dtype, block_q=None, block_k=None):
    """(block_q, block_k) of one kernel (``kind``: fwd, dq, dkv) from
    what it can see: the largest multiples of 128 that divide the rows,
    up to ``_tile_target``, shrunk (the larger side first) until the
    tile loop fits the VMEM the resident rows leave.  An explicit block
    wins and has to divide its row."""
    stats = kind == "dkv"
    resident = T if stats else Tk  # the row the kernel's loop walks
    room = _VMEM_MAX - resident * _row_bytes(D, dtype, stats)

    def candidates(n, explicit, target):
        if explicit is not None:
            explicit = min(explicit, n)
            if n % explicit:
                raise ValueError(
                    "flash_attention: a block of %d does not divide a "
                    "row of %d tokens" % (explicit, n))
            return [explicit]
        return [c for c in range(min(target, n), 127, -128)
                if n % c == 0] or [n]

    want_q, want_k = _tile_target(kind, resident)
    qs = candidates(T, block_q, want_q)
    ks = candidates(Tk, block_k, want_k)
    while _tile_bytes(kind, qs[0], ks[0], D) > max(room, _VMEM_TILE_MIN):
        if len(qs) > 1 and (qs[0] >= ks[0] or len(ks) == 1):
            qs.pop(0)
        elif len(ks) > 1:
            ks.pop(0)
        else:
            break
    return qs[0], ks[0]


def _row_params(kind, T, D, dtype, bq, bk):
    """Compiler params for a kernel that keeps two whole (T, D) rows of
    one head in VMEM, double-buffered by the pipeline — K and V in the
    forward and dq kernels, Q and dO (plus the lse and delta rows) in
    dkv — beside its (bq, bk) tile loop.  Resident rows are fetched once
    per head where a grid axis would stream them once per opposite
    block; the price is a bound on T (``_max_row``), raised here instead
    of left to the compiler."""
    from jax.experimental.pallas import tpu as pltpu
    stats = kind == "dkv"
    rows = T * _row_bytes(D, dtype, stats)
    if rows + _VMEM_TILE_MIN > _VMEM_MAX:
        raise ValueError(
            "flash_attention: a %d-token row needs %d MiB of VMEM "
            "resident in the %s kernel (head_dim %d, %s) and the kernels "
            "may use %d MiB; the largest supported length is %d tokens "
            "forward and %d with the backward — split the sequence over "
            "devices (parallel.ring_attention_sharded)"
            % (T, (rows + _VMEM_TILE_MIN) >> 20,
               "dkv" if stats else "forward/dq", D,
               jnp.dtype(dtype).name, _VMEM_MAX >> 20,
               _max_row(D, dtype, False), _max_row(D, dtype, True)))
    need = min(rows + max(_tile_bytes(kind, bq, bk, D), _VMEM_TILE_MIN),
               _VMEM_MAX)
    if need <= _VMEM_DEFAULT:
        return None
    return pltpu.CompilerParams(vmem_limit_bytes=need)


def _visible(q0, k0, shape, q_axis):
    """True where the query at position ``q0 + i`` may see the key at
    ``k0 + j``; queries run along ``q_axis`` of ``shape``."""
    qpos = q0 + jax.lax.broadcasted_iota(jnp.int32, shape, q_axis)
    kpos = k0 + jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis)
    return qpos >= kpos


def _visited(q_last, k0, bk, nk):
    """Key tiles of ``bk`` from ``k0`` that the query at ``q_last``
    sees any of: the tiles past them lie in the future, and are
    skipped."""
    return jnp.clip((q_last - k0) // bk + 1, 0, nk).astype(jnp.int32)


def _walk(tile, carry, lower, upper, n):
    """``tile(i, carry)`` over tiles ``[lower, upper)`` of a row of ``n``;
    a row that is one tile takes no loop (its tile masks what it must)."""
    if n == 1:
        return tile(0, carry)
    return jax.lax.fori_loop(lower, upper, tile, carry)


_NT = (((1,), (1,)), ((), ()))   # (m, d) x (n, d) -> (m, n)
_NN = (((1,), (0,)), ((), ()))   # (m, n) x (n, d) -> (m, d)


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


def _per_shard(kernel, shard, in_specs, out_specs):
    """``kernel`` as one call per shard of ``shard = (mesh, batch_axis,
    head_axis)`` — what ``parallel.sharding.kernel_shard`` answers for
    the mesh in scope; the specs name those axes.  In a trace that is
    manual over some of the mesh's axes already (a pipeline body over
    ``pp`` with ``tp`` left to GSPMD) the ``shard_map`` nests and takes
    the rest."""
    mesh, batch_axis, head_axis = shard
    if batch_axis is None and head_axis is None \
            and tuple(mesh.shape.items()) not in _warned_whole:
        _warned_whole.add(tuple(mesh.shape.items()))
        _logger.warning(
            "attention kernel: no axis of mesh %s shards its batch rows "
            "or heads — every device runs the WHOLE kernel", dict(mesh.shape))
    ctx = jax.sharding.get_abstract_mesh()
    if ctx.manual_axes:
        return jax.shard_map(
            kernel, mesh=ctx, in_specs=in_specs, out_specs=out_specs,
            axis_names=set(mesh.axis_names) - set(ctx.manual_axes),
            check_vma=False)
    return jax.shard_map(kernel, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


# ---------------------------------------------------------------------------
# forward kernel: (o, lse)
# ---------------------------------------------------------------------------
#
# All three kernels walk the opposite row tile by tile; causal, the tiles
# in the future are not visited and every visited one is masked (on the
# chip the mask hides behind the tile's other passes: a loop of its own
# for the tiles the diagonal spares measured 3-7% slower at 2,048 and
# 4,096 tokens, PERF.md section 6, PR 29).  Q, K, V and dO reach the MXU
# in the dtype they are stored in and every product accumulates in
# float32; p and ds are float32 and are cast to that dtype only as the
# operand of the product that consumes them.  Row statistics stay
# float32: the forward carries its running maximum and sum as (bq, 1)
# columns, and dkv's tile is the transpose of the others' so that lse and
# delta meet it as the (1, bq) rows they are stored as.

def _fwd_call(q, k, v, q_off, k_off, causal, scale, bq=None, bk=None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    BH, T, D = q.shape
    Tk = k.shape[1]
    # GQA: k/v may carry fewer heads; query row bh reads kv row bh//rep
    # via the BlockSpec index map — the repeated K/V are never
    # materialized in HBM (4x activation saving for 32q/8kv models)
    rep = BH // k.shape[0]
    bq, bk = _pick_tiles("fwd", T, Tk, D, k.dtype, bq, bk)
    nq, nk = T // bq, Tk // bk

    def kernel(qo_ref, ko_ref, q_ref, k_ref, v_ref, o_ref, lse_ref):
        q0 = qo_ref[0] + pl.program_id(1) * bq  # the block's first query
        k0 = ko_ref[0]
        qblk = q_ref[0]

        def tile(j, carry):
            acc, m_prev, l_prev = carry
            at = pl.multiple_of(j * bk, bk)
            kblk = k_ref[0, pl.ds(at, bk), :]
            vblk = v_ref[0, pl.ds(at, bk), :]
            s = _dot(qblk, kblk, _NT) * scale  # (bq, bk)
            if causal:
                s = jnp.where(_visible(q0, k0 + j * bk, (bq, bk), 0), s,
                              NEG_INF)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            if causal:  # a row may have seen no key yet
                m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
                alpha = jnp.where(jnp.isneginf(m_prev), 0.0,
                                  jnp.exp(m_prev - m_safe))
            else:       # m_new is finite: exp(-inf - m_new) is 0
                m_safe = m_new
                alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_safe)  # 0 where masked: s is -inf
            l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
            acc = acc * alpha + _dot(p.astype(vblk.dtype), vblk, _NN)
            return acc, m_new, l_new

        carry = (jnp.zeros((bq, D), jnp.float32),
                 jnp.full((bq, 1), NEG_INF, jnp.float32),
                 jnp.zeros((bq, 1), jnp.float32))
        acc, m, l = _walk(
            tile, carry, 0,
            _visited(q0 + bq - 1, k0, bk, nk) if causal else nk, nk)
        l_safe = jnp.where(l == 0, 1.0, l)
        o_ref[0] = (acc / l_safe).astype(o_ref.dtype)
        lse = jnp.where(l == 0, NEG_INF, m + jnp.log(l_safe))
        lse_ref[0, 0] = lse[:, 0]

    with jax.named_scope("tiles_q%d_k%d" % (bq, bk)):
        out, lse = pl.pallas_call(
            kernel,
            name="flash_fwd",
            out_shape=(jax.ShapeDtypeStruct((BH, T, D), q.dtype),
                       jax.ShapeDtypeStruct((BH, 1, T), jnp.float32)),
            grid=(BH, nq),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec((1, bq, D), lambda bh, i: (bh, i, 0)),
                pl.BlockSpec((1, Tk, D), lambda bh, i: (bh // rep, 0, 0)),
                pl.BlockSpec((1, Tk, D), lambda bh, i: (bh // rep, 0, 0)),
            ],
            out_specs=(pl.BlockSpec((1, bq, D), lambda bh, i: (bh, i, 0)),
                       pl.BlockSpec((1, 1, bq), lambda bh, i: (bh, 0, i))),
            compiler_params=_row_params("fwd", Tk, D, k.dtype, bq, bk),
            interpret=_INTERPRET,
        )(q_off, k_off, q, k, v)
    return out, lse


# ---------------------------------------------------------------------------
# backward kernels: dq, then (dk, dv) — recompute-based
# ---------------------------------------------------------------------------

def _bwd_dq_call(q, k, v, do, lse, delta, q_off, k_off, causal, scale,
                 bq=None, bk=None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    BH, T, D = q.shape
    Tk = k.shape[1]
    rep = BH // k.shape[0]  # GQA (see _fwd_call)
    bq, bk = _pick_tiles("dq", T, Tk, D, k.dtype, bq, bk)
    nq, nk = T // bq, Tk // bk

    def kernel(qo_ref, ko_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
               delta_ref, dq_ref):
        q0 = qo_ref[0] + pl.program_id(1) * bq
        k0 = ko_ref[0]
        qblk = q_ref[0]
        doblk = do_ref[0]
        lse_b = lse_ref[0, 0]       # (bq,)
        # fully-masked rows have lse=-inf AND all scores -inf; substituting
        # a finite lse keeps exp(s - lse) = exp(-inf) = 0 for them (a 2-D
        # bool mask would need an i1 reshape Mosaic doesn't support)
        lse_b = jnp.where(jnp.isneginf(lse_b), 0.0, lse_b)
        dlt_b = delta_ref[0, 0]

        def tile(j, acc):
            at = pl.multiple_of(j * bk, bk)
            kblk = k_ref[0, pl.ds(at, bk), :]
            vblk = v_ref[0, pl.ds(at, bk), :]
            s = _dot(qblk, kblk, _NT) * scale  # (bq, bk)
            if causal:
                s = jnp.where(_visible(q0, k0 + j * bk, (bq, bk), 0), s,
                              NEG_INF)
            # the rows turn into columns tile by tile: held as columns
            # across the loop they are 64 vregs each and spill (the dq
            # kernel ran 10% slower so, PERF.md section 6, PR 29)
            p = jnp.exp(s - lse_b[:, None])
            dp = _dot(doblk, vblk, _NT)        # (bq, bk)
            ds = p * (dp - dlt_b[:, None])  # the scale waits for the sum
            return acc + _dot(ds.astype(kblk.dtype), kblk, _NN)

        acc = _walk(
            tile, jnp.zeros((bq, D), jnp.float32), 0,
            _visited(q0 + bq - 1, k0, bk, nk) if causal else nk, nk)
        dq_ref[0] = (acc * scale).astype(dq_ref.dtype)

    with jax.named_scope("tiles_q%d_k%d" % (bq, bk)):
        return pl.pallas_call(
            kernel,
            name="flash_bwd_dq",
            out_shape=jax.ShapeDtypeStruct((BH, T, D), q.dtype),
            grid=(BH, nq),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec((1, bq, D), lambda bh, i: (bh, i, 0)),
                pl.BlockSpec((1, Tk, D), lambda bh, i: (bh // rep, 0, 0)),
                pl.BlockSpec((1, Tk, D), lambda bh, i: (bh // rep, 0, 0)),
                pl.BlockSpec((1, bq, D), lambda bh, i: (bh, i, 0)),
                pl.BlockSpec((1, 1, bq), lambda bh, i: (bh, 0, i)),
                pl.BlockSpec((1, 1, bq), lambda bh, i: (bh, 0, i)),
            ],
            out_specs=pl.BlockSpec((1, bq, D), lambda bh, i: (bh, i, 0)),
            compiler_params=_row_params("dq", Tk, D, k.dtype, bq, bk),
            interpret=_INTERPRET,
        )(q_off, k_off, q, k, v, do, lse, delta)


def _bwd_dkv_call(q, k, v, do, lse, delta, q_off, k_off, causal, scale,
                  bq=None, bk=None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    BH, T, D = q.shape
    Tk = k.shape[1]
    BHkv = k.shape[0]
    rep = BH // BHkv  # GQA: each kv head serves `rep` query heads
    bq, bk = _pick_tiles("dkv", T, Tk, D, q.dtype, bq, bk)
    nq, nk = T // bq, Tk // bk

    def kernel(qo_ref, ko_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
               delta_ref, dk_ref, dv_ref, dk_s, dv_s):
        r = pl.program_id(2)  # query-head index within the kv group
        q0 = qo_ref[0]
        k0 = ko_ref[0] + pl.program_id(1) * bk  # the block's first key
        kblk = k_ref[0]
        vblk = v_ref[0]

        # the tile is the transpose of the other kernels', keys down and
        # queries across: lse and delta arrive as rows over the queries,
        # and both sums are plain (bk, bq) x (bq, D) products
        def tile(i, carry):
            dk_acc, dv_acc = carry
            at = pl.multiple_of(i * bq, bq)
            qblk = q_ref[0, pl.ds(at, bq), :]
            doblk = do_ref[0, pl.ds(at, bq), :]
            lse_r = lse_ref[0, :, pl.ds(at, bq)]    # (1, bq)
            dlt_r = delta_ref[0, :, pl.ds(at, bq)]
            # fully-masked rows: see the dq kernel
            lse_r = jnp.where(jnp.isneginf(lse_r), 0.0, lse_r)
            st = _dot(kblk, qblk, _NT) * scale       # (bk, bq)
            if causal:
                st = jnp.where(_visible(q0 + i * bq, k0, (bk, bq), 1), st,
                               NEG_INF)
            pt = jnp.exp(st - lse_r)
            dv_acc = dv_acc + _dot(pt.astype(doblk.dtype), doblk, _NN)
            dpt = _dot(vblk, doblk, _NT)             # (bk, bq)
            dst = pt * (dpt - dlt_r)  # the scale waits for the sum
            dk_acc = dk_acc + _dot(dst.astype(qblk.dtype), qblk, _NN)
            return dk_acc, dv_acc

        carry = (jnp.zeros((bk, D), jnp.float32),
                 jnp.zeros((bk, D), jnp.float32))
        # causal: from the first query tile that sees the block's first key
        lower = jnp.clip((k0 - q0) // bq, 0, nq).astype(jnp.int32) \
            if causal else 0
        dk_acc, dv_acc = _walk(tile, carry, lower, nq, nq)
        dk_acc = dk_acc * scale

        # accumulate the rep query heads of this kv group in fp32
        # scratch (the innermost grid dim revisits the same output
        # block), flush on the last one
        @pl.when(r == 0)
        def _init():
            dk_s[...] = dk_acc
            dv_s[...] = dv_acc

        @pl.when(r > 0)
        def _acc():
            dk_s[...] += dk_acc
            dv_s[...] += dv_acc

        @pl.when(r == rep - 1)
        def _flush():
            dk_ref[0] = dk_s[...].astype(dk_ref.dtype)
            dv_ref[0] = dv_s[...].astype(dv_ref.dtype)

    with jax.named_scope("tiles_q%d_k%d" % (bq, bk)):
        return pl.pallas_call(
            kernel,
            name="flash_bwd_dkv",
            out_shape=(jax.ShapeDtypeStruct((BHkv, Tk, D), k.dtype),
                       jax.ShapeDtypeStruct((BHkv, Tk, D), v.dtype)),
            grid=(BHkv, nk, rep),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec((1, T, D), lambda g, j, r: (g * rep + r, 0, 0)),
                pl.BlockSpec((1, bk, D), lambda g, j, r: (g, j, 0)),
                pl.BlockSpec((1, bk, D), lambda g, j, r: (g, j, 0)),
                pl.BlockSpec((1, T, D), lambda g, j, r: (g * rep + r, 0, 0)),
                pl.BlockSpec((1, 1, T), lambda g, j, r: (g * rep + r, 0, 0)),
                pl.BlockSpec((1, 1, T), lambda g, j, r: (g * rep + r, 0, 0)),
            ],
            out_specs=(pl.BlockSpec((1, bk, D), lambda g, j, r: (g, j, 0)),
                       pl.BlockSpec((1, bk, D), lambda g, j, r: (g, j, 0))),
            scratch_shapes=[pltpu.VMEM((bk, D), jnp.float32),
                            pltpu.VMEM((bk, D), jnp.float32)],
            compiler_params=_row_params("dkv", T, D, q.dtype, bq, bk),
            interpret=_INTERPRET,
        )(q_off, k_off, q, k, v, do, lse, delta)


# ---------------------------------------------------------------------------
# custom-vjp wrapper over (B, H, T, D)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _flash_lse(q, k, v, q_off, k_off, causal, scale, bq=None, bk=None):
    o, lse = _flash_lse_fwd(q, k, v, q_off, k_off, causal, scale, bq, bk)[0]
    return o, lse


def _flash_lse_fwd(q, k, v, q_off, k_off, causal, scale, bq=None, bk=None):
    B, H, T, D = q.shape
    Hkv, Tk = k.shape[1], k.shape[2]
    o, lse = _fwd_call(q.reshape(B * H, T, D), k.reshape(B * Hkv, Tk, D),
                       v.reshape(B * Hkv, Tk, D), q_off, k_off, causal,
                       scale, bq=bq, bk=bk)
    # named here, before they part into outputs and residuals: the
    # residuals are what a checkpoint policy has to see by name
    o = checkpoint_name(o.reshape(B, H, T, D), ATTENTION_KERNEL_OUT)
    lse = checkpoint_name(lse.reshape(B, H, T), ATTENTION_KERNEL_OUT)
    return (o, lse), (q, k, v, o, lse, q_off, k_off)


def _flash_lse_bwd(causal, scale, bq, bk, res, cot):
    q, k, v, o, lse, q_off, k_off = res
    do, dlse = cot
    B, H, T, D = q.shape
    Hkv, Tk = k.shape[1], k.shape[2]
    # Δ = rowsum(dO ∘ O) - dlse  (lse cotangent folds into the same ds
    # recurrence: d lse/d s = P)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    delta = delta - dlse.astype(jnp.float32)
    qr = q.reshape(B * H, T, D)
    kr = k.reshape(B * Hkv, Tk, D)
    vr = v.reshape(B * Hkv, Tk, D)
    dor = do.reshape(B * H, T, D).astype(q.dtype)
    lser = lse.reshape(B * H, 1, T)
    dltr = delta.reshape(B * H, 1, T)
    dq = _bwd_dq_call(qr, kr, vr, dor, lser, dltr, q_off, k_off, causal,
                      scale, bq=bq, bk=bk)
    dk, dv = _bwd_dkv_call(qr, kr, vr, dor, lser, dltr, q_off, k_off,
                           causal, scale, bq=bq, bk=bk)
    import numpy as onp
    zero_tan = onp.zeros((1,), jax.dtypes.float0)  # int inputs take float0
    return (dq.reshape(B, H, T, D), dk.reshape(B, Hkv, Tk, D),
            dv.reshape(B, Hkv, Tk, D), zero_tan, zero_tan)


_flash_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)


def _dense_with_lse(q, k, v, q_off, k_off, causal, scale):
    """XLA fallback with identical (o, lse) semantics (runs anywhere).
    GQA kv heads are materialized here (the fallback is the small-shape/
    off-TPU path; the memory win belongs to the kernel)."""
    if k.shape[1] != q.shape[1]:
        rep = q.shape[1] // k.shape[1]
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        T, Tk = q.shape[2], k.shape[2]
        qpos = q_off[0] + jnp.arange(T)
        kpos = k_off[0] + jnp.arange(Tk)
        s = jnp.where(qpos[:, None] >= kpos[None, :], s, NEG_INF)
    m = jnp.max(s, axis=-1)
    m_safe = jnp.where(jnp.isneginf(m), 0.0, m)
    p = jnp.exp(s - m_safe[..., None])
    if causal:
        p = jnp.where((qpos[:, None] >= kpos[None, :]), p, 0.0)
    l = jnp.sum(p, axis=-1)
    l_safe = jnp.where(l == 0, 1.0, l)
    o = jnp.einsum("bhqk,bhkd->bhqd", (p / l_safe[..., None]).astype(v.dtype),
                   v)
    lse = jnp.where(l == 0, NEG_INF, m + jnp.log(l_safe))
    return o.astype(q.dtype), lse


#: score elements (B*H*T*Tk) above which the off-TPU fallback switches
#: from the one-shot dense form to the chunked online-softmax form —
#: same (o, lse) semantics, O(chunk²) peak memory instead of O(T·Tk).
#: 2^26 fp32 scores ≈ 256 MB, the last size where materializing the
#: full block is cheaper than the scan bookkeeping.  FORWARD only:
#: ``flash_attention_block_bwd``'s off-TPU fallback still goes dense,
#: so huge blocks differentiate on TPU (blocked Mosaic bwd kernels)
#: but not on the CPU proxy mesh (ROADMAP PR-15 remainder).
_CHUNK_THRESHOLD = 1 << 26
_CHUNK = 4096


def _chunk_for(T):
    """Largest power-of-two chunk (≤ _CHUNK) dividing T, or None."""
    c = _CHUNK
    while c >= 128:
        if T % c == 0:
            return c
        c //= 2
    return None


def _chunked_with_lse(q, k, v, q_off, k_off, causal, scale, cq, ck):
    """Memory-bounded XLA fallback: online softmax over (cq × ck) score
    chunks — identical (o, lse) semantics to ``_dense_with_lse`` but the
    (T × Tk) score matrix never materializes, which is what lets the
    CPU-mesh ring run million-token blocks (131k × 131k fp32 scores
    would be 68 GB *per ring step*).  Causal chunks strictly above the
    diagonal are skipped via a dynamic inner trip count and fully
    visible chunks skip the mask arithmetic (an extra compare+select
    pass over T² elements is real time at these sizes)."""
    from jax import lax
    if k.shape[1] != q.shape[1]:
        rep = q.shape[1] // k.shape[1]
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    B, H, T, D = q.shape
    Tk = k.shape[2]
    nq, nk = T // cq, Tk // ck
    # q chunks leading so lax.scan maps over them
    qm = jnp.moveaxis(q.reshape(B, H, nq, cq, D), 2, 0)

    def per_q(carry, inp):
        qc, qi = inp
        q0 = q_off[0] + qi * cq

        def body(j, st):
            m, l, acc = st
            kc = lax.dynamic_slice_in_dim(k, j * ck, ck, axis=2)
            vc = lax.dynamic_slice_in_dim(v, j * ck, ck, axis=2)
            s = jnp.einsum("bhqd,bhkd->bhqk", qc, kc,
                           preferred_element_type=jnp.float32) * scale
            if causal:
                k0 = k_off[0] + j * ck

                def masked(s):
                    qpos = q0 + jnp.arange(cq)
                    kpos = k0 + jnp.arange(ck)
                    return jnp.where(qpos[:, None] >= kpos[None, :], s,
                                     NEG_INF)

                # chunk fully visible iff its smallest q sees its
                # largest k: q0 >= k0 + ck - 1
                s = lax.cond(q0 >= k0 + ck - 1, lambda s: s, masked, s)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
            p = jnp.exp(s - m_safe[..., None])
            corr = jnp.where(jnp.isneginf(m), 0.0,
                             jnp.exp(m - m_safe))
            l = l * corr + jnp.sum(p, axis=-1)
            acc = acc * corr[..., None] + jnp.einsum(
                "bhqk,bhkd->bhqd", p, vc.astype(jnp.float32))
            return m_new, l, acc

        if causal:
            # last k chunk with any visible position for this q chunk
            upper = jnp.clip((q0 + cq - 1 - k_off[0]) // ck + 1, 0,
                             nk).astype(jnp.int32)
        else:
            upper = nk
        m0 = jnp.full((B, H, cq), -jnp.inf)
        l0 = jnp.zeros((B, H, cq))
        a0 = jnp.zeros((B, H, cq, D))
        m, l, acc = lax.fori_loop(0, upper, body, (m0, l0, a0))
        m_safe = jnp.where(jnp.isneginf(m), 0.0, m)
        l_safe = jnp.where(l == 0, 1.0, l)
        o = (acc / l_safe[..., None]).astype(q.dtype)
        lse = jnp.where(l == 0, NEG_INF, m_safe + jnp.log(l_safe))
        return carry, (o, lse)

    _, (o, lse) = lax.scan(per_q, 0, (qm, jnp.arange(nq)))
    o = jnp.moveaxis(o, 0, 2).reshape(B, H, T, D)
    lse = jnp.moveaxis(lse, 0, 2).reshape(B, H, T)
    return o, lse


def merge_attention_parts(acc_o, acc_lse, o_s, lse_s):
    """Exact combine of two normalized partial attentions over disjoint
    key sets: o = (o1·e^l1 + o2·e^l2)/(e^l1+e^l2), max-shifted; float32
    ``(o, lse)``.  What the ring does a step (``parallel/ring.py``);
    EVA attention runs one softmax instead (``eva_flash_attention``)."""
    m = jnp.maximum(acc_lse, lse_s)
    m_safe = jnp.where(jnp.isneginf(m), 0.0, m)
    w1 = jnp.where(jnp.isneginf(acc_lse), 0.0, jnp.exp(acc_lse - m_safe))
    w2 = jnp.where(jnp.isneginf(lse_s), 0.0, jnp.exp(lse_s - m_safe))
    tot = w1 + w2
    tot_safe = jnp.where(tot == 0.0, 1.0, tot)
    o = (acc_o * w1[..., None] + o_s.astype(jnp.float32) * w2[..., None]) \
        / tot_safe[..., None]
    lse = jnp.where(tot == 0.0, -jnp.inf, m_safe + jnp.log(tot_safe))
    return o, lse


def flash_attention_with_lse(q, k, v, causal=False, scale=None,
                             q_offset=None, k_offset=None, block_q=None,
                             block_k=None, shard=None):
    """Blocked attention returning (output, logsumexp) on (B, H, T, D).

    GQA/MQA: ``k``/``v`` may carry fewer heads (H % H_kv == 0); the
    kernel maps each query head to its kv group via block index maps, so
    the repeated K/V are never materialized (a Llama-3-class 32q/8kv
    layout reads 4x less KV from HBM than the repeat-then-attend form).

    ``q_offset``/``k_offset`` are dynamic global position offsets for the
    causal mask (int32 scalars or shape-(1,) arrays) — pass the ring-step
    block offsets here.  Gradients flow through both outputs.  With
    ``shard`` (``parallel.sharding.kernel_shard``; required for a bare
    call under a mesh) the kernels run per shard of batch and heads.
    """
    if q.shape[1] % k.shape[1] != 0:
        raise ValueError(
            "flash_attention: %d query heads not a multiple of %d kv "
            "heads" % (q.shape[1], k.shape[1]))
    if scale is None:
        scale = q.shape[-1] ** -0.5
    q_off = jnp.zeros((1,), jnp.int32) if q_offset is None else \
        jnp.asarray(q_offset, jnp.int32).reshape(1)
    k_off = jnp.zeros((1,), jnp.int32) if k_offset is None else \
        jnp.asarray(k_offset, jnp.int32).reshape(1)
    if not _pallas_available() or not _shapes_ok(q, k):
        B, H, T = q.shape[0], q.shape[1], q.shape[2]
        Tk = k.shape[2]
        if B * H * T * Tk > _CHUNK_THRESHOLD:
            cq, ck = _chunk_for(T), _chunk_for(Tk)
            if cq and ck:
                return _chunked_with_lse(q, k, v, q_off, k_off, causal,
                                         scale, cq, ck)
        return _dense_with_lse(q, k, v, q_off, k_off, causal, scale)
    def kernel(q, k, v, q_off, k_off):
        return _flash_lse(q, k, v, q_off, k_off, causal, scale, block_q,
                          block_k)

    if shard is not None:
        spec = P(shard[1], shard[2], None, None)
        kernel = _per_shard(kernel, shard, (spec,) * 3 + (P(), P()),
                            (spec, P(shard[1], shard[2], None)))
    return kernel(q, k, v, q_off, k_off)


def flash_attention_block_bwd(q, k, v, do, lse, delta, causal=False,
                              scale=None, q_offset=None, k_offset=None,
                              block_q=None, block_k=None):
    """(dq, dk, dv) of ONE attention block against the GLOBAL merged
    logsumexp — the ring-attention backward primitive.

    ``lse`` (B, H, T) is the logsumexp of the FULL (all-blocks) softmax
    and ``delta`` (B, H, T) its rowsum(dO·O) correction, so the block's
    probabilities ``exp(s - lse)`` are the exact global ones and the
    per-block (dq, dk, dv) contributions sum to the dense gradient.
    This is what lets ``parallel/ring.py`` re-rotate K/V in backward
    instead of stashing every rotated block as an autodiff residual:
    each device calls this once per ring step on the block it currently
    holds.  On TPU it rides the same Mosaic dq/dkv kernels as the flash
    custom VJP; elsewhere an XLA fallback with identical semantics.
    Returns fp32 (the ring accumulates across blocks in fp32)."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    q_off = jnp.zeros((1,), jnp.int32) if q_offset is None else \
        jnp.asarray(q_offset, jnp.int32).reshape(1)
    k_off = jnp.zeros((1,), jnp.int32) if k_offset is None else \
        jnp.asarray(k_offset, jnp.int32).reshape(1)
    B, H, T, D = q.shape
    Hkv, Tk = k.shape[1], k.shape[2]
    if _pallas_available() and _shapes_ok(q, k):
        qr = q.reshape(B * H, T, D)
        kr = k.reshape(B * Hkv, Tk, D)
        vr = v.reshape(B * Hkv, Tk, D)
        dor = do.reshape(B * H, T, D).astype(q.dtype)
        lser = lse.reshape(B * H, 1, T)
        dltr = delta.reshape(B * H, 1, T)
        dq = _bwd_dq_call(qr, kr, vr, dor, lser, dltr, q_off, k_off,
                          causal, scale, bq=block_q, bk=block_k)
        dk, dv = _bwd_dkv_call(qr, kr, vr, dor, lser, dltr, q_off,
                               k_off, causal, scale, bq=block_q,
                               bk=block_k)
        return (dq.reshape(B, H, T, D).astype(jnp.float32),
                dk.reshape(B, Hkv, Tk, D).astype(jnp.float32),
                dv.reshape(B, Hkv, Tk, D).astype(jnp.float32))
    rep = H // Hkv
    kf = jnp.repeat(k, rep, axis=1) if rep > 1 else k
    vf = jnp.repeat(v, rep, axis=1) if rep > 1 else v
    s = jnp.einsum("bhqd,bhkd->bhqk", q, kf,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        qpos = q_off[0] + jnp.arange(T)
        kpos = k_off[0] + jnp.arange(Tk)
        mask = qpos[:, None] >= kpos[None, :]
        s = jnp.where(mask, s, NEG_INF)
    p = jnp.exp(s - lse.astype(jnp.float32)[..., None])
    if causal:
        p = jnp.where(mask, p, 0.0)
    dof = do.astype(jnp.float32)
    dv_b = jnp.einsum("bhqk,bhqd->bhkd", p, dof)
    dp = jnp.einsum("bhqd,bhkd->bhqk", dof, vf.astype(jnp.float32))
    ds = p * (dp - delta.astype(jnp.float32)[..., None]) * scale
    dq_b = jnp.einsum("bhqk,bhkd->bhqd", ds, kf.astype(jnp.float32))
    dk_b = jnp.einsum("bhqk,bhqd->bhkd", ds, q.astype(jnp.float32))
    if rep > 1:
        dk_b = dk_b.reshape(B, Hkv, rep, Tk, D).sum(axis=2)
        dv_b = dv_b.reshape(B, Hkv, rep, Tk, D).sum(axis=2)
    return dq_b, dk_b, dv_b


# ---------------------------------------------------------------------------
# paged attention — the mx.serve decode read path
# ---------------------------------------------------------------------------

def _paged_shapes_ok(q, k_pages):
    psz, D = k_pages.shape[2], k_pages.shape[3]
    return psz >= 128 and psz % 128 == 0 and D in (64, 128, 256)


def _paged_kernel_call(q, k_pages, v_pages, page_table, lengths, scale):
    """Pallas page-table decode attention: grid (slot, kv-head, page),
    the page axis innermost so each (slot, head) accumulates an online
    softmax over its pages in VMEM scratch.  Page blocks are DMA'd
    straight from the pool via a scalar-prefetched page-table index map
    — the repeated GQA K/V are never materialized and no contiguous
    (S, MP*psz, ...) gather ever exists in HBM."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    S, H, D = q.shape
    P, Hkv, psz, _ = k_pages.shape
    MP = page_table.shape[1]
    rep = H // Hkv
    qr = q.reshape(S, Hkv, rep, D)

    def kernel(pt_ref, len_ref, q_ref, k_ref, v_ref, o_ref, m_s, l_s,
               acc_s):
        s = pl.program_id(0)
        j = pl.program_id(2)
        valid = len_ref[s] - j * psz  # tokens of this slot in this page

        @pl.when(j == 0)
        def _init():
            m_s[...] = jnp.full_like(m_s, NEG_INF)
            l_s[...] = jnp.zeros_like(l_s)
            acc_s[...] = jnp.zeros_like(acc_s)

        @pl.when(valid > 0)
        def _page():
            qb = q_ref[0, 0].astype(jnp.float32) * scale    # (rep, D)
            kb = k_ref[0, 0].astype(jnp.float32)            # (psz, D)
            vb = v_ref[0, 0]
            sc = jax.lax.dot_general(
                qb, kb, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)          # (rep, psz)
            kpos = jax.lax.broadcasted_iota(jnp.int32, (rep, psz), 1)
            sc = jnp.where(kpos < valid, sc, NEG_INF)
            m_prev = m_s[:, 0]
            m_cur = jnp.max(sc, axis=1)
            m_new = jnp.maximum(m_prev, m_cur)
            m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
            p = jnp.where(kpos < valid,
                          jnp.exp(sc - m_safe[:, None]), 0.0)
            alpha = jnp.where(jnp.isneginf(m_prev), 0.0,
                              jnp.exp(m_prev - m_safe))
            l_s[:, 0] = l_s[:, 0] * alpha + jnp.sum(p, axis=1)
            acc_s[...] = acc_s[...] * alpha[:, None] + jax.lax.dot_general(
                p.astype(vb.dtype), vb, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_s[:, 0] = m_new

        @pl.when(j == MP - 1)
        def _flush():
            l = l_s[:, 0]
            l_safe = jnp.where(l == 0, 1.0, l)
            o_ref[0, 0] = (acc_s[...] / l_safe[:, None]).astype(o_ref.dtype)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # page_table, lengths
        grid=(S, Hkv, MP),
        in_specs=[
            pl.BlockSpec((1, 1, rep, D), lambda s, g, j, pt, ln:
                         (s, g, 0, 0)),
            pl.BlockSpec((1, 1, psz, D), lambda s, g, j, pt, ln:
                         (pt[s, j], g, 0, 0)),
            pl.BlockSpec((1, 1, psz, D), lambda s, g, j, pt, ln:
                         (pt[s, j], g, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, rep, D), lambda s, g, j, pt, ln:
                               (s, g, 0, 0)),
        scratch_shapes=[pltpu.VMEM((rep, 1), jnp.float32),
                        pltpu.VMEM((rep, 1), jnp.float32),
                        pltpu.VMEM((rep, D), jnp.float32)],
    )
    out = pl.pallas_call(
        kernel,
        name="paged_attention",
        out_shape=jax.ShapeDtypeStruct((S, Hkv, rep, D), q.dtype),
        grid_spec=grid_spec,
        interpret=_INTERPRET,
    )(page_table.astype(jnp.int32), lengths.astype(jnp.int32),
      qr, k_pages, v_pages)
    return out.reshape(S, H, D)


def _paged_dense(q, k_pages, v_pages, page_table, lengths, scale):
    """XLA fallback: gather each slot's pages into a contiguous view
    and run masked attention (fp32 softmax).  The gather materializes
    the padded context — the small-shape/off-TPU path; the in-place
    page reads belong to the kernel."""
    S, H, D = q.shape
    Hkv = k_pages.shape[1]
    g = k_pages[page_table]                  # (S, MP, Hkv, psz, D)
    MP, psz = g.shape[1], g.shape[3]
    k = g.transpose(0, 1, 3, 2, 4).reshape(S, MP * psz, Hkv, D)
    v = v_pages[page_table].transpose(0, 1, 3, 2, 4) \
        .reshape(S, MP * psz, Hkv, D)
    if Hkv != H:
        rep = H // Hkv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    sc = jnp.einsum("shd,skhd->shk", q.astype(jnp.float32),
                    k.astype(jnp.float32),
                    preferred_element_type=jnp.float32) * scale
    kpos = jnp.arange(MP * psz, dtype=jnp.int32)
    mask = kpos[None, None, :] < lengths[:, None, None]
    sc = jnp.where(mask, sc, NEG_INF)
    m = jnp.max(sc, axis=-1)
    m_safe = jnp.where(jnp.isneginf(m), 0.0, m)
    p = jnp.where(mask, jnp.exp(sc - m_safe[..., None]), 0.0)
    l = jnp.sum(p, axis=-1)
    l_safe = jnp.where(l == 0, 1.0, l)
    o = jnp.einsum("shk,skhd->shd", (p / l_safe[..., None]), v.astype(
        jnp.float32))
    return o.astype(q.dtype)


def paged_attention(q, k_pages, v_pages, page_table, lengths, scale=None,
                    shard=None):
    """One decode step's attention read over a paged KV cache.

    ``q``: (S, H, D) — one query token per batch slot; ``k_pages`` /
    ``v_pages``: (P, H_kv, page_size, D) single-layer page pools
    (un-repeated GQA heads — the layout the flash kernels consume);
    ``page_table``: (S, MP) int32 page ids per slot (unused entries
    must hold a valid index, conventionally the trash page 0);
    ``lengths``: (S,) int32 — tokens to attend over per slot, the new
    token included.  A slot with ``lengths == 0`` returns zeros.

    On TPU with kernel-friendly shapes this is a Pallas scalar-prefetch
    kernel whose page reads are driven by the page table directly —
    with ``shard`` (``parallel.sharding.kernel_shard``; required under
    a mesh) one kernel per shard of the heads, each over its own shard
    of the pools; elsewhere a dense gather fallback with identical
    semantics."""
    if q.shape[1] % k_pages.shape[1] != 0:
        raise ValueError(
            "paged_attention: %d query heads not a multiple of %d kv "
            "heads" % (q.shape[1], k_pages.shape[1]))
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if not _pallas_available() or not _paged_shapes_ok(q, k_pages):
        return _paged_dense(q, k_pages, v_pages, page_table, lengths,
                            scale)
    kernel = functools.partial(_paged_kernel_call, scale=scale)
    if shard is not None:
        heads = shard[2]
        kernel = _per_shard(
            kernel, shard,
            (P(None, heads, None), P(None, heads, None, None),
             P(None, heads, None, None), P(), P()),
            P(None, heads, None))
    return kernel(q, k_pages, v_pages, page_table, lengths)


def flash_attention(q, k, v, causal=False, scale=None, block_q=None,
                    block_k=None, shard=None):
    """Blocked flash attention on (B, H, T, D), Pallas forward + backward.

    k/v may carry fewer (grouped/multi-query) heads — see
    ``flash_attention_with_lse``.  With ``shard``
    (``parallel.sharding.kernel_shard``; required under a mesh) the
    kernels run per shard of the batch and of the heads.  Falls back to
    XLA dense attention off-TPU or for unsupported shapes; a row too
    long for the kernels' VMEM raises (``_row_params``)."""
    if q.shape[1] % k.shape[1] != 0:
        raise ValueError(
            "flash_attention: %d query heads not a multiple of %d kv "
            "heads" % (q.shape[1], k.shape[1]))
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if not _pallas_available() or not _shapes_ok(q, k):
        if k.shape[1] != q.shape[1]:
            rep = q.shape[1] // k.shape[1]
            k = jnp.repeat(k, rep, axis=1)
            v = jnp.repeat(v, rep, axis=1)
        return dot_product_attention(q, k, v, causal=causal, scale=scale)

    def kernel(q, k, v):
        zero = jnp.zeros((1,), jnp.int32)
        return _flash_lse(q, k, v, zero, zero, causal, scale, block_q,
                          block_k)[0]

    if shard is not None:
        spec = P(shard[1], shard[2], None, None)
        kernel = _per_shard(kernel, shard, (spec,) * 3, spec)
    return kernel(q, k, v)


# ---------------------------------------------------------------------------
# EVA attention: a window's keys and the summaries before it, one softmax
# ---------------------------------------------------------------------------
#
# ``models/evabyte.py``'s kernels.  The query at i, in window w = i // W,
# sees under one softmax the summaries [0, per * w) — ``per`` = W / chunk
# of them a window, one a chunk of every earlier window — and the keys of
# its own window up to itself.  A head's summaries are one (S, D) row,
# S = per * (nw - 1), resident beside its K and V rows.  The forward and
# dq kernels walk, for a query tile, the summary tiles its window sees
# and then its window's key tiles up to the diagonal, into one running
# softmax; dkv walks a head's key blocks and then its summary blocks,
# each against the query tiles that see it: its own window's from the
# diagonal on, or every later window's.  A summary tile lies within one
# window's chunks where 128 divides ``per`` and is then seen whole or
# not at all; otherwise the summaries are one tile, masked.  Every key
# tile a window walks is masked, as in the flash kernels: the mask paid
# on the diagonal's tiles alone, in a loop of their own, measured 2-7%
# slower in all three kernels (PERF.md section 6, PR 38).

def _eva_tiles(kind, W, per, S, D, dtype, block_q, block_k):
    """(bq, bk, bs): the query and key tiles of the walk over a window,
    as the flash kernels pick them for rows of ``W``, and the summary
    tile: the largest multiple of 128 up to 512 that divides ``per``,
    else all ``S`` as one."""
    bq, bk = _pick_tiles(kind, W, W, D, dtype, block_q, block_k)
    bs = next((c for c in range(min(per, 512) // 128 * 128, 127, -128)
               if per % c == 0), S)
    return bq, bk, bs


def _softmax_tile(carry, qblk, kblk, vblk, scale, seen):
    """One key tile into the forward's running softmax; ``seen`` masks
    it (None: every key is seen)."""
    acc, m_prev, l_prev = carry
    s = _dot(qblk, kblk, _NT) * scale
    if seen is not None:
        s = jnp.where(seen, s, NEG_INF)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
    alpha = jnp.where(jnp.isneginf(m_prev), 0.0, jnp.exp(m_prev - m_safe))
    p = jnp.exp(s - m_safe)
    l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
    return (acc * alpha + _dot(p.astype(vblk.dtype), vblk, _NN), m_new,
            l_new)


def _dq_tile(acc, qblk, doblk, lse_b, dlt_b, kblk, vblk, scale, seen):
    """One key tile's part of the query tile's dq (unscaled)."""
    s = _dot(qblk, kblk, _NT) * scale
    if seen is not None:
        s = jnp.where(seen, s, NEG_INF)
    p = jnp.exp(s - lse_b[:, None])
    ds = p * (_dot(doblk, vblk, _NT) - dlt_b[:, None])
    return acc + _dot(ds.astype(kblk.dtype), kblk, _NN)


def _eva_query_walk(W, per, bq, bk, bs, refs, tile, carry):
    """Inside the forward or dq kernel: ``tile(carry, kblk, vblk, seen)``
    over what the grid step's query tile sees — the summary tiles of its
    window, then its window's key tiles up to the diagonal."""
    from jax.experimental import pallas as pl
    k_ref, v_ref, ks_ref, vs_ref = refs
    qpw, kpw = W // bq, W // bk
    i = pl.program_id(1)
    w = i // qpw                   # the query tile's window
    q0 = (i - w * qpw) * bq        # its first query, within the window

    def summaries(j, c):
        at = pl.multiple_of(j * bs, bs)
        seen = None if per % bs == 0 else at + jax.lax.broadcasted_iota(
            jnp.int32, (bq, bs), 1) < per * w
        return tile(c, ks_ref[0, pl.ds(at, bs), :],
                    vs_ref[0, pl.ds(at, bs), :], seen)

    def keys(j, c):
        at = pl.multiple_of(w * W + j * bk, bk)
        return tile(c, k_ref[0, pl.ds(at, bk), :],
                    v_ref[0, pl.ds(at, bk), :],
                    _visible(q0, j * bk, (bq, bk), 0))

    carry = jax.lax.fori_loop(0, (per * w + bs - 1) // bs, summaries, carry)
    return _walk(keys, carry, 0, _visited(q0 + bq - 1, 0, bk, kpw), kpw)


def _eva_specs(T, S, D, bq):
    """In-specs of the forward and dq kernels: the query tile, the
    head's K and V rows and its summaries, resident."""
    from jax.experimental import pallas as pl
    return [pl.BlockSpec((1, bq, D), lambda h, i: (h, i, 0)),
            pl.BlockSpec((1, T, D), lambda h, i: (h, 0, 0)),
            pl.BlockSpec((1, T, D), lambda h, i: (h, 0, 0)),
            pl.BlockSpec((1, S, D), lambda h, i: (h, 0, 0)),
            pl.BlockSpec((1, S, D), lambda h, i: (h, 0, 0))]


def _eva_fwd_call(q, k, v, ks, vs, W, scale, bq=None, bk=None):
    from jax.experimental import pallas as pl

    BH, T, D = q.shape
    S = ks.shape[1]
    per = S // (T // W - 1)
    bq, bk, bs = _eva_tiles("fwd", W, per, S, D, k.dtype, bq, bk)

    def kernel(q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref, lse_ref):
        qblk = q_ref[0]
        acc, m, l = _eva_query_walk(
            W, per, bq, bk, bs, (k_ref, v_ref, ks_ref, vs_ref),
            lambda c, kb, vb, seen: _softmax_tile(c, qblk, kb, vb, scale,
                                                  seen),
            (jnp.zeros((bq, D), jnp.float32),
             jnp.full((bq, 1), NEG_INF, jnp.float32),
             jnp.zeros((bq, 1), jnp.float32)))
        o_ref[0] = (acc / l).astype(o_ref.dtype)  # a query sees itself
        lse_ref[0, 0] = (m + jnp.log(l))[:, 0]

    with jax.named_scope("tiles_q%d_k%d_s%d" % (bq, bk, bs)):
        return pl.pallas_call(
            kernel,
            name="eva_flash_fwd",
            out_shape=(jax.ShapeDtypeStruct((BH, T, D), q.dtype),
                       jax.ShapeDtypeStruct((BH, 1, T), jnp.float32)),
            grid=(BH, T // bq),
            in_specs=_eva_specs(T, S, D, bq),
            out_specs=(pl.BlockSpec((1, bq, D), lambda h, i: (h, i, 0)),
                       pl.BlockSpec((1, 1, bq), lambda h, i: (h, 0, i))),
            compiler_params=_row_params("fwd", T + S, D, k.dtype, bq,
                                        max(bk, bs)),
            interpret=_INTERPRET,
        )(q, k, v, ks, vs)


def _eva_bwd_dq_call(q, k, v, ks, vs, do, lse, delta, W, scale, bq=None,
                     bk=None):
    from jax.experimental import pallas as pl

    BH, T, D = q.shape
    S = ks.shape[1]
    per = S // (T // W - 1)
    bq, bk, bs = _eva_tiles("dq", W, per, S, D, k.dtype, bq, bk)

    def kernel(q_ref, k_ref, v_ref, ks_ref, vs_ref, do_ref, lse_ref,
               delta_ref, dq_ref):
        qblk, doblk = q_ref[0], do_ref[0]
        lse_b, dlt_b = lse_ref[0, 0], delta_ref[0, 0]
        acc = _eva_query_walk(
            W, per, bq, bk, bs, (k_ref, v_ref, ks_ref, vs_ref),
            lambda acc, kb, vb, seen: _dq_tile(acc, qblk, doblk, lse_b,
                                               dlt_b, kb, vb, scale, seen),
            jnp.zeros((bq, D), jnp.float32))
        dq_ref[0] = (acc * scale).astype(dq_ref.dtype)

    with jax.named_scope("tiles_q%d_k%d_s%d" % (bq, bk, bs)):
        return pl.pallas_call(
            kernel,
            name="eva_flash_bwd_dq",
            out_shape=jax.ShapeDtypeStruct((BH, T, D), q.dtype),
            grid=(BH, T // bq),
            in_specs=_eva_specs(T, S, D, bq) + [
                pl.BlockSpec((1, bq, D), lambda h, i: (h, i, 0)),
                pl.BlockSpec((1, 1, bq), lambda h, i: (h, 0, i)),
                pl.BlockSpec((1, 1, bq), lambda h, i: (h, 0, i))],
            out_specs=pl.BlockSpec((1, bq, D), lambda h, i: (h, i, 0)),
            compiler_params=_row_params("dq", T + S, D, k.dtype, bq,
                                        max(bk, bs)),
            interpret=_INTERPRET,
        )(q, k, v, ks, vs, do, lse, delta)


def _eva_bwd_dkv_call(q, k, v, ks, vs, do, lse, delta, W, scale, bq=None,
                      bk=None):
    """(dk, dv, dks, dvs).  The grid walks a head's key blocks, then its
    summary blocks: the blocks of the kind not walked stay where they
    were, unwritten, so each output block is written once."""
    from jax.experimental import pallas as pl

    BH, T, D = q.shape
    S = ks.shape[1]
    per = S // (T // W - 1)
    bq, bk, bs = _eva_tiles("dkv", W, per, S, D, q.dtype, bq, bk)
    nq, nk, ns = T // bq, T // bk, S // bs
    qpw, kpw = W // bq, W // bk
    exact = per % bs == 0     # a summary block is seen whole, or not

    def kernel(q_ref, k_ref, v_ref, ks_ref, vs_ref, do_ref, lse_ref,
               delta_ref, dk_ref, dv_ref, dks_ref, dvs_ref):
        j = pl.program_id(1)

        # keys down, queries across, as in the flash dkv kernel
        def walk(kblk, vblk, seen, lower, n):
            def tile(i, carry):
                dk_acc, dv_acc = carry
                at = pl.multiple_of(i * bq, bq)
                qblk = q_ref[0, pl.ds(at, bq), :]
                doblk = do_ref[0, pl.ds(at, bq), :]
                st = _dot(kblk, qblk, _NT) * scale
                if seen is not None:
                    st = jnp.where(seen(i), st, NEG_INF)
                pt = jnp.exp(st - lse_ref[0, :, pl.ds(at, bq)])
                dv_acc = dv_acc + _dot(pt.astype(doblk.dtype), doblk, _NN)
                dst = pt * (_dot(vblk, doblk, _NT)
                            - delta_ref[0, :, pl.ds(at, bq)])
                return (dk_acc + _dot(dst.astype(qblk.dtype), qblk, _NN),
                        dv_acc)

            zero = jnp.zeros((kblk.shape[0], D), jnp.float32)
            dk_acc, dv_acc = jax.lax.fori_loop(lower, n, tile, (zero, zero))
            return dk_acc * scale, dv_acc

        @pl.when(j < nk)
        def _keys():
            k0 = j * bk
            # from the diagonal to the end of the key's window
            dk_acc, dv_acc = walk(
                k_ref[0], v_ref[0],
                lambda i: _visible(i * bq, k0, (bk, bq), 1), k0 // bq,
                (j // kpw + 1) * qpw)
            dk_ref[0] = dk_acc.astype(dk_ref.dtype)
            dv_ref[0] = dv_acc.astype(dv_ref.dtype)

        @pl.when(j >= nk)
        def _summaries():
            s0 = (j - nk) * bs
            # every query tile of the windows after the block's first
            # chunk's window
            dk_acc, dv_acc = walk(
                ks_ref[0], vs_ref[0], None if exact else
                lambda i: s0 + jax.lax.broadcasted_iota(
                    jnp.int32, (bs, bq), 0) < per * (i // qpw),
                (s0 // per + 1) * qpw, nq)
            dks_ref[0] = dk_acc.astype(dks_ref.dtype)
            dvs_ref[0] = dv_acc.astype(dvs_ref.dtype)

    def key_block(h, j):
        return h, jnp.minimum(j, nk - 1), 0

    def summary_block(h, j):
        return h, jnp.maximum(j - nk, 0), 0

    with jax.named_scope("tiles_q%d_k%d_s%d" % (bq, bk, bs)):
        return pl.pallas_call(
            kernel,
            name="eva_flash_bwd_dkv",
            out_shape=(jax.ShapeDtypeStruct((BH, T, D), k.dtype),
                       jax.ShapeDtypeStruct((BH, T, D), v.dtype),
                       jax.ShapeDtypeStruct((BH, S, D), ks.dtype),
                       jax.ShapeDtypeStruct((BH, S, D), vs.dtype)),
            grid=(BH, nk + ns),
            in_specs=[
                pl.BlockSpec((1, T, D), lambda h, j: (h, 0, 0)),
                pl.BlockSpec((1, bk, D), key_block),
                pl.BlockSpec((1, bk, D), key_block),
                pl.BlockSpec((1, bs, D), summary_block),
                pl.BlockSpec((1, bs, D), summary_block),
                pl.BlockSpec((1, T, D), lambda h, j: (h, 0, 0)),
                pl.BlockSpec((1, 1, T), lambda h, j: (h, 0, 0)),
                pl.BlockSpec((1, 1, T), lambda h, j: (h, 0, 0)),
            ],
            out_specs=(pl.BlockSpec((1, bk, D), key_block),
                       pl.BlockSpec((1, bk, D), key_block),
                       pl.BlockSpec((1, bs, D), summary_block),
                       pl.BlockSpec((1, bs, D), summary_block)),
            compiler_params=_row_params("dkv", T, D, q.dtype, bq,
                                        max(bk, bs)),
            interpret=_INTERPRET,
        )(q, k, v, ks, vs, do, lse, delta)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _eva_flash(q, k, v, ks, vs, window, scale, bq, bk):
    return _eva_flash_fwd(q, k, v, ks, vs, window, scale, bq, bk)[0]


def _eva_flash_fwd(q, k, v, ks, vs, window, scale, bq, bk):
    B, H, T, D = q.shape
    o, lse = _eva_fwd_call(*(a.reshape(B * H, -1, D)
                             for a in (q, k, v, ks, vs)),
                           window, scale, bq, bk)
    # named for a checkpoint policy, as the flash forward's
    o = checkpoint_name(o.reshape(B, H, T, D), ATTENTION_KERNEL_OUT)
    lse = checkpoint_name(lse.reshape(B, H, T), ATTENTION_KERNEL_OUT)
    return o, (q, k, v, ks, vs, o, lse)


def _eva_flash_bwd(window, scale, bq, bk, res, do):
    q, k, v, ks, vs, o, lse = res
    B, H, T, D = q.shape
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    rows = [a.reshape(B * H, -1, D) for a in (q, k, v, ks, vs)]
    rows += [do.reshape(B * H, T, D).astype(q.dtype),
             lse.reshape(B * H, 1, T), delta.reshape(B * H, 1, T)]
    dq = _eva_bwd_dq_call(*rows, window, scale, bq, bk)
    grads = _eva_bwd_dkv_call(*rows, window, scale, bq, bk)
    return tuple(g.reshape(B, H, -1, D) for g in (dq,) + grads)


_eva_flash.defvjp(_eva_flash_fwd, _eva_flash_bwd)


def _eva_dense(q, k, v, ks, vs, window, scale):
    """XLA stand-in of the EVA kernels (runs anywhere): each window's
    scores against the summaries and its own keys, one softmax."""
    B, H, T, D = q.shape
    nw, S = T // window, ks.shape[2]
    per = S // (nw - 1)
    qw, kw, vw = (a.reshape(B, H, nw, window, D) for a in (q, k, v))
    far = jnp.einsum("bhnqd,bhsd->bhnqs", qw, ks,
                     preferred_element_type=jnp.float32) * scale
    near = jnp.einsum("bhnqd,bhnkd->bhnqk", qw, kw,
                      preferred_element_type=jnp.float32) * scale
    seen = jnp.arange(S)[None, :] < per * jnp.arange(nw)[:, None]
    causal = jnp.tril(jnp.ones((window, window), bool))
    s = jnp.concatenate([jnp.where(seen[:, None, :], far, NEG_INF),
                         jnp.where(causal, near, NEG_INF)], axis=-1)
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)  # a query sees itself
    o = jnp.einsum("bhnqs,bhsd->bhnqd", p[..., :S], vs,
                   preferred_element_type=jnp.float32) \
        + jnp.einsum("bhnqk,bhnkd->bhnqd", p[..., S:], vw,
                     preferred_element_type=jnp.float32)
    return o.reshape(B, H, T, D).astype(q.dtype)


def eva_flash_attention(q, k, v, ks, vs, window, scale=None, block_q=None,
                        block_k=None, shard=None):
    """EVA attention's one softmax on (B, H, T, D), ``T`` two or more
    whole windows: the query at i sees the keys of its window up to
    itself and the first ``per * (i // window)`` summaries, ``ks``,
    ``vs`` (B, H, S, D) with S = per * (T / window - 1) — the chunks of
    every window but the last.  Pallas forward and backward (one call
    each of ``eva_flash_fwd``, ``eva_flash_bwd_dq``,
    ``eva_flash_bwd_dkv``); gradients reach the summaries.  With
    ``shard`` (``parallel.sharding.kernel_shard``) the kernels run per
    shard of batch and heads.  Off the TPU, or at a window the kernels
    do not take (a multiple of 128), the XLA stand-in."""
    B, H, T, D = q.shape
    nw = T // window
    if k.shape != q.shape or v.shape != q.shape or nw < 2 \
            or T % window or ks.shape[2] % (nw - 1) \
            or ks.shape != vs.shape or ks.shape[:2] != (B, H):
        raise ValueError(
            "eva_flash_attention: q, k, v %s and summaries %s are not two "
            "or more windows of %d with a head's summaries each"
            % (q.shape, ks.shape, window))
    if scale is None:
        scale = D ** -0.5
    if not _pallas_available() or window % 128 or D not in (64, 128, 256):
        return _eva_dense(q, k, v, ks, vs, window, scale)

    def kernel(q, k, v, ks, vs):
        return _eva_flash(q, k, v, ks, vs, window, scale, block_q, block_k)

    if shard is not None:
        spec = P(shard[1], shard[2], None, None)
        kernel = _per_shard(kernel, shard, (spec,) * 5, spec)
    return kernel(q, k, v, ks, vs)


# ---------------------------------------------------------------------------
# sparse attention over each query's own selected keys (DeepSeek Sparse
# Attention): the query at i attends to the rows idx[i, :n_valid[i]] of K/V
# ---------------------------------------------------------------------------
#
# ``models/dsa.py``'s kernels.  A query's keys are a list of its own, so
# no tile of keys is shared by two queries: the kernels walk each query's
# own rows, a (query block, kv group) a grid step, the group's query
# heads read against the group's rows as they are (GQA without repeated
# K/V).  The work of a query is its selection's size, wherever the
# selected keys lie.
#
# The forward goes a chunk of queries at a time: the chunk's selected K/V
# rows are gathered by XLA (one gather of the K and V rows of every
# group, a row a selected key) and ``dsa_fwd`` walks them.  The backward
# walks the causal tiles under the selection's bits where the caller
# hands them over (the file's end; ``sparse_attention_takes_bits`` says
# for which shapes).  Without bits it goes by chunks too, the rows'
# dK/dV added into the keys' gradient by an XLA scatter.  (A forward
# that fetched its own rows ran twice as fast as the chunks on a v5e,
# but its ``pbar`` stays live beside the copy ``jax.checkpoint`` saves of
# it, 0.27 GB a layer at 32k, and the sparse cell's step then does not
# load.)

#: queries a grid step of the sparse attention kernels
_DSA_BLOCK_Q = 8


def _dsa_specs(bq, K, R, D):
    from jax.experimental import pallas as pl
    return {"bias": pl.BlockSpec((bq, K), lambda i, g: (i, 0)),
            "q": pl.BlockSpec((bq, R, D), lambda i, g: (i, g, 0)),
            "kv": pl.BlockSpec((bq, K, 2 * D), lambda i, g: (i, 0, g)),
            "stat": pl.BlockSpec((1, bq, R), lambda i, g: (g, i, 0)),
            "row": pl.BlockSpec((1, bq, K), lambda i, g: (g, i, 0))}


def _dsa_params(bq, K, D, dtype):
    """The pipeline double-buffers a step's (bq, K, 2D) rows; the
    (bq, R, K) float32 scores live beside them four at a time."""
    from jax.experimental.pallas import tpu as pltpu
    need = 2 * 2 * bq * K * 2 * D * jnp.dtype(dtype).itemsize \
        + 8 * bq * 8 * K * 4 + (4 << 20)
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel"),
        vmem_limit_bytes=int(min(max(need, _VMEM_DEFAULT), _VMEM_MAX)))


_BMM = (((2,), (2,)), ((0,), (0,)))   # (b, m, d) x (b, n, d) -> (b, m, n)
_BMN = (((2,), (1,)), ((0,), (0,)))   # (b, m, n) x (b, n, d) -> (b, m, d)
_BTN = (((1,), (1,)), ((0,), (0,)))   # (b, m, n) x (b, m, d) -> (b, n, d)


def _bdot(a, b, dims):
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


def _dsa_bwd_block(q, k, v, bias, do, lse, delta, scale):
    """``dq`` (bq, R, D) and the rows' ``dk``, ``dv`` (bq, K, D), all
    float32; ``lse`` and ``delta`` (bq, R)."""
    s = _bdot(q, k, _BMM) * scale + bias[:, None, :]
    p = jnp.exp(s - lse[:, :, None])            # 0 on an empty slot
    dp = _bdot(do, v, _BMM)
    ds = p * (dp - delta[:, :, None])   # the scale waits for the sum
    return (_bdot(ds.astype(k.dtype), k, _BMN) * scale,
            _bdot(ds.astype(q.dtype), q, _BTN) * scale,
            _bdot(p.astype(do.dtype), do, _BTN))


def _dsa_fwd_call(q, kv, bias, scale, bq):
    """One chunk: ``q`` (N, H, D); ``kv`` (N, K, 2 G D), query i's
    selected rows, group g's K then V at columns [2 g D, 2 (g + 1) D);
    ``bias`` (N, K) float32, 0 on a key and -inf on an empty slot.
    Returns ``o`` (N, H, D), ``lse`` (G, N, R) and ``pbar`` (G, N, K),
    group g's heads' softmax weights summed and divided by H."""
    from jax.experimental import pallas as pl
    N, H, D = q.shape
    K = kv.shape[1]
    G = kv.shape[2] // (2 * D)
    R = H // G
    sp = _dsa_specs(bq, K, R, D)

    def kernel(bias_ref, q_ref, kv_ref, o_ref, lse_ref, pbar_ref):
        kv = kv_ref[...]
        k, v = kv[:, :, :D], kv[:, :, D:]
        s = _bdot(q_ref[...], k, _BMM) * scale \
            + bias_ref[...][:, None, :]                 # (bq, R, K)
        m = jnp.max(s, axis=2, keepdims=True)  # a query has a key
        p = jnp.exp(s - m)
        l = jnp.sum(p, axis=2, keepdims=True)
        o_ref[...] = (_bdot(p.astype(v.dtype), v, _BMN) / l) \
            .astype(o_ref.dtype)
        lse_ref[0] = (m + jnp.log(l))[:, :, 0]
        pbar_ref[0] = jnp.sum(p / l, axis=1) * (1.0 / H)

    with jax.named_scope("tiles_q%d_k%d" % (bq, K)):
        return pl.pallas_call(
            kernel,
            name="dsa_fwd",
            out_shape=(jax.ShapeDtypeStruct((N, H, D), q.dtype),
                       jax.ShapeDtypeStruct((G, N, R), jnp.float32),
                       jax.ShapeDtypeStruct((G, N, K), jnp.float32)),
            grid=(N // bq, G),
            in_specs=[sp["bias"], sp["q"], sp["kv"]],
            out_specs=(sp["q"], sp["stat"], sp["row"]),
            compiler_params=_dsa_params(bq, K, D, kv.dtype),
            interpret=_INTERPRET,
        )(bias, q, kv)


def _dsa_bwd_call(q, kv, bias, do, lse, delta, scale, bq):
    """One chunk's ``dq`` (N, H, D) and every selected row's ``dK`` and
    ``dV`` (N, K, 2 G D), laid out as ``kv``; ``lse`` and ``delta``
    (G, N, R)."""
    from jax.experimental import pallas as pl
    N, H, D = q.shape
    K = kv.shape[1]
    G = kv.shape[2] // (2 * D)
    R = H // G
    sp = _dsa_specs(bq, K, R, D)

    def kernel(bias_ref, q_ref, kv_ref, do_ref, lse_ref, dlt_ref, dq_ref,
               dkv_ref):
        kv = kv_ref[...]
        dq, dk, dv = _dsa_bwd_block(
            q_ref[...], kv[:, :, :D], kv[:, :, D:], bias_ref[...],
            do_ref[...], lse_ref[0], dlt_ref[0], scale)
        dq_ref[...] = dq.astype(dq_ref.dtype)
        dkv_ref[:, :, :D] = dk.astype(dkv_ref.dtype)
        dkv_ref[:, :, D:] = dv.astype(dkv_ref.dtype)

    with jax.named_scope("tiles_q%d_k%d" % (bq, K)):
        return pl.pallas_call(
            kernel,
            name="dsa_bwd",
            out_shape=(jax.ShapeDtypeStruct((N, H, D), q.dtype),
                       jax.ShapeDtypeStruct(kv.shape, kv.dtype)),
            grid=(N // bq, G),
            in_specs=[sp["bias"], sp["q"], sp["kv"], sp["q"], sp["stat"],
                      sp["stat"]],
            out_specs=(sp["q"], sp["kv"]),
            compiler_params=_dsa_params(bq, K, D, kv.dtype),
            interpret=_INTERPRET,
        )(bias, q, kv, do, lse, delta)


def _dsa_fwd_dense(q, kv, bias, scale, bq=None):
    """XLA stand-in of ``_dsa_fwd_call`` (same arguments and results)."""
    N, H, D = q.shape
    K = kv.shape[1]
    G = kv.shape[2] // (2 * D)
    R = H // G
    kv = kv.reshape(N, K, G, 2, D)
    qg = q.reshape(N, G, R, D)
    s = jnp.einsum("ngrd,nkgd->ngrk", qg, kv[:, :, :, 0],
                   preferred_element_type=jnp.float32) * scale \
        + bias[:, None, None, :]
    lse = jax.nn.logsumexp(s, axis=-1)
    p = jnp.exp(s - lse[..., None])
    o = jnp.einsum("ngrk,nkgd->ngrd", p.astype(kv.dtype), kv[:, :, :, 1],
                   preferred_element_type=jnp.float32)
    return (o.reshape(N, H, D).astype(q.dtype), jnp.swapaxes(lse, 0, 1),
            jnp.swapaxes(jnp.sum(p, axis=2), 0, 1) / H)


def _dsa_bwd_dense(q, kv, bias, do, lse, delta, scale, bq=None):
    """XLA stand-in of ``_dsa_bwd_call``."""
    N, H, D = q.shape
    K = kv.shape[1]
    G = kv.shape[2] // (2 * D)
    R = H // G
    kv5 = kv.reshape(N, K, G, 2, D)
    k, v = kv5[:, :, :, 0], kv5[:, :, :, 1]
    qg, dog = q.reshape(N, G, R, D), do.reshape(N, G, R, D)
    s = jnp.einsum("ngrd,nkgd->ngrk", qg, k,
                   preferred_element_type=jnp.float32) * scale \
        + bias[:, None, None, :]
    p = jnp.exp(s - jnp.swapaxes(lse, 0, 1)[..., None])
    dp = jnp.einsum("ngrd,nkgd->ngrk", dog, v,
                    preferred_element_type=jnp.float32)
    ds = p * (dp - jnp.swapaxes(delta, 0, 1)[..., None])
    dq = jnp.einsum("ngrk,nkgd->ngrd", ds.astype(k.dtype), k,
                    preferred_element_type=jnp.float32) * scale
    dk = jnp.einsum("ngrk,ngrd->nkgd", ds.astype(q.dtype), qg,
                    preferred_element_type=jnp.float32) * scale
    dv = jnp.einsum("ngrk,ngrd->nkgd", p.astype(do.dtype), dog,
                    preferred_element_type=jnp.float32)
    dkv = jnp.stack([dk, dv], axis=3).reshape(kv.shape)
    return dq.reshape(N, H, D).astype(q.dtype), dkv.astype(kv.dtype)


def _dsa_count(path):
    """Bump the profiler's counter of the path a call was traced on."""
    from .. import profiler
    profiler.counter_bump("sparse_attn::" + path, 1)


def _dsa_rows(k, v):
    """(N, G, D) K and V -> (N, 2 G D): a key's row, group g's K then V
    at columns [2 g D, 2 (g + 1) D)."""
    N, G, D = k.shape
    return jnp.stack([k, v], axis=2).reshape(N, 2 * G * D)


def _dsa_bias(n_valid, K):
    return jnp.where(jnp.arange(K)[None, :] < n_valid[:, None], 0.0,
                     NEG_INF).astype(jnp.float32)


def _dsa_calls():
    if _pallas_available():
        return _dsa_fwd_call, _dsa_bwd_call
    return _dsa_fwd_dense, _dsa_bwd_dense


def _dsa_chunked_bwd(q, k, v, idx, n_valid, do, lse, delta, scale, chunk,
                     bq):
    """``dq`` and the keys' float32 ``dk``, ``dv`` (N, G, D) a chunk of
    queries at a time: the chunk's selected rows gathered, ``dsa_bwd``
    (or its XLA stand-in) over them, their dK/dV scattered into the
    keys'."""
    N, H, D = q.shape
    G, K = k.shape[1], idx.shape[1]
    bwd_call = _dsa_calls()[1]
    rows = _dsa_rows(k, v)
    delta = jnp.swapaxes(delta.reshape(N // chunk, chunk, G, H // G), 1, 2)

    def one(acc, c):
        qc, dc, ic, nc, lc, tc = c
        with jax.named_scope("gather"):
            sel = jnp.take(rows, ic, axis=0)
        dq, dsel = bwd_call(qc, sel, _dsa_bias(nc, K), dc, lc, tc, scale, bq)
        with jax.named_scope("scatter"):
            acc = acc.at[ic].add(dsel.astype(jnp.float32))
        return acc, dq

    acc, dq = jax.lax.scan(
        one, jnp.zeros(rows.shape, jnp.float32),
        (_chunked(q, chunk), _chunked(do, chunk), _chunked(idx, chunk),
         _chunked(n_valid, chunk), lse, delta))
    acc = acc.reshape(N, G, 2, D)
    return dq.reshape(N, H, D), acc[:, :, 0], acc[:, :, 1]


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _dsa(q, k, v, idx, n_valid, bits, scale, chunk, bq):
    return _dsa_fwd(q, k, v, idx, n_valid, bits, scale, chunk, bq)[0]


def _chunked(a, chunk):
    return a.reshape((a.shape[0] // chunk, chunk) + a.shape[1:])


def _dsa_fwd(q, k, v, idx, n_valid, bits, scale, chunk, bq):
    N, H, D = q.shape
    K = idx.shape[1]
    fwd_call = _dsa_calls()[0]
    rows = _dsa_rows(k, v)

    def one(_, c):
        qc, ic, nc = c
        with jax.named_scope("gather"):
            sel = jnp.take(rows, ic, axis=0)
        return None, fwd_call(qc, sel, _dsa_bias(nc, K), scale, bq)

    _, (o, lse, pbar) = jax.lax.scan(
        one, None, (_chunked(q, chunk), _chunked(idx, chunk),
                    _chunked(n_valid, chunk)))
    o = checkpoint_name(o.reshape(N, H, D), ATTENTION_KERNEL_OUT)
    lse = checkpoint_name(lse, ATTENTION_KERNEL_OUT)    # (n, G, C, R)
    pbar = checkpoint_name(jnp.sum(pbar, axis=1).reshape(N, K),
                           ATTENTION_KERNEL_OUT)
    return (o, pbar), (q, k, v, idx, n_valid, bits, o, lse)


def _dsa_bwd(scale, chunk, bq, res, cot):
    q, k, v, idx, n_valid, bits, o, lse = res
    do = cot[0]                 # the weights' mean is read unrolled
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), -1)
    args = (q, k, v, idx, n_valid, do.astype(q.dtype), lse, delta, scale)
    if bits is None:
        _dsa_count("chunked_bwd")
        dq, dk, dv = _dsa_chunked_bwd(*args, chunk, bq)
    else:                  # the causal tiles under the selection's bits
        _dsa_count("masked_bwd")
        dq, dk, dv = _dsa_masked(*args, bits)
    import numpy as onp
    zero = onp.zeros(idx.shape, jax.dtypes.float0)
    return (dq, dk.astype(k.dtype), dv.astype(v.dtype), zero,
            onp.zeros(n_valid.shape, jax.dtypes.float0),
            None if bits is None else onp.zeros(bits.shape, zero.dtype))


_dsa.defvjp(_dsa_fwd, _dsa_bwd)


def sparse_attention(q, k, v, idx, n_valid, scale=None, chunk=256,
                     block_q=None, bits=None):
    """Attention of each query over its own selected keys, token-major:
    ``q`` (N, H, D), ``k`` / ``v`` (N, G, D) with G dividing H (GQA: head
    h reads group h // (H / G)), ``idx`` (N, K) int rows of ``k`` / ``v``
    and ``n_valid`` (N,) int: query i attends to ``idx[i, :n_valid[i]]``
    (at least one), the other slots are empty; every slot names a row
    of ``k``; ``bits``, where given, the same sets one bit a (query, key)
    of each sequence (``pack_selection``).  Returns ``o`` (N, H, D) and
    ``pbar`` (N, K) float32, the heads' mean of each slot's softmax weight
    (0 on an empty slot), which has no gradient.  The forward goes
    ``chunk`` queries at a time (it divides N): their selected rows are
    gathered (scope ``gather``) and ``dsa_fwd`` walks them.  The backward
    walks the causal tiles under the bits where they are given (sequences
    of whole tiles and heads of 128 lanes: ``sparse_attention_takes_bits``
    says where a caller should make them); else it goes by chunks too,
    their rows' dK/dV scattered into the keys' (scope ``scatter``).
    ``sparse_attn::masked_bwd`` / ``chunked_bwd`` count the calls traced
    on each path.  Off the TPU the same in XLA."""
    N, H, D = q.shape
    G = k.shape[1]
    if H % G or k.shape != v.shape or k.shape[0] != N \
            or idx.shape[0] != N or N % min(chunk, N):
        raise ValueError(
            "sparse_attention: q %s, k/v %s, idx %s: heads a multiple of "
            "the groups, a row of idx a query, and the chunk dividing the "
            "queries" % (q.shape, k.shape, idx.shape))
    if bits is not None:
        T = 32 * bits.shape[0]
        if bits.shape[1] != N or N % T or _dsa_masked_tiles(T, D) is None:
            raise ValueError(
                "sparse_attention: bits %s are not (T / 32, %d) words of "
                "whole sequences the masked backward walks (T whole tiles, "
                "heads of D %% 128 == 0)" % (bits.shape, N))
        bits = bits.astype(jnp.uint32)
    if scale is None:
        scale = D ** -0.5
    bq = block_q or min(_DSA_BLOCK_Q, N)
    return _dsa(q, k, v, idx.astype(jnp.int32), n_valid.astype(jnp.int32),
                bits, float(scale), min(chunk, N), bq)


# ---------------------------------------------------------------------------
# the lightning indexer's scores: I[t, s] = sum_j w[t, j] relu(q[j, t] . k[s])
# ---------------------------------------------------------------------------

#: the (queries, keys) tile of the index score kernel
_INDEX_TILE = 512


def _index_call(q, k, w, q_off, causal, bq, bk):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    Hi, Tq, Di = q.shape
    Tk = k.shape[0]

    def kernel(qo_ref, q_ref, k_ref, w_ref, o_ref):
        q0 = qo_ref[0] + pl.program_id(0) * bq
        k0 = pl.program_id(1) * bk

        def scores():
            kblk = k_ref[...]
            w = w_ref[...]
            acc = jnp.zeros((bq, bk), jnp.float32)
            for j in range(Hi):
                s = _dot(q_ref[j], kblk, _NT)
                acc = acc + jnp.maximum(s, 0.0) * w[:, j:j + 1]
            if causal:
                acc = jnp.where(_visible(q0, k0, (bq, bk), 0), acc,
                                NEG_INF)
            o_ref[...] = acc

        if causal:
            pl.when(k0 <= q0 + bq - 1)(scores)

            @pl.when(k0 > q0 + bq - 1)
            def _future():
                o_ref[...] = jnp.full((bq, bk), NEG_INF, jnp.float32)
        else:
            scores()

    return pl.pallas_call(
        kernel,
        name="dsa_index",
        out_shape=jax.ShapeDtypeStruct((Tq, Tk), jnp.float32),
        grid=(Tq // bq, Tk // bk),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec((Hi, bq, Di), lambda i, j: (0, i, 0)),
                  pl.BlockSpec((bk, Di), lambda i, j: (j, 0)),
                  pl.BlockSpec((bq, Hi), lambda i, j: (i, 0))],
        out_specs=pl.BlockSpec((bq, bk), lambda i, j: (i, j)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=_INTERPRET,
    )(q_off, q, k, w)


def index_scores_dense(q, k, w, q0=0, causal=True):
    """XLA form of :func:`index_scores` (and its gradient)."""
    s = jnp.einsum("jtd,sd->jts", q, k, preferred_element_type=jnp.float32)
    out = jnp.einsum("jts,tj->ts", jnp.maximum(s, 0.0),
                     w.astype(jnp.float32))
    if causal:
        qpos = q0 + jnp.arange(q.shape[1])[:, None]
        out = jnp.where(jnp.arange(k.shape[0])[None, :] <= qpos, out,
                        NEG_INF)
    return out


def index_scores(q, k, w, q0=0, causal=True):
    """The indexer's scores ``I[t, s] = sum_j w[t, j] relu(q[j, t] .
    k[s])``, float32 (Tq, Tk), -inf where ``s`` lies after the query's
    position ``q0 + t`` (``causal``; ``q0`` may be traced).  ``q``
    (heads, Tq, d), ``k`` (Tk, d), ``w`` (Tq, heads).  No gradient: the
    indexer's loss differentiates :func:`index_scores_dense`.  One
    Pallas call (``dsa_index``, 512 x 512 tiles, the future's tiles
    skipped) where the tiles divide the rows."""
    Hi, Tq, Di = q.shape
    Tk = k.shape[0]
    b = _INDEX_TILE
    if not _pallas_available() or Tq % b or Tk % b or Di % 64:
        return index_scores_dense(q, k, w, q0, causal)
    q_off = jnp.reshape(jnp.asarray(q0, jnp.int32), (1,))
    return _index_call(q, k, w.astype(jnp.float32), q_off, causal, b, b)


# ---------------------------------------------------------------------------
# sliding-window attention: the query at t sees the keys t - window < s <= t
# ---------------------------------------------------------------------------
#
# ``window_attention``'s kernels.  Where the flash kernels keep a head's
# whole K/V (or Q/dO) row resident and walk it to the diagonal, the band
# touches a few tiles of the opposite row: each grid step is handed those
# tiles as blocks of their own, one ``slot`` each, which the pipeline
# fetches, so no row is resident and a step moves its tiles alone.  The
# forward and dq kernels' slots are the key tiles from the first one the
# query tile's earliest query sees up to the diagonal; dkv's are the query
# tiles from the key tile's own diagonal to the last that still sees it.
# A slot past that range (at the rows' ends) repeats the last tile and is
# skipped; a tile that lies inside the band is taken unmasked, one that
# straddles either of its edges masked.  Everything else is the flash
# kernels' arithmetic.

def _band_keys(i, bq, bk, window, xp):
    """The first and last key tile of which query tile ``i`` sees any."""
    q0 = i * bq
    return xp.maximum(q0 - window + 1, 0) // bk, (q0 + bq - 1) // bk


def _band_queries(j, bq, bk, window, T, xp):
    """The first and last query tile that sees any key of key tile
    ``j``."""
    k0 = j * bk
    return k0 // bq, xp.minimum(k0 + bk + window - 2, T - 1) // bq


import types  # noqa: E402  (here: the older kernels' lines stay put)

#: ``_band_keys`` and ``_band_queries`` on Python ints
_INTS = types.SimpleNamespace(maximum=max, minimum=min)


def _band_slots(T, window, bq, bk):
    """(key tiles a query tile sees, query tiles that see a key tile), at
    most: the slots of the forward and dq kernels and of dkv."""
    keys = [_band_keys(i, bq, bk, window, _INTS) for i in range(T // bq)]
    queries = [_band_queries(j, bq, bk, window, T, _INTS)
               for j in range(T // bk)]
    return (max(hi - lo for lo, hi in keys) + 1,
            max(hi - lo for lo, hi in queries) + 1)


def _band_visited(T, window, bq, bk):
    """The (query tile, key tile) pairs the band visits, a head."""
    return sum(hi - lo + 1 for lo, hi in (
        _band_keys(i, bq, bk, window, _INTS) for i in range(T // bq)))


def _in_band(q0, k0, shape, q_axis, window):
    qpos = q0 + jax.lax.broadcasted_iota(jnp.int32, shape, q_axis)
    kpos = k0 + jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis)
    return (kpos <= qpos) & (kpos > qpos - window)


def _band_case(q0, k0, bq, bk, window, seen):
    """The branch a slot takes: 0 not in the band (skipped), 1 inside it
    (unmasked), 2 across an edge (masked)."""
    whole = (k0 + bk - 1 <= q0) & (k0 >= q0 + bq - window)
    return jnp.where(seen, jnp.where(whole, 1, 2), 0)


def _swa_tiles(T, window, block_q=None, block_k=None):
    """(block_q, block_k): the largest of 512, 256 and 128 that divides
    the row and is no wider than the window (128 at the least); an
    explicit block wins and has to divide the row."""
    def pick(explicit):
        if explicit is not None:
            if T % explicit or explicit % 128:
                raise ValueError(
                    "window_attention: a block of %d does not divide a row "
                    "of %d tokens in multiples of 128" % (explicit, T))
            return explicit
        return next(b for b in (512, 256, 128)
                    if T % b == 0 and (b <= window or b == 128))
    return pick(block_q), pick(block_k)


def _swa_params(kind, slots, bq, bk, D, dtype):
    """Compiler params: a slot's blocks double-buffered beside the tile
    loop's working set (``_tile_bytes``)."""
    from jax.experimental.pallas import tpu as pltpu
    per_slot = 2 * 2 * max(bq, bk) * D * jnp.dtype(dtype).itemsize
    need = slots * per_slot + _tile_bytes(kind, bq, bk, D)
    if need <= _VMEM_DEFAULT:
        return None
    return pltpu.CompilerParams(vmem_limit_bytes=min(need, _VMEM_MAX))


def _softmax_step(carry, qblk, kblk, vblk, scale, mask):
    """One key tile into a query tile's running softmax; ``mask`` (or
    None: every pair seen) may leave a row with no key yet."""
    acc, m_prev, l_prev = carry
    s = _dot(qblk, kblk, _NT) * scale
    if mask is not None:
        s = jnp.where(mask, s, NEG_INF)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
    alpha = jnp.where(jnp.isneginf(m_prev), 0.0, jnp.exp(m_prev - m_safe))
    p = jnp.exp(s - m_safe)
    l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc = acc * alpha + _dot(p.astype(vblk.dtype), vblk, _NN)
    return acc, m_new, l_new


def _band_walk(n, case_of, step, carry):
    """``step(b, masked)(carry)`` over the ``n`` slots, each by its case
    (``case_of(b)``: skip, unmasked, masked)."""
    for b in range(n):
        carry = jax.lax.switch(case_of(b), [lambda c: c, step(b, False),
                                            step(b, True)], carry)
    return carry


def _key_slots(n, bq, bk, window, rep, D):
    """The forward and dq kernels' ``n`` key-tile slots over a grid of
    (query head, query tile): slot b holds the b-th key tile the query
    tile sees, the last one where it sees fewer."""
    from jax.experimental import pallas as pl

    def slot(b):
        def index(bh, i):
            lo, hi = _band_keys(i, bq, bk, window, jnp)
            return bh // rep, jnp.minimum(lo + b, hi), 0
        return pl.BlockSpec((1, bk, D), index)

    return [slot(b) for b in range(n)]


def _swa_fwd_call(q, k, v, window, scale, bq, bk):
    from jax.experimental import pallas as pl

    BH, T, D = q.shape
    rep = BH // k.shape[0]
    nb, _ = _band_slots(T, window, bq, bk)

    def kernel(q_ref, *refs):
        ks, vs, (o_ref, lse_ref) = refs[:nb], refs[nb:2 * nb], refs[2 * nb:]
        q0 = pl.program_id(1) * bq
        lo, hi = _band_keys(pl.program_id(1), bq, bk, window, jnp)
        qblk = q_ref[0]

        def step(b, masked):
            def run(carry):
                mask = _in_band(q0, (lo + b) * bk, (bq, bk), 0, window) \
                    if masked else None
                return _softmax_step(carry, qblk, ks[b][0], vs[b][0], scale,
                                     mask)
            return run

        acc, m, l = _band_walk(
            nb, lambda b: _band_case(q0, (lo + b) * bk, bq, bk, window,
                                     lo + b <= hi), step,
            (jnp.zeros((bq, D), jnp.float32),
             jnp.full((bq, 1), NEG_INF, jnp.float32),
             jnp.zeros((bq, 1), jnp.float32)))
        o_ref[0] = (acc / l).astype(o_ref.dtype)    # a query sees itself
        lse_ref[0, 0] = (m + jnp.log(l))[:, 0]

    row = pl.BlockSpec((1, bq, D), lambda bh, i: (bh, i, 0))
    with jax.named_scope("tiles_q%d_k%d" % (bq, bk)):
        return pl.pallas_call(
            kernel,
            name="swa_fwd",
            out_shape=(jax.ShapeDtypeStruct((BH, T, D), q.dtype),
                       jax.ShapeDtypeStruct((BH, 1, T), jnp.float32)),
            grid=(BH, T // bq),
            in_specs=[row] + _key_slots(nb, bq, bk, window, rep, D) * 2,
            out_specs=(row, pl.BlockSpec((1, 1, bq),
                                         lambda bh, i: (bh, 0, i))),
            compiler_params=_swa_params("fwd", 2 * nb, bq, bk, D, k.dtype),
            interpret=_INTERPRET,
        )(q, *[k] * nb, *[v] * nb)


def _swa_bwd_dq_call(q, k, v, do, lse, delta, window, scale, bq, bk):
    from jax.experimental import pallas as pl

    BH, T, D = q.shape
    rep = BH // k.shape[0]
    nb, _ = _band_slots(T, window, bq, bk)

    def kernel(q_ref, do_ref, lse_ref, delta_ref, *refs):
        ks, vs, dq_ref = refs[:nb], refs[nb:2 * nb], refs[2 * nb]
        q0 = pl.program_id(1) * bq
        lo, hi = _band_keys(pl.program_id(1), bq, bk, window, jnp)
        qblk, doblk = q_ref[0], do_ref[0]
        lse_b, dlt_b = lse_ref[0, 0], delta_ref[0, 0]    # (bq,)

        def step(b, masked):
            def run(acc):
                kblk, vblk = ks[b][0], vs[b][0]
                s = _dot(qblk, kblk, _NT) * scale
                if masked:
                    s = jnp.where(_in_band(q0, (lo + b) * bk, (bq, bk), 0,
                                           window), s, NEG_INF)
                p = jnp.exp(s - lse_b[:, None])
                ds = p * (_dot(doblk, vblk, _NT) - dlt_b[:, None])
                return acc + _dot(ds.astype(kblk.dtype), kblk, _NN)
            return run

        acc = _band_walk(
            nb, lambda b: _band_case(q0, (lo + b) * bk, bq, bk, window,
                                     lo + b <= hi), step,
            jnp.zeros((bq, D), jnp.float32))
        dq_ref[0] = (acc * scale).astype(dq_ref.dtype)

    row = pl.BlockSpec((1, bq, D), lambda bh, i: (bh, i, 0))
    stats = pl.BlockSpec((1, 1, bq), lambda bh, i: (bh, 0, i))
    with jax.named_scope("tiles_q%d_k%d" % (bq, bk)):
        return pl.pallas_call(
            kernel,
            name="swa_bwd_dq",
            out_shape=jax.ShapeDtypeStruct((BH, T, D), q.dtype),
            grid=(BH, T // bq),
            in_specs=[row, row, stats, stats]
            + _key_slots(nb, bq, bk, window, rep, D) * 2,
            out_specs=row,
            compiler_params=_swa_params("dq", 2 * nb, bq, bk, D, k.dtype),
            interpret=_INTERPRET,
        )(q, do, lse, delta, *[k] * nb, *[v] * nb)


def _swa_bwd_dkv_call(q, k, v, do, lse, delta, window, scale, bq, bk):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    BH, T, D = q.shape
    BHkv = k.shape[0]
    rep = BH // BHkv
    _, nb = _band_slots(T, window, bq, bk)

    def query_tile(b, shape, at):
        def index(g, j, r):
            lo, hi = _band_queries(j, bq, bk, window, T, jnp)
            return at(g * rep + r, jnp.minimum(lo + b, hi))
        return pl.BlockSpec(shape, index)

    def kernel(k_ref, v_ref, *refs):
        qs, dos, lses, dlts = (refs[n * nb:(n + 1) * nb] for n in range(4))
        dk_ref, dv_ref, dk_s, dv_s = refs[4 * nb:]
        r = pl.program_id(2)
        k0 = pl.program_id(1) * bk
        lo, hi = _band_queries(pl.program_id(1), bq, bk, window, T, jnp)
        kblk, vblk = k_ref[0], v_ref[0]

        # the flash dkv kernel's transposed tile: keys down, queries across
        def step(b, masked):
            def run(carry):
                dk_acc, dv_acc = carry
                qblk, doblk = qs[b][0], dos[b][0]
                st = _dot(kblk, qblk, _NT) * scale      # (bk, bq)
                if masked:
                    st = jnp.where(_in_band((lo + b) * bq, k0, (bk, bq), 1,
                                            window), st, NEG_INF)
                pt = jnp.exp(st - lses[b][0])
                dv_acc = dv_acc + _dot(pt.astype(doblk.dtype), doblk, _NN)
                dst = pt * (_dot(vblk, doblk, _NT) - dlts[b][0])
                return dk_acc + _dot(dst.astype(qblk.dtype), qblk, _NN), \
                    dv_acc
            return run

        dk_acc, dv_acc = _band_walk(
            nb, lambda b: _band_case((lo + b) * bq, k0, bq, bk, window,
                                     lo + b <= hi), step,
            (jnp.zeros((bk, D), jnp.float32),
             jnp.zeros((bk, D), jnp.float32)))
        dk_acc = dk_acc * scale

        # a kv group's rep query heads add up in float32 scratch, as in
        # the flash dkv kernel
        @pl.when(r == 0)
        def _init():
            dk_s[...] = dk_acc
            dv_s[...] = dv_acc

        @pl.when(r > 0)
        def _acc():
            dk_s[...] += dk_acc
            dv_s[...] += dv_acc

        @pl.when(r == rep - 1)
        def _flush():
            dk_ref[0] = dk_s[...].astype(dk_ref.dtype)
            dv_ref[0] = dv_s[...].astype(dv_ref.dtype)

    def rows(h, i):
        return h, i, 0

    def stats(h, i):
        return h, 0, i

    col = pl.BlockSpec((1, bk, D), lambda g, j, r: (g, j, 0))
    with jax.named_scope("tiles_q%d_k%d" % (bq, bk)):
        return pl.pallas_call(
            kernel,
            name="swa_bwd_dkv",
            out_shape=(jax.ShapeDtypeStruct((BHkv, T, D), k.dtype),
                       jax.ShapeDtypeStruct((BHkv, T, D), v.dtype)),
            grid=(BHkv, T // bk, rep),
            in_specs=[col, col]
            + [query_tile(b, (1, bq, D), rows) for b in range(nb)] * 2
            + [query_tile(b, (1, 1, bq), stats) for b in range(nb)] * 2,
            out_specs=(col, col),
            scratch_shapes=[pltpu.VMEM((bk, D), jnp.float32),
                            pltpu.VMEM((bk, D), jnp.float32)],
            compiler_params=_swa_params("dkv", 2 * nb, bq, bk, D, q.dtype),
            interpret=_INTERPRET,
        )(k, v, *[q] * nb, *[do] * nb, *[lse] * nb, *[delta] * nb)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _swa(q, k, v, window, scale, bq, bk):
    return _swa_fwd(q, k, v, window, scale, bq, bk)[0]


def _swa_fwd(q, k, v, window, scale, bq, bk):
    B, H, T, D = q.shape
    Hkv = k.shape[1]
    o, lse = _swa_fwd_call(q.reshape(B * H, T, D), k.reshape(B * Hkv, T, D),
                           v.reshape(B * Hkv, T, D), window, scale, bq, bk)
    # named for a checkpoint policy, as the flash forward's
    o = checkpoint_name(o.reshape(B, H, T, D), ATTENTION_KERNEL_OUT)
    lse = checkpoint_name(lse.reshape(B, H, T), ATTENTION_KERNEL_OUT)
    return o, (q, k, v, o, lse)


def _swa_bwd(window, scale, bq, bk, res, do):
    q, k, v, o, lse = res
    B, H, T, D = q.shape
    Hkv = k.shape[1]
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    rows = (q.reshape(B * H, T, D), k.reshape(B * Hkv, T, D),
            v.reshape(B * Hkv, T, D), do.reshape(B * H, T, D).astype(q.dtype),
            lse.reshape(B * H, 1, T), delta.reshape(B * H, 1, T))
    dq = _swa_bwd_dq_call(*rows, window, scale, bq, bk)
    dk, dv = _swa_bwd_dkv_call(*rows, window, scale, bq, bk)
    return (dq.reshape(B, H, T, D), dk.reshape(B, Hkv, T, D),
            dv.reshape(B, Hkv, T, D))


_swa.defvjp(_swa_fwd, _swa_bwd)


def _swa_dense(q, k, v, window, scale):
    """XLA stand-in of the window kernels (runs anywhere): one dense
    softmax over the band."""
    rep = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    t = jnp.arange(q.shape[2])
    band = (t[None, :] <= t[:, None]) & (t[None, :] > t[:, None] - window)
    p = jax.nn.softmax(jnp.where(band, s, NEG_INF), axis=-1)  # sees itself
    o = jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o.astype(q.dtype)


def window_attention(q, k, v, window, scale=None, block_q=None, block_k=None,
                     shard=None):
    """Causal attention over a sliding window on (B, H, T, D): the query
    at t sees the keys ``t - window < s <= t``.  k/v may carry fewer
    (grouped) heads, read in place as the flash kernels read them.
    Pallas forward and backward (``swa_fwd``, ``swa_bwd_dq``,
    ``swa_bwd_dkv``) that visit only the tiles the band touches; with
    ``shard`` (``parallel.sharding.kernel_shard``) they run per shard of
    batch and heads.  Off the TPU, or at a shape the kernels do not take
    (``_shapes_ok``), a dense masked softmax.  Each kernel call as traced
    bumps the profiler's counters ``window_attn::calls`` and
    ``window_attn::key_tiles`` (the (query tile, key tile) pairs its
    forward visits over every head)."""
    B, H, T, D = q.shape
    if H % k.shape[1] or k.shape[2] != T or window < 1:
        raise ValueError(
            "window_attention: %d query heads over %d kv heads, %d keys for "
            "%d queries, window %d" % (H, k.shape[1], k.shape[2], T, window))
    if scale is None:
        scale = D ** -0.5
    if not _pallas_available() or not _shapes_ok(q, k):
        return _swa_dense(q, k, v, window, scale)
    bq, bk = _swa_tiles(T, window, block_q, block_k)
    from .. import profiler
    profiler.counter_bump("window_attn::calls", 1)
    profiler.counter_bump("window_attn::key_tiles",
                          B * H * _band_visited(T, window, bq, bk))

    def kernel(q, k, v):
        return _swa(q, k, v, window, scale, bq, bk)

    if shard is not None:
        spec = P(shard[1], shard[2], None, None)
        kernel = _per_shard(kernel, shard, (spec,) * 3, spec)
    return kernel(q, k, v)


# ---------------------------------------------------------------------------
# the sparse attention's masked backward (``sparse_attention`` with bits)
# ---------------------------------------------------------------------------
#
# ``models/dsa.select`` can hand the selection out as one bit a (query,
# key) of the sequence (``pack_selection``): ``bits`` (T / 32, N) uint32,
# a column of words a query.  Keys go in bands of 256: key s is bit
# (s % 256) // 8 of word row 8 (s // 256) + s % 8, so a band's 8 word rows
# unpack, shift by shift, into 32 slabs of 8 keys stacked along the
# sublanes (``_dsa_unpack``).  Two kernels walk the sequence's causal
# tiles as the flash backward does, with every query head of a KV group
# in one grid step (one unpacked mask and one K/V tile serve its R
# heads): ``dsa_masked_dq`` over (group, query tile, key tile) and
# ``dsa_masked_dkv`` over (group, key tile, query tile), each adding its
# products into VMEM float32 along its last grid axis and writing once.
# A tile in the future is skipped, its blocks' indices held at the last
# one fetched, so the pipeline moves nothing for it.  An unselected
# pair's probability is an exact zero: the set, the softmax under the
# forward's lse and the float32 sums are the row kernels'; only the order
# of the sums differs.  The work is every causal tile's wherever the
# selected keys lie, so a caller hands the bits over by shape
# (``sparse_attention_takes_bits``).

#: (queries, keys) of a masked backward tile, the largest that divide T
_DSA_MASKED_TILES = ((256, 128), (512, 256))


def _dsa_masked_tiles(T, D):
    """(bq, bk) of the masked backward for sequences of ``T`` and heads
    of ``D``, or None where it cannot walk them: keys in whole bands of
    256 and heads of whole 128-lane rows."""
    bq = [b for b in _DSA_MASKED_TILES[0] if T % b == 0]
    bk = [b for b in _DSA_MASKED_TILES[1] if T % b == 0]
    return (bq[0], bk[0]) if bq and bk and D % 128 == 0 else None


def sparse_attention_takes_bits(T, K, D):
    """True where a caller of ``sparse_attention`` with sequences of
    ``T``, ``K`` slots a query and heads of ``D`` should hand it the
    selection's bits, so that the backward walks the causal tiles: the
    tiles whole, and a query's bits (T / 8 bytes) no larger than its
    slots' indices kept beside them (4 K).  Past that length the chunks
    of rows stay."""
    return _dsa_masked_tiles(T, D) is not None and T // 8 <= 4 * K


def pack_selection(sel):
    """(n, L) bool, L whole bands of 256 keys -> the (L / 32, n) uint32
    words of the masked backward's layout."""
    n, L = sel.shape
    b = jnp.arange(32, dtype=jnp.uint32)[None, None, :, None]
    w = jnp.sum(sel.reshape(n, L // 256, 32, 8).astype(jnp.uint32) << b,
                axis=2, dtype=jnp.uint32)
    return jnp.transpose(w, (1, 2, 0)).reshape(L // 32, n)


def unpack_selection(bits):
    """The (L, n) bool of :func:`pack_selection`'s words."""
    nb = bits.shape[0] // 8
    b = jnp.arange(32, dtype=jnp.uint32)[None, :, None, None]
    return ((bits.reshape(nb, 1, 8, -1) >> b) & 1).reshape(nb * 256, -1) \
        != 0


def _dsa_unpack(words):
    """A kernel's (bk / 32, bq) words -> (bk, bq) int32 0 / 1, slab by
    slab along the sublanes."""
    slabs = []
    for m in range(words.shape[0] // 8):
        w = words[8 * m:8 * m + 8]
        slabs += [(w >> b) & 1 for b in range(32)]
    return jnp.concatenate(slabs, axis=0).astype(jnp.int32)


def _dsa_masked_params(bq, bk, R, D, dtype):
    """Eight (bq, bk) float32 tiles alive at once, the double-buffered
    query and K/V blocks, the float32 accumulators and 4 MiB for the
    compiler."""
    from jax.experimental.pallas import tpu as pltpu
    need = (8 * bq * bk * 4
            + 4 * (2 * R * bq + 2 * bk) * D * jnp.dtype(dtype).itemsize
            + 2 * max(R * bq, bk) * D * 4 + (4 << 20))
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=int(min(max(need, _VMEM_DEFAULT), _VMEM_MAX)))


def _dsa_masked_dq_call(q, k, v, bits, do, lse, delta, T, scale, bq, bk):
    """``dq`` (N, H D) of the masked walk: ``q``, ``do`` (N, H D), ``k``,
    ``v`` (N, G D), ``bits`` (T / 32, N), ``lse`` and ``delta`` (G,
    N / bq, 1, R bq), a query tile's heads one after the other."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    N, HD = q.shape
    G, nq, _, RB = lse.shape
    R, D = RB // bq, HD // (G * RB // bq)
    nqs, nks = T // bq, T // bk

    def last(i):    # the last key tile query tile i sees any of
        return ((i % nqs) * bq + bq - 1) // bk

    def key_tile(g, i, j):
        return (i // nqs) * nks + jnp.minimum(j, last(i)), g

    def kernel(q_ref, k_ref, v_ref, bits_ref, do_ref, lse_ref, dlt_ref,
               dq_ref, acc):
        i, j = pl.program_id(1), pl.program_id(2)

        @pl.when(j == 0)
        def _():
            acc[...] = jnp.zeros(acc.shape, acc.dtype)

        @pl.when(j <= last(i))
        def _():
            kblk, vblk = k_ref[...], v_ref[...]
            mask = _dsa_unpack(bits_ref[...]).T != 0           # (bq, bk)
            for r in range(R):      # the group's heads share the mask
                cols, at = pl.ds(r * D, D), pl.ds(r * bq, bq)
                s = _dot(q_ref[:, cols], kblk, _NT) * scale
                p = jnp.exp(jnp.where(mask, s, NEG_INF)
                            - lse_ref[0, 0, 0, at][:, None])
                dp = _dot(do_ref[:, cols], vblk, _NT)
                ds = p * (dp - dlt_ref[0, 0, 0, at][:, None])
                acc[at, :] += _dot(ds.astype(kblk.dtype), kblk, _NN)

        @pl.when(j == nks - 1)
        def _():
            for r in range(R):
                dq_ref[:, r * D:(r + 1) * D] = (
                    acc[r * bq:(r + 1) * bq, :] * scale).astype(dq_ref.dtype)

    rows = pl.BlockSpec((bq, R * D), lambda g, i, j: (i, g))
    stat = pl.BlockSpec((1, 1, 1, RB), lambda g, i, j: (g, i, 0, 0))
    return pl.pallas_call(
        kernel,
        name="dsa_masked_dq",
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        grid=(G, nq, nks),
        in_specs=[rows, pl.BlockSpec((bk, D), key_tile),
                  pl.BlockSpec((bk, D), key_tile),
                  pl.BlockSpec((bk // 32, bq), lambda g, i, j: (
                      jnp.minimum(j, last(i)), i)),
                  rows, stat, stat],
        out_specs=rows,
        scratch_shapes=[pltpu.VMEM((RB, D), jnp.float32)],
        compiler_params=_dsa_masked_params(bq, bk, R, D, q.dtype),
        interpret=_INTERPRET,
    )(q, k, v, bits, do, lse, delta)


def _dsa_masked_dkv_call(q, k, v, bits, do, lse, delta, T, scale, bq, bk):
    """``dk``, ``dv`` (N, G D) of the masked walk, in the K/V dtype;
    arguments as :func:`_dsa_masked_dq_call`'s."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    N, HD = q.shape
    G, nq, _, RB = lse.shape
    R, D = RB // bq, HD // (G * RB // bq)
    nqs, nks = T // bq, T // bk

    def first(j):   # the first query tile that sees key tile j
        return (j % nks) * bk // bq

    def query_tile(j, i):
        return (j // nks) * nqs + jnp.maximum(i, first(j))

    def kernel(q_ref, k_ref, v_ref, bits_ref, do_ref, lse_ref, dlt_ref,
               dk_ref, dv_ref, dk_s, dv_s):
        j, i = pl.program_id(1), pl.program_id(2)

        @pl.when(i == 0)
        def _():
            dk_s[...] = jnp.zeros(dk_s.shape, dk_s.dtype)
            dv_s[...] = jnp.zeros(dv_s.shape, dv_s.dtype)

        # keys down and a tile's queries across, head after head: lse and
        # delta meet the tile as the rows they are stored as
        @pl.when(i >= first(j))
        def _():
            kblk, vblk = k_ref[...], v_ref[...]
            mask = _dsa_unpack(bits_ref[...]) != 0              # (bk, bq)
            dk, dv = dk_s[...], dv_s[...]
            for r in range(R):      # the group's heads share the mask
                cols, at = pl.ds(r * D, D), pl.ds(r * bq, bq)
                qh, doh = q_ref[:, cols], do_ref[:, cols]
                st = _dot(kblk, qh, _NT) * scale
                pt = jnp.exp(jnp.where(mask, st, NEG_INF)
                             - lse_ref[0, 0, :, at])
                dv = dv + _dot(pt.astype(doh.dtype), doh, _NN)
                dst = pt * (_dot(vblk, doh, _NT) - dlt_ref[0, 0, :, at])
                dk = dk + _dot(dst.astype(qh.dtype), qh, _NN)
            dk_s[...], dv_s[...] = dk, dv

        @pl.when(i == nqs - 1)
        def _():
            dk_ref[...] = (dk_s[...] * scale).astype(dk_ref.dtype)
            dv_ref[...] = dv_s[...].astype(dv_ref.dtype)

    rows = pl.BlockSpec((bq, R * D), lambda g, j, i: (query_tile(j, i), g))
    stat = pl.BlockSpec((1, 1, 1, RB),
                        lambda g, j, i: (g, query_tile(j, i), 0, 0))
    keys = pl.BlockSpec((bk, D), lambda g, j, i: (j, g))
    return pl.pallas_call(
        kernel,
        name="dsa_masked_dkv",
        out_shape=(jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)),
        grid=(G, N // bk, nqs),
        in_specs=[rows, keys, keys,
                  pl.BlockSpec((bk // 32, bq), lambda g, j, i: (
                      j % nks, query_tile(j, i))),
                  rows, stat, stat],
        out_specs=(keys, keys),
        scratch_shapes=[pltpu.VMEM((bk, D), jnp.float32),
                        pltpu.VMEM((bk, D), jnp.float32)],
        compiler_params=_dsa_masked_params(bq, bk, R, D, q.dtype),
        interpret=_INTERPRET,
    )(q, k, v, bits, do, lse, delta)


def _dsa_masked_bwd(q, k, v, bits, do, lse, delta, scale):
    """``dq`` and the keys' ``dk``, ``dv`` (N, G, D) by the masked walk;
    ``lse`` as the forward saved it, ``delta`` (N, H)."""
    N, H, D = q.shape
    G = k.shape[1]
    R, T = H // G, 32 * bits.shape[0]
    bq, bk = _dsa_masked_tiles(T, D)

    def stat(s):    # (N, H) -> (G, N / bq, 1, R bq)
        return jnp.transpose(s.reshape(N // bq, bq, G, R), (2, 0, 3, 1)) \
            .reshape(G, N // bq, 1, R * bq)

    lse = jnp.swapaxes(lse, 1, 2).reshape(N, H)       # (n, G, C, R)
    args = (q.reshape(N, H * D), k.reshape(N, G * D), v.reshape(N, G * D),
            bits, do.reshape(N, H * D), stat(lse), stat(delta), T, scale,
            bq, bk)
    with jax.named_scope("tiles_q%d_k%d" % (bq, bk)):
        dq = _dsa_masked_dq_call(*args)
        dk, dv = _dsa_masked_dkv_call(*args)
    return dq.reshape(N, H, D), dk.reshape(N, G, D), dv.reshape(N, G, D)


def _dsa_masked_dense(q, k, v, bits, do, lse, delta, scale):
    """XLA stand-in of :func:`_dsa_masked_bwd`: the same masked products
    a chunk of 256 queries at a time against its sequence's keys, so no
    (T, T) array exists."""
    N, H, D = q.shape
    G = k.shape[1]
    R, T = H // G, 32 * bits.shape[0]
    C = min(256, T)
    lse = jnp.swapaxes(lse, 1, 2).reshape(N, H)

    def one(acc, c):
        qc, dc, lc, tc, words, c0 = c
        s0 = c0 // T * T
        kb = jax.lax.dynamic_slice_in_dim(k, s0, T)
        vb = jax.lax.dynamic_slice_in_dim(v, s0, T)
        qg, dg = qc.reshape(C, G, R, D), dc.reshape(C, G, R, D)
        s = jnp.einsum("cgrd,sgd->cgrs", qg, kb,
                       preferred_element_type=jnp.float32) * scale
        s = jnp.where(unpack_selection(words).T[:, None, None, :], s,
                      NEG_INF)
        p = jnp.exp(s - lc.reshape(C, G, R)[..., None])
        dp = jnp.einsum("cgrd,sgd->cgrs", dg, vb,
                        preferred_element_type=jnp.float32)
        ds = p * (dp - tc.reshape(C, G, R)[..., None])
        dq = jnp.einsum("cgrs,sgd->cgrd", ds.astype(k.dtype), kb,
                        preferred_element_type=jnp.float32) * scale
        dk = jnp.einsum("cgrs,cgrd->sgd", ds.astype(q.dtype), qg,
                        preferred_element_type=jnp.float32) * scale
        dv = jnp.einsum("cgrs,cgrd->sgd", p.astype(do.dtype), dg,
                        preferred_element_type=jnp.float32)
        part = jax.lax.dynamic_slice_in_dim(acc, s0, T) \
            + jnp.stack([dk, dv], axis=2)
        return jax.lax.dynamic_update_slice_in_dim(acc, part, s0, 0), \
            dq.reshape(C, H, D).astype(q.dtype)

    words = jnp.swapaxes(bits.reshape(bits.shape[0], N // C, C), 0, 1)
    acc, dq = jax.lax.scan(
        one, jnp.zeros((N, G, 2, D), jnp.float32),
        (_chunked(q, C), _chunked(do, C), _chunked(lse, C),
         _chunked(delta, C), words, jnp.arange(0, N, C)))
    return dq.reshape(N, H, D), acc[:, :, 0], acc[:, :, 1]


def _dsa_masked(q, k, v, idx, n_valid, do, lse, delta, scale, bits):
    """The masked backward's ``dq``, ``dk``, ``dv``: the kernels, or off
    the TPU their XLA stand-in."""
    walk = _dsa_masked_bwd if _pallas_available() else _dsa_masked_dense
    return walk(q, k, v, bits, do, lse, delta, scale)
