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
dq/dk/dv, so training memory stays O(T) like the forward.

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
from jax.sharding import PartitionSpec as P

from .nn import dot_product_attention

_INTERPRET = os.environ.get("MXNET_PALLAS_INTERPRET", "0") == "1"
_logger = logging.getLogger(__name__)
_warned_whole = set()  # mesh shapes already told about, once per process
NEG_INF = float("-inf")

#: VMEM the TPU compiler grants one kernel unasked, and the most these
#: kernels ask for.  A v5e core has 128 MiB; the rest is left to the
#: compiler's own scratch.  tests/test_chip_compile.py compiles the
#: longest rows this admits (forward and backward) for the described chip.
_VMEM_DEFAULT = 16 << 20
_VMEM_MAX = 100 << 20
#: allowed on top of the resident rows for a kernel's own tiles
_VMEM_SLACK = 4 << 20


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
    """The longest row (tokens, a multiple of 128) a kernel takes: at
    D=128 bf16, 98,304 in the forward and dq kernels and 87,296 in dkv —
    so 87,296 wherever the backward runs."""
    return (_VMEM_MAX - _VMEM_SLACK) // _row_bytes(D, dtype, stats) \
        // 128 * 128


def _row_params(T, D, dtype, stats=False):
    """Compiler params for a kernel that keeps two whole (T, D) rows of
    one head in VMEM, double-buffered by the pipeline — K and V in the
    forward and dq kernels, Q and dO (plus the lse and delta rows,
    ``stats``) in dkv.  Resident rows are fetched once per head where a
    grid axis would stream them once per opposite block; the price is a
    bound on T (``_max_row``), raised here instead of left to the
    compiler."""
    from jax.experimental.pallas import tpu as pltpu
    need = T * _row_bytes(D, dtype, stats) + _VMEM_SLACK
    if need > _VMEM_MAX:
        raise ValueError(
            "flash_attention: a %d-token row needs %d MiB of VMEM "
            "resident in the %s kernel (head_dim %d, %s) and the kernels "
            "may use %d MiB; the largest supported length is %d tokens "
            "forward and %d with the backward — split the sequence over "
            "devices (parallel.ring_attention_sharded)"
            % (T, need >> 20, "dkv" if stats else "forward/dq", D,
               jnp.dtype(dtype).name, _VMEM_MAX >> 20,
               _max_row(D, dtype, False), _max_row(D, dtype, True)))
    if need <= _VMEM_DEFAULT:
        return None
    return pltpu.CompilerParams(vmem_limit_bytes=need)


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

def _fwd_call(q, k, v, q_off, k_off, causal, scale, bq=128, bk=128):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    BH, T, D = q.shape
    Tk = k.shape[1]
    # GQA: k/v may carry fewer heads; query row bh reads kv row bh//rep
    # via the BlockSpec index map — the repeated K/V are never
    # materialized in HBM (4x activation saving for 32q/8kv models)
    rep = BH // k.shape[0]
    bq = min(bq, T)
    bk = min(bk, Tk)
    nq = pl.cdiv(T, bq)
    nk = pl.cdiv(Tk, bk)

    def kernel(qo_ref, ko_ref, q_ref, k_ref, v_ref, o_ref, lse_ref):
        qi = pl.program_id(1)
        q_off_v = qo_ref[0]
        k_off_v = ko_ref[0]
        qblk = q_ref[0].astype(jnp.float32) * scale

        def body(j, carry):
            acc, m_prev, l_prev = carry
            kblk = k_ref[0, pl.ds(j * bk, bk), :].astype(jnp.float32)
            vblk = v_ref[0, pl.ds(j * bk, bk), :]
            s = jax.lax.dot_general(
                qblk, kblk, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)  # (bq, bk)
            if causal:
                qpos = q_off_v + qi * bq + jax.lax.broadcasted_iota(
                    jnp.int32, (bq, bk), 0)
                kpos = k_off_v + j * bk + jax.lax.broadcasted_iota(
                    jnp.int32, (bq, bk), 1)
                s = jnp.where(qpos >= kpos, s, NEG_INF)
            m_cur = jnp.max(s, axis=1)
            m_new = jnp.maximum(m_prev, m_cur)
            m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
            p = jnp.exp(s - m_safe[:, None])
            if causal:
                p = jnp.where(qpos >= kpos, p, 0.0)
            alpha = jnp.where(jnp.isneginf(m_prev), 0.0,
                              jnp.exp(m_prev - m_safe))
            l_new = l_prev * alpha + jnp.sum(p, axis=1)
            acc = acc * alpha[:, None] + jax.lax.dot_general(
                p.astype(vblk.dtype), vblk, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return acc, m_new, l_new

        acc0 = jnp.zeros((bq, D), jnp.float32)
        m0 = jnp.full((bq,), NEG_INF, jnp.float32)
        l0 = jnp.zeros((bq,), jnp.float32)
        if causal:
            # skip key blocks strictly in this query block's future
            qmax = q_off_v + (qi + 1) * bq - 1
            upper = jnp.clip(
                (qmax - k_off_v) // bk + 1, 0, nk).astype(jnp.int32)
        else:
            upper = nk
        acc, m, l = jax.lax.fori_loop(0, upper, body, (acc0, m0, l0))
        l_safe = jnp.where(l == 0, 1.0, l)
        o_ref[0] = (acc / l_safe[:, None]).astype(o_ref.dtype)
        lse_ref[0, 0] = jnp.where(l == 0, NEG_INF, m + jnp.log(l_safe))

    grid = (BH, nq)
    out, lse = pl.pallas_call(
        kernel,
        name="flash_fwd",
        out_shape=(jax.ShapeDtypeStruct((BH, T, D), q.dtype),
                   jax.ShapeDtypeStruct((BH, 1, T), jnp.float32)),
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, bq, D), lambda bh, i: (bh, i, 0)),
            pl.BlockSpec((1, Tk, D), lambda bh, i: (bh // rep, 0, 0)),
            pl.BlockSpec((1, Tk, D), lambda bh, i: (bh // rep, 0, 0)),
        ],
        out_specs=(pl.BlockSpec((1, bq, D), lambda bh, i: (bh, i, 0)),
                   pl.BlockSpec((1, 1, bq), lambda bh, i: (bh, 0, i))),
        compiler_params=_row_params(Tk, D, k.dtype),
        interpret=_INTERPRET,
    )(q_off, k_off, q, k, v)
    return out, lse


# ---------------------------------------------------------------------------
# backward kernels: dq, then (dk, dv) — recompute-based
# ---------------------------------------------------------------------------

def _bwd_dq_call(q, k, v, do, lse, delta, q_off, k_off, causal, scale,
                 bq=128, bk=128):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    BH, T, D = q.shape
    Tk = k.shape[1]
    rep = BH // k.shape[0]  # GQA (see _fwd_call)
    bq = min(bq, T)
    bk = min(bk, Tk)
    nq = pl.cdiv(T, bq)
    nk = pl.cdiv(Tk, bk)

    def kernel(qo_ref, ko_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
               delta_ref, dq_ref):
        qi = pl.program_id(1)
        q_off_v = qo_ref[0]
        k_off_v = ko_ref[0]
        qblk = q_ref[0].astype(jnp.float32)
        doblk = do_ref[0].astype(jnp.float32)
        lse_b = lse_ref[0, 0]       # (bq,)
        dlt_b = delta_ref[0, 0]     # (bq,)
        # fully-masked rows have lse=-inf AND all scores -inf; substituting
        # a finite lse keeps exp(s - lse) = exp(-inf) = 0 for them (a 2-D
        # bool mask would need an i1 reshape Mosaic doesn't support)
        lse_b = jnp.where(jnp.isneginf(lse_b), 0.0, lse_b)

        def body(j, acc):
            kblk = k_ref[0, pl.ds(j * bk, bk), :].astype(jnp.float32)
            vblk = v_ref[0, pl.ds(j * bk, bk), :].astype(jnp.float32)
            s = jax.lax.dot_general(
                qblk, kblk, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            if causal:
                qpos = q_off_v + qi * bq + jax.lax.broadcasted_iota(
                    jnp.int32, (bq, bk), 0)
                kpos = k_off_v + j * bk + jax.lax.broadcasted_iota(
                    jnp.int32, (bq, bk), 1)
                s = jnp.where(qpos >= kpos, s, NEG_INF)
            p = jnp.exp(s - lse_b[:, None])
            dp = jax.lax.dot_general(
                doblk, vblk, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)  # (bq, bk)
            ds = p * (dp - dlt_b[:, None]) * scale
            return acc + jax.lax.dot_general(
                ds, kblk, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        if causal:
            qmax = q_off_v + (qi + 1) * bq - 1
            upper = jnp.clip(
                (qmax - k_off_v) // bk + 1, 0, nk).astype(jnp.int32)
        else:
            upper = nk
        acc = jax.lax.fori_loop(0, upper, body,
                                jnp.zeros((bq, D), jnp.float32))
        dq_ref[0] = acc.astype(dq_ref.dtype)

    grid = (BH, nq)
    return pl.pallas_call(
        kernel,
        name="flash_bwd_dq",
        out_shape=jax.ShapeDtypeStruct((BH, T, D), q.dtype),
        grid=grid,
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
        compiler_params=_row_params(Tk, D, k.dtype),
        interpret=_INTERPRET,
    )(q_off, k_off, q, k, v, do, lse, delta)


def _bwd_dkv_call(q, k, v, do, lse, delta, q_off, k_off, causal, scale,
                  bq=128, bk=128):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    BH, T, D = q.shape
    Tk = k.shape[1]
    BHkv = k.shape[0]
    rep = BH // BHkv  # GQA: each kv head serves `rep` query heads
    bq = min(bq, T)
    bk = min(bk, Tk)
    nq = pl.cdiv(T, bq)
    nk = pl.cdiv(Tk, bk)

    def kernel(qo_ref, ko_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
               delta_ref, dk_ref, dv_ref, dk_s, dv_s):
        kj = pl.program_id(1)
        r = pl.program_id(2)  # query-head index within the kv group
        q_off_v = qo_ref[0]
        k_off_v = ko_ref[0]
        kblk = k_ref[0].astype(jnp.float32)
        vblk = v_ref[0].astype(jnp.float32)

        def body(i, carry):
            dk_acc, dv_acc = carry
            qblk = q_ref[0, pl.ds(i * bq, bq), :].astype(jnp.float32)
            doblk = do_ref[0, pl.ds(i * bq, bq), :].astype(jnp.float32)
            lse_b = lse_ref[0, 0, pl.ds(i * bq, bq)]
            dlt_b = delta_ref[0, 0, pl.ds(i * bq, bq)]
            lse_b = jnp.where(jnp.isneginf(lse_b), 0.0, lse_b)
            s = jax.lax.dot_general(
                qblk, kblk, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # (bq, bk)
            if causal:
                qpos = q_off_v + i * bq + jax.lax.broadcasted_iota(
                    jnp.int32, (bq, bk), 0)
                kpos = k_off_v + kj * bk + jax.lax.broadcasted_iota(
                    jnp.int32, (bq, bk), 1)
                s = jnp.where(qpos >= kpos, s, NEG_INF)
            p = jnp.exp(s - lse_b[:, None])
            dv_acc = dv_acc + jax.lax.dot_general(
                p, doblk, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)  # (bk, D)
            dp = jax.lax.dot_general(
                doblk, vblk, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)  # (bq, bk)
            ds = p * (dp - dlt_b[:, None]) * scale
            dk_acc = dk_acc + jax.lax.dot_general(
                ds, qblk, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)  # (bk, D)
            return dk_acc, dv_acc

        if causal:
            # first query block that can see this key block
            kmin = k_off_v + kj * bk
            lower = jnp.clip((kmin - q_off_v) // bq, 0, nq).astype(jnp.int32)
        else:
            lower = 0
        dk0 = jnp.zeros((bk, D), jnp.float32)
        dv0 = jnp.zeros((bk, D), jnp.float32)
        dk_acc, dv_acc = jax.lax.fori_loop(lower, nq, body, (dk0, dv0))
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

    grid = (BHkv, nk, rep)
    return pl.pallas_call(
        kernel,
        name="flash_bwd_dkv",
        out_shape=(jax.ShapeDtypeStruct((BHkv, Tk, D), k.dtype),
                   jax.ShapeDtypeStruct((BHkv, Tk, D), v.dtype)),
        grid=grid,
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
        compiler_params=_row_params(T, D, q.dtype, stats=True),
        interpret=_INTERPRET,
    )(q_off, k_off, q, k, v, do, lse, delta)


# ---------------------------------------------------------------------------
# custom-vjp wrapper over (B, H, T, D)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _flash_lse(q, k, v, q_off, k_off, causal, scale, bq=128, bk=128):
    o, lse = _flash_lse_fwd(q, k, v, q_off, k_off, causal, scale, bq, bk)[0]
    return o, lse


def _flash_lse_fwd(q, k, v, q_off, k_off, causal, scale, bq=128, bk=128):
    B, H, T, D = q.shape
    Hkv, Tk = k.shape[1], k.shape[2]
    o, lse = _fwd_call(q.reshape(B * H, T, D), k.reshape(B * Hkv, Tk, D),
                       v.reshape(B * Hkv, Tk, D), q_off, k_off, causal,
                       scale, bq=bq, bk=bk)
    o = o.reshape(B, H, T, D)
    lse = lse.reshape(B, H, T)
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


def flash_attention_with_lse(q, k, v, causal=False, scale=None,
                             q_offset=None, k_offset=None, block_q=128,
                             block_k=128):
    """Blocked attention returning (output, logsumexp) on (B, H, T, D).

    GQA/MQA: ``k``/``v`` may carry fewer heads (H % H_kv == 0); the
    kernel maps each query head to its kv group via block index maps, so
    the repeated K/V are never materialized (a Llama-3-class 32q/8kv
    layout reads 4x less KV from HBM than the repeat-then-attend form).

    ``q_offset``/``k_offset`` are dynamic global position offsets for the
    causal mask (int32 scalars or shape-(1,) arrays) — pass the ring-step
    block offsets here.  Gradients flow through both outputs.
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
    return _flash_lse(q, k, v, q_off, k_off, causal, scale, block_q,
                      block_k)


def flash_attention_block_bwd(q, k, v, do, lse, delta, causal=False,
                              scale=None, q_offset=None, k_offset=None,
                              block_q=128, block_k=128):
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


def flash_attention(q, k, v, causal=False, scale=None, block_q=128,
                    block_k=128, shard=None):
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
