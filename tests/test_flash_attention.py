"""Flash-attention Pallas kernels (forward + recompute backward) vs the
XLA dense reference, run in Pallas interpret mode on CPU so the *actual
kernel code* is exercised without TPU hardware (the reference validates
its fused attention in tests/python/unittest/test_operator.py
``test_multihead_attention_selfatt`` with numeric grad checks).
"""
import numpy as onp
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.ops import pallas_ops
from mxnet_tpu.ops.nn import dot_product_attention
from mxnet_tpu.test_utils import assert_almost_equal


def _rand(shape, seed):
    return jnp.asarray(onp.random.RandomState(seed).normal(0, 1, shape),
                       jnp.float32)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_forward_matches_dense(interpret_kernels, causal):
    B, H, T, D = 2, 2, 256, 64
    q, k, v = (_rand((B, H, T, D), s) for s in (0, 1, 2))
    o_f = pallas_ops.flash_attention(q, k, v, causal=causal)
    o_d = dot_product_attention(q, k, v, causal=causal)
    assert_almost_equal(onp.asarray(o_f), onp.asarray(o_d), rtol=2e-4,
                        atol=2e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_backward_matches_dense(interpret_kernels, causal):
    B, H, T, D = 1, 2, 256, 64
    q, k, v = (_rand((B, H, T, D), s) for s in (3, 4, 5))
    w = jnp.cos(jnp.arange(D, dtype=jnp.float32))

    def loss_f(q, k, v):
        return (pallas_ops.flash_attention(q, k, v, causal=causal) * w).sum()

    def loss_d(q, k, v):
        return (dot_product_attention(q, k, v, causal=causal) * w).sum()

    gf = jax.grad(loss_f, (0, 1, 2))(q, k, v)
    gd = jax.grad(loss_d, (0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", gf, gd):
        assert_almost_equal(onp.asarray(a), onp.asarray(b), rtol=2e-3,
                            atol=2e-3)


# ---------------------------------------------------------------------------
# the tile loop: every tile the kernels may run, and the picker's own
# ---------------------------------------------------------------------------

_TILE_CASES = [(128, 128), (256, 512), (512, 256), (512, 512), None]
# (T, Tk, q_offset, k_offset): a square row whose tiles are fully
# visible, on the diagonal and skipped; and a block as the ring hands it
# over, T != Tk, the diagonal off every tile's corner, keys in the future
_ROW_CASES = {"square": (1024, 1024, 0, 0), "offset": (512, 1024, 400, 130)}
# relative distance (Frobenius) of the PARENT's 128 x 128 kernels' bf16
# results from the float32 dense reference over these cases, read before
# the tile loop changed: one bf16 rounding, the result's own
_PARENT_BF16_GAP = {"o": 2.25e-3, "dq": 2.27e-3, "dk": 2.17e-3,
                    "dv": 2.16e-3}
# o and dv keep that; dq and dk are now sums over ds rounded to bf16, a
# second independent rounding of the same size (measured: 2.82e-3, 2.36e-3)
_BF16_ROOM = {"o": 1.05, "dv": 1.05, "dq": 2 ** 0.5, "dk": 2 ** 0.5}

_tile_runs = {}


def _tile_run(tiles, rows, rep, causal, dtype):
    """(o, lse, dq, dk, dv) of the kernels and of the float32 dense form
    for one case; the forward and the backward test share the run."""
    key = (tiles, rows, rep, causal, dtype)
    if key in _tile_runs:
        return _tile_runs[key]
    T, Tk, q_off, k_off = _ROW_CASES[rows]
    H, D = 4 if rep == 4 else 2, 64
    q = _rand((1, H, T, D), 40).astype(dtype)
    k, v = (_rand((1, H // rep, Tk, D), s).astype(dtype) for s in (41, 42))
    w = jnp.cos(jnp.arange(D, dtype=jnp.float32))
    blocks = {} if tiles is None else {"block_q": tiles[0],
                                       "block_k": tiles[1]}

    def flash(q, k, v):
        o, lse = pallas_ops.flash_attention_with_lse(
            q, k, v, causal=causal, q_offset=q_off, k_offset=k_off,
            **blocks)
        return (o.astype(jnp.float32) * w).sum() + 0.7 * lse.sum(), (o, lse)

    def dense(q, k, v):
        o, lse = pallas_ops._dense_with_lse(
            q, k, v, jnp.asarray([q_off], jnp.int32),
            jnp.asarray([k_off], jnp.int32), causal, D ** -0.5)
        return (o * w).sum() + 0.7 * lse.sum(), (o, lse)

    def run(fn, *args):
        (_, (o, lse)), g = jax.jit(jax.value_and_grad(
            fn, (0, 1, 2), has_aux=True))(*args)
        return [onp.asarray(a, dtype=onp.float32) for a in (o, lse) + g]

    got = run(flash, q, k, v)
    assert got[0].dtype == onp.float32 and got[2].shape == q.shape
    want = run(dense, *(a.astype(jnp.float32) for a in (q, k, v)))
    _tile_runs[key] = dict(zip(("o", "lse", "dq", "dk", "dv"),
                               zip(got, want)))
    return _tile_runs[key]


def _check_tile_case(run, names, dtype):
    for name in names:
        got, want = run[name]
        if dtype == "float32" or name == "lse":
            assert_almost_equal(got, want, rtol=2e-5, atol=2e-5)
        else:
            gap = onp.linalg.norm(got - want) / onp.linalg.norm(want)
            assert gap <= _PARENT_BF16_GAP[name] * _BF16_ROOM[name], \
                (name, gap)


_tile_params = [
    pytest.mark.parametrize("dtype", ["float32", "bfloat16"]),
    pytest.mark.parametrize("causal", [True, False],
                            ids=["causal", "full"]),
    pytest.mark.parametrize("rep", [1, 4], ids=["mha", "gqa4"]),
    pytest.mark.parametrize("rows", list(_ROW_CASES)),
    pytest.mark.parametrize(
        "tiles", _TILE_CASES,
        ids=["picked" if t is None else "%dx%d" % t for t in _TILE_CASES]),
]


def _tile_cases(fn):
    for mark in _tile_params:
        fn = mark(fn)
    return fn


@_tile_cases
def test_flash_tiles_forward_matches_dense(interpret_kernels, tiles, rows,
                                           rep, causal, dtype):
    _check_tile_case(_tile_run(tiles, rows, rep, causal, dtype),
                     ("o", "lse"), dtype)


@_tile_cases
def test_flash_tiles_backward_matches_dense(interpret_kernels, tiles, rows,
                                            rep, causal, dtype):
    _check_tile_case(_tile_run(tiles, rows, rep, causal, dtype),
                     ("dq", "dk", "dv"), dtype)


@pytest.mark.parametrize("kind", ["fwd", "dq", "dkv"])
def test_picker_chooses_from_the_shape(kind):
    pick = pallas_ops._pick_tiles
    bf16 = jnp.bfloat16
    for T, Tk, D in [(1024, 1024, 128), (4096, 4096, 128), (1152, 4096, 64),
                     (16384, 16384, 128), (2048, 640, 256),
                     (87296, 87296, 128)]:
        bq, bk = pick(kind, T, Tk, D, bf16)
        assert bq % 128 == 0 and bk % 128 == 0 and T % bq == 0 \
            and Tk % bk == 0 and max(bq, bk) <= 1024, (T, Tk, D, bq, bk)
    # what the chip measured: a row of up to 1,024 tokens (the serving
    # prefill's ladder, BERT's rows) is one tile; 512 x 512 past that;
    # the backward kernels 1,024 x 1,024 from 8,192 tokens
    for T in (128, 256, 384, 512, 1024):
        assert pick(kind, T, T, 128, bf16) == (T, T)
    # the row a kernel's loop walks decides: K/V's, in dkv the queries'
    assert pick(kind, 128, 4096, 128, bf16) == \
        ((128, 1024) if kind == "dkv" else (128, 512))
    assert pick(kind, 4096, 4096, 128, bf16) == (512, 512)
    assert pick(kind, 16384, 16384, 128, bf16) == \
        ((512, 512) if kind == "fwd" else (1024, 1024))
    # a long row leaves the tile what _max_row kept for the smallest
    longest = pallas_ops._max_row(128, bf16, kind == "dkv")
    bq, bk = pick(kind, longest, longest, 128, bf16)
    assert pallas_ops._tile_bytes(kind, bq, bk, 128) \
        <= pallas_ops._VMEM_TILE_MIN
    # an explicit block wins, one side or both, and has to divide its row
    assert pick(kind, 4096, 4096, 128, bf16, 256, 128) == (256, 128)
    assert pick(kind, 4096, 4096, 128, bf16, None, 256)[1] == 256
    assert pick(kind, 128, 128, 64, bf16, 512, 512) == (128, 128)
    with pytest.raises(ValueError, match="does not divide"):
        pick(kind, 4096, 4096, 128, bf16, 384, None)


def test_longest_rows_unchanged_by_the_tile_budget():
    assert pallas_ops._max_row(128, jnp.bfloat16, False) == 98304
    assert pallas_ops._max_row(128, jnp.bfloat16, True) == 87296


def test_flash_with_lse_offsets_and_lse_grad(interpret_kernels):
    """Offset-aware causal masking and the lse cotangent path — exactly
    what ring attention needs per step."""
    B, H, T, D = 1, 2, 128, 64
    q, k, v = (_rand((B, H, T, D), s) for s in (6, 7, 8))

    def loss_f(q_, k_, v_):
        o, lse = pallas_ops.flash_attention_with_lse(
            q_, k_, v_, causal=True, q_offset=128, k_offset=0)
        return (o * 1.3).sum() + (lse * 0.7).sum()

    def loss_dense(q_, k_, v_):
        s = jnp.einsum("bhqd,bhkd->bhqk", q_, k_) * (D ** -0.5)
        qpos = 128 + jnp.arange(T)
        kpos = jnp.arange(T)
        s = jnp.where(qpos[:, None] >= kpos[None, :], s, -jnp.inf)
        lse = jax.scipy.special.logsumexp(s, axis=-1)
        o = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v_)
        return (o * 1.3).sum() + (lse * 0.7).sum()

    gf = jax.grad(loss_f, (0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, (0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", gf, gd):
        assert_almost_equal(onp.asarray(a), onp.asarray(b), rtol=2e-3,
                            atol=2e-3)


def test_flash_future_block_fully_masked(interpret_kernels):
    """A K/V block entirely in the query block's future must contribute
    zero output and lse=-inf (the ring 'skip' case, handled by masking)."""
    B, H, T, D = 1, 1, 128, 64
    q, k, v = (_rand((B, H, T, D), s) for s in (9, 10, 11))
    o, lse = pallas_ops.flash_attention_with_lse(
        q, k, v, causal=True, q_offset=0, k_offset=4096)
    assert onp.all(onp.asarray(o) == 0.0)
    assert onp.all(onp.isneginf(onp.asarray(lse)))
    # and gradients through it are zero, not NaN
    g = jax.grad(lambda q_: pallas_ops.flash_attention_with_lse(
        q_, k, v, causal=True, q_offset=0, k_offset=4096)[0].sum())(q)
    assert onp.all(onp.asarray(g) == 0.0)


def test_flash_bf16(interpret_kernels):
    B, H, T, D = 1, 2, 128, 64
    q, k, v = (_rand((B, H, T, D), s).astype(jnp.bfloat16)
               for s in (12, 13, 14))
    o_f = pallas_ops.flash_attention(q, k, v, causal=True)
    o_d = dot_product_attention(q, k, v, causal=True)
    assert o_f.dtype == jnp.bfloat16
    assert_almost_equal(onp.asarray(o_f, dtype=onp.float32),
                        onp.asarray(o_d, dtype=onp.float32),
                        rtol=3e-2, atol=3e-2)


def test_ring_uses_kernel_in_interpret_mode(interpret_kernels):
    """The ring→Pallas seam: traced per-step offsets from lax.axis_index
    feed the kernel's SMEM scalars inside fori_loop under shard_map —
    exercised with real kernel code (interpret mode), cp=2, T_local=128."""
    from jax.sharding import PartitionSpec as P  # noqa: F401
    from mxnet_tpu import parallel

    mesh = parallel.create_mesh(cp=2)
    B, H, T, D = 1, 2, 256, 64
    q, k, v = (_rand((B, H, T, D), s) for s in (20, 21, 22))
    for causal in (False, True):
        ring = parallel.ring_attention_sharded(q, k, v, mesh, causal=causal)
        dense = dot_product_attention(q, k, v, causal=causal)
        assert_almost_equal(onp.asarray(ring), onp.asarray(dense),
                            rtol=3e-4, atol=3e-4)
    # and gradients through the kernel-backed ring
    def lr(q_):
        return parallel.ring_attention_sharded(q_, k, v, mesh,
                                               causal=True).sum()

    def ld(q_):
        return dot_product_attention(q_, k, v, causal=True).sum()

    gr = jax.grad(lr)(q)
    gd = jax.grad(ld)(q)
    assert_almost_equal(onp.asarray(gr), onp.asarray(gd), rtol=2e-3,
                        atol=2e-3)


def test_flash_custom_block_sizes(interpret_kernels):
    B, H, T, D = 1, 1, 256, 64
    q, k, v = (_rand((B, H, T, D), s) for s in (30, 31, 32))
    o = pallas_ops.flash_attention(q, k, v, causal=True, block_q=64,
                                   block_k=64)
    d = dot_product_attention(q, k, v, causal=True)
    assert_almost_equal(onp.asarray(o), onp.asarray(d), rtol=2e-4,
                        atol=2e-4)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("hkv", [1, 2])
def test_flash_gqa_matches_repeated_dense(interpret_kernels, causal, hkv):
    """GQA/MQA: kv with fewer heads through the kernel's index-mapped
    blocks == dense attention over explicitly repeated kv — forward and
    all three gradients (dk/dv reduce over each kv group)."""
    B, H, T, D = 1, 4, 256, 64
    rep = H // hkv
    q = _rand((B, H, T, D), 0)
    k = _rand((B, hkv, T, D), 1)
    v = _rand((B, hkv, T, D), 2)

    def loss_flash(q, k, v):
        return pallas_ops.flash_attention(q, k, v, causal=causal).sum()

    def loss_dense(q, k, v):
        kr = jnp.repeat(k, rep, axis=1)
        vr = jnp.repeat(v, rep, axis=1)
        return dot_product_attention(q, kr, vr, causal=causal).sum()

    o_f = pallas_ops.flash_attention(q, k, v, causal=causal)
    o_d = dot_product_attention(q, jnp.repeat(k, rep, 1),
                                jnp.repeat(v, rep, 1), causal=causal)
    assert_almost_equal(onp.asarray(o_f), onp.asarray(o_d), rtol=2e-4,
                        atol=2e-4)
    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gd, "qkv"):
        assert a.shape == b.shape, name
        assert_almost_equal(onp.asarray(a), onp.asarray(b), rtol=5e-4,
                            atol=5e-4)


def test_flash_gqa_indivisible_heads_rejected(interpret_kernels):
    q = _rand((1, 3, 256, 64), 0)
    k = _rand((1, 2, 256, 64), 1)
    with pytest.raises(ValueError, match="not a multiple"):
        pallas_ops.flash_attention(q, k, k)


def test_flash_gqa_fallback_path():
    """Off-kernel (non-interpret CPU) the GQA form falls back to dense
    with materialized repeats — same numerics, (B, Hkv, T, D) grads."""
    B, H, hkv, T, D = 1, 4, 2, 64, 16  # T not 128-aligned -> fallback
    q = _rand((B, H, T, D), 3)
    k = _rand((B, hkv, T, D), 4)
    v = _rand((B, hkv, T, D), 5)
    o = pallas_ops.flash_attention(q, k, v, causal=True)
    ref = dot_product_attention(q, jnp.repeat(k, 2, 1),
                                jnp.repeat(v, 2, 1), causal=True)
    assert_almost_equal(onp.asarray(o), onp.asarray(ref), rtol=1e-5,
                        atol=1e-5)


def test_npx_flash_attention_entry_point():
    """User-facing ``mx.npx.flash_attention``: NDArray in/out, dense-
    equivalent values, and gradients through the autograd tape (the
    documented MIGRATION.md surface)."""
    import mxnet_tpu as mx
    from mxnet_tpu import autograd

    B, H, T, D = 1, 2, 64, 16
    rs = onp.random.RandomState(11)
    qn, kn, vn = (rs.normal(0, 1, (B, H, T, D)).astype("float32")
                  for _ in range(3))
    q, k, v = (mx.np.array(a) for a in (qn, kn, vn))
    out = mx.npx.flash_attention(q, k, v, causal=True)
    ref = dot_product_attention(jnp.asarray(qn), jnp.asarray(kn),
                                jnp.asarray(vn), causal=True)
    assert_almost_equal(out.asnumpy(), onp.asarray(ref), rtol=1e-5,
                        atol=1e-5)

    for a in (q, k, v):
        a.attach_grad()
    with autograd.record():
        y = mx.npx.flash_attention(q, k, v, causal=True).sum()
    y.backward()

    def loss(qa, ka, va):
        return dot_product_attention(qa, ka, va, causal=True).sum()

    refg = jax.grad(loss, argnums=(0, 1, 2))(
        jnp.asarray(qn), jnp.asarray(kn), jnp.asarray(vn))
    for g, r in zip((q.grad, k.grad, v.grad), refg):
        assert_almost_equal(g.asnumpy(), onp.asarray(r), rtol=1e-4,
                            atol=1e-4)


def test_npx_flash_attention_gqa_shapes():
    """GQA through the npx surface: (B, Hkv, T, D) kv against
    (B, Hq, T, D) queries returns (B, Hq, T, D)."""
    import mxnet_tpu as mx
    q = mx.np.random.normal(0, 1, (1, 4, 64, 16))
    k = mx.np.random.normal(0, 1, (1, 2, 64, 16))
    v = mx.np.random.normal(0, 1, (1, 2, 64, 16))
    out = mx.npx.flash_attention(q, k, v)
    assert out.shape == (1, 4, 64, 16)


@pytest.mark.parametrize("causal", [False, True])
def test_chunked_fallback_matches_dense_with_lse(monkeypatch, causal):
    """The memory-bounded chunked fallback (what lets the CPU-mesh ring
    run million-token blocks without a (T x Tk) score matrix) has
    IDENTICAL (o, lse) semantics to the one-shot dense form — forced on
    at small sizes by dropping the size threshold and chunk size (512
    tokens / 128-chunks = a 4x4 chunk grid), across causality,
    ring-style block offsets, and GQA heads."""
    monkeypatch.setattr(pallas_ops, "_CHUNK_THRESHOLD", 0)
    monkeypatch.setattr(pallas_ops, "_CHUNK", 128)
    B, H, T, D = 1, 2, 512, 8
    q = _rand((B, H, T, D), 31)
    for hkv, q_off, k_off in ((H, 0, 0),       # diagonal block
                              (H, 1024, 0),    # fully visible block
                              (H, 0, 1024),    # fully masked block
                              (H, 512, 256),   # partial overlap
                              (1, 512, 256)):  # GQA
        k = _rand((B, hkv, T, D), 32)
        v = _rand((B, hkv, T, D), 33)
        o_c, lse_c = pallas_ops.flash_attention_with_lse(
            q, k, v, causal=causal, q_offset=q_off, k_offset=k_off)
        off = (jnp.asarray([q_off], jnp.int32),
               jnp.asarray([k_off], jnp.int32))
        o_d, lse_d = pallas_ops._dense_with_lse(
            q, k, v, off[0], off[1], causal, D ** -0.5)
        assert_almost_equal(onp.asarray(o_c), onp.asarray(o_d),
                            rtol=2e-6, atol=2e-6)
        lc, ld = onp.asarray(lse_c), onp.asarray(lse_d)
        mask = onp.isfinite(ld)
        onp.testing.assert_array_equal(onp.isfinite(lc), mask)
        onp.testing.assert_allclose(lc[mask], ld[mask], rtol=2e-6,
                                    atol=2e-6)


def test_chunked_fallback_threshold_and_divisibility_gate():
    """Below the score-element threshold (or with a sequence no >=128
    power-of-two chunk divides) the fallback stays the one-shot dense
    form — the chunked path only arms when it pays."""
    B, H, T, D = 1, 1, 128, 8
    q = _rand((B, H, T, D), 34)
    k = _rand((B, H, T, D), 35)
    v = _rand((B, H, T, D), 36)
    calls = []
    real = pallas_ops._chunked_with_lse

    def spy(*a, **kw):
        calls.append(1)
        return real(*a, **kw)

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(pallas_ops, "_chunked_with_lse", spy)
        pallas_ops.flash_attention_with_lse(q, k, v, causal=True)
        assert not calls                   # under threshold: dense
        mp.setattr(pallas_ops, "_CHUNK_THRESHOLD", 0)
        pallas_ops.flash_attention_with_lse(q, k, v, causal=True)
        assert calls                       # forced: chunked
    assert pallas_ops._chunk_for(8192) == 4096
    assert pallas_ops._chunk_for(640) == 128   # falls to a divisor
    assert pallas_ops._chunk_for(60) is None   # no >=128 pow2 divides


# ---------------------------------------------------------------------------
# under a mesh: one kernel per (dp, tp) shard, wrapped in a shard_map
# ---------------------------------------------------------------------------

def _flash_sharded(q, k, v):
    """As the models call it: the caller says how the kernel is split."""
    from mxnet_tpu import parallel
    return pallas_ops.flash_attention(
        q, k, v, causal=True,
        shard=parallel.kernel_shard(q.shape[0], k.shape[1]))


@pytest.mark.parametrize("axes,hkv", [({"dp": 2, "tp": 2}, 2),
                                      ({"dp": 2, "tp": 2}, 1),
                                      ({"tp": 4}, 4)],
                         ids=["dp2xtp2", "tp-drops-for-mqa", "tp4"])
def test_flash_per_shard_under_mesh_matches_dense(interpret_kernels, axes,
                                                  hkv):
    """GQA forward and grads through the per-shard wrap equal the dense
    reference: heads and batch rows are independent, so sharding them
    changes nothing.  With 1 KV head the tp axis cannot split the heads
    and drops out of the wrap (every tp shard holds them all)."""
    from mxnet_tpu import parallel
    B, Hq, T, D = 2, 4, 128, 64
    q = _rand((B, Hq, T, D), 0)
    k, v = (_rand((B, hkv, T, D), s) for s in (1, 2))
    w = jnp.cos(jnp.arange(D, dtype=jnp.float32))
    rep = Hq // hkv

    def loss_f(q, k, v):
        return (_flash_sharded(q, k, v) * w).sum()

    def loss_d(q, k, v):
        return (dot_product_attention(q, jnp.repeat(k, rep, axis=1),
                                      jnp.repeat(v, rep, axis=1),
                                      causal=True) * w).sum()

    mesh = parallel.create_mesh(**axes)
    with parallel.mesh_scope(mesh):
        lowered = jax.jit(jax.value_and_grad(loss_f, (0, 1, 2))) \
            .lower(q, k, v)
        assert "sdy.manual_computation" in lowered.as_text()
        lf, gf = lowered.compile()(q, k, v)
    ld, gd = jax.value_and_grad(loss_d, (0, 1, 2))(q, k, v)
    assert_almost_equal(onp.asarray(lf), onp.asarray(ld), rtol=2e-4,
                        atol=2e-4)
    for a, b in zip(gf, gd):
        assert_almost_equal(onp.asarray(a), onp.asarray(b), rtol=2e-3,
                            atol=2e-3)


def test_paged_attention_per_shard_under_mesh(interpret_kernels):
    from mxnet_tpu import parallel
    S, Hq, Hkv, D, psz, pages, MP = 3, 8, 2, 64, 128, 7, 3
    rng = onp.random.RandomState(2)
    q = jnp.asarray(rng.randn(S, Hq, D).astype(onp.float32))
    kp, vp = (jnp.asarray(rng.randn(pages, Hkv, psz, D)
                          .astype(onp.float32)) for _ in range(2))
    pt = jnp.asarray(rng.randint(1, pages, (S, MP)).astype(onp.int32))
    lens = jnp.asarray(onp.array([5, 3 * psz, 0], onp.int32))
    dense = pallas_ops._paged_dense(q, kp, vp, pt, lens, D ** -0.5)
    def paged(q, kp, vp, pt, lens):
        return pallas_ops.paged_attention(
            q, kp, vp, pt, lens,
            shard=parallel.kernel_shard(S, Hkv, batch_axis=None))

    with parallel.mesh_scope(parallel.create_mesh(tp=2)):
        lowered = jax.jit(paged).lower(q, kp, vp, pt, lens)
        assert "sdy.manual_computation" in lowered.as_text()
        got = lowered.compile()(q, kp, vp, pt, lens)
    onp.testing.assert_allclose(onp.asarray(got), onp.asarray(dense),
                                atol=2e-5)


def test_kernel_shard_keeps_what_the_mesh_can_split():
    """The caller's axis names, kept where the mesh has them, they
    divide, and the trace is not manual over them already."""
    from jax.sharding import PartitionSpec as P
    from mxnet_tpu import parallel
    assert parallel.kernel_shard(2, 2) is None  # no mesh in scope
    mesh = parallel.create_mesh(dp=2, tp=2)
    with parallel.mesh_scope(parallel.create_mesh(dp=1)):
        assert parallel.kernel_shard(2, 2) is None  # one device
    with parallel.mesh_scope(mesh):
        assert parallel.kernel_shard(2, 2) == (mesh, "dp", "tp")
        assert parallel.kernel_shard(2, 1) == (mesh, "dp", None)
        assert parallel.kernel_shard(3, 2) == (mesh, None, "tp")
        assert parallel.kernel_shard(2, 2, batch_axis=None) == \
            (mesh, None, "tp")
        assert parallel.kernel_shard(2, 2, "rows", "tp") == \
            (mesh, None, "tp")
        seen = []

        def body(x):
            seen.append(parallel.kernel_shard(2, 2))
            return x

        jax.eval_shape(jax.shard_map(body, mesh=mesh, in_specs=P(),
                                     out_specs=P(), axis_names={"dp"},
                                     check_vma=False), jnp.zeros(2))
        jax.eval_shape(jax.shard_map(body, mesh=mesh, in_specs=P(),
                                     out_specs=P(), check_vma=False),
                       jnp.zeros(2))
    assert seen == [(mesh, None, "tp"), None]


def test_flash_per_shard_inside_a_partly_manual_trace(interpret_kernels):
    """A pipeline-style body, manual over ``pp`` with ``tp`` left to
    GSPMD: the kernel's shard_map nests and takes the rest."""
    from jax.sharding import PartitionSpec as P
    from mxnet_tpu import parallel
    q = _rand((2, 4, 128, 64), 0)
    k, v = (_rand((2, 2, 128, 64), s) for s in (1, 2))
    mesh = parallel.create_mesh(pp=2, tp=2)
    stage = jax.shard_map(_flash_sharded, mesh=mesh,
                          in_specs=(P("pp"),) * 3, out_specs=P("pp"),
                          axis_names={"pp"}, check_vma=False)
    with parallel.mesh_scope(mesh):
        got = jax.jit(stage)(q, k, v)
    want = dot_product_attention(q, jnp.repeat(k, 2, axis=1),
                                 jnp.repeat(v, 2, axis=1), causal=True)
    assert_almost_equal(onp.asarray(got), onp.asarray(want), rtol=2e-4,
                        atol=2e-4)


def test_a_mesh_that_splits_nothing_is_said_once(interpret_kernels, caplog):
    """No axis of the mesh shards batch rows or heads: still a shard_map
    (GSPMD cannot take the kernel), every device runs all of it, and the
    log says so — once."""
    from mxnet_tpu import parallel
    q = _rand((1, 2, 128, 64), 0)
    pallas_ops._warned_whole.clear()
    with parallel.mesh_scope(parallel.create_mesh(cp=2)), \
            caplog.at_level("WARNING", logger=pallas_ops.__name__):
        for _ in range(2):  # two traces: a fresh jit each time
            got = jax.jit(lambda *a: _flash_sharded(*a))(q, q, q)
    assert len([r for r in caplog.records
                if "WHOLE" in r.getMessage()]) == 1
    want = dot_product_attention(q, q, q, causal=True)
    assert_almost_equal(onp.asarray(got), onp.asarray(want), rtol=2e-4,
                        atol=2e-4)


def test_flash_row_past_vmem_raises_with_the_bound(monkeypatch):
    """The kernels keep a head's whole K/V (dkv: Q/dO) row in VMEM.  A
    row that cannot fit is the repo's own error, naming the bound and
    the longest row that does fit — never another implementation."""
    monkeypatch.setattr(pallas_ops, "_pallas_available", lambda: True)
    both = r"100 MiB.*98304 tokens forward and 87296 with the backward"

    def flash(q):
        return pallas_ops.flash_attention(q, q, q, causal=True)

    def row(T):
        return jax.ShapeDtypeStruct((1, 8, T, 128), jnp.bfloat16)

    with pytest.raises(ValueError, match="forward/dq kernel.*" + both):
        jax.eval_shape(flash, row(131072))
    # a row the forward takes and the backward does not: dkv also keeps
    # the lse and delta rows
    jax.eval_shape(flash, row(98304))
    with pytest.raises(ValueError, match="dkv kernel.*" + both):
        jax.eval_shape(jax.grad(lambda q: flash(q).astype(jnp.float32).sum()),
                       row(98304))


def test_interpret_mode_is_for_the_cpu_platform(monkeypatch):
    monkeypatch.setattr(pallas_ops, "_INTERPRET", True)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="MXNET_PALLAS_INTERPRET"):
        pallas_ops._pallas_available()
