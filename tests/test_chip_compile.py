"""The main path's kernels, compiled for the chip they run on.

The TPU's compiler is installed beside the CPU suite and compiles for a
chip that is described, not attached (``jax.experimental.topologies``).
It refuses what interpret mode lets through: a kernel that keeps more
in VMEM than a v5e core grants, and a Mosaic call left for GSPMD to
partition.  Each case compiles a kernel at Llama-3-8B head layout
(32 query / 8 KV heads of 128, bf16) from shapes alone and asserts the
kernel is in the compiled text — nothing runs, so nothing here is a
result or a time.

This is the ONLY test file that loads the chip's compiler: one process
at a time may hold the library, so the topology is described inside the
module-scoped fixture below (never at import), and every compile
happens in the test's own process.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as onp
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, \
    SingleDeviceSharding

import mxnet_tpu as mx
from mxnet_tpu import gluon, parallel
from mxnet_tpu.gluon import nn
from mxnet_tpu.ops import pallas_ops

B, H, HKV, D = 1, 32, 8, 128


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_SKIP_MDS_QUERY", "true")  # no GCE probe
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu here, or another process holds it
        pytest.skip("no v5e:2x2 topology can be described here: %s"
                    % str(e)[:200])


@pytest.fixture
def on_chip(monkeypatch):
    """The kernel gate reads the default backend, which is the CPU in
    this suite: answer for it as a chip would."""
    monkeypatch.setattr(pallas_ops, "_pallas_available", lambda: True)
    monkeypatch.setattr(pallas_ops, "_INTERPRET", False)


def _qkv(T, sharding, batch=B):
    return (jax.ShapeDtypeStruct((batch, H, T, D), jnp.bfloat16,
                                 sharding=sharding),
            jax.ShapeDtypeStruct((batch, HKV, T, D), jnp.bfloat16,
                                 sharding=sharding),
            jax.ShapeDtypeStruct((batch, HKV, T, D), jnp.bfloat16,
                                 sharding=sharding))


def _flash(q, k, v):
    # as the models call it: the caller says how the kernel is split
    return pallas_ops.flash_attention(
        q, k, v, causal=True,
        shard=parallel.kernel_shard(q.shape[0], k.shape[1]))


def _flash_grad(q, k, v):
    return jax.grad(lambda *a: _flash(*a).astype(jnp.float32).sum(),
                    argnums=(0, 1, 2))(q, k, v)


def _kernels(fn, *avals):
    return jax.jit(fn).lower(*avals).compile().as_text() \
        .count("tpu_custom_call")


# 32768 is past the 16 MiB of VMEM a kernel gets unasked: K and V rows
# of 8 MiB each, double-buffered.  The kernels ask for what they hold
# (pallas_ops._row_params), so the advertised length compiles — and so
# does the longest row _row_params admits, which is what holds its bound
# (_VMEM_MAX) to the chip's compiler: 98,304 tokens forward, 87,296 where
# the backward kernels run too.
@pytest.mark.parametrize("T", [2048, 8192, 32768, 98304])
def test_flash_forward_compiles(topo, on_chip, T):
    assert T <= pallas_ops._max_row(D, jnp.bfloat16, False) == 98304
    one_chip = SingleDeviceSharding(topo.devices[0])
    assert _kernels(_flash, *_qkv(T, one_chip)) == 1


@pytest.mark.parametrize("T", [2048, 8192, 32768, 87296])
def test_flash_grad_compiles(topo, on_chip, T):
    assert T <= pallas_ops._max_row(D, jnp.bfloat16, True) == 87296
    one_chip = SingleDeviceSharding(topo.devices[0])
    # the forward, dq and dkv kernels
    assert _kernels(_flash_grad, *_qkv(T, one_chip)) == 3


def test_a_kernels_bytes_do_not_depend_on_who_called_it_first(topo, on_chip):
    # jax keeps a kernel's trace from the first time it met it, and the
    # kernel's serialized body carries its ops' locations: with the call
    # stack in them the same function lowers to other bytes after
    # another caller (and misses the persistent cache);
    # utils.compile_cache.stable_locations() leaves the stack out
    from mxnet_tpu.utils import compile_cache
    avals = _qkv(2048, SingleDeviceSharding(topo.devices[0]))

    def deeper(*a):
        return _flash_grad(*a)

    def texts():
        out = []
        for first in (_flash_grad, deeper):
            jax.clear_caches()
            jax.jit(first).lower(*avals)
            out.append(jax.jit(_flash_grad).lower(*avals).as_text())
        return out
    a, b = texts()
    assert a != b
    with compile_cache.stable_locations():
        a, b = texts()
    assert a == b and a.count("tpu_custom_call") == 3


def _paged_avals(sh):
    S, pages, psz, MP = 8, 64, 128, 9
    pool = jax.ShapeDtypeStruct((pages, HKV, psz, D), jnp.bfloat16,
                                sharding=sh(P(None, "tp", None, None)))
    return (jax.ShapeDtypeStruct((S, H, D), jnp.bfloat16,
                                 sharding=sh(P(None, "tp", None))),
            pool, pool,
            jax.ShapeDtypeStruct((S, MP), jnp.int32, sharding=sh(P())),
            jax.ShapeDtypeStruct((S,), jnp.int32, sharding=sh(P())))


def _paged(q, k_pages, v_pages, page_table, lengths):
    return pallas_ops.paged_attention(
        q, k_pages, v_pages, page_table, lengths,
        shard=parallel.kernel_shard(q.shape[0], k_pages.shape[1],
                                    batch_axis=None))


def test_paged_attention_compiles(topo, on_chip):
    one_chip = SingleDeviceSharding(topo.devices[0])
    assert _kernels(_paged, *_paged_avals(lambda spec: one_chip)) == 1


# GSPMD cannot partition a Mosaic kernel ("Mosaic kernels cannot be
# automatically partitioned"): under a mesh the caller passes the axes
# that shard batch and heads (parallel.kernel_shard), the entry points
# wrap the kernel in a shard_map over them, and the kernel stays in
# every device's program.
@pytest.mark.parametrize("fn,n", [(_flash, 1), (_flash_grad, 3)],
                         ids=["forward", "grad"])
def test_flash_compiles_on_dp_tp_mesh(topo, on_chip, fn, n):
    mesh = Mesh(onp.array(topo.devices).reshape(2, 2), ("dp", "tp"))
    sh = NamedSharding(mesh, P("dp", "tp", None, None))
    with parallel.mesh_scope(mesh):
        assert _kernels(fn, *_qkv(2048, sh, batch=2)) == n


def test_paged_attention_compiles_on_tp_mesh(topo, on_chip):
    mesh = Mesh(onp.array(topo.devices[:2]), ("tp",))
    with parallel.mesh_scope(mesh):
        assert _kernels(
            _paged,
            *_paged_avals(lambda spec: NamedSharding(mesh, spec))) == 1


def test_flash_compiles_inside_a_partly_manual_trace(topo, on_chip):
    """A pipeline-style body, manual over ``pp`` with ``tp`` left to
    GSPMD: the kernel's shard_map nests and takes the rest."""
    mesh = Mesh(onp.array(topo.devices).reshape(2, 2), ("pp", "tp"))
    stage = jax.shard_map(_flash, mesh=mesh, in_specs=(P("pp"),) * 3,
                          out_specs=P("pp"), axis_names={"pp"},
                          check_vma=False)
    sh = NamedSharding(mesh, P("pp", "tp", None, None))
    with parallel.mesh_scope(mesh):
        assert _kernels(stage, *_qkv(2048, sh, batch=2)) == 1


def test_train_step_aot_topology_mesh():
    """TrainStep(aot=True) compiles against a TPU *topology description*
    with zero chips: the lowered+compiled artifact is the real TPU
    executable text (the HLO ratchet's evidence source).  Skips when the
    AOT client is unavailable in this environment."""
    import os
    os.environ.setdefault("TPU_SKIP_MDS_QUERY", "true")  # no GCE probe
    mx.np.random.seed(0)
    try:
        from jax.experimental import topologies
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x4")
    except Exception as e:  # env-dependent: no libtpu/AOT support
        pytest.skip("TPU AOT topology client unavailable: %s"
                    % str(e)[:120])
    mesh = jax.sharding.Mesh(onp.array(topo.devices), ("dp",))
    net = nn.Dense(16, in_units=32)
    net.initialize()
    step = parallel.TrainStep(net, gluon.loss.L2Loss(),
                              mx.optimizer.SGD(learning_rate=0.1),
                              mesh=mesh, zero1=True, aot=True)
    x = mx.np.random.uniform(-1, 1, (16, 32))
    y = mx.np.random.uniform(-1, 1, (16, 16))
    txt = step.lower(x, y).compile().as_text()
    assert "all-gather" in txt  # the sharded update's param gather
    with pytest.raises(RuntimeError, match="aot"):
        step(x, y)


# The names the device trace is read by (PERF.md section 3): a kernel's
# ``name=`` becomes the custom call's instruction name and its op_name,
# a ``jax.named_scope`` a component of the op_name of every op under it.
def _kernel_lines(fn, *avals):
    text = jax.jit(fn).lower(*avals).compile().as_text()
    return [ln for ln in text.splitlines() if "tpu_custom_call" in ln]


@pytest.mark.parametrize("fn,names", [
    (_flash, ["flash_fwd"]),
    (_flash_grad, ["flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"]),
    (_paged, ["paged_attention"])], ids=["forward", "grad", "paged"])
def test_kernels_carry_their_names(topo, on_chip, fn, names):
    one_chip = SingleDeviceSharding(topo.devices[0])
    avals = _paged_avals(lambda spec: one_chip) if fn is _paged \
        else _qkv(2048, one_chip)
    lines = _kernel_lines(fn, *avals)
    assert len(lines) == len(names)
    for name in names:     # .../jvp(flash_fwd)/pallas_call
        named = [ln for ln in lines if re.search(
            r'op_name="[^"]*\b%s\)*/pallas_call"' % name, ln)]
        assert len(named) == 1, name


@pytest.mark.parametrize("fn,kinds", [
    (_flash, {"fwd": "flash_fwd"}),
    (_flash_grad, {"fwd": "flash_fwd", "dq": "flash_bwd_dq",
                   "dkv": "flash_bwd_dkv"})], ids=["forward", "grad"])
def test_flash_compiles_at_the_token_cells_shape(topo, on_chip, fn, kinds):
    """``ouro_2p6b_train_2x4096``'s attention, (2, 16, 4096, 128) bf16,
    with the tiles the picker gives it: each kernel once, under its old
    name, the tile it runs as the scope component right above it."""
    one_chip = SingleDeviceSharding(topo.devices[0])
    row = jax.ShapeDtypeStruct((2, 16, 4096, 128), jnp.bfloat16,
                               sharding=one_chip)
    lines = _kernel_lines(fn, row, row, row)
    assert len(lines) == len(kinds)
    for kind, name in kinds.items():
        bq, bk = pallas_ops._pick_tiles(kind, 4096, 4096, 128, jnp.bfloat16)
        assert (bq, bk) != (128, 128)
        named = [ln for ln in lines if re.search(
            r'op_name="[^"]*\btiles_q%d_k%d\)*/%s/pallas_call"'
            % (bq, bk, name), ln)]
        assert len(named) == 1, (name, bq, bk)


def test_looped_step_compiles_with_flash_under_block_recompute(topo,
                                                               on_chip):
    """The looped decoder's training step at the published widths, two
    of the layers and 2 x 1,024 tokens, compiled for one described v5e:
    the flash kernels lower inside the scan over the passes and under
    the block-level ``jax.checkpoint`` (forward, dq, dkv a layer: the
    marked block keeps the forward kernel's output and row sums, so no
    kernel is in the recomputed part), by name; the step's temporaries
    stay a fraction of what the unmarked step needs, and over the bare
    checkpoint's by what is kept and no more."""
    from mxnet_tpu.models import LoopedLM, ouro_2p6b_config
    from mxnet_tpu.ndarray.ndarray import NDArray
    from mxnet_tpu.numpy import random as _random
    one_chip = SingleDeviceSharding(topo.devices[0])

    def compiled(recompute, keeps=None):
        cfg = ouro_2p6b_config(n_layers=2, vocab_size=8192,
                               dtype="bfloat16")
        net = LoopedLM(cfg)
        for blk in net.layers:
            blk.recompute(recompute)
            if keeps is not None:
                blk._recompute_keeps = keeps
        net.cast("bfloat16")
        net.initialize()
        step = parallel.TrainStep(
            net, None, mx.optimizer.AdamW(learning_rate=3e-4), mesh=None,
            forward_fn=lambda net, t, l: net.loss(t, l, chunk=1024))
        tok = jnp.zeros((2, 1024), jnp.int32)
        step._jitted = step._build((tok, tok))
        args = ({n: p._data._data for n, p in step._params}, step._states,
                jnp.int32(1), jnp.float32(3e-4), _random.new_key(), tok, tok)
        avals = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(jnp.shape(a), a.dtype,
                                           sharding=one_chip), args)
        return step._jitted.lower(*avals).compile()

    marked = compiled(True)
    text = marked.as_text()
    assert "HloModule jit_step" in text
    kernels = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert len(kernels) == 2 * 3
    for name in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        named = [ln for ln in kernels if re.search(
            r'op_name="jit\(step\)/[^"]*/loop/while/body/[^"]*/attention/'
            r'[^"]*\b%s\)*/pallas_call"' % name, ln)]
        assert len(named) == 2, name
    assert not any("rematted_computation" in ln for ln in kernels)
    assert 'exit_loss/' in text
    temp = marked.memory_analysis().temp_size_in_bytes
    assert temp < 0.8 * compiled(False).memory_analysis().temp_size_in_bytes
    # against the bare checkpoint, which runs the forward kernel again:
    # o (2 x 1,024 x 2,048 bf16) and lse (2 x 16 x 1,024 float32) for
    # each of the 2 layers x 4 passes, and a tenth for what moves round
    # (at the cell's full shape the live peak rises by exactly that and
    # the packed temporaries by 43% more: PERF.md section 6, PR 31)
    bare = compiled(True, keeps=())
    assert bare.as_text().count("tpu_custom_call") == 2 * 4
    kept = 2 * 4 * (2 * 1024 * 2048 * 2 + 2 * 16 * 1024 * 4)
    assert temp - bare.memory_analysis().temp_size_in_bytes <= 1.1 * kept


def test_eva_attention_compiles_at_the_byte_cells_shape(topo, on_chip):
    """``evabyte_6p5b_train_1x8192``'s attention, (1, 32, 8192, 128) bf16
    with a head's two pooling vectors, forward and gradient: one call
    each of the fused kernels (four windows of 2,048; 384 summaries, 128
    a window), under ``eva/eva_flash`` and the tiles the picker gives
    them, and no op of the attention outside ``eva``."""
    from mxnet_tpu.models import eva_attention
    one_chip = SingleDeviceSharding(topo.devices[0])
    row = jax.ShapeDtypeStruct((1, 32, 8192, 128), jnp.bfloat16,
                               sharding=one_chip)
    vec = jax.ShapeDtypeStruct((32, 128), jnp.bfloat16, sharding=one_chip)

    def grad(q, k, v, mu, phi):
        return jax.grad(lambda *a: eva_attention(*a, 2048, 16)
                        .astype(jnp.float32).sum(), range(5))(
                            q, k, v, mu, phi)

    text = jax.jit(grad).lower(row, row, row, vec, vec).compile().as_text()
    lines = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert len(lines) == 3
    for kind in ("fwd", "dq", "dkv"):
        tiles = pallas_ops._eva_tiles(kind, 2048, 128, 384, 128,
                                      jnp.bfloat16, None, None)
        assert tiles == (512, 512, 128), kind
        name = "eva_flash_" + ("fwd" if kind == "fwd" else "bwd_" + kind)
        named = [ln for ln in lines if re.search(
            r'op_name="[^"]*\beva\)*/eva_flash/tiles_q%d_k%d_s%d/%s/'
            r'pallas_call"' % (tiles + (name,)), ln)]
        assert len(named) == 1, (name, lines)
    # every op is the attention's, under ``eva``, but the loss this
    # test puts on it: the cast, the sum and its cotangent
    for op in set(re.findall(r'op_name="(jit\(grad\)/[^"]*)"', text)):
        assert re.match(r"jit\(grad\)/(transpose\()?jvp\(eva\)", op) \
            or re.fullmatch(r"jit\(grad\)/(transpose\()?jvp\(\)\)?/"
                            r"(convert_element_type|reduce_sum|"
                            r"broadcast_in_dim)", op), op


def test_the_token_cells_flash_kernels_keep_their_names_and_tiles(
        topo, on_chip):
    """``ouro_2p6b_train_2x4096``'s attention, (2, 16, 4096, 128) bf16
    causal, forward and gradient: the three flash kernels the parent
    compiled, by name and tile — EVA's kernels are a path of their
    own."""
    one_chip = SingleDeviceSharding(topo.devices[0])
    row = jax.ShapeDtypeStruct((2, 16, 4096, 128), jnp.bfloat16,
                               sharding=one_chip)
    lines = _kernel_lines(_flash_grad, row, row, row)
    assert len(lines) == 3
    for name in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        named = [ln for ln in lines if re.search(
            r'op_name="[^"]*\btiles_q512_k512\)*/%s/pallas_call"' % name,
            ln)]
        assert len(named) == 1, (name, lines)
    assert not any("eva" in ln for ln in lines)


def test_sparse_attention_compiles_at_the_sparse_cells_shape(topo, on_chip):
    """``keye_vl2_30b_a3b_train_1x32768``'s sparse attention at its
    widths and length (32,768 queries, 32 heads and 4 K/V groups of 128,
    2,048 selected keys a query, bf16), forward and gradient, with the
    selection's bits as ``models/dsa.py`` hands them: ``dsa_fwd`` under
    the tile it runs, the masked backward's ``dsa_masked_dq`` and
    ``dsa_masked_dkv`` under theirs (one call each), no ``dsa_bwd``, and
    neither a gather nor a scatter of rows left to XLA in the
    backward."""
    one_chip = SingleDeviceSharding(topo.devices[0])
    N, K = 32768, 2048

    def av(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def grad(q, k, v, idx, n_valid, bits):
        return jax.grad(lambda *a: pallas_ops.sparse_attention(
            *a, idx, n_valid, bits=bits)[0].astype(jnp.float32).sum(),
            range(3))(q, k, v)

    before = mx.profiler.get_counters().get("sparse_attn::masked_bwd", 0)
    text = jax.jit(grad).lower(
        av((N, 32, 128)), av((N, 4, 128)), av((N, 4, 128)),
        av((N, K), jnp.int32), av((N,), jnp.int32),
        av((N // 32, N), jnp.uint32)).compile().as_text()
    assert mx.profiler.get_counters()["sparse_attn::masked_bwd"] \
        == before + 1
    lines = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert len(lines) == 3
    bq, bk = pallas_ops._dsa_masked_tiles(N, 128)
    for name, tile in (("dsa_fwd", "q8_k2048"),
                       ("dsa_masked_dq", "q%d_k%d" % (bq, bk)),
                       ("dsa_masked_dkv", "q%d_k%d" % (bq, bk))):
        assert any(re.search(r'op_name="[^"]*\btiles_%s\)*/%s/'
                             r'pallas_call"' % (tile, name), ln)
                   for ln in lines), (name, lines)
    assert not any("dsa_bwd" in ln for ln in lines)
    assert not re.search(r'op_name="[^"]*/scatter/', text)
    assert not re.search(r"= \S+ scatter\(", text)
    assert not re.search(r'op_name="[^"]*transpose\([^"]*/gather/', text)


def test_the_sparse_cells_step_asks_no_more_than_the_chip_has_loaded(
        topo, on_chip, tmp_path):
    """``keye_vl2_30b_a3b_train_1x32768``'s step (4 layers at published
    widths, every block marked for recomputation, AdamW's float32
    moments, 32,768 tokens) compiled for the described v5e from shapes
    alone: the temporaries the chip reserves to load it (XLA's
    memory-usage report, ``preallocated-temp``) stay at or under 10.41
    GiB, a size the chip has loaded (10.54 GiB are free beside the
    cell's state), with one masked backward a layer."""
    from mxnet_tpu.models import TransformerLM, keye_vl2_30b_a3b_config
    from mxnet_tpu.ndarray.ndarray import NDArray
    from mxnet_tpu.numpy import random as _random
    one_chip = SingleDeviceSharding(topo.devices[0])

    def av(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    net = TransformerLM(keye_vl2_30b_a3b_config(
        n_layers=4, vocab_size=18992, moe_held=16, dtype="bfloat16"))
    net.cast("bfloat16")
    for blk in net.layers:
        blk.recompute()
    params = net.collect_params()
    for p in params.values():      # the step is lowered from shapes
        p._data = NDArray(jnp.zeros((), p.dtype))
    step = parallel.TrainStep(
        net, None, mx.optimizer.SGD(learning_rate=3e-4), mesh=None,
        forward_fn=lambda net, t, l: net.loss(t, l, chunk=2048))
    step.optimizer = mx.optimizer.AdamW(learning_rate=3e-4, beta1=0.9,
                                        beta2=0.95, epsilon=1e-8, wd=0.1)
    tok = jnp.zeros((1, 32768), jnp.int32)
    key = _random.new_key()
    args = ({n: av(p.shape, p.dtype) for n, p in params.items()},
            {n: (av(params[n].shape, jnp.float32),) * 2
             for n in step._trainable},
            av((), jnp.int32), av((), jnp.float32), av(key.shape, key.dtype),
            av(tok.shape, tok.dtype), av(tok.shape, tok.dtype))
    before = mx.profiler.get_counters().get("sparse_attn::masked_bwd", 0)
    step._build((tok, tok)).lower(*args).compile(compiler_options={
        "xla_dump_to": str(tmp_path), "xla_dump_hlo_module_re": "jit_step"})
    assert mx.profiler.get_counters()["sparse_attn::masked_bwd"] \
        == before + 4
    report, = [f for f in os.listdir(tmp_path)
               if f.endswith("after_optimizations-memory-usage-report.txt")]
    sizes = [float(m) for m in re.findall(
        r"size ([0-9.]+)GiB, preallocated-temp", open(
            os.path.join(tmp_path, report)).read())]
    assert sizes and max(sizes) <= 10.41, sizes


@pytest.mark.parametrize("block", [None, 256, 128])
def test_window_attention_compiles_at_the_laguna_cells_shape(topo, on_chip,
                                                             block):
    """``laguna_s21_train_1x8192``'s sliding layer, 72 query heads over 8
    K/V heads of 128, 8,192 tokens, a 512-token window, bf16, forward and
    gradient: one call each of ``swa_fwd``, ``swa_bwd_dq`` and
    ``swa_bwd_dkv``, each right under the tile it runs (512 x 512 by
    the picker), and no flash kernel."""
    one_chip = SingleDeviceSharding(topo.devices[0])

    def av(shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    def grad(q, k, v):
        return jax.grad(lambda *a: pallas_ops.window_attention(
            *a, 512, block_q=block, block_k=block).astype(jnp.float32)
            .sum(), range(3))(q, k, v)

    lines = _kernel_lines(grad, av((1, 72, 8192, 128)),
                          av((1, 8, 8192, 128)), av((1, 8, 8192, 128)))
    assert len(lines) == 3
    tile = block or 512
    for name in ("swa_fwd", "swa_bwd_dq", "swa_bwd_dkv"):
        named = [ln for ln in lines if re.search(
            r'op_name="[^"]*\btiles_q%d_k%d\)*/%s/pallas_call"'
            % (tile, tile, name), ln)]
        assert len(named) == 1, (name, lines)
    assert not any("flash_" in ln for ln in lines)


def test_index_kernel_and_grouped_matmul_compile_at_the_cells_widths(
        topo, on_chip):
    """The indexer's scores of 512 queries against 32,768 keys (16 heads
    of 64) and the held experts' grouped matmul (16 experts of 2,048 x
    768 over 4,096 rows): one ``dsa_index`` call, megablox's kernels."""
    from mxnet_tpu.models.experts import grouped_matmul
    one_chip = SingleDeviceSharding(topo.devices[0])

    def av(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    lines = _kernel_lines(
        lambda q, k, w: pallas_ops.index_scores(q, k, w, q0=32256),
        av((16, 512, 64)), av((32768, 64)), av((512, 16), jnp.float32))
    assert len(lines) == 1 and "dsa_index" in lines[0]
    assert _kernels(grouped_matmul, av((4096, 2048)), av((16, 2048, 768)),
                    av((17,), jnp.int32)) >= 1


def test_the_laguna_cells_experts_hold_the_held_share_of_the_pairs(
        topo, on_chip, monkeypatch):
    """``laguna_s21_train_1x8192``'s routed experts, 8,192 tokens, top 10
    of 256 of which 8 are held, 3,072 wide, experts of 1,024, bf16,
    forward and gradient: megablox's ``gmm`` runs on the 5,120 rows of
    the bounded buffer, first and in the loop under ``overflow`` that
    takes the pairs past them; the compiled program's temporaries are no
    larger than those of one buffer of the 65,536 pairs the 8 experts can
    take (8 a token)."""
    from mxnet_tpu.models import experts
    one_chip = SingleDeviceSharding(topo.devices[0])
    T, top_k, held, E, Dm, F = 8192, 10, 8, 256, 3072, 1024

    def av(shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    def compiled():     # a function of its own: the bound is read tracing
        def layer(x, r, w1, w3, w2):
            def loss(*a):
                with jax.named_scope("experts"):    # as the model's block
                    y, aux, _ = experts.routed_experts(*a, 0, top_k,
                                                       "sigmoid", 2.5)
                return jnp.sum(y.astype(jnp.float32) ** 2) + aux
            return jax.value_and_grad(loss, range(5))(x, r, w1, w3, w2)

        return jax.jit(layer).lower(
            av((T, Dm)), av((E, Dm)), av((held, Dm, F)), av((held, Dm, F)),
            av((held, F, Dm))).compile()

    def gmm_rows(program):
        rows = {}
        for hit in re.finditer(r"= bf16\[(\d+),\d+\][^ ]* custom-call\(.*"
                               r'op_name="([^"]*/jit\(gmm\)/pallas_call)"',
                               program.as_text()):
            rows.setdefault("/overflow/" in hit.group(2), set()).add(
                int(hit.group(1)))
        return rows

    assert experts._capacity(T, top_k, held, E) == 5120
    bounded = compiled()
    assert gmm_rows(bounded) == {False: {5120}, True: {5120}}
    monkeypatch.setattr(experts, "_capacity",
                        lambda T, top_k, held, E: T * min(top_k, held))
    every_pair = compiled()
    assert gmm_rows(every_pair) == {False: {65536}, True: {65536}}
    assert bounded.memory_analysis().temp_size_in_bytes \
        <= every_pair.memory_analysis().temp_size_in_bytes


def test_decode_program_carries_its_scopes(topo, on_chip):
    """The serving decode program at one layer of 128-wide heads: the
    K/V write, the attention read (the paged kernel under it, by name)
    and the sampler are told apart in the compiled text."""
    from mxnet_tpu import serve
    from mxnet_tpu.models import tiny_config
    mesh = Mesh(onp.array(topo.devices[:1]), ("dp",))
    cfg = tiny_config(dim=512, n_heads=4, n_kv_heads=2, n_layers=1,
                      dtype="bfloat16")
    lowered, _ = serve.lower_decode_program(cfg=cfg, mesh=mesh)
    text = lowered.compile().as_text()
    assert "HloModule jit_decode" in text
    for scope in ("layer0/attention/kv_write/",
                  "layer0/attention/attention/", "/sample/"):
        assert 'op_name="jit(decode)/' in text and scope in text, scope
    (kernel,) = [ln for ln in text.splitlines()
                 if "tpu_custom_call" in ln]
    assert re.search(r'op_name="jit\(decode\)/layer0/attention/attention/'
                     r'[^"]*paged_attention/pallas_call"', kernel)


def test_the_masked_sparse_backward_compiles_at_the_longest_sequence_the_rule_admits(
        topo, on_chip):
    """``sparse_attention_takes_bits`` at its edge, at the sparse cell's
    widths: the longest whole number of 256-key bands it admits for
    2,048 slots a query (65,536 tokens, whose bits weigh what the slots'
    indices do) compiles for the described v5e with the masked backward's
    two kernels and no row scatter, and one band more is not admitted."""
    one_chip = SingleDeviceSharding(topo.devices[0])
    K = 2048

    def av(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    N = 32768
    while pallas_ops.sparse_attention_takes_bits(N + 256, K, 128):
        N += 256
    assert N == 65536

    def grad(q, k, v, idx, n_valid, bits):
        return jax.grad(lambda *a: pallas_ops.sparse_attention(
            *a, idx, n_valid, bits=bits)[0].astype(jnp.float32).sum(),
            range(3))(q, k, v)

    before = mx.profiler.get_counters().get("sparse_attn::masked_bwd", 0)
    text = jax.jit(grad).lower(
        av((N, 32, 128)), av((N, 4, 128)), av((N, 4, 128)),
        av((N, K), jnp.int32), av((N,), jnp.int32),
        av((N // 32, N), jnp.uint32)).compile().as_text()
    assert mx.profiler.get_counters()["sparse_attn::masked_bwd"] \
        == before + 1
    for name in ("dsa_masked_dq", "dsa_masked_dkv"):
        assert any(re.search(r'op_name="[^"]*/%s/pallas_call"' % name, ln)
                   for ln in text.splitlines()), name
    assert not re.search(r'op_name="[^"]*/scatter/', text)
