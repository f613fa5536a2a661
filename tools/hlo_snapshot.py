#!/usr/bin/env python3
"""hlo_snapshot — pinned programs for the chip-independent HLO perf
ratchet.

Lowers and compiles a fixed set of parallelism-seam programs (ring
attention fwd+grad, pipeline schedules, the ZeRO-1 train step) for BOTH
the CPU backend and — via a PJRT *topology description* (no chips
needed; ``jax.experimental.topologies`` + libtpu) — the real TPU
backend, writes each compiled module's text, and compares collective
counts + named ``mx.analysis.hlo`` check verdicts against the
checked-in ``tools/hlo_baseline.json`` through
``tools/mxlint.py --hlo ... --hlo-baseline``.  A collective-count
increase or a check flipping to FAIL fails CI on any box, chips or not;
an improvement fails too until the baseline is ratcheted down
(``--write-baseline``), so wins stay locked in.

The TPU artifacts are where the overlap evidence lives: the double-
buffered ring must carry its neighbor exchange ONLY in async
``collective-permute-start/done`` form with the flash kernel scheduled
inside the window, and the ZeRO-1 step's updated-param all-gathers must
ride ``async-collective-start`` wrappers (scheduled over the backward
tail).  The CPU artifacts pin the counts (and record that this
backend's collectives are synchronous — the pre-overlap state the TPU
schedule removes).

Usage:
  python tools/hlo_snapshot.py --check            # generate + ratchet (CI)
  python tools/hlo_snapshot.py --write-baseline   # regenerate baseline
  python tools/hlo_snapshot.py --out DIR          # artifacts only
"""
import argparse
import json
import os
import subprocess
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BASELINE = os.path.join(ROOT, "tools", "hlo_baseline.json")

# backend setup must precede any jax import
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_SKIP_MDS_QUERY", "true")
_prev = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in _prev:
    os.environ["XLA_FLAGS"] = \
        _prev + " --xla_force_host_platform_device_count=8"
sys.path.insert(0, ROOT)

TOPOLOGY = "v5e:2x4"  # 8 abstract TPU devices, matching the CPU mesh

#: per-program kwargs for the named checks (kinds/require_present/
#: allow_sync reach the collective checks) — recorded into the baseline
#: so ``mxlint --hlo-baseline`` re-runs each program's checks with the
#: SAME arguments.  Without these, ``collective_overlap`` would inspect
#: only its default kind (collective_permute) and the ZeRO-1 programs'
#: all-gather overlap verdicts would be vacuous.
CHECK_ARGS = {
    "ring_cpu": {"kinds": ["collective_permute"]},
    "ring_overlap_tpu": {"kinds": ["collective_permute"],
                         "require_present": True},
    "ring2_cpu": {"kinds": ["collective_permute"]},
    "ring2_tpu": {"kinds": ["collective_permute"],
                  "require_present": True},
    "pipeline_gpipe_cpu": {"kinds": ["collective_permute",
                                     "all_reduce"]},
    "pipeline_1f1b_vjp_cpu": {"kinds": ["collective_permute"]},
    "pipeline_1f1b_vjp_tpu": {"kinds": ["collective_permute"],
                              "require_present": True},
    "train_step_zero1_cpu": {"kinds": ["all_gather", "all_reduce"]},
    "train_step_zero1_tpu": {"kinds": ["all_gather"],
                             "require_present": True,
                             "allow_sync": True},
    # the mx.serve decode step is single-replica: NO collectives may
    # appear (kinds=[] keeps the overlap checks vacuous-ok) and — the
    # load-bearing verdict — no host transfers: a decode that bounces
    # through the host caps serving throughput at PCIe speeds.  The
    # collective_counts ratchet pins the all-zero counts.
    "serve_decode_cpu": {"kinds": []},
    "serve_decode_tpu": {"kinds": []},
    # the tensor-parallel decode replica: TP matmul collectives ARE
    # expected (the counts ratchet pins how many), the overlap checks
    # stay vacuous (kinds=[]), and no_host_transfers remains the
    # load-bearing verdict — sampling included, the sharded decode
    # must stay device-resident end to end.
    "serve_decode_tp_cpu": {"kinds": []},
    "serve_decode_tp_tpu": {"kinds": []},
}


def _tpu_devices():
    """Devices of the TPU topology description, or None with a warning
    when the AOT client is unavailable (no libtpu in the env).  Queried
    ONCE — all TPU meshes are built from the same device list."""
    try:
        from jax.experimental import topologies
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name=TOPOLOGY)
        return list(topo.devices)
    except Exception as e:  # env-skip, loudly
        print("hlo_snapshot: TPU AOT unavailable (%s) — skipping TPU "
              "artifacts" % str(e).splitlines()[0][:120], file=sys.stderr)
        return None


def _ring_text(mesh, axis="cp"):
    """Ring attention fwd+grad, striped causal layout on pre-striped
    (device-order) data — the production long-context path: the stripe
    permutation lives in the data loader (``parallel.seq_data``), so
    the pinned program must carry ring collectives ONLY, no layout
    gathers.  ``axis`` may be an (outer, inner) pair — the 2-level
    DCN×ICI ring."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from mxnet_tpu.parallel.ring import ring_attention_sharded

    B, H, T, D = 1, 2, 1024, 64
    q = jax.ShapeDtypeStruct(
        (B, H, T, D), jnp.bfloat16,
        sharding=NamedSharding(mesh, P(None, None, axis, None)))

    def loss(qq, kk, vv):
        o = ring_attention_sharded(qq, kk, vv, mesh, axis_name=axis,
                                   causal=True, layout="striped",
                                   permute_inputs=False)
        return o.astype(jnp.float32).sum()

    return jax.jit(jax.grad(loss, argnums=(0, 1, 2))) \
        .lower(q, q, q).compile().as_text()


def _pipeline_text(mesh, schedule, with_backward, axis="pp"):
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.parallel import pipeline as pl

    n = mesh.shape[axis]
    D, M, mbs = 32, 8, 2
    ws = jax.ShapeDtypeStruct((n, D, D), jnp.float32)
    x = jax.ShapeDtypeStruct((M * mbs, D), jnp.float32)

    def stage(w, a):
        return jax.nn.relu(a @ w)

    if with_backward:
        def f(w, xx, gg):
            return pl.pipeline_vjp(stage, w, xx, gg, mesh, M,
                                   axis_name=axis, schedule=schedule)
        return jax.jit(f).lower(ws, x, x).compile().as_text()

    def f(w, xx):
        return pl.pipeline_apply(stage, w, xx, mesh, M, axis_name=axis,
                                 schedule=schedule)
    return jax.jit(f).lower(ws, x).compile().as_text()


def _zero1_text(mesh):
    import mxnet_tpu as mx
    from mxnet_tpu import gluon, parallel
    from mxnet_tpu.gluon import nn

    mx.np.random.seed(0)
    net = nn.HybridSequential()
    net.add(nn.Dense(1024, in_units=512, activation="relu"),
            nn.Dense(1024, in_units=1024, activation="relu"),
            nn.Dense(512, in_units=1024))
    net.initialize()
    step = parallel.TrainStep(
        net, gluon.loss.L2Loss(),
        mx.optimizer.SGD(learning_rate=0.1, momentum=0.9),
        mesh=mesh, zero1=True, aot=True)
    x = mx.np.random.uniform(-1, 1, (64, 512))
    y = mx.np.random.uniform(-1, 1, (64, 512))
    return step.lower(x, y).compile().as_text()


def _serve_decode_text(mesh=None, force_pallas=False, kv_heads=1):
    """The mx.serve continuous-batching decode program (one token per
    batch slot over the paged KV cache), AOT-lowered with abstract
    params via ``serve.lower_decode_program`` — the serving analog of
    the ``TrainStep(aot=True)`` seam.  ``force_pallas`` compiles the
    Pallas page-table kernel into the TPU artifact (the topology
    client reports a cpu default backend, so the kernel gate is
    answered here as a chip would).  A mesh with a ``tp`` axis shards
    the weights by annotation and the pools over Hkv — pass
    ``kv_heads`` divisible by the axis size.  The pinned tp programs
    are the dense stand-in's; the kernel under a mesh is compiled by
    tests/test_chip_compile.py."""
    from mxnet_tpu import serve
    from mxnet_tpu.models import tiny_config
    from mxnet_tpu.ops import pallas_ops

    # kernel-shaped decode config: head_dim 128, page_size 128 (the
    # Mosaic tiling the paged-attention kernel wants)
    cfg = tiny_config(dim=256, n_heads=2, n_kv_heads=kv_heads,
                      dtype="bfloat16")
    scfg = serve.ServeConfig(slots=4, page_size=128, pages=16,
                             ladder=(128,), max_new=128,
                             cache_dir=None, int8=False)
    gate = pallas_ops._pallas_available
    if force_pallas:
        pallas_ops._pallas_available = lambda: True
    try:
        lowered, _ = serve.lower_decode_program(cfg=cfg, serve_cfg=scfg,
                                                mesh=mesh)
        return lowered.compile().as_text()
    finally:
        pallas_ops._pallas_available = gate


def build_artifacts(out_dir):
    """Generate every pinned program; returns {name: path}."""
    import jax
    import numpy as onp
    from jax.sharding import Mesh

    paths = {}

    def emit(name, text):
        p = os.path.join(out_dir, name + ".hlo.txt")
        # mxlint: disable=R2 -- ephemeral per-run artifact in a temp
        # dir, regenerated every invocation; the durable output
        # (hlo_baseline.json) does go through atomic_write
        with open(p, "w", encoding="utf-8") as f:
            f.write(text)
        paths[name] = p
        print("hlo_snapshot: %s (%d KB)" % (name, len(text) // 1024),
              file=sys.stderr)

    cpu = onp.array(jax.devices())
    emit("ring_cpu", _ring_text(Mesh(cpu, ("cp",))))
    emit("ring2_cpu", _ring_text(Mesh(cpu.reshape(2, 4), ("dcn", "cp")),
                                 axis=("dcn", "cp")))
    emit("pipeline_gpipe_cpu",
         _pipeline_text(Mesh(cpu, ("pp",)), "gpipe", False))
    emit("pipeline_1f1b_vjp_cpu",
         _pipeline_text(Mesh(cpu, ("pp",)), "1f1b", True))
    emit("train_step_zero1_cpu", _zero1_text(Mesh(cpu, ("dp",))))
    emit("serve_decode_cpu", _serve_decode_text())
    # the tensor-parallel serving replica (tp=2): weights sharded by
    # their .shard() annotations, paged KV pools split over Hkv
    emit("serve_decode_tp_cpu",
         _serve_decode_text(mesh=Mesh(cpu[:2], ("tp",)), kv_heads=2))

    tpu_devs = _tpu_devices()
    if tpu_devs is not None:
        tpu = onp.array(tpu_devs)
        emit("ring_overlap_tpu", _ring_text(Mesh(tpu, ("cp",))))
        # the 2-level DCN×ICI ring on the real TPU topology: the outer
        # (cross-slice) exchange must ride async start/done with the
        # whole inner sweep scheduled inside its window
        emit("ring2_tpu", _ring_text(Mesh(tpu.reshape(2, 4),
                                          ("dcn", "cp")),
                                     axis=("dcn", "cp")))
        emit("pipeline_1f1b_vjp_tpu",
             _pipeline_text(Mesh(tpu, ("pp",)), "1f1b", True))
        emit("train_step_zero1_tpu", _zero1_text(Mesh(tpu, ("dp",))))
        # serving decode is single-replica: a 1-device mesh of the
        # topology, with the Pallas page-table kernel forced in
        emit("serve_decode_tpu",
             _serve_decode_text(mesh=Mesh(tpu[:1], ("dp",)),
                                force_pallas=True))
        emit("serve_decode_tp_tpu",
             _serve_decode_text(mesh=Mesh(tpu[:2], ("tp",)),
                                kv_heads=2))
    return paths


def main(argv=None):
    ap = argparse.ArgumentParser(prog="hlo_snapshot",
                                 description=__doc__)
    ap.add_argument("--check", action="store_true",
                    help="generate artifacts and ratchet them against "
                    "tools/hlo_baseline.json (the CI mode)")
    ap.add_argument("--write-baseline", action="store_true",
                    help="regenerate tools/hlo_baseline.json from the "
                    "current toolchain's artifacts")
    ap.add_argument("--out", default=None,
                    help="directory for the artifact texts (default: a "
                    "temp dir)")
    args = ap.parse_args(argv)

    out_dir = args.out or tempfile.mkdtemp(prefix="hlo_snapshot_")
    os.makedirs(out_dir, exist_ok=True)
    paths = build_artifacts(out_dir)

    if args.write_baseline:
        from mxnet_tpu.analysis import hlo
        base = {}
        for name, p in sorted(paths.items()):
            with open(p, encoding="utf-8") as f:
                txt = f.read()
            check_args = CHECK_ARGS.get(name, {})
            base[name] = {
                "check_args": check_args,
                "collective_counts": hlo.collective_counts(txt),
                "checks": {r.name: r.ok
                           for r in hlo.run_text_checks(txt,
                                                        **check_args)},
            }
        from mxnet_tpu.utils import serialization
        with serialization.atomic_write(BASELINE, "w") as f:
            json.dump(base, f, indent=1, sort_keys=True)
            f.write("\n")
        print("hlo_snapshot: wrote %s (%d programs)"
              % (BASELINE, len(base)))
        return 0

    if args.check:
        # completeness first: every baselined program must have been
        # generated — a silently-skipped TPU artifact would un-gate
        # exactly the async-overlap evidence this ratchet exists for
        with open(BASELINE, encoding="utf-8") as f:
            expected = set(json.load(f))
        missing = expected - set(paths)
        if missing:
            print("hlo_snapshot: FAILED — baselined program(s) %s were "
                  "not generated in this environment; the overlap "
                  "ratchet cannot run blind (restore the TPU AOT "
                  "client, or deliberately shrink the baseline with "
                  "--write-baseline)" % ", ".join(sorted(missing)),
                  file=sys.stderr)
            return 1
        cmd = [sys.executable, os.path.join(ROOT, "tools", "mxlint.py"),
               "--hlo-baseline", BASELINE]
        for p in sorted(paths.values()):
            cmd += ["--hlo", p]
        rc = subprocess.call(cmd)
        if rc:
            print("hlo_snapshot: RATCHET FAILED — a pinned program's "
                  "collectives or check verdicts moved; see above "
                  "(regenerate deliberately with --write-baseline)",
                  file=sys.stderr)
        return rc
    return 0


if __name__ == "__main__":
    sys.exit(main())
