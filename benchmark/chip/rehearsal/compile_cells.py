#!/usr/bin/env python3
"""Compile, at the cells' real shapes and for a described ``v5e:2x2``
chip, what a cell's run puts on the chip and has not been there before:
the serving replica's decode and prefill programs (does the pool fit?)
and the float32 reference of the training cell with its fp8 control (do
256 rows of float32 activations fit?).  The TPU compiler refuses here
what it would refuse there, and ``memory_analysis()`` says what each
program holds.  Nothing runs; a compile that passes is not a chip run.
(The ResNet-50 step program itself ran on the chip in PR 22.)

    JAX_PLATFORMS=cpu python benchmark/chip/rehearsal/compile_cells.py \\
        [--config mistral7b_v03|resnet50_v1] [--pages N] [--slots N]
"""
import argparse
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(HERE))))

import common  # noqa: E402


def report(name, compiled, t0):
    ma = compiled.memory_analysis()
    print("%-14s compile %.0f s  arguments %.2f GB  temp %.2f GB  "
          "output %.2f GB  alias %.2f GB  kernels %d"
          % (name, time.time() - t0, ma.argument_size_in_bytes / 1e9,
             ma.temp_size_in_bytes / 1e9, ma.output_size_in_bytes / 1e9,
             ma.alias_size_in_bytes / 1e9,
             compiled.as_text().count("tpu_custom_call")), flush=True)


def serve_programs(model, chip, pages=None, slots=None):
    import jax
    import jax.numpy as jnp
    from mxnet_tpu import serve
    from mxnet_tpu.models import TransformerLM
    from mxnet_tpu.models.transformer import LlamaConfig
    from mxnet_tpu.ops import pallas_ops
    pallas_ops._pallas_available = lambda: True   # the chip's branch
    r = dict(model["replica"])
    r["pages"] = pages or r["pages"]
    r["slots"] = slots or r["slots"]
    cfg = LlamaConfig(
        vocab_size=model["vocab_size"], dim=model["hidden_size"],
        n_layers=model["num_hidden_layers"],
        n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"],
        hidden_dim=model["intermediate_size"],
        rope_theta=model["rope_theta"], norm_eps=model["rms_norm_eps"],
        max_seq_len=model["max_position_embeddings"],
        dtype=model["torch_dtype"])
    scfg = serve.ServeConfig(slots=r["slots"], page_size=r["page_size"],
                             ladder=tuple(r["ladder"]),
                             max_new=r["max_new"], pages=r["pages"],
                             int8=False, temperature=0.0)
    net = TransformerLM(cfg)
    ps = net.collect_params()
    spec = scfg.cache_spec(cfg)
    dt = jnp.dtype(cfg.dtype)

    def av(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    pool = av((spec.n_layers, spec.pages, spec.n_kv_heads, spec.page_size,
               spec.head_dim), dt)
    pav = {k: av(tuple(p.shape), dt) for k, p in ps.items()}
    S, MP = spec.slots, spec.max_pages_per_slot
    i32 = lambda *s: av(s, jnp.int32)      # noqa: E731
    f32 = lambda *s: av(s, jnp.float32)    # noqa: E731
    print("replica %s: pools 2 x %.2f GB, weights %.2f GB"
          % (r, 2 * pool.size / 1e9, 2 * sum(
              a.size for a in pav.values()) / 1e9), flush=True)
    t0 = time.time()
    decode = serve._build_decode_fn(net, ps, spec.page_size, {}, dt)
    report("decode", jax.jit(decode, donate_argnums=(1, 2)).lower(
        pav, pool, pool, i32(S, MP), i32(S), i32(S), av((S,), jnp.bool_),
        i32(S), i32(S), f32(S), i32(S), f32(S)).compile(), t0)
    prefill = serve._build_prefill_fn(net, ps, spec.page_size, {}, dt)
    for T in scfg.ladder:
        t0 = time.time()
        report("prefill%d" % T, jax.jit(
            prefill, donate_argnums=(1, 2)).lower(
            pav, pool, pool, i32(MP), i32(1, T), i32(), i32(), i32(),
            f32(), i32(), f32()).compile(), t0)


def train_reference(model, chip):
    import jax
    import jax.numpy as jnp
    ref = common.module("reference", model["family"])
    B, S = model["batch_size"], model["image_size"]
    specs = ref.leaf_specs(model)
    p = {k: jax.ShapeDtypeStruct(tuple(s["shape"]), jnp.dtype(s["dtype"]),
                                 sharding=chip) for k, s in specs.items()}
    m = {k: jax.ShapeDtypeStruct(a.shape, jnp.float32, sharding=chip)
         for k, a in p.items()}
    x = jax.ShapeDtypeStruct((B, 3, S, S), jnp.dtype(model["param_dtype"]),
                             sharding=chip)
    y = jax.ShapeDtypeStruct((B,), jnp.int32, sharding=chip)
    for prec in ("f32", "fp8"):
        t0 = time.time()
        report("reference." + prec, ref.make_step(
            model, precision=prec).lower(p, m, x, y).compile(), t0)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default=None)
    ap.add_argument("--pages", type=int, default=None)
    ap.add_argument("--slots", type=int, default=None)
    a = ap.parse_args()
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    for name in ([a.config] if a.config else ["resnet50_v1",
                                               "mistral7b_v03"]):
        model = common.load_json(common.HERE, "configs", name + ".json")
        print("== %s" % name, flush=True)
        if model["family"] == "decoder":
            serve_programs(model, chip, a.pages, a.slots)
        else:
            train_reference(model, chip)


if __name__ == "__main__":
    main()
