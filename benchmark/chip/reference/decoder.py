"""Plain reference of a pre-norm RoPE / grouped-query / SwiGLU decoder
(Mistral-7B's equations, Jiang et al. 2023, arXiv:2310.06825, as its
public ``modeling_mistral.py`` states them): the full forward over a
whole sequence in float32 ``jax.numpy`` at ``highest`` matmul precision.
No kernel, no cache, no batching of requests, nothing of the program.

Weights arrive a layer at a time from ``weights(names)`` so that a model
whose float32 copy would not fit beside anything else still runs.
Departure from the publication: none (no sliding window in v0.3; rotary
halves are ``x[..., :d/2]`` and ``x[..., d/2:]`` as in the public code).
"""
import math

import jax
import jax.numpy as jnp

from .precision import rounder


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, theta):
    """x: (T, H, D) at positions 0..T-1."""
    T, _, D = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


def _heads(q, k, v, n_heads, n_kv):
    """q (T, H*D), k and v (T, Hkv*D) split into heads."""
    T = q.shape[0]
    D = q.shape[1] // n_heads
    return q.reshape(T, n_heads, D), k.reshape(T, n_kv, D), \
        v.reshape(T, n_kv, D)


def leaf_specs(model):
    """``{name: {"kind", "scale", "shape", "dtype"}}`` of every leaf:
    matrices normal with the published ``initializer_range``, norms 1."""
    dt, std = model["torch_dtype"], model["initializer_range"]
    d, f, v = (model["hidden_size"], model["intermediate_size"],
               model["vocab_size"])
    hd = d // model["num_attention_heads"]
    kv = model["num_key_value_heads"] * hd

    def mat(rows, cols):
        return {"kind": "normal", "scale": std, "shape": [rows, cols],
                "dtype": dt}

    one = {"kind": "const", "scale": 1.0, "shape": [d], "dtype": dt}
    specs = {"embed": mat(v, d)}
    for i in range(model["num_hidden_layers"]):
        n = layer_names(i)
        specs.update({n["attn_norm"]: one, n["wq"]: mat(d, d),
                      n["wk"]: mat(kv, d), n["wv"]: mat(kv, d),
                      n["wo"]: mat(d, d), n["ffn_norm"]: one,
                      n["w_gate"]: mat(f, d), n["w_up"]: mat(f, d),
                      n["w_down"]: mat(d, f)})
    specs.update({"final_norm": one, "lm_head": mat(v, d)})
    return specs


def layer_names(i):
    p = "layer%d." % i
    return {"attn_norm": p + "attention_norm", "wq": p + "wq",
            "wk": p + "wk", "wv": p + "wv", "wo": p + "wo",
            "ffn_norm": p + "ffn_norm", "w_gate": p + "w_gate",
            "w_up": p + "w_up", "w_down": p + "w_down"}


def _make_layer(model, precision):
    rnd = rounder(precision)
    H, Hkv = model["num_attention_heads"], model["num_key_value_heads"]
    eps, theta = model["rms_norm_eps"], model["rope_theta"]

    def mm(x, w):
        # y = x @ w.T, both operands as the precision holds them
        return jnp.matmul(rnd(x, -1), rnd(w, -1).T)

    def one(x, w):
        T = x.shape[0]
        h = _rms(x, w["attn_norm"], eps)
        q, k, v = _heads(mm(h, w["wq"]), mm(h, w["wk"]),
                             mm(h, w["wv"]), H, Hkv)
        D = q.shape[-1]
        q, k = _rope(q, theta), _rope(k, theta)
        k = jnp.repeat(k, H // Hkv, axis=1)
        v = jnp.repeat(v, H // Hkv, axis=1)
        s = jnp.einsum("thd,shd->hts", rnd(q, -1), rnd(k, -1)) / math.sqrt(D)
        mask = jnp.tril(jnp.ones((T, T), bool))
        p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
        o = jnp.einsum("hts,shd->thd", rnd(p, -1), rnd(v, 0))
        x = x + mm(o.reshape(T, H * D), w["wo"])
        h = _rms(x, w["ffn_norm"], eps)
        return x + mm(jax.nn.silu(mm(h, w["w_gate"])) * mm(h, w["w_up"]),
                      w["w_down"])

    @jax.jit
    def layer(xs, w):
        w = {k: a.astype(jnp.float32) for k, a in w.items()}
        with jax.default_matmul_precision("highest"):
            return jax.lax.map(lambda x: one(x, w), xs)

    return layer


def logits(model, weights, tokens, precision="f32"):
    """float32 logits ``(R, T, vocab)`` of the token rows ``tokens``
    ``(R, T)`` (pad behind a sequence: causal, so padding cannot reach
    what comes before it).  ``weights(names) -> {name: array}`` gives the
    named leaves in the type they are served in."""
    rnd = rounder(precision)
    layer = _make_layer(model, precision)
    emb = weights(["embed"])["embed"]
    xs = jnp.take(emb, jnp.asarray(tokens), axis=0).astype(jnp.float32)
    del emb
    for i in range(model["num_hidden_layers"]):
        names = layer_names(i)
        got = weights(list(names.values()))
        xs = layer(xs, {k: got[n] for k, n in names.items()})
        del got

    @jax.jit
    def head(xs, g, w):
        with jax.default_matmul_precision("highest"):
            h = _rms(xs, g.astype(jnp.float32), model["rms_norm_eps"])
            return jnp.matmul(rnd(h, -1), rnd(w.astype(jnp.float32), -1).T)

    got = weights(["final_norm", "lm_head"])
    return head(xs, got["final_norm"], got["lm_head"])
