"""Plain reference of the training step of Laguna-S-2.1's language model
(``config.json`` of ``poolside/Laguna-S-2.1``): forward, loss, gradients
and AdamW in float32 ``jax.numpy`` at ``highest`` matmul precision, no
kernel, nothing of the program.

    block l: u = N1(x)
             q = R_l(u Wq)_h, k = R_l(u Wk)_g, v = (u Wv)_g; H_l heads h
                 (48 on a full layer, 72 on a sliding one), 8 groups,
                 g(h) = h // (H_l / 8)
             R_l: full layers YaRN rotary over each head's first 64 dims
                 (cos, sin times attention_factor), sliding layers
                 rotate-half rotary at theta 1e4 over all 128
             o_h = softmax over visible s of q_h . k_g(h) / sqrt(128),
                 times v; visible: s <= t (full), t - 512 < s <= t
                 (sliding)
             o_h <- sigmoid(u Wgate)_h o_h
             x <- x + o Wo
             u' = N2(x)
             layer 0:  x <- x + W2(silu(W1 u') * W3 u')       (12,288)
             others:   r = sigmoid(u' Wr) over all 256 experts
                       E_t = top 10 of r_t, g = 2.5 r / sum_{E_t} r
                       x <- x + sum_{e in E_t, e held} g_e SwiGLU_e(u')
                                + SwiGLU_shared(u')             (1,024)
                       router term 0.001 * 256 * sum_e f_e P_e,
                       P from r normalised over all 256
    head:    loss = mean_t CE(Nf(x) Whead, label_t)
             + mean over the MoE layers of the router term

The attention is one dense softmax a block of queries over the keys it
may see (a sliding layer's block over its window's keys alone); the
experts are computed dense, every token through every held expert, its
gate 0 where the token does not route there.  Departures from the
published description are those of the configuration's ``assumed``
(where the description is silent) and its ``deployment`` (8 of 256
experts held: what the absent experts add is left out, as in the
program; a vocabulary slice).

As the sparse cell's reference, the step is a chain of jitted pieces (a
block of each kind, the head with the loss), each run forward with its
input kept and then, last to first, again with ``jax.vjp``; inside a
block the queries go a block at a time and the experts one at a time,
each made again in the backward (``jax.checkpoint``).
"""
import math

import jax
import jax.numpy as jnp
import numpy as onp

from .looped_decoder import _f32
from .precision import contraction

ATTENTION = ("attention_norm", "wq", "wk", "wv", "wo", "head_gate",
             "ffn_norm")
DENSE = ("mlp_gate", "mlp_up", "mlp_down")
EXPERTS = ("router", "w_gate", "w_up", "w_down", "shared_gate", "shared_up",
           "shared_down")
#: queries a block of the attention
QUERY_BLOCK = 256
#: tokens a block of the head's logits
HEAD_BLOCK = 4096


def layers(model):
    """``[(sliding, heads, dense)]`` of the layers held here, from the
    configuration's per-layer lists."""
    n = model["num_hidden_layers"]
    return [(kind == "sliding_attention", heads, mlp == "dense")
            for kind, heads, mlp in zip(
                model["layer_types"][:n],
                model["num_attention_heads_per_layer"][:n],
                model["mlp_layer_types"][:n])]


def leaf_names(dense):
    return ATTENTION + (DENSE if dense else EXPERTS)


def leaf_specs(model):
    """``{name: {"kind", "scale", "shape", "dtype"}}`` of every leaf in
    forward order: matrices normal(0, ``init_std``) as (out, in), the
    held experts' (held, in, out), norm gains 1."""
    dt, std = model["param_dtype"], model["init_std"]
    d, v = model["hidden_size"], model["vocab_size"]
    G, D = model["num_key_value_heads"], model["head_dim"]
    n, Fe = model["num_experts"], model["moe_intermediate_size"]
    Fs, F = model["shared_expert_intermediate_size"], \
        model["intermediate_size"]

    def normal(*shape):
        return {"kind": "normal", "scale": std, "shape": list(shape),
                "dtype": dt}

    def const(value, width):
        return {"kind": "const", "scale": value, "shape": [width],
                "dtype": dt}

    specs = {"embed": normal(v, d)}
    for i, (_, H, dense) in enumerate(layers(model)):
        shapes = {"wq": (H * D, d), "wk": (G * D, d), "wv": (G * D, d),
                  "wo": (d, H * D), "head_gate": (H, d),
                  "mlp_gate": (F, d), "mlp_up": (F, d), "mlp_down": (d, F),
                  "router": (model["router_width"], d),
                  "w_gate": (n, d, Fe), "w_up": (n, d, Fe),
                  "w_down": (n, Fe, d), "shared_gate": (Fs, d),
                  "shared_up": (Fs, d), "shared_down": (d, Fs)}
        for name in leaf_names(dense):
            key = "layer%d.%s" % (i, name)
            specs[key] = const(1.0, d) if name.endswith("_norm") \
                else normal(*shapes[name])
    specs["final_norm"] = const(1.0, d)
    specs["lm_head"] = normal(v, d)
    return specs


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _yarn(rot, theta, r):
    """YaRN's inverse frequencies over ``rot`` dims and its scale, as
    ``transformers``' ``_compute_yarn_parameters`` (truncate true)."""
    def dim_of(turns):
        return rot * math.log(r["original_max_position_embeddings"]
                              / (turns * 2 * math.pi)) / (2 * math.log(theta))

    base = theta ** (onp.arange(0, rot, 2, dtype=onp.float64) / rot)
    low = max(math.floor(dim_of(r["beta_fast"])), 0)
    high = min(math.ceil(dim_of(r["beta_slow"])), rot - 1)
    if high == low:
        high += 0.001
    ramp = onp.clip((onp.arange(rot // 2) - low) / (high - low), 0, 1)
    extrapolate = 1.0 - ramp
    inv = (1.0 / (r["factor"] * base)) * (1 - extrapolate) \
        + (1.0 / base) * extrapolate
    return inv, r["attention_factor"]


def _rotary(x, r, D):
    """x: (T, H, D) at positions 0..T-1, the layer type's rotary ``r``
    (``rope_parameters`` of that type)."""
    rot = int(D * r["partial_rotary_factor"])
    if r["rope_type"] == "yarn":
        inv, scale = _yarn(rot, r["rope_theta"], r)
    else:
        inv = 1.0 / r["rope_theta"] ** (onp.arange(0, rot, 2) / rot)
        scale = 1.0
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] \
        * jnp.asarray(inv, jnp.float32)[None, :]
    cos = (jnp.cos(ang) * scale)[:, None, :]
    sin = (jnp.sin(ang) * scale)[:, None, :]
    a, b, rest = x[..., :rot // 2], x[..., rot // 2:rot], x[..., rot:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos, rest], -1)


def _pieces(model, precision, how):
    """The pure functions of one sequence: ``block(x (T, d), w, kind,
    sel) -> (x, router term)`` for ``kind = (sliding, heads, dense)``,
    its experts those of ``sel`` (T, top) where it is given, else the
    router's top k; ``route(x, w, kind) -> (T, top)`` the router's top k
    of a MoE block; ``exits(x, gain, head, labels (T,), scale) ->
    (scale * loss, ce)``.
    ``how``: ``attention`` (``full``: a sliding layer sees every earlier
    key), ``gate`` (False: no head gate), ``shared_expert`` (False: none)."""
    G, D = model["num_key_value_heads"], model["head_dim"]
    eps, W = model["rms_norm_eps"], model["sliding_window"]
    E, top, first = (model["router_width"], model["num_experts_per_tok"],
                     model["first_expert_held"])
    n_held, scaling = model["num_experts"], model["moe_routed_scaling_factor"]
    coef = model["router_aux_loss_coef"]
    rope = model["rope_parameters"]
    mm = contraction(precision, lambda x, w: jnp.matmul(x, w.T))
    mmx = contraction(precision, jnp.matmul)
    qk = contraction(precision, lambda a, b: jnp.einsum("qhd,shd->hqs",
                                                        a, b))
    pv = contraction(precision, lambda p, b: jnp.einsum("hqs,shd->qhd",
                                                        p, b))
    full_keys = how["attention"] == "full"

    def attend(q, k, v, window):
        """(T, H, D) queries against (T, G, D) keys, a block of queries
        at a time; ``window`` 0: every earlier key."""
        T, H, _ = q.shape
        qb = min(QUERY_BLOCK, T)
        # a sliding layer's block sees [t0 - W + 1, t0 + qb): the keys
        # padded in front by W so that the span is a fixed slice
        span = qb + W if window else T
        kk, vv = jnp.repeat(k, H // G, 1), jnp.repeat(v, H // G, 1)
        if window:
            pad = jnp.zeros((W,) + kk.shape[1:], kk.dtype)
            kk, vv = jnp.concatenate([pad, kk]), jnp.concatenate([pad, vv])

        def one(args):
            qblk, t0 = args
            if window:
                ks = jax.lax.dynamic_slice_in_dim(kk, t0, span)
                vs = jax.lax.dynamic_slice_in_dim(vv, t0, span)
                kpos = t0 - W + jnp.arange(span)
            else:
                ks, vs, kpos = kk, vv, jnp.arange(T)
            qpos = t0 + jnp.arange(qb)
            seen = kpos[None, :] <= qpos[:, None]
            if window:
                seen = seen & (kpos[None, :] > qpos[:, None] - window) \
                    & (kpos[None, :] >= 0)
            a = qk(qblk, ks) / math.sqrt(D)
            a = jax.nn.softmax(jnp.where(seen[None], a, -jnp.inf), -1)
            return pv(a, vs)

        o = jax.lax.map(jax.checkpoint(one),
                        (q.reshape(T // qb, qb, H, D),
                         jnp.arange(T // qb) * qb))
        return o.reshape(T, H, D)

    def swiglu(u, wg, wu, wd):
        return mm(jax.nn.silu(mm(u, wg)) * mm(u, wu), wd)

    def experts(u, w, sel):
        T = u.shape[0]
        r = jax.nn.sigmoid(mm(u, w["router"]))                  # (T, E)
        if sel is None:
            top_r, top_e = jax.lax.top_k(r, top)
        else:
            top_e, top_r = sel, jnp.take_along_axis(r, sel, -1)
        gates = scaling * top_r / jnp.sum(top_r, -1, keepdims=True)
        slot = top_e - first
        here = (slot >= 0) & (slot < n_held)
        gate = jnp.einsum("tk,tke->te", jnp.where(here, gates, 0.0),
                          jax.nn.one_hot(slot, n_held))         # (T, held)
        f = jnp.zeros((E,), jnp.float32).at[top_e.reshape(-1)].add(1.0) / T
        p = r / jnp.sum(r, -1, keepdims=True)
        aux = coef * E * jnp.sum(f * jnp.mean(p, 0))

        def expert(y, args):
            w1, w3, w2, g = args
            h = jax.nn.silu(mmx(u, w1)) * mmx(u, w3)
            return y + g[:, None] * mmx(h, w2), None

        y, _ = jax.lax.scan(jax.checkpoint(expert), jnp.zeros_like(u),
                            (w["w_gate"], w["w_up"], w["w_down"], gate.T))
        if how["shared_expert"]:
            y = y + swiglu(u, w["shared_gate"], w["shared_up"],
                           w["shared_down"])
        return y, aux

    def mix(x, w, kind):
        """The attention half of a block, its residual added."""
        sliding, H, _ = kind
        T = x.shape[0]
        r = rope["sliding_attention" if sliding else "full_attention"]
        u = _rms(x, w["attention_norm"], eps)
        q = _rotary(mm(u, w["wq"]).reshape(T, H, D), r, D)
        k = _rotary(mm(u, w["wk"]).reshape(T, G, D), r, D)
        v = mm(u, w["wv"]).reshape(T, G, D)
        o = attend(q, k, v, W if sliding and not full_keys else 0)
        if how["gate"]:
            o = o * jax.nn.sigmoid(mm(u, w["head_gate"]))[..., None]
        return x + mm(o.reshape(T, H * D), w["wo"])

    def block(x, w, kind, sel=None):
        x = mix(x, w, kind)
        u = _rms(x, w["ffn_norm"], eps)
        if kind[2]:
            return x + swiglu(u, w["mlp_gate"], w["mlp_up"],
                              w["mlp_down"]), jnp.float32(0.0)
        y, aux = experts(u, w, sel)
        return x + y, aux

    def route(x, w, kind):
        u = _rms(mix(x, w, kind), w["ffn_norm"], eps)
        return jax.lax.top_k(jax.nn.sigmoid(mm(u, w["router"])), top)[1]

    def exits(x, gain, head_w, labels, scale):
        z = _rms(x, gain, eps)
        T = z.shape[0]
        hb = min(HEAD_BLOCK, T)

        def one(args):
            zb, yb = args
            logp = jax.nn.log_softmax(mm(zb, head_w), -1)
            return -jnp.take_along_axis(logp, yb[:, None], -1)[:, 0]

        ce = jax.lax.map(jax.checkpoint(one),
                         (z.reshape(T // hb, hb, -1), labels.reshape(-1, hb)))
        ce = jnp.mean(ce)
        return scale * ce, ce

    return block, exits, route


_PROGRAMS = {}


def _programs(model, precision, how):
    """The pieces as jitted programs, forward and backward, at
    ``highest`` matmul precision; weights arrive in ``param_dtype`` and
    are widened inside, their gradients leave in float32.  One set a
    precision, control and configuration, shared by the steps made of
    them; a block's kind is static."""
    key = (precision, tuple(sorted(how.items())), repr(sorted(model.items())))
    if key not in _PROGRAMS:
        _PROGRAMS[key] = _jitted(model, precision, how)
    return _PROGRAMS[key]


def _jitted(model, precision, how):
    block, exits, route = _pieces(model, precision, how)

    def highest(fn, static=()):
        def run(*args):
            with jax.default_matmul_precision("highest"):
                return fn(*args)
        return jax.jit(run, static_argnums=static)

    def exits_backward(x, gain, head_w, labels, scale):
        return jax.value_and_grad(exits, argnums=(0, 1, 2), has_aux=True)(
            x, *_f32((gain, head_w)), labels, scale)

    def block_backward(x, w, kind, sel, dy, d_router):
        return jax.vjp(lambda x, w: block(x, w, kind, sel), x, _f32(w))[1](
            (dy, d_router))

    return {
        "embed": jax.jit(lambda e, tokens: jnp.take(
            e.astype(jnp.float32), tokens, axis=0)),
        "embed_bwd": jax.jit(lambda e, tokens, dh: jnp.zeros(
            e.shape, jnp.float32).at[tokens].add(dh)),
        "block": highest(lambda x, w, kind, sel: block(x, _f32(w), kind,
                                                       sel), (2,)),
        "block_bwd": highest(block_backward, (2,)),
        "route": highest(lambda x, w, kind: route(x, _f32(w), kind), (2,)),
        "exits": highest(lambda x, g, hw, labels, scale: exits(
            x, *_f32((g, hw)), labels, scale)),
        "exits_bwd": highest(exits_backward)}


def _layer(params, i, dense):
    return {n: params["layer%d.%s" % (i, n)] for n in leaf_names(dense)}


def make_step(model, precision="f32", attention="window", gate=True,
              shared_expert=True, optimizer=None, drop_state_at=None,
              selection=None):
    """``step(params, state, tokens, labels) -> (loss, parts, grads,
    params', state')`` of one AdamW step on (B, T) tokens and labels;
    ``params`` in ``param_dtype``, ``state`` None before the first step,
    then ``(t, m, v)`` with float32 moments.  ``parts``: ``{"ce",
    "router_loss"}``, each (1,).  ``step.loss(params, tokens, labels) ->
    (loss, parts)`` is the forward alone.  ``drop_state_at``: the step
    (from 1) whose moments the caller will not read.

    The controls: ``precision`` (``fp8``: every product's operands e4m3,
    its backward cotangent e5m2), ``attention`` (``full``: the sliding
    layers see every earlier key), ``gate`` (False: no head gate),
    ``shared_expert`` (False: the shared expert left out), ``optimizer``
    (settings in the configuration's place: a learning rate of 0 is a
    state left unchanged).  ``selection``: ``[{layer: (T, top) ids}]`` a
    sequence, the experts each MoE layer takes in place of its router's
    top k (``selections`` gives the reference's own)."""
    opt = dict(model["optimizer"], **(optimizer or {}))
    lr, b1, b2, eps, wd = (opt["learning_rate"], opt["beta1"], opt["beta2"],
                           opt["epsilon"], opt["wd"])
    kinds = layers(model)
    n_moe = sum(not dense for _, _, dense in kinds)
    run = _programs(model, precision, {"attention": attention, "gate": gate,
                                       "shared_expert": shared_expert})

    def exits_of(params, labels, scale):
        return (params["final_norm"], params["lm_head"], labels,
                jnp.float32(scale))

    def pinned(r, i):
        return selection[r].get(i) if selection else None

    def forward(params, tokens, labels):
        n = tokens.shape[0]
        loss, ce, rl = 0.0, 0.0, 0.0
        for r, (row, lab) in enumerate(zip(tokens, labels)):
            h = run["embed"](params["embed"], row)
            for i, kind in enumerate(kinds):
                h, b = run["block"](h, _layer(params, i, kind[2]), kind,
                                    pinned(r, i))
                rl = rl + b / (n * n_moe)
            part, c = run["exits"](h, *exits_of(params, lab, 1.0 / n))
            loss, ce = loss + part, ce + c / n
        return loss + rl, _parts(ce, rl)

    def gradient(params, tokens, labels):
        n = tokens.shape[0]
        grads = {}

        def add(name, g):
            grads[name] = grads[name] + g if name in grads else g

        loss, ce, rl = 0.0, 0.0, 0.0
        for r, (row, lab) in enumerate(zip(tokens, labels)):
            kept = []
            h = run["embed"](params["embed"], row)
            for i, kind in enumerate(kinds):
                kept.append(h)
                h, b = run["block"](h, _layer(params, i, kind[2]), kind,
                                    pinned(r, i))
                rl = rl + b / (n * n_moe)
            (part, c), (dh, d_gain, d_head) = run["exits_bwd"](
                h, *exits_of(params, lab, 1.0 / n))
            loss, ce = loss + part, ce + c / n
            del h
            add("final_norm", d_gain)
            add("lm_head", d_head)
            for i in reversed(range(len(kinds))):
                kind = kinds[i]
                dh, g = run["block_bwd"](kept.pop(),
                                         _layer(params, i, kind[2]), kind,
                                         pinned(r, i), dh,
                                         jnp.float32(1.0 / (n * n_moe)))
                for name, leaf in g.items():
                    add("layer%d.%s" % (i, name), leaf)
            add("embed", run["embed_bwd"](params["embed"], row, dh))
        return loss + rl, _parts(ce, rl), grads

    @jax.jit
    def update(w, g, m, v, t):
        wf = w.astype(jnp.float32)
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        mhat = m / (1 - b1 ** t)
        vhat = v / (1 - b2 ** t)
        new = wf - lr * (mhat / (jnp.sqrt(vhat) + eps) + wd * wf)
        return new.astype(w.dtype), m, v

    def step(params, state, tokens, labels):
        loss, parts, grads = gradient(params, tokens, labels)
        t, m, v = state or (0, {}, {})
        t += 1
        new_p, new_m, new_v = {}, {}, {}
        for k, w in params.items():
            zero = jnp.zeros(w.shape, jnp.float32)
            new_p[k], mk, vk = update(
                w, grads[k], m.get(k, zero), v.get(k, zero), jnp.float32(t))
            if t != drop_state_at:
                new_m[k], new_v[k] = mk, vk
        state = None if t == drop_state_at else (t, new_m, new_v)
        return loss, parts, grads, new_p, state

    step.loss, step.gradient = forward, gradient
    return step


def selections(model, params, tokens):
    """``[{layer: (T, top) ids}]`` a sequence of (B, T) ``tokens``: the
    experts the float32 reference's router picks in each MoE layer."""
    run = _programs(model, "f32", {"attention": "window", "gate": True,
                                   "shared_expert": True})
    out = []
    for row in tokens:
        h, picked = run["embed"](params["embed"], row), {}
        for i, kind in enumerate(layers(model)):
            w = _layer(params, i, kind[2])
            if not kind[2]:
                picked[i] = run["route"](h, w, kind)
            h, _ = run["block"](h, w, kind, None)
        out.append(picked)
    return out


def _parts(ce, rl):
    return {"ce": jnp.reshape(ce, (1,)),
            "router_loss": jnp.reshape(rl, (1,))}
