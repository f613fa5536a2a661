"""Plain reference of a looped decoder's training step (Ouro, "Scaling
Latent Reasoning via Looped Language Models", arXiv:2510.25741, as the
public ``config.json`` and modelling code of ``ByteDance/Ouro-2.6B``
state it): forward, the expected-exit loss, gradients and AdamW in
float32 ``jax.numpy`` at ``highest`` matmul precision.  Dense attention,
whole-vocabulary cross-entropy, the loops over passes and layers Python
``for``s.  No kernel, no chunked head, nothing of the program.

    block l:  h <- h + N2(Attn(N1(h)));  h <- h + N4(SwiGLU(N3(h)))
    loop:     h0 = E[tokens];  ht = Nf(Stack(h(t-1))),  t = 1..P, the
              same weights at every t;  logits_t = ht W_head
    gate:     lambda_t = sigmoid(ht w_g + b_g);
              p_t = lambda_t prod_{j<t}(1 - lambda_j),  p_P = prod_{j<P}(1 - lambda_j)
    loss:     mean over tokens of  sum_t p_t CE_t - beta H(p)

Departures from the publication, each as the configuration's file states
it under ``assumed``: ``beta``, AdamW's settings and the sequence length
are not in ``config.json``; weight decay falls on every leaf; parameters
are held in ``param_dtype`` between steps (the update is computed in
float32 and rounded once when stored), the moments in float32; no
gradient clipping; one document a sequence.

So that float32 at 8,192 tokens fits one chip once the program's state
is freed, and compiles in seconds to programs small enough for the
compile cache, the step is computed in blocks: one sequence at a time,
and within it one jitted program a piece (a block, the final norm, the
four exits with the loss), each run forward with its input kept and
then, last to first, run again with ``jax.vjp`` for its input's and its
weights' gradient.  A shared weight's gradient is the sum of those of
its uses, added up as they come.  Attention runs a head at a time, a
head's scores and an exit's logits are made again in the backward
(``jax.checkpoint``), and the update is applied a leaf at a time.
"""
import math

import jax
import jax.numpy as jnp

from .precision import contraction

NORMS = ("attention_norm", "attention_post_norm", "ffn_norm",
         "ffn_post_norm")
MATS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


def leaf_specs(model):
    """``{name: {"kind", "scale", "shape", "dtype"}}`` of every leaf in
    forward order: matrices normal(0, ``init_std``), gains 1, the gate's
    bias 0."""
    dt, std = model["param_dtype"], model["init_std"]
    d, f, v = (model["hidden_size"], model["intermediate_size"],
               model["vocab_size"])
    hd = model["head_dim"]
    q, kv = model["num_attention_heads"] * hd, \
        model["num_key_value_heads"] * hd

    def mat(rows, cols):
        return {"kind": "normal", "scale": std, "shape": [rows, cols],
                "dtype": dt}

    one = {"kind": "const", "scale": 1.0, "shape": [d], "dtype": dt}
    shapes = {"wq": (q, d), "wk": (kv, d), "wv": (kv, d), "wo": (d, q),
              "w_gate": (f, d), "w_up": (f, d), "w_down": (d, f)}
    specs = {"embed": mat(v, d)}
    for i in range(model["num_hidden_layers"]):
        for n in NORMS:
            specs["layer%d.%s" % (i, n)] = one
        for n in MATS:
            specs["layer%d.%s" % (i, n)] = mat(*shapes[n])
    specs["final_norm"] = one
    specs["lm_head"] = mat(v, d)
    specs["gate.w"] = mat(1, d)
    specs["gate.b"] = {"kind": "const", "scale": 0.0, "shape": [1],
                       "dtype": dt}
    return specs


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, theta):
    """x: (T, H, D) at positions 0..T-1, rotate-half."""
    T, _, D = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


def _pieces(model, precision):
    """The pure functions of one sequence: ``block(x (T, d), w) -> x``,
    ``final(x, gain) -> x`` and ``exits(hs [P x (T, d)], head, gate_w,
    gate_b, labels (T,), scale) -> (scale * mean loss, (ce (P,), p
    (P,)))``, the exits' mean cross-entropy and mean probability."""
    H, Hkv, D = (model["num_attention_heads"],
                 model["num_key_value_heads"], model["head_dim"])
    eps, theta, beta = (model["rms_norm_eps"], model["rope_theta"],
                        model["beta"])
    mm = contraction(precision, lambda x, w: jnp.matmul(x, w.T))
    scores = contraction(precision, lambda q, k: jnp.matmul(q, k.T))
    mix = contraction(precision, jnp.matmul)

    def head(qkv):
        """One head's causal ``softmax(q k^T / sqrt(D)) v``, (T, D)."""
        q, k, v = qkv
        T = q.shape[0]
        s = scores(q, k) / math.sqrt(D)
        mask = jnp.tril(jnp.ones((T, T), bool))
        return mix(jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1), v)

    def block(x, w):
        T = x.shape[0]
        h = _rms(x, w["attention_norm"], eps)
        q = _rope(mm(h, w["wq"]).reshape(T, H, D), theta)
        k = _rope(mm(h, w["wk"]).reshape(T, Hkv, D), theta)
        v = mm(h, w["wv"]).reshape(T, Hkv, D)
        k = jnp.repeat(k, H // Hkv, axis=1)
        v = jnp.repeat(v, H // Hkv, axis=1)
        # a head at a time, its (T, T) scores made again in the backward
        o = jax.lax.map(jax.checkpoint(head),
                        tuple(jnp.swapaxes(a, 0, 1) for a in (q, k, v)))
        o = mm(jnp.swapaxes(o, 0, 1).reshape(T, H * D), w["wo"])
        x = x + _rms(o, w["attention_post_norm"], eps)
        h = _rms(x, w["ffn_norm"], eps)
        f = mm(jax.nn.silu(mm(h, w["w_gate"])) * mm(h, w["w_up"]),
               w["w_down"])
        return x + _rms(f, w["ffn_post_norm"], eps)

    def final(x, gain):
        return _rms(x, gain, eps)

    def exit_ce(h, head_w, labels):
        logp = jax.nn.log_softmax(mm(h, head_w), axis=-1)
        return -jnp.take_along_axis(logp, labels[:, None], 1)[:, 0]

    def exits(hs, head_w, gate_w, gate_b, labels, scale):
        ces = [jax.checkpoint(exit_ce)(h, head_w, labels) for h in hs]
        lams = [jax.nn.sigmoid(jnp.matmul(h, gate_w.T)[:, 0] + gate_b[0])
                for h in hs]
        ps, left = [], jnp.ones_like(lams[0])
        for lam in lams[:-1]:
            ps.append(lam * left)
            left = left * (1.0 - lam)
        ps.append(left)
        ce, p = jnp.stack(ces), jnp.stack(ps)              # (P, T) each
        entropy = -jnp.sum(p * jnp.log(p), axis=0)
        loss = jnp.mean(jnp.sum(p * ce, axis=0) - beta * entropy)
        return scale * loss, (jnp.mean(ce, axis=1), jnp.mean(p, axis=1))

    return block, final, exits


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def _programs(model, precision):
    """The pieces as jitted programs, forward and backward, at
    ``highest`` matmul precision; weights arrive in ``param_dtype`` and
    are widened inside, their gradients leave in float32."""
    block, final, exits = _pieces(model, precision)

    def highest(fn):
        def run(*args):
            with jax.default_matmul_precision("highest"):
                return fn(*args)
        return jax.jit(run)

    def backward(fn):
        """``(x, w, dy) -> (dx, dw)`` of ``y = fn(x, w)``, the forward
        made again."""
        return highest(lambda x, w, dy: jax.vjp(fn, x, _f32(w))[1](dy))

    def exits_backward(hs, head_w, gate_w, gate_b, labels, scale):
        return jax.value_and_grad(
            exits, argnums=(0, 1, 2, 3), has_aux=True)(
                hs, *_f32((head_w, gate_w, gate_b)), labels, scale)

    return {
        "embed": jax.jit(lambda e, tokens: jnp.take(
            e.astype(jnp.float32), tokens, axis=0)),
        "embed_bwd": jax.jit(lambda e, tokens, dh: jnp.zeros(
            e.shape, jnp.float32).at[tokens].add(dh)),
        "block": highest(lambda x, w: block(x, _f32(w))),
        "block_bwd": backward(block),
        "final": highest(lambda x, g: final(x, _f32(g))),
        "final_bwd": backward(final),
        "exits": highest(lambda hs, hw, gw, gb, labels, scale: exits(
            hs, *_f32((hw, gw, gb)), labels, scale)),
        "exits_bwd": highest(exits_backward)}


def _layer(params, i):
    return {n: params["layer%d.%s" % (i, n)] for n in NORMS + MATS}


def _forward(run, model, passes, params, tokens, keep=None):
    """One sequence's normed states ``[h1 .. hP]``; with ``keep`` a
    list, every piece's input is appended to it in forward order."""
    h = run["embed"](params["embed"], tokens)
    states = []
    for _ in range(passes):
        for i in range(model["num_hidden_layers"]):
            if keep is not None:
                keep.append(h)
            h = run["block"](h, _layer(params, i))
        if keep is not None:
            keep.append(h)
        h = run["final"](h, params["final_norm"])
        states.append(h)
    return states


def logits(model, params, tokens):
    """Every pass's float32 logits (B, P, T, vocab) of ``tokens`` (B, T);
    for small sizes."""
    run = _programs(model, "f32")
    head_w = params["lm_head"].astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        return jnp.stack([jnp.stack([
            jnp.matmul(h, head_w.T) for h in _forward(
                run, model, model["total_ut_steps"], params, row)])
            for row in tokens])


def make_step(model, precision="f32", rows=None, passes=None,
              last_pass_grad=False, optimizer=None, bias_correction=True):
    """``step(params, state, tokens, labels) -> (loss, parts, grads,
    params', state')`` of one AdamW step; ``params`` in ``param_dtype``,
    ``state`` None before the first step, then ``(t, m, v)`` with the
    moments float32; ``tokens``, ``labels`` (B, T) int.  ``parts``: the
    mean cross-entropy ``ce`` (P,) and the mean exit probability ``p``
    (P,) of every exit.  ``step.loss(params, tokens, labels) -> (loss,
    parts)`` is the forward alone.

    The controls: ``precision`` (``fp8``: every product's operands
    e4m3, its backward cotangent e5m2), ``rows`` (only the first
    ``rows`` sequences: part of the batch left out), ``passes`` (the
    loop run another number of times), ``last_pass_grad`` (no gradient
    through the earlier passes: a shared weight's gradient is one use,
    not the sum over the passes), ``optimizer`` (settings put in the
    configuration's place: a learning rate of 0 is a state left
    unchanged), ``bias_correction`` off (the moments used as they are,
    not over ``1 - beta^t``)."""
    opt = dict(model["optimizer"], **(optimizer or {}))
    lr, b1, b2, eps, wd = (opt["learning_rate"], opt["beta1"], opt["beta2"],
                           opt["epsilon"], opt["wd"])
    passes = passes or model["total_ut_steps"]
    layers = model["num_hidden_layers"]
    run = _programs(model, precision)

    def exits_of(params, labels, scale):
        return (params["lm_head"], params["gate.w"], params["gate.b"],
                labels, jnp.float32(scale))

    def cut(tokens, labels):
        return (tokens, labels) if rows is None \
            else (tokens[:rows], labels[:rows])

    def forward(params, tokens, labels):
        tokens, labels = cut(tokens, labels)
        n = tokens.shape[0]
        loss, ce, p = 0.0, 0.0, 0.0
        for row, lab in zip(tokens, labels):
            hs = _forward(run, model, passes, params, row)
            part, (c, q) = run["exits"](hs, *exits_of(params, lab, 1.0 / n))
            loss, ce, p = loss + part, ce + c / n, p + q / n
        return loss, {"ce": ce, "p": p}

    def gradient(params, tokens, labels):
        tokens, labels = cut(tokens, labels)
        n = tokens.shape[0]
        grads = {}

        def add(name, g):
            grads[name] = grads[name] + g if name in grads else g

        loss, ce, p = 0.0, 0.0, 0.0
        for row, lab in zip(tokens, labels):
            kept = []
            hs = _forward(run, model, passes, params, row, kept)
            (part, (c, q)), (dhs, d_head, d_gw, d_gb) = run["exits_bwd"](
                hs, *exits_of(params, lab, 1.0 / n))
            loss, ce, p = loss + part, ce + c / n, p + q / n
            del hs
            add("lm_head", d_head)
            add("gate.w", d_gw)
            add("gate.b", d_gb)
            dh = None        # the gradient of the next pass's input
            for t in reversed(range(passes)):
                dh = dhs[t] if dh is None else dhs[t] + dh
                dh, g = run["final_bwd"](kept.pop(), params["final_norm"],
                                         dh)
                add("final_norm", g)
                for i in reversed(range(layers)):
                    dh, g = run["block_bwd"](kept.pop(), _layer(params, i),
                                             dh)
                    for name, leaf in g.items():
                        add("layer%d.%s" % (i, name), leaf)
                if last_pass_grad and t == passes - 1:
                    dh = None
            if dh is None:
                dh = jnp.zeros((row.shape[0], model["hidden_size"]),
                               jnp.float32)
            add("embed", run["embed_bwd"](params["embed"], row, dh))
        return loss, {"ce": ce, "p": p}, grads

    @jax.jit
    def update(w, g, m, v, t):
        wf = w.astype(jnp.float32)
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        mhat = m / (1 - b1 ** t) if bias_correction else m
        vhat = v / (1 - b2 ** t) if bias_correction else v
        new = wf - lr * (mhat / (jnp.sqrt(vhat) + eps) + wd * wf)
        return new.astype(w.dtype), m, v

    def step(params, state, tokens, labels):
        loss, parts, grads = gradient(params, tokens, labels)
        t, m, v = state or (0, {}, {})
        t += 1
        new_p, new_m, new_v = {}, {}, {}
        for k, w in params.items():
            zero = jnp.zeros(w.shape, jnp.float32)
            new_p[k], new_m[k], new_v[k] = update(
                w, grads[k], m.get(k, zero), v.get(k, zero),
                jnp.float32(t))
        return loss, parts, grads, new_p, (t, new_m, new_v)

    step.loss, step.gradient = forward, gradient
    return step
