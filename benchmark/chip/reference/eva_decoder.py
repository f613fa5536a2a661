"""Plain reference of EvaByte's training step (``config.json`` and the
modelling code of ``EvaByte/EvaByte``; the attention is EVA, Zheng et
al., "Efficient Attention via Control Variates", ICLR 2023, in the causal
chunked form of the model's ``eva.py`` / ``eva_pt_ref.py``): forward, the
multi-byte prediction loss, gradients and AdamW in float32 ``jax.numpy``
at ``highest`` matmul precision.  The attention is one dense masked
softmax over the concatenated key set ``[the window's tokens | every
chunk's summary]``; no kernel, no merge by logsumexp, nothing of the
program.

    block:   u = N1(x);  q, k, v = u Wq, u Wk, u Wv;  RoPE(theta) on q, k
             chunk j = tokens [c j, c (j + 1)), in window floor(c j / W)
             k~_j = sum_m softmax_m(k_m . mu_h) k_m
             v~_j = sum_m softmax_m(k_m . phi_h) v_m      (per head h)
             query i in window w sees the tokens m <= i of window w and
             the summaries j of the windows before w, under one softmax
             of s q.k, s = D^-1/2;  x <- x + o Wo
             x <- x + W_down(silu(W_gate u') * W_up u'),  u' = N2(x)
    norms:   N(x) = x / rms(x) * (1 + g), g the stored leaf (unit offset)
    head:    z = Nf(x);  logits[t, k] = z_t W_head[k], k = 0..7;
             loss = mean_k mean_t CE(logits[t, k], byte[t + 1 + k])

Where the reading of ``described_as`` ("EVA chunked linearized
attention") and these equations differ: the catalog's summary names the
family of the mechanism (EVA linearizes the *remote* part through
control variates); the chunked causal form the model runs, and this file
computes, is exact softmax attention over the window's tokens and one
learned summary a chunk, with no random features.  Each choice that
``config.json`` does not fix is in the configuration's file under
``assumed``: the pooling logits carry no further scale, the rotary form
is rotate-half, the eight heads' losses weigh equally, parameters are
held in ``param_dtype`` between steps (the update is computed in float32
and rounded once when stored), the moments in float32, no clipping, one
document a sequence.

So that float32 at 8,192 bytes fits one chip once the program's state is
freed, and compiles in seconds to programs small enough for the compile
cache, the step is a chain of jitted pieces (a block, the final norm
with the heads and the loss), each run forward with its input kept and
then, last to first, again with ``jax.vjp``.  Attention runs a (head,
window) pair at a time, its scores made again in the backward
(``jax.checkpoint``), and the update is applied a leaf at a time.
"""
import math

import jax
import jax.numpy as jnp

from .looped_decoder import _f32, _rope   # rotate-half rotary; widen a tree
from .precision import contraction

NORMS = ("attention_norm", "ffn_norm")
MATS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")
POOLS = ("adaptive_mu_k", "adaptive_phi")


def leaf_specs(model):
    """``{name: {"kind", "scale", "shape", "dtype"}}`` of every leaf in
    forward order: matrices normal(0, ``init_std``), the norms' stored
    offsets 0, the pooling vectors normal(0, D^-1/2) and clamped at one
    deviation (``clamp``: ``common.make_weights`` draws, the driver
    clips)."""
    dt, std = model["param_dtype"], model["init_std"]
    d, f, v = (model["hidden_size"], model["intermediate_size"],
               model["vocab_size"])
    H = model["num_attention_heads"]
    hd = d // H
    if model["num_key_value_heads"] != H:
        raise ValueError("eva pools a head's own keys: as many K/V heads")

    def mat(rows, cols):
        return {"kind": "normal", "scale": std, "shape": [rows, cols],
                "dtype": dt}

    zero = {"kind": "const", "scale": 0.0, "shape": [d], "dtype": dt}
    pool = {"kind": "normal", "scale": hd ** -0.5, "shape": [H, hd],
            "dtype": dt, "clamp": hd ** -0.5}
    shapes = {"wq": (d, d), "wk": (d, d), "wv": (d, d), "wo": (d, d),
              "w_gate": (f, d), "w_up": (f, d), "w_down": (d, f)}
    specs = {"embed": mat(v, d)}
    for i in range(model["num_hidden_layers"]):
        for n in NORMS:
            specs["layer%d.%s" % (i, n)] = zero
        for n in MATS:
            specs["layer%d.%s" % (i, n)] = mat(*shapes[n])
        for n in POOLS:
            specs["layer%d.%s" % (i, n)] = pool
    specs["final_norm"] = zero
    specs["lm_head"] = mat(model["num_pred_heads"] * v, d)
    return specs


def clamp(specs, weights):
    """``weights`` with every leaf whose spec has a ``clamp`` clipped to
    it."""
    return {n: jnp.clip(w, -specs[n]["clamp"], specs[n]["clamp"])
            if "clamp" in specs[n] else w for n, w in weights.items()}


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + g)


def _pieces(model, precision, summaries, pooling, heads):
    """The pure functions of one sequence: ``block(x (T, d), w) -> x``
    and ``exits(x, gain, head, labels (T, K), scale) -> (scale * loss,
    ce (K,))``."""
    H = model["num_attention_heads"]
    D = model["hidden_size"] // H
    eps, theta = model["rms_norm_eps"], model["rope_theta"]
    W, c = model["window_size"], model["chunk_size"]
    K, V = model["num_pred_heads"], model["vocab_size"]
    mm = contraction(precision, lambda x, w: jnp.matmul(x, w.T))
    scores = contraction(precision, lambda q, k: jnp.matmul(q, k.T))
    mix = contraction(precision, jnp.matmul)

    def pooled(kc, xc, vec):
        """(n, H, D): every chunk's ``xc`` (n, c, H, D) weighed by the
        softmax over the chunk of ``kc . vec``."""
        if pooling == "mean":
            vec = jnp.zeros_like(vec)
        p = jax.nn.softmax(jnp.einsum("nchd,hd->nch", kc, vec), axis=1)
        return jnp.einsum("nch,nchd->nhd", p, xc)

    def block(x, w):
        T = x.shape[0]
        win = min(W, T)
        nw, per = T // win, win // c
        h = _rms(x, w["attention_norm"], eps)
        q = _rope(mm(h, w["wq"]).reshape(T, H, D), theta)
        k = _rope(mm(h, w["wk"]).reshape(T, H, D), theta)
        v = mm(h, w["wv"]).reshape(T, H, D)
        kc = k.reshape(T // c, c, H, D)
        ks = pooled(kc, kc, w["adaptive_mu_k"])            # (T / c, H, D)
        vs = pooled(kc, v.reshape(T // c, c, H, D), w["adaptive_phi"])
        own = jnp.arange(T // c) // per                    # a chunk's window
        causal = jnp.tril(jnp.ones((win, win), bool))

        def head_window(args):
            """One head's queries of one window over ``[the window's
            tokens | all summaries]``, one masked softmax."""
            qw, kw, vw, ksh, vsh, wi = args
            if summaries == "none":
                seen = jnp.zeros_like(own, bool)
            elif summaries == "own_window_too":
                seen = own <= wi
            else:
                seen = own < wi
            keys = jnp.concatenate([kw, ksh], 0)
            vals = jnp.concatenate([vw, vsh], 0)
            mask = jnp.concatenate(
                [causal, jnp.broadcast_to(seen[None, :], (win, T // c))], 1)
            s = scores(qw, keys) / math.sqrt(D)
            return mix(jax.nn.softmax(jnp.where(mask, s, -jnp.inf), -1),
                       vals)

        def windows(a):          # (T, H, D) -> (H * nw, win, D)
            return jnp.swapaxes(a, 0, 1).reshape(H * nw, win, D)

        def every(a):            # (T / c, H, D) -> (H * nw, T / c, D)
            return jnp.repeat(jnp.swapaxes(a, 0, 1), nw, axis=0)

        o = jax.lax.map(jax.checkpoint(head_window),
                        (windows(q), windows(k), windows(v), every(ks),
                         every(vs), jnp.tile(jnp.arange(nw), H)))
        o = jnp.swapaxes(o.reshape(H, T, D), 0, 1).reshape(T, H * D)
        x = x + mm(o, w["wo"])
        h = _rms(x, w["ffn_norm"], eps)
        return x + mm(jax.nn.silu(mm(h, w["w_gate"])) * mm(h, w["w_up"]),
                      w["w_down"])

    def exits(x, gain, head_w, labels, scale):
        z = _rms(x, gain, eps)
        logp = jax.nn.log_softmax(
            mm(z, head_w).reshape(x.shape[0], K, V), axis=-1)
        ce = -jnp.mean(jnp.take_along_axis(
            logp, labels[..., None], axis=-1)[..., 0], axis=0)   # (K,)
        loss = ce[0] if heads == "next_byte" else jnp.mean(ce)
        return scale * loss, ce

    return block, exits


def _programs(model, precision, summaries="before", pooling="learned",
              heads="all"):
    """The pieces as jitted programs, forward and backward, at
    ``highest`` matmul precision; weights arrive in ``param_dtype`` and
    are widened inside, their gradients leave in float32."""
    block, exits = _pieces(model, precision, summaries, pooling, heads)

    def highest(fn):
        def run(*args):
            with jax.default_matmul_precision("highest"):
                return fn(*args)
        return jax.jit(run)

    def exits_backward(x, gain, head_w, labels, scale):
        return jax.value_and_grad(exits, argnums=(0, 1, 2), has_aux=True)(
            x, *_f32((gain, head_w)), labels, scale)

    return {
        "embed": jax.jit(lambda e, tokens: jnp.take(
            e.astype(jnp.float32), tokens, axis=0)),
        "embed_bwd": jax.jit(lambda e, tokens, dh: jnp.zeros(
            e.shape, jnp.float32).at[tokens].add(dh)),
        "block": highest(lambda x, w: block(x, _f32(w))),
        "block_bwd": highest(lambda x, w, dy: jax.vjp(
            block, x, _f32(w))[1](dy)),
        "exits": highest(lambda x, g, hw, labels, scale: exits(
            x, *_f32((g, hw)), labels, scale)),
        "exits_bwd": highest(exits_backward)}


def _layer(params, i):
    return {n: params["layer%d.%s" % (i, n)] for n in NORMS + MATS + POOLS}


def _forward(run, model, params, tokens, keep=None):
    """One sequence's last stream (T, d); with ``keep`` a list, every
    block's input is appended to it in forward order."""
    h = run["embed"](params["embed"], tokens)
    for i in range(model["num_hidden_layers"]):
        if keep is not None:
            keep.append(h)
        h = run["block"](h, _layer(params, i))
    return h


def logits(model, params, tokens):
    """Float32 logits (B, T, K, vocab) of ``tokens`` (B, T); for small
    sizes."""
    run = _programs(model, "f32")
    eps = model["rms_norm_eps"]
    K, V = model["num_pred_heads"], model["vocab_size"]
    with jax.default_matmul_precision("highest"):
        return jnp.stack([jnp.matmul(
            _rms(_forward(run, model, params, row),
                 params["final_norm"].astype(jnp.float32), eps),
            params["lm_head"].astype(jnp.float32).T).reshape(-1, K, V)
            for row in tokens])


def make_step(model, precision="f32", summaries="before",
              pooling="learned", heads="all", optimizer=None,
              bias_correction=True, drop_state_at=None):
    """``step(params, state, tokens, labels) -> (loss, parts, grads,
    params', state')`` of one AdamW step; ``params`` in ``param_dtype``,
    ``state`` None before the first step, then ``(t, m, v)`` with the
    moments float32; ``tokens`` (B, T), ``labels`` (B, T, K) int.
    ``parts``: ``{"ce": (K,)}``, every head's mean cross-entropy.
    ``step.loss(params, tokens, labels) -> (loss, parts)`` is the
    forward alone.  ``drop_state_at``: the step (counted from 1) whose
    moments the caller will not read; it returns None in their place, so
    that 8 bytes a parameter of float32 are not made beside the
    gradients.

    The controls: ``precision`` (``fp8``: every product's operands e4m3,
    its backward cotangent e5m2), ``summaries`` (``none``: windowed
    attention alone; ``own_window_too``: the mask off by one window),
    ``pooling`` (``mean``: the learned vectors ignored), ``heads``
    (``next_byte``: the first predictor's loss alone), ``optimizer``
    (settings put in the configuration's place: a learning rate of 0 is
    a state left unchanged), ``bias_correction`` off."""
    opt = dict(model["optimizer"], **(optimizer or {}))
    lr, b1, b2, eps, wd = (opt["learning_rate"], opt["beta1"], opt["beta2"],
                           opt["epsilon"], opt["wd"])
    layers = model["num_hidden_layers"]
    run = _programs(model, precision, summaries, pooling, heads)

    def exits_of(params, labels, scale):
        return (params["final_norm"], params["lm_head"], labels,
                jnp.float32(scale))

    def forward(params, tokens, labels):
        n = tokens.shape[0]
        loss, ce = 0.0, 0.0
        for row, lab in zip(tokens, labels):
            x = _forward(run, model, params, row)
            part, c = run["exits"](x, *exits_of(params, lab, 1.0 / n))
            loss, ce = loss + part, ce + c / n
        return loss, {"ce": ce}

    def gradient(params, tokens, labels):
        n = tokens.shape[0]
        grads = {}

        def add(name, g):
            grads[name] = grads[name] + g if name in grads else g

        loss, ce = 0.0, 0.0
        for row, lab in zip(tokens, labels):
            kept = []
            x = _forward(run, model, params, row, kept)
            (part, c), (dh, d_gain, d_head) = run["exits_bwd"](
                x, *exits_of(params, lab, 1.0 / n))
            loss, ce = loss + part, ce + c / n
            del x
            add("final_norm", d_gain)
            add("lm_head", d_head)
            for i in reversed(range(layers)):
                dh, g = run["block_bwd"](kept.pop(), _layer(params, i), dh)
                for name, leaf in g.items():
                    add("layer%d.%s" % (i, name), leaf)
            add("embed", run["embed_bwd"](params["embed"], row, dh))
        return loss, {"ce": ce}, grads

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
            new_p[k], mk, vk = update(
                w, grads[k], m.get(k, zero), v.get(k, zero), jnp.float32(t))
            if t != drop_state_at:
                new_m[k], new_v[k] = mk, vk
        state = None if t == drop_state_at else (t, new_m, new_v)
        return loss, parts, grads, new_p, state

    step.loss, step.gradient = forward, gradient
    return step
