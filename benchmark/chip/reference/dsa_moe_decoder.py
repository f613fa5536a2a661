"""Plain reference of the training step of Keye-VL-2.0-30B-A3B's language
model (``config.json`` of ``Kwai-Keye/Keye-VL-2.0-30B-A3B``; the attention
is DeepSeek Sparse Attention, DeepSeek-V3.2-Exp technical report section
2.1): forward, loss, gradients and AdamW in float32 ``jax.numpy`` at
``highest`` matmul precision, no kernel, nothing of the program.

    block:   u = N1(x)
             q = RoPE(Nq(u Wq)_h), k = RoPE(Nk(u Wk)_g), v = (u Wv)_g,
                 32 heads h, 4 groups g(h) = h // 8, RMSNorm over a head
             indexer on stopgrad(u): qI_j = RoPE_half(u WIq)_j (16 x 64),
                 kI = RoPE_half(LN(u WIk)), w_j = (u WIw)_j / 4
             I[t, s] = sum_j w_j relu(qI_j . kI_s / 8), s <= t
             S_t = top min(2048, t + 1) of I[t, :] (lax.top_k over the
                 masked row: exact, ties to the lower position)
             o_h = softmax over S_t of q_h . k_g(h) / sqrt(128), times v
             L^I = mean_t KL(p_t || softmax_{S_t} I_t),
                 p_t = stopgrad(mean_h of the weights over S_t)
             x <- x + o Wo
             u' = N2(x);  r = softmax(u' Wr) over all 128 experts
             E_t = top 8 of r_t, g = r / sum_{E_t} r
             x <- x + sum_{e in E_t, e held} g_e W2_e(silu(W1_e u') * W3_e u')
             router term 0.001 * 128 * sum_e f_e P_e over all 128
    head:    loss = mean_t CE(Nf(x) Whead, label_t)
             + sum over layers of L^I + mean over layers of the router term

The selection is made from the reference's own float32 scores.  The
attention is one dense softmax a block of queries, its keys masked to the
selected set (or to every visible key: the ``dense_attention``
control); the experts are computed dense, every token through every held
expert, its gate 0 where the token does not route there.  Departures
from the published description are those of the configuration's
``assumed`` (where the description is silent) and its ``deployment``
(16 of 128 experts held: what the absent experts add is left out, as in
the program; a vocabulary slice).

So that float32 at 32,768 tokens fits one chip once the program's state
is freed, the step is a chain of jitted pieces (a block, the head with
the loss), each run forward with its input kept and then, last to first,
again with ``jax.vjp``; inside a block the queries go a block at a time
(their keys cut at the last query's position, in spans of 8,192) and the
experts one at a time, each made again in the backward
(``jax.checkpoint``).
"""
import math

import jax
import jax.numpy as jnp

from .looped_decoder import _f32, _rope
from .precision import contraction

NORMS = ("attention_norm", "q_norm", "k_norm", "ffn_norm")
MATS = ("wq", "wk", "wv", "wo", "idx_wq", "idx_wk", "idx_w", "router")
EXPERTS = ("w_gate", "w_up", "w_down")
INDEX_NORM = ("idx_norm_g", "idx_norm_b")
LEAVES = NORMS + MATS + INDEX_NORM + EXPERTS
#: queries a block of the attention and of the selection
QUERY_BLOCK = 256
#: the keys of a block of queries are cut at a multiple of this
SPAN = 8192
#: tokens a block of the head's logits
HEAD_BLOCK = 4096


def leaf_specs(model):
    """``{name: {"kind", "scale", "shape", "dtype"}}`` of every leaf in
    forward order: matrices normal(0, ``init_std``) as (out, in), the
    held experts' (held, in, out), norm gains 1 and the indexer norm's
    bias 0."""
    dt, std = model["param_dtype"], model["init_std"]
    d, v = model["hidden_size"], model["vocab_size"]
    H, G, D = (model["num_attention_heads"], model["num_key_value_heads"],
               model["head_dim"])
    sa = model["sa_config"]
    Hi, Di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    n, F = model["num_experts"], model["moe_intermediate_size"]
    E = model["num_local_experts"]

    def normal(*shape):
        return {"kind": "normal", "scale": std, "shape": list(shape),
                "dtype": dt}

    def const(value, width):
        return {"kind": "const", "scale": value, "shape": [width],
                "dtype": dt}

    shapes = {"wq": (H * D, d), "wk": (G * D, d), "wv": (G * D, d),
              "wo": (d, H * D), "idx_wq": (Hi * Di, d), "idx_wk": (Di, d),
              "idx_w": (Hi, d), "router": (E, d), "w_gate": (n, d, F),
              "w_up": (n, d, F), "w_down": (n, F, d)}
    widths = {"attention_norm": d, "q_norm": D, "k_norm": D, "ffn_norm": d}
    specs = {"embed": normal(v, d)}
    for i in range(model["num_hidden_layers"]):
        for name in LEAVES:
            key = "layer%d.%s" % (i, name)
            if name in widths:
                specs[key] = const(1.0, widths[name])
            elif name in INDEX_NORM:
                specs[key] = const(1.0 if name == "idx_norm_g" else 0.0, Di)
            else:
                specs[key] = normal(*shapes[name])
    specs["final_norm"] = const(1.0, d)
    specs["lm_head"] = normal(v, d)
    return specs


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _layer_norm(x, g, b, eps=1e-6):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g + b


def _rope_half(x, theta):
    """Rotary on the first half of the last axis; (T, H, D)."""
    h = x.shape[-1] // 2
    return jnp.concatenate([_rope(x[..., :h], theta), x[..., h:]], -1)


def _spans(T):
    """``[(first query, queries, keys)]``: blocks of ``QUERY_BLOCK``
    queries grouped by the span their last query's keys end in."""
    qb = min(QUERY_BLOCK, T)
    out, t = [], 0
    while t < T:
        keys = min(T, -(-(t + qb) // SPAN) * SPAN)
        out.append((t, keys - t, keys))
        t = keys
    return out


def _pieces(model, precision):
    """The pure functions of one sequence: ``block(x (T, d), w, how) ->
    (x, L^I, router term)``, ``selection(x, w) -> (idx (T, K),
    n_valid)`` and ``exits(x, gain, head, labels (T,), scale) -> (scale
    * loss, ce)``.  ``how`` holds two booleans (arrays, so that the
    controls they make share the programs): attention over every visible
    key, and the absent experts' pairs computed too."""
    H, G, D = (model["num_attention_heads"], model["num_key_value_heads"],
               model["head_dim"])
    sa = model["sa_config"]
    Hi, Di, K = (sa["indexer_num_heads"], sa["indexer_head_dim"],
                 sa["topk"])
    eps, theta = model["rms_norm_eps"], model["rope_theta"]
    E, top, first = (model["num_local_experts"],
                     model["num_experts_per_tok"], model["first_expert_held"])
    n_held = model["num_experts"]
    coef = model["router_aux_loss_coef"]
    mm = contraction(precision, lambda x, w: jnp.matmul(x, w.T))
    mmx = contraction(precision, jnp.matmul)
    qk = contraction(precision, lambda a, b: jnp.einsum("qhd,shd->hqs",
                                                        a, b))
    pv = contraction(precision, lambda p, b: jnp.einsum("hqs,shd->qhd",
                                                        p, b))
    iq = contraction(precision, lambda a, b: jnp.einsum("qjd,sd->qjs",
                                                        a, b))

    def index_inputs(u, w):
        T = u.shape[0]
        ud = jax.lax.stop_gradient(u)
        qi = _rope_half(mm(ud, w["idx_wq"]).reshape(T, Hi, Di), theta)
        ki = _layer_norm(mm(ud, w["idx_wk"]), w["idx_norm_g"],
                         w["idx_norm_b"])
        ki = _rope_half(ki[:, None, :], theta)[:, 0]
        wi = mm(ud, w["idx_w"]) * Hi ** -0.5
        return qi, ki, wi

    def scores(qi, ki, wi, t0):
        """(queries, keys) index scores, -inf after a query's position."""
        s = jnp.einsum("qjs,qj->qs", jax.nn.relu(iq(qi, ki) / math.sqrt(Di)),
                       wi)
        qpos = t0 + jnp.arange(qi.shape[0])[:, None]
        return jnp.where(jnp.arange(ki.shape[0])[None, :] <= qpos, s,
                         -jnp.inf)

    def select(s, t0):
        """The selected set of each row as a mask, and its slots."""
        vals, idx = jax.lax.top_k(s, K)
        n_valid = jnp.minimum(K, t0 + jnp.arange(s.shape[0]) + 1)
        valid = jnp.arange(K)[None, :] < n_valid[:, None]
        mask = jnp.zeros(s.shape, bool).at[
            jnp.arange(s.shape[0])[:, None], idx].set(valid)
        return mask, idx, n_valid

    def attend(q, k, v, qi, ki, wi, t0, dense):
        """One span's query blocks, the first at ``t0``: ``(o (n, H, D),
        kl (n,))``."""
        kk, vv = jnp.repeat(k, H // G, 1), jnp.repeat(v, H // G, 1)

        def one(args):
            qb, qib, wib, t0 = args
            s = scores(qib, ki, wib, t0)
            sel, _, _ = select(s, t0)
            visible = jnp.isfinite(s)
            seen = jnp.where(dense, visible, sel)
            a = qk(qb, kk) / math.sqrt(D)
            a = jax.nn.softmax(jnp.where(seen[None], a, -jnp.inf), -1)
            o = pv(a, vv)
            p = jax.lax.stop_gradient(jnp.where(sel, jnp.mean(a, 0), 0.0))
            p = p / jnp.sum(p, -1, keepdims=True)
            logq = jax.nn.log_softmax(jnp.where(sel, s, -jnp.inf), -1)
            kl = jnp.sum(jnp.where(sel, jax.scipy.special.xlogy(p, p)
                                   - p * jnp.where(sel, logq, 0.0), 0.0),
                         -1)
            return o, kl

        n = q.shape[0]
        qb = min(QUERY_BLOCK, n)
        starts = jnp.arange(n // qb) * qb

        def blocks(a):
            return a.reshape((n // qb, qb) + a.shape[1:])

        o, kl = jax.lax.map(jax.checkpoint(one),
                            (blocks(q), blocks(qi), blocks(wi), starts + t0))
        return o.reshape(n, H, D), kl.reshape(n)

    def ffn(u, w, every):
        T = u.shape[0]
        r = jax.nn.softmax(mm(u, w["router"]), -1)            # (T, E)
        top_p, top_e = jax.lax.top_k(r, top)
        gates = top_p / jnp.sum(top_p, -1, keepdims=True)
        slot = jnp.where(every, top_e % n_held, top_e - first)
        here = (slot >= 0) & (slot < n_held)
        gate = jnp.einsum("tk,tke->te", jnp.where(here, gates, 0.0),
                          jax.nn.one_hot(slot, n_held))        # (T, held)
        f = jnp.zeros((E,), jnp.float32).at[top_e.reshape(-1)].add(1.0) / T
        aux = coef * E * jnp.sum(f * jnp.mean(r, 0))

        def expert(y, args):
            w1, w3, w2, g = args
            h = jax.nn.silu(mmx(u, w1)) * mmx(u, w3)
            return y + g[:, None] * mmx(h, w2), None

        y, _ = jax.lax.scan(jax.checkpoint(expert), jnp.zeros_like(u),
                            (w["w_gate"], w["w_up"], w["w_down"], gate.T))
        return y, aux

    def block(x, w, how):
        T = x.shape[0]
        dense, every = how
        u = _rms(x, w["attention_norm"], eps)
        q = _rope(_rms(mm(u, w["wq"]).reshape(T, H, D), w["q_norm"], eps),
                  theta)
        k = _rope(_rms(mm(u, w["wk"]).reshape(T, G, D), w["k_norm"], eps),
                  theta)
        v = mm(u, w["wv"]).reshape(T, G, D)
        qi, ki, wi = index_inputs(u, w)
        outs, kls = [], []
        for t0, n, keys in _spans(T):
            o, kl = attend(q[t0:t0 + n], k[:keys], v[:keys],
                           qi[t0:t0 + n], ki[:keys], wi[t0:t0 + n], t0,
                           dense)
            outs.append(o)
            kls.append(kl)
        o = jnp.concatenate(outs).reshape(T, H * D)
        index_loss = jnp.mean(jnp.concatenate(kls))
        x = x + mm(o, w["wo"])
        y, aux = ffn(_rms(x, w["ffn_norm"], eps), w, every)
        return x + y, index_loss, aux

    def selection(x, w):
        """Layer ``w``'s selected slots of the stream ``x`` (T, d)."""
        T = x.shape[0]
        u = _rms(x, w["attention_norm"], eps)
        qi, ki, wi = index_inputs(u, w)
        idx = []
        for t0, n, keys in _spans(T):
            qb = min(QUERY_BLOCK, n)

            def one(args, keys=keys):
                qib, wib, s0 = args
                return select(scores(qib, ki[:keys], wib, s0), s0)[1]

            i = jax.lax.map(one, (qi[t0:t0 + n].reshape(n // qb, qb, Hi, Di),
                                  wi[t0:t0 + n].reshape(n // qb, qb, Hi),
                                  t0 + jnp.arange(n // qb) * qb))
            idx.append(i.reshape(n, K))
        return jnp.concatenate(idx), jnp.minimum(K, jnp.arange(T) + 1)

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

    return block, selection, exits


_PROGRAMS = {}


def _programs(model, precision):
    """The pieces as jitted programs, forward and backward, at
    ``highest`` matmul precision; weights arrive in ``param_dtype`` and
    are widened inside, their gradients leave in float32.  One set a
    precision and configuration, shared by the steps made of them."""
    key = (precision, repr(sorted(model.items())))
    if key not in _PROGRAMS:
        _PROGRAMS[key] = _jitted(model, precision)
    return _PROGRAMS[key]


def _jitted(model, precision):
    block, selection, exits = _pieces(model, precision)

    def highest(fn):
        def run(*args):
            with jax.default_matmul_precision("highest"):
                return fn(*args)
        return jax.jit(run)

    def exits_backward(x, gain, head_w, labels, scale):
        return jax.value_and_grad(exits, argnums=(0, 1, 2), has_aux=True)(
            x, *_f32((gain, head_w)), labels, scale)

    def block_backward(x, w, how, dy, d_index, d_router):
        return jax.vjp(lambda x, w: block(x, w, how), x, _f32(w))[1](
            (dy, d_index, d_router))

    return {
        "embed": jax.jit(lambda e, tokens: jnp.take(
            e.astype(jnp.float32), tokens, axis=0)),
        "embed_bwd": jax.jit(lambda e, tokens, dh: jnp.zeros(
            e.shape, jnp.float32).at[tokens].add(dh)),
        "block": highest(lambda x, w, how: block(x, _f32(w), how)),
        "block_bwd": highest(block_backward),
        "selection": highest(lambda x, w: selection(x, _f32(w))),
        "exits": highest(lambda x, g, hw, labels, scale: exits(
            x, *_f32((g, hw)), labels, scale)),
        "exits_bwd": highest(exits_backward)}


def _layer(params, i):
    return {n: params["layer%d.%s" % (i, n)] for n in LEAVES}


def make_step(model, precision="f32", attention="sparse", index_loss=True,
              experts="held", optimizer=None, drop_state_at=None):
    """``step(params, state, tokens, labels) -> (loss, parts, grads,
    params', state')`` of one AdamW step on (B, T) tokens and labels;
    ``params`` in ``param_dtype``, ``state`` None before the first step,
    then ``(t, m, v)`` with float32 moments.  ``parts``: ``{"ce",
    "index_loss", "router_loss"}``, each (1,).  ``step.loss(params,
    tokens, labels) -> (loss, parts)`` is the forward alone;
    ``step.selection(params, tokens)`` the first layer's selection of
    the first sequence, as this step computes it.  ``drop_state_at``: the
    step (from 1) whose moments the caller will not read.

    The controls: ``precision`` (``fp8``: every product's operands e4m3,
    its backward cotangent e5m2), ``attention`` (``dense``: every visible
    key, no selection), ``index_loss`` (False: the indexer's KL left out
    of the loss), ``experts`` (``all``: the pairs routed to absent
    experts computed too, by held expert e mod 16's weights: the part
    another chip adds, added here), ``optimizer`` (settings in the
    configuration's place: a learning rate of 0 is a state left
    unchanged)."""
    opt = dict(model["optimizer"], **(optimizer or {}))
    lr, b1, b2, eps, wd = (opt["learning_rate"], opt["beta1"], opt["beta2"],
                           opt["epsilon"], opt["wd"])
    layers = model["num_hidden_layers"]
    weight = model["index_loss_weight"] if index_loss else 0.0
    run = _programs(model, precision)
    how = (jnp.bool_(attention == "dense"), jnp.bool_(experts == "all"))

    def exits_of(params, labels, scale):
        return (params["final_norm"], params["lm_head"], labels,
                jnp.float32(scale))

    def forward(params, tokens, labels):
        n = tokens.shape[0]
        loss, ce, il, rl = 0.0, 0.0, 0.0, 0.0
        for row, lab in zip(tokens, labels):
            h = run["embed"](params["embed"], row)
            for i in range(layers):
                h, a, b = run["block"](h, _layer(params, i), how)
                il, rl = il + weight * a / n, rl + b / (n * layers)
            part, c = run["exits"](h, *exits_of(params, lab, 1.0 / n))
            loss, ce = loss + part, ce + c / n
        return loss + il + rl, _parts(ce, il, rl)

    def gradient(params, tokens, labels):
        n = tokens.shape[0]
        grads = {}

        def add(name, g):
            grads[name] = grads[name] + g if name in grads else g

        loss, ce, il, rl = 0.0, 0.0, 0.0, 0.0
        for row, lab in zip(tokens, labels):
            kept = []
            h = run["embed"](params["embed"], row)
            for i in range(layers):
                kept.append(h)
                h, a, b = run["block"](h, _layer(params, i), how)
                il, rl = il + weight * a / n, rl + b / (n * layers)
            (part, c), (dh, d_gain, d_head) = run["exits_bwd"](
                h, *exits_of(params, lab, 1.0 / n))
            loss, ce = loss + part, ce + c / n
            del h
            add("final_norm", d_gain)
            add("lm_head", d_head)
            for i in reversed(range(layers)):
                dh, g = run["block_bwd"](kept.pop(), _layer(params, i), how,
                                         dh, jnp.float32(weight / n),
                                         jnp.float32(1.0 / (n * layers)))
                for name, leaf in g.items():
                    add("layer%d.%s" % (i, name), leaf)
            add("embed", run["embed_bwd"](params["embed"], row, dh))
        return loss + il + rl, _parts(ce, il, rl), grads

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

    def first_selection(params, tokens):
        h = run["embed"](params["embed"], tokens[0])
        return run["selection"](h, _layer(params, 0))

    step.loss, step.gradient = forward, gradient
    step.selection = first_selection
    return step


def _parts(ce, il, rl):
    return {"ce": jnp.reshape(ce, (1,)), "index_loss": jnp.reshape(il, (1,)),
            "router_loss": jnp.reshape(rl, (1,))}
