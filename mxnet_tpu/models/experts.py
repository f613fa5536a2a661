"""Routed experts: a token's FFN is the gated sum of the ``top_k`` of
``num_experts`` SwiGLU experts its router picks (Qwen3-MoE's layer; the
same in DeepSeek's and Keye's language models).

    r_t = softmax(W_r u_t)            over all experts, float32
    E_t = the top_k experts of r_t,   g_{t,e} = r_{t,e} / sum_{E_t} r
    y_t = sum_{e in E_t and H} g_{t,e} W2_e(silu(W1_e u_t) * W3_e u_t)
    aux = coef * num_experts * sum_e f_e P_e,  f_e the share of tokens
          that route to e, P_e the mean of r_{t,e}; over all experts

``H`` is the set of experts this layer holds: ``held`` of them from
``first`` (expert parallelism gives a device a slice; one device that
holds them all is the slice of every expert).  The router sees every
expert, so a token's gates are those of the whole model; the experts
that are not here add nothing (their device adds it).  No token is
dropped: the (token, expert) pairs are sorted by expert, the pairs of
the experts held here first, and the grouped matmul (``megablox.gmm``
on the TPU, ``lax.ragged_dot`` elsewhere) runs the held groups' rows
alone; the buffer is sized for every pair (static shapes), the rows past
the held groups are never computed.  The dispatch and the combine are
gathers by the sort's permutation and its inverse, each the other's
transpose.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..gluon.block import HybridBlock
from ..gluon.nn import Dense
from ..gluon.parameter import Parameter
from ..ndarray.ndarray import apply_op


def route(x, router_w, top_k):
    """``(probs (T, E) float32, experts (T, top_k), gates (T, top_k))``,
    the gates normalised over a token's top_k."""
    logits = jax.lax.dot_general(x, router_w, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, top_k)
    return probs, top_e, top_p / jnp.sum(top_p, axis=-1, keepdims=True)


def balance_loss(probs, experts):
    """``E * sum_e f_e P_e`` (Switch Transformer eq. 4 over top-k
    routing, as Qwen3-MoE computes it): ``f_e`` the share of tokens that
    route to e, ``P_e`` the mean router probability of e."""
    T, E = probs.shape
    f = jnp.zeros((E,), jnp.float32).at[experts.reshape(-1)].add(1.0) / T
    return E * jnp.sum(f * jnp.mean(probs, axis=0))


@jax.custom_vjp
def _permute(a, order, inverse):
    """``a[order]`` whose transpose is the gather ``g[inverse]``, no
    scatter (``order`` is a permutation, ``inverse`` its inverse)."""
    return jnp.take(a, order, axis=0)


def _permute_fwd(a, order, inverse):
    return _permute(a, order, inverse), (order, inverse)


def _permute_bwd(res, g):
    order, inverse = res
    import numpy as onp
    zero = onp.zeros(order.shape, jax.dtypes.float0)
    return jnp.take(g, inverse, axis=0), zero, zero


_permute.defvjp(_permute_fwd, _permute_bwd)


@jax.custom_vjp
def _dispatch(x, order, inverse):
    """The pairs' rows in ``order``: pair ``p`` of ``x`` (T, D) is token
    ``p // top_k``; the transpose gathers the pairs back by ``inverse``
    and sums a token's."""
    return jnp.take(x, order // (order.shape[0] // x.shape[0]), axis=0)


def _dispatch_fwd(x, order, inverse):
    return _dispatch(x, order, inverse), (order, inverse, x.shape[0])


def _dispatch_bwd(res, g):
    order, inverse, T = res
    import numpy as onp
    zero = onp.zeros(order.shape, jax.dtypes.float0)
    g = jnp.take(g, inverse, axis=0)
    return (jnp.sum(g.reshape(T, -1, g.shape[1]).astype(jnp.float32),
                    axis=1).astype(g.dtype), zero, zero)


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


def _tiling(m, k, n):
    """megablox tiles: 512 rows, a dimension whole up to 1,024 or in
    halves of it."""
    def side(d):
        return d if d <= 1024 else next(
            (t for t in (1024, 512, 256, 128) if d % t == 0), 128)
    return min(512, m), side(k), side(n)


def grouped_matmul(x, w, sizes):
    """``x`` (M, K) rows grouped by ``sizes`` (G + 1,) — the last group's
    rows are not computed and read 0 — times ``w`` (G, K, N) group by
    group: megablox's kernels on the TPU, ``lax.ragged_dot`` elsewhere
    and under a mesh."""
    from ..ops.pallas_ops import _INTERPRET, _pallas_available
    from ..parallel.mesh import current_mesh
    M = x.shape[0]
    # GSPMD cannot partition the kernel: under a mesh XLA's ragged dot
    if _pallas_available() and M % 512 == 0 and current_mesh() is None:
        from jax.experimental.pallas.ops.tpu.megablox import gmm
        return gmm(x, w, sizes, x.dtype, _tiling, None, None, False,
                   _INTERPRET)
    return jax.lax.ragged_dot(x, w, sizes[:-1]).astype(x.dtype)


def sigmoid_route(x, router_w, top_k, scale):
    """``route`` with sigmoid scores (DeepSeek-V3's router, Laguna's):
    ``r = sigmoid(W_r u)``, the top_k's gates ``scale * r / sum_{E_t} r``;
    ``probs`` the scores normalised over all experts, which is what the
    balance loss reads as ``P_e``."""
    from .dsa import checkpoint_keep
    logits = jax.lax.dot_general(x, router_w, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    scores = jax.nn.sigmoid(logits)
    # a block made again for its backward routes as its forward did:
    # recomputed scores can differ in the last bit and flip a near tie
    top_e = checkpoint_keep(jax.lax.top_k(scores, top_k)[1])
    # the top k's scores by a masked sum: a gather along the experts
    # takes 0.8 ms a call on a v5e
    picked = top_e[..., None] == jnp.arange(scores.shape[-1],
                                            dtype=top_e.dtype)
    top_s = jnp.sum(jnp.where(picked, scores[:, None, :], 0.0), axis=-1)
    gates = scale * top_s / jnp.sum(top_s, axis=-1, keepdims=True)
    return scores / jnp.sum(scores, axis=-1, keepdims=True), top_e, gates


def routed_experts(x, router_w, w1, w3, w2, first, top_k, score="softmax",
                   scale=1.0):
    """One layer's routed experts on ``x`` (T, D): ``router_w`` (E, D)
    over all experts, ``w1``, ``w3`` (held, D, F) and ``w2`` (held, F, D)
    of experts ``first .. first + held - 1``; the router's ``score``,
    softmax (``route``) or sigmoid (``sigmoid_route``, whose gates are
    times ``scale``).  Returns ``(y (T, D), balance loss, pairs
    routed to held experts)``.  Named scopes ``router``, ``dispatch``,
    ``gmm`` (the grouped matmuls) and ``combine``."""
    T, D = x.shape
    held = w1.shape[0]
    with jax.named_scope("router"):
        probs, experts, gates = route(x, router_w, top_k) \
            if score == "softmax" else sigmoid_route(x, router_w, top_k,
                                                     scale)
        aux = balance_loss(probs, experts)
    with jax.named_scope("dispatch"):
        local = experts - first
        here = (local >= 0) & (local < held)
        group = jnp.where(here, local, held).reshape(-1)   # (T * top_k,)
        order = jnp.argsort(group, stable=True).astype(jnp.int32)
        inverse = jnp.zeros_like(order).at[order].set(
            jnp.arange(order.shape[0], dtype=jnp.int32))
        sizes = jnp.zeros((held + 1,), jnp.int32).at[group].add(1)
        rows = _dispatch(x, order, inverse)
    with jax.named_scope("gmm"):
        h = jax.nn.silu(grouped_matmul(rows, w1, sizes)) \
            * grouped_matmul(rows, w3, sizes)
        out = grouped_matmul(h.astype(x.dtype), w2, sizes)
    with jax.named_scope("combine"):
        out = _permute(out, inverse, order).reshape(T, top_k, D)
        g = jnp.where(here, gates, 0.0)
        y = jnp.einsum("tk,tkd->td", g, out.astype(jnp.float32))
    return y.astype(x.dtype), aux, jnp.sum(here.astype(jnp.int32))


class RoutedExperts(HybridBlock):
    """The routed-expert FFN of a transformer block
    (``LlamaConfig.moe_num_experts``): a router over all
    ``moe_num_experts`` experts, ``moe_top_k`` a token, and the SwiGLU
    weights of the ``moe_held`` experts from ``moe_first_held`` (all of
    them where ``moe_held`` is 0); the layer's ``LayerSpec`` gives the
    router's scores and scaling and the width of a shared expert, which
    every token passes through ungated beside the routed ones.  ``forward``
    gives ``(y, {"router_loss": moe_aux_coef * balance loss,
    "held_pairs": pairs routed here})``; the routed experts run under the
    scope ``experts``, the shared one beside them under ``shared_expert``.
    """

    def __init__(self, cfg, spec=None):
        super().__init__()
        from .transformer import FeedForward, LayerSpec
        spec = spec or LayerSpec()
        self._score, self._scale = spec.router_score, spec.routed_scale
        E = cfg.moe_num_experts
        held = cfg.moe_held or E
        F = cfg.moe_hidden_dim or cfg.hidden_dim
        D = cfg.dim
        if not 0 <= cfg.moe_first_held <= E - held or cfg.moe_top_k > E:
            raise ValueError(
                "routed experts: %d held from %d of %d experts, top %d"
                % (held, cfg.moe_first_held, E, cfg.moe_top_k))
        self._first, self._top_k = cfg.moe_first_held, cfg.moe_top_k
        self._coef = cfg.moe_aux_coef
        self.router = Dense(E, use_bias=False, flatten=False, in_units=D,
                            dtype=cfg.dtype)
        self.experts_w1 = Parameter(shape=(held, D, F), dtype=cfg.dtype,
                                    name="experts_w1").shard(("ep", None,
                                                              None))
        self.experts_w3 = Parameter(shape=(held, D, F), dtype=cfg.dtype,
                                    name="experts_w3").shard(("ep", None,
                                                              None))
        self.experts_w2 = Parameter(shape=(held, F, D), dtype=cfg.dtype,
                                    name="experts_w2").shard(("ep", None,
                                                              None))
        self._shared = bool(spec.shared_hidden_dim)
        if self._shared:
            self.shared_expert = FeedForward(cfg, spec.shared_hidden_dim)

    def forward(self, x):
        first, top_k, coef = self._first, self._top_k, self._coef
        score, scale = self._score, self._scale

        def f(a, r, w1, w3, w2):
            B, T, D = a.shape
            with jax.named_scope("experts"):
                y, aux, n = routed_experts(a.reshape(B * T, D), r, w1, w3,
                                           w2, first, top_k, score, scale)
            return y.reshape(B, T, D), coef * aux, n

        y, aux, n = apply_op(f, [x, self.router.weight.data(),
                                 self.experts_w1.data(),
                                 self.experts_w3.data(),
                                 self.experts_w2.data()], n_out=3,
                             name="routed_experts")
        if self._shared:
            y = y + self.shared_expert(x)
        return y, {"router_loss": aux, "held_pairs": n}


__all__ = ["RoutedExperts", "routed_experts", "route", "sigmoid_route",
           "balance_loss", "grouped_matmul"]
