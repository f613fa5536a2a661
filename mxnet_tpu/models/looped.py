"""Looped decoder LM: the stack of layers runs ``cfg.passes`` times over
the same weights, an exit gate weighs the passes, and the training loss
is the expected cross-entropy over the exits less an entropy bonus
(Ouro, "Scaling Latent Reasoning via Looped Language Models",
arXiv:2510.25741, first-stage objective).

    h0 = E[tokens];  ht = Nf(Stack(h(t-1)))  for t = 1..passes
    logits_t = ht W_head;  lambda_t = sigmoid(ht w_g + b_g)
    p_t = lambda_t prod_{j<t} (1 - lambda_j),  the last exit takes the rest
    loss = mean over tokens of  sum_t p_t CE_t - beta H(p)

``Stack`` is :class:`~.transformer.TransformerBlock` (sandwich norms with
``cfg.sandwich_norm``), ``Nf`` the one final norm, applied at the end of
every pass.  The parameters do not depend on ``passes``; with one pass
and no sandwich the logits are :class:`~.transformer.TransformerLM`'s.
Every block application is a call of the same gluon block, so a weight's
gradient is the sum over its uses.  Each block is marked as one that
may be made again (``Block.recompute``): a training step keeps, a block
application, its input and the attention kernel's output and row sums
(so the kernel runs once), and makes the rest of the interior again.
Under the scan a block is always made again (``parallel.TrainStep``
spares only blocks applied in the step's own trace: the single-pass
model's); kept, its interior would be stacked once a pass.
The head never makes whole logits in the loss, and makes each chunk of
them once a step: the loss is linear in the exits' cross-entropies with
weights the gate has made before the head runs, so ``loss`` forms the
head's and the hidden states' gradients where the logits are alive
(``ops.nn.weighted_chunked_softmax_cross_entropy``).  ``exit_parts``
gives the per-token values for callers with cotangents of their own
(``ops.nn.chunked_softmax_cross_entropy``, which makes the logits again
in its backward).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..gluon.nn import Dense
from ..ndarray.ndarray import NDArray, apply_op
from ..ops.nn import (chunked_softmax_cross_entropy,
                      weighted_chunked_softmax_cross_entropy)
from .transformer import LlamaConfig, TransformerLM


def exit_log_probs(gate_logits):
    """``log p`` (P, N) of the exit distribution from the gate's logits
    (P, N): ``p_t = lambda_t prod_{j<t} (1 - lambda_j)`` and the last
    exit takes what is left, in log space and float32."""
    z = gate_logits.astype(jnp.float32)
    log_lam = jax.nn.log_sigmoid(z)
    stay = jnp.cumsum(jax.nn.log_sigmoid(-z), axis=0)   # log prod (1 - lam)
    before = jnp.concatenate([jnp.zeros_like(stay[:1]), stay[:-1]], axis=0)
    return jnp.concatenate([log_lam[:-1] + before[:-1], before[-1:]], axis=0)


def expected_exit_loss(ce, log_p, beta):
    """``mean_n(sum_t p_t CE_t - beta H(p))`` from per-token, per-exit
    cross-entropies and log exit probabilities, both (P, N)."""
    p = jnp.exp(log_p)
    return jnp.mean(jnp.sum(p * (ce + beta * log_p), axis=0))


class LoopedLM(TransformerLM):
    """Input (B, T) int tokens.  ``forward`` gives the last pass's logits
    (the model's output without early exit); ``loss`` the training loss
    above; ``exit_parts`` what the loss is made of."""

    _loops = True

    def __init__(self, cfg: LlamaConfig = None, **kwargs):
        super().__init__(cfg, **kwargs)
        cfg = self.cfg
        self.exit_gate = Dense(1, use_bias=True, flatten=False,
                               in_units=cfg.dim, dtype=cfg.dtype)
        for blk in self.layers:
            blk.recompute()

    def _one_pass(self, h):
        for blk in self.layers:
            h = blk(h)
        return self.norm(h)

    def hidden_states(self, tokens):
        """``[h1 .. hP]``, each (B, T, dim) and normed.  Inside a trace
        the passes are a ``lax.scan`` over the one stack of blocks (the
        parameters are the scan's constants: in the backward their
        gradients accumulate pass by pass, and no pass's interior
        outlives its iteration); eagerly, where the tape follows every
        op, and for a single pass, the loop is a Python ``for``."""
        h = self._embed(tokens)
        passes = self.cfg.passes
        with jax.named_scope("loop"):
            if passes == 1 or not isinstance(h._data, jax.core.Tracer):
                states = []
                for t in range(passes):
                    with jax.named_scope("pass%d" % (t + 1)):
                        h = self._one_pass(h)
                    states.append(h)
                return states

            def one(carry, _):
                out = self._one_pass(NDArray(carry))._data
                return out, out

            stacked = jax.lax.scan(one, h._data, None, length=passes)[1]
        return [NDArray(stacked[t]) for t in range(passes)]

    def forward(self, tokens, cache=None):
        if cache is not None:
            raise NotImplementedError(
                "a looped model has no cached path: the cache would be "
                "keyed by (pass, layer) (ROADMAP, Queue 2)")
        return self.output(self.hidden_states(tokens)[-1])

    def exit_logits(self, tokens):
        """Every pass's whole logits (tests and small sizes)."""
        return [self.output(h) for h in self.hidden_states(tokens)]

    def exit_parts(self, tokens, labels, chunk=2048):
        """``(ce, log_p)``, both (P, B*T) float32: every exit's
        per-token cross-entropy against ``labels`` (B, T) and the log
        exit probabilities."""
        states = self.hidden_states(tokens)
        dim = self.cfg.dim

        def ces(head, y, *hs):
            return jnp.stack([chunked_softmax_cross_entropy(
                h.reshape(-1, dim), head, y.reshape(-1), chunk)
                for h in hs])

        with jax.named_scope("exit_loss"):
            ce = apply_op(ces, [self.output.weight.data(), labels] + states,
                          name="exit_cross_entropy")
            log_p = self._exit_log_probs(states)
        return ce, log_p

    def _exit_log_probs(self, states):
        return apply_op(
            lambda *zs: exit_log_probs(
                jnp.stack([z.reshape(-1) for z in zs])),
            [self.exit_gate(h) for h in states], name="exit_log_probs")

    def loss(self, tokens, labels, beta=0.05, chunk=2048, exits=False):
        """The scalar training loss; for ``TrainStep(forward_fn=...)``.
        With ``exits`` also what a training loop logs of the exits,
        ``(loss, {"ce": (P,), "p": (P,)})``: every exit's mean
        cross-entropy and mean exit probability (``TrainStep`` hands
        such a pair through as the step's aux).

        ``expected_exit_loss(*exit_parts(...))`` in value and gradients,
        computed as ``sum_tn (p_t(n) / N) CE_t(n) + beta mean_n sum_t p_t
        log p_t``: the gate runs first, and the four exits' rows go
        through the head as one weighted sum, whose gradients are formed
        in the forward (one call, so that the head's gradient is summed
        in float32 over all exits and cast once).  The gate's gradient
        comes through the weights and through the entropy term."""
        states = self.hidden_states(tokens)
        dim = self.cfg.dim

        def expected(head, y, lp, *hs):
            p = jnp.exp(lp)
            total, ce = weighted_chunked_softmax_cross_entropy(
                jnp.stack(hs).reshape(-1, dim), head,
                jnp.tile(y.reshape(-1), len(hs)),
                (p / lp.shape[1]).reshape(-1), chunk)
            return (total + beta * jnp.mean(jnp.sum(p * lp, axis=0)),
                    jnp.mean(ce.reshape(lp.shape), axis=1))

        with jax.named_scope("exit_loss"):
            log_p = self._exit_log_probs(states)
            loss, mean_ce = apply_op(
                expected, [self.output.weight.data(), labels, log_p] + states,
                name="expected_exit_loss")
            if not exits:
                return loss
            return loss, {
                "ce": mean_ce,
                "p": apply_op(lambda lp: jnp.mean(jnp.exp(lp), axis=1),
                              [log_p], name="exit_mean_p")}
