"""Builds the program's looped decoder and its training step the way a
user of the library does: ``mx.models.LoopedLM`` from the library's own
constructor of the published configuration, AdamW,
``parallel.TrainStep(net, None, opt, forward_fn=...)`` with the model's
own loss; then gives it the benchmark's weights and says which of the
program's parameters is which of the reference's leaves."""

_FLAT = {"embed": "tok_embeddings.weight", "final_norm": "norm.gamma",
         "lm_head": "output.weight", "gate.w": "exit_gate.weight",
         "gate.b": "exit_gate.bias"}
_LEAF = {"attention_norm": "attention_norm.gamma",
         "attention_post_norm": "attention_post_norm.gamma",
         "ffn_norm": "ffn_norm.gamma",
         "ffn_post_norm": "ffn_post_norm.gamma",
         "wq": "attention.wq.weight", "wk": "attention.wk.weight",
         "wv": "attention.wv.weight", "wo": "attention.wo.weight",
         "w_gate": "feed_forward.w1.weight",
         "w_up": "feed_forward.w3.weight",
         "w_down": "feed_forward.w2.weight"}
# the configuration file's key for each field of the library's config
_FIELDS = {"vocab_size": "vocab_size", "dim": "hidden_size",
           "n_heads": "num_attention_heads",
           "n_kv_heads": "num_key_value_heads",
           "hidden_dim": "intermediate_size",
           "max_seq_len": "max_position_embeddings",
           "rope_theta": "rope_theta", "norm_eps": "rms_norm_eps",
           "passes": "total_ut_steps"}


def _program_name(ref):
    """``layer3.w_gate`` -> ``layer3.feed_forward.w1.weight``."""
    if ref in _FLAT:
        return _FLAT[ref]
    layer, leaf = ref.split(".")
    return "%s.%s" % (layer, _LEAF[leaf])


class TrainCell:
    """The compiled step with its state: ``step(tokens, labels)`` is
    ``TrainStep.__call__`` and gives ``(loss, {"ce": (P,), "p": (P,)})``,
    the exits' mean cross-entropy and mean probability being the step's
    own aux outputs (what a training loop logs of a looped model)."""

    def __init__(self, model, weights, kernel_marker="tpu_custom_call"):
        import mxnet_tpu as mx
        from mxnet_tpu import parallel
        from mxnet_tpu.models import LoopedLM, ouro_2p6b_config
        from mxnet_tpu.ndarray.ndarray import NDArray
        self._NDArray = NDArray
        # the library's constructor of the published configuration, every
        # field the file states set from the file (tests/test_bench_looped
        # holds the two to each other)
        cfg = ouro_2p6b_config(n_layers=model["num_hidden_layers"],
                               dtype=model["param_dtype"],
                               **{f: model[k] for f, k in _FIELDS.items()})
        if cfg.dim // cfg.n_heads != model["head_dim"]:
            raise RuntimeError("head_dim is not hidden_size / heads")
        net = LoopedLM(cfg)
        net.cast(model["param_dtype"])     # the norms' gains too
        ps = net.collect_params()
        self.names = {}
        for ref, value in weights.items():
            ps[_program_name(ref)].set_data(NDArray(value))
            self.names[_program_name(ref)] = ref
        unset = [n for n, p in ps.items() if p._data is None]
        if unset:
            raise RuntimeError("parameters the benchmark made no weights "
                               "for: %s" % unset)
        o = model["optimizer"]
        opt = getattr(mx.optimizer, o["name"])(
            learning_rate=o["learning_rate"], beta1=o["beta1"],
            beta2=o["beta2"], epsilon=o["epsilon"], wd=o["wd"])
        self.beta1 = o["beta1"]
        beta, chunk = model["beta"], model["loss_chunk"]
        self.net = net
        self.step = parallel.TrainStep(
            net, None, opt, mesh=None,
            forward_fn=lambda net, tokens, labels: net.loss(
                tokens, labels, beta=beta, chunk=chunk, exits=True))
        self.kernel_marker = kernel_marker
        self._compiled = None

    def wrap(self, x, y):
        return self._NDArray(x), self._NDArray(y)

    def update_norms(self, before):
        """Per leaf, under the reference's names, the norm of what the
        parameters have moved from ``before`` (the weights the cell was
        built from; a state never written back reads 0 everywhere)."""
        import jax.numpy as jnp
        ps = self.net.collect_params()
        return {ref: float(jnp.linalg.norm(
            ps[name].data()._data.astype(jnp.float32)
            - before[ref].astype(jnp.float32)))
            for name, ref in self.names.items()}

    def first_moment(self, only=None):
        """Adam's first moment under the reference's names (copies)."""
        import jax.numpy as jnp
        return {ref: jnp.copy(self.step._states[name][0])
                for name, ref in self.names.items()
                if only is None or ref in only}

    def first_moment_norms(self):
        import jax.numpy as jnp
        return {ref: float(jnp.sqrt(jnp.sum(jnp.square(
            self.step._states[name][0])))) for name, ref in
            self.names.items()}

    def compiled(self, x, y):
        if self._compiled is None:
            self._compiled = self.step.lower(x, y).compile()
            if self.kernel_marker and self.kernel_marker \
                    not in self._compiled.as_text():
                raise RuntimeError(
                    "the step holds no %s: dense attention was compiled "
                    "where the flash kernels belong" % self.kernel_marker)
        return self._compiled

    def temp_bytes(self, x, y):
        """Temporaries of the compiled step program (the runtime's
        ``peak_bytes_in_use`` leaves them out)."""
        return int(self.compiled(x, y).memory_analysis().temp_size_in_bytes)

    def free(self):
        self.net = self.step = self._compiled = None
