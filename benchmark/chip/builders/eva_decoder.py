"""Builds the program's EvaByte and its training step the way a user of
the library does: ``mx.models.EvaByteLM`` from the library's own
constructor of the published configuration, AdamW,
``parallel.TrainStep(net, None, opt, forward_fn=...)`` with the model's
own loss; then gives it the benchmark's weights and says which of the
program's parameters is which of the reference's leaves."""

from builders import looped_decoder

_FLAT = {"embed": "tok_embeddings.weight", "final_norm": "norm.gamma",
         "lm_head": "output.weight"}
_LEAF = {"attention_norm": "attention_norm.gamma",
         "ffn_norm": "ffn_norm.gamma",
         "wq": "attention.wq.weight", "wk": "attention.wk.weight",
         "wv": "attention.wv.weight", "wo": "attention.wo.weight",
         "adaptive_mu_k": "attention.adaptive_mu_k",
         "adaptive_phi": "attention.adaptive_phi",
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
           "window_size": "window_size", "chunk_size": "chunk_size",
           "num_pred_heads": "num_pred_heads",
           "norm_unit_offset": "norm_add_unit_offset"}


def _program_name(ref):
    """``layer3.w_gate`` -> ``layer3.feed_forward.w1.weight``."""
    if ref in _FLAT:
        return _FLAT[ref]
    layer, leaf = ref.split(".")
    return "%s.%s" % (layer, _LEAF[leaf])


def library_config(model):
    """The library's constructor of the published configuration, every
    field the file states set from the file (tests/test_bench_evabyte
    holds the two to each other)."""
    from mxnet_tpu.models import evabyte_6p5b_config
    if model["attention_class"] != "eva":
        raise RuntimeError("the builder builds eva attention, the file "
                           "says %r" % model["attention_class"])
    return evabyte_6p5b_config(
        n_layers=model["num_hidden_layers"], dtype=model["param_dtype"],
        attn_impl=model["attention_class"],
        residual_dtype="float32" if model["fp32_skip_add"] else None,
        **{f: model[k] for f, k in _FIELDS.items()})


class TrainCell(looped_decoder.TrainCell):
    """The compiled step with its state: ``step(bytes, labels)`` is
    ``TrainStep.__call__`` and gives ``(loss, {"ce": (K,)})``, the
    heads' mean cross-entropies being the step's own aux output (what a
    training loop logs of such a model).  What the driver reads of the
    step (moments, moves, the compiled program's temporaries) it reads
    as of the looped decoder's cell."""

    def __init__(self, model, weights, kernel_marker="tpu_custom_call"):
        import mxnet_tpu as mx
        from mxnet_tpu import parallel
        from mxnet_tpu.models import EvaByteLM
        from mxnet_tpu.ndarray.ndarray import NDArray
        self._NDArray = NDArray
        if not model["fp32_logits"]:
            raise RuntimeError("EvaByteLM's logits are float32")
        net = EvaByteLM(library_config(model))
        net.cast(model["param_dtype"])     # the norms' offsets too
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
        self.net = net
        self.step = parallel.TrainStep(
            net, None, opt, mesh=None,
            forward_fn=lambda net, tokens, labels: net.loss(
                tokens, labels, heads=True))
        self.kernel_marker = kernel_marker
        self._compiled = None
